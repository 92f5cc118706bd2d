"""Static-analysis smoke benchmark (``--only analysis``).

Times the two layers of ``repro.analysis`` over the real repo: the AST
lint pass on ``src/repro`` (pure ast, no jax) and one jaxpr audit of a
configured device reduce.  The derived column carries the invariants the
timing is worthless without: files linted / violations found (must stay
0) and audit checks passed.  Keeping the lint pass cheap matters — it
runs inside tier-1 pytest on every change.
"""
from __future__ import annotations

import os
import time
from typing import List, Tuple

Row = Tuple[str, float, str]

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def bench_analysis_lint() -> List[Row]:
    """Full-catalog lint of src/repro: wall time + clean-repo invariant."""
    from repro.analysis import lint_paths
    src = os.path.join(_REPO, "src", "repro")
    lint_paths([src])                       # warm (fs cache, rule imports)
    reps = 3
    t0 = time.perf_counter()
    for _ in range(reps):
        violations, files = lint_paths([src])
    us = (time.perf_counter() - t0) / reps * 1e6
    return [("analysis/lint_src", us,
             f"files={files} violations={len(violations)}")]


def bench_analysis_audit() -> List[Row]:
    """One jaxpr audit of a configured (2,2) device reduce (trace only)."""
    import numpy as np

    from repro.analysis.auditor import audit_reduce
    from repro.core.api import SparseAllreduce
    from repro.launch.mesh import make_mesh

    m = 4
    rng = np.random.RandomState(m)
    out_idx = [rng.choice(4096, rng.randint(5, 16),
                          replace=False).astype(np.uint32) for _ in range(m)]
    in_idx = [rng.choice(4096, rng.randint(5, 16),
                         replace=False).astype(np.uint32) for _ in range(m)]
    ar = SparseAllreduce(m, (2, 2), backend="device",
                         mesh=make_mesh((m,), ("d",)), seed=m)
    ar.config(out_idx, in_idx)
    audit_reduce(ar)                        # warm (first trace)
    reps = 3
    t0 = time.perf_counter()
    for _ in range(reps):
        report = audit_reduce(ar)
    us = (time.perf_counter() - t0) / reps * 1e6
    n_ok = sum(1 for c in report.checks if c.ok)
    return [("analysis/audit_reduce_2x2", us,
             f"ok={report.ok} checks={n_ok}/{len(report.checks)}")]


ALL_BENCHES = [bench_analysis_lint, bench_analysis_audit]
