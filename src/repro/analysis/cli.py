"""Command-line front end: ``python -m repro.analysis [paths] [flags]``.

Default run lints the given paths (ERROR severity fails; add ``--strict``
to fail on warnings too).  ``--audit`` additionally runs the jaxpr
dispatch auditor's self-contained sweep (traces real SparseAllreduce /
GraphEngine entry points on forced host devices — needs jax, a few
seconds).  ``--json`` writes the combined machine-readable report,
``--list-rules`` prints the catalog, ``--select`` restricts to given
rule ids.

Exit codes: 0 clean, 1 findings/audit failures, 2 usage or internal
error.  The console entry ``repro-analysis`` (pyproject) is the same
main.
"""
from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional, Sequence

from .engine import all_rules, lint_paths
from .violations import AnalysisReport

_AUDIT_DEVICES = 8  # host-device count forced for the --audit sweep


def _build_parser() -> argparse.ArgumentParser:
    """The argparse surface (flags documented in README 'Static checks')."""
    p = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="AST lint + jaxpr dispatch audit for the repro stack")
    p.add_argument("paths", nargs="*", default=["src"],
                   help="files/directories to lint (default: src)")
    p.add_argument("--strict", action="store_true",
                   help="fail on warnings too, not just errors")
    p.add_argument("--select", action="append", default=None,
                   metavar="RULE", help="only run these rule ids "
                   "(repeatable, e.g. --select RA201)")
    p.add_argument("--json", metavar="PATH", default=None,
                   help="write the machine-readable report to PATH "
                   "('-' for stdout)")
    p.add_argument("--audit", action="store_true",
                   help="also run the jaxpr dispatch auditor sweep "
                   "(imports jax, forces host devices)")
    p.add_argument("--list-rules", action="store_true",
                   help="print the rule catalog and exit")
    return p


def _list_rules() -> None:
    """Print the catalog: id, severity, scope, title."""
    for cls in all_rules():
        scope = ",".join(cls.scope)
        print(f"{cls.rule_id}  {cls.severity:7s}  [{scope}]  {cls.title}")


def _audit_sweep() -> List:
    """Self-contained auditor run: real entry points, small shapes.

    Covers degrees {(4,), (2,2)} x replication {1, 2} for the reduce path
    and a (4,2) PageRank engine for the k-round dispatch contract — all
    within 8 forced host devices.
    """
    # must precede the first jax import in this process
    os.environ.setdefault(
        "XLA_FLAGS",
        f"--xla_force_host_platform_device_count={_AUDIT_DEVICES}")
    import jax
    import numpy as np

    from repro.core.api import SparseAllreduce
    from repro.launch.mesh import make_mesh
    from .auditor import audit_engine, audit_reduce

    reports = []
    for degs in [(4,), (2, 2)]:
        m = int(np.prod(degs))
        rng = np.random.RandomState(m)
        out_idx = [rng.choice(4096, rng.randint(5, 16),
                              replace=False).astype(np.uint32)
                   for _ in range(m)]
        in_idx = [rng.choice(4096, rng.randint(5, 16),
                             replace=False).astype(np.uint32)
                  for _ in range(m)]
        for r in (1, 2):
            ar = SparseAllreduce(m, degs, backend="device", replication=r,
                                 mesh=make_mesh((m * r,), ("d",)),
                                 seed=m)
            ar.config(out_idx, in_idx)
            reports.append(audit_reduce(ar))

    from repro.data.pipeline import powerlaw_graph
    from repro.graph.pagerank import build_partitions, make_pagerank_engine
    edges = powerlaw_graph(300, 1200, seed=1)
    parts = build_partitions(edges, 300, _AUDIT_DEVICES)
    engine, extras, p0 = make_pagerank_engine(
        parts, 300, degrees=(4, 2),
        mesh=make_mesh((_AUDIT_DEVICES,), ("d",)))
    reports.append(audit_engine(engine, 5, p0, extras))

    # overlap schedules: the double-buffered engine rotation and the
    # bucketed stage-major dense sync (pure-reordering contract)
    from repro.graph.engine import GraphEngine
    import numpy as _np
    ov_engine = GraphEngine(
        [_np.asarray(o) for o in engine.out_sets],
        [_np.asarray(i) for i in engine.in_sets],
        engine.app, degrees=(4, 2),
        mesh=make_mesh((_AUDIT_DEVICES,), ("d",)), overlap=True)
    reports.append(audit_engine(ov_engine, 5, p0, extras))

    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map
    from .auditor import audit_overlap_sync
    from repro.core.allreduce import (dense_allreduce_hierarchical,
                                      dense_allreduce_hierarchical_bucketed,
                                      make_device_plan)
    plan = make_device_plan([("d", _AUDIT_DEVICES)], {"d": (4, 2)}, 8, 8)
    mesh = make_mesh((_AUDIT_DEVICES,), ("d",))
    sizes = (64, 32, 96)

    def _mk(schedule):
        def body(*xs):
            xs = [x.reshape(x.shape[1:]) for x in xs]
            if schedule == "stage_major":
                outs = dense_allreduce_hierarchical_bucketed(xs, plan)
            else:
                outs = [dense_allreduce_hierarchical(x, plan) for x in xs]
            return tuple(o[None] for o in outs)
        return shard_map(body, mesh=mesh,
                         in_specs=(P("d"),) * len(sizes),
                         out_specs=(P("d"),) * len(sizes), check_vma=False)

    args = tuple(jnp.zeros((_AUDIT_DEVICES, n), jnp.float32) for n in sizes)
    reports.append(audit_overlap_sync(
        "dense_allreduce_hierarchical_bucketed", _mk("stage_major"),
        _mk("sequential"), *args, depth=plan.logical.depth,
        n_buckets=len(sizes)))
    return reports


def main(argv: Optional[Sequence[str]] = None) -> int:
    """Entry point; returns the process exit code."""
    args = _build_parser().parse_args(argv)
    if args.list_rules:
        _list_rules()
        return 0

    report = AnalysisReport()
    try:
        report.violations, report.files_checked = lint_paths(
            args.paths, select=args.select)
    except (FileNotFoundError, SyntaxError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    if args.audit:
        report.audits = _audit_sweep()

    for v in report.violations:
        print(v)
    for a in report.audits:
        status = "ok" if a.ok else "FAIL"
        print(f"audit [{status}] {a.target}")
        for c in a.failures():
            print(f"    {c}")

    if args.json:
        text = report.to_json(None if args.json == "-" else args.json)
        if args.json == "-":
            print(text)

    ok = report.ok(strict=args.strict)
    n_err, n_all = len(report.errors), len(report.violations)
    print(f"{report.files_checked} files checked: {n_all} finding(s) "
          f"({n_err} error(s))"
          + (f", {len(report.audits)} audit(s)" if report.audits else "")
          + f" -> {'clean' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":  # pragma: no cover - exercised via __main__.py
    sys.exit(main())
