"""repro.analysis regression tests: rule catalog, fixtures, CLI, auditor.

Layer 1 (lint) tests run in-process — the engine is pure ``ast`` and
never imports jax.  Layer 2 (auditor) tests follow the repo convention
of one subprocess per multi-device scenario with
XLA_FLAGS=--xla_force_host_platform_device_count=N.

The repo-clean test (``test_repo_src_is_strict_clean``) is the tier-1
gate: ``src/repro`` must hold zero findings at HEAD — fix the code or
carry a ``# noqa: RAxxx`` with the rule id, never loosen a rule to pass.
"""
import glob
import json
import os
import re
import shutil
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import Severity, all_rules, lint_paths

ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
SRC = os.path.join(ROOT, "src", "repro")
FIXTURES = os.path.join(ROOT, "tests", "fixtures", "analysis")

_ENV16 = dict(os.environ,
              XLA_FLAGS="--xla_force_host_platform_device_count=16",
              PYTHONPATH=os.path.join(ROOT, "src")
              + os.pathsep + os.environ.get("PYTHONPATH", ""))

_EXPECT_RE = re.compile(r"#\s*expect:\s*([A-Z]+\d+(?:\s*,\s*[A-Z]+\d+)*)")


def _run(code: str, env=_ENV16):
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def _expected_lines(path: str, rule_id: str):
    """Lines carrying a ``# expect: <rule_id>`` marker."""
    out = set()
    with open(path) as f:
        for i, line in enumerate(f.read().splitlines(), 1):
            m = _EXPECT_RE.search(line)
            if m and rule_id in {s.strip() for s in m.group(1).split(",")}:
                out.add(i)
    return out


def _fixture_files(rule_id: str, kind: str):
    return sorted(glob.glob(
        os.path.join(FIXTURES, "**", f"{rule_id.lower()}_{kind}*.py"),
        recursive=True))


# ---------------------------------------------------------------------------
# catalog sanity + per-rule fixtures
# ---------------------------------------------------------------------------

def test_rule_catalog_sane():
    """>= 8 distinct rules, unique ids, metadata filled in, and a
    positive + negative fixture pair for every rule."""
    rules = all_rules()
    ids = [r.rule_id for r in rules]
    assert len(rules) >= 8
    assert len(set(ids)) == len(ids)
    for cls in rules:
        assert re.fullmatch(r"RA\d{3}", cls.rule_id), cls
        assert cls.severity in (Severity.ERROR, Severity.WARNING)
        assert cls.title and cls.rationale, f"{cls.rule_id} missing metadata"
        assert _fixture_files(cls.rule_id, "pos"), \
            f"{cls.rule_id}: no positive fixture"
        assert _fixture_files(cls.rule_id, "neg"), \
            f"{cls.rule_id}: no negative fixture"


@pytest.mark.parametrize("rule_id", [r.rule_id for r in all_rules()])
def test_rule_fixtures(rule_id):
    """Positives flag exactly the ``# expect`` lines; negatives (near-miss
    code) stay clean."""
    for path in _fixture_files(rule_id, "pos"):
        want = _expected_lines(path, rule_id)
        assert want, f"{path}: positive fixture has no expect markers"
        vs, _ = lint_paths([path], select=[rule_id])
        got = {v.line for v in vs}
        assert got == want, (f"{rule_id} on {os.path.basename(path)}: "
                             f"flagged {sorted(got)}, marked {sorted(want)}")
    for path in _fixture_files(rule_id, "neg"):
        vs, _ = lint_paths([path], select=[rule_id])
        assert not vs, (f"{rule_id} false positives on "
                        f"{os.path.basename(path)}: {[str(v) for v in vs]}")


def test_repo_src_is_strict_clean():
    """Tier-1 gate: zero findings (warnings included) over src/repro."""
    violations, files = lint_paths([SRC])
    assert files > 50, f"suspiciously few files linted: {files}"
    assert not violations, "src/repro must lint clean:\n" + \
        "\n".join(str(v) for v in violations)


def test_noqa_requires_rule_id_scoping(tmp_path):
    """``# noqa: RA205`` silences exactly that rule on that line."""
    bad = tmp_path / "hot64.py"
    bad.write_text(textwrap.dedent("""\
        '''tmp module.'''
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return x.astype(jnp.float64)  # noqa: RA205
    """))
    vs, _ = lint_paths([str(bad)])
    assert not vs, [str(v) for v in vs]
    # a different rule id on the comment must NOT silence RA205
    bad.write_text(bad.read_text().replace("RA205", "RA201"))
    vs, _ = lint_paths([str(bad)], select=["RA205"])
    assert len(vs) == 1 and vs[0].rule_id == "RA205"


def test_hot_region_force_comment(tmp_path):
    """`# analysis: hot` pulls a dynamically-dispatched fn into scope."""
    mod = tmp_path / "dyn.py"
    mod.write_text(textwrap.dedent("""\
        '''tmp module.'''
        import numpy as np

        def cold(x):
            return np.mean(x)

        def dispatched(x):  # analysis: hot
            return np.mean(x)
    """))
    vs, _ = lint_paths([str(mod)], select=["RA202"])
    assert len(vs) == 1
    assert "dispatched" in vs[0].message


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_src_strict_exits_zero():
    """Acceptance: `python -m repro.analysis src --strict` is clean at
    HEAD (the console entry point runs the same main)."""
    r = subprocess.run([sys.executable, "-m", "repro.analysis", "src",
                        "--strict"], cwd=ROOT, env=_ENV16,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "clean" in r.stdout


def test_cli_flags_injected_violation(tmp_path):
    """A host sync dropped into a linted file turns the CLI red, and the
    --json report carries the machine-readable finding."""
    bad = tmp_path / "leaky.py"
    bad.write_text(textwrap.dedent("""\
        '''tmp module.'''
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return np.asarray(x) * 2
    """))
    out = tmp_path / "report.json"
    r = subprocess.run([sys.executable, "-m", "repro.analysis", str(bad),
                        "--json", str(out)], cwd=ROOT, env=_ENV16,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 1, r.stdout
    report = json.loads(out.read_text())
    assert report["files_checked"] == 1
    ids = {v["rule_id"] for v in report["violations"]}
    assert "RA201" in ids, report


def test_cli_list_rules():
    r = subprocess.run([sys.executable, "-m", "repro.analysis",
                        "--list-rules"], cwd=ROOT, env=_ENV16,
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0
    for cls in all_rules():
        assert cls.rule_id in r.stdout


def test_ruff_config_matches_if_available():
    """pyproject carries the ruff config; run it when the binary exists
    (not in the pinned container — config still must parse)."""
    with open(os.path.join(ROOT, "pyproject.toml")) as f:
        cfg = f.read()
    assert "[tool.ruff]" in cfg and "tests/fixtures" in cfg
    ruff = shutil.which("ruff")
    if ruff is None:
        pytest.skip("ruff not installed in this environment")
    r = subprocess.run([ruff, "check", "src"], cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# Layer 2: jaxpr dispatch auditor (subprocess, forced host devices)
# ---------------------------------------------------------------------------

AUDIT_PRELUDE = r"""
from repro.launch.mesh import make_mesh
import numpy as np, jax, jax.numpy as jnp
from repro.core.api import SparseAllreduce
from repro.analysis.auditor import (audit_callable, audit_engine,
                                    audit_reduce, collective_counts,
                                    trace_jaxpr)

def configured(degs, r, seed=None):
    m = int(np.prod(degs))
    rng = np.random.RandomState(seed if seed is not None else m)
    out_idx = [rng.choice(4096, rng.randint(5, 16), replace=False)
               .astype(np.uint32) for _ in range(m)]
    in_idx = [rng.choice(4096, rng.randint(5, 16), replace=False)
              .astype(np.uint32) for _ in range(m)]
    ar = SparseAllreduce(m, degs, backend="device", replication=r,
                         mesh=make_mesh((m * r,), ("d",)), seed=m)
    ar.config(out_idx, in_idx)
    return ar
"""

REDUCE_AUDIT_CODE = AUDIT_PRELUDE + r"""
# acceptance sweep: collective count == 2 * plan depth for every degree
# schedule x replication (r=2 prepends the replica-merge stage: depth+1)
for degs in [(4,), (2, 2), (4, 2)]:
    for r in (1, 2):
        ar = configured(degs, r)
        planned, _ = ar.planned_parts()
        want_depth = len(degs) + (1 if r > 1 else 0)
        assert planned.depth == want_depth, (degs, r, planned.depth)
        rep = audit_reduce(ar)
        assert rep.ok, rep.to_dict()
        d = {c.check_id: c for c in rep.checks}
        c = d["collectives_equal_plan_depth"]
        assert c.expected == 2 * want_depth == c.actual, (degs, r, c)
print("REDUCE_AUDIT_OK")
"""


@pytest.mark.slow
def test_audit_reduce_collectives_equal_plan_depth():
    """Traced all_to_all count == 2*depth across degrees x replication."""
    assert "REDUCE_AUDIT_OK" in _run(REDUCE_AUDIT_CODE)


REDUCE_INJECT_CODE = AUDIT_PRELUDE + r"""
# injection: a second reduce doubles the collectives -> count check fails
ar = configured((2, 2), 1)
planned, _ = ar.planned_parts()
meta = ar.staging_metadata()
f = ar.reduce_fn

def doubled(v):
    return f(v) + f(v * 2.0)

rep = audit_callable("doubled-reduce", doubled,
                     jnp.zeros((meta["num_physical"], meta["u_cap"]),
                               jnp.float32),
                     expected_all_to_all=2 * planned.depth)
bad = {c.check_id: c for c in rep.checks}["all_to_all_count"]
assert not bad.ok and bad.actual == 4 * planned.depth, bad

# injection: a host callback on the hot path -> forbidden-primitive check
def leaky(v):
    jax.debug.callback(lambda x: None, v[0, 0])
    return f(v)

rep2 = audit_callable("leaky-reduce", leaky,
                      jnp.zeros((meta["num_physical"], meta["u_cap"]),
                                jnp.float32))
forb = {c.check_id: c for c in rep2.checks}["no_forbidden_primitives"]
assert not forb.ok and "debug_callback" in forb.actual, forb
print("REDUCE_INJECT_OK")
"""


@pytest.mark.slow
def test_audit_catches_injected_extra_collective_and_callback():
    """Acceptance: deliberately injecting an extra collective or a host
    callback makes the corresponding check fail."""
    assert "REDUCE_INJECT_OK" in _run(REDUCE_INJECT_CODE)


ENGINE_AUDIT_CODE = r"""
from repro.launch.mesh import make_mesh
import numpy as np, jax
from repro.data.pipeline import powerlaw_graph
from repro.graph.pagerank import build_partitions, make_pagerank_engine
from repro.analysis.auditor import audit_engine, collective_counts, \
    iter_eqns, trace_jaxpr

edges = powerlaw_graph(300, 1200, seed=1)
parts = build_partitions(edges, 300, 8)
engine, extras, p0 = make_pagerank_engine(
    parts, 300, degrees=(4, 2), mesh=make_mesh((8,), ("d",)))

for k in (1, 7):
    rep = audit_engine(engine, k, p0, extras)
    assert rep.ok, rep.to_dict()

# negative: k python-loop single-round dispatches instead of one fused
# scan -> the one-dispatch and per-round checks both fail
class LoopyEngine:
    '''Anti-pattern shim: re-dispatches a 1-round run k times.'''
    def __init__(self, e):
        self.e = e
        self.planned = e.planned
    def routing_args(self):
        return self.e.routing_args()
    def run_fn(self, k, collect="last"):
        one = self.e.run_fn(1, collect)
        def loopy(state, extras, *routing):
            out = traj = None
            for _ in range(k):
                state, out, traj = one(state, extras, *routing)
            return state, out, traj
        return loopy

bad = audit_engine(LoopyEngine(engine), 3, p0, extras)
d = {c.check_id: c for c in bad.checks}
assert not d["one_scan_dispatch"].ok and \
    d["one_scan_dispatch"].actual == 3, d["one_scan_dispatch"]
assert not d["per_round_collectives_equal_plan_depth"].ok
print("ENGINE_AUDIT_OK")
"""


@pytest.mark.slow
def test_audit_engine_one_dispatch_per_run():
    """k-round engine run is one scan with all collectives inside; an
    unfused k-loop fails the dispatch-count check."""
    assert "ENGINE_AUDIT_OK" in _run(ENGINE_AUDIT_CODE)


TRAIN_AUDIT_CODE = r"""
from repro.launch.mesh import make_mesh
import numpy as np, jax, jax.numpy as jnp
from repro.configs import ARCHS, get_config
from repro.models import transformer as T
from repro.optim.adamw import AdamW
from repro.train.step import make_train_step
from repro.analysis.auditor import audit_callable

cfg = get_config(sorted(ARCHS)[0]).reduced()
mesh = make_mesh((4, 1), ("data", "model"))
step, _ = make_train_step(cfg, mesh, sync="sparse", donate=False)
params = T.init_params(cfg, tp=1, seed=0)
opt = AdamW().init(params)
B, S = 4, 16
rng = np.random.RandomState(0)
batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab, (B, S)), jnp.int32),
         "labels": jnp.asarray(rng.randint(0, cfg.vocab, (B, S)), jnp.int32)}
if cfg.img_tokens:
    batch["img_embeds"] = jnp.asarray(
        rng.randn(B, cfg.img_tokens, cfg.d_model), jnp.float32)
if cfg.enc_layers:
    batch["enc_frames"] = jnp.asarray(
        rng.randn(B, cfg.enc_seq, cfg.d_model), jnp.float32)

rep = audit_callable("make_train_step[sync=sparse]", step,
                     params, opt, batch)
assert rep.ok, rep.to_dict()
census = {c.check_id: c for c in rep.checks}["collective_census"]
assert sum(census.actual.values()) > 0, census  # sync really traced
print("TRAIN_AUDIT_OK", census.actual)
"""


@pytest.mark.slow
def test_audit_train_step_hot_path_clean():
    """A real make_train_step trace has no callbacks/transfers/f64 and
    dtype-stable scan carries."""
    out = _run(TRAIN_AUDIT_CODE)
    assert "TRAIN_AUDIT_OK" in out
