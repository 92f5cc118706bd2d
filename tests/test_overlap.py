"""Overlap-schedule bit-exactness harness (ARCHITECTURE.md "Overlap &
scheduling").

Proves the async paths safe in three tiers:

* single-process property tests (always on): the bucket partition is an
  order-preserving exact cover under its byte bound for *every* input,
  the overlap cost model degrades exactly to the bulk-synchronous one,
  the rate-optimal bound / rate-fraction algebra holds, and the knob
  validation fires before any mesh work.
* ``@pytest.mark.slow`` subprocess tests (default tier-1): bitwise
  parity of the bucketed hier gradient sync and of the double-buffered
  graph engine on 8 forced host devices, plus the jaxpr auditor's
  positive fixtures *and* injection tests — a hidden full-tree ``psum``
  smuggled into the overlapped program, or a rotation the engine never
  performed, must make the audit fail (the checks have teeth).
* ``@pytest.mark.overlap`` sweep (excluded from default runs via
  pyproject ``addopts``; run standalone with ``pytest -m overlap``): the
  16-device degree x merge x replication x wire parity cross, sparse-sync
  combos (minutes of XLA compile each — that cost is why the marker
  exists), and full-train-step composition.

Bitwise assertions use dyadic-lattice gradients (``randint/64``) so
every sum is exactly representable: equality then isolates the
*schedule* — any reordering bug shows up as a wrong bit, never as
tolerable float noise.
"""
import math
import os
import subprocess
import sys

import pytest
from hypothesis import given, settings, strategies as st

from repro.core.netmodel import (EC2_2013, TPU_ICI, Fabric,
                                 rate_fraction, rate_optimal_allreduce_s)
from repro.core.topology import ButterflyPlan
from repro.train.step import plan_grad_buckets

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")
_ENV8 = dict(os.environ,
             XLA_FLAGS="--xla_force_host_platform_device_count=8",
             PYTHONPATH=_SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
_ENV16 = dict(_ENV8, XLA_FLAGS="--xla_force_host_platform_device_count=16")


def _run(code: str, env=_ENV8):
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


# ---------------------------------------------------------------------------
# Bucket partition properties (single process)
# ---------------------------------------------------------------------------

@given(st.lists(st.integers(min_value=0, max_value=5000),
                min_size=0, max_size=40),
       st.integers(min_value=1, max_value=4000))
@settings(max_examples=60, deadline=None)
def test_buckets_exact_cover_and_byte_bound(sizes, bucket_bytes):
    buckets = plan_grad_buckets(sizes, bucket_bytes)
    # order-preserving exact cover: concatenation is range(len(sizes))
    flat = [i for b in buckets for i in b]
    assert flat == list(range(len(sizes)))
    assert all(b for b in buckets)
    for b in buckets:
        nbytes = sum(sizes[i] * 4 for i in b)
        # byte bound, except a single oversized leaf in its own bucket
        assert nbytes <= bucket_bytes or len(b) == 1


@given(st.lists(st.integers(min_value=0, max_value=5000),
                min_size=1, max_size=20),
       st.integers(min_value=1, max_value=4000),
       st.integers(min_value=0, max_value=10 ** 9))
@settings(max_examples=40, deadline=None)
def test_buckets_cover_under_permutation(sizes, bucket_bytes, seed):
    """The exact-cover + bound contract holds for every leaf order (the
    sync order is reverse-backward, not sorted — nothing may rely on
    monotone sizes)."""
    import numpy as np
    perm = np.random.RandomState(seed).permutation(len(sizes))
    shuffled = [sizes[p] for p in perm]
    buckets = plan_grad_buckets(shuffled, bucket_bytes)
    assert [i for b in buckets for i in b] == list(range(len(shuffled)))
    for b in buckets:
        assert sum(shuffled[i] * 4 for i in b) <= bucket_bytes or len(b) == 1


def test_buckets_deterministic_cases():
    # greedy contiguous fill: 3 x 40-byte leaves under an 80-byte budget
    assert plan_grad_buckets([10, 10, 10], 80) == [[0, 1], [2]]
    # exact fit is allowed (strict > comparison), crossing it splits
    assert plan_grad_buckets([10, 10], 80) == [[0, 1]]
    assert plan_grad_buckets([10, 11], 80) == [[0], [1]]
    # an oversized leaf gets a bucket of its own, neighbours unaffected
    assert plan_grad_buckets([2, 100, 2], 16) == [[0], [1], [2]]
    # zero-size leaves ride along without opening buckets
    assert plan_grad_buckets([0, 0, 4], 16) == [[0, 1, 2]]
    assert plan_grad_buckets([], 16) == []


def test_buckets_validation():
    with pytest.raises(ValueError, match="bucket_bytes"):
        plan_grad_buckets([1], 0)
    with pytest.raises(ValueError, match="bucket_bytes"):
        plan_grad_buckets([1], -4)
    with pytest.raises(ValueError, match="bytes_per_elem"):
        plan_grad_buckets([1], 64, bytes_per_elem=0)
    with pytest.raises(ValueError, match="leaf size"):
        plan_grad_buckets([4, -1], 64)


def test_sync_overlap_knob_validation():
    """The settings check fires before any mesh/plan work — None stands
    in for cfg/mesh and must never be touched."""
    from repro.train.step import make_sync_fn, make_train_step
    with pytest.raises(ValueError, match="ring sync is a single psum"):
        make_train_step(None, None, sync="ring", sync_overlap="bucketed")
    with pytest.raises(ValueError, match="ring sync is a single psum"):
        make_sync_fn(None, None, sync="ring", sync_overlap="bucketed")
    with pytest.raises(ValueError, match="sync_overlap must be one of"):
        make_train_step(None, None, sync="hier", sync_overlap="eager")
    with pytest.raises(ValueError, match="only applies to the sparse"):
        make_train_step(None, None, sync="hier", sync_wire="delta",
                        sync_overlap="bucketed")


# ---------------------------------------------------------------------------
# Overlap cost model (single process)
# ---------------------------------------------------------------------------

_FABRICS = [EC2_2013, TPU_ICI,
            Fabric(name="floored", beta_bytes_per_s=1e9, alpha_s=1e-4,
                   floor_bytes=4096.0, gamma_s=2e-5)]


@given(st.floats(min_value=0.0, max_value=1e9),
       st.integers(min_value=0, max_value=16),
       st.booleans(), st.sampled_from(_FABRICS))
@settings(max_examples=60, deadline=None)
def test_stage_split_sums_to_stage_time(nbytes, fanout, serial, fabric):
    lat, bw = fabric.stage_split(nbytes, fanout, serial=serial)
    assert lat >= 0.0 and bw >= 0.0
    assert math.isclose(lat + bw, fabric.stage_time(nbytes, fanout,
                                                    serial=serial),
                        rel_tol=1e-12, abs_tol=1e-18)


@given(st.sampled_from([(4,), (2, 2), (4, 2), (16, 4), (2, 2, 2)]),
       st.floats(min_value=1.0, max_value=1e6),
       st.sampled_from(_FABRICS), st.booleans(),
       st.floats(min_value=0.0, max_value=10.0))
@settings(max_examples=60, deadline=None)
def test_overlap_model_degrades_to_sync(degrees, n0, fabric, serial, hidden):
    """t_ov = serial + max(bw, hidden): equals the bulk-synchronous model
    at hidden=0, is monotone in hidden, and is bracketed by
    [max(t_sync_parts, hidden), t_sync + hidden]."""
    plan = ButterflyPlan(int(math.prod(degrees)), degrees)
    t_sync = plan.modeled_time(n0, 10.0 * n0, fabric, serial_nic=serial)
    t0 = plan.modeled_overlap_time(n0, 10.0 * n0, fabric, serial_nic=serial,
                                   hidden_compute_s=0.0)
    th = plan.modeled_overlap_time(n0, 10.0 * n0, fabric, serial_nic=serial,
                                   hidden_compute_s=hidden)
    assert math.isclose(t0, t_sync, rel_tol=1e-9, abs_tol=1e-15)
    assert th >= t0 - 1e-15 and th >= hidden
    assert th <= t_sync + hidden + 1e-12
    th2 = plan.modeled_overlap_time(n0, 10.0 * n0, fabric, serial_nic=serial,
                                    hidden_compute_s=2.0 * hidden)
    assert th2 >= th - 1e-15


@given(st.floats(min_value=0.0, max_value=1e9),
       st.integers(min_value=1, max_value=1024),
       st.sampled_from(_FABRICS))
@settings(max_examples=60, deadline=None)
def test_rate_bound_properties(nbytes, m, fabric):
    opt = rate_optimal_allreduce_s(nbytes, m, fabric)
    if m == 1:
        assert opt == 0.0
        return
    # latency floor + bandwidth term, monotone in payload
    assert opt >= 2.0 * math.ceil(math.log2(m)) * fabric.alpha_s
    assert rate_optimal_allreduce_s(2.0 * nbytes, m, fabric) >= opt
    # the fraction of the bound itself is exactly 1; degenerate guard
    if opt > 0.0:
        assert math.isclose(rate_fraction(opt, nbytes, m, fabric), 1.0,
                            rel_tol=1e-12)
    assert rate_fraction(0.0, nbytes, m, fabric) == 0.0


def test_select_plan_reports_rate_position():
    import warnings
    from repro.core.autotune import select_plan
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        sync = select_plan(64, 1e5, 1e6, EC2_2013)
        ov = select_plan(64, 1e5, 1e6, EC2_2013,
                         overlap_compute_s=0.5)
    for rep in (sync, ov):
        assert rep.rate_optimal_s is not None and rep.rate_optimal_s > 0.0
        assert math.isclose(rep.rate_fraction,
                            rep.rate_optimal_s / rep.modeled_s,
                            rel_tol=1e-12)
        assert 0.0 < rep.rate_fraction <= 1.0 + 1e-9
    assert sync.overlap_compute_s is None
    assert ov.overlap_compute_s == 0.5
    # hiding bandwidth can only help the makespan beyond the hidden work
    assert ov.modeled_s <= sync.modeled_s + 0.5 + 1e-9
    assert ov.modeled_s >= 0.5


def test_plan_cache_key_overlap_compat():
    """overlap_compute_s=0 must leave every pre-existing digest unchanged;
    nonzero values key separately (an overlap-reranked plan is not a valid
    bulk-synchronous answer)."""
    from repro.core.autotune import plan_cache_key
    base = dict(mesh=[("data", 8)], nnz=1e4, index_range=1e5, merge="sort",
                replication=1, width=1, fabric=EC2_2013)
    k0 = plan_cache_key(**base)
    k0b = plan_cache_key(**base, overlap_compute_s=0.0)
    kov = plan_cache_key(**base, overlap_compute_s=1e-3)
    assert k0 == k0b and "overlap_bucket" not in k0
    assert "overlap_bucket" in kov and kov != k0


# ---------------------------------------------------------------------------
# Auditor fixtures + injection (subprocess, trace-only: fast)
# ---------------------------------------------------------------------------

AUDIT_SYNC_CODE = r"""
from repro.launch.mesh import make_mesh
import jax, jax.numpy as jnp, numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P
from repro.compat import shard_map
from repro.analysis.auditor import audit_overlap_sync
from repro.core.allreduce import (dense_allreduce_hierarchical,
                                  dense_allreduce_hierarchical_bucketed,
                                  make_device_plan)

plan = make_device_plan([("d", 8)], {"d": (4, 2)}, 8, 8)
mesh = make_mesh((8,), ("d",))
sizes = (64, 32, 96)

def mk(schedule):
    def body(*xs):
        xs = [x.reshape(x.shape[1:]) for x in xs]
        if schedule == "stage_major":
            outs = dense_allreduce_hierarchical_bucketed(xs, plan)
        elif schedule == "sequential":
            outs = [dense_allreduce_hierarchical(x, plan) for x in xs]
        elif schedule == "injected_psum":
            # the attack the audit must catch: correct butterfly plus a
            # hidden full-tree reduction patching the result
            outs = dense_allreduce_hierarchical_bucketed(xs, plan)
            fix = lax.psum(outs[0].sum(), "d")
            outs = [outs[0] + 0.0 * fix] + outs[1:]
        return tuple(o[None] for o in outs)
    return shard_map(body, mesh=mesh, in_specs=(P("d"),) * len(sizes),
                     out_specs=(P("d"),) * len(sizes), check_vma=False)

args = tuple(jnp.zeros((8, n), jnp.float32) for n in sizes)
dep = plan.logical.depth

rep = audit_overlap_sync("bucketed", mk("stage_major"), mk("sequential"),
                         *args, depth=dep, n_buckets=3)
assert rep.ok, [str(c) for c in rep.failures()]

rep = audit_overlap_sync("hidden-psum", mk("injected_psum"),
                         mk("sequential"), *args, depth=dep, n_buckets=3)
assert not rep.ok
assert "same_total_collectives" in [c.check_id for c in rep.failures()], \
    [c.check_id for c in rep.failures()]

rep = audit_overlap_sync("bucket-major", mk("sequential"), mk("sequential"),
                         *args, depth=dep, n_buckets=3)
assert not rep.ok
failed = [c.check_id for c in rep.failures()]
assert "stage_major_interleaving" in failed, failed
assert "same_total_collectives" not in failed, failed

rep = audit_overlap_sync("wrong-buckets", mk("stage_major"),
                         mk("sequential"), *args, depth=dep, n_buckets=2)
assert not rep.ok
print("AUDIT_SYNC_OK")
"""


AUDIT_ENGINE_CODE = r"""
from repro.launch.mesh import make_mesh
import jax, numpy as np
from repro.analysis.auditor import audit_engine
from repro.data.pipeline import powerlaw_graph
from repro.graph.engine import GraphEngine
from repro.graph.pagerank import build_partitions, make_pagerank_engine

edges = powerlaw_graph(300, 1200, seed=1)
parts = build_partitions(edges, 300, 8)
mesh = make_mesh((8,), ("d",))
engine, extras, p0 = make_pagerank_engine(parts, 300, degrees=(4, 2),
                                          mesh=mesh)
ov = GraphEngine([np.asarray(o) for o in engine.out_sets],
                 [np.asarray(i) for i in engine.in_sets],
                 engine.app, degrees=(4, 2), mesh=mesh, overlap=True)

rep = audit_engine(ov, 5, p0, extras)
assert rep.ok, [str(c) for c in rep.failures()]
assert "overlap=True" in rep.target

# k=1 has nothing to rotate: the synchronous contract must apply
rep = audit_engine(ov, 1, p0, extras)
assert rep.ok, [str(c) for c in rep.failures()]
assert "overlap=False" in rep.target

# injection: claim a rotation the program never performed -- pin the
# synchronous build in the run-fn cache FIRST (flipping the flag before
# tracing would genuinely switch schedules), then audit: the auditor
# expects depth collectives before the scan and must fail on 0
engine.run_fn(5, "last")
engine.overlap = True
rep = audit_engine(engine, 5, p0, extras)
assert not rep.ok
assert "prologue_epilogue_split" in [c.check_id for c in rep.failures()], \
    [c.check_id for c in rep.failures()]

# inverse injection: deny the rotation of a genuinely overlapped program
# (its k=5 build is already cached from the positive audit above)
ov.overlap = False
rep = audit_engine(ov, 5, p0, extras)
assert not rep.ok
assert "no_collectives_outside_scan" in [c.check_id for c in rep.failures()], \
    [c.check_id for c in rep.failures()]
print("AUDIT_ENGINE_OK")
"""


@pytest.mark.slow
def test_audit_overlap_sync_fixtures_and_injection():
    assert "AUDIT_SYNC_OK" in _run(AUDIT_SYNC_CODE)


@pytest.mark.slow
def test_audit_engine_overlap_and_injection():
    assert "AUDIT_ENGINE_OK" in _run(AUDIT_ENGINE_CODE)


# ---------------------------------------------------------------------------
# Bitwise parity: bucketed hier sync (subprocess, 8 devices)
# ---------------------------------------------------------------------------

_SYNC_PRELUDE = r"""
import dataclasses, numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.configs import get_config
from repro.models import transformer as T
from repro.train.step import make_sync_fn

cfg = dataclasses.replace(
    get_config("qwen1.5-0.5b").reduced(d_model=64, d_ff=128, vocab=256,
                                       n_heads=2, n_kv=1, head_dim=32),
    tie_embeddings=False)

def dyadic_grads(params, seed):
    rng = np.random.RandomState(seed)
    return jax.tree.map(
        lambda p: jnp.asarray(
            rng.randint(-128, 129, p.shape).astype(np.float32) / 64
        ).astype(p.dtype), params)

def check_pair(mesh, tp, seed, **kw):
    params = T.init_params(cfg, tp, seed=0)
    grads = dyadic_grads(params, seed)
    rng = np.random.RandomState(seed + 1)
    dp = mesh.shape["data"]
    tokens = jnp.asarray(rng.randint(0, cfg.vocab, (2 * dp, 16)), jnp.int32)
    outs = {}
    for overlap in ("off", "bucketed"):
        fn, _ = make_sync_fn(cfg, mesh, sync_overlap=overlap,
                             sync_bucket_bytes=48 << 10, **kw)
        outs[overlap] = jax.jit(fn)(grads, tokens)
    a, ovf_a = jax.tree.map(np.asarray, outs["off"])
    b, ovf_b = jax.tree.map(np.asarray, outs["bucketed"])
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert all(np.array_equal(x, y) for x, y in zip(la, lb)), kw
    assert int(np.asarray(ovf_a)) == 0 and int(np.asarray(ovf_b)) == 0, kw
    return b
"""


HIER_PARITY_CODE = _SYNC_PRELUDE + r"""
mesh = make_mesh((4, 2), ("data", "model"))
check_pair(mesh, 2, 7, sync="hier", dp_degrees={"data": (2, 2)})
check_pair(mesh, 2, 11, sync="hier", dp_degrees={"data": (4,)},
           replication=2)

# degenerate bucket budgets: everything in one bucket / one leaf per
# bucket must still be bitwise (schedule changes, math never does)
params = T.init_params(cfg, 2, seed=0)
grads = dyadic_grads(params, 3)
tokens = jnp.zeros((8, 16), jnp.int32)
ref = None
for bb in (1, 48 << 10, 1 << 30):
    fn, _ = make_sync_fn(cfg, mesh, sync="hier",
                         dp_degrees={"data": (2, 2)},
                         sync_overlap="bucketed", sync_bucket_bytes=bb)
    out, ovf = jax.jit(fn)(grads, tokens)
    leaves = [np.asarray(l) for l in jax.tree.leaves(out)]
    assert int(np.asarray(ovf)) == 0
    if ref is None:
        ref = leaves
    else:
        assert all(np.array_equal(x, y) for x, y in zip(ref, leaves)), bb
print("HIER_PARITY_OK")
"""


@pytest.mark.slow
def test_sync_parity_hier_bucketed():
    assert "HIER_PARITY_OK" in _run(HIER_PARITY_CODE)


# ---------------------------------------------------------------------------
# Bitwise parity: double-buffered engine (subprocess, 8 devices)
# ---------------------------------------------------------------------------

ENGINE_PARITY_CODE = r"""
from repro.launch.mesh import make_mesh
import jax, jax.numpy as jnp, numpy as np
from repro.data.pipeline import powerlaw_graph
from repro.graph.engine import EngineApp, GraphEngine
from repro.graph.pagerank import build_partitions, make_pagerank_engine

mesh = make_mesh((8,), ("d",))

def leaves(x):
    return [np.asarray(l) for l in jax.tree.leaves(x)]

def run_pair(mk_engine, state, extras, k, collect="last"):
    res = []
    for overlap in (False, True):
        eng = mk_engine(overlap)
        final, last_out, traj = eng.run(k, state, extras, collect=collect)
        res.append((leaves(final) + leaves(last_out)
                    + (leaves(traj) if collect == "trajectory" else [])))
        rep = eng.sync_report()
        assert rep["overlap"] is overlap
        assert eng.report["dispatches"] == 1 and eng.report["rounds"] == k
    return res

# dyadic app: gather + halving update keeps every value on the binary
# lattice, so overlap-vs-sync equality must hold to the last bit at ANY k
# (including k=2, where the scan shrinks to length 1 and XLA fuses most
# aggressively)
rng = np.random.RandomState(5)
M, R = 8, 4096
out_idx = [rng.choice(R, rng.randint(5, 16), replace=False).astype(np.uint32)
           for _ in range(M)]
in_idx = [rng.choice(R, rng.randint(5, 16), replace=False).astype(np.uint32)
          for _ in range(M)]

def mk_dyadic(overlap):
    app = EngineApp(
        out_fn=lambda s, e: s[e["sel"]],
        update_fn=lambda s, inr, e, ax: 0.5 * s + inr,
        name="dyadic")
    return GraphEngine(out_idx, in_idx, app, degrees=(4, 2), mesh=mesh,
                       overlap=overlap)

probe = mk_dyadic(False)
sel = rng.randint(0, probe.uin_cap, (M, probe.u_cap)).astype(np.int32)
state = (rng.randint(-128, 129, (M, probe.uin_cap))
         .astype(np.float32) / 64)
extras = {"sel": jnp.asarray(sel)}
for k in (1, 2, 3, 6):
    a, b = run_pair(mk_dyadic, jnp.asarray(state), extras, k)
    assert all(np.array_equal(x, y) for x, y in zip(a, b)), k
a, b = run_pair(mk_dyadic, jnp.asarray(state), extras, 4,
                collect="trajectory")
assert all(np.array_equal(x, y) for x, y in zip(a, b)), "trajectory"
print("DYADIC_OK")

# PageRank (non-dyadic 1/deg weights): the schedule itself is a pure
# reordering (the dyadic app above proves it to the last bit), but the
# rotated program gives XLA different fusion opportunities around the
# ELL matvec, whose reassociated sums of non-representable values drift
# by an ulp -- so the non-lattice contract is tight allclose, not
# equality
edges = powerlaw_graph(300, 1200, seed=1)
parts = build_partitions(edges, 300, 8)

def mk_pr(overlap):
    eng, extras, p0 = make_pagerank_engine(parts, 300, degrees=(4, 2),
                                           mesh=mesh)
    if overlap:
        eng = GraphEngine([np.asarray(o) for o in eng.out_sets],
                          [np.asarray(i) for i in eng.in_sets],
                          eng.app, degrees=(4, 2), mesh=mesh, overlap=True)
    mk_pr.extras, mk_pr.p0 = extras, p0
    return eng

mk_pr(False)
for k in (2, 3, 6):
    a, b = run_pair(mk_pr, mk_pr.p0, mk_pr.extras, k)
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=1e-6, atol=1e-12)
print("ENGINE_PARITY_OK")
"""


@pytest.mark.slow
def test_engine_overlap_parity():
    out = _run(ENGINE_PARITY_CODE)
    assert "DYADIC_OK" in out and "ENGINE_PARITY_OK" in out


# ---------------------------------------------------------------------------
# The full sweep (pytest -m overlap; excluded from default runs --
# sparse-mode XLA compiles run minutes per combination)
# ---------------------------------------------------------------------------

SWEEP_HIER_16_CODE = _SYNC_PRELUDE + r"""
mesh = make_mesh((8, 2), ("data", "model"))
for degs in [(4, 2), (2, 2, 2), (8,)]:
    for r in (1, 2):
        check_pair(mesh, 2, 13 + r, sync="hier", dp_degrees={"data": degs},
                   replication=r)
        print("hier", degs, "r", r, "ok", flush=True)
print("SWEEP_HIER_16_OK")
"""

SWEEP_SPARSE_SORT_CODE = _SYNC_PRELUDE + r"""
mesh = make_mesh((4, 2), ("data", "model"))
check_pair(mesh, 2, 17, sync="sparse", dp_degrees={"data": (2, 2)},
           sync_merge="sort", sync_wire="raw", sparse_tokens_hint=32)
print("SWEEP_SPARSE_SORT_OK")
"""

SWEEP_SPARSE_FUSED_CODE = _SYNC_PRELUDE + r"""
mesh = make_mesh((4, 2), ("data", "model"))
check_pair(mesh, 2, 19, sync="sparse", dp_degrees={"data": (4,)},
           sync_merge="fused", sync_wire="delta", replication=2,
           sparse_tokens_hint=32)
print("SWEEP_SPARSE_FUSED_OK")
"""

SWEEP_SPARSE_BANDED_CODE = _SYNC_PRELUDE + r"""
mesh = make_mesh((4, 2), ("data", "model"))
check_pair(mesh, 2, 23, sync="sparse", dp_degrees={"data": (2, 2)},
           sync_merge="banded", sync_wire="delta", sparse_tokens_hint=32)
print("SWEEP_SPARSE_BANDED_OK")
"""

TRAIN_STEP_CODE = r"""
from repro.launch.mesh import make_mesh
import dataclasses, numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models import transformer as T
from repro.optim.adamw import AdamW
from repro.train.step import make_train_step

cfg = dataclasses.replace(
    get_config("qwen1.5-0.5b").reduced(d_model=64, d_ff=128, vocab=256,
                                       n_heads=2, n_kv=1, head_dim=32),
    tie_embeddings=False)
mesh = make_mesh((4, 2), ("data", "model"))
rng = np.random.RandomState(0)
batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab, (8, 32)), jnp.int32),
         "labels": jnp.asarray(rng.randint(0, cfg.vocab, (8, 32)), jnp.int32)}
outs = {}
for overlap in ("off", "bucketed"):
    step, _ = make_train_step(cfg, mesh, sync="hier",
                              dp_degrees={"data": (2, 2)},
                              opt=AdamW(lr=1e-3), sync_overlap=overlap,
                              sync_bucket_bytes=48 << 10)
    params = T.init_params(cfg, 2, seed=0)
    opt_state = AdamW().init(params)
    for _ in range(2):
        params, opt_state, m = step(params, opt_state, batch)
    outs[overlap] = (params, float(m["loss"]))
pa, la = outs["off"]
pb, lb = outs["bucketed"]
# end-to-end the two step programs differ outside the sync too (XLA may
# fuse the backward differently around the rescheduled collectives), so
# composition is checked to tight tolerance, not bitwise -- the bitwise
# claim is the sync-only harness's
assert np.isclose(la, lb, rtol=1e-5), (la, lb)
for x, y in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
    np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                               rtol=2e-5, atol=1e-7)
print("TRAIN_STEP_OK")
"""


@pytest.mark.overlap
@pytest.mark.slow
def test_sweep_hier_degrees_16dev():
    assert "SWEEP_HIER_16_OK" in _run(SWEEP_HIER_16_CODE, env=_ENV16)


@pytest.mark.overlap
@pytest.mark.slow
def test_sweep_sparse_sort_raw():
    assert "SWEEP_SPARSE_SORT_OK" in _run(SWEEP_SPARSE_SORT_CODE)


@pytest.mark.overlap
@pytest.mark.slow
def test_sweep_sparse_fused_delta_replicated():
    assert "SWEEP_SPARSE_FUSED_OK" in _run(SWEEP_SPARSE_FUSED_CODE)


@pytest.mark.overlap
@pytest.mark.slow
def test_sweep_sparse_banded_delta():
    assert "SWEEP_SPARSE_BANDED_OK" in _run(SWEEP_SPARSE_BANDED_CODE)


@pytest.mark.overlap
@pytest.mark.slow
def test_train_step_composition():
    assert "TRAIN_STEP_OK" in _run(TRAIN_STEP_CODE)
