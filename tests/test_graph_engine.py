"""Device-resident iterative graph engine (repro.graph.engine).

In-process: the vectorized ELL construction vs a per-edge loop oracle,
ell_matvec paths, engine validation, and — on a single-device 1-node
mesh — the amortization contract: ``engine.run(k)`` performs exactly ONE
jitted dispatch and traces the per-round body exactly once, however
large k is.  Subprocess (16 forced host devices): k-iteration
device-vs-sim parity for PageRank / HADI / spectral plus the same
one-dispatch regression on a real multi-device mesh.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.data.pipeline import powerlaw_graph
from repro.graph.engine import build_ell, ell_matvec, stack_ell
from repro.graph.pagerank import (build_partitions, make_pagerank_engine,
                                  pagerank, pagerank_dense_reference)

_ENV = dict(os.environ,
            XLA_FLAGS="--xla_force_host_platform_device_count=16",
            PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src")
            + os.pathsep + os.environ.get("PYTHONPATH", ""))


def _run(code: str):
    r = subprocess.run([sys.executable, "-c", code], env=_ENV,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


# ---------------------------------------------------------------------------
# vectorized ELL build (the old per-edge Python loop, kept as oracle here)
# ---------------------------------------------------------------------------

def _ell_loop_reference(rows, cols, weights, n_rows, min_k=1):
    counts = np.bincount(rows, minlength=n_rows) if n_rows else \
        np.zeros(0, np.int64)
    kmax = max(int(counts.max(initial=0)), min_k)
    ell_c = np.full((n_rows, kmax), -1, np.int32)
    ell_w = np.zeros((n_rows, kmax), np.float32)
    slot = np.zeros(n_rows, np.int64)
    for e in np.argsort(rows, kind="stable"):
        r = rows[e]
        ell_c[r, slot[r]] = cols[e]
        ell_w[r, slot[r]] = weights[e]
        slot[r] += 1
    return ell_c, ell_w


@pytest.mark.parametrize("n_rows,n_edges", [(1, 1), (7, 40), (64, 500),
                                            (13, 13), (5, 0)])
def test_build_ell_matches_loop(n_rows, n_edges):
    rng = np.random.RandomState(n_rows * 1000 + n_edges)
    rows = rng.randint(0, n_rows, n_edges)
    cols = rng.randint(0, 50, n_edges)
    wts = rng.randn(n_edges).astype(np.float32)
    got_c, got_w = build_ell(rows, cols, wts, n_rows)
    ref_c, ref_w = _ell_loop_reference(rows, cols, wts, n_rows)
    np.testing.assert_array_equal(got_c, ref_c)
    np.testing.assert_array_equal(got_w, ref_w)


def test_build_ell_degenerate():
    c, w = build_ell(np.zeros(0, int), np.zeros(0, int), np.zeros(0), 0)
    assert c.shape == (0, 1) and w.shape == (0, 1)
    c, w = build_ell(np.zeros(0, int), np.zeros(0, int), np.zeros(0), 3)
    assert c.shape == (3, 1) and (c == -1).all() and (w == 0).all()


def test_partition_ell_tables_match_spmv(graph_small):
    """The vectorized Partition.ell_tables drives the engine's ELL matvec
    to the same product as the numpy spmv (the satellite regression: no
    per-edge Python loop, same ELL layout)."""
    import jax.numpy as jnp
    edges, n = graph_small
    parts = build_partitions(edges, n, 4)
    rng = np.random.RandomState(0)
    for p in parts:
        c, w = p.ell_tables()
        ref_c, ref_w = _ell_loop_reference(p.dst_pos, p.src_pos,
                                           p.inv_outdeg, len(p.out_idx))
        np.testing.assert_array_equal(c, ref_c)
        np.testing.assert_array_equal(np.asarray(w, np.float32), ref_w)
        x = rng.randn(len(p.in_idx))
        got = ell_matvec(jnp.asarray(c), jnp.asarray(w),
                         jnp.asarray(x, jnp.float32))
        np.testing.assert_allclose(np.asarray(got, np.float64), p.spmv(x),
                                   rtol=1e-5, atol=1e-8)


@pytest.fixture(scope="module")
def graph_small():
    return powerlaw_graph(300, 2000, seed=1), 300


# ---------------------------------------------------------------------------
# ell_matvec paths
# ---------------------------------------------------------------------------

def test_ell_matvec_widths_and_kernel():
    import jax.numpy as jnp
    rng = np.random.RandomState(3)
    cols = rng.randint(-1, 20, (16, 5)).astype(np.int32)
    wts = rng.randn(16, 5).astype(np.float32)
    x1 = rng.randn(20).astype(np.float32)
    xw = rng.randn(20, 3).astype(np.float32)
    ref1 = np.zeros(16)
    refw = np.zeros((16, 3))
    for r in range(16):
        for k in range(5):
            if cols[r, k] >= 0:
                ref1[r] += wts[r, k] * x1[cols[r, k]]
                refw[r] += wts[r, k] * xw[cols[r, k]]
    got1 = np.asarray(ell_matvec(jnp.asarray(cols), jnp.asarray(wts),
                                 jnp.asarray(x1)))
    gotw = np.asarray(ell_matvec(jnp.asarray(cols), jnp.asarray(wts),
                                 jnp.asarray(xw)))
    np.testing.assert_allclose(got1, ref1, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gotw, refw, rtol=1e-5, atol=1e-6)


def test_stack_ell_pads():
    t1 = (np.array([[1, 2]], np.int32), np.array([[1.0, 2.0]], np.float32))
    t2 = (np.full((3, 1), 0, np.int32), np.ones((3, 1), np.float32))
    cols, wts = stack_ell([t1, t2], 4)
    assert cols.shape == (2, 4, 2) and wts.shape == (2, 4, 2)
    assert cols[0, 0, 1] == 2 and cols[1, 2, 0] == 0
    assert (cols[0, 1:] == -1).all() and (cols[1, :, 1] == -1).all()


# ---------------------------------------------------------------------------
# engine on a single-device 1-node mesh: the amortization contract
# ---------------------------------------------------------------------------

def test_engine_one_dispatch_per_run(graph_small):
    """engine.run(k): exactly one jitted dispatch, the per-round body and
    the planned reduce traced exactly once (lax.scan, not an unrolled or
    per-iteration loop) — for any k; re-running the same k re-dispatches
    without re-tracing."""
    edges, n = graph_small
    parts = build_partitions(edges, n, 1)
    engine, extras, p0 = make_pagerank_engine(parts, n, degrees=())
    reduce_traces = []
    orig = engine.planned.reduce_on_device
    engine.planned.reduce_on_device = \
        lambda *a, **k: (reduce_traces.append(1), orig(*a, **k))[1]
    engine.run(7, p0, extras)
    assert engine.report == {"dispatches": 1, "rounds": 7, "step_traces": 1}
    assert len(reduce_traces) == 1
    engine.run(7, p0, extras)          # cached compile: no new trace
    assert engine.report == {"dispatches": 2, "rounds": 14, "step_traces": 1}
    assert len(reduce_traces) == 1
    engine.run(3, p0, extras)          # new k: one more trace, one dispatch
    assert engine.report == {"dispatches": 3, "rounds": 17, "step_traces": 2}
    assert len(reduce_traces) == 2
    rep = engine.sync_report()
    assert rep["host_roundtrips"] == 3
    assert rep["reduce_collectives_per_round"] == 2 * rep["butterfly_depth"]


def test_engine_run_places_inputs_on_their_shards(graph_small):
    """engine.run hands its program state and extras already sharded over
    the engine's mesh axis: host arrays go straight to their shards, never
    staged whole on one device (the stacked ELL tables of M partitions do
    not fit on one chip)."""
    from jax.sharding import PartitionSpec as P
    edges, n = graph_small
    parts = build_partitions(edges, n, 1)
    engine, extras, p0 = make_pagerank_engine(parts, n, degrees=())
    seen = []

    def program(state, extras_, *routing):
        seen.append((state, extras_))
        return state, state, None

    engine._run_cache[(2, "last")] = program
    engine.run(2, p0, extras)
    (state, got), = seen
    leaves = [state] + list(got.values())
    assert len(leaves) == 3
    for a in leaves:
        assert a.sharding.mesh == engine.mesh
        assert a.sharding.spec == P(engine.axis)


def test_engine_one_dispatch_per_run_overlap(graph_small):
    """The amortization contract survives the double-buffered schedule:
    a ``run(k)`` on an ``overlap=True`` engine is still one dispatch with
    the rotated body traced once (the reduce *halves* each appear twice
    per build — prologue/epilogue plus the scanned body — but never per
    round), and re-running a cached k re-traces nothing.  k=1 falls back
    to the synchronous body."""
    from repro.graph.engine import GraphEngine
    edges, n = graph_small
    parts = build_partitions(edges, n, 1)
    base, extras, p0 = make_pagerank_engine(parts, n, degrees=())
    engine = GraphEngine(base.out_sets, base.in_sets, base.app,
                         degrees=(), overlap=True)
    down_traces, up_traces = [], []
    orig_down = engine.planned.reduce_down_on_device
    orig_up = engine.planned.reduce_up_on_device
    engine.planned.reduce_down_on_device = \
        lambda *a, **k: (down_traces.append(1), orig_down(*a, **k))[1]
    engine.planned.reduce_up_on_device = \
        lambda *a, **k: (up_traces.append(1), orig_up(*a, **k))[1]
    engine.run(7, p0, extras)
    assert engine.report == {"dispatches": 1, "rounds": 7, "step_traces": 1}
    assert len(down_traces) == 2 and len(up_traces) == 2
    engine.run(7, p0, extras)          # cached compile: no new trace
    assert engine.report == {"dispatches": 2, "rounds": 14, "step_traces": 1}
    assert len(down_traces) == 2 and len(up_traces) == 2
    engine.run(3, p0, extras)          # new k: one more build
    assert engine.report == {"dispatches": 3, "rounds": 17, "step_traces": 2}
    assert len(down_traces) == 4 and len(up_traces) == 4
    rep = engine.sync_report()
    assert rep["overlap"] is True
    assert rep["host_roundtrips"] == 3
    # k=1 has nothing to rotate: the synchronous fallback body runs
    # (reduce_on_device composes the same halves, once each)
    engine.run(1, p0, extras)
    assert engine.report == {"dispatches": 4, "rounds": 18, "step_traces": 3}
    assert len(down_traces) == 5 and len(up_traces) == 5


def test_engine_pagerank_single_node_matches_dense(graph_small):
    edges, n = graph_small
    ref = pagerank_dense_reference(edges, n, iters=8)
    got, stats = pagerank(edges, n, m=1, degrees=(), iters=8,
                          backend="device")
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-8)
    assert stats["engine"]["dispatches"] == 1
    assert stats["engine"]["rounds"] == 8


def test_engine_validation(graph_small):
    edges, n = graph_small
    parts = build_partitions(edges, n, 1)
    engine, extras, p0 = make_pagerank_engine(parts, n, degrees=())
    with pytest.raises(ValueError):
        engine.run(0, p0, extras)
    with pytest.raises(ValueError):
        engine.run(2, p0, extras, collect="everything")
    from repro.core import SparseAllreduce
    ar = SparseAllreduce(1, (), backend="sim")
    with pytest.raises(ValueError):
        ar.planned_parts()
    ar_dev = SparseAllreduce(1, (), backend="device")
    with pytest.raises(RuntimeError):
        ar_dev.planned_parts()
    with pytest.raises(RuntimeError):
        ar_dev.staging_metadata()


# ---------------------------------------------------------------------------
# multi-device parity (subprocess, 16 forced host devices)
# ---------------------------------------------------------------------------

PARITY_CODE = r"""
import numpy as np, jax
from repro.data.pipeline import powerlaw_graph
from repro.graph.hadi import hadi, hadi_bitstring_reference
from repro.graph.pagerank import (build_partitions, make_pagerank_engine,
                                  pagerank, pagerank_dense_reference)
from repro.graph.spectral import power_iteration, power_iteration_reference

DEVS = np.array(jax.devices())
def mesh_of(n):
    return jax.sharding.Mesh(DEVS[:n], ("nodes",))

edges = powerlaw_graph(500, 3000, seed=1)
n = 500

# PageRank: k-iteration device == sim oracle == dense reference (fp32 tol)
ref = pagerank_dense_reference(edges, n, iters=10)
for m, degs in [(8, (4, 2)), (4, (2, 2))]:
    sim, _ = pagerank(edges, n, m=m, degrees=degs, iters=10)
    got, stats = pagerank(edges, n, m=m, degrees=degs, iters=10,
                          backend="device", mesh=mesh_of(m))
    np.testing.assert_allclose(got, sim, rtol=1e-4, atol=1e-10)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-10)
    assert stats["engine"]["dispatches"] == 1, stats["engine"]
    assert stats["engine"]["rounds"] == 10
    assert stats["engine"]["step_traces"] == 1

# one-dispatch regression on a real multi-device mesh
parts = build_partitions(edges, n, 8)
engine, extras, p0 = make_pagerank_engine(parts, n, (2, 2, 2),
                                          mesh=mesh_of(8))
traces = []
orig = engine.planned.reduce_on_device
engine.planned.reduce_on_device = \
    lambda *a, **k: (traces.append(1), orig(*a, **k))[1]
engine.run(6, p0, extras)
assert engine.report == {"dispatches": 1, "rounds": 6, "step_traces": 1}
assert len(traces) == 1
print("PAGERANK_ENGINE_OK")

# HADI: device bitstrings bit-identical to the sim oracle + global OR ref
eff_s, curve_s, st_s = hadi(edges, n, m=4, degrees=(4,), max_hops=5,
                            trials=3, bits=16)
eff_d, curve_d, st_d = hadi(edges, n, m=4, degrees=(4,), max_hops=5,
                            trials=3, bits=16, backend="device",
                            mesh=mesh_of(4))
assert eff_s == eff_d and st_s["hops_run"] == st_d["hops_run"]
np.testing.assert_array_equal(curve_s, curve_d)
np.testing.assert_array_equal(st_s["b_final"], st_d["b_final"])
refb = hadi_bitstring_reference(edges, n, st_d["b0"].reshape(n, -1),
                                st_d["hops_run"])
np.testing.assert_array_equal(st_d["b_final"].reshape(n, -1), refb)
assert st_d["engine"]["dispatches"] == 1
print("HADI_ENGINE_OK")

# spectral: fused normalize (psum) per round, tolerance-bounded
lam_r, v_r = power_iteration_reference(edges, n, iters=20, seed=2)
lam_d, v_d, st = power_iteration(edges, n, m=4, degrees=(2, 2), iters=20,
                                 seed=2, backend="device", mesh=mesh_of(4))
assert abs(lam_d - lam_r) / lam_r < 1e-4, (lam_d, lam_r)
cos = abs(np.dot(v_d, v_r)) / (np.linalg.norm(v_d) * np.linalg.norm(v_r))
assert cos > 1 - 1e-6, cos
assert st["engine"]["dispatches"] == 1 and st["engine"]["rounds"] == 20
print("SPECTRAL_ENGINE_OK")
"""


@pytest.mark.slow
def test_engine_parity_device_vs_sim_16dev():
    """k-iteration PageRank/HADI/spectral on the device engine match the
    simulator oracle (HADI bit-identically) with exactly one dispatch and
    one body trace per run, on 4/8-node meshes in a 16-device
    subprocess."""
    out = _run(PARITY_CODE)
    assert "PAGERANK_ENGINE_OK" in out
    assert "HADI_ENGINE_OK" in out
    assert "SPECTRAL_ENGINE_OK" in out
