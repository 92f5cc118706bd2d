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

import jax
import numpy as np
import pytest

from repro.data.pipeline import powerlaw_graph
from repro.graph.engine import (build_ell, degree_class, ell_extras,
                                ell_matvec)
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


def _f64_product(rows, cols, weights, n_rows, x):
    """``(y, mag, deg)``: the float64 product of float32 weights and ``x``
    by rows, the row sums of ``|w * x|`` and the entries per row."""
    w = np.asarray(weights, np.float32).astype(np.float64)
    t = w.reshape((-1,) + (1,) * (x.ndim - 1)) * x[cols]
    y = np.zeros((n_rows,) + x.shape[1:])
    mag = np.zeros_like(y)
    np.add.at(y, rows, t)
    np.add.at(mag, rows, np.abs(t))
    return y, mag, np.bincount(rows, minlength=n_rows)


def _assert_f32_close(got, rows, cols, weights, n_rows, x):
    """float32's bound on an ELL product: a row of d entries is within
    d * 2^-23 * sum |w * x| of the exact product (each product rounded
    once, d - 1 additions in any order, padding adding exact zeros)."""
    ref, mag, deg = _f64_product(rows, cols, weights, n_rows, x)
    bound = np.maximum(deg, 1).reshape((-1,) + (1,) * (x.ndim - 1)) \
        * 2.0 ** -23 * mag
    err = np.abs(np.asarray(got, np.float64) - ref)
    assert (err <= bound).all(), np.max(err - bound)


def _one_class(cols, wts):
    """``[R, K]`` tables as the extras of one degree class holding every
    row in order: what ``ell_matvec`` reads for a plain ELL table."""
    import jax.numpy as jnp
    return {"cols": jnp.asarray(cols), "wts": jnp.asarray(wts), "more": (),
            "perm": jnp.arange(cols.shape[0], dtype=jnp.int32)}


def _one_node(extras, i=0):
    """Node ``i``'s tables of stacked ``ell_extras``, as the engine hands
    them to ``ell_matvec`` (leading M axis dropped)."""
    import jax.numpy as jnp
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a[i]), extras)


def test_partition_ell_tables_match_spmv(graph_small):
    """The vectorized Partition.ell_tables equals the per-edge loop's
    tables exactly and drives the engine's ELL matvec to the numpy
    spmv's product within float32's summation bound (a cancelling row can
    miss any fixed relative tolerance); the degree-sliced ``ell_extras``
    keeps to the same bound."""
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
        x = rng.randn(len(p.in_idx)).astype(np.float32).astype(np.float64)
        n_out = len(p.out_idx)
        # the spmv's float64 weights, rounded to float32, move a row by at
        # most 2^-24 * sum |w * x|
        ref, mag, _ = _f64_product(*p.ell_coo(), n_out, x)
        assert (np.abs(ref - p.spmv(x)) <= 2.0 ** -23 * mag).all()
        extras = ell_extras([p.ell_coo()], n_out)
        for ell in (_one_class(c, w), _one_node(extras)):
            got = ell_matvec(ell, jnp.asarray(x, jnp.float32))
            _assert_f32_close(got, *p.ell_coo(), n_out, x)


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
    ell = _one_class(cols, wts)
    got1 = np.asarray(ell_matvec(ell, jnp.asarray(x1)))
    gotw = np.asarray(ell_matvec(ell, jnp.asarray(xw)))
    np.testing.assert_allclose(got1, ref1, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gotw, refw, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# degree-sliced ELL layout
# ---------------------------------------------------------------------------

def _sliced_loop_reference(triplets, n_rows):
    """The sliced layout by a per-row loop: classes ceil(log2 d), rows in
    row order within a class, entries in edge order within a row."""
    import math
    deg = [np.bincount(r, minlength=n_rows) for r, _, _ in triplets]
    klass = [[math.ceil(math.log2(d)) if d else -1 for d in dd]
             for dd in deg]
    classes = sorted({c for kk in klass for c in kk if c >= 0})
    members = [[[row for row in range(n_rows) if kk[row] == c]
                for c in classes] for kk in klass]
    r_cap = [max(len(mm[j]) for mm in members) for j in range(len(classes))]
    k_cap = [max(deg[i][row] for i, mm in enumerate(members)
                 for row in mm[j]) for j in range(len(classes))]
    m = len(triplets)
    tables = [(np.full((m, r, k), -1, np.int32),
               np.zeros((m, r, k), np.float32))
              for r, k in zip(r_cap, k_cap)]
    offsets = np.concatenate([[0], np.cumsum(r_cap)])
    perm = np.full((m, n_rows), offsets[-1], np.int32)
    for i, (rows, cols, wts) in enumerate(triplets):
        for j, mm in enumerate(members[i]):
            for p, row in enumerate(mm):
                perm[i, row] = offsets[j] + p
        fill = np.zeros(n_rows, np.int64)
        for row, col, w in zip(rows, cols, wts):
            j = classes.index(klass[i][row])
            p = members[i][j].index(row)
            tables[j][0][i, p, fill[row]] = col
            tables[j][1][i, p, fill[row]] = w
            fill[row] += 1
    return tables, perm, members, offsets


def _hub_triplets(m, n_rows, seed):
    """M nodes' COO entries with Zipf row degrees: a few hub rows, many
    rows of degree 1-2, some empty rows."""
    rng = np.random.RandomState(seed)
    out = []
    for i in range(m):
        deg = np.minimum(rng.zipf(1.6, n_rows), 300)
        deg[rng.rand(n_rows) < 0.1] = 0
        rows = rng.permutation(np.repeat(np.arange(n_rows), deg))
        out.append((rows, rng.randint(0, 97, len(rows)),
                    rng.randn(len(rows)).astype(np.float32)))
    return out


@pytest.mark.parametrize("m,n_rows,seed", [(1, 60, 0), (3, 40, 1),
                                           (4, 25, 2)])
def test_ell_extras_sliced_matches_loop(m, n_rows, seed):
    """The sliced build equals a per-row loop: class membership, K_c as
    the class's widest row, stable slot order; ``perm`` is the inverse
    of the class placement (it round-trips every live row)."""
    triplets = _hub_triplets(m, n_rows, seed)
    extras = ell_extras(triplets, n_rows)
    tables, perm, members, offsets = _sliced_loop_reference(triplets,
                                                            n_rows)
    got = [(extras["cols"], extras["wts"])] + list(extras["more"])
    assert len(got) == len(tables) > 1
    for (gc, gw), (rc, rw) in zip(got, tables):
        np.testing.assert_array_equal(gc, rc)
        np.testing.assert_array_equal(gw, rw)
    np.testing.assert_array_equal(extras["perm"], perm)
    for i in range(m):
        inv = np.full(offsets[-1] + 1, -1)
        live = np.flatnonzero(np.bincount(triplets[i][0],
                                          minlength=n_rows))
        inv[extras["perm"][i, live]] = live
        assert len(set(extras["perm"][i, live])) == len(live)
        for j, mm in enumerate(members[i]):
            placed = inv[offsets[j]: offsets[j + 1]]
            np.testing.assert_array_equal(placed[: len(mm)], mm)
            assert (placed[len(mm):] == -1).all()
        empty = np.setdiff1d(np.arange(n_rows), live)
        assert (extras["perm"][i, empty] == offsets[-1]).all()


def _plain_extras(triplets, n_rows):
    """Every node's ``build_ell`` tables padded to the widest row of any
    node and stacked, as the extras of one class holding every row."""
    tables = [build_ell(*t, n_rows) for t in triplets]
    k = max(c.shape[1] for c, _ in tables)
    return {"cols": np.stack([np.pad(c, [(0, 0), (0, k - c.shape[1])],
                                     constant_values=-1) for c, _ in tables]),
            "wts": np.stack([np.pad(w, [(0, 0), (0, k - w.shape[1])])
                             for _, w in tables]),
            "more": (),
            "perm": np.tile(np.arange(n_rows, dtype=np.int32),
                            (len(triplets), 1))}


@pytest.mark.parametrize("m", [1, 4])
@pytest.mark.parametrize("w", [None, 3])
def test_sliced_matvec_equals_plain(m, w):
    """On a power-law graph the sliced ``ell_matvec`` gives the plain
    layout's product (every row padded to the widest, one class): both
    within float32's bound of the exact product for x of shape [N] and
    [N, W], and bit for bit on 0/1 sums (HADI's OR through the additive
    reduce)."""
    import jax.numpy as jnp
    edges = powerlaw_graph(400, 3000, seed=5)
    parts = build_partitions(edges, 400, m)
    u_cap = max(len(p.out_idx) for p in parts)
    triplets = [p.ell_coo() for p in parts]
    sliced = ell_extras(triplets, u_cap)
    assert len(sliced["more"]) > 0
    plain = _plain_extras(triplets, u_cap)
    ones = [(r, c, np.ones(len(r), np.float32)) for r, c, _ in triplets]
    sliced01 = ell_extras(ones, u_cap)
    plain01 = _plain_extras(ones, u_cap)
    rng = np.random.RandomState(m)
    n_in = max(len(p.in_idx) for p in parts)
    shape = (n_in,) if w is None else (n_in, w)
    x = rng.randn(*shape).astype(np.float32)
    bits = jnp.asarray((rng.rand(*shape) < 0.3).astype(np.float32))
    for i, (rows, cols_i, wts_i) in enumerate(triplets):
        for ell in (sliced, plain):
            got = ell_matvec(_one_node(ell, i), jnp.asarray(x))
            _assert_f32_close(got, rows, cols_i, wts_i, u_cap,
                              x.astype(np.float64))
        np.testing.assert_array_equal(
            np.asarray(ell_matvec(_one_node(sliced01, i), bits)),
            np.asarray(ell_matvec(_one_node(plain01, i), bits)))


def _near_regular(m, n_rows, seed):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(m):
        rows = rng.permutation(np.repeat(np.arange(n_rows),
                                         4 + rng.randint(0, 2, n_rows)))
        out.append((rows, rng.randint(0, 50, len(rows)),
                    rng.randn(len(rows)).astype(np.float32)))
    return out


@pytest.mark.parametrize("degree", [1, 4, 16])
def test_ell_extras_one_class_is_build_ell(degree):
    """Rows of one degree make one class whose tables are ``build_ell``'s
    and whose ``perm`` keeps every row in place."""
    rng = np.random.RandomState(degree)
    rows = rng.permutation(np.repeat(np.arange(30), degree))
    cols, wts = rng.randint(0, 50, len(rows)), rng.randn(len(rows))
    extras = ell_extras([(rows, cols, wts)], 30)
    c, w = build_ell(rows, cols, wts, 30)
    assert extras["more"] == ()
    np.testing.assert_array_equal(extras["cols"][0], c)
    np.testing.assert_array_equal(extras["wts"][0], w)
    np.testing.assert_array_equal(extras["perm"][0], np.arange(30))


@pytest.mark.parametrize("w", [None, 3])
def test_ell_extras_no_edges(w):
    """A graph with no edges gets one class of no rows; every row of the
    product reads the zero slot."""
    import jax.numpy as jnp
    empty = (np.zeros(0, np.int64), np.zeros(0, np.int64), np.zeros(0))
    extras = ell_extras([empty, empty], 5)
    assert extras["cols"].shape == (2, 0, 1) and extras["more"] == ()
    np.testing.assert_array_equal(extras["perm"], np.zeros((2, 5)))
    x = jnp.ones((7,) if w is None else (7, w), jnp.float32)
    got = ell_matvec(_one_node(extras, 1), x)
    np.testing.assert_array_equal(np.asarray(got), np.zeros((5,) + x.shape[1:]))


@pytest.mark.parametrize("maker", [_near_regular, _hub_triplets])
def test_ell_extras_leaves_lead_with_m(maker):
    """Near-regular and hub graphs alike keep a 3-D ``cols`` / ``wts``
    pair and give every leaf the leading M axis the engine shards over
    its mesh."""
    extras = ell_extras(maker(3, 40, 7), 48)
    assert extras["cols"].ndim == 3 and extras["wts"].ndim == 3
    assert extras["cols"].shape == extras["wts"].shape
    for leaf in jax.tree_util.tree_leaves(extras):
        assert leaf.shape[0] == 3


@pytest.mark.parametrize("top", [1, 2 ** 16 - 1, 2 ** 16, 2 ** 40])
def test_stable_argsort_matches_numpy(top):
    """The 16-bit-digit sort orders keys of every width as numpy's stable
    argsort does, ties in input order."""
    from repro.graph.engine import _stable_argsort
    rng = np.random.RandomState(top % 1000)
    keys = rng.randint(0, top + 1, 5000, dtype=np.int64)
    keys[:50] = top
    np.testing.assert_array_equal(_stable_argsort(keys),
                                  np.argsort(keys, kind="stable"))


def test_degree_class():
    d = np.array([0, 1, 2, 3, 4, 5, 8, 9, 16975, 2 ** 40, 2 ** 40 + 1])
    np.testing.assert_array_equal(
        degree_class(d), [-1, 0, 1, 2, 2, 3, 3, 4, 15, 40, 41])


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
    leaves = [state] + jax.tree_util.tree_leaves(got)
    assert len(leaves) == 1 + len(jax.tree_util.tree_leaves(extras)) >= 3
    for a in leaves:
        assert a.sharding.mesh == engine.mesh
        assert a.sharding.spec == P(engine.axis)


def test_engine_run_keeps_placed_inputs(graph_small):
    """Inputs already sharded over the engine's mesh axis reach the
    program as they are (the window's extras and each dispatch's state),
    with host arrays beside them still placed."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    edges, n = graph_small
    parts = build_partitions(edges, n, 1)
    engine, extras, p0 = make_pagerank_engine(parts, n, degrees=())
    shard = NamedSharding(engine.mesh, P(engine.axis))
    placed = jax.device_put(extras, shard)
    seen = []

    def program(state, extras_, *routing):
        seen.append((state, extras_))
        return state, state, None

    engine._run_cache[(2, "last")] = program
    engine.run(2, p0, dict(placed, host=np.ones((1, 3), np.float32)))
    (state, got), = seen
    assert state.sharding == shard and got["host"].sharding == shard
    for a, b in zip(jax.tree_util.tree_leaves(placed),
                    jax.tree_util.tree_leaves(dict(got, host=None))):
        assert a is b


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
