"""Band-limited merge kernels (merge="banded") parity + tile-work bounds.

The banded pipeline must be bit-identical to the ``"sort"`` oracle (concat
+ argsort + segment_compact) on every workload the butterfly can hand it,
and its instrumented tile counts must meet the band bounds the kernels are
built on: the one-hot scatter-add visits at most ceil(k*bm/bk)+1 input
tiles per output tile (vs C/bk for fused), and the rank-merge compare runs
only on merge-frontier tiles.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sparse_vec as sv
from repro.core.sparse_vec import SENTINEL, HashPerm, SparseChunk
from repro.kernels import costmodel, ops
from repro.kernels.onehot_scatter import (band_inner_tiles,
                                          banded_onehot_scatter_add,
                                          onehot_scatter_add)
from repro.kernels.rank_merge import rank_counts, rank_tile_stats
from repro.kernels.ref import rank_counts_ref
from repro.launch.mesh import make_mesh


def _powerlaw_runs(k, cap, width, seed):
    """k sorted SENTINEL-padded runs of hash-permuted Zipf indices, each
    run's valid indices unique (the butterfly invariant banded relies on).

    Values are drawn on a dyadic lattice (multiples of 1/64 in [-2, 2]): a
    sum of up to ~64 such values is exactly representable in f32, so every
    summation order produces the same bits — bit-identity assertions then
    test the *merge logic*, not accumulation-association luck.
    """
    rng = np.random.RandomState(seed)
    perm = HashPerm.make(seed + 1)
    idx = np.full((k, cap), 0xFFFFFFFF, np.uint32)
    vshape = (k, cap) if width == 0 else (k, cap, width)
    val = np.zeros(vshape, np.float32)
    for r in range(k):
        raw = (rng.zipf(1.6, cap * 2) % 50_000).astype(np.uint32)
        h = np.unique(perm.fwd_np(raw))
        n = min(len(h), rng.randint(1, cap + 1))
        idx[r, :n] = h[:n]
        shape = (n,) if width == 0 else (n, width)
        val[r, :n] = (rng.randint(-128, 129, shape) / 64.0).astype(np.float32)
    return jnp.asarray(idx), jnp.asarray(val)


def _sort_path(idx, val, out_cap):
    cat = sv.concat_sorted_groups(idx, val)
    return sv.segment_compact(cat, out_cap), sv.compact_overflow(cat, out_cap)


def _assert_chunks_equal(got, want):
    np.testing.assert_array_equal(np.asarray(got.idx), np.asarray(want.idx))
    np.testing.assert_array_equal(np.asarray(got.val), np.asarray(want.val))


# ---------------------------------------------------------------------------
# Parity vs the sort oracle: k sweep x widths {1, W}
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [2, 4, 8, 16])
@pytest.mark.parametrize("width", [0, 3])
def test_banded_bit_identical_to_sort_path(k, width):
    cap = 96 if k <= 4 else 48
    idx, val = _powerlaw_runs(k, cap, width, seed=k * 10 + width)
    out_cap = k * cap
    want, want_ovf = _sort_path(idx, val, out_cap)
    got, ovf = ops.merge_sorted_runs(idx, val, out_cap, mode="banded")
    _assert_chunks_equal(got, want)
    assert int(ovf) == int(want_ovf) == 0


@pytest.mark.parametrize("k,cap", [(2, 64), (4, 32)])
def test_banded_overflow_matches_sort_path(k, cap):
    idx, val = _powerlaw_runs(k, cap, 0, seed=7)
    out_cap = max(8, (k * cap) // 4)
    want, want_ovf = _sort_path(idx, val, out_cap)
    got, ovf = ops.merge_sorted_runs(idx, val, out_cap, mode="banded")
    _assert_chunks_equal(got, want)
    assert int(ovf) == int(want_ovf) > 0


def test_banded_matches_fused():
    idx, val = _powerlaw_runs(4, 64, 2, seed=21)
    got_f, ovf_f = ops.merge_sorted_runs(idx, val, 256, mode="fused")
    got_b, ovf_b = ops.merge_sorted_runs(idx, val, 256, mode="banded")
    _assert_chunks_equal(got_b, got_f)
    assert int(ovf_f) == int(ovf_b)


# ---------------------------------------------------------------------------
# Degenerate streams
# ---------------------------------------------------------------------------

def test_banded_all_duplicate_runs():
    """Every run identical => every index has the maximal multiplicity k."""
    k, cap = 8, 32
    one = np.sort(HashPerm.make(5).fwd_np(
        np.arange(cap, dtype=np.uint32)))
    idx = jnp.asarray(np.tile(one, (k, 1)))
    val = jnp.asarray((np.random.RandomState(0).randint(-128, 129, (k, cap))
                       / 64.0).astype(np.float32))
    want, _ = _sort_path(idx, val, k * cap)
    got, ovf = ops.merge_sorted_runs(idx, val, k * cap, mode="banded")
    _assert_chunks_equal(got, want)
    assert int(ovf) == 0


def test_banded_all_sentinel_runs():
    idx = jnp.full((4, 16), SENTINEL, jnp.uint32)
    val = jnp.zeros((4, 16), jnp.float32)
    got, ovf = ops.merge_sorted_runs(idx, val, 64, mode="banded")
    assert int(got.count()) == 0
    assert int(ovf) == 0


def test_banded_single_valid_row():
    k, cap = 4, 16
    idx = np.full((k, cap), 0xFFFFFFFF, np.uint32)
    val = np.zeros((k, cap), np.float32)
    idx[2, 0] = 1234
    val[2, 0] = 7.5
    got, ovf = ops.merge_sorted_runs(jnp.asarray(idx), jnp.asarray(val),
                                     k * cap, mode="banded")
    want, _ = _sort_path(jnp.asarray(idx), jnp.asarray(val), k * cap)
    _assert_chunks_equal(got, want)
    assert int(got.count()) == 1 and int(ovf) == 0


# ---------------------------------------------------------------------------
# merge_add / segment_compact banded entry points
# ---------------------------------------------------------------------------

def test_merge_add_banded_parity():
    idx, val = _powerlaw_runs(2, 80, 0, seed=11)
    a = SparseChunk(idx=idx[0], val=val[0])
    b = SparseChunk(idx=idx[1], val=val[1])
    want = sv.merge_add(a, b, 160)
    got = ops.merge_add(a, b, 160, mode="banded")
    _assert_chunks_equal(got, want)


def test_segment_compact_banded_with_max_dup():
    """A sorted chunk whose indices repeat at most max_dup times."""
    rng = np.random.RandomState(4)
    base = np.sort(rng.choice(10_000, 40, replace=False).astype(np.uint32))
    reps = rng.randint(1, 4, 40)                  # multiplicity <= 3
    idx_np = np.repeat(base, reps)
    c = 160
    idx = np.full(c, 0xFFFFFFFF, np.uint32)
    idx[:len(idx_np)] = idx_np
    val = rng.randn(c).astype(np.float32)
    val[len(idx_np):] = 0.0
    ch = SparseChunk(idx=jnp.asarray(idx), val=jnp.asarray(val))
    want = sv.segment_compact(ch, c)
    got = ops.segment_compact(ch, c, max_dup=3)
    _assert_chunks_equal(got, want)


def test_mode_validation():
    idx, val = _powerlaw_runs(2, 16, 0, seed=1)
    with pytest.raises(ValueError):
        ops.merge_sorted_runs(idx, val, 32, mode="bogus")
    from repro.core.api import SparseAllreduce
    ar = SparseAllreduce(8, (4, 2), merge="banded")
    assert ar.merge == "banded"
    with pytest.raises(ValueError):
        SparseAllreduce(8, (4, 2), merge="bandit")
    from repro.train.step import make_train_step
    from repro.configs import get_config
    with pytest.raises(ValueError):
        make_train_step(get_config("qwen1.5-0.5b").reduced(),
                        make_mesh((1, 1), ("data", "model")),
                        sync_merge="bogus")


# ---------------------------------------------------------------------------
# Banded rank_counts parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strict", [True, False])
@pytest.mark.parametrize("bm,bn", [(512, 512), (32, 64), (8, 8)])
def test_banded_rank_counts_parity(strict, bm, bn):
    rng = np.random.RandomState(bm + bn + strict)
    for _ in range(5):
        ca, cb = rng.randint(1, 200), rng.randint(1, 200)
        a = np.sort(rng.randint(0, 5000, ca).astype(np.uint32))
        b = np.sort(rng.randint(0, 5000, cb).astype(np.uint32))
        a[-max(1, ca // 5):] = 0xFFFFFFFF      # sentinel tails
        got = rank_counts(jnp.asarray(a), jnp.asarray(b), strict=strict,
                          bm=bm, bn=bn, banded=True, interpret=True)
        ref = rank_counts_ref(jnp.asarray(a), jnp.asarray(b),
                              "left" if strict else "right")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_banded_rank_counts_all_equal_streams():
    a = jnp.asarray(np.full(64, 9, np.uint32))
    for strict in (True, False):
        got = rank_counts(a, a, strict=strict, bm=16, bn=16, banded=True, interpret=True)
        want = np.full(64, 0 if strict else 64, np.int32)
        np.testing.assert_array_equal(np.asarray(got), want)


# ---------------------------------------------------------------------------
# Tile-work bounds (the point of the banded mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,cap", [(2, 2048), (4, 1024), (8, 512), (16, 256)])
def test_scatter_band_bound(k, cap):
    """Banded one-hot scatter visits <= ceil(k*bm/bk)+1 input tiles per
    output tile; fused scans all C/bk."""
    bm, bk = costmodel.SCATTER_BM, costmodel.SCATTER_BK
    c = k * cap
    rep_b = costmodel.scatter_tile_report(c, 1, c, mode="banded", band=k)
    rep_f = costmodel.scatter_tile_report(c, 1, c, mode="fused")
    bound = -(-k * bm // bk) + 1
    assert rep_b["inner_tiles_per_out_tile"] == band_inner_tiles(k, bm, bk) \
        == bound
    assert rep_b["inner_tiles_per_out_tile"] <= bound
    assert rep_f["inner_tiles_per_out_tile"] == -(-c // bk)
    assert rep_b["tiles"] < rep_f["tiles"]


def test_banded_scatter_kernel_parity_monotone_pos():
    """The banded kernel == dense kernel on a monotone pos stream.  Same
    tile shapes on both sides: identical bk partitions make the partial-sum
    groupings identical (out-of-window tiles contribute exact zeros), so
    even randn values must match bitwise."""
    rng = np.random.RandomState(0)
    band, rows = 4, 300
    mult = rng.randint(1, band + 1, rows)
    pos_np = np.repeat(np.arange(rows), mult)
    c = len(pos_np)
    val = rng.randn(c, 5).astype(np.float32)
    pos = jnp.asarray(pos_np.astype(np.int32))
    got = banded_onehot_scatter_add(pos, jnp.asarray(val), rows, band=band,
                                    bm=64, bk=128, interpret=True)
    ref = onehot_scatter_add(pos, jnp.asarray(val), rows, bm=64, bk=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_banded_scatter_block_multiple_boundary():
    """c an exact multiple of bk with source-less output tiles beyond the
    last destination: the start-block table must stay within the padded
    input (regression for an off-the-end block index)."""
    band, rows, bk = 8, 64, 512
    pos_np = np.repeat(np.arange(rows), band)          # c = 512 == bk
    val = np.arange(len(pos_np), dtype=np.float32)[:, None]
    got = banded_onehot_scatter_add(jnp.asarray(pos_np.astype(np.int32)),
                                    jnp.asarray(val), 1024, band=band,
                                    bk=bk, interpret=True)
    ref = onehot_scatter_add(jnp.asarray(pos_np.astype(np.int32)),
                             jnp.asarray(val), 1024, bk=bk, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_rank_frontier_only_tiles():
    """On hash-unique sorted streams, the banded rank kernel's compare work
    is confined to the merge frontier: O(Ca/bm + Cb/bn) tiles, not the full
    (Ca/bm)*(Cb/bn) plane."""
    perm = HashPerm.make(2)
    a = np.sort(perm.fwd_np(np.arange(4096, dtype=np.uint32)))
    b = np.sort(perm.fwd_np(np.arange(4096, 8192, dtype=np.uint32)))
    bm = bn = 128
    st = rank_tile_stats(a, b, strict=True, bm=bm, bn=bn)
    n_a, n_b = len(a) // bm, len(b) // bn
    assert st["total_tiles"] == n_a * n_b
    assert st["frontier_tiles"] <= n_a + n_b
    assert st["frontier_tiles"] + st["full_below_tiles"] \
        + st["skipped_tiles"] == st["total_tiles"]
    # the cheap classification must agree with actual counts: checked by
    # parity tests above; here assert the instrumented report plumbs through
    rep = costmodel.merge_tile_report(
        jnp.asarray(np.stack([a, b])), 8192, mode="banded",
        rank_bm=bm, rank_bn=bn)
    assert rep["rank_compare_tiles"] <= 2 * (n_a + n_b)
    assert rep["rank_compare_tiles"] + rep["rank_cheap_tiles"] \
        == rep["rank_total_tiles"]
    assert rep["scatter_inner_tiles_per_out_tile"] == band_inner_tiles(
        2, costmodel.SCATTER_BM, costmodel.SCATTER_BK)
