"""Pallas kernels vs pure-jnp oracles (interpret mode; shape/dtype sweeps)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import sparse_vec as sv
from repro.core.sparse_vec import SparseChunk
from repro.kernels import ops
from repro.kernels.onehot_scatter import (banded_onehot_scatter_add,
                                          onehot_scatter_add)
from repro.kernels.rank_merge import rank_counts
from repro.kernels.ref import onehot_scatter_add_ref, rank_counts_ref


# ---------------------------------------------------------------------------
# onehot_scatter_add
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("c,w,rows", [(16, 1, 8), (100, 7, 50), (512, 128, 256),
                                      (513, 130, 100), (64, 1, 1)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_onehot_scatter_sweep(c, w, rows, dtype):
    rng = np.random.RandomState(c + w)
    pos = rng.randint(-1, rows + 2, c).astype(np.int32)   # incl. out-of-range
    val = rng.randn(c, w).astype(dtype)
    got = onehot_scatter_add(jnp.asarray(pos), jnp.asarray(val), rows, interpret=True)
    ref = onehot_scatter_add_ref(jnp.asarray(pos), jnp.asarray(val), rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-3 if dtype == np.float16 else 1e-5,
                               atol=1e-3 if dtype == np.float16 else 1e-5)


@pytest.mark.parametrize("bm,bk,bn", [(8, 16, 8), (128, 512, 128)])
def test_onehot_scatter_blockspec_sweep(bm, bk, bn):
    rng = np.random.RandomState(0)
    pos = rng.randint(0, 40, 200).astype(np.int32)
    val = rng.randn(200, 20).astype(np.float32)
    got = onehot_scatter_add(jnp.asarray(pos), jnp.asarray(val), 40,
                             bm=bm, bk=bk, bn=bn, interpret=True)
    ref = onehot_scatter_add_ref(jnp.asarray(pos), jnp.asarray(val), 40)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# rank_counts (merge ranks)
# ---------------------------------------------------------------------------

@given(st.lists(st.integers(0, 300), min_size=1, max_size=150),
       st.lists(st.integers(0, 300), min_size=1, max_size=150),
       st.booleans())
@settings(max_examples=40, deadline=None)
def test_rank_counts_property(a, b, strict):
    a = np.sort(np.array(a, np.uint32))
    b = np.sort(np.array(b, np.uint32))
    got = rank_counts(jnp.asarray(a), jnp.asarray(b), strict=strict, interpret=True)
    ref = rank_counts_ref(jnp.asarray(a), jnp.asarray(b),
                          "left" if strict else "right")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_rank_counts_with_sentinels():
    a = np.array([5, 10, 0xFFFFFFFF, 0xFFFFFFFF], np.uint32)
    b = np.array([1, 10, 0xFFFFFFFF], np.uint32)
    got_l = np.asarray(rank_counts(jnp.asarray(a), jnp.asarray(b), strict=True, interpret=True))
    np.testing.assert_array_equal(got_l, [1, 1, 2, 2])
    got_r = np.asarray(rank_counts(jnp.asarray(a), jnp.asarray(b),
                                   strict=False, interpret=True))
    np.testing.assert_array_equal(got_r, [1, 2, 3, 3])


def test_merge_is_permutation():
    rng = np.random.RandomState(1)
    for ca, cb in [(64, 64), (100, 30), (1, 700)]:
        a = np.sort(rng.randint(0, 500, ca).astype(np.uint32))
        b = np.sort(rng.randint(0, 500, cb).astype(np.uint32))
        ra = np.arange(ca) + np.asarray(
            rank_counts(jnp.asarray(a), jnp.asarray(b), strict=True, interpret=True))
        rb = np.arange(cb) + np.asarray(
            rank_counts(jnp.asarray(b), jnp.asarray(a), strict=False, interpret=True))
        assert sorted(list(ra) + list(rb)) == list(range(ca + cb))


# ---------------------------------------------------------------------------
# kernel-backed segment_compact / merge_add vs the pure-jnp versions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("c,w,out_cap", [(64, 0, 64), (200, 4, 120),
                                         (33, 0, 16)])
def test_segment_compact_kernel_vs_ref(c, w, out_cap):
    rng = np.random.RandomState(c)
    n = rng.randint(1, c + 1)
    idx = np.full(c, 0xFFFFFFFF, np.uint32)
    idx[:n] = np.sort(rng.randint(0, 80, n).astype(np.uint32))
    val = rng.randn(*((c, w) if w else (c,))).astype(np.float32)
    ch = SparseChunk(idx=jnp.asarray(idx), val=jnp.asarray(val))
    ref = sv.segment_compact(ch, out_cap)
    got = ops.segment_compact(ch, out_cap)
    np.testing.assert_array_equal(np.asarray(ref.idx), np.asarray(got.idx))
    np.testing.assert_allclose(np.asarray(ref.val), np.asarray(got.val),
                               rtol=1e-5, atol=1e-5)


@given(st.integers(1, 120), st.integers(1, 120), st.integers(0, 3),
       st.integers(0, 10_000))
@settings(max_examples=30, deadline=None)
def test_merge_add_kernel_property(ca, cb, w, seed):
    rng = np.random.RandomState(seed)

    def mk(c):
        n = rng.randint(1, c + 1)
        idx = np.full(c, 0xFFFFFFFF, np.uint32)
        idx[:n] = np.sort(rng.randint(0, 150, n).astype(np.uint32))
        val = rng.randn(*((c, w) if w else (c,))).astype(np.float32)
        mask = idx != 0xFFFFFFFF
        val = val * (mask[:, None] if w else mask)
        return SparseChunk(idx=jnp.asarray(idx), val=jnp.asarray(val))

    a, b = mk(ca), mk(cb)
    ref = sv.merge_add(a, b, 200)
    got = ops.merge_add(a, b, 200)
    np.testing.assert_array_equal(np.asarray(ref.idx), np.asarray(got.idx))
    np.testing.assert_allclose(np.asarray(ref.val), np.asarray(got.val),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# interpret mode is the caller's decision, never a kernel default
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("call", [
    lambda p, v, i: onehot_scatter_add(p, v, 8),
    lambda p, v, i: banded_onehot_scatter_add(p, v, 8, band=2),
    lambda p, v, i: rank_counts(i, i),
])
def test_raw_kernels_require_interpret(call):
    pos = jnp.zeros((8,), jnp.int32)
    val = jnp.ones((8, 1), jnp.float32)
    idx = jnp.arange(8, dtype=jnp.uint32)
    with pytest.raises(TypeError, match="interpret"):
        call(pos, val, idx)


def test_interpret_mode_follows_backend():
    import jax
    assert ops.interpret_mode() == (jax.default_backend() != "tpu")
