"""jit'd public wrappers over the Pallas kernels.

``segment_compact`` / ``merge_add`` here are drop-in, kernel-backed versions
of the pure-jnp ones in ``core.sparse_vec`` (which remain the oracles).
Each call decides interpret mode from the backend it is traced for
(:func:`interpret_mode`): the Pallas interpreter off-TPU, the compiled
kernel on the chip.

Merge modes (``mode="fused" | "banded"``): both run the same rank-merge +
compact + one-hot scatter-add pipeline; ``banded`` additionally exploits
the monotonicity of the sorted streams to band-limit both kernels — the
rank compare planes collapse to frontier tiles and the scatter's inner grid
dimension to the static ``ceil(band*bm/bk)+1`` bound (see
``kernels.costmodel`` for the tile/FLOP accounting).  Banded results are
bit-identical to fused and to the sort-based oracle.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.sparse_vec import SENTINEL, SparseChunk
from .onehot_scatter import banded_onehot_scatter_add, onehot_scatter_add
from .rank_merge import rank_counts

MERGE_KERNEL_MODES = ("fused", "banded")


def interpret_mode() -> bool:
    """True off-TPU (Pallas interpreter), False on the chip.  Asked per
    call, at trace time — never once at import."""
    return jax.default_backend() != "tpu"


def _check_mode(mode: str) -> None:
    if mode not in MERGE_KERNEL_MODES:
        raise ValueError(
            f"mode must be one of {MERGE_KERNEL_MODES}, got {mode!r}")


def _compact_positions(idx: jax.Array, out_capacity: int):
    """Destination row per entry of a sorted idx stream (+ head flags)."""
    valid = idx != jnp.uint32(SENTINEL)
    is_head = jnp.concatenate([jnp.ones((1,), bool), idx[1:] != idx[:-1]]) & valid
    pos = jnp.cumsum(is_head.astype(jnp.int32)) - 1
    pos = jnp.where(valid & (pos < out_capacity), pos, out_capacity)
    return pos, is_head


def _compact_scatter_add(merged_idx: jax.Array, ranks: Optional[jax.Array],
                         val: jax.Array, out_capacity: int,
                         mode: str = "fused", band: Optional[int] = None,
                         scale: Optional[jax.Array] = None,
                         out_dtype=None
                         ) -> Tuple[SparseChunk, jax.Array]:
    """Shared tail of every compact pipeline: scatter the head index of each
    duplicate group, then coalesce values with a single one-hot MXU matmul.

    ``merged_idx``: sorted [C] uint32 stream; ``ranks``: position of value
    row e within that stream (None when the rows are already in stream
    order); ``val``: [C] or [C, W].  Rows whose compact position exceeds
    ``out_capacity`` fall off the one-hot tiles (drop semantics).

    ``mode="fused"`` feeds the scatter-add straight from the input layout
    (``final_pos[e] = pos[ranks[e]]`` — arbitrary order, so the kernel
    scans every input tile per output tile).  ``mode="banded"`` first
    permutes the values into merge order, making the destination stream
    ``pos`` non-decreasing with multiplicity <= ``band``, which lets the
    band-limited kernel visit only ceil(band*bm/bk)+1 input tiles per
    output tile.  Returns ``(chunk, n_unique)``.

    ``scale`` [C] f32: per-source-row dequantization factor fused into the
    one-hot matmul (wire-decode path — ``val`` stays in its on-wire dtype).
    ``out_dtype`` overrides the output value dtype (default: ``val``'s own
    dtype; a fused decode wants the compute dtype instead).
    """
    _check_mode(mode)
    out_dtype = out_dtype if out_dtype is not None else val.dtype
    pos, is_head = _compact_positions(merged_idx, out_capacity)
    out_idx = jnp.full((out_capacity,), SENTINEL, jnp.uint32)
    out_idx = out_idx.at[jnp.where(is_head, pos, out_capacity)].set(
        merged_idx, mode="drop")
    v2 = val if val.ndim == 2 else val[:, None]
    if mode == "banded":
        if band is None:
            raise ValueError("banded mode needs a source-multiplicity bound")
        if ranks is not None:                    # permute into merge order
            v2 = jnp.zeros_like(v2).at[ranks].set(v2)
            if scale is not None:
                scale = jnp.zeros_like(scale).at[ranks].set(scale)
        out_val = banded_onehot_scatter_add(
            pos, v2, out_capacity, band=band, scale=scale,
            interpret=interpret_mode()).astype(out_dtype)
    else:
        final_pos = pos if ranks is None else pos[ranks]
        out_val = onehot_scatter_add(final_pos, v2, out_capacity, scale=scale,
                                     interpret=interpret_mode()).astype(out_dtype)
    if val.ndim == 1:
        out_val = out_val[:, 0]
    return (SparseChunk(idx=out_idx, val=out_val),
            jnp.sum(is_head.astype(jnp.int32)))


def segment_compact(chunk: SparseChunk, out_capacity: Optional[int] = None,
                    max_dup: Optional[int] = None) -> SparseChunk:
    """Kernel-backed coalesce of a sorted chunk (MXU one-hot scatter-add).

    ``max_dup``: optional bound on how many times any index repeats in the
    chunk; when given, the band-limited kernel is used (a sorted chunk is
    already in stream order, so no permutation is needed).
    """
    out_capacity = out_capacity or chunk.capacity
    mode = "banded" if max_dup is not None else "fused"
    out, _ = _compact_scatter_add(chunk.idx, None, chunk.val, out_capacity,
                                  mode=mode, band=max_dup)
    return out


def merge_add(a: SparseChunk, b: SparseChunk,
              out_capacity: Optional[int] = None,
              mode: str = "fused") -> SparseChunk:
    """Kernel-backed merge of two sorted chunks with collision summation.

    1. merge ranks via the blocked compare kernel (no data-dependent loop)
    2. build the merged idx stream with one scatter
    3. coalesce values straight from the *inputs* with a single fused
       one-hot matmul: final_pos[e] = compact_pos[rank[e]].

    ``mode="banded"`` assumes each input chunk has unique valid indices
    (multiplicity <= 2 in the merge) and band-limits both kernels.
    """
    _check_mode(mode)
    banded = mode == "banded"
    ca, cb = a.capacity, b.capacity
    out_capacity = out_capacity or (ca + cb)
    rank_a = jnp.arange(ca, dtype=jnp.int32) + rank_counts(
        a.idx, b.idx, strict=True, interpret=interpret_mode(), banded=banded)
    rank_b = jnp.arange(cb, dtype=jnp.int32) + rank_counts(
        b.idx, a.idx, strict=False, interpret=interpret_mode(), banded=banded)
    merged_idx = jnp.zeros((ca + cb,), jnp.uint32)
    merged_idx = merged_idx.at[rank_a].set(a.idx)
    merged_idx = merged_idx.at[rank_b].set(b.idx)
    # entry e of (a ++ b) lands at compact position pos[rank_e]
    ranks = jnp.concatenate([rank_a, rank_b])
    cat = jnp.concatenate([a.val, b.val], axis=0)
    out, _ = _compact_scatter_add(merged_idx, ranks, cat, out_capacity,
                                  mode=mode, band=2)
    return out


def merge_sorted_runs(idx: jax.Array, val: jax.Array, out_capacity: int,
                      mode: str = "fused",
                      row_scale: Optional[jax.Array] = None,
                      out_dtype=None) -> Tuple[SparseChunk, jax.Array]:
    """Fused k-way merge: rank-merge sorted runs, compact duplicate indices,
    and scatter-add the values in one pass (no full re-sort).

    This is the per-layer hot path of the butterfly: after ``all_to_all``
    each device holds k *already sorted* runs (``idx`` [k, cap] uint32,
    SENTINEL-padded; ``val`` [k, cap] or [k, cap, W]).  The sort-based
    path re-sorts all k*cap rows from scratch; here the merge permutation
    is computed directly instead:

    1. rank of run r's element i in the merge =
       ``i + sum_s #{j : runs[s][j] (<= if s<r else <) runs[r][i]}``
       (non-strict against earlier runs keeps the merge stable) — k*(k-1)
       blocked compare-and-reduce kernels, no data-dependent loop;
    2. one scatter materializes the merged idx stream; head flags + cumsum
       give each entry its compacted destination row;
    3. values go straight from the input layout into the compacted output
       through a single one-hot MXU matmul: ``final_pos[e] = pos[rank[e]]``.

    ``mode="banded"`` band-limits both kernel families using the run
    structure: the rank compare planes resolve non-frontier tiles from
    scalar-prefetched block edges, and the scatter-add (on values permuted
    into merge order, where destinations are monotone with multiplicity
    <= k) visits only ceil(k*bm/bk)+1 input tiles per output tile.  It
    assumes each run's valid indices are unique — the butterfly invariant
    (runs are compacted chunks), giving merge multiplicity <= k.

    Returns ``(chunk, overflow)`` with the same contract as
    ``sparse_vec.segment_compact`` + ``compact_overflow`` on the sorted
    concatenation: ``overflow`` counts unique indices beyond
    ``out_capacity`` (dropped).  Sentinel padding sorts to the tail and is
    dropped by the compact step automatically.

    ``row_scale`` [k] f32: per-run dequantization scale (the int8 wire
    format ships one scale per all_to_all row); it is broadcast per entry
    and fused into the scatter-add kernel, so quantized values are widened
    only in-register.  ``out_dtype`` sets the output value dtype (wire
    decodes pass the compute dtype; default keeps ``val``'s dtype).
    """
    _check_mode(mode)
    banded = mode == "banded"
    k, cap = idx.shape
    total = k * cap
    ranks = []
    for r in range(k):
        rk = jnp.arange(cap, dtype=jnp.int32)
        for s in range(k):
            if s == r:
                continue
            rk = rk + rank_counts(idx[r], idx[s], strict=(s > r),
                                  interpret=interpret_mode(), banded=banded)
        ranks.append(rk)
    rank = jnp.stack(ranks).reshape((total,))        # bijection on [0, total)
    flat_idx = idx.reshape((total,))
    merged_idx = jnp.zeros((total,), jnp.uint32).at[rank].set(flat_idx)
    scale = None
    if row_scale is not None:
        scale = jnp.repeat(row_scale.astype(jnp.float32), cap)
    out, n_unique = _compact_scatter_add(
        merged_idx, rank, val.reshape((total,) + val.shape[2:]), out_capacity,
        mode=mode, band=k, scale=scale, out_dtype=out_dtype)
    return out, jnp.maximum(n_unique - out_capacity, 0)

