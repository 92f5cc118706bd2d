"""Merge-rank kernel: positions of two sorted streams in their merge.

The paper merges sorted sparse vectors pairwise (tree sum).  On TPU a
data-dependent two-pointer merge is hostile to the vector unit; instead the
merge *permutation* is computed directly:

    rank_a[i] = i + #{j : b_j <  a_i}       (stable: a before b on ties)
    rank_b[j] = j + #{i : a_i <= b_j}

The counting term is a blocked compare-and-reduce over the (Ca, Cb) plane —
pure VPU work with in-register iota tiles, no HBM intermediate.  Sentinel
padding (0xFFFFFFFF) sorts to the tail of the merge automatically.

``banded=True`` exploits the sortedness of *both* streams: per-block
min/max edges (scalar-prefetched) classify each (a-block, b-block) tile
against the merge frontier — tiles strictly below it contribute a constant
``bn`` per row, tiles strictly above contribute nothing, and only the
O(Ca/bm + Cb/bn) frontier tiles run the full compare-and-reduce.
``rank_tile_stats`` reports that classification (it is derived from the
same edge tables the kernel consumes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compat import CompilerParams, PrefetchScalarGridSpec

_BIAS = -(2 ** 31)

# default compare-plane tile shape — shared with the costmodel.  1-D
# blocks are whole multiples of the TPU's 1024-element tile of a 1-D
# uint32 array (Mosaic refuses a block that splits one).
BM, BN = 1024, 1024


def _kernel(a_ref, b_ref, cnt_ref, *, strict: bool):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    bias = jnp.asarray(_BIAS, jnp.int32)
    a = a_ref[...].astype(jnp.int32) + bias      # [bm] order-preserving
    b = b_ref[...].astype(jnp.int32) + bias      # [bn]
    if strict:
        hits = (b[None, :] < a[:, None])
    else:
        hits = (b[None, :] <= a[:, None])
    cnt_ref[...] += jnp.sum(hits.astype(jnp.int32), axis=1)


# ---------------------------------------------------------------------------
# Banded variant: block-edge triage against the merge frontier
# ---------------------------------------------------------------------------

def _pad_sorted(x: jax.Array, block: int) -> jax.Array:
    """Pad a sorted uint32 stream with MAX to a block multiple."""
    n = x.shape[0]
    np_ = pl.cdiv(n, block) * block
    return jnp.full((np_,), 0xFFFFFFFF, jnp.uint32).at[:n].set(x)


def _block_edges(x_padded: jax.Array, block: int) -> jax.Array:
    """[2, nblocks] int32 (min, max) per block of a sorted padded stream,
    in the biased order-preserving int32 domain the kernels compare in."""
    b = (x_padded.astype(jnp.int32) + jnp.int32(_BIAS)).reshape((-1, block))
    return jnp.stack([b[:, 0], b[:, -1]])


def _tile_classes(a_edges: jax.Array, b_edges: jax.Array, strict: bool):
    """(full, skip) boolean [I, J] tables: b-block entirely below every row
    of the a-block (contributes bn per row), or entirely above (contributes
    nothing).  Everything else is a frontier tile.  Mirrors the kernel's
    ``pl.when`` conditions exactly — both consume the same edge tables."""
    a_lo, a_hi = a_edges[0][:, None], a_edges[1][:, None]
    b_lo, b_hi = b_edges[0][None, :], b_edges[1][None, :]
    if strict:
        full = b_hi < a_lo
        skip = b_lo >= a_hi
    else:
        full = b_hi <= a_lo
        skip = b_lo > a_hi
    return full, skip & ~full


def rank_tile_stats(a: jax.Array, b: jax.Array, *, strict: bool = True,
                    bm: int = BM, bn: int = BN) -> dict:
    """Tile-work counter for the banded kernel on concrete streams: how many
    (a-block, b-block) tiles run the full compare (frontier) vs are resolved
    from block edges alone.  The dense kernel runs the compare on all
    ``total`` tiles."""
    a_edges = _block_edges(_pad_sorted(jnp.asarray(a), bm), bm)
    b_edges = _block_edges(_pad_sorted(jnp.asarray(b), bn), bn)
    full, skip = _tile_classes(a_edges, b_edges, strict)
    n_full = int(jnp.sum(full))
    n_skip = int(jnp.sum(skip))
    total = int(full.shape[0] * full.shape[1])
    return {"total_tiles": total, "full_below_tiles": n_full,
            "skipped_tiles": n_skip,
            "frontier_tiles": total - n_full - n_skip}


def _banded_kernel(ae_ref, be_ref, a_ref, b_ref, cnt_ref, *, strict: bool,
                   bn: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    a_lo, a_hi = ae_ref[0, i], ae_ref[1, i]
    b_lo, b_hi = be_ref[0, j], be_ref[1, j]
    if strict:
        full = b_hi < a_lo
        skip = b_lo >= a_hi
    else:
        full = b_hi <= a_lo
        skip = b_lo > a_hi

    @pl.when(full)
    def _whole_block_below():                    # every b in block counts
        cnt_ref[...] += jnp.int32(bn)

    @pl.when(jnp.logical_not(full | skip))
    def _frontier():                             # straddles: full compare
        bias = jnp.asarray(_BIAS, jnp.int32)
        a = a_ref[...].astype(jnp.int32) + bias
        b = b_ref[...].astype(jnp.int32) + bias
        if strict:
            hits = (b[None, :] < a[:, None])
        else:
            hits = (b[None, :] <= a[:, None])
        cnt_ref[...] += jnp.sum(hits.astype(jnp.int32), axis=1)


@functools.partial(jax.jit, static_argnames=("strict", "bm", "bn",
                                             "interpret", "banded"))
def rank_counts(a: jax.Array, b: jax.Array, *, interpret: bool,
                strict: bool = True, bm: int = BM, bn: int = BN,
                banded: bool = False) -> jax.Array:
    """counts[i] = #{j : b_j < a_i} (strict) or <= (not strict); uint32 in.

    ``interpret`` is the caller's decision (``kernels.ops`` passes True
    only off-TPU): the Pallas interpreter is never a silent default."""
    ca, cb = a.shape[0], b.shape[0]
    # pad a with MAX (counts for pads are garbage, sliced off), b with MAX
    # (never counted by '<' against real values; '<=' against MAX pads of a
    # is sliced off anyway).
    a_p = _pad_sorted(a, bm)
    b_p = _pad_sorted(b, bn)
    cap, cbp = a_p.shape[0], b_p.shape[0]

    grid = (cap // bm, cbp // bn)
    if banded:
        a_edges = _block_edges(a_p, bm)
        b_edges = _block_edges(b_p, bn)
        grid_spec = PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[pl.BlockSpec((bm,), lambda i, j, ae, be: (i,)),
                      pl.BlockSpec((bn,), lambda i, j, ae, be: (j,))],
            out_specs=pl.BlockSpec((bm,), lambda i, j, ae, be: (i,)),
        )
        out = pl.pallas_call(
            functools.partial(_banded_kernel, strict=strict, bn=bn),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((cap,), jnp.int32),
            compiler_params=CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(a_edges, b_edges, a_p, b_p)
    else:
        out = pl.pallas_call(
            functools.partial(_kernel, strict=strict),
            grid=grid,
            in_specs=[pl.BlockSpec((bm,), lambda i, j: (i,)),
                      pl.BlockSpec((bn,), lambda i, j: (j,))],
            out_specs=pl.BlockSpec((bm,), lambda i, j: (i,)),
            out_shape=jax.ShapeDtypeStruct((cap,), jnp.int32),
            compiler_params=CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(a_p, b_p)
    counts = out[:ca]
    # b's padding is MAX. strict '<': pads never count (nothing exceeds MAX).
    # non-strict '<=': pads DO count against queries that are themselves MAX
    # (sentinel rows of a are real array rows) — subtract them.
    if not strict and cbp != cb:
        counts = counts - jnp.where(a == jnp.uint32(0xFFFFFFFF),
                                    jnp.int32(cbp - cb), jnp.int32(0))
    return counts
