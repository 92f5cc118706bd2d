"""One-hot MXU scatter-add — the TPU-native form of the paper's merge-sum.

The paper's CPU insight (§III-A): summing sparse vectors by *coherent
addition of sorted index streams* is ~5x faster than hash tables because it
matches the memory system.  The TPU analogue: once destinations ``pos`` are
known (sorted indices make them a cheap cumsum), the scatter-add

    out[p, :] += sum_{i : pos_i = p} val[i, :]

is a matmul  ``out = OneHot(pos)^T @ val``  — which runs on the MXU at full
throughput instead of serializing through scatter hardware.  This kernel is
the workhorse behind ``segment_compact`` and ``merge_add``.

Tiling: grid (I, J, K) over (out-rows/bm, width/bn, in-rows/bk), K innermost
accumulating into the (bm, bn) VMEM out tile.  The one-hot tile (bk, bm) is
generated in-register from the pos block — it never touches HBM.

``banded_onehot_scatter_add`` is the band-limited variant for *monotone*
``pos`` streams (merge order): when every destination row absorbs at most
``band`` sources, the sources of any bm-row output tile form a contiguous
window of at most band*bm rows, so a scalar-prefetched per-output-tile
start-block table shrinks the inner grid dimension from C/bk to the static
``band_inner_tiles(band, bm, bk) = ceil(band*bm/bk)+1`` — a C/(band*bm)-fold
cut of the MXU tile work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compat import CompilerParams, PrefetchScalarGridSpec

# default tile shapes (out-rows, width, in-rows) — shared with the
# costmodel so the instrumented tile/FLOP reports describe these kernels.
# BK is a multiple of 1024: the TPU lays a 1-D int32/f32 array out in
# tiles of 1024, and Mosaic refuses a 1-D block that splits such a tile.
BM, BN, BK = 128, 128, 1024

# The one-hot matmul must reproduce f32 sums exactly: default TPU matmul
# precision would round the values to bf16 on the MXU.
_EXACT = jax.lax.Precision.HIGHEST


def _kernel(pos_ref, val_ref, out_ref, *, bm: int, bk: int):
    i = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    pos = pos_ref[...]                                   # [bk] int32
    rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bk, bm), 1)
    onehot = (pos[:, None] == rows).astype(jnp.float32)  # [bk, bm]
    out_ref[...] += jax.lax.dot_general(
        onehot, val_ref[...].astype(jnp.float32),
        (((0,), (0,)), ((), ())),                        # contract over bk
        precision=_EXACT, preferred_element_type=jnp.float32)


def _scaled_kernel(pos_ref, scale_ref, val_ref, out_ref, *, bm: int, bk: int):
    # Wire-decode fusion: val arrives in its on-wire dtype (int8/bf16) and
    # is dequantized in-register — scale_ref [bk] is the per-source-row
    # quantization scale — so the widened f32 form never touches HBM.
    i = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    pos = pos_ref[...]                                   # [bk] int32
    rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bk, bm), 1)
    onehot = (pos[:, None] == rows).astype(jnp.float32)  # [bk, bm]
    v = val_ref[...].astype(jnp.float32) * scale_ref[...][:, None]
    out_ref[...] += jax.lax.dot_general(
        onehot, v, (((0,), (0,)), ((), ())),
        precision=_EXACT, preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("num_rows", "bm", "bn", "bk", "interpret"))
def onehot_scatter_add(pos: jax.Array, val: jax.Array, num_rows: int,
                       *, interpret: bool, scale: jax.Array | None = None,
                       bm: int = BM, bn: int = BN, bk: int = BK) -> jax.Array:
    """out[num_rows, W] = scatter-add of val [C, W] at rows pos [C].

    Out-of-range pos (e.g. drop bins, padding parked at num_rows) fall off
    every one-hot tile and vanish — free drop semantics.

    ``scale`` [C] f32, when given, multiplies each source row in-register
    before the one-hot matmul — the fused dequantization hook for the
    int8 wire format (``val`` stays in its on-wire dtype end to end).

    ``interpret`` is the caller's decision (``kernels.ops`` passes True
    only off-TPU): the Pallas interpreter is never a silent default.
    """
    c, w = val.shape
    # pad to tile multiples
    cp = pl.cdiv(c, bk) * bk
    wp = pl.cdiv(w, bn) * bn
    rp = pl.cdiv(num_rows, bm) * bm
    pos_p = jnp.full((cp,), -1, jnp.int32).at[:c].set(pos.astype(jnp.int32))
    val_p = jnp.zeros((cp, wp), val.dtype).at[:c, :w].set(val)

    grid = (rp // bm, wp // bn, cp // bk)
    pos_spec = pl.BlockSpec((bk,), lambda i, j, k: (k,))
    val_spec = pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))
    if scale is None:
        kernel = functools.partial(_kernel, bm=bm, bk=bk)
        in_specs = [pos_spec, val_spec]
        operands = (pos_p, val_p)
    else:
        kernel = functools.partial(_scaled_kernel, bm=bm, bk=bk)
        in_specs = [pos_spec, pl.BlockSpec((bk,), lambda i, j, k: (k,)),
                    val_spec]
        scale_p = jnp.zeros((cp,), jnp.float32).at[:c].set(
            scale.astype(jnp.float32))
        operands = (pos_p, scale_p, val_p)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rp, wp), jnp.float32),
        compiler_params=CompilerParams(dimension_semantics=("parallel", "parallel",
                                       "arbitrary")),
        interpret=interpret,
    )(*operands)
    return out[:num_rows, :w]


# ---------------------------------------------------------------------------
# Band-limited variant for monotone pos streams
# ---------------------------------------------------------------------------

def band_inner_tiles(band: int, bm: int, bk: int) -> int:
    """Static bound on input tiles any output tile draws from: the <=band*bm
    source rows of a bm-row output tile are contiguous, so they span at most
    ceil(band*bm/bk) blocks plus one for start-of-window misalignment."""
    return -(-band * bm // bk) + 1


def _banded_kernel(starts_ref, pos_ref, val_ref, out_ref, *, bm: int, bk: int):
    i = pl.program_id(0)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    pos = pos_ref[...]                                   # [bk] int32
    rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bk, bm), 1)
    onehot = (pos[:, None] == rows).astype(jnp.float32)  # [bk, bm]
    out_ref[...] += jax.lax.dot_general(
        onehot, val_ref[...].astype(jnp.float32),
        (((0,), (0,)), ((), ())),
        precision=_EXACT, preferred_element_type=jnp.float32)


def _banded_scaled_kernel(starts_ref, pos_ref, scale_ref, val_ref, out_ref,
                          *, bm: int, bk: int):
    # Banded twin of _scaled_kernel: fused per-source-row dequantization.
    i = pl.program_id(0)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    pos = pos_ref[...]                                   # [bk] int32
    rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bk, bm), 1)
    onehot = (pos[:, None] == rows).astype(jnp.float32)  # [bk, bm]
    v = val_ref[...].astype(jnp.float32) * scale_ref[...][:, None]
    out_ref[...] += jax.lax.dot_general(
        onehot, v, (((0,), (0,)), ((), ())),
        precision=_EXACT, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("num_rows", "band", "bm", "bn",
                                             "bk", "interpret"))
def banded_onehot_scatter_add(pos: jax.Array, val: jax.Array, num_rows: int,
                              *, band: int, interpret: bool,
                              scale: jax.Array | None = None,
                              bm: int = BM, bn: int = BN,
                              bk: int = BK) -> jax.Array:
    """Band-limited scatter-add: requires ``pos`` non-decreasing with at most
    ``band`` sources per destination row (rows parked at >= num_rows — drop
    bins / padding — must sit at the tail).

    A host-side searchsorted builds the per-output-tile start-block table;
    the kernel's BlockSpec index maps read it via scalar prefetch, so output
    tile i visits only input blocks [starts[i], starts[i] + KB) with the
    static KB = band_inner_tiles(band, bm, bk) — instead of all C/bk.
    Out-of-window rows load but never match the one-hot row range, and the
    window provably covers every in-range source, so the result is exactly
    ``onehot_scatter_add(pos, val, num_rows)``.

    ``scale`` [C] f32: fused per-source-row dequantization, as in
    :func:`onehot_scatter_add` (pad rows carry scale 0).
    """
    c, w = val.shape
    kb = band_inner_tiles(band, bm, bk)
    cp = pl.cdiv(c, bk) * bk
    wp = pl.cdiv(w, bn) * bn
    rp = pl.cdiv(num_rows, bm) * bm
    # pad (kb-1) extra blocks so starts[i]+t never reads out of bounds; the
    # pad rows are parked at -1 and never match any output row.
    cpad = cp + (kb - 1) * bk
    pos_i32 = pos.astype(jnp.int32)
    pos_p = jnp.full((cpad,), -1, jnp.int32).at[:c].set(pos_i32)
    val_p = jnp.zeros((cpad, wp), val.dtype).at[:c, :w].set(val)

    n_out_tiles = rp // bm
    first_src = jnp.searchsorted(pos_i32,
                                 jnp.arange(n_out_tiles, dtype=jnp.int32) * bm,
                                 side="left")
    # clamp: first_src == c on a c that is a block multiple would address
    # one block past the pad; shifting such (source-less) windows down one
    # block keeps every read in bounds without losing coverage.
    starts = jnp.minimum((first_src // bk).astype(jnp.int32),
                         jnp.int32(cpad // bk - kb))

    grid = (n_out_tiles, wp // bn, kb)
    pos_spec = pl.BlockSpec((bk,), lambda i, j, t, s: (s[i] + t,))
    val_spec = pl.BlockSpec((bk, bn), lambda i, j, t, s: (s[i] + t, j))
    if scale is None:
        kernel = functools.partial(_banded_kernel, bm=bm, bk=bk)
        in_specs = [pos_spec, val_spec]
        operands = (starts, pos_p, val_p)
    else:
        kernel = functools.partial(_banded_scaled_kernel, bm=bm, bk=bk)
        in_specs = [pos_spec,
                    pl.BlockSpec((bk,), lambda i, j, t, s: (s[i] + t,)),
                    val_spec]
        scale_p = jnp.zeros((cpad,), jnp.float32).at[:c].set(
            scale.astype(jnp.float32))
        operands = (starts, pos_p, scale_p, val_p)
    grid_spec = PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t, s: (i, j)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rp, wp), jnp.float32),
        compiler_params=CompilerParams(dimension_semantics=("parallel", "parallel",
                                       "arbitrary")),
        interpret=interpret,
    )(*operands)
    return out[:num_rows, :w]
