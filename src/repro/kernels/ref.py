"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
from __future__ import annotations

import jax.numpy as jnp


def onehot_scatter_add_ref(pos: jnp.ndarray, val: jnp.ndarray,
                           num_rows: int) -> jnp.ndarray:
    """out[p] = sum_{i: pos_i == p} val[i].  pos entries outside [0, num_rows)
    are dropped — including negatives (jnp's own .at[] would wrap them).
    val: [C, W] -> out [num_rows, W]."""
    pos = jnp.where(pos < 0, num_rows, pos)
    out = jnp.zeros((num_rows,) + val.shape[1:], jnp.float32)
    return out.at[pos].add(val.astype(jnp.float32), mode="drop")


def rank_counts_ref(a: jnp.ndarray, b: jnp.ndarray, side: str) -> jnp.ndarray:
    """counts[i] = #{j : b_j < a_i}  (side='left')  or <= (side='right').

    a, b: uint32 sorted.  Used to compute stable merge ranks:
      rank_a[i] = i + counts_left(a, b)[i]
      rank_b[j] = j + counts_right(b, a)[j]
    """
    bias = jnp.int64(-2**31) if a.dtype == jnp.int64 else jnp.int32(-2**31)
    ai = a.astype(jnp.int32) + jnp.int32(-2**31)
    bi = b.astype(jnp.int32) + jnp.int32(-2**31)
    if side == "left":
        return jnp.searchsorted(bi, ai, side="left").astype(jnp.int32)
    return jnp.searchsorted(bi, ai, side="right").astype(jnp.int32)

