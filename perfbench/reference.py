"""Plain references: what the timed paths must produce, with nothing of
the program.  A lower ``dtype`` gives the control.  PageRank runs in
float64 on the host; with ``dtype=bfloat16`` every stored value and
product is rounded to bfloat16 (sums are rounded once, after a float64
accumulation, which flatters the control).  The union sum runs on the
chips in float32, exact on the benchmark's values, or in bfloat16."""
from __future__ import annotations

import ml_dtypes
import numpy as np

BF16 = "bfloat16"


def _rounder(dtype):
    if dtype is None:
        return lambda x: x
    if dtype == BF16:
        return lambda x: x.astype(ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"unknown reference dtype {dtype!r}")


def pagerank_states(edges: np.ndarray, n_vertices: int, rounds: int,
                    every: int, damping: float = 0.85, dtype=None):
    """PageRank from the uniform vector: the dense reference of
    ``repro.graph.pagerank.pagerank_dense_reference`` (column-normalised
    G, teleport (1-d)/n), returning the vector after each multiple of
    ``every`` rounds: ``[rounds // every, n]``."""
    rnd = _rounder(dtype)
    src, dst = edges[:, 0], edges[:, 1]
    outdeg = np.bincount(src, minlength=n_vertices).astype(np.float64)
    outdeg[outdeg == 0] = 1.0
    inv = rnd(1.0 / outdeg)
    p = rnd(np.full(n_vertices, 1.0 / n_vertices))
    out = []
    for r in range(1, rounds + 1):
        # an edge's term p[src] / outdeg[src] depends on its source alone
        q = rnd(np.bincount(dst, weights=rnd(p * inv)[src],
                            minlength=n_vertices))
        p = rnd((1 - damping) / n_vertices + rnd(damping * q))
        if r % every == 0:
            out.append(p)
    return np.stack(out) if out else np.zeros((0, n_vertices))


def union_sum(vals, pos, out_capacity: int, dtype="float32"):
    """Union sum on the chips (as ``chip_smoke.phase_allreduce`` checks
    it): every worker's rows ``vals`` ``[M, C, W]`` added into row ``pos``
    ``[M, C]`` of the sorted union (``out_capacity`` for padding rows,
    which drop), in ``dtype``.  Returns ``[out_capacity, W]`` float32."""
    import jax.numpy as jnp
    w = vals.shape[-1]
    total = jnp.zeros((out_capacity, w), dtype).at[pos.reshape(-1)].add(
        vals.reshape(-1, w).astype(dtype), mode="drop")
    return total.astype(jnp.float32)
