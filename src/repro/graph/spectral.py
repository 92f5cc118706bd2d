"""Spectral methods: distributed power iteration (paper §I-A.2).

"Almost all eigenvalue algorithms use repeated matrix-vector products" — the
matvec is the same edge-partitioned SpMV + Sparse Allreduce as PageRank; the
Rayleigh normalization is a scalar allreduce per iteration (negligible, done
through the same primitive with a single shared index so the schedule stays
on-network rather than through a driver).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from repro.core import SparseAllreduce
from .pagerank import build_partitions


def power_iteration(edges: np.ndarray, n_vertices: int, m: int,
                    degrees=(4, 2), iters: int = 30, symmetrize: bool = True,
                    backend: str = "sim", seed: int = 0, mesh=None
                    ) -> Tuple[float, np.ndarray, dict]:
    """Leading eigenvalue/eigenvector of the (symmetrized) adjacency matrix.

    Returns (eigenvalue, eigenvector [n], stats).

    ``backend="sim"`` (oracle): per-iteration numpy loop, driver-side
    Rayleigh normalization in float64.  ``backend="device"``: the graph
    engine fuses all ``iters`` matvec+reduce+normalize rounds into one
    jitted dispatch — the normalization runs as an ownership-weighted
    ``lax.psum`` inside the same shard_map step, so the whole power
    iteration stays on device; float32, tolerance-bounded vs the oracle;
    ``stats["engine"]`` carries the dispatch/sync report.
    """
    if symmetrize:
        edges = np.concatenate([edges, edges[:, ::-1]], axis=0)
    parts = build_partitions(edges, n_vertices, m, seed=seed)
    # adjacency matvec (unnormalized): weight 1 per edge
    for p in parts:
        p.inv_outdeg = np.ones_like(p.inv_outdeg)
    if backend == "device":
        return _power_iteration_device(parts, n_vertices, degrees, iters,
                                       seed, mesh)

    # one allreduce handles the matvec; scalar reductions ride along on a
    # reserved index (n_vertices) appended to every node's out/in sets.
    SCALAR = np.uint32(n_vertices)
    ar = SparseAllreduce(m, degrees, backend=backend, seed=seed)
    out_sets = [np.concatenate([p.out_idx, [SCALAR]]).astype(np.uint32)
                for p in parts]
    in_sets = [np.concatenate([p.in_idx, [SCALAR]]).astype(np.uint32)
               for p in parts]
    ar.config(out_sets, in_sets)

    rng = np.random.RandomState(seed)
    v = rng.randn(n_vertices)
    v /= np.linalg.norm(v)
    p_in = [v[p.in_idx] for p in parts]
    lam = 0.0
    for it in range(iters):
        outs = []
        for i, p in enumerate(parts):
            q = p.spmv(p_in[i])
            # local partial squared-norm of the partial product: nodes owning
            # disjoint EDGES may share rows, so the exact norm needs the
            # reduced vector; we reduce values first, norms second.
            outs.append(np.concatenate([q, [0.0]]))
        ins = ar.reduce(outs)
        # second pass: everyone now holds reduced q on its in-set; compute
        # partial norms over the *bottom-owned* disjoint ranges to avoid
        # double counting: approximate with driver norm on assembled vector.
        q_full = np.zeros(n_vertices)
        seen = np.zeros(n_vertices, bool)
        for i, p in enumerate(parts):
            vals = ins[i][:-1]
            put = ~seen[p.in_idx]
            q_full[p.in_idx[put]] = vals[put]
            seen[p.in_idx] = True
        nrm = np.linalg.norm(q_full)
        if nrm == 0:
            break
        lam = nrm  # Rayleigh estimate for symmetric A with unit v
        v = q_full / nrm
        p_in = [v[p.in_idx] for p in parts]
    return float(lam), v, {"iters": iters}


def _power_iteration_device(parts, n_vertices: int, degrees, iters: int,
                            seed: int, mesh
                            ) -> Tuple[float, np.ndarray, dict]:
    """Device path: matvec + reduce + Rayleigh normalization fused per
    round.  Each vertex of the in-set union is *owned* by the first node
    requesting it (host-precomputed 0/1 weights), so the squared-norm
    ``psum`` counts every vertex exactly once — the on-device analogue of
    the sim's driver-side dedup."""
    from . import engine as eng
    m = len(parts)

    def out_fn(s, e):
        return eng.ell_matvec(e, s["v"])

    def update_fn(s, in_raw, e, ax):
        import jax.numpy as jnp
        from jax import lax
        part = jnp.sum(e["norm_w"] * in_raw * in_raw)
        nrm = jnp.sqrt(lax.psum(part, ax))
        ok = nrm > 0
        v2 = jnp.where(ok, in_raw / jnp.maximum(nrm, 1e-30), s["v"])
        lam = jnp.where(ok, nrm, s["lam"][0]) * jnp.ones_like(s["lam"])
        return {"v": v2, "lam": lam}

    app = eng.EngineApp(name="spectral", out_fn=out_fn, update_fn=update_fn)
    engine = eng.GraphEngine(
        [p.out_idx.astype(np.uint32) for p in parts],
        [p.in_idx.astype(np.uint32) for p in parts],
        app, degrees=degrees, mesh=mesh, seed=seed)
    extras = eng.ell_extras([p.ell_coo() for p in parts], engine.u_cap)

    # ownership: vertex counted at the first node (in index order) whose
    # in-set requests it — mirrors the sim's first-writer-wins assembly
    norm_w = np.zeros((m, engine.uin_cap), np.float32)
    seen = np.zeros(n_vertices, bool)
    for i, p in enumerate(parts):
        own = ~seen[p.in_idx]
        norm_w[i, : len(p.in_idx)] = own
        seen[p.in_idx] = True

    rng = np.random.RandomState(seed)
    v = rng.randn(n_vertices)
    v /= np.linalg.norm(v)
    v0 = np.zeros((m, engine.uin_cap), np.float32)
    for i, p in enumerate(parts):
        v0[i, : len(p.in_idx)] = v[p.in_idx]
    state0 = {"v": v0, "lam": np.zeros((m, 1), np.float32)}
    final, _, _ = engine.run(iters, state0, dict(extras, norm_w=norm_w))
    v_dev = np.asarray(final["v"], np.float64)
    lam = float(np.asarray(final["lam"])[0, 0])

    v_full = np.zeros(n_vertices)
    seen[:] = False
    for i, p in enumerate(parts):
        own = ~seen[p.in_idx]
        v_full[p.in_idx[own]] = v_dev[i, : len(p.in_idx)][own]
        seen[p.in_idx] = True
    return lam, v_full, {"iters": iters, "engine": engine.sync_report()}


def power_iteration_reference(edges: np.ndarray, n_vertices: int,
                              iters: int = 30, symmetrize: bool = True,
                              seed: int = 0) -> Tuple[float, np.ndarray]:
    if symmetrize:
        edges = np.concatenate([edges, edges[:, ::-1]], axis=0)
    rng = np.random.RandomState(seed)
    v = rng.randn(n_vertices)
    v /= np.linalg.norm(v)
    lam = 0.0
    for _ in range(iters):
        q = np.zeros(n_vertices)
        np.add.at(q, edges[:, 1], v[edges[:, 0]])
        lam = np.linalg.norm(q)
        if lam == 0:
            break
        v = q / lam
    return float(lam), v
