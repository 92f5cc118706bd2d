"""HADI diameter estimation over Sparse Allreduce (paper §I-A.2, eq. 3).

HADI iterates b^{h+1} = G x_or b^h with Flajolet-Martin bitstrings.  Our
allreduce is additive; OR transfers exactly because the bitstrings are 0/1
vectors: OR(a, b) = min(a + b, 1) — sum through the network, clamp at the
receiver.  (This is the documented adaptation of eq. 3's x_or operator.)

Neighbourhood-size estimate per FM: N(h) ~ 2^{b(h)} / 0.77351 with b the
average lowest-zero-bit position; effective diameter = smallest h with
N(h) >= 0.9 * N(h_max).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from repro.core import SparseAllreduce
from .pagerank import build_partitions

FM_PHI = 0.77351


def fm_bitstrings(n: int, bits: int, trials: int, rng) -> np.ndarray:
    """[n, trials, bits] 0/1 — bit i set with prob 2^-(i+1)."""
    probs = 2.0 ** (-(np.arange(bits) + 1.0))
    return (rng.random_sample((n, trials, bits)) < probs).astype(np.float64)


def _fm_estimate(b: np.ndarray) -> float:
    """b: [n, trials, bits] union bitstrings -> neighbourhood size sum."""
    zero = b < 0.5
    # lowest zero bit per (vertex, trial)
    low = np.argmax(zero, axis=-1)
    low = np.where(zero.any(axis=-1), low, b.shape[-1])
    return float(np.sum(2.0 ** np.mean(low, axis=-1) / FM_PHI))


def hadi(edges: np.ndarray, n_vertices: int, m: int, degrees=(4, 2),
         max_hops: int = 16, bits: int = 24, trials: int = 4,
         backend: str = "sim", seed: int = 0, mesh=None
         ) -> Tuple[int, np.ndarray, dict]:
    """Returns (effective diameter, N(h) curve, stats).

    ``backend="sim"`` (oracle): per-hop numpy loop through the simulator.
    ``backend="device"``: the iterative graph engine fuses all
    ``max_hops`` OR-rounds into one jitted dispatch (per-hop bitstrings
    collected on device, early-stop applied post-hoc on the host curve —
    bit-identical to the sim because the 0/1 sums are exact in fp32);
    ``stats["engine"]`` carries the dispatch/sync report.
    """
    rng = np.random.RandomState(seed)
    parts = build_partitions(edges, n_vertices, m, seed=seed)
    # inbound = read-set for the next hop PLUS own written rows, so every
    # vertex with in-edges receives its updated bitstring somewhere
    req = [np.union1d(p.in_idx, p.out_idx).astype(np.uint32) for p in parts]
    if backend == "device":
        return _hadi_device(parts, req, n_vertices, degrees, max_hops,
                            bits, trials, rng, seed, mesh)
    ar = SparseAllreduce(m, degrees, backend=backend, seed=seed,
                         value_width=trials * bits)
    ar.config([p.out_idx.astype(np.uint32) for p in parts], req)

    b = fm_bitstrings(n_vertices, bits, trials, rng)  # global (self-bit)
    b0 = b.copy()
    curve = [_fm_estimate(b)]
    for h in range(max_hops):
        # out value of a row v = OR over partition edges of b[src]
        outs = []
        for p in parts:
            acc = np.zeros((len(p.out_idx), trials, bits))
            np.add.at(acc, p.dst_pos, b[p.src])
            outs.append(np.minimum(acc, 1.0).reshape(len(p.out_idx), -1))
        ins = ar.reduce(outs)
        newb = b.copy()
        for i, p in enumerate(parts):
            ridx = np.union1d(p.in_idx, p.out_idx)
            got = np.minimum(ins[i], 1.0).reshape(-1, trials, bits)
            newb[ridx] = np.maximum(newb[ridx], got)
        # vertices also OR their own previous bits (self-loop in HADI)
        b = np.maximum(b, newb)
        est = _fm_estimate(b)
        curve.append(est)
        if est <= curve[-2] * 1.0001:
            break
    curve = np.array(curve)
    target = 0.9 * curve[-1]
    eff = int(np.argmax(curve >= target))
    return eff, curve, {"hops_run": len(curve) - 1, "b0": b0, "b_final": b}


def _hadi_device(parts, req, n_vertices: int, degrees, max_hops: int,
                 bits: int, trials: int, rng, seed: int, mesh
                 ) -> Tuple[int, np.ndarray, dict]:
    """Device path: all hops in one dispatch, early stop applied post-hoc.

    Per-node state = bitstrings over the node's request set (OR transfers
    through the additive reduce as sum + clamp; 0/1 sums are exact in
    fp32, so per-hop strings are bit-identical to the sim oracle).  The
    scan collects every hop's state (``collect="trajectory"``); the host
    then assembles the global per-hop strings and applies the same
    plateau early-stop the sim loop uses, truncating the curve.
    """
    from . import engine as eng
    m, w = len(parts), trials * bits

    def out_fn(s, e):
        acc = eng.ell_matvec(e, s)
        import jax.numpy as jnp
        return jnp.minimum(acc, 1.0)

    def update_fn(s, in_raw, e, ax):
        import jax.numpy as jnp
        return jnp.maximum(s, jnp.minimum(in_raw, 1.0))

    app = eng.EngineApp(name="hadi", out_fn=out_fn, update_fn=update_fn,
                        value_width=w)
    engine = eng.GraphEngine(
        [p.out_idx.astype(np.uint32) for p in parts], req, app,
        degrees=degrees, mesh=mesh, seed=seed)
    # edge (src, dst) contributes b[src] to row dst: cols = src position in
    # the request set, rows = dst position in out_idx, weight 1 (OR)
    extras = eng.ell_extras(
        [(p.dst_pos, np.searchsorted(req[i], p.src),
          np.ones(len(p.src), np.float32)) for i, p in enumerate(parts)],
        engine.u_cap)

    b0 = fm_bitstrings(n_vertices, bits, trials, rng)
    state0 = np.zeros((m, engine.uin_cap, w), np.float32)
    for i, r in enumerate(req):
        state0[i, : len(r)] = b0[r].reshape(len(r), w)
    _, _, traj = engine.run(max_hops, state0, extras, collect="trajectory")
    traj = np.asarray(traj, np.float64)           # [hops, M, req_cap, w]

    b = b0.copy()
    curve = [_fm_estimate(b)]
    for h in range(max_hops):
        for i, r in enumerate(req):
            b[r] = np.maximum(b[r],
                              traj[h, i, : len(r)].reshape(len(r), trials,
                                                           bits))
        est = _fm_estimate(b)
        curve.append(est)
        if est <= curve[-2] * 1.0001:
            break
    curve = np.array(curve)
    target = 0.9 * curve[-1]
    eff = int(np.argmax(curve >= target))
    return eff, curve, {"hops_run": len(curve) - 1, "b0": b0, "b_final": b,
                        "engine": engine.sync_report()}


def hadi_bitstring_reference(edges: np.ndarray, n_vertices: int,
                             b0: np.ndarray, hops: int) -> np.ndarray:
    """Deterministic oracle: global OR-iteration of the same bitstrings.
    Distributed HADI must produce bit-identical strings after each hop."""
    b = b0.copy()
    for _ in range(hops):
        new = b.copy()
        acc = np.zeros_like(b)
        np.add.at(acc, edges[:, 1], b[edges[:, 0]])
        new = np.maximum(new, np.minimum(acc, 1.0))
        b = np.maximum(b, new)
    return b


def bfs_neighbourhood_reference(edges: np.ndarray, n_vertices: int,
                                max_hops: int) -> np.ndarray:
    """Exact N(h) = total pairs within h hops (small graphs; oracle)."""
    radj = [[] for _ in range(n_vertices)]   # in-neighbours: b[d] |= b[s]
    for s, d in edges:
        radj[d].append(s)
    curve = [n_vertices]
    reach = [1 << v for v in range(n_vertices)]  # bitset per vertex
    for h in range(max_hops):
        new = list(reach)
        for v in range(n_vertices):
            acc = reach[v]
            for u in radj[v]:
                acc |= reach[u]
            new[v] = acc
        reach = new
        curve.append(sum(bin(r).count("1") for r in reach))
        if curve[-1] == curve[-2]:
            break
    return np.array(curve, np.float64)
