"""Distributed PageRank on Sparse Allreduce (paper §I-A.2, §III-B, Fig 9).

Faithful to the paper's workflow: random edge partition across M nodes; each
node's outbound set = rows its edges write, inbound set = columns its edges
read; ``config`` once (static graph), then per iteration
``in.values = reduce(out.values)`` + local SpMV.

The local SpMV runs in numpy (simulator backend) or as the device engine's
ELL gather-sum (``backend="device"``).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from repro import obs
from repro.core import SparseAllreduce
from repro.core.netmodel import EC2_2013, Fabric
from repro.data.pipeline import random_edge_partition


@dataclasses.dataclass
class Partition:
    """One node's share of the edge-partitioned graph."""
    src: np.ndarray           # [E_i] global column ids (reads)
    dst: np.ndarray           # [E_i] global row ids (writes)
    in_idx: np.ndarray        # unique sorted src
    out_idx: np.ndarray       # unique sorted dst
    src_pos: np.ndarray       # src -> position in in_idx
    dst_pos: np.ndarray       # dst -> position in out_idx
    inv_outdeg: np.ndarray    # [E_i] 1/outdeg of src (column-normalized G)

    def spmv(self, in_values: np.ndarray) -> np.ndarray:
        """out[dst_pos] += in[src_pos] / outdeg(src)."""
        out = np.zeros(len(self.out_idx), np.float64)
        np.add.at(out, self.dst_pos, in_values[self.src_pos] * self.inv_outdeg)
        return out

    def ell_coo(self, weights: Optional[np.ndarray] = None
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """``(rows, cols, weights)`` of this partition's SpMV: edge ``e``
        adds ``in[src_pos[e]] * w[e]`` to row ``dst_pos[e]`` — what the
        engine's ELL tables (``engine.ell_extras``) are built from."""
        w = self.inv_outdeg if weights is None else weights
        return self.dst_pos, self.src_pos, w

    def ell_tables(self, weights: Optional[np.ndarray] = None
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """Padded ELL ``(cols, wts)`` of this partition's SpMV — vectorized
        (``engine.build_ell``: bincount/argsort, no per-edge Python loop);
        one node's rows padded to its widest, where the engine's tables
        (``engine.ell_extras``) pad each degree class to its own."""
        from .engine import build_ell
        return build_ell(*self.ell_coo(weights), len(self.out_idx))


def build_partitions(edges: np.ndarray, n_vertices: int, m: int,
                     seed: int = 0) -> List[Partition]:
    with obs.span("repro.graph.build_partitions"):
        outdeg = np.bincount(edges[:, 0],
                             minlength=n_vertices).astype(np.float64)
        outdeg[outdeg == 0] = 1.0
        parts = []
        for e in random_edge_partition(edges, m, seed=seed):
            src, dst = e[:, 0], e[:, 1]
            in_idx = np.unique(src)
            out_idx = np.unique(dst)
            parts.append(Partition(
                src=src, dst=dst, in_idx=in_idx, out_idx=out_idx,
                src_pos=np.searchsorted(in_idx, src),
                dst_pos=np.searchsorted(out_idx, dst),
                inv_outdeg=1.0 / outdeg[src]))
        return parts


def pagerank(edges: np.ndarray, n_vertices: int, m: int,
             degrees=(4, 2), iters: int = 10, damping: float = 0.85,
             backend: str = "sim", fabric: Fabric = EC2_2013,
             seed: int = 0, mesh=None
             ) -> Tuple[np.ndarray, dict]:
    """Returns (scores [n_vertices], stats).  Unreached vertices keep the
    teleport mass only.

    ``backend="sim"`` (oracle): per-iteration numpy loop through the
    message-level simulator — float64, runs anywhere.
    ``backend="device"``: the device-resident iterative engine
    (``repro.graph.engine``) — all ``iters`` rounds of SpMV + planned
    reduce fused into ONE jitted dispatch on a mesh of ``m`` devices
    (``mesh`` or the process defaults); float32, tolerance-bounded against
    the sim oracle.  ``stats["engine"]`` carries the dispatch/sync report.
    """
    parts = build_partitions(edges, n_vertices, m, seed=seed)
    if backend == "device":
        return _pagerank_device(parts, n_vertices, degrees, iters, damping,
                                seed, fabric, mesh)
    ar = SparseAllreduce(m, degrees, backend=backend, fabric=fabric,
                         seed=seed)
    cstats = ar.config([p.out_idx.astype(np.uint32) for p in parts],
                       [p.in_idx.astype(np.uint32) for p in parts])

    # iterate: node i holds P over its in_idx; outbound values are the
    # *partial products* q_i (no teleport — the receiver applies
    # P = (1-d)/n + d * sum(q) after the reduce, so teleport counts once).
    p_in = [np.full(len(p.in_idx), 1.0 / n_vertices) for p in parts]
    q_partial = [np.zeros(len(p.out_idx)) for p in parts]
    reduce_time = 0.0
    for it in range(iters):
        for i, p in enumerate(parts):
            q_partial[i] = p.spmv(p_in[i])
        in_raw = ar.reduce(q_partial)
        if ar.stats is not None:
            reduce_time += ar.stats.reduce_time_s
        for i in range(m):
            p_in[i] = (1 - damping) / n_vertices + damping * in_raw[i]

    # assemble final scores from the last partials (teleport added once)
    qsum = np.zeros(n_vertices)
    for i, p in enumerate(parts):
        np.add.at(qsum, p.out_idx, q_partial[i])
    scores = (1 - damping) / n_vertices + damping * qsum
    stats = {"config": cstats, "reduce_time_s": reduce_time}
    return scores, stats


def make_pagerank_app(parts: List[Partition], n_vertices: int,
                      damping: float = 0.85):
    """The engine-agnostic PageRank pieces: ``(app, out_sets, in_sets)``.

    Shared by :func:`make_pagerank_engine` and the supervised loop
    (``repro.resilience.engine.SupervisedEngineLoop``), which owns its own
    engine construction / remapping and only needs the per-round app."""
    from . import engine as eng
    app = eng.EngineApp(
        name="pagerank",
        out_fn=lambda s, e: eng.ell_matvec(e, s),
        update_fn=lambda s, in_raw, e, ax:
            (1.0 - damping) / n_vertices + damping * in_raw)
    return (app,
            [p.out_idx.astype(np.uint32) for p in parts],
            [p.in_idx.astype(np.uint32) for p in parts])


def pagerank_state(parts: List[Partition], n_vertices: int,
                   u_cap: int, uin_cap: int):
    """ELL extras (``engine.ell_extras``) + the uniform initial state for
    a PageRank run over ``parts``, sized to an engine's frozen ``u_cap`` /
    ``uin_cap``."""
    from . import engine as eng
    with obs.span("repro.graph.ell_tables"):
        extras = eng.ell_extras([p.ell_coo() for p in parts], u_cap)
        p0 = np.zeros((len(parts), uin_cap), np.float32)
        for i, p in enumerate(parts):
            p0[i, : len(p.in_idx)] = 1.0 / n_vertices
        return extras, p0


def make_pagerank_engine(parts: List[Partition], n_vertices: int,
                         degrees=(4, 2), damping: float = 0.85,
                         seed: int = 0,
                         fabric: Fabric = EC2_2013, mesh=None):
    """Build the device-resident PageRank engine (config once, reuse per
    ``run``): returns ``(engine, extras, p0)`` — everything
    ``engine.run(k, p0, extras)`` needs.  Shared by
    ``pagerank(backend="device")`` and the fig8/fig9 benchmarks."""
    from . import engine as eng
    app, out_sets, in_sets = make_pagerank_app(parts, n_vertices, damping)
    engine = eng.GraphEngine(out_sets, in_sets, app, degrees=degrees,
                             mesh=mesh, seed=seed, fabric=fabric)
    extras, p0 = pagerank_state(parts, n_vertices, engine.u_cap,
                                engine.uin_cap)
    return engine, extras, p0


def assemble_pagerank_scores(parts: List[Partition], last_q: np.ndarray,
                             n_vertices: int, damping: float) -> np.ndarray:
    """Global scores from the engine's final partial products ``last_q``
    ``[M, u_cap]`` (teleport added once, same as the sim loop's
    assembly)."""
    last_q = np.asarray(last_q, np.float64)
    qsum = np.zeros(n_vertices)
    for i, p in enumerate(parts):
        np.add.at(qsum, p.out_idx, last_q[i, : len(p.out_idx)])
    return (1 - damping) / n_vertices + damping * qsum


def _pagerank_device(parts: List[Partition], n_vertices: int, degrees,
                     iters: int, damping: float,
                     seed: int, fabric: Fabric, mesh
                     ) -> Tuple[np.ndarray, dict]:
    """Device path: k PageRank rounds in one dispatch (graph engine)."""
    engine, extras, p0 = make_pagerank_engine(
        parts, n_vertices, degrees, damping, seed, fabric, mesh)
    _, last_q, _ = engine.run(iters, p0, extras)
    scores = assemble_pagerank_scores(parts, last_q, n_vertices, damping)
    stats = {"config": engine.config_stats, "reduce_time_s": 0.0,
             "engine": engine.sync_report()}
    return scores, stats


def pagerank_dense_reference(edges: np.ndarray, n_vertices: int,
                             iters: int = 10, damping: float = 0.85
                             ) -> np.ndarray:
    outdeg = np.bincount(edges[:, 0], minlength=n_vertices).astype(np.float64)
    outdeg[outdeg == 0] = 1.0
    p = np.full(n_vertices, 1.0 / n_vertices)
    for _ in range(iters):
        q = np.zeros(n_vertices)
        np.add.at(q, edges[:, 1], p[edges[:, 0]] / outdeg[edges[:, 0]])
        p = (1 - damping) / n_vertices + damping * q
    return p
