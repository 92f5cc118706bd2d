"""Traffic generators, copied from the program so that its later changes
cannot move the yardstick.

* :func:`powerlaw_graph` — Chung-Lu power-law graph, as
  ``repro.data.pipeline.powerlaw_graph`` (same draws for the same seed).
* :func:`uniform_graph` — both endpoints uniform: no hubs.
* :func:`graph` — the graph a traffic file names by its ``law``.
* :func:`relabel` — the run's seed applied to a fixed graph: a random
  vertex permutation, so every seed does the same work on an isomorphic
  graph.
* :func:`zipf_ids` — Zipf ids over a vocabulary through one shared
  ranking, the inverse-CDF sampler of ``repro.data.pipeline.zipf_tokens``.
* :func:`draws_for_fraction` — how many such ids a node draws for its
  distinct ones to be a given share of the vocabulary.
* :class:`MixHash` — the paper's index hash: an odd-multiplier xor mix,
  a bijection of uint32 (as ``repro.core.sparse_vec.HashPerm``).
"""
from __future__ import annotations

import dataclasses

import numpy as np

SENTINEL = np.uint32(0xFFFFFFFF)


def rng_for(seed: int, stream: int) -> np.random.RandomState:
    """A numpy generator for one purpose (``stream``) of a run seed.  The
    seed may be any non-negative integer, wider than 32 bits too."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(1)
    return np.random.RandomState(int(words[0]))


def seed32(seed: int, stream: int) -> int:
    """A 31-bit integer seed for ``jax.random.key`` from a run seed."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(1)
    return int(words[0] >> 1)


def powerlaw_graph(n_vertices: int, n_edges: int, alpha: float = 2.0,
                   seed: int = 0) -> np.ndarray:
    """Edge list ``[E, 2]`` (src, dst) of a Chung-Lu graph whose degrees
    follow a power law with exponent ``alpha``; self-loops dropped."""
    rng = np.random.RandomState(seed)
    w = np.arange(1, n_vertices + 1, dtype=np.float64) ** (-1.0 / (alpha - 1))
    p = w / w.sum()
    src = rng.choice(n_vertices, size=n_edges, p=p).astype(np.int64)
    dst = rng.choice(n_vertices, size=n_edges, p=p).astype(np.int64)
    keep = src != dst
    edges = np.stack([src[keep], dst[keep]], axis=1)
    perm = rng.permutation(n_vertices).astype(np.int64)
    return perm[edges]


def uniform_graph(n_vertices: int, n_edges: int, seed: int = 0) -> np.ndarray:
    """Edge list ``[E, 2]`` with both endpoints uniform; self-loops
    dropped."""
    rng = np.random.RandomState(seed)
    src = rng.randint(0, n_vertices, size=n_edges).astype(np.int64)
    dst = rng.randint(0, n_vertices, size=n_edges).astype(np.int64)
    keep = src != dst
    return np.stack([src[keep], dst[keep]], axis=1)


def graph(traffic: dict) -> np.ndarray:
    """The fixed graph of a traffic file: ``law`` (``chung_lu`` with its
    ``alpha``, or ``uniform``), ``vertices``, ``edge_factor`` sampled edges
    a vertex, ``graph_seed``."""
    n = int(traffic["vertices"])
    e = int(traffic["edge_factor"]) * n
    seed = int(traffic["graph_seed"])
    if traffic["law"] == "chung_lu":
        return powerlaw_graph(n, e, alpha=float(traffic["alpha"]), seed=seed)
    if traffic["law"] == "uniform":
        return uniform_graph(n, e, seed=seed)
    raise ValueError(f"unknown graph law {traffic['law']!r}")


def relabel(edges: np.ndarray, n_vertices: int,
            rng: np.random.RandomState) -> np.ndarray:
    """The same graph under a random vertex permutation: degrees, row
    count and widest row are unchanged, every address moves.  The edges
    keep their order, which the generators draw at random already."""
    perm = rng.permutation(n_vertices).astype(np.int64)
    return perm[edges]


def zipf_ids(rng: np.random.RandomState, n: int, vocab: int, exponent: float,
             ranking: np.ndarray) -> np.ndarray:
    """``n`` ids in ``[0, vocab)``: rank r drawn with weight r^-exponent,
    then mapped through ``ranking`` (one permutation shared by every
    worker).  The endpoints of a Chung-Lu graph of degree exponent alpha
    are drawn so with ``exponent = 1 / (alpha - 1)``."""
    w = np.arange(1, vocab + 1, dtype=np.float64) ** (-exponent)
    cdf = np.cumsum(w) / np.sum(w)
    ranks = np.searchsorted(cdf, rng.random_sample(n))
    return ranking[np.minimum(ranks, vocab - 1)]


def draws_for_fraction(vocab: int, exponent: float, fraction: float) -> int:
    """The fewest draws of :func:`zipf_ids` whose expected number of
    distinct ids is ``fraction`` of ``vocab`` or more."""
    w = np.arange(1, vocab + 1, dtype=np.float64) ** (-exponent)
    q = w / w.sum()

    def distinct(t):
        return float(np.sum(-np.expm1(t * np.log1p(-q))))

    lo, hi = 1, 1
    while distinct(hi) < fraction * vocab:
        lo, hi = hi, hi * 2
    while lo < hi:
        mid = (lo + hi) // 2
        if distinct(mid) < fraction * vocab:
            lo = mid + 1
        else:
            hi = mid
    return lo


@dataclasses.dataclass(frozen=True)
class MixHash:
    """``h(i) = ((i ^ xor) * mult) mod 2^32`` with odd ``mult``."""
    mult: int
    xor: int

    @staticmethod
    def draw(rng: np.random.RandomState) -> "MixHash":
        mult = (int(rng.randint(0, 1 << 31)) * 2 + 1) * 2654435761 % (1 << 32)
        return MixHash(mult=mult | 1, xor=int(rng.randint(0, 1 << 31)))

    def __call__(self, ids: np.ndarray) -> np.ndarray:
        i = ids.astype(np.uint64)
        out = ((i ^ np.uint64(self.xor)) * np.uint64(self.mult)) % (1 << 32)
        return out.astype(np.uint32)
