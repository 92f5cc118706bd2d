"""The copied generators give what the program's gave, and the traffic
files' statistics."""
import json
import os

import numpy as np

from perfbench import gen
from perfbench.tests.conftest import ROOT


def _traffic(name):
    with open(os.path.join(ROOT, "perfbench", "traffic", name + ".json")) as f:
        return json.load(f)


def test_chung_lu_smoke_graph_sizes():
    """n, E, R, K of the one-chip graph, seed 0: the smoke run's graph."""
    n = 31488
    edges = gen.graph(_traffic("powerlaw22"))
    dst = edges[:, 1]
    assert len(edges) == 502492
    assert len(np.unique(dst)) == 31189
    assert int(np.bincount(dst, minlength=n).max()) == 16975


def test_relabel_keeps_the_work():
    n = 4096
    edges = gen.powerlaw_graph(n, 16 * n, alpha=2.2, seed=0)
    a = gen.relabel(edges, n, gen.rng_for(2 ** 31 + 11, 1))
    b = gen.relabel(edges, n, gen.rng_for(2 ** 31 + 11, 1))
    c = gen.relabel(edges, n, gen.rng_for(7, 1))
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    for e in (a, c):
        assert len(e) == len(edges)
        assert len(np.unique(e[:, 1])) == len(np.unique(edges[:, 1]))
        assert np.bincount(e[:, 1]).max() == np.bincount(edges[:, 1]).max()


def test_zipf_ids_and_hash():
    rng = np.random.RandomState(0)
    ranking = rng.permutation(1000)
    ids = gen.zipf_ids(rng, 50000, 1000, 1.0, ranking)
    assert ids.min() >= 0 and ids.max() < 1000
    counts = np.bincount(ids, minlength=1000)
    assert counts[ranking[0]] == counts.max()      # rank 1 is the mode
    mix = gen.MixHash.draw(rng)
    h = mix(np.arange(1 << 16, dtype=np.uint32))
    assert len(np.unique(h)) == 1 << 16             # a bijection


def test_uniform_graph_has_no_hubs():
    n = 1 << 14
    t = dict(_traffic("uniform22"), vertices=n)
    edges = gen.graph(t)
    assert len(edges) > 16 * n - 64 and not np.any(edges[:, 0] == edges[:, 1])
    counts = np.bincount(edges[:, 1], minlength=n)
    assert counts.max() < 3 * 16


def test_union_traffic_holds_the_node_fraction():
    """Per worker, the distinct ids are the traffic's per-node fraction of
    the vocabulary, and never more than its capacity."""
    t, vocab = _traffic("table1_twitter"), 151936
    exponent = 1.0 / (t["alpha"] - 1.0)
    draws = gen.draws_for_fraction(vocab, exponent, t["node_fraction"])
    rng = gen.rng_for(2 ** 31 + 3, 1)
    ranking = rng.permutation(vocab)
    counts = [len(np.unique(gen.zipf_ids(rng, draws, vocab, exponent,
                                         ranking))) for _ in range(12)]
    assert abs(np.mean(counts) / vocab - t["node_fraction"]) < 0.002
    assert max(counts) < t["capacity"] and 4 * t["capacity"] == t["out_capacity"]
