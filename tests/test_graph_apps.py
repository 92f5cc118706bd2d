"""Graph applications vs exact references (the paper's workloads)."""
import numpy as np
import pytest

from repro.data.pipeline import powerlaw_graph, random_edge_partition
from repro.graph.hadi import hadi, hadi_bitstring_reference
from repro.graph.pagerank import pagerank, pagerank_dense_reference
from repro.graph.spectral import power_iteration, power_iteration_reference


@pytest.fixture(scope="module")
def graph():
    return powerlaw_graph(500, 3000, seed=1), 500


@pytest.mark.parametrize("degrees", [(4, 2), (8,), (2, 2, 2)])
def test_pagerank_matches_dense(graph, degrees):
    edges, n = graph
    ref = pagerank_dense_reference(edges, n, iters=10)
    got, stats = pagerank(edges, n, m=8, degrees=degrees, iters=10)
    np.testing.assert_allclose(got, ref, rtol=1e-8, atol=1e-12)
    assert stats["reduce_time_s"] > 0


def test_pagerank_scores_positive_mass_conserved(graph):
    """Positive scores; total mass equals the dense formulation's (dangling
    vertices leak teleport mass in the simple iteration — both sides match)."""
    edges, n = graph
    got, _ = pagerank(edges, n, m=8, iters=30)
    ref = pagerank_dense_reference(edges, n, iters=30)
    assert got.min() > 0
    assert got.sum() == pytest.approx(ref.sum(), rel=1e-9)
    assert 0.5 < got.sum() <= 1.0 + 1e-9


def test_hadi_bitstrings_exact(graph):
    edges, n = graph
    eff, curve, st = hadi(edges, n, m=8, max_hops=6, trials=4, bits=20)
    ref = hadi_bitstring_reference(edges, n, st["b0"].reshape(n, -1),
                                   st["hops_run"])
    np.testing.assert_array_equal(st["b_final"].reshape(n, -1), ref)
    assert 1 <= eff <= st["hops_run"]
    assert np.all(np.diff(curve) >= -1e-9)   # monotone growth


def test_power_iteration_matches_reference(graph):
    edges, n = graph
    lam, v, _ = power_iteration(edges, n, m=8, iters=25, seed=2)
    lam_ref, v_ref = power_iteration_reference(edges, n, iters=25, seed=2)
    assert lam == pytest.approx(lam_ref, rel=1e-6)
    cos = abs(np.dot(v, v_ref)) / (np.linalg.norm(v) * np.linalg.norm(v_ref))
    assert cos > 1 - 1e-8


def test_random_edge_partition_covers(graph):
    edges, n = graph
    parts = random_edge_partition(edges, 8, seed=0)
    assert sum(len(p) for p in parts) == len(edges)
    got = np.sort(np.concatenate(parts).view(np.int64).reshape(-1, 2), axis=0)
    want = np.sort(edges, axis=0)
    np.testing.assert_array_equal(
        np.sort(np.concatenate(parts), axis=0), np.sort(edges, axis=0))


def test_partition_sparsity_table1():
    """Table I analogue: per-partition vertex fraction shrinks with M."""
    edges = powerlaw_graph(20000, 200000, seed=3)
    for m, max_frac in [(8, 0.8), (64, 0.35)]:
        parts = random_edge_partition(edges, m, seed=0)
        fracs = [len(np.unique(p)) / 20000 for p in parts]
        assert np.mean(fracs) < max_frac
