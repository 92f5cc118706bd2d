"""A whole run of each cell on the CPU at a small size, past the harness's
look for a chip: sound, it comes out correct; with the timed path broken
underneath, not correct.  The controls fail their limits."""
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness
from perfbench.tests.conftest import ROOT

PAGERANK = "pagerank.powerlaw22.1chip"
UNIFORM = "pagerank.uniform22.1chip"
UNION = "union.table1_twitter.4chip"

SMALL = {
    PAGERANK: ({}, {"vertices": 2048}),
    UNIFORM: ({}, {"vertices": 4096}),
    UNION: ({"hidden_size": 64},
            {"node_fraction": 0.01, "capacity": 2048,
             "out_capacity": 8192, "pool_steps": 2, "checked_calls": 2}),
}


@pytest.fixture
def small(monkeypatch):
    """Cells at test sizes: same files, smaller numbers."""
    load = harness.load_cell

    def small_cell(bench, name, root=harness.ROOT):
        cell, config, traffic = load(bench, name, root)
        cfg, trf = SMALL[name]
        return cell, dict(config, **cfg), dict(traffic, **trf)

    monkeypatch.setattr(harness, "load_cell", small_cell)


def run(cell, seed=2 ** 31 + 7, trace=False):
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    peaks = {"hbm_bytes_per_s": 819e9, "ici_bytes_per_s": 200e9}
    return harness.execute(bench, cell, seed, 0.5, trace, jax.devices(),
                           peaks, time.time(), log=lambda s: None)


@pytest.mark.parametrize("cell", [PAGERANK, UNIFORM, UNION])
def test_sound_run_is_correct(small, cell):
    res = run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    want = {m["name"] for m in harness.cell_metrics(bench, cell, "end_to_end")}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"


def _engine_fault(kind):
    from repro.graph.engine import GraphEngine
    orig = GraphEngine.run

    def run(self, k, state, extras=None, **kw):
        final, last, traj = orig(self, k, state, extras, **kw)
        if kind == "state_unchanged":
            final = state
        elif kind == "answer_altered":
            final = final.at[0, 3].multiply(1.001)
        return final, last, traj
    return GraphEngine, "run", run


class _NoExchangeLax:
    """``jax.lax`` with the butterfly's exchanges left out: all_to_all
    keeps this chip's buckets, all_gather repeats this chip's chunk."""

    def __getattr__(self, name):
        return getattr(jax.lax, name)

    @staticmethod
    def all_to_all(x, *a, **kw):
        return x

    @staticmethod
    def all_gather(x, axis_name, axis_index_groups=None, axis=0, tiled=False):
        k = len(axis_index_groups[0])
        return jnp.concatenate([x] * k, axis=0)


def _union_fault(kind):
    from repro.core.api import SparseAllreduce
    orig = SparseAllreduce.union_reduce

    def union_reduce(self, idx, val, out_capacity, **kw):
        if kind == "half_left_out":
            m = idx.shape[0]
            keep = (jnp.arange(m) < m // 2)[:, None]
            idx = jnp.where(keep, idx, jnp.uint32(0xFFFFFFFF))
            val = jnp.where(keep[..., None], val, 0.0)
        oi, ov, ovf = orig(self, idx, val, out_capacity, **kw)
        if kind == "state_unchanged":
            pad = out_capacity - idx.shape[1]
            oi = jnp.pad(idx, ((0, 0), (0, pad)),
                         constant_values=jnp.uint32(0xFFFFFFFF))
            ov = jnp.pad(val, ((0, 0), (0, pad), (0, 0)))
        elif kind == "answer_altered":
            ov = ov.at[1, 5, 0].multiply(1.001)
        return oi, ov, ovf
    return SparseAllreduce, "union_reduce", union_reduce


@pytest.mark.parametrize("cell,fault", [
    (PAGERANK, "state_unchanged"), (PAGERANK, "answer_altered"),
    (UNION, "state_unchanged"), (UNION, "answer_altered"),
    (UNION, "half_left_out"), (UNION, "exchange_left_out")])
def test_broken_path_is_not_correct(small, monkeypatch, cell, fault):
    if fault == "exchange_left_out":
        from repro.core import allreduce
        monkeypatch.setattr(allreduce, "lax", _NoExchangeLax())
    elif cell == PAGERANK:
        monkeypatch.setattr(*_engine_fault(fault))
    else:
        monkeypatch.setattr(*_union_fault(fault))
    res = run(cell)
    assert not res["correct"], res["checks"]
    assert res["failed"] >= 1


@pytest.mark.parametrize("cell,units", [(PAGERANK, 30), (UNIFORM, 30),
                                        (UNION, 2)])
def test_control_fails_its_limit(small, cell, units):
    """The reference in bfloat16 in the program's place, judged by the
    program's own comparison, fails its limit, as it does on the chip at
    the cell's own size."""
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    _, config, traffic = harness.load_cell(bench, cell)
    drv = harness.driver_class(config)(config, traffic, 5, jax.devices()[:4])
    checks = drv.control(units)
    assert not all(c.ok for c in checks), checks
    assert drv.failed >= 1
    first = checks[0]
    assert first.value > 3 * first.limit if first.limit else first.value > 1e-3


def test_metric_split_by_cell_shares_its_reader():
    ctx = harness.Context(facts={"window_s": 2.0}, peaks={}, setup={},
                          trace={"busy_s": 1.5})
    for name in ("idle_share.pagerank", "idle_share.union", "idle_share"):
        assert harness.reader(name)(ctx) == pytest.approx(25.0)


def test_run_refuses_without_a_chip():
    """On the CPU the command exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", PAGERANK,
         "--seed", "1", "--seconds", "1"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_run_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", PAGERANK,
         "--seed", "1", "--seconds", "1"], cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_numbers_compared_are_printed_last(small, capsys):
    res = run(PAGERANK)
    assert set(res["checks"]) == {"max_rel_err", "layout_mismatch",
                                  "retraces", "window_compiles"}
    for c in res["checks"].values():
        assert np.isfinite(c["value"]) and np.isfinite(c["limit"])
