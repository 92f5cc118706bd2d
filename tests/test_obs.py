"""The program's spans, scopes and counters (``repro.obs``).

In-process: host spans off and on, the engine's scopes in its compiled
program and its spans, the union plan's ``slots_received``.  Subprocess (4
forced host devices): the union program's scopes in its compiled text, its
spans and the counter over calls.
"""
import ast
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from repro import obs

_ENV = dict(os.environ,
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
            PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src")
            + os.pathsep + os.environ.get("PYTHONPATH", ""))


@pytest.fixture
def spans_on():
    obs.reset()
    obs.enable()
    try:
        yield
    finally:
        obs.enable(False)
        obs.reset()


def test_span_off_records_nothing():
    assert obs.span("repro.a") is obs.span("repro.b")
    with obs.span("repro.a"):
        with obs.span("repro.b"):
            pass
    assert obs.spans() == []


def test_spans_nest_with_their_parent(spans_on):
    with obs.span("repro.outer"):
        with obs.span("repro.inner"):
            pass
        with obs.span("repro.inner"):
            pass
    with obs.span("repro.next"):
        pass
    got = [(s.name, s.parent) for s in obs.spans()]
    assert got == [("repro.inner", "repro.outer"),
                   ("repro.inner", "repro.outer"),
                   ("repro.outer", None), ("repro.next", None)]
    outer = obs.spans()[2]
    assert all(outer.start_ns <= s.start_ns <= s.end_ns <= outer.end_ns
               for s in obs.spans()[:2])
    obs.reset()
    assert obs.spans() == []


def test_span_closes_on_error(spans_on):
    with pytest.raises(ValueError):
        with obs.span("repro.fails"):
            raise ValueError("x")
    with obs.span("repro.after"):
        pass
    assert [(s.name, s.parent) for s in obs.spans()] == [
        ("repro.fails", None), ("repro.after", None)]


@pytest.mark.parametrize("degrees,cap,out_cap,want", [
    # the four-chip benchmark cell: 32,768 + 65,536 down, 65,536 + 131,072 up
    ((2, 2), 32768, 131072, 294912),
    ((4,), 64, 512, 3 * 32 + 3 * 128),
    ((), 64, 512, 0)])
def test_slots_received_from_the_plan(degrees, cap, out_cap, want):
    from repro.core.allreduce import make_device_plan
    m = int(np.prod(degrees)) if degrees else 1
    plan = make_device_plan([("d", m)], {"d": degrees}, in_capacity=cap,
                            out_capacity=out_cap)
    assert plan.slots_received == want


def _op_names(hlo_text):
    return re.findall(r'op_name="([^"]*)"', hlo_text)


def _engine(overlap):
    from repro.data.pipeline import powerlaw_graph
    from repro.graph.pagerank import build_partitions, make_pagerank_engine
    edges = powerlaw_graph(256, 1500, alpha=2.2, seed=3)
    parts = build_partitions(edges, 256, 1, seed=0)
    engine, extras, p0 = make_pagerank_engine(parts, 256, degrees=())
    engine.overlap = overlap
    return engine, extras, p0


@pytest.mark.parametrize("k,overlap", [(1, False), (3, True)])
def test_engine_program_carries_its_scopes(k, overlap):
    import jax
    engine, extras, p0 = _engine(overlap)
    p0, extras = jax.device_put((p0, extras))
    text = engine.run_fn(k).lower(p0, extras,
                                  *engine.routing_args()).compile().as_text()
    names = _op_names(text)
    for scope in ("engine/out", "engine/reduce", "engine/update",
                  "engine/out/ell_matvec"):
        assert any(f"/{scope}/" in n for n in names), scope


def test_engine_spans(spans_on):
    engine, extras, p0 = _engine(False)
    got = [(s.name, s.parent) for s in obs.spans()]
    assert ("repro.graph.build_partitions", None) in got
    assert ("repro.engine.config", None) in got
    assert ("repro.graph.ell_tables", None) in got
    obs.reset()
    engine.run(1, p0, extras)[0].block_until_ready()
    engine.run(1, p0, extras)[0].block_until_ready()
    assert [(s.name, s.parent) for s in obs.spans()] == [
        ("repro.engine.stage", "repro.engine.run"),
        ("repro.engine.launch", "repro.engine.run"),
        ("repro.engine.run", None)] * 2
    assert engine.report["dispatches"] == 2


UNION_CODE = r"""
import re
import numpy as np, jax
from repro import obs
from repro.core import SparseAllreduce
from repro.launch.mesh import make_mesh

M, C, W, OUT = 4, 64, 8, 256
rng = np.random.RandomState(0)
idx = np.full((M, C), 0xFFFFFFFF, np.uint32)
for n in range(M):
    rows = np.unique(rng.randint(0, 1 << 30, 20)).astype(np.uint32)
    idx[n, :len(rows)] = rows
val = rng.randn(M, C, W).astype(np.float32)
ar = SparseAllreduce(M, (2, 2), backend="device",
                     mesh=make_mesh((M,), ("nodes",)), plan_cache=False)
obs.enable()
for _ in range(3):
    jax.block_until_ready(ar.union_reduce(idx, val, OUT))
print("SPANS", [(s.name, s.parent) for s in obs.spans()])
print("STATS", ar.union_plan_stats)
obs.enable(False)
fn = ar.union_fn(idx, val, OUT)
text = fn.lower(idx, val).compile().as_text()
print("MODULE", text.splitlines()[0].split(",")[0])
names = re.findall(r'op_name="([^"]*)"', text)
print("SCOPES", sorted({m for n in names for m in
                        re.findall(r"union/(?:down|up)\d/\w+|union/trim", n)}))
print("STATS2", ar.union_plan_stats)
"""


def test_union_program_scopes_spans_and_counter():
    r = subprocess.run([sys.executable, "-c", UNION_CODE], env=_ENV,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    out = {line.split(" ", 1)[0]: line.split(" ", 1)[1]
           for line in r.stdout.splitlines() if " " in line}
    assert out["MODULE"] == "HloModule jit_union_allreduce"
    assert ast.literal_eval(out["SCOPES"]) == [
        "union/down0/bucket", "union/down0/exchange", "union/down0/merge",
        "union/down1/bucket", "union/down1/exchange", "union/down1/merge",
        "union/trim", "union/up0/gather", "union/up1/gather"]
    call = [("repro.union_reduce.launch", "repro.union_reduce"),
            ("repro.union_reduce", None)]
    assert ast.literal_eval(out["SPANS"]) == \
        [("repro.union_reduce.plan", "repro.union_reduce")] + call * 3
    # (2, 2) at C = 64, out 256: buckets of 64 then 128, chunks 128 then
    # 256 gathered; one call adds 64 + 128 + 128 + 256
    assert ast.literal_eval(out["STATS"]) == {"hits": 2, "misses": 1,
                                  "slots_received": 3 * 576}
    # union_fn resolves the plan as a call does, and launches nothing
    assert ast.literal_eval(out["STATS2"]) == {"hits": 3, "misses": 1,
                                   "slots_received": 3 * 576}
