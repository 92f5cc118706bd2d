"""Device time by the program's scopes, and the chip's clock joined to the
host's by run id (``perfbench/scopes.py``)."""
import os
import shutil

import pytest

from perfbench import scopes as sc
from perfbench import trace as tr
from perfbench.tests.test_harness import small  # noqa: F401  (fixture)

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


def _load(tmp_path, fixture):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(os.path.join(FIXTURES, fixture), d / "host.xplane.pb")
    return sc.load(str(tmp_path))


@pytest.mark.parametrize("fixture,device,lo_us,hi_us", [
    ("butterfly_4chip.xplane.pb", 0, 1160.0, 1635.4),
    ("butterfly_4chip.xplane.pb", 3, None, None),
    ("gather_1chip.xplane.pb", 0, 1294.0, 1751.2)])
def test_clock_bounds_from_paired_runs(tmp_path, fixture, device, lo_us,
                                       hi_us):
    """Each run pairs with its host enqueue and completion by run id; the
    old rule (the least shift starting every run after its launch span)
    falls outside the bounds."""
    planes = _load(tmp_path, fixture)
    lo, hi = sc.clock_bounds_ns(planes, device)
    assert lo < hi
    if lo_us is not None:
        assert lo * 1e-3 == pytest.approx(lo_us, abs=0.1)
        assert hi * 1e-3 == pytest.approx(hi_us, abs=0.1)
    if fixture.startswith("butterfly"):
        assert not lo <= tr.clock_offset_ns(planes, 0) <= hi


def test_load_keeps_what_trace_load_keeps(tmp_path):
    planes = _load(tmp_path, "butterfly_4chip.xplane.pb")
    again = tr.load(str(tmp_path))
    assert tr.summarize(planes) == tr.summarize(again)
    assert tr.host_spans(planes) == tr.host_spans(again)
    runs = planes[sc.RUNS_PLANE]
    assert len(runs[tr.MODULES_LINE]) == len(runs[sc.ENQUEUE]) == 12
    assert {k for k, _, _ in runs[tr.MODULES_LINE]} == \
        {k for k, _, _ in runs[sc.COMPLETE]}


def test_clock_bounds_need_a_pair():
    planes = {sc.RUNS_PLANE: {tr.MODULES_LINE: [("0/1", 0.0, 10.0)],
                              sc.ENQUEUE: [("1/1", 5.0, 1.0)],
                              sc.COMPLETE: [("0/1", 50.0, 1.0)]}}
    assert sc.clock_bounds_ns(planes, 0) is None
    planes[sc.RUNS_PLANE][sc.ENQUEUE].append(("0/1", 3.0, 1.0))
    assert sc.clock_bounds_ns(planes, 0) == (3.0, 40.0)


HLO = """\
HloModule jit_union_allreduce, is_scheduled=true

%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %add.2 = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b), metadata={op_name="jit(run_k)/while/body/shard_map/engine/update/add" source_file="x.py" source_line=3}
  ROOT %tuple.3 = (s32[], f32[8]{0}) tuple(s32[] %i, f32[8]{0} %add.2)
}

ENTRY %main.9 (Arg_0.1: u32[4,8]) -> u32[4,8] {
  %fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fc.1, metadata={op_name="jit(union_allreduce)/shard_map/union/down0/bucket/jit(searchsorted)/while/body/lt"}
  %all-to-all.5 = f32[2,4]{1,0} all-to-all(f32[2,4]{1,0} %x), dimensions={0}, metadata={op_name="jit(union_allreduce)/shard_map/union/down0/exchange/all_to_all"}
  %gather.6 = f32[8]{0} gather(f32[8]{0} %x, s32[8,1]{1,0} %i), metadata={op_name="jit(run_k)/shard_map/engine/out/ell_matvec/gather"}
  %copy.7 = f32[8]{0} copy(f32[8]{0} %x), metadata={op_name="jit(run_k)/shard_map/reshape"}
  %sort.8 = f32[8]{0} sort(f32[8]{0} %x), metadata={op_name="jit(f)/union/down0/merged/sort"}
  ROOT %while.9 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), body=%body.1
}
"""


def test_scope_map_takes_the_innermost_listed_scope():
    assert sc.scope_map(HLO) == {
        "add.2": "engine/update",
        "fusion.4": "union/down0/bucket",
        "all-to-all.5": "union/down0/exchange",
        "gather.6": "ell_matvec",
    }


def _scoped_planes():
    """Two chips.  Chip 0: a bucket fusion, an exchange overlapping it, an
    op in no scope and a ``while`` around them; chip 1: one bucket op."""
    op = lambda name, s, d: (name, float(s), float(d))
    return {
        "/device:TPU:0": {"XLA Ops": [
            op("%while.9 = (s32[]) while((s32[]) %t), body=%b", 0, 1000),
            op("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
               100, 200),
            op("all-to-all.5", 250, 150),
            op("%copy.7 = f32[8]{0} copy(f32[8]{0} %x)", 500, 50),
            op("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
               600, 100)]},
        "/device:TPU:1": {"XLA Ops": [op("fusion.4", 0, 400)]},
    }


def test_scope_times_nest_and_count_the_unscoped():
    of = sc.scope_map(HLO)
    t = sc.scope_times(_scoped_planes(), [0], of)
    assert t == {"union/down0/bucket": pytest.approx(300e-9),
                 "union/down0/exchange": pytest.approx(150e-9),
                 sc.UNSCOPED: pytest.approx(50e-9)}
    both = sc.scope_times(_scoped_planes(), [0, 1], of)
    assert both["union/down0/bucket"] == pytest.approx((300e-9 + 400e-9) / 2)
    assert sc.grouped(both) == {
        "union_bucket": pytest.approx(350e-9),
        "union_exchange": pytest.approx(75e-9),
        sc.UNSCOPED: pytest.approx(25e-9)}
    # the loop is counted through its body: scopes add up to busy time
    busy = tr.summarize(_scoped_planes(), [0])["busy_s"]
    assert sum(t.values()) == pytest.approx(busy + 50e-9)   # 50 ns overlap


def test_idle_gaps_named_by_the_innermost_program_span():
    """The chip placed by run id, a gap inside the program's launch span is
    named by it, not by the bench span around it."""
    op = lambda name, s, d: (name, float(s), float(d))
    planes = {
        "/device:TPU:0": {"XLA Ops": [op("fusion.1", 0, 100),
                                      op("fusion.2", 300, 100)],
                          "XLA Modules": [op("jit_f", 0, 100),
                                          op("jit_f", 300, 100)]},
        "/host:CPU": {"python": [
            op("bench.call", 1000, 500),
            op("repro.union_reduce", 1010, 480),
            op("repro.union_reduce.launch", 1100, 300),
            op("bench.wait", 1500, 100)]},
        sc.RUNS_PLANE: {
            tr.MODULES_LINE: [op("0/1", 0, 100), op("0/2", 300, 100)],
            sc.ENQUEUE: [op("0/1", 950, 10), op("0/2", 1250, 10)],
            sc.COMPLETE: [op("0/1", 1200, 5), op("0/2", 1590, 5)]},
    }
    assert sc.clock_bounds_ns(planes, 0) == (950.0, 1100.0)
    # on the host clock the chip runs [950, 1050) and [1250, 1350); the
    # window is the bench spans', [1000, 1600)
    assert sc.idle_gaps(planes, 0) == [
        ("between runs: repro.union_reduce", pytest.approx(250e-9)),
        ("between runs: repro.union_reduce.launch", pytest.approx(200e-9))]
    # cut at span boundaries: [1050, 1100) in the reduce, [1100, 1250) in
    # its launch; [1350, 1400) launch, [1400, 1490) reduce, [1490, 1500)
    # the bench call, [1500, 1600) the wait
    assert sc.idle_by_span(planes, 0) == {
        "repro.union_reduce": pytest.approx(140e-9),
        "repro.union_reduce.launch": pytest.approx(200e-9),
        "bench.call": pytest.approx(10e-9),
        "bench.wait": pytest.approx(100e-9)}


def test_span_table_and_cost():
    from repro.obs import Span
    rows = sc.span_table([Span("repro.a", None, 0, 2000),
                          Span("repro.a", None, 0, 4000)])
    assert rows == {"repro.a": {"count": 2, "total_s": pytest.approx(6e-6),
                                "mean_us": pytest.approx(3.0)}}
    from repro import obs
    cost = sc.span_cost_us(200)
    assert set(cost) == {"off", "on"} and cost["off"] > 0
    assert obs.spans() == [] and obs.span("repro.x") is obs.span("repro.y")


@pytest.mark.parametrize("cell", ["pagerank.powerlaw22.1chip",
                                  "union.table1_twitter.4chip"])
def test_whole_run_with_spans_on(small, tmp_path, cell):
    """A run at test size with the program's spans on and the window traced
    (no TPU planes here, so no scope times): correct, the set-up and window
    spans and the counters read, and spans off again afterwards."""
    import jax
    from repro import obs
    res = sc.execute(cell, 2 ** 31 + 7, 0.5, True, jax.devices(),
                     {"hbm_bytes_per_s": 819e9, "ici_bytes_per_s": 200e9},
                     str(tmp_path / "trace"), log=lambda s: None)
    assert res["correct"], res["checks"]
    assert obs.span("repro.x") is obs.span("repro.y") and obs.spans() == []
    spans, n = res["window_spans"], res["units"]
    if cell.startswith("pagerank"):
        assert set(res["setup_spans_s"]) >= {
            "repro.graph.build_partitions", "repro.engine.config",
            "repro.graph.ell_tables", "repro.engine.run"}
        assert spans["repro.engine.run"]["count"] == \
            spans["repro.engine.launch"]["count"] == res["counters"][
                "dispatches"] == n
    else:
        assert spans["repro.union_reduce"]["count"] == n
        assert "repro.union_reduce.plan" not in spans
        assert res["counters"]["misses"] == 0
        assert 0 < res["wire_fill_pct"] < 100
    assert res["scope_ms_per_unit"] == {}
    assert res["clock_bounds_us"] is None


def test_recorded_scoped_union_trace(tmp_path):
    """A TPU v5e 2x2 trace of three calls of
    ``SparseAllreduce(4, (2, 2)).union_reduce`` at C = 2048, W = 64,
    ``out_capacity`` 8192 (each in ``bench.call``/``bench.wait``, the
    program's spans on), cut down to the lines and events the reduction
    reads, with the compiled text of its program: the ops the text maps to
    ``union/*`` scopes hold at least 95% of the op time, and the idle gaps
    inside a program span are named by it."""
    import gzip
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(os.path.join(FIXTURES, "union_scoped_4chip.xplane.pb.gz")) \
            as f:
        (d / "host.xplane.pb").write_bytes(f.read())
    with gzip.open(os.path.join(FIXTURES, "union_scoped_4chip.hlo.txt.gz"),
                   "rt") as f:
        text = f.read()
    planes = sc.load(str(tmp_path))
    ids = tr.device_ids(planes)
    assert ids == [0, 1, 2, 3]
    times = sc.scope_times(planes, ids, sc.scope_map(text))
    scoped = sum(v for k, v in times.items() if k.startswith("union/"))
    assert scoped >= 0.95 * sum(times.values())
    assert {k for k in times if k.startswith("union/")} == {
        f"union/down{l}/{p}" for l in (0, 1)
        for p in ("bucket", "exchange", "merge")} | {
        "union/up0/gather", "union/up1/gather", "union/trim"}
    spans = [n for n, _, _ in sc.host_spans(planes)]
    assert spans.count("repro.union_reduce") == 3
    lo, hi = sc.clock_bounds_ns(planes, 0)
    assert 0 < hi - lo < 2e6
    for name, _ in sc.idle_gaps(planes, 0):
        assert name.split(": ")[1].startswith(("bench.", "repro.")), name
    idle = sc.idle_by_span(planes, 0)
    assert idle["repro.union_reduce.launch"] > 0
    busy = tr.summarize(planes, [0])["busy_s"]
    window = sc.host_spans(planes)[-1][2] - sc.host_spans(planes)[0][1]
    assert busy + sum(idle.values()) == pytest.approx(window * 1e-9, rel=1e-3)
