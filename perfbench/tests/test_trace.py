"""The reduction from trace to device numbers."""
import os

import pytest

from perfbench import trace as tr

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


def _planes():
    """Two chips; chip 0 runs a fusion overlapping an all-to-all, then an
    all-gather; chip 1 one fusion.  Host spans cover 0..1000 ns."""
    op = lambda name, s, d: (name, float(s), float(d))
    return {
        "/device:TPU:0": {
            "XLA Ops": [op("fusion.1", 100, 200), op("all_to_all.19", 250, 100),
                        op("all-gather.16", 600, 100),
                        op("fusion.2", 650, 20)],
            "XLA Modules": [op("jit_step", 100, 600)],
        },
        "/device:TPU:1": {"XLA Ops": [op("fusion.1", 0, 500)]},
        "/host:CPU": {"python": [op("bench.call", 0, 90),
                                 op("bench.wait", 90, 910)]},
    }


def test_busy_is_the_union_of_op_intervals():
    s = tr.summarize(_planes(), [0])
    # chip 0: [100, 350) and [600, 700)
    assert s["busy_s"] == pytest.approx(350e-9)
    assert s["collective_s"]["alltoall"] == pytest.approx(100e-9)
    assert s["collective_s"]["allgather"] == pytest.approx(100e-9)
    assert s["other_s"] == pytest.approx(220e-9)
    assert s["modules"] == {"jit_step": [pytest.approx(600e-9)]}


def test_summary_averages_over_chips():
    s = tr.summarize(_planes(), [0, 1])
    assert s["chips"] == 2
    assert s["busy_s"] == pytest.approx((350e-9 + 500e-9) / 2)
    assert s["ops"]["fusion.1 fusion"] == pytest.approx((200e-9 + 500e-9) / 2)


def test_idle_gaps_are_named_by_the_host_span_around_them():
    gaps = tr.idle_gaps(_planes(), 0)
    # gaps of chip 0 inside 0..1000: [0,100), [350,600), [700,1000)
    assert [round(g * 1e9) for _, g in gaps] == [300, 250, 100]
    assert [n for n, _ in gaps] == ["between runs: bench.wait",
                                     "inside run: bench.wait",
                                     "between runs: bench.call"]
    assert sum(g for _, g in gaps) + 350e-9 == pytest.approx(1000e-9)


@pytest.mark.parametrize("name,kind", [
    ("all_to_all.19", "alltoall"), ("all-to-all.3", "alltoall"),
    ("all-gather-start.2", "allgather"), ("all-gather-done.2", "allgather"),
    ("all-reduce.1", "allreduce"), ("fusion.12", None), ("sort.3", None),
    ("convert_bitcast_fusion", None), ("gather.7", None)])
def test_collective_kind_by_name(name, kind):
    assert tr.collective_kind(name) == kind


@pytest.mark.parametrize("text,kind,container", [
    ("%fusion.23 = f32[529433275]{0:T(1024)} fusion(f32[31184]{0:T(1024)S(1)} "
     "%reduce.17, s32[529433600]{0:T(1024)} %pad), kind=kCustom", None, False),
    ("%all_to_all.22 = f32[2,24576,1024]{2,1,0:T(8,128)} all-to-all("
     "f32[2,24576,1024]{2,1,0:T(8,128)} %x), dimensions={0}", "alltoall", False),
    ("%all-gather-start.3 = (f32[8]{0}, f32[16]{0}) all-gather-start(f32[8]{0} "
     "%y)", "allgather", False),
    ("%while.11 = (s32[]{:T(128)}, f32[1,31184]{1,0:T(1,128)}) while((s32[]"
     "{:T(128)}, f32[1,31184]{1,0:T(1,128)}) %tuple.47), condition=%c",
     None, True)])
def test_ops_named_by_their_hlo_text(text, kind, container):
    assert tr.collective_kind(text) == kind
    assert tr.is_container(text) == container
    assert "{" not in tr.short_name(text)


def test_a_loop_counts_through_its_body():
    planes = {"/device:TPU:0": {"XLA Ops": [
        ("%while.1 = (s32[]) while((s32[]) %t), body=%b", 0.0, 1000.0),
        ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)", 100.0, 300.0),
        ("%all-to-all.1 = f32[8]{0} all-to-all(f32[8]{0} %y)", 500.0, 100.0)]}}
    s = tr.summarize(planes, [0])
    assert s["busy_s"] == pytest.approx(400e-9)
    assert s["other_s"] == pytest.approx(300e-9)
    assert s["collective_s"] == {"alltoall": pytest.approx(100e-9)}
    assert all("while" not in name for name in s["ops"])


def test_recorded_one_chip_trace(tmp_path):
    """A TPU v5e trace of three runs of a gather-and-sum program, each
    launched in a ``bench.call`` span and waited on in ``bench.wait``."""
    import shutil
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(os.path.join(FIXTURES, "gather_1chip.xplane.pb"),
                d / "host.xplane.pb")
    planes = tr.load(str(tmp_path))
    assert tr.device_ids(planes) == [0]
    s = tr.summarize(planes)
    (runs,) = s["modules"].values()
    assert len(runs) == 3
    assert 0 < s["busy_s"] <= sum(runs)
    assert s["collective_s"] == {} and s["other_s"] == s["busy_s"]
    assert [n for n, _, _ in tr.host_spans(planes)] == \
        ["bench.call", "bench.wait"] * 3
    # the chip's clock runs about a millisecond behind the host's
    assert 0.5e6 < tr.clock_offset_ns(planes) < 2e6
    gaps = tr.idle_gaps(planes, 0)
    assert gaps and all(n.split(": ")[1].startswith("bench.") for n, _ in gaps)
    window = tr.host_spans(planes)[-1][2] - tr.host_spans(planes)[0][1]
    assert s["busy_s"] + sum(g for _, g in gaps) == pytest.approx(
        window * 1e-9, rel=1e-4)


def test_recorded_four_chip_trace(tmp_path):
    """A TPU v5e 2x2 trace of three runs of a butterfly-shaped program
    (an all-to-all in pairs, a sort, an all-gather in pairs).  XLA keeps
    the name ``all_to_all.6`` on an op it turned into a reshape: the
    opcode, not the name, makes a collective."""
    import shutil
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(os.path.join(FIXTURES, "butterfly_4chip.xplane.pb"),
                d / "host.xplane.pb")
    planes = tr.load(str(tmp_path))
    assert tr.device_ids(planes) == [0, 1, 2, 3]
    ops = [tr.parse_op(n)[:2] for n, _, _ in
           planes["/device:TPU:0"]["XLA Ops"]]
    assert ("all_to_all.6", "reshape") in ops
    assert ("all_to_all.7", "all-to-all") in ops
    s = tr.summarize(planes)
    assert s["chips"] == 4
    assert set(s["collective_s"]) == {"alltoall", "allgather"}
    assert s["collective_s"]["alltoall"] + s["collective_s"]["allgather"] \
        + s["other_s"] == pytest.approx(s["busy_s"])
    assert any(name.startswith("sort.15 sort") for name in s["ops"])
