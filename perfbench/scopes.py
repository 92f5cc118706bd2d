#!/usr/bin/env python3
"""Where a cell's time goes, by the program's own spans and scopes.

    python3 perfbench/scopes.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of a cell as ``perfbench/run.py`` makes it, with the program's host
spans on (``repro.obs.enable()`` before set-up).  The last line of stdout is
one JSON object: the cell's checks and end-to-end metrics, the set-up and
window spans (``repro.*``), and the counters the program keeps.  With
``--trace 1`` the window runs under the JAX profiler, and the line adds the
device time of each listed scope (``union/down0/bucket``, ``ell_matvec``,
...) per call or round, the chip's clock placed on the host's by run id
(:func:`clock_bounds_ns`, logged as ``clock offset [lo, hi] us``) and the
idle gaps named by the innermost host span around them.  Without a TPU it
exits non-zero, as ``run.py`` does.

The reduction is kept apart from ``perfbench/trace.py`` and tested on
recorded traces (``perfbench/tests/test_scopes.py``):

* :func:`load` reads what ``trace.load`` reads, plus the program's host
  spans and a ``/runs`` plane of program runs keyed ``"<device>/<run_id>"``:
  each chip's ``XLA Modules`` runs, and the host's ``DoEnqueueProgram`` and
  ``CompleteCallbacks`` events for the same runs;
* :func:`scope_map` maps each HLO instruction of a compiled program to the
  innermost listed scope in its ``op_name`` metadata (op events on the TPU
  carry no ``op_name``, so the map comes from ``compiled.as_text()``, whose
  instruction names are the names of the trace's ``XLA Ops`` events);
* :func:`scope_times` sums device op time by scope; :func:`idle_gaps` names
  gaps by bench and program spans on the joined clocks.
"""
from __future__ import annotations

import time

T_START = time.time()   # set-up is counted from here

import argparse
import fnmatch
import glob
import json
import os
import re
import shutil
import sys
import tempfile
from typing import Dict, List, Optional, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import trace as tr   # noqa: E402

PROGRAM_PREFIX = "repro."
RUNS_PLANE = "/runs"
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"
UNSCOPED = "unscoped"
# the program's device scopes (repro.obs.scope), innermost listed one wins
SCOPES = re.compile(
    r"(?:^|/)(union/(?:down\d+/(?:bucket|exchange|merge)|up\d+/gather|trim)"
    r"|planned/(?:down|up)\d+|engine/(?:out|reduce|update)|ell_matvec)"
    r"(?=/|$)")
# "  %fusion.3 = f32[8]{0} fusion(...), ..., metadata={op_name="a/b" ...}"
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+) = .*?"
                          r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
# per-layer groups of scopes, as PERF.md names them
GROUPS = {
    "union_bucket": "union/down*/bucket",
    "union_exchange": "union/down*/exchange",
    "union_merge": "union/down*/merge",
    "union_gather": "union/up*/gather",
    "union_trim": "union/trim",
    "engine_out": "engine/out",
    "ell_matvec": "ell_matvec",
    "engine_reduce": "engine/reduce",
    "planned": "planned/*",
    "engine_update": "engine/update",
    UNSCOPED: UNSCOPED,
}


# -- reading ------------------------------------------------------------------

def _newest_trace(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(trace_dir: str) -> tr.Planes:
    """``trace.load(trace_dir)`` plus the program's host spans and the
    ``/runs`` plane (module docstring)."""
    from jax.profiler import ProfileData
    planes = tr.load(trace_dir)
    data = ProfileData.from_file(_newest_trace(trace_dir))
    runs = planes.setdefault(RUNS_PLANE, {})
    for plane in data.planes:
        device = tr.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if device and line.name == tr.MODULES_LINE:
                for ev in line.events:
                    rid = dict(ev.stats)["run_id"]
                    runs.setdefault(tr.MODULES_LINE, []).append(
                        (f"{device.group(1)}/{rid}", float(ev.start_ns),
                         float(ev.duration_ns)))
            elif plane.name == tr.HOST_PLANE:
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_PREFIX):
                        planes[tr.HOST_PLANE].setdefault(line.name, []).append(
                            (ev.name, float(ev.start_ns),
                             float(ev.duration_ns)))
                    elif ev.name in (ENQUEUE, COMPLETE):
                        st = dict(ev.stats)
                        runs.setdefault(ev.name, []).append(
                            (f"{st['device_ordinal']}/{st['run_id']}",
                             float(ev.start_ns), float(ev.duration_ns)))
    return planes


def scope_map(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: innermost listed scope}`` of a compiled
    program's text; instructions in no listed scope are left out."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            found = SCOPES.findall(m.group(2))
            if found:
                out[m.group(1)] = found[-1]
    return out


# -- reducing -----------------------------------------------------------------

def _runs(planes: tr.Planes, line: str, device: int) -> Dict[str, tuple]:
    pre = f"{device}/"
    return {k: (s, s + d) for k, s, d in
            planes.get(RUNS_PLANE, {}).get(line, []) if k.startswith(pre)}


def clock_bounds_ns(planes: tr.Planes,
                    device: int = 0) -> Optional[Tuple[float, float]]:
    """``(lo, hi)``: the nanoseconds to add to a chip's timestamps to put
    them on the host's clock lie between these.  Runs are paired with their
    host events by run id: a run starts no earlier than the start of its
    ``DoEnqueueProgram`` (lo, the largest such shift over runs) and ends no
    later than the start of its ``CompleteCallbacks`` (hi, the smallest).
    None where no run of the chip can be paired."""
    runs = _runs(planes, tr.MODULES_LINE, device)
    enq = _runs(planes, ENQUEUE, device)
    done = _runs(planes, COMPLETE, device)
    lo = [enq[k][0] - s for k, (s, _) in runs.items() if k in enq]
    hi = [done[k][0] - e for k, (_, e) in runs.items() if k in done]
    if not lo or not hi:
        return None
    return max(lo), min(hi)


def scope_times(planes: tr.Planes, devices: Sequence[int],
                scope_of: Dict[str, str]) -> Dict[str, float]:
    """Seconds of device op time per scope (``scope_of[instruction]``, or
    ``"unscoped"``), the union of the scope's op intervals on each chip,
    averaged over ``devices``.  Loops are counted through their bodies, as
    in ``trace.summarize``."""
    total: Dict[str, float] = {}
    for dev in devices:
        by: Dict[str, list] = {}
        for name, s, d in planes.get(f"/device:TPU:{dev}", {}).get(
                tr.OPS_LINE, []):
            if tr.is_container(name):
                continue
            key = scope_of.get(tr.parse_op(name)[0], UNSCOPED)
            by.setdefault(key, []).append((s, s + d))
        for key, iv in by.items():
            total[key] = total.get(key, 0.0) + tr.union_ns(iv)
    c = max(len(devices), 1)
    return {k: v * 1e-9 / c for k, v in total.items()}


def grouped(times: Dict[str, float], groups: Dict[str, str] = GROUPS
            ) -> Dict[str, float]:
    """Scope times summed by group (``fnmatch`` patterns); groups with
    nothing in them are left out."""
    out = {}
    for name, pattern in groups.items():
        hit = [v for k, v in times.items() if fnmatch.fnmatchcase(k, pattern)]
        if hit:
            out[name] = sum(hit)
    return out


def host_spans(planes: tr.Planes) -> List[Tuple[str, float, float]]:
    """Bench and program host spans: ``(name, start_ns, end_ns)``."""
    out = []
    for evs in planes.get(tr.HOST_PLANE, {}).values():
        out.extend((n, s, s + d) for n, s, d in evs
                   if n.startswith((tr.SPAN_PREFIX, PROGRAM_PREFIX)))
    return sorted(out, key=lambda t: t[1])


def _idle(planes: tr.Planes, device: int):
    """``(spans, runs, gaps)`` of one chip on the host's clock: the bench
    and program spans, the chip's program runs placed at the low end of
    :func:`clock_bounds_ns` (``trace.clock_offset_ns`` where no run
    pairs), and its idle intervals inside the bench spans' window."""
    spans = host_spans(planes)
    bench = [t for t in spans if t[0].startswith(tr.SPAN_PREFIX)]
    if not bench:
        return spans, [], []
    bounds = clock_bounds_ns(planes, device)
    shift = bounds[0] if bounds else tr.clock_offset_ns(planes, device)
    lines = planes.get(f"/device:TPU:{device}", {})
    lo, hi = bench[0][1], max(e for _, _, e in bench)
    evs = [e for e in lines.get(tr.OPS_LINE, []) if not tr.is_container(e[0])]
    busy = [(max(s, lo), min(e, hi)) for s, e in
            tr.merged([(s + shift, s + d + shift) for _, s, d in evs])
            if e > lo and s < hi]
    runs = [(s + shift, s + d + shift)
            for _, s, d in lines.get(tr.MODULES_LINE, [])]
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return spans, runs, gaps


def _innermost(spans, t: float) -> str:
    around = [(n, s) for n, s, e in spans if s <= t <= e]
    return max(around, key=lambda a: a[1])[0] if around \
        else "host:unannotated"


def idle_gaps(planes: tr.Planes, device: int = 0,
              top: int = 10) -> List[Tuple[str, float]]:
    """``trace.idle_gaps`` with the chip placed by run id (:func:`_idle`)
    and each gap named by the innermost bench or program span around its
    midpoint.  ``[(name, seconds)]``, longest first."""
    spans, runs, gaps = _idle(planes, device)
    named = []
    for s, e in gaps:
        mid = (s + e) / 2
        where = "inside run" if any(rs <= mid <= re_ for rs, re_ in runs) \
            else "between runs"
        named.append((f"{where}: {_innermost(spans, mid)}", (e - s) * 1e-9))
    return sorted(named, key=lambda g: -g[1])[:top]


def idle_by_span(planes: tr.Planes, device: int = 0) -> Dict[str, float]:
    """Idle seconds of one chip by what the host was in meanwhile: each
    gap of :func:`_idle` cut at every span boundary inside it, each piece
    given to the innermost span around it."""
    spans, _, gaps = _idle(planes, device)
    edges = sorted({t for _, s, e in spans for t in (s, e)})
    out: Dict[str, float] = {}
    for s, e in gaps:
        cuts = [s] + [t for t in edges if s < t < e] + [e]
        for a, b in zip(cuts, cuts[1:]):
            name = _innermost(spans, (a + b) / 2)
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


def span_table(spans) -> Dict[str, dict]:
    """``{name: {count, mean_us, total_s}}`` of ``repro.obs`` spans."""
    out: Dict[str, dict] = {}
    for sp in spans:
        row = out.setdefault(sp.name, {"count": 0, "total_s": 0.0})
        row["count"] += 1
        row["total_s"] += (sp.end_ns - sp.start_ns) * 1e-9
    for row in out.values():
        row["mean_us"] = 1e6 * row["total_s"] / row["count"]
    return out


def span_cost_us(n: int = 20000) -> Dict[str, float]:
    """Host microseconds of one ``repro.obs.span`` entered and left, off
    and on (no profiler running).  Leaves spans off and none recorded."""
    from repro import obs
    out = {}
    for on in (False, True):
        obs.enable(on)
        t0 = time.perf_counter_ns()
        for _ in range(n):
            with obs.span("repro.cost"):
                pass
        out["on" if on else "off"] = (time.perf_counter_ns() - t0) / n / 1e3
    obs.enable(False)
    obs.reset()
    return out


# -- one run ------------------------------------------------------------------

def compiled_text(drv) -> str:
    """The compiled text of the program a cell's window runs: the engine's
    k-round dispatch, or the union allreduce of the pool's first step,
    compiled afresh with JAX's in-memory caches cleared and the persistent
    cache off.  The persistent cache's key leaves metadata out, so a
    program read back from it can carry the ``op_name``s of an older
    compile of the same instructions (one from before the scopes were
    added reads with none).  Call it after the window: the window's own
    compiled program is dropped."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    if hasattr(drv, "engine"):
        fn = drv.engine.run_fn(drv.k)
        args = (drv.p0, drv.extras) + tuple(drv.engine.routing_args())
    else:
        fn = drv.ar.union_fn(drv.idx_dev[0], drv.val_dev[0], drv.out_cap)
        args = (drv.idx_dev[0], drv.val_dev[0])
    was = jax.config.jax_enable_compilation_cache
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return fn.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def per_unit(facts: dict) -> Tuple[str, int]:
    """What a cell's window counts: ``("call", calls)`` or
    ``("round", rounds)``."""
    return ("call", facts["calls"]) if "calls" in facts \
        else ("round", facts["rounds"])


def execute(cell_name: str, seed: int, seconds: float, trace: bool,
            devices, peaks: dict, trace_dir: str, log=print) -> dict:
    """One run of ``cell_name`` with the program's spans on."""
    import jax
    from perfbench import harness
    from repro import obs
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, traffic = harness.load_cell(bench, cell_name)
    chips = int(cell["chips"])
    cost = span_cost_us()
    obs.enable()
    clock = harness.CompileClock()
    try:
        drv = harness.driver_class(config)(config, traffic, seed,
                                           devices[:chips])
        spans: Dict[str, float] = {}
        drv.setup(spans)
        for line in drv.describe():
            log(line)
        setup = dict(spans, setup_s=time.time() - T_START,
                     compile_s=clock.seconds)
        setup_spans = obs.spans()
        obs.reset()
        counters0 = counters(drv)
        compiles = clock.count
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
        try:
            facts = drv.window(seconds, jax.profiler.TraceAnnotation)
        finally:
            if trace:
                jax.profiler.stop_trace()
        window_spans = obs.spans()
        window_compiles = clock.count - compiles
        counters1 = counters(drv)
        obs.enable(False)
        hlo = compiled_text(drv) if trace else None
    finally:
        clock.close()
        obs.enable(False)
        obs.reset()
    drv.release()
    checks = drv.check() + [harness.Check("window_compiles",
                                          window_compiles, 0)]
    ctx = harness.Context(facts=facts, peaks=peaks, setup=setup)
    unit, units = per_unit(facts)
    result = {
        "workload": cell_name, "seed": seed,
        "correct": all(c.ok for c in checks) and facts["attempted"] > 0,
        "metrics": {k: v["value"] for k, v in harness.read_metrics(
            harness.cell_metrics(bench, cell_name, "end_to_end"),
            ctx).items()},
        "setup_timings_s": spans,
        "setup_spans_s": {k: v["total_s"] for k, v in
                          span_table(setup_spans).items()},
        "window_spans": span_table(window_spans),
        "counters": {k: counters1[k] - counters0[k] for k in counters1},
        "unit": unit, "units": units,
        "span_cost_us": cost,
    }
    if "calls" in facts and result["counters"].get("slots_received"):
        slots = result["counters"]["slots_received"] / facts["calls"]
        fills = [(u - o) / slots for own, u in
                 zip(facts["own_rows"], facts["union_rows"]) for o in own]
        result["wire_fill_pct"] = 100.0 * sum(fills) / len(fills)
    if trace:
        planes = load(trace_dir)
        ids = [d.id for d in devices[:chips]]
        summary = tr.summarize(planes, ids)
        of = scope_map(hlo)
        times = scope_times(planes, ids, of)
        bounds = clock_bounds_ns(planes, ids[0])
        result.update({
            "busy_ms_per_unit": 1e3 * summary["busy_s"] / units,
            "scope_ms_per_unit": {k: 1e3 * v / units
                                  for k, v in grouped(times).items()},
            "scopes_ms_per_unit": {k: 1e3 * v / units
                                   for k, v in sorted(times.items())},
            "mapped_instructions": len(of),
            "clock_bounds_us": [b * 1e-3 for b in bounds] if bounds else None,
            "clock_offset_old_us": 1e-3 * tr.clock_offset_ns(planes, ids[0]),
            "idle_gaps": idle_gaps(planes, ids[0]),
            "idle_ms_per_unit_by_span": {
                k: 1e3 * v / units
                for k, v in idle_by_span(planes, ids[0]).items()},
            "idle_gaps_old": tr.idle_gaps(planes, ids[0]),
            "unscoped_ops": top_unscoped(planes, ids, of),
        })
        if "ell_matvec" in times and "needed_bytes_per_round" in facts:
            least = facts["needed_bytes_per_round"] / peaks["hbm_bytes_per_s"]
            result["ell_matvec_roofline_pct"] = \
                100.0 * least / (times["ell_matvec"] / units)
        if bounds:
            log(f"clock offset [{bounds[0] * 1e-3:.1f}, "
                f"{bounds[1] * 1e-3:.1f}] us")
        shutil.rmtree(trace_dir, ignore_errors=True)
    result["checks"] = {c.name: [c.value, c.limit] for c in checks}
    return result


def counters(drv) -> Dict[str, int]:
    """The program's counters of the cell's entry point."""
    if hasattr(drv, "engine"):
        return dict(drv.engine.report)
    return dict(drv.ar.union_plan_stats)


def top_unscoped(planes: tr.Planes, devices: Sequence[int],
                 scope_of: Dict[str, str], top: int = 8):
    """The ops outside every listed scope that took most time:
    ``[(op name, seconds averaged over chips)]``."""
    acc: Dict[str, float] = {}
    for dev in devices:
        for name, _, d in planes.get(f"/device:TPU:{dev}", {}).get(
                tr.OPS_LINE, []):
            if not tr.is_container(name) and \
                    tr.parse_op(name)[0] not in scope_of:
                key = tr.short_name(name)
                acc[key] = acc.get(key, 0.0) + d * 1e-9 / len(devices)
    return sorted(acc.items(), key=lambda t: -t[1])[:top]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    from perfbench import harness
    from perfbench.peaks import UnknownDevice, peaks_for
    harness.bootstrap_program(ROOT)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"perfbench: no TPU; JAX sees {devices[0].platform}",
              file=sys.stderr)
        return 3
    try:
        peaks = peaks_for(devices[0].device_kind)
    except UnknownDevice as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 4
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = execute(args.workload, args.seed, args.seconds, bool(args.trace),
                     devices, peaks, os.path.join(ROOT, ".perfbench_trace"),
                     log=lambda s: print(s, flush=True))
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
