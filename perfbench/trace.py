"""Reduction of a JAX profiler trace to the benchmark's device numbers.

A trace is read once into plain tuples (:func:`load`), so the reduction can
be tested on a recorded file and on hand-made events alike:

    {plane_name: {line_name: [(event_name, start_ns, dur_ns), ...]}}

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed HLO op and ``XLA Modules`` one per program run.  The
host's ``/host:CPU`` plane holds the benchmark's own ``TraceAnnotation``
spans (names starting ``bench.``), on a clock about a millisecond apart
from the chip's (:func:`clock_offset_ns`).

Busy time is the union of op intervals, so ops that overlap count once; a
``while`` (a ``lax.scan``) is counted through the ops of its body.
Collectives are told apart by their HLO opcode.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]
Planes = Dict[str, Dict[str, List[Event]]]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."

# HLO opcodes of collectives, by the kind a metric reads
COLLECTIVE_KINDS = {
    "all-to-all": "alltoall",
    "all-gather": "allgather",
    "all-reduce": "allreduce",
    "reduce-scatter": "reducescatter",
    "collective-permute": "permute",
    "send": "sendrecv",
    "recv": "sendrecv",
}
# ops whose interval holds other ops' events: counted through those
CONTAINERS = ("while", "conditional", "call")
_ASYNC = re.compile(r"-(start|done|update)$")
# "%fusion.23 = f32[529433275]{0:T(1024)} fusion(...), kind=..."
_HLO = re.compile(r"^%?(?P<inst>[\w.-]+) = (?P<shape>.*?) "
                  r"(?P<op>[a-z][a-z0-9_-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def load(trace_dir: str) -> Planes:
    """Read the newest ``.xplane.pb`` under ``trace_dir``: the device
    planes' ops and program runs, and the benchmark's host spans."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    out: Planes = {}
    for plane in data.planes:
        host = plane.name == HOST_PLANE
        if not host and not DEVICE_PLANE.match(plane.name):
            continue
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            if not host and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                if host and not ev.name.startswith(SPAN_PREFIX):
                    continue
                evs.append((ev.name, float(ev.start_ns),
                            float(ev.duration_ns)))
    return out


def parse_op(name: str) -> Tuple[str, str, str]:
    """``(instruction, opcode, shape)`` of an ``XLA Ops`` event name.  On
    the TPU the name is the instruction's HLO text; a bare instruction
    name (``all-gather.16``, ``all_to_all.19``) gives its opcode by its
    stem."""
    m = _HLO.match(name)
    if m:
        return m.group("inst"), m.group("op"), _LAYOUT.sub("", m.group("shape"))
    inst = name.lstrip("%")
    stem = re.sub(r"(\.\d+)+$", "", inst).replace("_", "-")
    return inst, stem, ""


def short_name(name: str) -> str:
    """``instruction opcode shape`` (layouts dropped), at most 96 chars."""
    inst, op, shape = parse_op(name)
    return f"{inst} {op} {shape}".strip()[:96]


def collective_kind(op_name: str) -> Optional[str]:
    """The collective kind of an HLO op (``"alltoall"``, ...) or None."""
    op = _ASYNC.sub("", parse_op(op_name)[1])
    return COLLECTIVE_KINDS.get(op)


def is_container(op_name: str) -> bool:
    return parse_op(op_name)[1] in CONTAINERS


def union_ns(intervals: Sequence[Tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The union of intervals as disjoint, sorted intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def device_ids(planes: Planes) -> List[int]:
    return sorted(int(DEVICE_PLANE.match(p).group(1))
                  for p in planes if DEVICE_PLANE.match(p))


def summarize(planes: Planes, devices: Optional[Sequence[int]] = None) -> dict:
    """Per-chip busy times, averaged over the chips in ``devices`` (all
    device planes when None).  Seconds throughout:

    * ``busy_s``: union of every op's interval;
    * ``collective_s[kind]``: union of that kind's collective ops;
    * ``other_s``: union of the ops that are not collectives;
    * ``ops``: ``{op name: seconds}`` summed over the op's runs;
    * ``modules``: ``{program name: [run seconds, ...]}`` of chip 0;
    * ``chips``: how many chips were averaged.
    """
    ids = list(devices) if devices is not None else device_ids(planes)
    busy = other = 0.0
    coll: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    modules: Dict[str, List[float]] = {}
    for n, dev in enumerate(ids):
        lines = planes.get(f"/device:TPU:{dev}", {})
        evs = [e for e in lines.get(OPS_LINE, []) if not is_container(e[0])]
        spans = [(s, s + d) for _, s, d in evs]
        busy += union_ns(spans)
        by_kind: Dict[str, list] = {}
        rest = []
        for name, s, d in evs:
            k = collective_kind(name)
            (by_kind.setdefault(k, []) if k else rest).append((s, s + d))
            key = short_name(name)
            ops[key] = ops.get(key, 0.0) + d
        other += union_ns(rest)
        for k, iv in by_kind.items():
            coll[k] = coll.get(k, 0.0) + union_ns(iv)
        if n == 0:
            for name, _, d in lines.get(MODULES_LINE, []):
                modules.setdefault(name, []).append(d * 1e-9)
    c = max(len(ids), 1)
    return {"busy_s": busy * 1e-9 / c, "other_s": other * 1e-9 / c,
            "collective_s": {k: v * 1e-9 / c for k, v in coll.items()},
            "ops": {k: v * 1e-9 / c for k, v in ops.items()},
            "modules": modules, "chips": len(ids)}


def host_spans(planes: Planes) -> List[Tuple[str, float, float]]:
    """The benchmark's own host spans: ``(name, start_ns, end_ns)``."""
    out = []
    for evs in planes.get(HOST_PLANE, {}).values():
        out.extend((n, s, s + d) for n, s, d in evs
                   if n.startswith(SPAN_PREFIX))
    return sorted(out, key=lambda t: t[1])


LAUNCH_SPANS = ("bench.call", "bench.dispatch")


def clock_offset_ns(planes: Planes, device: int = 0) -> float:
    """Nanoseconds to add to a chip's timestamps to put them on the host's
    clock.  The two clocks differ by about a millisecond in recorded
    traces (a program run starts before the host span that launched it).
    Where the chip ran one program per launch span, the offset is the
    least shift that starts every run after its launch; else 0."""
    runs = sorted(s for _, s, _ in
                  planes.get(f"/device:TPU:{device}", {}).get(MODULES_LINE, []))
    launches = [s for n, s, _ in host_spans(planes) if n in LAUNCH_SPANS]
    if not runs or len(runs) != len(launches):
        return 0.0
    return max(0.0, max(h - d for h, d in zip(launches, runs)))


def idle_gaps(planes: Planes, device: int = 0,
              top: int = 10) -> List[Tuple[str, float]]:
    """The longest idle gaps of one chip inside the benchmark's spans,
    each named by where it falls, ``inside run`` (within a program run) or
    ``between runs``, and by the innermost host span around its midpoint
    (``host:unannotated`` where none is).  ``[(name, seconds)]``, longest
    first."""
    spans = host_spans(planes)
    if not spans:
        return []
    shift = clock_offset_ns(planes, device)
    lines = planes.get(f"/device:TPU:{device}", {})
    lo, hi = spans[0][1], max(e for _, _, e in spans)
    evs = [e for e in lines.get(OPS_LINE, []) if not is_container(e[0])]
    busy = [(max(s, lo), min(e, hi)) for s, e in
            merged([(s + shift, s + d + shift) for _, s, d in evs])
            if e > lo and s < hi]
    runs = [(s + shift, s + d + shift)
            for _, s, d in lines.get(MODULES_LINE, [])]
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    named = []
    for s, e in gaps:
        mid = (s + e) / 2
        around = [(n, ss) for n, ss, ee in spans if ss <= mid <= ee]
        host = max(around, key=lambda t: t[1])[0] if around \
            else "host:unannotated"
        where = "inside run" if any(rs <= mid <= re_ for rs, re_ in runs) \
            else "between runs"
        named.append((f"{where}: {host}", (e - s) * 1e-9))
    return sorted(named, key=lambda t: -t[1])[:top]


def top_ops(summary: dict, top: int = 10) -> List[Tuple[str, float]]:
    """The device ops that took most time: ``[(op name, seconds)]``."""
    return sorted(summary["ops"].items(), key=lambda t: -t[1])[:top]
