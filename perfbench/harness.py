"""The harness: finds a cell's configuration, traffic, driver and metric
readers by name, times set-up and the window, reads the trace, and builds
the result line.

Everything cell-specific is data or a file of its own:

* ``BENCHMARK.json`` names each cell's configuration and traffic;
* ``perfbench/configs/<config>.json`` holds the deployment, and its
  ``driver`` key names ``perfbench/drivers/<driver>.py``;
* ``perfbench/traffic/<traffic>.json`` holds the traffic's parameters;
* ``perfbench/metrics/<metric>.py`` computes one metric with ``read(ctx)``,
  returning None where the cell gives it nothing to read.  A quantity split
  by the end-to-end metric it moves (``idle_share.pagerank``,
  ``idle_share.union``) is read by ``perfbench/metrics/<quantity>.py``
  where the split name has no file of its own.

A driver module defines ``Driver(config, traffic, seed, devices)`` with
``setup(spans)``, ``describe()``, ``window(seconds, annotate) -> facts``,
``release()``, ``check() -> [Check]`` (which also sets ``failed``, the
count of dispatches or calls found wrong) and ``control(units) ->
[Check]``, the reference in a lower precision judged by the same
comparison (``perfbench/control.py``); see ``drivers/pagerank.py``.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

from perfbench import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# JAX events whose durations are compilation: the backend compile, or the
# persistent-cache read that stands in for it (the trace and lowering
# events nest inside and are left out).
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


@dataclasses.dataclass
class Check:
    """One compared number: ``value`` must not exceed ``limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Context:
    """What a metric reader sees."""
    facts: dict                    # the driver's window counts and sizes
    peaks: dict                    # perfbench.peaks row of the device
    setup: dict                    # setup_s, compile_s and the driver's spans
    trace: Optional[dict] = None   # perfbench.trace.summarize() of the window


class CompileClock:
    """Seconds and count of compilations while registered."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += duration
            self.count += 1

    def close(self) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(bench: dict, name: str, root: str = ROOT):
    """``(workload, config, traffic)`` of cell ``name``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    return cell, config, traffic


def cell_metrics(bench: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that ``cell`` reports."""
    e2e = bench["end_to_end"]
    e2e_cells = {m["name"]: m.get("workloads") for m in e2e}
    out = []
    for m in bench[kind]:
        cells = m.get("workloads")
        if cells is None and kind == "per_layer":
            cells = e2e_cells.get(m["moves"])
        if cells is None or cell in cells:
            out.append(m)
    return out


def reader(name: str) -> Callable[[Context], Optional[float]]:
    """``read`` of ``perfbench/metrics/<name>.py``, or of the file of the
    quantity before the name's first dot."""
    path = os.path.join(HERE, "metrics", name + ".py")
    if not os.path.exists(path):
        path = os.path.join(HERE, "metrics", name.split(".")[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics: List[dict], ctx: Context) -> Dict[str, dict]:
    out = {}
    for m in metrics:
        v = reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def driver_class(config: dict):
    return importlib.import_module(
        f"perfbench.drivers.{config['driver']}").Driver


def execute(bench: dict, cell_name: str, seed: int, seconds: float,
            trace: bool, devices, peaks: dict, t_start: float,
            trace_dir: Optional[str] = None, log=print) -> dict:
    """One run of one cell on ``devices``: set-up, window, check, metrics.
    Returns the result object (without ``device``)."""
    import jax
    cell, config, traffic = load_cell(bench, cell_name)
    chips = int(cell["chips"])
    clock = CompileClock()
    try:
        drv = driver_class(config)(config, traffic, seed, devices[:chips])
        spans: Dict[str, float] = {}
        drv.setup(spans)
        for line in drv.describe():
            log(line)
        setup = dict(spans, setup_s=time.time() - t_start,
                     compile_s=clock.seconds)
        compiles_before = clock.count
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
        try:
            facts = drv.window(seconds, jax.profiler.TraceAnnotation)
        finally:
            if trace:
                jax.profiler.stop_trace()
        window_compiles = clock.count - compiles_before
    finally:
        clock.close()
    peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices[:chips])
    drv.release()
    checks = drv.check() + [Check("window_compiles", window_compiles, 0)]
    summary = None
    if trace:
        planes = tr.load(trace_dir)
        ids = [d.id for d in devices[:chips]]
        summary = tr.summarize(planes, ids)
        summary["gaps"] = tr.idle_gaps(planes, ids[0])
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Context(facts=facts, peaks=peaks, setup=setup, trace=summary)
    metrics = read_metrics(
        cell_metrics(bench, cell_name, "per_layer" if trace else "end_to_end"),
        ctx)
    correct = all(c.ok for c in checks) and facts["attempted"] > 0
    result = {"correct": correct, "attempted": int(facts["attempted"]),
              "failed": drv.failed if correct else max(drv.failed, 1),
              "metrics": metrics, "memory_peak_bytes": int(peak_mem)}
    if trace:
        result["busy_s"] = summary["busy_s"]
        result["window_s"] = facts["window_s"]
        result["breakdown"] = {"device_ops": [list(t) for t in tr.top_ops(summary)],
                               "idle_gaps": [list(t) for t in summary["gaps"]]}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def bootstrap_program(root: str = ROOT) -> None:
    """Put the program under test (``<root>/src``) on the import path;
    raises FileNotFoundError where the checkout has none."""
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(f"no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)
    os.environ.setdefault("REPRO_PLAN_CACHE", os.path.join(root, ".plan_cache"))
