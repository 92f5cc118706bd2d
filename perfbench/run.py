#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Set-up (data from the seed, program
build, warm-up of the cell's shapes) is timed as ``setup_s``; then the cell
is driven for ``--seconds``; then its answers are compared with the plain
reference.  With ``--trace 1`` the window runs under the JAX profiler and
the result carries the cell's per-layer metrics instead of its end-to-end
ones.  The last line of stdout is one JSON object; the numbers compared,
each beside its limit, are the last lines of stderr.  Without a TPU, with
fewer chips than the cell needs, on a chip with no row in the peak table,
or without the program (``src/``), the run exits non-zero and prints no
result.
"""
import time

T_START = time.time()   # set-up is counted from here

import argparse
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_DIR = os.path.join(ROOT, ".perfbench_trace")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    # the TPU runtime logs to /tmp/tpu_logs unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    from perfbench import harness
    from perfbench.peaks import UnknownDevice, peaks_for
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.load_cell(bench, args.workload)[0]
    try:
        harness.bootstrap_program(ROOT)
    except FileNotFoundError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    chips = int(cell["chips"])
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"perfbench: {args.workload} needs {chips} TPU chip(s); JAX "
              f"sees {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    try:
        peaks = peaks_for(devices[0].device_kind)
    except UnknownDevice as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 4
    from repro.launch.cache import use_compile_cache
    cache = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"compile cache: {cache}", flush=True)

    result = harness.execute(
        bench, args.workload, args.seed, args.seconds, bool(args.trace),
        devices, peaks, T_START, trace_dir=TRACE_DIR,
        log=lambda s: print(s, flush=True))
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices),
              "memory_peak_bytes": result.pop("memory_peak_bytes")}
    if args.trace:
        device["busy_s"] = result.pop("busy_s")
        device["window_s"] = result.pop("window_s")
    checks = result.pop("checks")
    line = dict(result, device=device, checks=checks)
    for name, c in checks.items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0 if line["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
