#!/usr/bin/env python3
"""Readings of a cell's control: the plain reference in the nearest lower
precision, put in the program's place and judged by the same comparison as
the program's answers (each driver's ``control``).  The benchmark's runs
never run it; it sets the upper reading of a limit (PERF.md).

    python3 perfbench/control.py --workload <name> --seeds 11,12,13 --units N

``--units`` is how much of a window to compare: PageRank dispatches, or
union pool steps.  Prints each seed's numbers beside their limits, then
one line with the least reading of each number; exits 0 when the control
failed a limit on every seed.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--units", type=int, required=True)
    args = ap.parse_args(argv)
    from perfbench import harness
    harness.bootstrap_program(ROOT)
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, traffic = harness.load_cell(bench, args.workload)
    import jax
    devices = jax.devices()[: int(cell["chips"])]
    least, caught = {}, True
    for seed in (int(s) for s in args.seeds.split(",")):
        drv = harness.driver_class(config)(config, traffic, seed, devices)
        checks = drv.control(args.units)
        caught &= not all(c.ok for c in checks)
        for c in checks:
            least[c.name] = min(least.get(c.name, c.value), c.value)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "checks": {c.name: {"value": c.value,
                                              "limit": c.limit,
                                              "ok": c.ok} for c in checks}}),
              flush=True)
    print(json.dumps({"workload": args.workload,
                      "device": devices[0].device_kind, "least": least,
                      "control_failed_every_seed": caught}), flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
