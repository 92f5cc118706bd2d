# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV; ``--json PATH`` additionally writes a machine-readable artifact so
# successive PRs accumulate a perf trajectory (see BENCH_pr2.json for the
# first committed baseline and EXPERIMENTS.md for the bench -> figure map).
import argparse
import json
import os
import sys
import traceback


def main(argv=None) -> None:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "src"))
    sys.path.insert(0, root)
    # The device-backed graph-engine rows (fig8/fig9) need a multi-device
    # mesh; off-TPU, force host devices BEFORE jax is first imported (the
    # benchmark modules below pull it in).  Respect a caller-set flag.
    if "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="",
                    help="also write results as a JSON list of "
                         "{name, us_per_call, derived} records")
    ap.add_argument("--only", default="",
                    help="comma-separated bench-function name substrings "
                         "to run (default: all)")
    args = ap.parse_args(argv)

    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    from benchmarks.bench_analysis import ALL_BENCHES as ANALYSIS_BENCHES
    from benchmarks.bench_autotune import ALL_BENCHES as AUTOTUNE_BENCHES
    from benchmarks.bench_fault_tolerance import ALL_BENCHES as FAULT_BENCHES
    from benchmarks.bench_overlap import ALL_BENCHES as OVERLAP_BENCHES
    from benchmarks.bench_serve import ALL_BENCHES as SERVE_BENCHES
    from benchmarks.bench_soak import ALL_BENCHES as SOAK_BENCHES
    from benchmarks.bench_wire import ALL_BENCHES as WIRE_BENCHES
    from benchmarks.paper_benches import ALL_BENCHES
    wanted = [s for s in args.only.split(",") if s]
    benches = [b for b in ALL_BENCHES + FAULT_BENCHES + AUTOTUNE_BENCHES
               + ANALYSIS_BENCHES + SOAK_BENCHES + WIRE_BENCHES
               + OVERLAP_BENCHES + SERVE_BENCHES
               if not wanted or any(s in b.__name__ for s in wanted)]
    print("name,us_per_call,derived")
    records = []
    failures = 0
    for bench in benches:
        try:
            for name, us, derived in bench():
                print(f"{name},{us:.1f},{derived}")
                records.append({"name": name, "us_per_call": round(us, 1),
                                "derived": derived})
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{bench.__name__},-1,ERROR:{e}")
            traceback.print_exc(file=sys.stderr)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1)
        print(f"# wrote {len(records)} records to {args.json}",
              file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
