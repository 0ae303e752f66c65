"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (us_per_call holds the headline
quantity scaled to integer microseconds where latency-like; see each
module's docstring for the derived column semantics).

The fleet bench additionally writes a machine-readable ``BENCH_fleet.json``
(p95s per scenario, planner wall times — see
``fleet_bench.run_with_json``) so the perf trajectory is tracked across
PRs; ``--json ''`` disables it, ``--smoke`` shrinks the fleet axes to a
seconds-scale CI invocation.

    PYTHONPATH=src python -m benchmarks.run [--only table2,fig3,...]
    PYTHONPATH=src python -m benchmarks.run --only fleet --smoke  # CI
"""
import argparse
import json
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma list of bench names")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale sizes for the fleet bench (CI)")
    ap.add_argument("--json", default="BENCH_fleet.json",
                    help="path for the fleet bench JSON payload "
                         "('' disables)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import figures, fleet_bench, kernel_bench, paper_tables, roofline

    def fleet() -> list:
        lines, payload = fleet_bench.run_with_json(quiet=True,
                                                   smoke=args.smoke)
        if args.json:
            with open(args.json, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
        return lines

    benches = {
        "fleet": fleet,
        "table2": lambda: paper_tables.run_table("openvla", quiet=True),
        "table3": lambda: paper_tables.run_table("cogact", quiet=True),
        "table4": lambda: paper_tables.run_ablation(quiet=True),
        "fig2": lambda: figures.fig2_segmentation(quiet=True),
        "fig3": lambda: figures.fig3_drift(quiet=True),
        "fig6": lambda: figures.fig6_overhead(quiet=True),
        "fig7": lambda: figures.fig7_thresholds(quiet=True),
        "adjust": lambda: figures.adjustment_overhead_vs_gain(quiet=True),
        "kernels": lambda: kernel_bench.run(quiet=True),
        "roofline": lambda: roofline.run(quiet=True),
    }
    failed = []
    print("name,us_per_call,derived")
    for name, fn in benches.items():
        if only and name not in only:
            continue
        try:
            for line in fn():
                print(line)
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
            print(f"{name},-1,FAILED {e}")
    if failed:
        sys.exit(1)


if __name__ == '__main__':
    main()
