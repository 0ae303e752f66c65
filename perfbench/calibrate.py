#!/usr/bin/env python3
"""Reads the compared numbers of many seeds in one process, for setting
the limits of ``correct``: each seed is a whole run of the cell (set-up,
a short window at the cell's own load, the reference over as many
requests as a run compares), with the controls' numbers (the reference
in int8 or fp8 in the program's place) beside the program's.  Not part
of a benchmark run.

    python3 perfbench/calibrate.py --workload openvla-7b-standin.solo \\
        --seconds 3 --seeds 2147483001 2147483002 ...

Prints one JSON line per seed and, last, for each number the largest
the program gave and the smallest the control gave.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="+", default=["int8"],
                    choices=("int8", "fp8"))
    args = ap.parse_args(argv)
    worst, best = {}, {}
    for seed in args.seeds:
        try:
            r = run.run(args.workload, seed, args.seconds, False,
                        controls=tuple(args.controls),
                        t_start=time.perf_counter())
        except run.NoChip as e:
            run.log(f"calibrate: {e}")
            return 3
        line = {"seed": seed, "correct": r["correct"],
                "checks": r["checks"]}
        print(json.dumps(line), flush=True)
        for k, c in r["checks"].items():
            worst[k] = max(worst.get(k, float("-inf")), c["value"])
            for q in args.controls:
                best[f"{k}.{q}"] = min(best.get(f"{k}.{q}", float("inf")),
                                       c[f"control_{q}"])
        del r
        gc.collect()
    print(json.dumps({"program_max": worst, "control_min": best}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
