#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's compared
numbers beside those of the control and of planted faults, on the chip at
a cell's own size.

    python3 bench/control.py --workload <name> --seeds 1,2,3 [--seconds 10]

For each seed it drives one run of the cell (set-up, a short window at
the cell's load, the reference) and prints one JSON line with the readings
that the cell's driver gives (its ``readings``): the numbers the run
compares (``program``: what sound runs read), the same numbers with the
plain reference put in the program's place computed in bfloat16 (the
control) and at the default matrix-product precision (how far rounding
alone moves each number), and with faults planted in that reference.

The benchmark's own runs do not run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench.harness import Manifest  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    manifest = Manifest.load()
    from bench.run import prepare
    for seed in [int(s) for s in args.seeds.split(",")]:
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=args.seconds, trace=0)
        run = prepare(ns, manifest)
        run.t_start = time.perf_counter()
        driver = harness.load_module(
            harness.BENCH / "drivers" / f"{run.traffic['driver']}.py")
        got = {}

        def check(run_, *recorded):
            got.update(driver.readings(run_, *recorded))
            return got["program"]

        with harness.CompileLog() as log:
            run.compile_log = log
            out = driver.run(run, check=check)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "metrics": out.metrics,
                          "attempted": out.attempted, "failed": out.failed,
                          "memory_peak_bytes": run.memory_peak_bytes,
                          **got}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
