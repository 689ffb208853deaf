#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's compared
numbers beside those of the control and of planted faults, on the chip at
a cell's own size.

    python3 bench/control.py --workload <name> --seeds 1,2,3 [--seconds 10]

For each seed it drives one run of the cell (set-up, a short window at
the cell's load, the reference) and prints one JSON line with:

- ``program``: the numbers the run compares (what sound runs read);
- ``control``: the same numbers with the plain reference put in the
  program's place, computed in bfloat16;
- ``default``: the same with the reference at the default matrix-product
  precision (one bfloat16 pass on the TPU, as the program's float32
  products take): how far rounding alone moves each number;
- ``faults``: the numbers with a fault planted in the reference put in the
  program's place: half of every batch left out (the loss the mean over
  the rest), and rounds that hand back the state they started from.

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


def fleet_readings(ref, seed, model, lora, env, recorded):
    """Program, control, rounding and fault readings of one fleet run."""
    import jax.numpy as jnp

    def replay(**kw):
        return ref.replay(seed, model, lora, env, recorded, **kw)

    honest = replay()
    against = lambda view: ref.compare(view, honest, recorded)
    return {"program": against(ref.program_view(recorded)),
            "control": against(replay(dt=jnp.bfloat16)),
            "default": against(replay(precision="default")),
            "faults": {"half_batch": against(replay(half_batch=True)),
                       "state_unchanged": against(
                           ref.unchanged(honest, recorded))}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    manifest = Manifest.load()
    from bench.run import prepare
    from bench.reference import fleet as ref
    inner = ref.check_rounds
    got = {}

    def check_rounds(seed_, model, lora, env, recorded):
        got.update(fleet_readings(ref, seed_, model, lora, env, recorded))
        return got["program"]

    ref.check_rounds = check_rounds
    for seed in [int(s) for s in args.seeds.split(",")]:
        ns = argparse.Namespace(workload=args.workload, seed=seed,
                                seconds=args.seconds, trace=0)
        run = prepare(ns, manifest)
        run.t_start = time.perf_counter()
        driver = harness.load_module(
            harness.BENCH / "drivers" / f"{run.traffic['driver']}.py")
        got.clear()
        with harness.CompileLog() as log:
            run.compile_log = log
            driver.run(run)
        print(json.dumps({"workload": args.workload, "seed": seed, **got}),
              flush=True)
    ref.check_rounds = inner
    return 0


if __name__ == "__main__":
    sys.exit(main())
