#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell comes from ``BENCHMARK.json``; its configuration, traffic and
limits from the files named after them under ``bench/``. The traffic file
names the driver (``bench/drivers/<driver>.py``) that builds the system,
warms it up and drives the measured window. With ``--trace 0`` the result
holds the cell's end-to-end metrics; with ``--trace 1`` the window runs
under the profiler and the result holds its per-layer metrics, each read
by ``bench/metrics/<metric>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with a trace,
``breakdown``), then ``checks``: each number compared with the plain
reference beside its limit. The same comparisons end standard error.
A run without a TPU, or with fewer chips than the cell asks for, exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime's logs stay inside the checkout, not at a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_cache" / "tpu_logs"))

from bench import harness  # noqa: E402
from bench.harness import BenchError, Manifest  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare(args, manifest: Manifest):
    """The run context for a cell: files read, chips checked, cache on."""
    cell = manifest.workload(args.workload)
    cfg_entry = manifest.config(cell["config"])
    devices = harness.require_chips(cell["chips"])
    harness.enable_compile_cache()
    trace_dir = None
    if args.trace:
        trace_dir = harness.ROOT / ".bench_cache" / "trace" / cell["name"]
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    bench = harness.BENCH
    return harness.Run(
        cell=cell,
        config=harness.load_json(harness.ROOT / cfg_entry["file"]),
        traffic=harness.load_json(bench / "traffic" /
                                  f"{cell['traffic']}.json"),
        limits=harness.load_json(bench / "limits" / f"{cell['name']}.json"),
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devices=devices, compile_log=None, t_start=T_START,
        trace_dir=trace_dir)


def main(argv=None) -> int:
    args = parse(argv)
    manifest = Manifest.load()
    run = prepare(args, manifest)
    driver = harness.load_module(
        harness.BENCH / "drivers" / f"{run.traffic['driver']}.py")
    with harness.CompileLog() as log:
        run.compile_log = log
        outcome = driver.run(run)
    result = harness.result_line(run, manifest, outcome)
    for c in outcome.checks:
        print(f"check {c.name} = {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchError:
        raise
