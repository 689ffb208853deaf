#!/usr/bin/env python3
"""Record the small TPU trace that tests/bench/test_bench_harness.py
reduces: one fused LoRA-linear kernel call after another inside harness
spans, with a host-only sleep between them.

    python3 tests/bench/data/record_trace.py <out_dir>

Writes ``small.xplane.pb`` and ``small.json`` (what the test expects) to
out_dir. Needs a TPU.
"""
import glob
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out: Path) -> None:
    import jax
    from bench.harness import Spans, require_chips
    from repro.kernels.lora_matmul import lora_matmul
    require_chips(1)
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(k[0], (256, 1024))
    w = jax.random.normal(k[1], (1024, 512))
    a = jax.random.normal(k[2], (1024, 32))
    b = jax.random.normal(k[3], (32, 512))
    f = jax.jit(lambda *t: lora_matmul(*t, scale=2.0))
    f(x, w, a, b).block_until_ready()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    spans = Spans()
    sleep_s = 0.2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with spans.span("round"):
        with spans.span("kernel"):
            for _ in range(5):
                f(x, w, a, b).block_until_ready()
        with spans.span("sleep"):
            time.sleep(sleep_s)
        with spans.span("kernel"):
            f(x, w, a, b).block_until_ready()
    jax.profiler.stop_trace()
    name, t0, t1 = spans.records[-1]
    src = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out / "small.xplane.pb")
    (out / "small.json").write_text(json.dumps(
        {"window_s": t1 - t0, "sleep_s": sleep_s,
         "kernel": "lora_matmul"}) + "\n")


if __name__ == "__main__":
    main(Path(sys.argv[1]))
