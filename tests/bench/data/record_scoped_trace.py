#!/usr/bin/env python3
"""Record the small TPU trace that tests/bench/test_program_trace.py
decodes: a jitted ``_round_step`` with two device scopes, ``round.train``
inside ``vmap(grad(...))`` and ``round.global_eval`` outside it, run once
a round inside the program's host spans ``repro.round`` (a step) and
``repro.stage`` (with arguments); then each scope's work alone, as its own
program, for the expected split.

    python3 tests/bench/data/record_scoped_trace.py <out_dir>

Writes ``scoped.xplane.pb`` and ``scoped.json`` (what the test expects) to
out_dir. The expected split comes from the ``XLA Modules`` events of the
programs run alone, read through ``jax.profiler.ProfileData``, which knows
nothing of scopes. Needs a TPU.
"""
import glob
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

ROUNDS = 4
LANES, N, D = 16, 1024, 1024


def main(out: Path) -> None:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    from bench.harness import require_chips
    from repro.telemetry import Telemetry, scope
    require_chips(1)

    def loss(wi, xi):
        with scope("round.train"):
            return jnp.sum(jnp.tanh(xi @ wi) ** 2)

    def train(w, x):
        return jax.vmap(jax.grad(loss))(w, x)

    def evaluate(g, q):
        h = g.mean(axis=0)
        for _ in range(4):
            h = jnp.tanh(h @ q)
        return h.sum()

    def _round_step(w, x, q):
        g = train(w, x)
        # no fusion across the two stages, so that each runs as it does
        # alone (the program's own stages may fuse across a boundary)
        g = jax.lax.optimization_barrier(g)
        with scope("round.global_eval"):
            e = evaluate(g, q)
        return g, e

    k = jax.random.split(jax.random.PRNGKey(0), 3)
    w = jax.random.normal(k[0], (LANES, D, D)) / D ** 0.5
    x = jax.random.normal(k[1], (LANES, N, D))
    q = jax.random.normal(k[2], (D, D)) / D ** 0.5
    step, alone_train, alone_eval = (jax.jit(_round_step), jax.jit(train),
                                     jax.jit(evaluate))
    g, _ = jax.block_until_ready(step(w, x, q))
    jax.block_until_ready((alone_train(w, x), alone_eval(g, q)))

    tel = Telemetry()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    args = []
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for r in range(ROUNDS):
        with tel.step("round", r):
            with tel.span("stage", round=r) as sp:
                time.sleep(0.002)
                args.append({"round": r, "staged_steps": 10 + r,
                             "lane_steps": 24})
                sp.set(staged_steps=10 + r, lane_steps=24)
            with tel.span("dispatch", round=r):
                res = step(w, x, q)
            with tel.span("wait", round=r):
                jax.block_until_ready(res)
    for _ in range(ROUNDS):
        jax.block_until_ready(alone_train(w, x))
    for _ in range(ROUNDS):
        jax.block_until_ready(alone_eval(g, q))
    jax.profiler.stop_trace()

    src = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    modules = {}
    for plane in ProfileData.from_file(src).planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        name = e.name.split("(")[0]
                        modules.setdefault(name, []).append(
                            e.duration_ns / 1e6)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out / "scoped.xplane.pb")
    (out / "scoped.json").write_text(json.dumps({
        "rounds": ROUNDS,
        "program_ms": statistics.median(modules["jit__round_step"]),
        "alone_ms": {"round.train": statistics.median(modules["jit_train"]),
                     "round.global_eval": statistics.median(
                         modules["jit_evaluate"])},
        "stage_args": args,
        "device_kind": jax.devices()[0].device_kind,
    }, indent=1) + "\n")


if __name__ == "__main__":
    main(Path(sys.argv[1]))
