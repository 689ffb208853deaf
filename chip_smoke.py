#!/usr/bin/env python3
"""Chip smoke test: the fused fleet round and the serving tier at ViT-Base
width (12 layers, d_model 768, 12 heads, d_ff 3072) on a TPU.

    python chip_smoke.py                # one chip: the phases below
    python chip_smoke.py --four-chips   # fused_sharded over 4 chips vs fused

One process drives everything. Phases, in order:

1. device check — a platform other than ``tpu`` exits non-zero; the
   script never falls back to the CPU;
2. fleet round — ``IoVSimulator(engine="fused")`` with the ``SimConfig``
   defaults (24 vehicles, 3 tasks, 3 local steps, batch 10, ranks
   2..32): two rounds through ``run()``, two through ``run_scanned(2)``;
   finite accuracy/energy/reward, one compile of the round body and one
   of the scan;
3. kernels — the round program must hold compiled Pallas kernels
   (``tpu_custom_call``) under the default ``"auto"`` dispatch;
4. serving — ``AdapterStore.from_sim`` + ``ServeEngine`` dense and paged,
   4 mixed-rank tenants, 32 decoded tokens each through a mid-stream
   retire + admit, one decode compile each, paged streams equal to the
   dense ones token for token;
5. references, once the main simulator is released (two full-width
   fleets do not fit one chip together): round 1 again on the jnp paths,
   compared with the kernel round, and ``engine="fused_check"``, which
   replays the serial trainer — its deviation must stay within
   ``CHECK_REL_BOUND`` while faults planted in the replay land above it.

The last line of standard output is one JSON object naming the device;
it is printed only when every phase passed. All inputs are generated from
``--seed``. Times and byte counts printed on the way are smoke output, not
benchmark metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import logging
import math
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# fused_check replays the serial trainer on the fused round's batches and
# starting adapters. Its measure is the fused engine's
# adapter_deviation: for each vehicle, ||fused − serial|| / ||serial −
# start||, the fused result's distance from the replay as a share of the
# replay's own update. Both sides run f32 matmuls at the TPU's DEFAULT
# precision (one bf16 MXU pass) in differently fused programs, so a few
# entries whose gradient is near zero take opposite Adam steps (±lr each)
# while the update as a whole agrees. On a v5e the worst vehicle read
# 0.1334 (its worst entry: fused +1.326e-2, serial −1.308e-2, of a
# possible lr·local_steps = 1.5e-2 each way), and faults planted in the
# replay read 0.83 to 1.37. The bound sits between, 2.2x above that
# reading and 2.8x below the mildest fault: the planted faults of
# plant_faults() must land above it.
CHECK_REL_BOUND = 0.3


# four-chip comparison: accuracy is quantized by the eval-set size, and
# the sharded merge reassociates the fleet sums (per-shard partials, then
# an all-reduce), so one borderline argmax may flip; energy is priced from
# the exactly matching ranks and channel draws.
ACC_TOL = 0.02
ENERGY_RTOL = 1e-4


class CompileLog(logging.Handler):
    """Collects ``(function, seconds)`` for every XLA compilation."""

    _PAT = re.compile(r"Finished XLA compilation of (\S+) in ([0-9.e+-]+) sec")

    def __init__(self):
        super().__init__()
        self.events = []

    def emit(self, record):
        m = self._PAT.search(record.getMessage())
        if m:
            self.events.append((m.group(1), float(m.group(2))))

    def count(self, name, since=0):
        return sum(1 for n, _ in self.events[since:] if n == name)

    def seconds(self, since=0):
        return sum(s for _, s in self.events[since:])

    def __enter__(self):
        logger = logging.getLogger("jax._src.dispatch")
        self._saved = logger.level, logger.propagate
        logger.setLevel(logging.DEBUG)
        logger.propagate = False    # keep the DEBUG records off stderr
        logger.addHandler(self)
        return self

    def __exit__(self, *exc):
        logger = logging.getLogger("jax._src.dispatch")
        logger.removeHandler(self)
        logger.setLevel(self._saved[0])
        logger.propagate = self._saved[1]


def require(cond, what):
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def finite_history(history):
    return all(math.isfinite(r[k]) for r in history
               for k in ("accuracy", "energy", "reward"))


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def sim_config(arch, seed, **kw):
    from repro.sim.simulator import SimConfig
    return SimConfig(train_arch=arch, rounds=4, seed=seed, **kw)


def capture_round_args(sim):
    """Wrap the fused engine's jitted round so that its first call's
    argument shapes are kept (not the arrays: they would pin a stale fleet
    state in device memory), to lower that program again. Returns the
    jitted round and the list the shapes land in; put the jitted round
    back on ``sim.fused._jit_round`` when done."""
    import jax
    jit_round = sim.fused._jit_round
    first_args = []

    def spy(*args):
        if not first_args:
            first_args.extend(jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=getattr(a, "sharding", None),
                    weak_type=getattr(a, "weak_type", False)), args))
        return jit_round(*args)

    sim.fused._jit_round = spy
    return jit_round, first_args


def kernel_sites(jit_round, args):
    """Compiled Pallas kernel calls in the round program."""
    return jit_round.lower(*args).as_text().count("tpu_custom_call")


def plant_faults(sim):
    """Wrap the serial replay of a ``fused_check`` simulator so that, at
    the first vehicle whose predecessor in the replay ran as many local
    steps, it also trains three faulted copies from the same start:

    - wrong batch: the predecessor's batches;
    - half the batch dropped: the first half of each batch, twice (same
      shape, the gradient of half the examples);
    - wrong labels: each batch's labels shifted by one example.

    Returns a dict that the replay fills with each fault's
    ``adapter_deviation`` share from the honest replay."""
    import numpy as np
    from repro.federated.fused_engine import adapter_deviation

    honest = sim.trainer.finetune
    found = {}
    prev = []

    def replay(params, start, data, steps, *a, batches=None, **kw):
        ref, metrics = honest(params, start, data, steps, *a,
                              batches=batches, **kw)
        if not found and prev and len(prev) == len(batches):
            half = batches[0]["labels"].shape[0] // 2
            planted = {
                "wrong batch": prev,
                "half the batch dropped": [
                    {k: np.concatenate([v[:half], v[:half]]) for k, v in
                     b.items()} for b in batches],
                "wrong labels": [
                    {"tokens": b["tokens"],
                     "labels": np.roll(b["labels"], 1)} for b in batches],
            }
            for name, bad_batches in planted.items():
                bad, _ = honest(params, start, data, steps,
                                batches=bad_batches)
                found[name] = adapter_deviation(bad, ref, start)[1]
        prev[:] = batches
        return ref, metrics

    sim.trainer.finetune = replay
    return found


def watch_worst_entry():
    """Keep the vehicle whose fused result differs most from the serial
    replay (largest single entry) while ``fused_check`` compares them,
    by wrapping ``fused_engine.adapter_deviation``. Returns the record
    (filled during the round) and a function that undoes the wrapping."""
    import numpy as np
    from repro.federated import fused_engine

    measure = fused_engine.adapter_deviation
    worst = {}

    def watched(got, ref, start):
        d_max, d_rel = measure(got, ref, start)
        if d_max > worst.get("d_max", -1.0):
            import jax
            leaves = [np.asarray(x) for t in (got, ref, start)
                      for x in jax.tree_util.tree_leaves(t)]
            n = len(leaves) // 3
            diffs = [np.abs(g - r) for g, r in zip(leaves[:n],
                                                   leaves[n:2 * n])]
            li = int(np.argmax([d.max() for d in diffs]))
            ix = np.unravel_index(int(np.argmax(diffs[li])),
                                  diffs[li].shape)
            g, r, s0 = (leaves[li + j * n][ix] for j in range(3))
            worst.update(d_max=d_max, d_rel=d_rel,
                         fused_step=float(g - s0), serial_step=float(r - s0))
        return d_max, d_rel

    fused_engine.adapter_deviation = watched

    def undo():
        fused_engine.adapter_deviation = measure
    return worst, undo


def fleet_phase(arch, seed, log):
    """Phase 2 and the kernel check of phase 3: four rounds of the fused
    engine at full width. Returns the simulator and its round-1 state."""
    import jax
    from repro.models import runmode
    from repro.models.attention import attention_path
    from repro.sim.simulator import IoVSimulator

    cfg = sim_config(arch, seed, engine="fused")
    t0 = time.perf_counter()
    sim = IoVSimulator(cfg)
    print(f"fleet: {arch.name} L={arch.num_layers} d_model={arch.d_model} "
          f"heads={arch.num_heads} d_ff={arch.d_ff}; "
          f"{cfg.num_vehicles} vehicles x {cfg.num_tasks} tasks, "
          f"local_steps={cfg.local_steps} batch={cfg.batch_size} "
          f"ranks={cfg.lora.candidate_ranks}; built in "
          f"{time.perf_counter() - t0:.1f}s")
    print(f"dispatch: attention path at the round's 24-token shape = "
          f"{attention_path(causal=True, Sq=24, Sk=24)}, LoRA GEMM = "
          f"{'lora_matmul kernel' if runmode.lora_kernel_enabled() else 'jnp'}"
          f" (interpret={runmode.kernel_interpret()}); f32 matmul "
          f"precision = {jax.config.jax_default_matmul_precision or 'DEFAULT'}"
          f" (DEFAULT on a TPU: one bf16 pass of the MXU)")

    jit_round, first_args = capture_round_args(sim)
    mark = len(log.events)
    t0 = time.perf_counter()
    sim.run(1)
    t_first = time.perf_counter() - t0
    # round 1 on the host, for the jnp comparison after this simulator's
    # device state is released
    round1 = (jax.device_get([srv.merged for srv in sim.servers]),
              sim.history[0]["accuracy"])
    t0 = time.perf_counter()
    sim.run(1)
    t_round = time.perf_counter() - t0
    t0 = time.perf_counter()
    sim.run_scanned(2)
    t_scan = time.perf_counter() - t0
    sim.fused._jit_round = jit_round
    for rec in sim.history:
        print(f"round {rec['round']}: acc={rec['accuracy']:.4f} "
              f"energy={rec['energy']:.2f} reward={rec['reward']:.4f} "
              f"ranks={[round(t['mean_rank'], 2) for t in rec['tasks']]}")
    require(len(sim.history) == 4, "four rounds recorded")
    require(finite_history(sim.history),
            "finite accuracy, energy and reward in every round")
    n_round = log.count("jit(_round_step)", mark)
    n_scan = log.count("jit(run)", mark)
    print(f"fleet timing: first round {t_first:.2f}s (compile "
          f"included), round after warm-up {t_round:.3f}s, run_scanned(2) "
          f"{t_scan:.2f}s (compile included); compiles: round body "
          f"{n_round}, scan {n_scan}; compile seconds "
          f"{log.seconds(mark):.1f}; peak_bytes_in_use "
          f"{peak_bytes(jax.devices()[0])}")
    require(n_round == 1, f"round body compiled once (got {n_round})")
    require(n_scan == 1, f"scanned rounds compiled once (got {n_scan})")

    n_kernels = kernel_sites(jit_round, first_args)
    print(f"kernels: {n_kernels} tpu_custom_call sites in the round program")
    require(n_kernels > 0, "compiled Pallas kernels in the round program")

    return sim, round1


def reference_phase(arch, seed, log, round1):
    """Phase 2's serial check and phase 3's jnp comparison. Run after the
    main simulator is released: each of these simulators holds its own
    fleet state, and at full width two at once do not fit one chip."""
    import jax
    import numpy as np
    from repro.models import runmode
    from repro.sim.simulator import IoVSimulator

    cfg = sim_config(arch, seed, engine="fused")
    # the same first round on the jnp paths (dispatch is read at trace
    # time, so the override must cover the round's first call)
    with runmode.overrides(USE_PALLAS_ATTN=False, USE_PALLAS_LORA=False):
        ref = IoVSimulator(cfg)
        ref.run(1)
    merged, acc = round1
    d_merged = max(
        float(np.max(np.abs(a - np.asarray(b)))) for a, b in
        zip(jax.tree_util.tree_leaves(merged),
            jax.tree_util.tree_leaves([srv.merged for srv in ref.servers])))
    d_acc = abs(acc - ref.history[0]["accuracy"])
    print(f"kernel vs jnp round 1: max |merged delta diff| = {d_merged:.3e}, "
          f"|accuracy diff| = {d_acc:.4f}")
    require(finite_history(ref.history) and math.isfinite(d_merged),
            "finite jnp-path round")
    del ref
    gc.collect()

    mark = len(log.events)
    check = IoVSimulator(dataclasses.replace(cfg, engine="fused_check"))
    faults = plant_faults(check)
    worst, undo = watch_worst_entry()
    try:
        check.run(1)
    finally:
        undo()
    rel, dev = check.engine_check_rel, check.engine_check_dev
    print(f"fused_check: max over vehicles of ||fused - serial|| / "
          f"||serial - start|| = {rel:.3e} (bound {CHECK_REL_BOUND}); max "
          f"|fused - serial| entry {dev:.3e}; {len(log.events) - mark} "
          f"compiles, {log.seconds(mark):.1f}s")
    step = cfg.lr * cfg.local_steps
    print(f"fused_check worst entry: fused update "
          f"{worst['fused_step']:+.3e}, serial update "
          f"{worst['serial_step']:+.3e} (lr*local_steps = {step:.1e}; "
          f"that vehicle's share {worst['d_rel']:.3e})")
    require(math.isfinite(rel) and rel <= CHECK_REL_BOUND,
            f"fused_check relative deviation {rel:.3e} within "
            f"{CHECK_REL_BOUND}")
    require(len(faults) == 3, "faults planted in the serial replay")
    for name, share in faults.items():
        print(f"planted fault, {name}: share {share:.3e}")
        require(share > CHECK_REL_BOUND,
                f"planted fault '{name}' ({share:.3e}) exceeds the bound "
                f"{CHECK_REL_BOUND}")


def serve_phase(sim, seed, block_size):
    """Phase 4: mixed-rank tenants through ServeEngine, with a retire and
    an admit mid-stream. Returns the generated streams."""
    import jax.numpy as jnp
    import numpy as np
    from repro.config import ServeSpec
    from repro.launch.adapter_cache import AdapterStore
    from repro.launch.serve import ServeEngine

    prompt, half = 4, 16
    spec = ServeSpec(max_batch=4, cache_len=48, block_size=block_size,
                     admission="evict_oldest")
    store = AdapterStore.from_sim(sim, spec)
    engine = ServeEngine(sim.params, sim.model_cfg, sim.cfg.lora, spec)
    ranks = (2, 8, 16, 32)
    for lane, r in enumerate(ranks):
        engine.assign(lane, store.get(lane % store.num_tasks, rank=r))
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, sim.model_cfg.vocab_size, (4, prompt))
    t0 = time.perf_counter()
    first = engine.generate(prompts, half)
    engine.retire(1)
    store.admit(engine, 2 % store.num_tasks, rank=4, lane=1)
    second = engine.generate(prompts, half)
    logits = engine.step(second[:, -1])
    dt = time.perf_counter() - t0
    gen = np.concatenate([first, second], axis=1)
    kind = f"paged(block {block_size})" if block_size else "dense"
    print(f"serve {kind}: {gen.shape[0]} tenants x {gen.shape[1]} tokens, "
          f"ranks {ranks} then rank 4 admitted on lane 1; "
          f"{engine.admits} admit / {engine.retires} retire; "
          f"{engine.compile_count} decode compile(s); {dt:.2f}s "
          f"(compile included)")
    require(engine.compile_count == 1,
            f"{kind} decode compiled once (got {engine.compile_count})")
    require(engine.admits == 1 and engine.retires == 1,
            f"{kind} retire + admit")
    require(gen.shape == (4, 2 * half), f"{kind} 32 tokens per tenant")
    require(((gen >= 0) & (gen < sim.model_cfg.vocab_size)).all(),
            f"{kind} token ids in the vocabulary")
    require(bool(jnp.isfinite(logits).all()), f"{kind} finite logits")
    return gen


def four_chip_phase(arch, seed, log):
    """fused_sharded over 4 chips vs fused on chip 0, same config."""
    from repro.config import ShardSpec
    from repro.sim.simulator import IoVSimulator

    import jax
    rounds = 3   # every arm still unexplored: rank choice is float-free
    hist = {}
    # the sharded round first: it is the program under test
    for engine, shard in (("fused_sharded", ShardSpec(num_shards=4)),
                          ("fused", ShardSpec())):
        cfg = sim_config(arch, seed, engine=engine, shard=shard)
        mark = len(log.events)
        t0 = time.perf_counter()
        sim = IoVSimulator(cfg)
        jit_round, first_args = capture_round_args(sim)
        sim.run(rounds)
        sim.fused._jit_round = jit_round
        n = log.count("jit(_round_step)", mark)
        print(f"{engine}: {rounds} rounds in {time.perf_counter() - t0:.1f}s"
              f" (compile included), {n} round-body compile(s), shards "
              f"{sim.fused.n_shards}; peak_bytes_in_use on chip 0 "
              f"{peak_bytes(jax.devices()[0])}")
        require(n == 1, f"{engine} round body compiled once (got {n})")
        if sim.fused.mesh is not None:
            # the one-chip round's kernels are checked by the default run
            n_kernels = kernel_sites(jit_round, first_args)
            print(f"{engine}: {n_kernels} tpu_custom_call sites in the "
                  f"sharded round program")
            require(n_kernels > 0, f"{engine} runs the compiled kernels")
        require(finite_history(sim.history), f"{engine} finite history")
        hist[engine] = sim.history
        del sim
        gc.collect()
    worst_acc = worst_energy = 0.0
    for ra, rb in zip(hist["fused"], hist["fused_sharded"]):
        for ta, tb in zip(ra["tasks"], rb["tasks"]):
            for k in ("active", "departing", "handoffs", "comm_params",
                      "mean_rank"):
                require(ta[k] == tb[k], f"round {ra['round']} task "
                        f"{ta['task']}: {k} {ta[k]} != {tb[k]}")
        worst_acc = max(worst_acc, abs(ra["accuracy"] - rb["accuracy"]))
        worst_energy = max(worst_energy, abs(ra["energy"] - rb["energy"])
                           / max(abs(ra["energy"]), 1e-12))
        print(f"round {ra['round']}: acc {ra['accuracy']:.4f} vs "
              f"{rb['accuracy']:.4f}, energy {ra['energy']:.4f} vs "
              f"{rb['energy']:.4f}, mean ranks "
              f"{[t['mean_rank'] for t in ra['tasks']]}")
    print(f"four-chip vs one-chip: ranks/active/handoffs identical over "
          f"{rounds} rounds; max |accuracy diff| {worst_acc:.4f} (tol "
          f"{ACC_TOL}), max energy rel diff {worst_energy:.2e} (tol "
          f"{ENERGY_RTOL:.0e})")
    require(worst_acc <= ACC_TOL, "accuracy within tolerance")
    require(worst_energy <= ENERGY_RTOL, "energy within tolerance")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only fused_sharded over 4 chips against "
                         "fused on chip 0")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform "
                         f"{dev.platform!r}); refusing to run on the CPU")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        raise SystemExit(f"chip_smoke: needs {want} chips, found "
                         f"{len(devices)}")
    print(f"device: {dev.device_kind} x{len(devices)}, jax {jax.__version__}")

    from repro.configs.vit_base_paper import vit_base_paper
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    arch = vit_base_paper()
    with CompileLog() as log:
        if args.four_chips:
            four_chip_phase(arch, args.seed, log)
        else:
            sim, round1 = fleet_phase(arch, args.seed, log)
            dense = serve_phase(sim, args.seed, block_size=0)
            paged = serve_phase(sim, args.seed, block_size=16)
            same = int((dense == paged).sum())
            print(f"serve dense vs paged: {same}/{dense.size} tokens equal")
            # the paged KV cache gathers the same entries the dense ring
            # holds: greedy streams must agree token for token
            require(same == dense.size, "paged decode equals dense decode")
            del sim
            gc.collect()
            reference_phase(arch, args.seed, log, round1)
        print(f"total compile seconds {log.seconds():.1f} over "
              f"{len(log.events)} compilations; peak_bytes_in_use "
              f"{peak_bytes(dev)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
