"""Serving example: train → checkpoint → serve (DESIGN.md §5).

Trains a tiny federated fleet for a few rounds (checkpointing each round),
then serves the TRAINED per-task adapters from the checkpoint through the
multi-tenant ServeEngine: every lane is a tenant holding a (task, RSU,
version) adapter at its own rank, all rank-padded into one compiled decode
program — hot-swapping tenants mid-stream never recompiles. The second
half of the stream runs continuous batching: tenants retire and new ones
admit mid-stream through the AdapterStore, sibling lanes undisturbed.
With --block-size > 0 the KV caches are block-paged (core/kv_blocks.py)
and retired tenants' blocks recycle to the new admissions.

    PYTHONPATH=src python examples/serve_decode.py --tokens 24 --block-size 8
"""
import argparse
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import CheckpointSpec, LoRAConfig, ServeSpec
from repro.launch.adapter_cache import AdapterStore
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import ServeEngine
from repro.models import transformer as T
from repro.sim.simulator import IoVSimulator, SimConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--tokens", type=int, default=24)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=0,
                    help="KV block size (> 0 pages the caches; 0 = dense)")
    args = ap.parse_args()
    enable_compile_cache()

    with tempfile.TemporaryDirectory() as ckpt_dir:
        # -- train a small fleet, checkpointing every round -------------
        cfg = SimConfig(
            method="ours", num_tasks=2, num_vehicles=6,
            rounds=args.rounds, local_steps=2,
            lora=LoRAConfig(rank=4, max_rank=8, candidate_ranks=(2, 4, 8)),
            checkpoint=CheckpointSpec(interval=1, dir=ckpt_dir),
            seed=0)
        sim = IoVSimulator(cfg)
        t0 = time.time()
        sim.run()
        print(f"trained {cfg.num_tasks} tasks × {args.rounds} rounds "
              f"in {time.time() - t0:.1f}s (checkpoints in {ckpt_dir})")

        # -- serve the trained adapters straight from the checkpoint ----
        cache_len = args.tokens + 8
        if args.block_size:
            cache_len += (-cache_len) % args.block_size
        spec = ServeSpec(max_batch=args.lanes, cache_len=cache_len,
                         block_size=args.block_size,
                         admission="evict_oldest")
        store = AdapterStore.from_checkpoint(cfg, ckpt_dir, spec=spec)
        # the frozen base weights are reproducible from the config seed —
        # exactly how IoVSimulator builds them
        params = T.init_params(jax.random.PRNGKey(cfg.seed), sim.model_cfg,
                               dtype=jnp.float32)
        engine = ServeEngine(params, sim.model_cfg, cfg.lora, spec)

        # one tenant per lane: cycle tasks × ranks (mixed-rank batch)
        ranks = cfg.lora.candidate_ranks
        for lane in range(engine.max_batch):
            task = lane % store.num_tasks
            paged = store.get(task, rank=ranks[lane % len(ranks)])
            engine.assign(lane, paged)
            print(f"lane {lane}: task {paged.task} rsu {paged.rsu} "
                  f"v{paged.version} rank {paged.rank} "
                  f"(slot {paged.slot_rank})")

        rng = np.random.default_rng(0)
        prompts = rng.integers(0, sim.model_cfg.vocab_size,
                               (engine.max_batch, 4))
        t0 = time.time()
        gen = engine.generate(prompts, args.tokens // 2)

        # continuous batching: retire one tenant mid-service and admit
        # new ones through the store — sibling lanes keep decoding
        # bit-undisturbed, the compiled program never changes, and (paged)
        # the retired lane's KV blocks recycle to the newcomers
        engine.retire(0)
        for lane in range(engine.max_batch):
            task = (lane + 1) % store.num_tasks
            store.admit(engine, task,
                        rank=ranks[(lane + 1) % len(ranks)], lane=lane)
        gen2 = engine.generate(prompts, args.tokens - args.tokens // 2)
        dt = time.time() - t0

        total = gen.shape[1] + gen2.shape[1] + 2 * (prompts.shape[1] - 1)
        print(f"served {engine.max_batch} lanes × {total} steps in "
              f"{dt:.1f}s ({engine.max_batch * total / dt:.1f} tok/s), "
              f"{engine.swaps} hot swaps ({engine.admits} admits / "
              f"{engine.retires} retires), "
              f"{engine.compile_count} decode compile(s), "
              f"adapter cache {store.cache.hits} hits / "
              f"{store.cache.misses} misses")
        if engine.paged:
            stats = engine.allocator_stats()
            print(f"block pool: {stats['num_blocks']} blocks, "
                  f"high water {stats['high_water']}, "
                  f"{stats['recycles']} recycled "
                  f"(reuse rate {stats['reuse_rate']:.2f})")
        print("sample stream:", np.concatenate([gen[0], gen2[0]])[:16])


if __name__ == "__main__":
    main()
