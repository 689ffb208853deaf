"""Distributed train step factory + training driver.

The unit of work is the paper-faithful federated local step: LoRA
fine-tuning of the adapter pytree over a frozen base (DESIGN.md §3), run
under pjit on the production mesh. Gradients reduce over (`pod`, `data`);
tensor/expert parallelism over `model`.

Also usable as a CLI for the end-to-end example:
    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --steps 200
(CPU: uses the reduced config unless --full.)

The CLI is resumable (DESIGN.md §7): ``--checkpoint-every N`` writes
adapters + Adam state + the data RNG cursor to ``--checkpoint-dir`` every
N steps (atomic npz, keyed by a config fingerprint), and ``--resume``
restores the latest one and continues the step loop bit-identically:
    PYTHONPATH=src python -m repro.launch.train --steps 200 \
        --checkpoint-every 50 --checkpoint-dir /tmp/lm-ckpt [--resume]
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import LoRAConfig, ModelConfig
from repro.launch import sharding as sh
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.optim import adam, apply_updates


def make_train_step(cfg: ModelConfig, lora: LoRAConfig, mesh, *,
                    lr: float = 1e-4, remat: bool = True,
                    seq_shard: bool = True, sliding_window=None,
                    donate: bool = True, scan_unroll: int = 1,
                    ce_chunk: int = 0, microbatch: int = 1):
    """Returns (step_fn, shardings dict). step(params, adapters, opt_state,
    batch) -> (adapters, opt_state, metrics). Differentiates adapters only.
    microbatch > 1: gradient accumulation — splits the global batch into
    `microbatch` sequential slices (activation memory ∝ 1/microbatch at
    identical math; §Perf iter 6)."""
    opt = adam(lr)
    constrain = sh.make_constrain(mesh, seq_shard)

    def loss_of(params, ad, batch):
        return T.loss_fn(params, ad, cfg, lora, batch, remat=remat,
                         constrain=constrain, scan_unroll=scan_unroll,
                         ce_chunk=ce_chunk)

    def step(params, adapters, opt_state, batch):
        if microbatch > 1:
            def resplit(t):
                return t.reshape((microbatch, t.shape[0] // microbatch)
                                 + t.shape[1:])
            mb = jax.tree_util.tree_map(resplit, batch)

            def body(carry, b):
                g_acc, m_acc = carry
                (_, metrics), grads = jax.value_and_grad(
                    lambda ad: loss_of(params, ad, b), has_aux=True
                )(adapters)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, grads)
                m_acc = jax.tree_util.tree_map(jnp.add, m_acc, metrics)
                return (g_acc, m_acc), None

            g0 = jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, jnp.float32), adapters)
            m0 = {"loss": jnp.zeros((), jnp.float32),
                  "aux": jnp.zeros((), jnp.float32),
                  "accuracy": jnp.zeros((), jnp.float32)}
            from repro.models import runmode
            (grads, metrics), _ = jax.lax.scan(
                body, (g0, m0), mb,
                unroll=runmode.inner_unroll(microbatch))
            grads = jax.tree_util.tree_map(lambda g: g / microbatch, grads)
            metrics = jax.tree_util.tree_map(lambda m: m / microbatch,
                                             metrics)
        else:
            (_, metrics), grads = jax.value_and_grad(
                lambda ad: loss_of(params, ad, batch), has_aux=True
            )(adapters)
        updates, opt_state = opt.update(grads, opt_state, adapters)
        adapters = apply_updates(adapters, updates)
        return adapters, opt_state, metrics

    def shardings_for(params, adapters, opt_state, batch):
        from repro.optim.adam import AdamState
        ps = sh.tree_shardings(mesh, params)
        ads = sh.tree_shardings(mesh, adapters, is_adapter=True)
        os_ = AdamState(
            step=NamedSharding(mesh, P()),
            mu=sh.tree_shardings(mesh, opt_state.mu, is_adapter=True),
            nu=sh.tree_shardings(mesh, opt_state.nu, is_adapter=True))
        bs = sh.batch_shardings(mesh, batch)
        return ps, ads, os_, bs

    def jit_step(params, adapters, opt_state, batch):
        """Returns the jitted step with explicit in/out shardings, given
        abstract (or concrete) arguments."""
        ps, ads, os_, bs = shardings_for(params, adapters, opt_state, batch)
        metrics_sh = {k: NamedSharding(mesh, P())
                      for k in ("loss", "aux", "accuracy")}
        return jax.jit(
            step,
            in_shardings=(ps, ads, os_, bs),
            out_shardings=(ads, os_, metrics_sh),
            donate_argnums=(1, 2) if donate else ())

    return step, jit_step


def abstract_state(cfg: ModelConfig, lora: LoRAConfig, *, rank: int,
                   dtype=jnp.bfloat16):
    """ShapeDtypeStruct pytrees for params/adapters/opt_state (no alloc)."""
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(
        functools.partial(T.init_params, cfg=cfg, dtype=dtype), key)
    adapters = jax.eval_shape(
        functools.partial(T.init_adapters, cfg=cfg, lora=lora,
                          dtype=jnp.float32, rank=rank), key)
    opt = adam(1e-4)
    opt_state = jax.eval_shape(opt.init, adapters)
    return params, adapters, opt_state


# ---------------------------------------------------------------------------
# CLI driver (end-to-end example entry point)
# ---------------------------------------------------------------------------

def main():
    import argparse
    import hashlib
    import json
    import time

    import numpy as np

    parser = argparse.ArgumentParser()
    parser.add_argument("--arch", default="smollm-135m")
    parser.add_argument("--steps", type=int, default=50)
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--seq", type=int, default=64)
    parser.add_argument("--rank", type=int, default=8)
    parser.add_argument("--lr", type=float, default=1e-3)
    parser.add_argument("--full", action="store_true",
                        help="use the full (not reduced) config; with "
                             "--simulate, train ViT-Base at its published "
                             "widths")
    parser.add_argument("--pallas", choices=("off", "auto", "on"),
                        default="auto",
                        help="kernelized LoRA GEMM + flash attention "
                             "dispatch: off = jnp paths, auto = compiled "
                             "kernels iff running on TPU, on = force "
                             "(interpret mode off-TPU — validation only)")
    parser.add_argument("--simulate", default=None, metavar="SCENARIO",
                        help="run an IoV federated fine-tuning scenario "
                             "(repro.sim.scenarios preset name) instead of "
                             "the LM step loop")
    parser.add_argument("--participation", choices=("sync", "semi-sync"),
                        default="sync",
                        help="--simulate round participation policy: sync "
                             "drops uploads from vehicles that leave "
                             "coverage mid-round; semi-sync buffers them "
                             "in flight and lands them up to max_delay "
                             "rounds late at staleness-discounted weight")
    parser.add_argument("--engine", default=None,
                        help="--simulate engine override "
                             "(serial|batched|fused|fused_sharded)")
    parser.add_argument("--rounds", type=int, default=None,
                        help="--simulate horizon (default: scenario's)")
    parser.add_argument("--seed", type=int, default=0,
                        help="--simulate scenario seed")
    parser.add_argument("--checkpoint-every", type=int, default=0,
                        help="checkpoint adapters/optimizer every N steps "
                             "(0 = off; needs --checkpoint-dir)")
    parser.add_argument("--checkpoint-dir", default=None,
                        help="directory for round_*.npz step checkpoints")
    parser.add_argument("--resume", action="store_true",
                        help="restore the latest checkpoint from "
                             "--checkpoint-dir and continue the step loop")
    args = parser.parse_args()
    if (args.checkpoint_every > 0 or args.resume) and not args.checkpoint_dir:
        parser.error("--checkpoint-every/--resume need --checkpoint-dir")

    enable_compile_cache()
    from repro.models import runmode
    mode = {"off": False, "auto": "auto", "on": True}[args.pallas]
    runmode.set_pallas_lora(mode)
    runmode.set_pallas_attn(mode)

    if args.simulate:
        from repro.sim import scenarios
        kw: Dict[str, Any] = {"participation": args.participation}
        if args.engine:
            kw["engine"] = args.engine
        if args.full:
            from repro.configs.vit_base_paper import vit_base_paper
            kw["train_arch"] = vit_base_paper()
        sim = scenarios.build_sim(args.simulate, rounds=args.rounds,
                                  seed=args.seed, **kw)
        R = sim.cfg.rounds
        hist = (sim.run_scanned(R) if sim.fused is not None else sim.run())
        for rec in hist:
            print(f"round {rec['round']:3d} acc={rec['accuracy']:.4f} "
                  f"energy={rec['energy']:.1f} reward={rec['reward']:.3f}")
        print(f"done: {args.simulate} ({args.participation}), "
              f"{R} rounds, final acc={hist[-1]['accuracy']:.4f}")
        return

    if args.full:
        from repro.config import get_arch
        cfg = get_arch(args.arch)
    else:
        import importlib
        mod = importlib.import_module(
            "repro.configs." + args.arch.replace("-", "_").replace(".", "_"))
        cfg = mod.reduced()
    lora = LoRAConfig(rank=args.rank)

    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg, dtype=jnp.float32)
    adapters = T.init_adapters(key, cfg, lora, rank=args.rank)
    opt = adam(args.lr)
    opt_state = opt.init(adapters)

    @jax.jit
    def step(params, adapters, opt_state, batch):
        def loss(ad):
            return T.loss_fn(params, ad, cfg, lora, batch)
        (_, metrics), grads = jax.value_and_grad(loss, has_aux=True)(adapters)
        updates, opt_state = opt.update(grads, opt_state, adapters)
        return apply_updates(adapters, updates), opt_state, metrics

    rng = np.random.default_rng(0)
    # the fingerprint pins everything that shapes the trajectory; a resume
    # against a different run config is rejected instead of diverging
    fp = hashlib.sha256(json.dumps(
        {"arch": args.arch, "full": args.full, "batch": args.batch,
         "seq": args.seq, "rank": args.rank, "lr": args.lr},
        sort_keys=True).encode()).hexdigest()
    start = 0
    if args.resume:
        from repro.checkpoint import latest_checkpoint, restore_round
        from repro.optim.adam import AdamState
        if latest_checkpoint(args.checkpoint_dir) is not None:
            start, state = restore_round(args.checkpoint_dir)
            meta = json.loads(bytes(np.asarray(state["meta"])).decode())
            if meta["fingerprint"] != fp:
                raise SystemExit(
                    "checkpoint in --checkpoint-dir was written by a "
                    "different run config (arch/batch/seq/rank/lr)")
            adapters = state["adapters"]
            opt_state = AdamState(step=state["opt"]["step"],
                                  mu=state["opt"]["mu"],
                                  nu=state["opt"]["nu"])
            rng.bit_generator.state = meta["rng"]
            print(f"resumed from step {start} ({args.checkpoint_dir})")
        else:
            print(f"no checkpoint in {args.checkpoint_dir}; "
                  "starting from step 0")

    def save_step(step_idx):
        from repro.checkpoint import prune_checkpoints, save_round
        save_round(args.checkpoint_dir, step_idx, {
            "adapters": adapters,
            "opt": {"step": opt_state.step, "mu": opt_state.mu,
                    "nu": opt_state.nu},
            "meta": np.frombuffer(json.dumps(
                {"fingerprint": fp, "step": step_idx,
                 "rng": rng.bit_generator.state}).encode(),
                np.uint8).copy()})
        prune_checkpoints(args.checkpoint_dir, keep_last=3)

    # tiny synthetic LM task: predict tok_{t+1} = (tok_t * 7 + 1) mod V
    V = cfg.vocab_size
    t0 = time.time()
    for i in range(start, args.steps):
        first = rng.integers(0, V, size=(args.batch, 1))
        seq = [first]
        for _ in range(args.seq):
            seq.append((seq[-1] * 7 + 1) % V)
        toks = np.concatenate(seq, 1)
        batch = {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
                 "labels": jnp.asarray(toks[:, 1:], jnp.int32)}
        if cfg.num_prefix_embeds:
            batch["prefix_embeds"] = jnp.zeros(
                (args.batch, cfg.num_prefix_embeds, cfg.d_model), jnp.float32)
        adapters, opt_state, m = step(params, adapters, opt_state, batch)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss={float(m['loss']):.4f} "
                  f"acc={float(m['accuracy']):.3f} "
                  f"({time.time()-t0:.1f}s)")
        if args.checkpoint_every and (i + 1) % args.checkpoint_every == 0:
            save_step(i + 1)
    print("done.")


if __name__ == "__main__":
    main()
