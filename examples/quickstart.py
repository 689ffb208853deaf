"""Quickstart: the paper's technique in 60 lines.

1. Build a model + LoRA adapters at a chosen rank.
2. Run a few local fine-tuning steps (vehicle side).
3. Aggregate two clients' updates at different ranks (RSU side, merged-Δθ).
4. Redistribute personalized truncated-SVD factors at new ranks.

    PYTHONPATH=src python examples/quickstart.py [--arch qwen2-0.5b]
"""
import argparse
import importlib

import jax
import jax.numpy as jnp

from repro.config import LoRAConfig
from repro.core import aggregation as agg
from repro.core.lora import tree_rank
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.optim import adam, apply_updates


def local_finetune(params, adapters, cfg, lora, key, steps=5):
    opt = adam(1e-3)
    opt_state = opt.init(adapters)

    @jax.jit
    def step(adapters, opt_state, batch):
        def loss(ad):
            return T.loss_fn(params, ad, cfg, lora, batch)
        (l, m), g = jax.value_and_grad(loss, has_aux=True)(adapters)
        up, opt_state = opt.update(g, opt_state, adapters)
        return apply_updates(adapters, up), opt_state, l

    for i in range(steps):
        key, k = jax.random.split(key)
        toks = jax.random.randint(k, (4, 32), 0, cfg.vocab_size)
        batch = {"tokens": toks, "labels": (toks * 7 + 1) % cfg.vocab_size}
        adapters, opt_state, l = step(adapters, opt_state, batch)
        print(f"  step {i}: loss {float(l):.4f}")
    return adapters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    args = ap.parse_args()
    enable_compile_cache()
    mod = importlib.import_module(
        "repro.configs." + args.arch.replace("-", "_").replace(".", "_"))
    cfg = mod.reduced()
    lora = LoRAConfig(rank=8, max_rank=16)
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg, dtype=jnp.float32)

    print("== vehicle A (rank 4) local fine-tuning ==")
    ad_a = T.init_adapters(key, cfg, lora, rank=4)
    ad_a = local_finetune(params, ad_a, cfg, lora, jax.random.PRNGKey(1))

    print("== vehicle B (rank 8) local fine-tuning ==")
    ad_b = T.init_adapters(key, cfg, lora, rank=8)
    ad_b = local_finetune(params, ad_b, cfg, lora, jax.random.PRNGKey(2))

    print("== RSU: rank-heterogeneous aggregation (merged Δθ) ==")
    merged = agg.aggregate_merged([ad_a, ad_b], [1.0, 2.0], lora.scale)

    print("== RSU: truncated-SVD redistribution at ranks {2, 16} ==")
    for r in (2, 16):
        out = agg.redistribute(merged, rank=r, scale=lora.scale,
                               max_rank=lora.max_rank)
        print(f"  rank {r}: adapters at rank {tree_rank(out)}")
    print("done — see examples/multi_task_iov.py for the full system.")


if __name__ == "__main__":
    main()
