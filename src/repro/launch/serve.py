"""Serving tier: prefill/decode step factories and the multi-tenant
ServeEngine (DESIGN.md §5).

The step factories lower prefill (full-sequence forward) and decode
(single-token with KV/state caches) onto a device mesh — decode is what
the `decode_32k` and `long_500k` input shapes lower (one new token against
a seq_len cache; sub-quadratic archs use constant-size state, full-
attention archs the sliding-window variant).

:class:`ServeEngine` is the multi-tenant batched decode loop above them:
``ServeSpec.max_batch`` lanes share ONE compiled decode program, each lane
carrying its own cache slice and a rank-padded adapter slot. Adapters of
any trained rank r ≤ slot width page in with zero tails (exact no-ops
under x·A·B) and their LoRA scale rides as a traced scalar — so
hot-swapping adapters across tenants, tasks, RSUs and ranks never changes
the program: the decode jit cache holds exactly one entry
(tests/test_serve.py pins this with a log_compiles guard).

Continuous batching rides on the same contract: ``admit(tenant)`` /
``retire(lane)`` are pure host-side data movement into the fixed slot
shape (adapter scatter + cache/allocator surgery on ONE lane), so tenants
enter and leave mid-stream while sibling lanes' positions, caches and
greedy streams stay bit-identical to an undisturbed run
(tests/test_continuous_batching.py). With ``ServeSpec.block_size > 0``
the ring-buffer KV caches move into a shared block pool behind per-lane
block tables (``core/kv_blocks.py``): long streams allocate blocks
incrementally instead of max-seq upfront, and a retired tenant's blocks
recycle to new admissions — still through the one compiled decode body
(tables are fixed-shape int32 data, never statics).

CLI example (batched requests through ServeEngine; reduced config unless
--full):
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --tokens 32
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import LoRAConfig, ModelConfig, ServeSpec
from repro.core import kv_blocks as kvb
from repro.core import lora as lora_lib
from repro.launch import sharding as sh
from repro.launch.adapter_cache import PagedAdapter
from repro.models import transformer as T
from repro.telemetry import Telemetry


def make_prefill_step(cfg: ModelConfig, lora: LoRAConfig, mesh, *,
                      seq_shard: bool = True, sliding_window=None,
                      scan_unroll: int = 1):
    constrain = sh.make_constrain(mesh, seq_shard)

    def prefill(params, adapters, batch):
        logits, _ = T.forward(params, adapters, cfg, lora, batch,
                              sliding_window=sliding_window,
                              constrain=constrain, scan_unroll=scan_unroll)
        return logits

    def jit_prefill(params, adapters, batch):
        ps = sh.tree_shardings(mesh, params)
        ads = (sh.tree_shardings(mesh, adapters, is_adapter=True)
               if adapters is not None else None)
        bs = sh.batch_shardings(mesh, batch)
        dp = sh._dp_for(mesh, batch["tokens"].shape[0])
        out_sh = NamedSharding(mesh, P(dp, None, "model"))
        return jax.jit(prefill, in_shardings=(ps, ads, bs),
                       out_shardings=out_sh)

    return prefill, jit_prefill


def make_decode_step(cfg: ModelConfig, lora: LoRAConfig, mesh, *,
                     sliding_window=None, donate: bool = True,
                     scan_unroll: int = 1, traced_scale: bool = False):
    """Decode step + jit builder.

    ``traced_scale=True`` appends a traced ``scale`` operand to the step
    (replacing the static ``lora.scale``): with rank-padded adapter slots
    this is what lets ONE compiled decode program serve adapters of every
    rank — α/r changes per swap, the program does not.
    """
    if traced_scale:
        def decode(params, adapters, token, caches, position, scale):
            logits, new_caches = T.decode_step(
                params, adapters, cfg, lora, token, caches, position,
                sliding_window=sliding_window, scan_unroll=scan_unroll,
                scale=scale)
            return logits, new_caches
    else:
        def decode(params, adapters, token, caches, position):
            logits, new_caches = T.decode_step(
                params, adapters, cfg, lora, token, caches, position,
                sliding_window=sliding_window, scan_unroll=scan_unroll)
            return logits, new_caches

    def jit_decode(params, adapters, token, caches, position, scale=None):
        ps = sh.tree_shardings(mesh, params)
        ads = (sh.tree_shardings(mesh, adapters, is_adapter=True)
               if adapters is not None else None)
        cs = sh.cache_shardings(mesh, caches)
        dp = sh._dp_for(mesh, token.shape[0])
        tok_sh = NamedSharding(mesh, P(dp, None))
        rep_sh = NamedSharding(mesh, P())
        out_sh = (NamedSharding(mesh, P(dp, None, "model")), cs)
        in_sh = (ps, ads, tok_sh, cs, rep_sh)
        if traced_scale:
            in_sh = in_sh + (rep_sh,)
        return jax.jit(decode, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=(3,) if donate else ())

    return decode, jit_decode


# ---------------------------------------------------------------------------
# Multi-tenant serving engine
# ---------------------------------------------------------------------------

def _snapshot(host: np.ndarray) -> jnp.ndarray:
    """Ship host state the engine keeps mutating in place (positions,
    scales, block tables). On the CPU backend ``jnp.asarray`` may alias
    the numpy buffer, and the decode call is dispatched asynchronously, so
    an in-place update after the call could race the program's read of
    it; a private copy makes the call see exactly this step's state."""
    return jnp.asarray(host.copy())


class ServeEngine:
    """Batched multi-tenant decode over rank-padded adapter slots.

    Each of the ``spec.max_batch`` lanes serves one tenant: a
    :class:`PagedAdapter` (task/RSU/version at any rank ≤ the slot width)
    plus its own cache slice and position counter. The decode program is
    ``vmap`` over lanes of the single-sequence :func:`T.decode_step` with
    a per-lane traced scale, jitted ONCE — assigning a different adapter,
    rank, or tenant to a lane is a pure data swap (``.at[lane].set``).

    Unassigned lanes hold zero adapters at zero scale — exact base-model
    decode — so a partially occupied engine is always safe to step.

    With ``spec.block_size > 0`` the engine runs block-paged: ring-buffer
    caches live in shared pools behind a :class:`~repro.core.kv_blocks.\
BlockAllocator`, lanes grow block-by-block as their streams lengthen, and
    ``retire``/``reset_lane`` return blocks to the free list for the next
    admission. Only SSM/recurrent state stays a per-lane dense carry.
    """

    def __init__(self, params, cfg: ModelConfig, lora: LoRAConfig,
                 spec: Optional[ServeSpec] = None, *,
                 dtype=jnp.float32, scan_unroll: int = 1):
        self.cfg = cfg
        self.lora = lora
        self.spec = spec or ServeSpec()
        self.params = params
        self.slot_rank = self.spec.resolve_max_rank(lora)
        self.dtype = dtype
        B = self.spec.max_batch
        # statics the compiled step closes over: the slot-width LoRAConfig
        # only contributes shapes (scale is traced), so it never varies
        slot_lora = dataclasses.replace(lora, rank=self.slot_rank,
                                        max_rank=self.slot_rank)
        zero = jax.tree_util.tree_map(
            jnp.zeros_like,
            T.init_adapters(jax.random.PRNGKey(0), cfg, slot_lora))
        self._zero_adapter = zero
        self._adapters = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (B,) + x.shape) + 0, zero)
        self._scales = np.zeros(B, np.float32)
        self._cache0 = T.init_caches(cfg, 1, self.spec.cache_len,
                                     dtype=dtype)
        self.paged = self.spec.paged
        self.allocator: Optional[kvb.BlockAllocator] = None
        if self.paged:
            bs = self.spec.block_size
            blocks_per_lane = self.spec.cache_len // bs
            num_blocks = self.spec.resolve_max_blocks()
            self.allocator = kvb.BlockAllocator(num_blocks, B,
                                                blocks_per_lane)
            state0, paged0 = kvb.split_cache_tree(cfg, self._cache0)
            self._state0 = state0
            self._pools = tuple(kvb.make_pool(c, num_blocks, bs)
                                for c in paged0)
            self._caches = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (B,) + x.shape) + 0, state0)
        else:
            self._caches = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (B,) + x.shape) + 0,
                self._cache0)
        self._positions = np.zeros(B, np.int32)
        self.assigned: Dict[int, Optional[PagedAdapter]] = \
            {i: None for i in range(B)}
        self.swaps = 0
        self.admits = 0
        self.retires = 0
        self._admit_order: list = []     # lanes, oldest admission first

        window = self.spec.sliding_window
        # the decode program's traces, as the counter "traces"
        self.telemetry = Telemetry()
        one_dev = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        # Pin explicit input shardings: the jit cache key must not depend
        # on whether an argument is committed (host-side lane surgery —
        # assign/reset_lane scatters — commits the caches/adapters, while
        # fresh init arrays and jit outputs are uncommitted; without the
        # pin the FIRST step after a reset re-lowers the whole program).
        if self.paged:
            def lane(params, pools, ad, scale, token, state, table_row,
                     position):
                logits, ns, written = T.decode_step_paged(
                    params, ad, cfg, slot_lora, token.reshape(1, 1), state,
                    pools, table_row, position, sliding_window=window,
                    scan_unroll=scan_unroll, scale=scale)
                return logits[0, 0], ns, written

            vlane = jax.vmap(lane,
                             in_axes=(None, None, 0, 0, 0, 0, 0, 0))
            bs = self.spec.block_size

            def serve_decode_paged(params, adapters, scales, tokens,
                                   states, pools, tables, positions):
                # host-side body: runs ONLY when jax (re)traces the
                # program, so "traces" counts compiled decode variants
                self.telemetry.add("traces")
                logits, new_states, written = vlane(
                    params, pools, adapters, scales, tokens, states,
                    tables, positions)
                # pools are unbatched under the lane vmap, so each lane's
                # just-written ring slot comes back as a value; one
                # scatter per pool lands them all (destination blocks are
                # disjoint across lanes — allocator invariant)
                new_pools = tuple(
                    kvb.scatter_written(pool, w, tables, positions, bs)
                    for pool, w in zip(pools, written))
                return logits, new_states, new_pools

            self._decode = jax.jit(
                serve_decode_paged,
                in_shardings=(one_dev,) * 8,
                donate_argnums=(4, 5) if self.spec.donate else ())
        else:
            def lane(params, ad, scale, token, caches, position):
                logits, nc = T.decode_step(
                    params, ad, cfg, slot_lora, token.reshape(1, 1),
                    caches, position, sliding_window=window,
                    scan_unroll=scan_unroll, scale=scale)
                return logits[0, 0], nc

            vlane = jax.vmap(lane, in_axes=(None, 0, 0, 0, 0, 0))

            def serve_decode(params, adapters, scales, tokens, caches,
                             positions):
                # host-side body: runs ONLY when jax (re)traces the
                # program, so this counter is the number of compiled
                # decode variants
                self.telemetry.add("traces")
                return vlane(params, adapters, scales, tokens, caches,
                             positions)

            self._decode = jax.jit(
                serve_decode,
                in_shardings=(one_dev,) * 6,
                donate_argnums=(4,) if self.spec.donate else ())

    # -- tenancy --------------------------------------------------------
    @property
    def max_batch(self) -> int:
        return self.spec.max_batch

    def assign(self, lane: int, paged: PagedAdapter, *,
               reset: bool = True) -> None:
        """Hot-swap `paged` into `lane`. Pure data movement: no shapes or
        statics change, so the compiled decode program is untouched."""
        if paged.slot_rank != self.slot_rank:
            raise ValueError(
                f"adapter paged for slot width {paged.slot_rank}, engine "
                f"slot width is {self.slot_rank}")
        self._adapters = jax.tree_util.tree_map(
            lambda full, one: full.at[lane].set(one.astype(full.dtype)),
            self._adapters, paged.adapters)
        self._scales[lane] = paged.scale
        self.assigned[lane] = paged
        self.swaps += 1
        if lane in self._admit_order:
            self._admit_order.remove(lane)
        self._admit_order.append(lane)
        if reset:
            self.reset_lane(lane)

    def evict(self, lane: int, *, reset: bool = True) -> None:
        """Return `lane` to base-model decode (zero adapter, zero scale)."""
        self._adapters = jax.tree_util.tree_map(
            lambda full, one: full.at[lane].set(one),
            self._adapters, self._zero_adapter)
        self._scales[lane] = 0.0
        self.assigned[lane] = None
        if lane in self._admit_order:
            self._admit_order.remove(lane)
        if reset:
            self.reset_lane(lane)

    def admit(self, paged: PagedAdapter, *,
              lane: Optional[int] = None) -> int:
        """Admit a tenant mid-stream: pick a lane (free lane first; under
        ``spec.admission="evict_oldest"`` retire the longest-admitted
        tenant when full; ``"strict"`` raises instead) and hot-swap the
        adapter in. Host-side data movement on that ONE lane — sibling
        lanes' positions, caches and streams are untouched, and the
        compiled decode program never changes. Returns the lane."""
        if lane is None:
            free = [i for i in range(self.max_batch)
                    if self.assigned[i] is None]
            if free:
                lane = free[0]
            elif self.spec.admission == "evict_oldest":
                lane = self.retire(self._admit_order[0])
            else:
                raise RuntimeError(
                    f"no free lane for tenant {paged.key} (all "
                    f"{self.max_batch} lanes occupied; ServeSpec."
                    "admission='strict' refuses to evict)")
        self.assign(lane, paged, reset=True)
        self.admits += 1
        return lane

    def retire(self, lane: int) -> int:
        """Retire `lane`'s tenant: back to base-model decode, stream
        reset, and (paged mode) its KV blocks recycled to the free list.
        Sibling lanes are bit-undisturbed. Returns the freed lane."""
        self.evict(lane, reset=True)
        self.retires += 1
        return lane

    def reset_lane(self, lane: int) -> None:
        """Fresh cache + position 0 for `lane` (new request). In paged
        mode this frees the lane's blocks (stamping their pool positions
        back to -1 so a recycler can never see them) and resets only the
        dense SSM carry."""
        if self.paged:
            freed = self.allocator.free_lane(lane)
            if freed:
                self._pools = tuple(kvb.release_blocks(p, freed)
                                    for p in self._pools)
            self._caches = jax.tree_util.tree_map(
                lambda c, z: c.at[lane].set(z.astype(c.dtype)),
                self._caches, self._state0)
        else:
            self._caches = jax.tree_util.tree_map(
                lambda c, z: c.at[lane].set(z.astype(c.dtype)),
                self._caches, self._cache0)
        self._positions[lane] = 0

    def lane_cache(self, lane: int):
        """The lane's dense-equivalent cache tree (host-side view; paged
        mode gathers the lane's blocks). Test/debug surface — the decode
        path never materializes this outside the jitted body."""
        state = jax.tree_util.tree_map(lambda c: c[lane], self._caches)
        if not self.paged:
            return state
        table = jnp.asarray(self.allocator.tables[lane])
        gathered = [kvb.gather_lane(p, table) for p in self._pools]
        return kvb.merge_lane_caches(self.cfg, state, gathered)

    def allocator_stats(self) -> Dict[str, Any]:
        """Block-allocator counters (empty dict when dense)."""
        return self.allocator.stats() if self.paged else {}

    # -- decode ---------------------------------------------------------
    def _ensure_blocks(self) -> None:
        """Back every lane's write slot for this step with a physical
        block. Streams grow one block at a time; a wrapped ring reuses
        the lane's own blocks (already mapped). Raises
        :class:`~repro.core.kv_blocks.BlockPoolExhausted` when the pool
        is out — loudly, never by stealing a sibling's block."""
        Sc, bs = self.spec.cache_len, self.spec.block_size
        for lane in range(self.max_batch):
            self.allocator.ensure(lane,
                                  (int(self._positions[lane]) % Sc) // bs)

    def step(self, tokens: Sequence[int]) -> jnp.ndarray:
        """Decode one token on every lane. tokens: (max_batch,) ints.
        Returns per-lane next-token logits, shape (max_batch, vocab)."""
        toks = jnp.asarray(np.asarray(tokens, np.int32).reshape(
            self.spec.max_batch))
        if self.paged:
            self._ensure_blocks()
            logits, self._caches, self._pools = self._decode(
                self.params, self._adapters, _snapshot(self._scales),
                toks, self._caches, self._pools,
                _snapshot(self.allocator.tables),
                _snapshot(self._positions))
        else:
            logits, self._caches = self._decode(
                self.params, self._adapters, _snapshot(self._scales),
                toks, self._caches, _snapshot(self._positions))
        self._positions += 1
        return logits

    def generate(self, prompts: np.ndarray, num_tokens: int) -> np.ndarray:
        """Greedy-decode `num_tokens` per lane after teacher-forcing the
        prompts. prompts: (max_batch, P) ints. Returns (max_batch,
        num_tokens) generated ids."""
        prompts = np.asarray(prompts)
        assert prompts.shape[0] == self.spec.max_batch
        tok = prompts[:, 0]
        out = []
        for i in range(prompts.shape[1] + num_tokens - 1):
            logits = self.step(tok)
            if i + 1 < prompts.shape[1]:
                tok = prompts[:, i + 1]
            else:
                tok = np.asarray(jnp.argmax(logits, axis=-1))
                out.append(tok)
        return np.stack(out, axis=1)

    @property
    def compile_count(self) -> int:
        """Traced-and-compiled variants of the decode program (the
        contract: 1). Counted by retraces of the jitted body — the C++
        fastpath may key extra cache entries on input provenance
        (committed/fresh) that all share ONE lowering, so the private
        ``_cache_size`` would overcount."""
        return self.telemetry.counters.get("traces", 0)


# ---------------------------------------------------------------------------
# CLI: batched request serving through ServeEngine
# ---------------------------------------------------------------------------

def main():
    import argparse
    import importlib
    import time

    from repro.config import get_arch
    from repro.launch.compile_cache import enable_compile_cache

    parser = argparse.ArgumentParser()
    parser.add_argument("--arch", default="qwen2-0.5b")
    parser.add_argument("--full", action="store_true",
                        help="serve the full (not reduced) config")
    parser.add_argument("--batch", type=int, default=4)
    parser.add_argument("--prompt-len", type=int, default=16)
    parser.add_argument("--tokens", type=int, default=32)
    args = parser.parse_args()
    enable_compile_cache()

    if args.full:
        cfg = get_arch(args.arch)
    else:
        cfg = importlib.import_module(
            "repro.configs." + args.arch.replace("-", "_").replace(".", "_")
        ).reduced()
    params = T.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    spec = ServeSpec(max_batch=args.batch,
                     cache_len=args.prompt_len + args.tokens)
    # unassigned lanes decode the base model (zero adapter, zero scale)
    engine = ServeEngine(params, cfg, LoRAConfig(rank=4), spec)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len))

    t0 = time.time()
    gen = engine.generate(prompts, args.tokens)
    dt = time.time() - t0
    compiles = engine.compile_count
    print(f"served {args.batch} requests × {gen.shape[1]} tokens in "
          f"{dt:.1f}s ({args.batch * gen.shape[1] / dt:.1f} tok/s, "
          f"{compiles} decode compile{'s' if compiles != 1 else ''})")
    print("sample:", gen[0][:16])


if __name__ == "__main__":
    main()
