"""Fused base+adapter GEMM: y = x·W + scale·(t⊙mask)·B with t = x·A.

Why fused (DESIGN.md §6): during LoRA fine-tuning every targeted linear
evaluates base GEMM *plus* adapter path. Done naively that is a second
read of the activations from HBM and a materialized (M, N) adapter product.
Here the adapter contribution is added into the same VMEM accumulator tile
as the base GEMM's k-loop epilogue — one output write, no extra HBM round
trip. t = x·A is O(M·K·r), r ≤ 64 ≪ N, computed once by the wrapper (its
cost is ~r/N of the base GEMM).

Two operands beyond the GEMM inputs:
  scale — shape (1, 1) f32 in SMEM, read as a scalar in the epilogue.
          Traced, not baked into the kernel: the fused round engine
          threads *per-vehicle dynamic* scales (alpha/rank), so a static
          scale would recompile per distinct value and break the
          one-compile round-body contract. Two-dimensional because the
          fleet vmap batches it: a (1,) operand becomes a (V, 1) array
          with a (1,) block, which the TPU lowering rejects, while a
          (1, 1) block always equals the array's last two dims.
  mask  — shape (1, r) f32 rank mask (rank_arange_mask row). The epilogue
          computes (t⊙mask)·B, extending the rank-padding invariant into
          the kernel: a rank-r vehicle under max_rank padding produces
          bit-identical output to the truncated adapter, because masked
          tail lanes contribute exact ±0 rows to the adapter dot.

Tiling: grid (M/bm, N/bn, K/bk), k innermost/sequential, f32 VMEM scratch
accumulator of (bm, bn); all tile dims 128-aligned for the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lora_mm_kernel(x_ref, w_ref, t_ref, b_ref, m_ref, s_ref, o_ref,
                    acc_scr, *, nk: int):
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...]
    w = w_ref[...]
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _finish():
        t = (t_ref[...] * m_ref[...]).astype(jnp.float32)   # (bm, r)
        bb = b_ref[...].astype(jnp.float32)                 # (r, bn)
        adapter = jax.lax.dot_general(
            t, bb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[...] = (acc_scr[...] + s_ref[0, 0] * adapter).astype(
            o_ref.dtype)


def lora_matmul_kernel(x: jnp.ndarray, w: jnp.ndarray, t: jnp.ndarray,
                       b: jnp.ndarray, mask: jnp.ndarray,
                       scale: jnp.ndarray, *,
                       block_m: int = 128, block_n: int = 128,
                       block_k: int = 512,
                       interpret: bool = False) -> jnp.ndarray:
    """x:(M,K) w:(K,N) t=x·A:(M,r) b:(r,N) mask:(1,r) scale:(1,1) → (M,N)."""
    M, K = x.shape
    N = w.shape[1]
    r = t.shape[1]
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    nm, nn, nk = M // bm, N // bn, K // bk

    kernel = functools.partial(_lora_mm_kernel, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, r), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((r, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, r), lambda i, j, kk: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w, t, b, mask, scale)
