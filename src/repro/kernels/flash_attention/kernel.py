"""Blockwise flash attention as a Pallas TPU kernel.

Tiling (TPU-native, DESIGN.md §6):
  grid = (B, H, Sq/bq, Sk/bk); the kv dimension is innermost and sequential
  ("arbitrary") so the online-softmax state (m, l, acc) lives in VMEM
  scratch across kv steps. Q/K/V blocks are VMEM tiles of
  (bq, D) / (bk, D); D and bq/bk are multiples of the 128-lane MXU width.
  GQA is expressed in the K/V index_map (query head h reads kv head
  h // (H // Hkv)) — no materialized head repeat.
  Causal + sliding-window masks are applied with absolute positions, with
  q rows aligned to the end of the kv axis (decode-friendly convention).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               sm_scale: float, causal: bool, window: Optional[int],
               bq: int, bk: int, sq: int, sk: int, nk: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)          # (bq, D)
    k = k_ref[...].astype(jnp.float32)          # (bk, D)
    v = v_ref[...].astype(jnp.float32)          # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale

    # absolute positions: q rows sit at the END of the kv axis
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + (sk - sq)
    k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                          # (bq, 1)
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # fully-masked rows keep m = NEG_INF; exp(NEG_INF − NEG_INF) would be 1,
    # so p must be forced to 0 outside the mask
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(kj == nk - 1)
    def _finish():
        o_ref[...] = (acc_scr[...]
                      / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_kernel(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True,
                           sliding_window: Optional[int] = None,
                           sm_scale: Optional[float] = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False) -> jnp.ndarray:
    """q: (B, H, Sq, D); k/v: (B, Hkv, Sk, D) → (B, H, Sq, D)."""
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    assert H % Hkv == 0
    group = H // Hkv
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nq, nk = Sq // bq, Sk // bk
    if sm_scale is None:
        sm_scale = float(1.0 / (D ** 0.5))

    kernel = functools.partial(
        _fa_kernel, sm_scale=sm_scale, causal=causal, window=sliding_window,
        bq=bq, bk=bk, sq=Sq, sk=Sk, nk=nk)

    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, bk, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((None, None, bk, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
