"""Attention: GQA/MQA, MLA (DeepSeek-V2), sliding-window, KV-cache decode.

Training/prefill attention is computed blockwise over the KV axis with an
online softmax (flash-attention pattern in pure jnp, lax.scan over KV blocks)
so peak memory stays O(S·block) instead of O(S²). The Pallas TPU kernel in
``repro.kernels.flash_attention`` implements the same contract; models select
it with ``use_pallas=True``.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import MLAConfig, ModelConfig
from repro.core.lora import apply_lora_linear
from repro.models.common import (apply_rope, fan_in_init, init_linear,
                                 softcap)

KV_BLOCK = 512

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# GQA attention params
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype=jnp.float32,
                   layers: Optional[int] = None) -> Dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    L = () if layers is None else (layers,)

    def lin(k, di, do, bias):
        p = {"w": fan_in_init(k, L + (di, do), dtype)}
        if bias:
            p["b"] = jnp.zeros(L + (do,), dtype)
        return p

    return {
        "q": lin(ks[0], d, nq * hd, cfg.qkv_bias),
        "k": lin(ks[1], d, nkv * hd, cfg.qkv_bias),
        "v": lin(ks[2], d, nkv * hd, cfg.qkv_bias),
        "o": lin(ks[3], nq * hd, d, False),
    }


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _flash_body(q, k, v, mask_fn, sm_scale, cap=0.0):
    """Blockwise online-softmax attention.

    q: (B, Sq, H, hd); k/v: (B, Sk, Hkv, hd). mask_fn(qi, ki) -> bool mask
    (Sq_block? no — full Sq) given absolute kv start. Returns (B, Sq, H, hd).
    """
    B, Sq, H, hd_k = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    hd_v = v.shape[-1]
    rep = H // Hkv
    nblk = max(1, (Sk + KV_BLOCK - 1) // KV_BLOCK)
    pad = nblk * KV_BLOCK - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nblk, KV_BLOCK, Hkv, hd_k).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nblk, KV_BLOCK, Hkv, hd_v).transpose(1, 0, 2, 3, 4)

    qf = q.astype(jnp.float32)

    def step(carry, inp):
        acc, m, l = carry
        blk_idx, kblk, vblk = inp
        k0 = blk_idx * KV_BLOCK
        kf = kblk.astype(jnp.float32)
        # scores: (B, Sq, H, KV_BLOCK)
        kf_r = jnp.repeat(kf, rep, axis=2) if rep > 1 else kf
        s = jnp.einsum("bqhd,bkhd->bqhk", qf, kf_r) * sm_scale
        if cap:
            s = cap * jnp.tanh(s / cap)
        kv_pos = k0 + jnp.arange(KV_BLOCK)
        msk = mask_fn(kv_pos)                      # (B?, Sq, KV_BLOCK)
        valid = kv_pos < Sk
        msk = jnp.logical_and(msk, valid[None, None, :])
        s = jnp.where(msk[:, :, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        vf = vblk.astype(jnp.float32)
        vf_r = jnp.repeat(vf, rep, axis=2) if rep > 1 else vf
        acc = acc * corr[..., None] + jnp.einsum("bqhk,bkhd->bqhd", p, vf_r)
        l = l * corr + jnp.sum(p, axis=-1)
        return (acc, m_new, l), None

    from repro.models import runmode
    acc0 = jnp.zeros((B, Sq, H, hd_v), jnp.float32)
    m0 = jnp.full((B, Sq, H), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, H), jnp.float32)
    # checkpoint each kv-block step: the scan backward would otherwise save
    # the (B,Sq,H,KV_BLOCK) score/prob tensors for EVERY block — recomputing
    # them blockwise is the flash-attention backward (the Pallas kernel
    # does the same in VMEM on real TPUs). §Perf iter 5.
    (acc, m, l), _ = jax.lax.scan(
        jax.checkpoint(step), (acc0, m0, l0),
        (jnp.arange(nblk), kb, vb), unroll=runmode.inner_unroll(nblk))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def _direct_attend(q, k, v, *, causal, q_positions, kv_positions,
                   sliding_window, sm_scale, cap=0.0):
    """Unblocked attention for short sequences: one grouped score einsum,
    masked softmax, one value einsum — no KV blocking, no online-softmax
    rescans, no checkpoint recompute in the backward. Numerically equal to
    the flash path up to float reassociation."""
    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    hd_v = v.shape[-1]
    rep = H // Hkv
    qg = q.reshape(B, Sq, Hkv, rep, hd).astype(jnp.float32)
    s = jnp.einsum("bqhrd,bkhd->bqhrk", qg, k.astype(jnp.float32)) * sm_scale
    if cap:
        s = cap * jnp.tanh(s / cap)
    mask = (kv_positions[:, None, :] >= 0)   # empty ring-buffer slots: pos=-1
    if causal:
        mask = jnp.logical_and(
            mask, kv_positions[:, None, :] <= q_positions[:, :, None])
    if sliding_window is not None:
        mask = jnp.logical_and(
            mask,
            kv_positions[:, None, :] > q_positions[:, :, None]
            - sliding_window)
    s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqhrk,bkhd->bqhrd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd_v).astype(q.dtype)


def _decode_attend(q, k, v, q_positions, kv_positions, sliding_window,
                   sm_scale, cap=0.0):
    """Single-token decode: one grouped einsum over the cache — no blocked
    reshape/transpose copies, no materialized GQA head repeat (§Perf #1)."""
    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    hd_v = v.shape[-1]
    rep = H // Hkv
    qg = q.reshape(B, Sq, Hkv, rep, hd).astype(jnp.float32)
    s = jnp.einsum("bqhrd,bkhd->bqhrk", qg, k.astype(jnp.float32)) * sm_scale
    if cap:
        s = cap * jnp.tanh(s / cap)
    mask = (kv_positions[:, None, :] >= 0)
    mask = jnp.logical_and(mask,
                           kv_positions[:, None, :] <= q_positions[:, :, None])
    if sliding_window is not None:
        mask = jnp.logical_and(
            mask,
            kv_positions[:, None, :] > q_positions[:, :, None]
            - sliding_window)
    s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqhrk,bkhd->bqhrd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd_v).astype(q.dtype)


def attention_path(*, causal: bool, Sq: int, Sk: int, cap: float = 0.0,
                   hd_k: Optional[int] = None,
                   hd_v: Optional[int] = None) -> str:
    """Which route :func:`attend` takes, in precedence order — the live
    half of the dispatch table in DESIGN.md §6.

    'decode'       — Sq==1 causal with FAST_DECODE: direct cache attention
    'pallas_flash' — flash kernel enabled (runmode.attn_kernel_enabled)
                     and the aligned causal train case
    'direct'       — short sequences under DIRECT_ATTN_MAX_SEQ
    'jnp_flash'    — blocked online-softmax jnp fallback
    """
    from repro.models import runmode
    if Sq == 1 and causal and runmode.FAST_DECODE:
        return "decode"
    if (runmode.attn_kernel_enabled() and causal and Sq == Sk and cap == 0.0
            and (hd_k is None or hd_k == hd_v)):
        return "pallas_flash"
    if Sq > 1 and max(Sq, Sk) <= runmode.DIRECT_ATTN_MAX_SEQ:
        return "direct"
    return "jnp_flash"


def attend(q, k, v, *, causal: bool, q_positions, kv_positions=None,
           sliding_window: Optional[int] = None, sm_scale=None, cap=0.0):
    """Generic attention. q: (B,Sq,H,hd), k/v: (B,Sk,Hkv,hd).

    q_positions: (B, Sq) absolute positions of queries.
    kv_positions: (B, Sk) absolute positions of keys (default arange).
    """
    from repro.models import runmode
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(Sk)[None, :], (B, Sk))
    path = attention_path(causal=causal, Sq=Sq, Sk=Sk, cap=cap,
                          hd_k=k.shape[-1], hd_v=v.shape[-1])
    if path == "decode":
        return _decode_attend(q, k, v, q_positions, kv_positions,
                              sliding_window, sm_scale, cap)
    if path == "pallas_flash":
        # Pallas flash kernel (train/prefill, standard aligned case; MLA's
        # split K/V head dims and softcapped archs use the jnp path)
        from repro.kernels.flash_attention.ops import flash_attention
        return flash_attention(q, k, v, causal=True,
                               sliding_window=sliding_window,
                               sm_scale=sm_scale,
                               interpret=runmode.kernel_interpret())
    if path == "direct":
        # short sequences: materializing the (Sq,Sk) scores is cheap, and
        # the blocked online-softmax machinery below (scan + per-block
        # checkpoint recompute) costs far more than it saves — on the CPU
        # simulator it dominated the whole train step (§Perf: ~4× faster
        # fwd+bwd at S=16, and it keeps the batched round engine's vmap
        # from degenerating into looped tiny GEMMs)
        return _direct_attend(q, k, v, causal=causal,
                              q_positions=q_positions,
                              kv_positions=kv_positions,
                              sliding_window=sliding_window,
                              sm_scale=sm_scale, cap=cap)

    def mask_fn(kv_blk_pos):
        # kv_blk_pos: (KV_BLOCK,) indices into the kv axis
        kp = jnp.take(kv_positions, jnp.clip(kv_blk_pos, 0, Sk - 1), axis=1)
        m = kp[:, None, :] >= 0        # empty ring-buffer slots carry pos=-1
        m = jnp.broadcast_to(m, (B, Sq, kv_blk_pos.shape[0]))
        if causal:
            m = jnp.logical_and(
                m, kp[:, None, :] <= q_positions[:, :, None])
        if sliding_window is not None:
            m = jnp.logical_and(
                m, kp[:, None, :] > q_positions[:, :, None] - sliding_window)
        return m

    return _flash_body(q, k, v, mask_fn, sm_scale, cap)


def apply_attention(p, adapters, x, cfg: ModelConfig, lora_scale: float,
                    positions, cache=None, cache_index=None,
                    sliding_window=None):
    """Self-attention with optional LoRA adapters and KV cache.

    Returns (out, new_cache). cache: dict(k=(B,Sc,Hkv,hd), v=...), ring-buffer
    semantics for sliding windows handled by the caller via cache_index.
    """
    B, S, d = x.shape
    hd = cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    ad = adapters or {}
    q = apply_lora_linear(p["q"], ad.get("q"), x, lora_scale)
    k = apply_lora_linear(p["k"], ad.get("k"), x, lora_scale)
    v = apply_lora_linear(p["v"], ad.get("v"), x, lora_scale)
    q = _split_heads(q, nq, hd)
    k = _split_heads(k, nkv, hd)
    v = _split_heads(v, nkv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None:
        # decode: insert the S new keys at cache_index (mod cache len)
        Sc = cache["k"].shape[1]
        idx = (cache_index + jnp.arange(S)) % Sc
        ck = cache["k"].at[:, idx].set(k.astype(cache["k"].dtype))
        cv = cache["v"].at[:, idx].set(v.astype(cache["v"].dtype))
        kv_pos = cache["pos"].at[:, idx].set(positions.astype(jnp.int32))
        new_cache = {"k": ck, "v": cv, "pos": kv_pos}
        out = attend(q, ck, cv, causal=True, q_positions=positions,
                     kv_positions=kv_pos, sliding_window=sliding_window)
    else:
        out = attend(q, k, v, causal=True, q_positions=positions,
                     sliding_window=sliding_window)
    out = out.reshape(B, S, nq * hd)
    out = apply_lora_linear(p["o"], ad.get("o"), out, lora_scale)
    return out, new_cache


def init_cache(cfg: ModelConfig, batch: int, length: int, dtype=jnp.bfloat16):
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, length, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, length, cfg.num_kv_heads, hd), dtype),
        "pos": jnp.full((batch, length), -1, jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLA — DeepSeek-V2 multi-head latent attention
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig, dtype=jnp.float32,
             layers: Optional[int] = None) -> Dict:
    m: MLAConfig = cfg.mla
    d, nq = cfg.d_model, cfg.num_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    L = () if layers is None else (layers,)

    def lin(k, di, do):
        return {"w": fan_in_init(k, L + (di, do), dtype)}

    p = {
        "kv_down": lin(ks[0], d, m.kv_lora_rank + m.qk_rope_head_dim),
        "kv_up": lin(ks[1], m.kv_lora_rank,
                     nq * (m.qk_nope_head_dim + m.v_head_dim)),
        "o": lin(ks[3], nq * m.v_head_dim, d),
    }
    if m.q_lora_rank:
        p["q_down"] = lin(ks[4], d, m.q_lora_rank)
        p["q_up"] = lin(ks[5], m.q_lora_rank, nq * qk_dim)
    else:
        p["q"] = lin(ks[2], d, nq * qk_dim)
    return p


def apply_mla(p, adapters, x, cfg: ModelConfig, lora_scale: float,
              positions, cache=None, cache_index=None, sliding_window=None):
    """MLA forward. The latent KV (c_kv, k_rope) is what gets cached —
    the paper-relevant property: cache is rank-compressed (kv_lora_rank),
    exactly the low-rank structure the reproduction exploits.
    """
    m: MLAConfig = cfg.mla
    B, S, d = x.shape
    nq = cfg.num_heads
    ad = adapters or {}

    if "q" in p:
        q = apply_lora_linear(p["q"], ad.get("q"), x, lora_scale)
    else:
        qd = apply_lora_linear(p["q_down"], ad.get("q_down"), x, lora_scale)
        q = apply_lora_linear(p["q_up"], ad.get("q_up"), qd, lora_scale)
    q = q.reshape(B, S, nq, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kvd = apply_lora_linear(p["kv_down"], ad.get("kv_down"), x, lora_scale)
    c_kv, k_rope = jnp.split(kvd, [m.kv_lora_rank], axis=-1)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)

    if cache is not None:
        Sc = cache["c_kv"].shape[1]
        idx = (cache_index + jnp.arange(S)) % Sc
        c_kv_all = cache["c_kv"].at[:, idx].set(c_kv.astype(cache["c_kv"].dtype))
        k_rope_all = cache["k_rope"].at[:, idx].set(
            k_rope[:, :, 0, :].astype(cache["k_rope"].dtype))
        kv_pos = cache["pos"].at[:, idx].set(positions.astype(jnp.int32))
        new_cache = {"c_kv": c_kv_all, "k_rope": k_rope_all, "pos": kv_pos}
    else:
        c_kv_all, k_rope_all, kv_pos = c_kv, k_rope[:, :, 0, :], None
        new_cache = None

    # up-project latent to per-head K (nope) and V
    kv = apply_lora_linear(p["kv_up"], ad.get("kv_up"),
                           c_kv_all.astype(x.dtype), lora_scale)
    kv = kv.reshape(B, -1, nq, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
    k_rope_b = jnp.broadcast_to(
        k_rope_all[:, :, None, :].astype(x.dtype),
        (B, k_nope.shape[1], nq, m.qk_rope_head_dim))
    k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    qq = jnp.concatenate([q_nope, q_rope], axis=-1)

    sm_scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    out = attend(qq, k, v, causal=True, q_positions=positions,
                 kv_positions=kv_pos, sliding_window=sliding_window,
                 sm_scale=sm_scale)
    out = out.reshape(B, S, nq * m.v_head_dim)
    out = apply_lora_linear(p["o"], ad.get("o"), out, lora_scale)
    return out, new_cache


def init_mla_cache(cfg: ModelConfig, batch: int, length: int,
                   dtype=jnp.bfloat16):
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, length, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, length, m.qk_rope_head_dim), dtype),
        "pos": jnp.full((batch, length), -1, jnp.int32),
    }
