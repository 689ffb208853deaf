"""Public jit'd wrapper for the flash attention kernel.

Accepts models' (B, S, H, D) layout, transposes to the kernel's
(B, H, S, D), pads sequence lengths up to block multiples (mask-safe:
padded kv rows land outside the causal mask; padded q rows are sliced off).

Differentiation: pallas_call has no automatic VJP; `flash_attention` is a
custom_vjp whose backward recomputes through the jnp oracle (flash-style
recompute — no O(S²) residuals saved). A dedicated Pallas backward kernel
is future work.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ref import attention_ref


def _fa_forward(q, k, v, causal, sliding_window, sm_scale, block_q,
                block_k, interpret):
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    bq = min(block_q, max(Sq, 8))
    bk = min(block_k, max(Sk, 8))
    pad_q = (-Sq) % bq
    pad_k = (-Sk) % bk
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    # padded q rows are appended at the end; with q aligned to the kv end
    # they see *more* context than real rows but are discarded below.
    # padded kv rows sit beyond every real q row under the causal mask.
    assert causal or pad_k == 0, "non-causal padding needs explicit masks"
    out = flash_attention_kernel(
        qt, kt, vt, causal=causal, sliding_window=sliding_window,
        sm_scale=sm_scale, block_q=bq, block_k=bk, interpret=interpret)
    out = out[:, :, :Sq, :]
    return out.transpose(0, 2, 1, 3)


def _ref_bhsd(q, k, v, causal, sliding_window, sm_scale):
    return attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal,
        sliding_window=sliding_window,
        sm_scale=sm_scale).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _fa(q, k, v, causal, sliding_window, sm_scale, block_q, block_k,
        interpret):
    return _fa_forward(q, k, v, causal, sliding_window, sm_scale, block_q,
                       block_k, interpret)


def _fa_fwd(q, k, v, causal, sliding_window, sm_scale, block_q, block_k,
            interpret):
    out = _fa_forward(q, k, v, causal, sliding_window, sm_scale, block_q,
                      block_k, interpret)
    return out, (q, k, v)


def _fa_bwd(causal, sliding_window, sm_scale, block_q, block_k, interpret,
            res, g):
    q, k, v = res
    # recompute-based backward through the jnp oracle (no saved S² tensors)
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _ref_bhsd(q_, k_, v_, causal, sliding_window,
                                     sm_scale), q, k, v)
    return vjp(g)


_fa.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.jit, static_argnames=(
    "causal", "sliding_window", "sm_scale", "block_q", "block_k",
    "interpret"))
def _fa_jit(q, k, v, causal, sliding_window, sm_scale, block_q, block_k,
            interpret):
    return _fa(q, k, v, causal, sliding_window, sm_scale, block_q, block_k,
               interpret)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True,
                    sliding_window: Optional[int] = None,
                    sm_scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, Sq, H, D); k/v: (B, Sk, Hkv, D) → (B, Sq, H, D).

    interpret=None autodetects from the backend: compiled on TPU hosts,
    Pallas interpreter elsewhere (the CPU/GPU validation path).
    """
    if interpret is None:
        from repro.models import runmode
        interpret = runmode.kernel_interpret()
    return _fa_jit(q, k, v, causal, sliding_window, sm_scale, block_q,
                   block_k, bool(interpret))
