"""Chunked WKV6 recurrence as a Pallas TPU kernel.

TPU adaptation (DESIGN.md §6): RWKV's serial recurrence becomes, per chunk
of Q steps, dense MXU work — an intra-chunk score matrix with log-space
decay ratios plus one (K×K) state contraction — while the state is carried
across chunks in VMEM scratch (the chunk axis is the innermost, sequential
grid dimension). This is the flash-linear-attention decomposition; the CUDA
original streams per-step, which would leave the MXU idle.

Grid: (B, H, S/Q). Blocks: r/k/v/logw tiles (Q, K) in VMEM; state scratch
(K, K) f32. Output y tile (Q, K) plus the final state written on the last
chunk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, sfin_ref,
                 state_scr, *, q: int, nc: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    r = r_ref[...].astype(jnp.float32)            # (Q, K)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    lw = w_ref[...].astype(jnp.float32)           # (Q, K) log decay ≤ 0
    u = u_ref[...].astype(jnp.float32)            # (1, K)

    cum = jnp.cumsum(lw, axis=0)                  # inclusive
    cum_excl = cum - lw

    q_dec = r * jnp.exp(cum_excl)                 # r_t ⊙ W_{t-1}
    k_dec = k * jnp.exp(-cum)                     # k_j / W_j
    scores = jax.lax.dot_general(
        q_dec, k_dec, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)       # (Q, Q)
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    scores = jnp.where(ii > jj, scores, 0.0)      # strictly causal
    y = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)   # (Q, 1)
    y = y + diag * v
    # inter-chunk: y += (r ⊙ W_{t-1}) · S_prev
    y = y + jax.lax.dot_general(q_dec, state_scr[...],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)

    # state update: S = diag(exp(cum_Q))·S + Σ_j diag(exp(cum_Q−cum_j)) k_j v_jᵀ
    tail = cum[-1:, :] - cum                      # (Q, K)
    ktail = k * jnp.exp(tail)
    s_new = (state_scr[...] * jnp.exp(cum[-1, :])[:, None]
             + jax.lax.dot_general(ktail, v, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32))
    state_scr[...] = s_new

    @pl.when(ci == nc - 1)
    def _finish():
        sfin_ref[...] = s_new.astype(sfin_ref.dtype)


def wkv6_kernel(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                logw: jnp.ndarray, u: jnp.ndarray, *, chunk: int = 128,
                interpret: bool = False):
    """r,k,v,logw: (B, H, S, K); u: (H, K) → (y (B,H,S,K), state (B,H,K,K))."""
    B, H, S, K = r.shape
    q = min(chunk, S)
    assert S % q == 0, (S, q)
    nc = S // q

    kernel = functools.partial(_wkv6_kernel, q=q, nc=nc)
    y, sfin = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((None, None, q, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, q, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, q, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, q, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, 1, K), lambda b, h, c: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, q, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, K, K), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, K), r.dtype),
            jax.ShapeDtypeStruct((B, H, K, K), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, K), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, logw, u.reshape(H, 1, K))
    return y, sfin
