"""Public wrapper for the fused LoRA GEMM.

`scale` and `rank_mask` are traced operands (scale rides in SMEM): the
fused round engine threads per-vehicle dynamic scales through `loss_fn`,
so sweeping scales — or ranks, via the mask — reuses one executable.
Only the block geometry and interpret flag are static.

Differentiation: Pallas interpret-mode kernels don't admit efficient
autodiff, so `lora_matmul` is a `custom_vjp` whose backward is `jax.vjp`
of a jnp reference that is op-for-op the plain `apply_lora_linear`
expression (plus the mask multiply). Under jit, XLA compiles that
reference to the same fused HLO as the plain path's backward, so
kernel-route gradients are bit-identical to the jnp route's (cotangents
for w/scale/mask exist but are DCE'd when unused — the engine only
differentiates the adapters).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.lora_matmul.kernel import lora_matmul_kernel


def _ref(x, w, a, b, scale, mask):
    # Op-for-op the plain-path expression in core/lora.apply_lora_linear;
    # the backward pass differentiates THIS, not the kernel.
    t = x.astype(a.dtype) @ a
    t = t * mask
    y = x @ w
    return y + (scale * (t @ b)).astype(y.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _lora_mm(x, w, a, b, scale, mask, cfg):
    block_m, block_n, block_k, interpret, use_kernel = cfg
    if not use_kernel:
        # oracle route: identical custom_vjp structure, jnp forward. The
        # engine parity tests diff the kernel against THIS — any deviation
        # is then attributable to the Pallas kernel itself, not to the
        # custom_vjp's recompute-vs-saved-residual strategy (which shifts
        # grads ~1e-6 vs plain autodiff under the layer-scan transpose).
        return _ref(x, w, a, b, scale, mask)
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[1]
    r = a.shape[-1]
    t = x.astype(a.dtype) @ a                 # (..., r) — r/N of base cost
    xf = x.reshape(-1, K)
    tf = t.reshape(-1, r)
    M = xf.shape[0]

    bm = min(block_m, M)
    bn = min(block_n, N)
    bk = min(block_k, K)
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    xp = jnp.pad(xf, ((0, pm), (0, pk)))
    wp = jnp.pad(w, ((0, pk), (0, pn)))
    tp = jnp.pad(tf, ((0, pm), (0, 0)))
    bp = jnp.pad(b, ((0, 0), (0, pn)))
    s1 = jnp.asarray(scale, jnp.float32).reshape((1, 1))
    m2 = jnp.asarray(mask, jnp.float32).reshape((1, r))
    out = lora_matmul_kernel(xp, wp, tp, bp, m2, s1, block_m=bm,
                             block_n=bn, block_k=bk, interpret=interpret)
    return out[:M, :N].reshape(lead + (N,))


def _lora_mm_fwd(x, w, a, b, scale, mask, cfg):
    return _lora_mm(x, w, a, b, scale, mask, cfg), (x, w, a, b, scale, mask)


def _lora_mm_bwd(cfg, res, g):
    _, vjp = jax.vjp(_ref, *res)
    return vjp(g)


_lora_mm.defvjp(_lora_mm_fwd, _lora_mm_bwd)


@functools.partial(jax.jit, static_argnames=(
    "block_m", "block_n", "block_k", "interpret", "use_kernel"))
def lora_matmul(x: jnp.ndarray, w: jnp.ndarray, a: jnp.ndarray,
                b: jnp.ndarray, *, scale=1.0, rank_mask=None,
                block_m: int = 128, block_n: int = 128, block_k: int = 512,
                interpret: bool = False,
                use_kernel: bool = True) -> jnp.ndarray:
    """y = x·W + scale·((x·A)⊙mask)·B with x: (..., K), w: (K, N),
    a: (K, r), b: (r, N). Returns (..., N).

    scale may be a Python float or a traced f32 scalar; rank_mask an
    (r,)-broadcastable f32 mask (None → all-ones, a bitwise no-op).
    Neither triggers recompilation across distinct values.
    use_kernel=False is the jnp-forward oracle route (same custom_vjp).
    """
    r = a.shape[-1]
    if rank_mask is None:
        rank_mask = jnp.ones((r,), jnp.float32)
    cfg = (int(block_m), int(block_n), int(block_k), bool(interpret),
           bool(use_kernel))
    return _lora_mm(x, w, a, b, scale, rank_mask, cfg)
