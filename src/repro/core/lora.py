"""Adaptive-rank LoRA adapters (paper §III-B).

An adapter for a linear `W: (d_in, d_out)` is a pair
``{"a": (d_in, r), "b": (r, d_out)}`` applied as
``y = x @ W + scale · (x @ a) @ b`` with ``scale = alpha / r``.

The paper's server-side redistribution works on the *merged* update
``Δθ = scale · aᵀ·b`` — see :func:`merge_delta`, :func:`factors_from_svd`.

Adapters for scanned layer stacks carry a leading layer axis: (L, d_in, r).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import LoRAConfig

Adapter = Dict[str, jnp.ndarray]


def init_adapter(key, d_in: int, d_out: int, rank: int,
                 dtype=jnp.float32, layers: Optional[int] = None) -> Adapter:
    """Kaiming-init A, zero-init B (standard LoRA init: Δθ starts at 0)."""
    sa = (d_in, rank) if layers is None else (layers, d_in, rank)
    sb = (rank, d_out) if layers is None else (layers, rank, d_out)
    a = jax.random.normal(key, sa) / jnp.sqrt(jnp.asarray(d_in, jnp.float32))
    return {"a": a.astype(dtype), "b": jnp.zeros(sb, dtype)}


def split_scale(scale) -> Tuple[Any, Any]:
    """Normalize the opaque LoRA scale argument.

    The model stack threads `scale` without interpreting it, so callers
    may pass either a scalar or a ``(scale, rank_mask)`` pair — the fused
    engine sends the pair when the kernelized route is enabled, extending
    rank-mask semantics into the kernel epilogue. Returns
    ``(scalar_scale, rank_mask_or_None)``.
    """
    if isinstance(scale, tuple):
        return scale[0], scale[1]
    return scale, None


def _kernel_route_ok(base: Dict[str, jnp.ndarray], adapter: Adapter) -> bool:
    # Bias excluded: the plain path computes (x·W + bias) + adapter while
    # the kernel epilogue would give (x·W + adapter) + bias — different
    # rounding, so a biased linear would break bit-exact engine parity.
    return ("b" not in base and base["w"].ndim == 2
            and adapter["a"].ndim == 2)


def apply_lora_linear(base: Dict[str, jnp.ndarray], adapter: Optional[Adapter],
                      x: jnp.ndarray, scale) -> jnp.ndarray:
    """y = x·W (+bias) + scale·(x·A)·B.  adapter=None → plain linear.

    scale: scalar, or ``(scale, rank_mask)`` (see :func:`split_scale`).
    With ``runmode.USE_PALLAS_LORA`` enabled, unbiased 2-D linears route
    through the fused Pallas GEMM (one accumulator tile, no second HBM
    read of x); everything else falls back to the jnp expression below,
    which is the kernel's bit-exactness oracle under jit.
    """
    scale, rank_mask = split_scale(scale)
    if adapter is not None:
        from repro.models import runmode
        if runmode.lora_kernel_enabled() and _kernel_route_ok(base, adapter):
            from repro.kernels.lora_matmul import lora_matmul
            return lora_matmul(
                x, base["w"], adapter["a"], adapter["b"],
                scale=scale, rank_mask=rank_mask,
                interpret=runmode.kernel_interpret(),
                use_kernel=not runmode.lora_kernel_oracle())
    y = x @ base["w"]
    if "b" in base:
        y = y + base["b"]
    if adapter is not None:
        # adapters are kept in f32 (they are trained); compute the low-rank
        # path in f32 and cast back to the base compute dtype
        lo1 = x.astype(adapter["a"].dtype) @ adapter["a"]
        if rank_mask is not None:
            lo1 = lo1 * rank_mask
        lo = lo1 @ adapter["b"]
        y = y + (scale * lo).astype(y.dtype)
    return y


def merge_delta(adapter: Adapter, scale: float) -> jnp.ndarray:
    """Δθ = scale · A·B, shape (d_in, d_out) (or (L, d_in, d_out))."""
    return scale * (adapter["a"] @ adapter["b"])


def factors_from_svd(u: jnp.ndarray, s: jnp.ndarray, vt: jnp.ndarray,
                     rank: int, scale: float, balanced: bool = False
                     ) -> Adapter:
    """Truncated-SVD factors for client redistribution.

    Default is the paper's literal split (Fig. 3): B_v = UΣ, A_v = Vᵀ.
    We hypothesized a *balanced* √Σ split would condition gradients better —
    REFUTED empirically (EXPERIMENTS.md §Paper): with Σ≈0 early in training
    the balanced split zeroes BOTH factors (no gradient signal at all),
    while the paper's split keeps b = Vᵀ at unit row norm so ∂L/∂a stays
    healthy — the same asymmetry as standard LoRA init. balanced=True kept
    for the ablation record.
    """
    if balanced:
        root = jnp.sqrt(jnp.maximum(s[:rank], 0.0) / scale)
        a = u[:, :rank] * root[None, :]
        b = root[:, None] * vt[:rank, :]
    else:
        a = (u[:, :rank] * s[:rank][None, :]) / scale
        b = vt[:rank, :]
    return {"a": a, "b": b}


def adapter_num_params(d_in: int, d_out: int, rank: int) -> int:
    return rank * (d_in + d_out)


# ---------------------------------------------------------------------------
# Rank padding (fused fleet engine)
#
# A rank-r adapter embedded in max_rank-wide buffers with the tail zeroed
# behaves *exactly* like the rank-r adapter: the extra columns of A and rows
# of B contribute 0 to (x·A)·B, receive zero gradients (each tail gradient
# is a product with the zeroed opposite factor), and Adam maps zero moments
# to zero updates — so the tail stays identically zero through training.
# This is what lets one jit program serve every rank in φ_η.
# ---------------------------------------------------------------------------

def rank_arange_mask(ranks: jnp.ndarray, max_rank: int) -> jnp.ndarray:
    """(..., max_rank) float mask: 1 where the rank index < ranks[...]."""
    idx = jnp.arange(max_rank, dtype=jnp.int32)
    return (idx < jnp.asarray(ranks)[..., None]).astype(jnp.float32)


def mask_adapter_tree(adapters: Any, mask: jnp.ndarray) -> Any:
    """Zero the padded rank tail of every adapter in a tree.

    mask: (max_rank,) or (V, max_rank) — with a leading vehicle axis the
    tree must carry a matching leading (V, ...) axis on every leaf.
    A-leaves are masked over their last axis, B-leaves over axis -2.
    """
    lead = mask.ndim - 1

    def mask_ad(ad):
        ma = mask.reshape(mask.shape[:lead] + (1,) * (ad["a"].ndim - 1 - lead)
                          + (mask.shape[-1],))
        mb = mask.reshape(mask.shape[:lead] + (1,) * (ad["b"].ndim - 2 - lead)
                          + (mask.shape[-1], 1))
        return {"a": ad["a"] * ma.astype(ad["a"].dtype),
                "b": ad["b"] * mb.astype(ad["b"].dtype)}

    from repro.core.aggregation import tree_paths, tree_get, tree_set
    out = adapters
    for path in tree_paths(adapters):
        out = tree_set(out, path, mask_ad(tree_get(out, path)))
    return out


def truncate_adapter_tree(adapters: Any, rank: int) -> Any:
    """Slice a (possibly padded) adapter tree down to `rank` — the exact
    inverse view of rank padding (used by the fused_check replay)."""
    from repro.core.aggregation import tree_paths, tree_get, tree_set
    out = adapters
    for path in tree_paths(adapters):
        ad = tree_get(out, path)
        out = tree_set(out, path, {"a": ad["a"][..., :rank],
                                   "b": ad["b"][..., :rank, :]})
    return out


def pad_adapter_tree(adapters: Any, max_rank: int) -> Any:
    """Zero-pad a rank-r adapter tree out to `max_rank` — the exact inverse
    of :func:`truncate_adapter_tree`. The zero tail is a no-op under
    x·A·B, so the padded tree decodes bit-identically to the original
    (the serving tier pages every adapter into max_rank-wide slots on the
    strength of this invariant)."""
    rank = tree_rank(adapters)
    if rank > max_rank:
        raise ValueError(
            f"adapter rank {rank} exceeds slot width max_rank={max_rank}")
    if rank == max_rank:
        return adapters
    from repro.core.aggregation import tree_paths, tree_get, tree_set
    pad = max_rank - rank
    out = adapters
    for path in tree_paths(adapters):
        ad = tree_get(out, path)
        pa = [(0, 0)] * (ad["a"].ndim - 1) + [(0, pad)]
        pb = [(0, 0)] * (ad["b"].ndim - 2) + [(0, pad), (0, 0)]
        out = tree_set(out, path, {"a": jnp.pad(ad["a"], pa),
                                   "b": jnp.pad(ad["b"], pb)})
    return out


def tree_rank(adapters: Any) -> int:
    """Rank of an adapter tree (all adapters share the client's rank).

    Every adapter dict holds {"a": (..., d_in, r), "b": (..., r, d_out)};
    the 'a' leaf's last axis is the rank.
    """
    flat = jax.tree_util.tree_flatten_with_path(adapters)[0]
    for path, leaf in flat:
        if any(getattr(k, "key", None) == "a" for k in path):
            return int(leaf.shape[-1])
    raise ValueError("no adapter 'a' leaf found")


def count_params(adapters: Any) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(adapters))
