"""Plain reference of the repository's decoder stack with LoRA adapters, in
straightforward ``jax.numpy``: no kernels, no caches, no batching tricks.
It imports nothing of the program.

It runs ``vit-base-paper`` as the repository runs it: token ids in (no
patch embedding), rotary positions, causal self-attention, pre-LayerNorm,
tanh-GELU MLP, classification from the last position through an untied
head. Departures from ViT-Base as published (arXiv:2010.11929): the
published model embeds 196 image patches plus a class token, adds learned
position embeddings, attends bidirectionally, uses the exact (erf) GELU
and classifies from the class token. :func:`weight_shapes` refuses any
other kind of configuration; :func:`dims` and :func:`targets` also size
the cost model's architecture, which may be another kind.

Weights come in as a dict laid out by name: ``embed``, ``final_norm``,
``segments[0]`` with ``norm1``, ``attn/{q,k,v,o}``, ``norm2`` and
``mlp/{up,down}``, stacked on a leading layer axis, and ``lm_head``.

Adapters come in as ``{target: (A (L, d_in, r), B (L, r, d_out))}`` and
add ``scale · (x A) B`` to the target's output. Float32 callers run under
``jax.default_matmul_precision("highest")``; ``dt`` selects the precision
the whole computation runs in (a bfloat16 control).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

EPS = 1e-6
NEG = -1e30


def dims(model: Dict[str, Any]) -> Dict[str, int]:
    d = model["d_model"]
    h = model["num_heads"]
    hd = model.get("head_dim") or d // h
    return {"L": model["num_layers"], "d": d, "H": h,
            "Hkv": model["num_kv_heads"], "hd": hd, "f": model["d_ff"],
            "V": model["vocab_size"]}


def is_glu(model) -> bool:
    return model["activation"] in ("swiglu", "geglu")


def targets(model) -> Dict[str, Tuple[str, int, int]]:
    """LoRA targets: name -> (block, d_in, d_out)."""
    g = dims(model)
    d, H, Hkv, hd, f = g["d"], g["H"], g["Hkv"], g["hd"], g["f"]
    t = {"q": ("attn", d, H * hd), "k": ("attn", d, Hkv * hd),
         "v": ("attn", d, Hkv * hd), "o": ("attn", H * hd, d),
         "up": ("mlp", d, f), "down": ("mlp", f, d)}
    if is_glu(model):
        t["gate"] = ("mlp", d, f)
    return t


def weight_shapes(model) -> Dict[str, Any]:
    """The weight layout, as a tree of ShapeDtypeStructs."""
    kind = (model["norm"], model["activation"], bool(model.get("qkv_bias")),
            bool(model.get("tie_embeddings")),
            model["num_kv_heads"] == model["num_heads"])
    if kind != ("layernorm", "gelu", False, False, True):
        raise ValueError(f"the reference runs pre-LayerNorm GELU models "
                         f"with full attention heads and an untied head, "
                         f"not {kind}")
    g = dims(model)
    L, d, V = g["L"], g["d"], g["V"]
    S = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    norm = lambda *lead: {"scale": S(*lead, d), "bias": S(*lead, d)}
    seg = {"norm1": norm(L), "norm2": norm(L), "attn": {}, "mlp": {}}
    for name, (blk, din, dout) in targets(model).items():
        seg[blk][name] = {"w": S(L, din, dout)}
    return {"embed": S(V, d), "final_norm": norm(), "segments": [seg],
            "lm_head": {"w": S(d, V)}}


def _norm(p, x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS) * p["scale"] + p["bias"]


def _rope(x, positions, theta):
    """Rotary positions on (..., S, heads, hd), rotating the two halves of
    each head (the repository's convention)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def hidden(weights, model, adapters: Optional[Dict[str, Any]], scale,
           tokens, dt=jnp.float32):
    """Final-normed hidden states (N, S, d) for token ids (N, S)."""
    g = dims(model)
    H, hd = g["H"], g["hd"]
    N, S = tokens.shape
    cast = lambda t: jax.tree_util.tree_map(lambda a: a.astype(dt), t)
    seg = cast(weights["segments"][0])
    ads = cast(adapters or {})
    scale = jnp.asarray(scale, dt)
    x = weights["embed"].astype(dt)[tokens]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]

    def lin(h, p, name, ad):
        y = h @ p["w"]
        if name in ad:
            a, b = ad[name]
            y = y + scale * ((h @ a) @ b)
        return y

    def layer(x, lw):
        p, ad = lw
        h = _norm(p["norm1"], x)
        q = lin(h, p["attn"]["q"], "q", ad).reshape(N, S, H, hd)
        k = lin(h, p["attn"]["k"], "k", ad).reshape(N, S, H, hd)
        v = lin(h, p["attn"]["v"], "v", ad).reshape(N, S, H, hd)
        q = _rope(q, pos, model["rope_theta"])
        k = _rope(k, pos, model["rope_theta"])
        s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / jnp.asarray(
            math.sqrt(hd), dt)
        s = jnp.where(causal[None, None], s, jnp.asarray(NEG, dt))
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("nhqk,nkhd->nqhd", pr, v).reshape(N, S, H * hd)
        x = x + lin(o, p["attn"]["o"], "o", ad)
        h = _norm(p["norm2"], x)
        m = _gelu_tanh(lin(h, p["mlp"]["up"], "up", ad))
        x = x + lin(m, p["mlp"]["down"], "down", ad)
        return x, None

    x, _ = jax.lax.scan(layer, x, (seg, ads))
    return _norm(cast(weights["final_norm"]), x)


def head(weights, h, dt=jnp.float32):
    """Logits over the classes for hidden states h."""
    return h @ weights["lm_head"]["w"].astype(dt)


def class_loss(weights, model, adapters, scale, tokens, labels,
               dt=jnp.float32):
    """Mean cross-entropy of the last position's logits against class
    labels (the fleet's fine-tuning objective)."""
    h = hidden(weights, model, adapters, scale, tokens, dt)
    logits = head(weights, h[:, -1, :], dt).astype(jnp.float32)
    lp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(lp, labels[:, None], axis=-1))
