"""Operations a fleet round needs, from the configuration's sizes and the
round's staged work (the ``mfu.fleet`` numerator).

Counted: the local fine-tuning of every vehicle in coverage, forward and
backward with respect to activations and adapters with the base frozen
(``4·N`` per token for the blocks' weights, the attention products, the
adapters' ``6·r·(d_in+d_out)`` per token, and the head on the last
position), over the steps each vehicle actually trains; the local eval of
each trained adapter and the global eval of each task's merged adapter
(forward only). Not counted: recomputation under remat, the lanes and
padded steps that the program computes and discards, and the SVD and
aggregation (under 1 % of the total at these sizes).
"""
from __future__ import annotations

from typing import Dict


def _sizes(model: Dict) -> Dict[str, int]:
    d = model["d_model"]
    H = model["num_heads"]
    hd = model.get("head_dim") or d // H
    return {"L": model["num_layers"], "d": d, "H": H,
            "Hkv": model["num_kv_heads"], "hd": hd, "f": model["d_ff"],
            "V": model["vocab_size"]}


def block_params(model: Dict) -> int:
    s = _sizes(model)
    d, f, H, Hkv, hd = s["d"], s["f"], s["H"], s["Hkv"], s["hd"]
    glu = model["activation"] in ("swiglu", "geglu")
    per = d * hd * (H + 2 * Hkv) + H * hd * d + d * f * (3 if glu else 2)
    return s["L"] * per


def adapter_width(model: Dict) -> int:
    """Σ (d_in + d_out) over one layer's LoRA targets."""
    s = _sizes(model)
    d, f, H, Hkv, hd = s["d"], s["f"], s["H"], s["Hkv"], s["hd"]
    glu = model["activation"] in ("swiglu", "geglu")
    w = 2 * (d + H * hd) + 2 * (d + Hkv * hd) + (d + f) * (3 if glu else 2)
    return s["L"] * w


def forward_per_sequence(model: Dict, seq: int, rank: float) -> float:
    """Forward operations of one sequence of ``seq`` tokens, with a
    rank-``rank`` adapter, classifying from the last position."""
    s = _sizes(model)
    blocks = 2 * block_params(model) * seq
    attn = s["L"] * 2 * 2 * s["H"] * s["hd"] * seq * (seq + 1) / 2
    adapters = 2 * rank * adapter_width(model) * seq
    head = 2 * s["d"] * s["V"]
    return blocks + attn + adapters + head


def train_per_sequence(model: Dict, seq: int, rank: float) -> float:
    """Forward and backward (activations and adapters; the base frozen):
    the backward takes twice the forward's operations except for the
    frozen weights' gradients, which are not formed."""
    s = _sizes(model)
    blocks = 4 * block_params(model) * seq
    attn = 3 * s["L"] * 2 * 2 * s["H"] * s["hd"] * seq * (seq + 1) / 2
    adapters = 6 * rank * adapter_width(model) * seq
    head = 4 * s["d"] * s["V"]
    return blocks + attn + adapters + head


def round_flops(model: Dict, seq: int, batch: int, trained_steps: int,
                trained_rank_steps: float, local_eval_seqs: int,
                local_eval_rank_seqs: float, global_eval_seqs: int,
                max_rank: int) -> float:
    """Operations of one round. ``trained_steps``: Σ over vehicles of the
    steps each trained (``batch`` sequences a step); ``trained_rank_steps``:
    the same weighted by each vehicle's rank; ``local_eval_seqs``: the
    sequences the vehicles in coverage evaluate (``local_eval_rank_seqs``
    weighted by rank); ``global_eval_seqs``: the held-out sequences the
    tasks with a merged update evaluate at ``max_rank``."""
    t0 = train_per_sequence(model, seq, 0.0)
    dt = train_per_sequence(model, seq, 1.0) - t0
    f0 = forward_per_sequence(model, seq, 0.0)
    df = forward_per_sequence(model, seq, 1.0) - f0
    train = batch * (trained_steps * t0 + trained_rank_steps * dt)
    local = local_eval_seqs * f0 + local_eval_rank_seqs * df
    glob = global_eval_seqs * (f0 + max_rank * df)
    return train + local + glob
