"""Operations and bytes of the fused LoRA linear (``kernels/lora_matmul``).

The linear computes ``y = x·W + scale·((x·A)⊙mask)·B``. The program forms
``t = x·A`` outside the kernel; one kernel call then streams x and W once,
reads t and B, and writes y once: ``2·M·K·N + 2·M·r·N`` operations over
``M·K + K·N + M·r + r·N + M·N`` elements. Under the fleet's vmap one call
covers every lane: x, t, B and y carry a leading lane axis, W (the frozen
base) does not.
"""
from __future__ import annotations

from typing import Any, Dict


def lora_linear_intensity(M: int, K: int, N: int, r: int,
                          dtype_bytes: int = 4) -> Dict[str, Any]:
    """FLOPs and minimum HBM traffic of the whole LoRA linear, both routes
    (the jnp route materializes the base and low-rank products apart; the
    fused route writes the output surface once)."""
    flops = 2 * M * K * N + 2 * M * K * r + 2 * M * r * N
    small = K * N + K * r + r * N + 2 * M * r      # W, A, B, t traffic
    jnp_bytes = dtype_bytes * (2 * M * K + small + 5 * M * N)
    fused_bytes = dtype_bytes * (2 * M * K + small + M * N)
    return {
        "flops": flops,
        "jnp_bytes": jnp_bytes,
        "fused_bytes": fused_bytes,
        "jnp_ai": flops / jnp_bytes,
        "fused_ai": flops / fused_bytes,
    }


def kernel_call(M: int, K: int, N: int, r: int, lanes: int = 1,
                dtype_bytes: int = 4) -> Dict[str, float]:
    """One kernel call: ``lanes`` lanes of (M, K)·(K, N) with a rank-r
    epilogue, W shared by the lanes."""
    flops = lanes * (2 * M * K * N + 2 * M * r * N)
    elems = lanes * (M * K + M * r + r * N + M * N) + K * N
    return {"flops": float(flops), "bytes": float(dtype_bytes * elems)}


def from_operands(operands, output) -> Dict[str, float]:
    """Work of a call from its operand shapes ``[x, w, t, b, mask, scale]``
    and output shape, as the trace's HLO text gives them: x (.., M, K),
    w (K, N), t (.., M, r), y (.., M, N); leading axes are lanes."""
    x, w, t = operands[0], operands[1], operands[2]
    M, K = x[-2], x[-1]
    N = w[-1]
    r = t[-1]
    lanes = 1
    for d in x[:-2]:
        lanes *= d
    return kernel_call(M, K, N, r, lanes)
