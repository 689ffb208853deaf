"""Operations and bytes of the flash-attention kernel
(``kernels/flash_attention``).

One call attends q (.., H, Sq, D) to k, v (.., Hkv, Sk, D) block by block
and writes o (.., H, Sq, D). Its score and value products take
``4·H·Sq·Sk·D`` operations per leading index; the kernel computes every
block it visits, so the count is of the executed (padded) shapes. The
least traffic reads q, k and v once and writes o once. Leading axes (the
batch, and the lanes of a vmap) multiply both.
"""
from __future__ import annotations

from typing import Dict, Sequence


def kernel_call(lead: int, H: int, Hkv: int, Sq: int, Sk: int, D: int,
                dtype_bytes: int = 4) -> Dict[str, float]:
    flops = lead * 4 * H * Sq * Sk * D
    elems = lead * (2 * H * Sq * D + 2 * Hkv * Sk * D)
    return {"flops": float(flops), "bytes": float(dtype_bytes * elems)}


def from_operands(operands: Sequence[Sequence[int]], output
                  ) -> Dict[str, float]:
    """Work of a call from its operand shapes ``[q, k, v]``."""
    q, k = operands[0], operands[1]
    lead = 1
    for d in q[:-3]:
        lead *= d
    H, Sq, D = q[-3], q[-2], q[-1]
    Hkv, Sk = k[-3], k[-2]
    return kernel_call(lead, H, Hkv, Sq, Sk, D)
