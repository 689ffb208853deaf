"""A kernel's share of its roofline, from the device trace.

For every trace event of the kernel, the operand shapes come from the
event's HLO text (the event's name), the work of that call from the kernel's
file under ``bench/work``, and its least time from the chip's peaks: the
larger of operations over peak FLOP/s and bytes over peak bandwidth. The
share is the summed least time over the summed measured time of those
events. It is a share of executed calls: the operations and bytes of the
shapes that ran, padding included.
"""
from __future__ import annotations

import re
import sys
from typing import Callable, Dict, List, Optional, Sequence, Tuple

SHAPE = re.compile(r"\b(?:pred|[suf]\d+|bf16|f8\w*)\[([0-9,]*)\]")


def shapes(long_name: str) -> Tuple[Tuple[int, ...], List[Tuple[int, ...]]]:
    """(output shape, operand shapes) from an HLO instruction's text."""
    dims = [tuple(int(d) for d in m.group(1).split(",") if d)
            for m in SHAPE.finditer(long_name)]
    if not dims:
        raise ValueError(f"no shapes in {long_name[:200]!r}")
    return dims[0], dims[1:]


def share(events: Sequence, work: Callable, peaks: Dict) -> Optional[Dict]:
    """Roofline share (%) of ``events``, each named by its HLO text; None
    when there are none. Also says which bound holds."""
    if not events:
        return None
    least = spent = t_flops = t_bytes = 0.0
    for e in events:
        out, ops = shapes(e.name)
        w = work(ops, out)
        tf = w["flops"] / peaks["bf16_flops_per_s"]
        tb = w["bytes"] / peaks["hbm_bytes_per_s"]
        least += max(tf, tb)
        t_flops += tf
        t_bytes += tb
        spent += e.dur / 1e9
    return {"share": 100.0 * least / spent,
            "bound": "memory" if t_bytes >= t_flops else "compute",
            "calls": len(events), "seconds": spent}


def kernel_share(ro, pattern: str, work: Callable) -> Optional[float]:
    """The roofline share of the device events whose HLO instruction name
    matches ``pattern``; None, with a note on stderr, when no event does."""
    from bench.trace import short_name
    rx = re.compile(pattern)
    events = ro.trace.events_matching(lambda e: bool(rx.fullmatch(
        short_name(e.name))))
    res = share(events, work, ro.peaks)
    if res is None:
        print(f"roofline: no device event matches {pattern!r}",
              file=sys.stderr)
        return None
    print(f"roofline {pattern}: {res['calls']} calls, {res['seconds']:.6f} "
          f"s, {res['bound']} bound, share {res['share']:.3f} %",
          file=sys.stderr)
    return res["share"]
