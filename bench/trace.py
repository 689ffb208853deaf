"""Reduction of a profiler trace (``*.xplane.pb``) to what the per-layer
metrics read: device busy time (the union of the intervals in which an
operation ran), device events by name, and idle gaps, each put down to the
harness span (``bench.<name>``) open on the host at the time.

The traced window is the stretch from the first to the last harness span
in the trace, which is the driver's measured window. Device planes are the
ones named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
operation run, named by the operation's HLO text (``%lora_matmul.268 =
f32[...] custom-call(...)``), with a loop's or a conditional's body nested
inside its event; their ``XLA Modules`` line holds one event per program
run.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    start: int          # ns
    end: int            # ns

    @property
    def dur(self) -> int:
        return self.end - self.start


def union_ns(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted intervals covering the same time as ``intervals``."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps_of(busy: Sequence[Tuple[int, int]], lo: int, hi: int
            ) -> List[Tuple[int, int]]:
    """The stretches of [lo, hi) that no busy interval covers."""
    out, cur = [], lo
    for a, b in busy:
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def innermost(spans: Sequence[Event], starts: Sequence[int], t: int
              ) -> Optional[str]:
    """The name of the latest-starting span open at time t; ``spans``
    sorted by start, ``starts`` their starts."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        s = spans[i]
        if s.start <= t < s.end:
            return s.name[len(SPAN_PREFIX):]
        i -= 1
    return None


OP_NAME = re.compile(r"^%?([A-Za-z_][A-Za-z0-9_\-]*?)(?:\.\d+)?(?: =|$)")


def short_name(hlo: str) -> str:
    """``%lora_matmul.268 = f32[..] custom-call(..)`` -> ``lora_matmul``."""
    m = OP_NAME.match(hlo)
    return m.group(1) if m else hlo[:40]


def self_times(events: Sequence[Event]) -> List[int]:
    """Each event's duration less the time of the events nested in it
    (a loop's body inside the loop's event), in ns, in input order."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start, -events[i].end))
    own = [e.dur for e in events]
    stack: List[int] = []
    for i in order:
        e = events[i]
        while stack and events[stack[-1]].end <= e.start:
            stack.pop()
        if stack and events[stack[-1]].end >= e.end:
            own[stack[-1]] -= e.dur
        stack.append(i)
    return own


@dataclasses.dataclass
class Reduced:
    window: Tuple[int, int]
    devices: List[Dict[str, List[Event]]]      # per device: line -> events
    spans: List[Event]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def ops(self, device: int = 0) -> List[Event]:
        return self.devices[device].get(OPS_LINE, [])

    def modules(self, device: int = 0) -> List[Event]:
        return self.devices[device].get(MODULES_LINE, [])

    def busy(self, device: int = 0) -> List[Tuple[int, int]]:
        lo, hi = self.window
        return clip(union_ns((e.start, e.end) for e in self.ops(device)),
                    lo, hi)

    @property
    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the devices."""
        n = max(1, len(self.devices))
        return sum(sum(b - a for a, b in self.busy(d))
                   for d in range(len(self.devices))) / n / 1e9

    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0 or not self.devices:
            return None
        return 1.0 - self.busy_s / self.window_s

    def gaps(self, device: int = 0) -> List[Tuple[str, float]]:
        """Idle gaps of the device as (harness span open then, seconds)."""
        lo, hi = self.window
        spans = sorted(self.spans, key=lambda s: s.start)
        starts = [s.start for s in spans]
        out = []
        for a, b in gaps_of(self.busy(device), lo, hi):
            out.append((innermost(spans, starts, (a + b) // 2) or "none",
                        (b - a) / 1e9))
        return out

    def op_seconds(self, device: int = 0) -> Dict[str, float]:
        """Self time of the device's operations in the window, by short
        operation name."""
        lo, hi = self.window
        evs = [e for e in self.ops(device) if e.end > lo and e.start < hi]
        tot: Dict[str, float] = collections.Counter()
        for e, own in zip(evs, self_times(evs)):
            tot[short_name(e.name)] += own / 1e9
        return dict(tot)

    def events_matching(self, pred, device: int = 0) -> List[Event]:
        lo, hi = self.window
        return [e for e in self.ops(device)
                if e.end > lo and e.start < hi and pred(e)]

    def breakdown(self, top: int = 10) -> Dict[str, List[List]]:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        by_span: Dict[str, float] = collections.Counter()
        for name, sec in self.gaps():
            by_span[name] += sec
        gaps = sorted(by_span.items(), key=lambda kv: -kv[1])
        return {"device_ops": [[n, s] for n, s in ops[:top]],
                "idle_gaps": [[n, s] for n, s in gaps[:top]]}


def reduce_file(path: str, n_devices: int = 1) -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: List[Dict[str, List[Event]]] = []
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and len(devices) < n_devices:
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        Event(e.name, int(e.start_ns), int(e.end_ns))
                        for e in line.events]
            devices.append(lines)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name, int(e.start_ns),
                                           int(e.end_ns)))
    if spans:
        window = (min(s.start for s in spans), max(s.end for s in spans))
    else:
        window = (0, 0)
    return Reduced(window=window, devices=devices, spans=spans)


def reduce_dir(trace_dir, n_devices: int = 1) -> Reduced:
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(files[-1], n_devices)
