"""The program's own measurement in a profiler trace: device time by the
program's scopes, and its host spans with their arguments.

The program (``src/repro/telemetry.py``) names each stage of the fleet
round with a device scope (``jax.named_scope``: ``round.train``,
``round.global_eval``, ...) and wraps its host work in spans
``repro.<name>`` whose keyword arguments carry counters. A device
operation's scope path (its HLO ``op_name``) reaches the trace as the
``tf_op`` stat of the operation's event *metadata*, which
``jax.profiler.ProfileData`` does not show, so this file decodes the
``.xplane.pb`` protobuf itself, with no dependency beyond the standard
library. It reads only the fields it needs:

    XSpace.planes = 1
    XPlane: name = 2, lines = 3, event_metadata = 4 (map<int64, ..>),
            stat_metadata = 5 (map<int64, ..>)
    XLine: name = 2, timestamp_ns = 3, events = 4
    XEvent: metadata_id = 1, offset_ps = 2, duration_ps = 3, stats = 4
    XEventMetadata: id = 1, name = 2, stats = 5
    XStatMetadata: id = 1, name = 2
    XStat: metadata_id = 1, double = 2, uint64 = 3, int64 = 4, str = 5,
           bytes = 6, ref = 7 (the name of another stat metadata)

A scope matches as a whole element of the ``op_name`` path, also inside
the wrappers that transforms add (``vmap(transpose(jvp(round.train)))``);
an operation whose path holds several scopes (a fusion across a stage
boundary) counts in the first. Device time is self time
(``bench.trace.self_times``: a loop's or a conditional's body is not
counted twice) of the operations that overlap the traced window, as
``Reduced.op_seconds`` counts it.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
import struct
import sys
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from bench.trace import (MODULES_LINE, OPS_LINE, gaps_of, self_times,
                         short_name)

SPAN_PREFIX = "repro."
ROUND_PROGRAM = "jit__round_step"
# the first ``round.<stage>`` element of an op_name path
SCOPE = re.compile(r"(?:^|[/(;])(round\.[A-Za-z0-9_]+)(?=[/):;]|$)")


# ---------------------------------------------------------------------------
# Wire format
# ---------------------------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int = 0, end: Optional[int] = None
            ) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of a message in buf[i:end]; a
    length-delimited value comes as its (start, end) in buf."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            n, i = _varint(buf, i)
            v = (i, i + n)
            i += n
        elif wt == 1:
            v = buf[i:i + 8]
            i += 8
        elif wt == 5:
            v = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wt} at byte {i}")
        yield key >> 3, wt, v


def _str(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf: bytes, span, names: Dict[int, str]) -> Tuple[str, Any]:
    mid, val = 0, None
    for f, _, v in _fields(buf, *span):
        if f == 1:
            mid = v
        elif f == 2:
            val = struct.unpack("<d", v)[0]
        elif f == 3:
            val = v
        elif f == 4:
            val = _signed(v)
        elif f == 5:
            val = _str(buf, v)
        elif f == 6:
            val = buf[v[0]:v[1]]
        elif f == 7:
            val = names.get(v, "")
    return names.get(mid, str(mid)), val


@dataclasses.dataclass
class Event:
    name: str
    start: int                  # ns
    end: int                    # ns
    args: Dict[str, Any]        # the event's own stats (a span's arguments)
    meta: Dict[str, Any]        # its metadata's stats (tf_op, program_id)

    @property
    def dur(self) -> int:
        return self.end - self.start

    @property
    def tf_op(self) -> str:
        return self.meta.get("tf_op", "")


@dataclasses.dataclass
class Plane:
    name: str
    lines: Dict[str, List[Event]]


def _map_entry(buf, span) -> Tuple[int, Optional[Tuple[int, int]]]:
    key, value = 0, None
    for f, _, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane(buf: bytes, span, want_line) -> Plane:
    name, lines, emeta, smeta = "", [], [], []
    for f, _, v in _fields(buf, *span):
        if f == 2:
            name = _str(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            emeta.append(v)
        elif f == 5:
            smeta.append(v)
    stat_names: Dict[int, str] = {}
    for entry in smeta:
        key, value = _map_entry(buf, entry)
        for f, _, v in _fields(buf, *value) if value else ():
            if f == 2:
                stat_names[key] = _str(buf, v)
    metas: Dict[int, Tuple[str, Dict[str, Any]]] = {}
    for entry in emeta:
        key, value = _map_entry(buf, entry)
        mname, mstats = "", {}
        for f, _, v in _fields(buf, *value) if value else ():
            if f == 2:
                mname = _str(buf, v)
            elif f == 5:
                k, val = _stat(buf, v, stat_names)
                mstats[k] = val
        metas[key] = (mname, mstats)
    out: Dict[str, List[Event]] = {}
    for lspan in lines:
        lname, ts, evs = "", 0, []
        for f, _, v in _fields(buf, *lspan):
            if f == 2:
                lname = _str(buf, v)
            elif f == 3:
                ts = _signed(v)
            elif f == 4:
                evs.append(v)
        keep = want_line(name, lname)
        if not keep:
            continue
        events = out.setdefault(lname, [])
        for espan in evs:
            mid = off = dur = 0
            stats = []
            for f, _, v in _fields(buf, *espan):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = _signed(v)
                elif f == 3:
                    dur = v
                elif f == 4:
                    stats.append(v)
            mname, mstats = metas.get(mid, ("", {}))
            args = {}
            if keep is not True:
                if not keep(mname):
                    continue
                args = dict(_stat(buf, s, stat_names) for s in stats)
            t0 = ts * 1000 + off
            events.append(Event(mname, t0 // 1000, (t0 + dur) // 1000,
                                args, mstats))
    return Plane(name, out)


def decode(buf: bytes, want_line=lambda plane, line: True) -> List[Plane]:
    """The planes of a serialized XSpace. ``want_line(plane, line)``
    selects lines by their names: False skips a line, True keeps every
    event (without its own stats), a callable keeps the events whose name
    it accepts, with their stats as ``args``."""
    return [_plane(buf, v, want_line)
            for f, _, v in _fields(buf) if f == 1]


# ---------------------------------------------------------------------------
# The program's trace
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ProgramTrace:
    devices: List[Dict[str, List[Event]]]   # per device: line -> events
    spans: List[Event]                      # host spans repro.*

    @property
    def instrumented(self) -> bool:
        """Whether the program wrote any span of its own (a program from
        before its telemetry wrote none)."""
        return bool(self.spans)

    def ops(self, window, device: int = 0) -> List[Event]:
        lo, hi = window
        return [e for e in self.devices[device].get(OPS_LINE, [])
                if e.end > lo and e.start < hi]

    def round_runs(self, window, device: int = 0) -> List[Event]:
        """The round program's runs (``XLA Modules`` events named
        ``jit__round_step(<program id>)``) that overlap the window."""
        lo, hi = window
        return [e for e in self.devices[device].get(MODULES_LINE, [])
                if e.end > lo and e.start < hi
                and e.name.startswith(ROUND_PROGRAM)]

    def span_events(self, name: str, window) -> List[Event]:
        """Host spans named ``name`` that start inside the window."""
        lo, hi = window
        return [s for s in self.spans if s.name == name and lo <= s.start < hi]


def _device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def _want(plane: str, line: str):
    if _device_plane(plane):
        return line in (OPS_LINE, MODULES_LINE)
    if plane.startswith("/host:"):
        return lambda ev: ev.startswith(SPAN_PREFIX)
    return False


def load_file(path: str, n_devices: int = 1) -> ProgramTrace:
    with open(path, "rb") as fh:
        planes = decode(fh.read(), _want)
    devices, spans = [], []
    for p in planes:
        if _device_plane(p.name) and len(devices) < n_devices:
            devices.append(p.lines)
        elif p.name.startswith("/host:"):
            for evs in p.lines.values():
                spans.extend(evs)
    spans.sort(key=lambda s: s.start)
    return ProgramTrace(devices, spans)


_LOADED: Dict[str, ProgramTrace] = {}


def load_dir(trace_dir, n_devices: int = 1) -> ProgramTrace:
    """The trace the harness reduces (the last ``*.xplane.pb`` under
    trace_dir), decoded once per process."""
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    if files[-1] not in _LOADED:
        _LOADED[files[-1]] = load_file(files[-1], n_devices)
    return _LOADED[files[-1]]


def for_readout(ro) -> ProgramTrace:
    return load_dir(ro.run.trace_dir, len(ro.run.devices))


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------

def stage_of(tf_op: str) -> Optional[str]:
    """The first ``round.<stage>`` scope in an op_name path, or None."""
    m = SCOPE.search(tf_op)
    return m.group(1) if m else None


def scope_seconds(pt: ProgramTrace, window, device: int = 0,
                  programs=None) -> Dict[Optional[str], float]:
    """Device self seconds in the window by scope (None: no scope), of
    the operations of ``programs`` (program ids; None: of every
    program)."""
    evs = pt.ops(window, device)
    tot: Dict[Optional[str], float] = collections.Counter()
    for e, own in zip(evs, self_times(evs)):
        if programs is None or str(e.meta.get("program_id")) in programs:
            tot[stage_of(e.tf_op)] += own / 1e9
    return dict(tot)


def round_program_ids(pt: ProgramTrace, window, device: int = 0) -> set:
    """The program ids of the round program's runs in the window (an
    operation's metadata names its program by ``program_id``)."""
    return {e.name[len(ROUND_PROGRAM):].strip("()")
            for e in pt.round_runs(window, device)}


def _without_telemetry(pt: ProgramTrace, what: str) -> bool:
    """True, with a note, for a program that wrote no span of its own: a
    program from before its telemetry, where the readers read 0."""
    if pt.instrumented:
        return False
    print(f"program trace: no repro.* span in the trace; {what} read 0 "
          "(a program without telemetry)", file=sys.stderr)
    return True


def per_run_ms(ro, scopes: Sequence[str]) -> Optional[float]:
    """Device self time under ``scopes`` per run of the round program in
    the readout's window, in ms. None where the program has telemetry but
    no operation ran under the scopes (a renamed scope) or no round
    program ran in the window."""
    pt = for_readout(ro)
    if _without_telemetry(pt, ", ".join(scopes)):
        return 0.0
    window = ro.trace.window
    runs = len(pt.round_runs(window))
    by_scope = scope_seconds(pt, window,
                             programs=round_program_ids(pt, window))
    secs = [by_scope[s] for s in scopes if s in by_scope]
    if not runs or not secs:
        print(f"program trace: {', '.join(scopes)} ran no operation in "
              f"{runs} round-program runs", file=sys.stderr)
        return None
    return 1e3 * sum(secs) / runs


def window_spans(ro, name: str) -> Optional[List[Event]]:
    """The host spans ``name`` (``repro.<span>``) that start in the
    readout's window: [] for a program without telemetry, None where the
    program has telemetry but no such span (a renamed span)."""
    pt = for_readout(ro)
    if _without_telemetry(pt, name):
        return []
    spans = pt.span_events(name, ro.trace.window)
    if not spans:
        print(f"program trace: no {name} span in the window",
              file=sys.stderr)
        return None
    return spans


def round_program_coverage(pt: ProgramTrace, window, device: int = 0,
                           top: int = 8) -> Dict[str, Any]:
    """How much of the round program's device self time in the window
    falls under some ``round.*`` scope, and the unscoped operations that
    take most of the rest. An operation belongs to the round program by
    its metadata's ``program_id``, which the module's name carries."""
    ids = round_program_ids(pt, window, device)
    evs = pt.ops(window, device)
    scoped = total = 0.0
    unscoped: Dict[str, float] = collections.Counter()
    for e, own in zip(evs, self_times(evs)):
        if str(e.meta.get("program_id")) not in ids:
            continue
        total += own
        if stage_of(e.tf_op):
            scoped += own
        else:
            unscoped[short_name(e.name)] += own / 1e9
    worst = sorted(unscoped.items(), key=lambda kv: -kv[1])[:top]
    return {"share": 100.0 * scoped / total if total else None,
            "program_s": total / 1e9, "unscoped": worst}


def _innermost(spans: Sequence[Event], starts: Sequence[int], t: int
               ) -> Optional[str]:
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        s = spans[i]
        if s.start <= t < s.end:
            return s.name[len(SPAN_PREFIX):]
        i -= 1
    return None


def stage_table(pt: ProgramTrace, window, busy, device: int = 0
                ) -> Dict[str, Any]:
    """Per round of the window: device ms by scope, host ms by span and
    idle ms by the innermost ``repro.*`` span open at the gap's middle."""
    runs = max(1, len(pt.round_runs(window, device)))
    by_scope = scope_seconds(pt, window, device,
                             round_program_ids(pt, window, device))
    dev = {k or "(none)": 1e3 * v / runs for k, v in by_scope.items()}
    lo, hi = window
    host: Dict[str, float] = collections.Counter()
    for s in pt.spans:
        if lo <= s.start < hi:
            host[s.name[len(SPAN_PREFIX):]] += (s.end - s.start) / 1e6 / runs
    idle: Dict[str, float] = collections.Counter()
    starts = [s.start for s in pt.spans]
    for a, b in gaps_of(busy, lo, hi):
        name = _innermost(pt.spans, starts, (a + b) // 2) or "(none)"
        idle[name] += (b - a) / 1e6 / runs
    return {"rounds": runs, "device_ms": dev, "host_ms": dict(host),
            "idle_ms": dict(idle)}


_REPORTED: set = set()


def report(ro) -> None:
    """Print, once per run, the round program's scope coverage and the
    per-stage table on standard error."""
    key = str(ro.run.trace_dir)
    if key in _REPORTED:
        return
    _REPORTED.add(key)
    pt = for_readout(ro)
    window = ro.trace.window
    cov = round_program_coverage(pt, window)
    table = stage_table(pt, window, ro.trace.busy())

    def fmt(d):
        return ", ".join(f"{k} {v:.3f}" for k, v in
                         sorted(d.items(), key=lambda kv: -kv[1]))
    share = cov["share"]
    print(f"program trace: {table['rounds']} round-program runs; scoped "
          f"share of the round program's device self time "
          f"{'n/a' if share is None else f'{share:.3f} %'} of "
          f"{cov['program_s']:.6f} s; unscoped ops (s): "
          f"{fmt(dict(cov['unscoped'])) or '-'}", file=sys.stderr)
    for part in ("device_ms", "host_ms", "idle_ms"):
        print(f"program trace: per round, {part}: {fmt(table[part]) or '-'}",
              file=sys.stderr)
