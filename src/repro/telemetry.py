"""The program's own measurement: host spans, counters and device scopes.

Host spans open a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``,
so a profiler trace shows them on the same clock as the device, with their
keyword arguments as event stats; each span's count and seconds also add
up in memory, where ``snapshot()`` reads them without a profiler. Counters
are plain integers. Device scopes (:func:`scope`) are ``jax.named_scope``:
HLO metadata that names every operation traced under them (the ``op_name``
that a device trace reports as ``tf_op``), free at run time.

Names carry no prefix; the ``repro.`` prefix is added here, so a program
span can never be taken for one of the benchmark harness's ``bench.*``
spans.
"""
from __future__ import annotations

import time
from typing import Any, Dict

import jax

PREFIX = "repro."


def scope(name: str):
    """Device scope: every operation traced inside carries ``name`` in its
    ``op_name`` path (inside transforms too, e.g. ``vmap(jvp(name))``)."""
    return jax.named_scope(name)


class _Span:
    __slots__ = ("_owner", "_name", "_ann", "_t0")

    def __init__(self, owner: "Telemetry", name: str, ann):
        self._owner, self._name, self._ann = owner, name, ann

    def __enter__(self) -> "_Span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        tot = self._owner.spans.setdefault(self._name, [0, 0.0])
        tot[0] += 1
        tot[1] += dt

    def set(self, **args: Any) -> None:
        """Add arguments known only at the span's end (a trace shows them
        like those given at its start)."""
        self._ann.set_metadata(**args)


class Telemetry:
    """Spans and counters of one engine. Memory stays bounded however long
    a run: one count and one total of seconds per span name."""

    def __init__(self):
        self.counters: Dict[str, int] = {}
        self.spans: Dict[str, list] = {}

    def add(self, name: str, n: int = 1) -> int:
        """Add ``n`` to counter ``name``; returns the new total."""
        total = self.counters.get(name, 0) + int(n)
        self.counters[name] = total
        return total

    def span(self, name: str, **args: Any) -> _Span:
        """Host span ``repro.<name>`` with ``args`` as trace stats."""
        return _Span(self, name,
                     jax.profiler.TraceAnnotation(PREFIX + name, **args))

    def step(self, name: str, n: int, **args: Any) -> _Span:
        """Like :meth:`span`, as a profiler step (XProf's step view)."""
        return _Span(self, name, jax.profiler.StepTraceAnnotation(
            PREFIX + name, step_num=int(n), **args))

    def snapshot(self) -> Dict[str, Any]:
        """Counters, and each span's count and total seconds."""
        return {"counters": dict(self.counters),
                "spans": {k: {"count": c, "seconds": s}
                          for k, (c, s) in self.spans.items()}}
