"""The benchmark's shared machinery: the manifest, the run context, the
device check, the compile counter, host spans and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own (``bench/configs``, ``bench/traffic``,
``bench/metrics``); this module finds those files by the names that
``BENCHMARK.json`` gives and holds nothing specific to any of them.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import logging
import re
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# JAX's persistent compilation cache: a fixed directory inside the
# checkout (the directory is part of the cache key, so it never moves).
CACHE_DIR = ROOT / ".bench_cache" / "jax"

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class BenchError(SystemExit):
    """A run that cannot produce a result: exit non-zero, print nothing."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


# ---------------------------------------------------------------------------
# Manifest
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Manifest:
    raw: Dict[str, Any]

    @classmethod
    def load(cls, path: Path = ROOT / "BENCHMARK.json") -> "Manifest":
        return cls(json.loads(Path(path).read_text()))

    def _by_name(self, key: str, name: str) -> Dict[str, Any]:
        for entry in self.raw[key]:
            if entry["name"] == name:
                return entry
        raise BenchError(f"no {key} entry named {name!r} in BENCHMARK.json")

    def workload(self, name: str) -> Dict[str, Any]:
        return self._by_name("workloads", name)

    def config(self, name: str) -> Dict[str, Any]:
        return self._by_name("configs", name)

    @staticmethod
    def _reports(metric: Dict[str, Any], cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    def end_to_end(self, cell: str) -> List[Dict[str, Any]]:
        return [m for m in self.raw["end_to_end"] if self._reports(m, cell)]

    def per_layer(self, cell: str) -> List[Dict[str, Any]]:
        return [m for m in self.raw["per_layer"] if self._reports(m, cell)]


def validate_manifest(raw: Dict[str, Any], root: Path = ROOT) -> List[str]:
    """Problems with a manifest, as readable strings (empty: none found).

    Checks the rules a reader of BENCHMARK.json relies on: names and units
    made of the allowed characters, unique names, every referenced config
    file and traffic file present, every per-layer metric moving an
    end-to-end metric that each of its cells reports, every cell reporting
    ``setup_s``, another end-to-end metric and a per-layer metric."""
    errs: List[str] = []
    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(raw) != want:
        errs.append(f"top-level keys {sorted(raw)} != {sorted(want)}")
        return errs
    names = set()
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in raw[key]:
            n = e.get("name", "")
            if not NAME_RE.match(n):
                errs.append(f"{key}: bad name {n!r}")
            if n in names:
                errs.append(f"duplicate name {n!r}")
            names.add(n)
    for m in raw["end_to_end"] + raw["per_layer"]:
        if not UNIT_RE.match(m.get("unit", "")):
            errs.append(f"{m['name']}: bad unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            errs.append(f"{m['name']}: better must be lower or higher")
    cfg_names = {c["name"] for c in raw["configs"]}
    for c in raw["configs"]:
        if not (root / c["file"]).is_file():
            errs.append(f"config {c['name']}: missing file {c['file']}")
        for k in c["reduced"]:
            if not NAME_RE.match(k):
                errs.append(f"config {c['name']}: bad reduced key {k!r}")
    cells = {w["name"]: w for w in raw["workloads"]}
    for w in raw["workloads"]:
        if w["config"] not in cfg_names:
            errs.append(f"cell {w['name']}: unknown config {w['config']}")
        if not NAME_RE.match(w["traffic"]):
            errs.append(f"cell {w['name']}: bad traffic name")
        elif not (root / "bench" / "traffic" /
                  f"{w['traffic']}.json").is_file():
            errs.append(f"cell {w['name']}: no traffic file for "
                        f"{w['traffic']}")
        if w["chips"] not in (1, 4):
            errs.append(f"cell {w['name']}: chips must be 1 or 4")
        if len(w["why"]) > 200:
            errs.append(f"cell {w['name']}: why longer than 200")
    e2e = {m["name"]: m for m in raw["end_to_end"]}
    if "setup_s" not in e2e:
        errs.append("no setup_s end-to-end metric")
    for m in raw["end_to_end"]:
        if not 0.01 <= m.get("bound", -1) <= 0.25:
            errs.append(f"{m['name']}: bound outside [0.01, 0.25]")
        if m["source"] not in ("host_clock", "device_trace"):
            errs.append(f"{m['name']}: end-to-end source {m['source']}")
    for m in raw["per_layer"]:
        if m["source"] not in ("device_trace", "program_span",
                               "program_counter", "host_clock"):
            errs.append(f"{m['name']}: bad source {m['source']}")
        if m["moves"] not in e2e:
            errs.append(f"{m['name']}: moves unknown metric {m['moves']}")
            continue
        target = e2e[m["moves"]]
        for cell in m.get("workloads", list(cells)):
            if cell not in cells:
                errs.append(f"{m['name']}: unknown cell {cell}")
            elif not Manifest._reports(target, cell):
                errs.append(f"{m['name']}: cell {cell} does not report "
                            f"{m['moves']}")
        reader = root / "bench" / "metrics" / f"{m['name']}.py"
        if not reader.is_file():
            errs.append(f"{m['name']}: no reader {reader.name}")
    man = Manifest(raw)
    for cell in cells:
        rep = {m["name"] for m in man.end_to_end(cell)}
        if "setup_s" not in rep or len(rep) < 2:
            errs.append(f"cell {cell}: needs setup_s and one more "
                        "end-to-end metric")
        if not man.per_layer(cell):
            errs.append(f"cell {cell}: no per-layer metric")
    return errs


def load_json(path: Path) -> Dict[str, Any]:
    return json.loads(Path(path).read_text())


def load_module(path: Path, name: Optional[str] = None):
    """Import a benchmark file by path (metric readers carry dots in their
    file names, so they are not importable as packages)."""
    name = name or "bench_" + re.sub(r"\W", "_", Path(path).stem)
    spec = importlib.util.spec_from_file_location(name, str(path))
    if spec is None or spec.loader is None:
        raise BenchError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# Device, compile cache, compile counter
# ---------------------------------------------------------------------------

def require_chips(n: int):
    """The devices to run on; refuses anything but a TPU with n chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU found (JAX platform "
                         f"{devices[0].platform!r}); refusing to run")
    if len(devices) < n:
        raise BenchError(f"the cell needs {n} chips, found {len(devices)}")
    return devices[:n]


def enable_compile_cache(path: Path = CACHE_DIR) -> Path:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    with every program eligible (so a warm run compiles nothing)."""
    import jax
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileLog(logging.Handler):
    """Counts XLA compilations and persistent-cache hits from JAX's logs:
    ``(program, seconds)`` per backend compile, and the programs that the
    persistent cache served."""

    _COMPILE = re.compile(
        r"Finished XLA compilation of (\S+) in ([0-9.e+-]+) sec")
    _HIT = re.compile(r"Persistent compilation cache hit for '([^']+)'")
    _MISS = re.compile(r"PERSISTENT COMPILATION CACHE MISS for '([^']+)'")
    _LOGGERS = ("jax._src.dispatch", "jax._src.compiler")

    def __init__(self):
        super().__init__()
        self.compiles: List[Tuple[str, float]] = []
        self.hits: List[str] = []
        self.misses: List[str] = []
        self._saved = {}

    def emit(self, record):
        msg = record.getMessage()
        m = self._COMPILE.search(msg)
        if m:
            self.compiles.append((m.group(1), float(m.group(2))))
            return
        for pat, out in ((self._HIT, self.hits), (self._MISS, self.misses)):
            m = pat.search(msg)
            if m:
                out.append(m.group(1))

    def mark(self) -> int:
        return len(self.compiles)

    def since(self, mark: int) -> List[Tuple[str, float]]:
        return self.compiles[mark:]

    def __enter__(self):
        for name in self._LOGGERS:
            lg = logging.getLogger(name)
            self._saved[name] = (lg.level, lg.propagate)
            lg.setLevel(logging.DEBUG)
            lg.propagate = False     # keep the DEBUG records off stderr
            lg.addHandler(self)
        return self

    def __exit__(self, *exc):
        for name in self._LOGGERS:
            lg = logging.getLogger(name)
            lg.removeHandler(self)
            lg.setLevel(self._saved[name][0])
            lg.propagate = self._saved[name][1]


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of the devices."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


# ---------------------------------------------------------------------------
# Run context
# ---------------------------------------------------------------------------

class Spans:
    """Host spans of the harness: kept in memory, and mirrored into the
    profiler's trace (``jax.profiler.TraceAnnotation``) so that idle gaps
    on the device can be put down to what the harness was doing."""

    def __init__(self):
        self.records: List[Tuple[str, float, float]] = []

    def span(self, name: str):
        return _Span(self, name)


class _Span:
    def __init__(self, owner: Spans, name: str):
        self.owner, self.name = owner, name

    def __enter__(self):
        import jax
        self._ann = jax.profiler.TraceAnnotation(f"bench.{self.name}")
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self.owner.records.append((self.name, self.t0, t1))


@dataclasses.dataclass
class Check:
    """One number compared against the plain reference, with its limit:
    the run is correct only if ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to the harness."""
    metrics: Dict[str, float]          # end-to-end, by name
    attempted: int
    failed: int
    checks: List[Check]
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Run:
    """Everything a driver may read: the cell, its configuration and
    traffic files, the seed, the window, and the harness's instruments."""
    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    seed: int
    seconds: float
    trace: bool
    devices: Any
    compile_log: CompileLog
    t_start: float
    spans: Spans = dataclasses.field(default_factory=Spans)
    trace_dir: Optional[Path] = None
    # filled by the driver: the traced window on the host clock, and the
    # device memory peak read before the reference runs
    window: Tuple[float, float] = (0.0, 0.0)
    memory_peak_bytes: int = 0

    def check(self, name: str, value: float) -> Check:
        if name not in self.limits:
            raise BenchError(f"no limit for {name!r} in the cell's limits")
        return Check(name, float(value), float(self.limits[name]))

    @property
    def window_seconds(self) -> float:
        """Length of the measured window: ``--seconds``, or in a traced run
        the traffic's ``trace_seconds`` where that is shorter (a trace of
        a few steps is enough, and a long one is slow to read)."""
        if self.trace:
            return min(self.seconds, float(self.traffic["trace_seconds"]))
        return self.seconds

    def start_trace(self) -> None:
        """Start the profiler when this run traces (host spans kept, the
        Python call tracer off)."""
        if self.trace:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)

    def stop_trace(self) -> None:
        if self.trace:
            import jax
            jax.profiler.stop_trace()

    def traced(self, fn: Callable[[], Any]) -> Any:
        """Run the measured window ``fn`` under the profiler when this run
        traces. Returns fn's result."""
        self.start_trace()
        try:
            return fn()
        finally:
            self.stop_trace()


# ---------------------------------------------------------------------------
# Result line
# ---------------------------------------------------------------------------

def peaks_for(device_kind: str) -> Dict[str, Any]:
    """The chip's published peaks; an unknown device is an error."""
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         "bench/peaks.json")
    return table[device_kind]


@dataclasses.dataclass
class Readout:
    """What a per-layer metric's reader reads: the run, the driver's
    outcome and counters, the reduced device trace and the chip's peaks."""
    run: Run
    outcome: Outcome
    trace: Any
    peaks: Dict[str, Any]

    @property
    def counters(self) -> Dict[str, Any]:
        return self.outcome.counters


def per_layer_metrics(run: Run, manifest: Manifest, outcome: Outcome,
                      reduced) -> Dict[str, Dict[str, Any]]:
    """The cell's per-layer metrics, each from its reader. A kernel's
    roofline (``<kernel>_roofline``) that finds no event of its kernel is
    left out of the line, with a note: a change that takes the kernel off
    the path silences it, and the step's ``mfu`` still bounds the gain.
    Any other metric that reads nothing ends the run."""
    ro = Readout(run, outcome, reduced, peaks_for(run.devices[0].device_kind))
    out = {}
    for m in manifest.per_layer(run.cell["name"]):
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(ro)
        if value is None:
            if not m["name"].endswith("_roofline"):
                raise BenchError(f"per-layer metric {m['name']} read "
                                 f"nothing in {run.cell['name']}")
            print(f"per-layer metric {m['name']}: its kernel ran no event "
                  f"in {run.cell['name']}; left out of the line",
                  file=sys.stderr)
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(run: Run, manifest: Manifest,
                outcome: Outcome) -> Dict[str, Any]:
    dev = run.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(run.devices),
              "memory_peak_bytes": int(run.memory_peak_bytes)}
    result: Dict[str, Any] = {
        "correct": bool(outcome.checks) and all(c.ok for c in
                                                outcome.checks),
        "attempted": int(outcome.attempted),
        "failed": int(outcome.failed),
    }
    if run.trace:
        from bench import trace as trace_lib
        reduced = trace_lib.reduce_dir(run.trace_dir, len(run.devices))
        result["metrics"] = per_layer_metrics(run, manifest, outcome,
                                              reduced)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["device"] = device
        result["breakdown"] = reduced.breakdown()
    else:
        units = {m["name"]: m["unit"] for m in manifest.end_to_end(
            run.cell["name"])}
        result["metrics"] = {k: {"value": float(v), "unit": units[k]}
                             for k, v in outcome.metrics.items()
                             if k in units}
        result["device"] = device
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in outcome.checks}
    return result
