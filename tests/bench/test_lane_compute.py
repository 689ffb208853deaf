"""The reader of ``lane_compute.fleet`` on host spans built here: 100 on
a program whose ``repro.stage`` spans carry no ``computed_steps`` (it
computes every lane), the share of ``lane_steps`` computed where they do,
0 on a program without telemetry, nothing where the span is renamed."""
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def pt():
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    import bench.program_trace as program_trace
    return program_trace


def _read(pt, monkeypatch, spans, window=(0, 1000)):
    from bench.harness import load_module
    trace = pt.ProgramTrace([{"XLA Ops": [], "XLA Modules": []}], spans)
    monkeypatch.setattr(pt, "for_readout", lambda ro: trace)
    ro = types.SimpleNamespace(
        run=types.SimpleNamespace(trace_dir=None, devices=[None]),
        trace=types.SimpleNamespace(window=window))
    reader = load_module(ROOT / "bench" / "metrics" / "lane_compute.fleet.py")
    return reader.read(ro)


def _stage(pt, start, **args):
    return pt.Event("repro.stage", start, start + 50, args, {})


def _round(pt, start):
    return pt.Event("repro.round", start, start + 300, {"step_num": 0}, {})


def test_spans_without_computed_steps_read_100(pt, monkeypatch):
    spans = [_round(pt, 100),
             _stage(pt, 200, staged_steps=54, lane_steps=216),
             _stage(pt, 600, staged_steps=108, lane_steps=216)]
    assert _read(pt, monkeypatch, spans) == pytest.approx(100.0)


def test_share_of_the_lane_steps_computed(pt, monkeypatch):
    spans = [_round(pt, 100),
             _stage(pt, 200, staged_steps=54, lane_steps=216,
                    computed_steps=90),
             _stage(pt, 600, staged_steps=81, lane_steps=216,
                    computed_steps=108),
             # starts after the window: not counted
             _stage(pt, 1200, staged_steps=0, lane_steps=216,
                    computed_steps=0)]
    assert _read(pt, monkeypatch, spans) == pytest.approx(
        100.0 * (90 + 108) / 432)
    assert _read(pt, monkeypatch, spans, window=(300, 1000)) == (
        pytest.approx(50.0))


def test_program_without_telemetry_reads_zero(pt, monkeypatch):
    assert _read(pt, monkeypatch, []) == 0.0


def test_renamed_span_reads_nothing(pt, monkeypatch):
    spans = [_round(pt, 100),
             pt.Event("repro.staging", 200, 250, {"lane_steps": 216}, {})]
    assert _read(pt, monkeypatch, spans) is None
