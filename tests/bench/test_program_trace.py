"""The decoder of the program's own measurement (bench/program_trace.py),
on the CPU: the wire format against the protobuf's own reader, scope
matching, the division per run of the round program, the readers of the
five per-layer metrics, and a trace recorded on a TPU v5e with two scopes
and the program's host spans (tests/bench/data, written by
record_scoped_trace.py there)."""
import json
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = ROOT / "tests" / "bench" / "data"
READERS = ("train_ms.fleet", "eval_ms.fleet", "redistribute_ms.fleet",
           "stage_ms.fleet", "lane_use.fleet")


@pytest.fixture(scope="module")
def pt():
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    import bench.program_trace as program_trace
    return program_trace


@pytest.fixture(scope="module")
def scoped(pt):
    meta = json.loads((DATA / "scoped.json").read_text())
    return pt.load_file(str(DATA / "scoped.xplane.pb")), meta


def _window(trace):
    evs = [e for d in trace.devices for line in d.values() for e in line]
    evs += trace.spans
    return (min(e.start for e in evs), max(e.end for e in evs) + 1)


# -- wire format ------------------------------------------------------------

def test_tf_op_of_the_kernel_in_the_small_trace(pt):
    trace = pt.load_file(str(DATA / "small.xplane.pb"))
    ops = trace.devices[0]["XLA Ops"]
    lora = [e for e in ops if e.name.startswith("%lora_matmul.1 ")]
    assert lora
    assert {e.tf_op for e in lora} == {
        "jit(<lambda>)/jit(lora_matmul)/pallas_call:"}
    assert not trace.instrumented         # harness spans only


@pytest.mark.parametrize("name", ["small.xplane.pb", "scoped.xplane.pb"])
def test_decoder_agrees_with_the_protobuf(pt, name):
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except Exception as e:          # noqa: BLE001 — any import failure
        pytest.skip(f"no protobuf reader for XSpace here: {e}")
    buf = (DATA / name).read_bytes()
    space = xplane_pb2.XSpace()
    space.ParseFromString(buf)
    planes = pt.decode(buf)
    assert [p.name for p in planes] == [p.name for p in space.planes]
    n = 0
    for ours, theirs in zip(planes, space.planes):
        stat = {k: v.name for k, v in theirs.stat_metadata.items()}
        for line in theirs.lines:
            got = ours.lines[line.name]
            assert len(got) == len(line.events)
            for e, ref in zip(got, line.events):
                md = theirs.event_metadata[ref.metadata_id]
                assert e.name == md.name
                tf_op = [s.str_value for s in md.stats
                         if stat[s.metadata_id] == "tf_op"]
                assert e.tf_op == (tf_op[0] if tf_op else "")
                t0 = line.timestamp_ns * 1000 + ref.offset_ps
                assert e.start == t0 // 1000
                assert e.end == (t0 + ref.duration_ps) // 1000
                n += 1
    assert n > 0


def test_host_span_args_come_with_the_spans(pt, scoped):
    trace, meta = scoped
    stages = [s for s in trace.spans if s.name == "repro.stage"]
    assert [{k: s.args[k] for k in ("round", "staged_steps", "lane_steps")}
            for s in stages] == meta["stage_args"]
    steps = [s for s in trace.spans if s.name == "repro.round"]
    assert [s.args["step_num"] for s in steps] == list(range(meta["rounds"]))
    assert not any(s.name.startswith("bench.") for s in trace.spans)


# -- scopes -----------------------------------------------------------------

@pytest.mark.parametrize("tf_op,stage", [
    ("jit(_round_step)/task0/round.train/vmap(while)/body/dot_general",
     "round.train"),
    ("jit(f)/vmap(transpose(jvp(round.train)))/checkpoint/mul",
     "round.train"),
    ("jit(f)/round.train/vmap(transpose(jvp(round.train)))/remat2",
     "round.train"),
    ("jit(_round_step)/task2/round.global_eval/cond/branch_1_fun/mul",
     "round.global_eval"),
    ("jit(<lambda>)/round.redistribute/cond/branch_1_fun/svd:",
     "round.redistribute"),
    ("jit(f)/round.train:", "round.train"),
    ("jit(f)/a/mul;jit(f)/round.costs/add", "round.costs"),
    ("jit(f)/round.select/x;jit(f)/round.costs/add", "round.select"),
    ("jit(f)/round.training/mul", "round.training"),
    ("jit(f)/xround.train/mul", None),
    ("jit(f)/task0/mul", None),
    ("", None),
])
def test_scope_matches_a_whole_path_element(pt, tf_op, stage):
    assert pt.stage_of(tf_op) == stage


def _ev(pt, name, start, end, tf_op="", program=7, **args):
    return pt.Event(name, start, end, args,
                    {"tf_op": tf_op, "program_id": program} if tf_op
                    else {"program_id": program})


def _synthetic(pt):
    """Two runs of the round program (program 7) and one of another
    program (9) in a window of 1000 ns; a loop nested in a training op."""
    ops = [
        _ev(pt, "%while.1", 0, 100, "jit(_round_step)/task0/round.train/w"),
        _ev(pt, "%fusion.2", 10, 60,
            "jit(_round_step)/task0/round.train/vmap(transpose(jvp(x)))"),
        _ev(pt, "%fusion.3", 100, 130,
            "jit(_round_step)/task0/round.local_eval/mul"),
        _ev(pt, "%copy.4", 130, 140),
        _ev(pt, "%while.5", 400, 500, "jit(_round_step)/task0/round.train/w"),
        _ev(pt, "%fusion.6", 500, 520,
            "jit(_round_step)/task1/round.global_eval/cond/mul"),
        _ev(pt, "%fusion.7", 520, 560,
            "jit(_round_step)/task1/round.redistribute/cond/svd"),
        _ev(pt, "%fusion.8", 800, 900, "jit(other)/round.train/mul",
            program=9),
    ]
    modules = [_ev(pt, "jit__round_step(7)", 0, 140),
               _ev(pt, "jit__round_step(7)", 400, 560),
               _ev(pt, "jit_other(9)", 800, 900)]
    spans = [pt.Event("repro.stage", 200, 260, {"staged_steps": 54,
                                                "lane_steps": 216}, {}),
             pt.Event("repro.stage", 600, 700, {"staged_steps": 108,
                                                "lane_steps": 216}, {}),
             pt.Event("repro.round", 150, 790, {"step_num": 1}, {})]
    return pt.ProgramTrace([{"XLA Ops": ops, "XLA Modules": modules}],
                           spans)


def _readout(pt, monkeypatch, trace, window=(0, 1000)):
    monkeypatch.setattr(pt, "for_readout", lambda ro: trace)
    return types.SimpleNamespace(
        run=types.SimpleNamespace(trace_dir=None, devices=[None]),
        trace=types.SimpleNamespace(window=window,
                                    busy=lambda: [(0, 140), (400, 560)]))


def _read(name, ro):
    from bench.harness import load_module
    return load_module(ROOT / "bench" / "metrics" / f"{name}.py").read(ro)


def test_self_time_by_scope_and_per_round_program_run(pt, monkeypatch):
    trace = _synthetic(pt)
    by = pt.scope_seconds(trace, (0, 1000))
    # the loop's own time excludes the op nested in it
    assert by["round.train"] == pytest.approx((50 + 50 + 100 + 100) * 1e-9)
    assert by[None] == pytest.approx(10e-9)
    assert pt.round_program_ids(trace, (0, 1000)) == {"7"}
    by = pt.scope_seconds(trace, (0, 1000), programs={"7"})
    assert by["round.train"] == pytest.approx((50 + 50 + 100) * 1e-9)
    assert len(trace.round_runs((0, 1000))) == 2
    ro = _readout(pt, monkeypatch, trace)
    # divided by the two jit__round_step runs, not by all three modules
    assert _read("train_ms.fleet", ro) == pytest.approx(200e-9 * 1e3 / 2)
    assert _read("eval_ms.fleet", ro) == pytest.approx(50e-9 * 1e3 / 2)
    assert _read("redistribute_ms.fleet", ro) == pytest.approx(
        40e-9 * 1e3 / 2)
    assert _read("stage_ms.fleet", ro) == pytest.approx(80e-6)
    assert _read("lane_use.fleet", ro) == pytest.approx(
        100.0 * 162 / 432)
    cov = pt.round_program_coverage(trace, (0, 1000))
    assert cov["share"] == pytest.approx(100.0 * 290 / 300)
    assert cov["unscoped"] == [("copy", pytest.approx(10e-9))]


def test_window_clips_runs_and_spans(pt, monkeypatch):
    trace = _synthetic(pt)
    assert len(trace.round_runs((300, 1000))) == 1
    ro = _readout(pt, monkeypatch, trace, window=(300, 1000))
    assert _read("train_ms.fleet", ro) == pytest.approx(100e-9 * 1e3)
    assert _read("lane_use.fleet", ro) == pytest.approx(50.0)


@pytest.mark.parametrize("name", READERS)
def test_program_without_telemetry_reads_zero(pt, monkeypatch, name):
    """A program from before its telemetry wrote no repro.* span and no
    scope: each reader reads 0 (the harness ends the run on None)."""
    trace = _synthetic(pt)
    for d in trace.devices:
        for e in d["XLA Ops"]:
            e.meta.pop("tf_op", None)
    trace.spans = [e for e in trace.spans if not e.name.startswith("repro")]
    assert _read(name, _readout(pt, monkeypatch, trace)) == 0.0


@pytest.mark.parametrize("name", READERS)
def test_renamed_scope_or_span_reads_nothing(pt, monkeypatch, name):
    """Where the program has telemetry but not the scope or span a reader
    names (a rename), the reader reads nothing, which ends the run."""
    trace = _synthetic(pt)
    for d in trace.devices:
        for e in d["XLA Ops"]:
            e.meta["tf_op"] = e.tf_op.replace("round.", "stage.")
    for s in trace.spans:
        s.name = s.name.replace("repro.stage", "repro.staging")
        s.args = {}
    assert _read(name, _readout(pt, monkeypatch, trace)) is None


# -- the chip-recorded trace ------------------------------------------------

def test_recorded_scopes_split_the_program(pt, scoped):
    trace, meta = scoped
    window = _window(trace)
    runs = trace.round_runs(window)
    assert len(runs) == meta["rounds"]
    by = pt.scope_seconds(trace, window,
                          programs=pt.round_program_ids(trace, window))
    per_run = {k: 1e3 * v / len(runs) for k, v in by.items()}
    # the recorder keeps the stages from fusing into each other, so each
    # scope's share of the program takes what its work takes alone
    for k, alone in meta["alone_ms"].items():
        assert per_run[k] == pytest.approx(alone, rel=0.1), k
    assert sum(per_run.values()) == pytest.approx(meta["program_ms"],
                                                  rel=0.15)
    cov = pt.round_program_coverage(trace, window)
    assert cov["share"] == pytest.approx(
        100.0 * (1.0 - per_run[None] / sum(per_run.values())))
    assert cov["share"] > 80.0
    assert [name for name, _ in cov["unscoped"]][0] == "copy-done"
    wrapped = {e.tf_op for e in trace.ops(window)
               if "transpose(jvp(round.train))" in e.tf_op}
    assert wrapped
