"""The program's own measurement (repro.telemetry): span totals and
counters, the device scopes of the fused round program, the engine's host
spans and counters in a profiler trace, and the guarantee that none of it
changes what a seeded run computes."""
import contextlib
import glob

import jax
import numpy as np
import pytest

from repro import telemetry
from repro.config import CheckpointSpec, ParticipationSpec, RSUTierSpec

STAGES = ("round.select", "round.redistribute", "round.train",
          "round.local_eval", "round.costs", "round.aggregate",
          "round.global_eval", "round.dual")
SPANS = ("round", "stage", "place", "dispatch", "retrace", "wait", "record",
         "sync", "checkpoint")
ROUNDS = 5


def _cfg(**kw):
    from repro.sim.simulator import SimConfig
    base = dict(method="ours", rounds=ROUNDS, num_vehicles=6, num_tasks=2,
                seed=3, local_steps=2, engine="fused")
    base.update(kw)
    return SimConfig(**base)


# -- the module -------------------------------------------------------------

def test_spans_and_counters_add_up():
    tel = telemetry.Telemetry()
    for i in range(3):
        with tel.span("stage", round=i) as sp:
            sp.set(staged_steps=i)
    with tel.step("round", 7):
        pass
    assert tel.add("rounds") == 1
    assert tel.add("staged_steps", 5) == 5
    snap = tel.snapshot()
    assert snap["counters"] == {"rounds": 1, "staged_steps": 5}
    assert snap["spans"]["stage"]["count"] == 3
    assert snap["spans"]["round"]["count"] == 1
    assert snap["spans"]["stage"]["seconds"] >= 0.0
    # a snapshot is a copy: it does not move with later counts
    tel.add("rounds")
    assert snap["counters"]["rounds"] == 1


def test_span_totals_stay_bounded():
    tel = telemetry.Telemetry()
    for _ in range(1000):
        with tel.span("record"):
            pass
    assert list(tel.spans) == ["record"]
    assert tel.snapshot()["spans"]["record"]["count"] == 1000


# -- device scopes of the round program ------------------------------------

@pytest.mark.parametrize("kw", [
    {},
    {"rsu_tier": RSUTierSpec(num_rsus_per_task=2, sync_period=2,
                             staleness_decay=0.7),
     "participation": ParticipationSpec(mode="semi_sync", max_delay=2)},
], ids=["trivial", "semi_sync_multi_rsu"])
def test_round_program_carries_every_scope(kw):
    from repro.sim.simulator import IoVSimulator
    sim = IoVSimulator(_cfg(**kw))
    f = sim.fused
    f._init_carry()
    x, fresh = f._stage_round([True] * f.T)
    data = {"params": f._params, "fresh": fresh}
    text = jax.jit(f._round_step).lower(
        f._carry, f._place_x(x), data).as_text(debug_info=True)
    for t in range(f.T):
        for stage in STAGES:
            assert f"jit(_round_step)/task{t}/{stage}" in text, (t, stage)
    assert "jit(_round_step)/round.alloc" in text


# -- host spans and counters ------------------------------------------------

@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A seeded fused run: its first round (which traces the round
    program and checkpoints) under the profiler, then the rest."""
    from repro.sim.simulator import IoVSimulator
    tmp = tmp_path_factory.mktemp("telemetry")
    sim = IoVSimulator(_cfg(checkpoint=CheckpointSpec(
        interval=1, dir=str(tmp / "ck"))))
    staged = []
    inner = sim.fused._stage_arrays

    def spy(allow):
        x, fresh = inner(allow)
        staged.append(int(x["counts"].sum()))
        return x, fresh
    sim.fused._stage_arrays = spy
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp / "trace"), profiler_options=opts)
    try:
        sim.run(1)
    finally:
        jax.profiler.stop_trace()
    sim.run(ROUNDS - 1)
    path = glob.glob(str(tmp / "trace" / "**" / "*.xplane.pb"),
                     recursive=True)[-1]
    return sim, staged, path


def test_counters_after_five_rounds(traced):
    sim, staged, _ = traced
    f = sim.fused
    ranks = {tuple(t["mean_rank"] for t in r["tasks"]) for r in sim.history}
    assert len(ranks) > 1                 # the rank mix changed
    c = f.telemetry.snapshot()["counters"]
    assert c["retraces"] == 1
    assert c["rounds"] == ROUNDS
    assert len(staged) == ROUNDS
    assert c["staged_steps"] == sum(staged)
    assert c["lane_steps"] == ROUNDS * f.T * f.Vp * f.steps_full
    assert 0 < c["staged_steps"] <= c["lane_steps"]
    assert c["fresh_draws"] > 0
    assert f.telemetry is sim.telemetry


def test_trace_holds_every_span_with_its_args(traced):
    from jax.profiler import ProfileData
    sim, staged, path = traced
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                assert not e.name.startswith("bench.")
                if e.name.startswith("repro."):
                    found.setdefault(e.name[len("repro."):], []).append(
                        {k: v for k, v in e.stats})
    assert set(SPANS) <= set(found), sorted(found)
    stage = found["stage"][0]
    assert stage["round"] == 0
    assert stage["staged_steps"] == staged[0]
    assert stage["lane_steps"] == sim.fused.T * sim.fused.Vp * 2
    assert stage["active"] == sum(t["active"] for t in sim.history[0]["tasks"])
    assert stage["fresh"] == stage["active"]     # first round: all fresh
    assert found["round"][0]["step_num"] == 0
    assert found["retrace"][0]["retraces"] == 1
    for name in ("place", "dispatch", "wait", "record", "sync",
                 "checkpoint"):
        assert found[name][0]["round"] == 0, name


class _Quiet:
    def __init__(self, *a, **kw):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def set(self, **kw):
        pass


def test_instrumentation_does_not_change_the_run(traced, monkeypatch):
    from repro.sim.simulator import IoVSimulator
    sim, _, _ = traced
    monkeypatch.setattr(telemetry, "scope",
                        lambda name: contextlib.nullcontext())
    monkeypatch.setattr(telemetry.Telemetry, "span",
                        lambda self, name, **a: _Quiet())
    monkeypatch.setattr(telemetry.Telemetry, "step",
                        lambda self, name, n, **a: _Quiet())
    plain = IoVSimulator(_cfg())
    plain.run(ROUNDS)
    assert plain.telemetry.snapshot()["spans"] == {}
    assert plain.history == sim.history
    for a, b in zip(jax.tree_util.tree_leaves(plain.servers[0].merged),
                    jax.tree_util.tree_leaves(sim.servers[0].merged)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
