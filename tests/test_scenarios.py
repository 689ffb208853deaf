"""Scenario registry (repro.sim.scenarios): every preset builds and runs
under the round engines, dynamic-fleet invariants hold, and the fused
engine reproduces the serial reference on a churning-fleet scenario.

Fast tier: registry contract + one round per preset on the env-default
engine (the CI fast-tier matrix sets REPRO_SIM_ENGINE={batched,fused}, so
both engines cover every preset across the two legs).
Slow tier: explicit batched AND fused runs per preset, serial/fused parity
on rush-hour (time-varying fleet), and the rsu-outage coverage story.
"""
import numpy as np
import pytest

from repro.config import LoRAConfig
from repro.sim import scenarios
from repro.sim.simulator import IoVSimulator

LORA = LoRAConfig(rank=4, max_rank=8, candidate_ranks=(2, 4, 8))


def _tiny_cfg():
    from repro.configs import vit_base_paper
    return vit_base_paper.vit_base_paper().with_overrides(
        name="vit-test-scn", num_layers=2, d_model=32, num_heads=2,
        num_kv_heads=2, head_dim=16, d_ff=64, vocab_size=64)


def _build(name, engine=None, rounds=3, seed=1, **overrides):
    kw = dict(engine=engine, train_arch=_tiny_cfg(), lora=LORA,
              local_steps=1)
    kw.update(overrides)
    return scenarios.build_config(name, method="ours", rounds=rounds,
                                  seed=seed, **kw)


# ---------------------------------------------------------------------------
# Registry contract
# ---------------------------------------------------------------------------

def test_registry_lists_the_five_presets():
    names = scenarios.list_scenarios()
    for expected in ("urban-grid", "highway-corridor", "rush-hour",
                     "sparse-rural", "rsu-outage"):
        assert expected in names
    with pytest.raises(KeyError, match="unknown scenario"):
        scenarios.get_scenario("does-not-exist")


@pytest.mark.parametrize("name", scenarios.list_scenarios())
def test_preset_builds_config(name):
    cfg = _build(name)
    assert cfg.scenario == name
    assert cfg.rounds == 3
    assert cfg.mobility_sim.trace is not None
    sc = scenarios.get_scenario(name)
    assert sc.description


def test_overrides_flow_through():
    cfg = _build("urban-grid", num_vehicles=6, num_tasks=2)
    assert cfg.num_vehicles == 6 and cfg.num_tasks == 2
    # the fleet-scaled default budget tracks the overridden sizes
    assert cfg.energy.e_total == pytest.approx(110.0 * 6 * 2)


# ---------------------------------------------------------------------------
# One round per preset on the env-default engine (fast tier; the CI matrix
# runs this file once per engine)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def one_round():
    """One round of a preset, built and run once per (preset, resolved
    engine) for the whole module: the default-engine test and the
    explicit-engine test of the same engine share the simulator instead
    of compiling and running it twice."""
    runs = {}

    def get(name, engine=None):
        cfg = _build(name, engine=engine, rounds=2)
        key = (name, IoVSimulator._resolve_engine(cfg))
        if key not in runs:
            sim = IoVSimulator(cfg)
            runs[key] = (sim, sim.run(1))
        return runs[key]
    return get


@pytest.mark.parametrize("name", scenarios.list_scenarios())
def test_preset_one_round_default_engine(name, one_round):
    sim, h = one_round(name)
    assert len(h) == 1
    r = h[0]
    assert np.isfinite(r["energy"]) and r["energy"] >= 0.0
    assert 0.0 <= r["accuracy"] <= 1.0
    present = int(sim.mobility.present.sum())
    for t in r["tasks"]:
        assert t["active"] <= present, "active vehicles exceed the fleet"


# ---------------------------------------------------------------------------
# Dynamic-fleet invariants (rush-hour)
# ---------------------------------------------------------------------------

def test_rush_hour_participation_varies_and_respects_presence():
    # serial engine: the invariant is engine-independent (active masks come
    # from the one shared round_view) and serial avoids the batched
    # engine's per-(rank, bucket) compile storm under churn
    sim = IoVSimulator(_build("rush-hour", engine="serial", rounds=8,
                              seed=0, num_vehicles=10, num_tasks=2))
    presence_counts, active_by_round = [], []
    for _ in range(8):
        rec = sim.run_round()
        present = sim.mobility.present
        presence_counts.append(int(present.sum()))
        active_by_round.append(tuple(t["active"] for t in rec["tasks"]))
        for t in rec["tasks"]:
            assert t["active"] <= int(present.sum())
    assert len(set(presence_counts)) > 1, "fleet never churned"
    assert len(set(active_by_round)) > 1, "active sets never churned"


# ---------------------------------------------------------------------------
# Slow tier: both engines explicitly + parity + outage story
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("name", scenarios.list_scenarios())
@pytest.mark.parametrize("engine", ["batched", "fused"])
def test_preset_one_round_each_engine(name, engine, one_round):
    _, h = one_round(name, engine)
    assert len(h) == 1
    assert np.isfinite(h[0]["energy"])


@pytest.mark.slow
def test_rush_hour_serial_fused_parity():
    """Churning-fleet serial/fused equivalence: arrivals and departures
    are zero-weight lanes in the fused engine's rank-padded fleet arrays,
    so ranks / comm volume / energy / accuracy must replay the serial
    reference exactly (to float tolerance) while the active sets vary."""
    R = 5

    def run(engine):
        sim = IoVSimulator(_build("rush-hour", engine=engine, rounds=R,
                                  seed=1, num_vehicles=10, local_steps=2))
        if engine == "fused":
            return sim.run_scanned(R)
        return sim.run()

    hs, hf = run("serial"), run("fused")
    actives = set()
    for r_s, r_f in zip(hs, hf):
        for t_s, t_f in zip(r_s["tasks"], r_f["tasks"]):
            assert t_s["active"] == t_f["active"]
            assert t_s["departing"] == t_f["departing"]
            assert t_s["mean_rank"] == pytest.approx(t_f["mean_rank"],
                                                     abs=1e-5)
            assert t_s["comm_params"] == t_f["comm_params"]
            assert t_s["energy"] == pytest.approx(t_f["energy"], rel=1e-4)
        assert r_s["accuracy"] == pytest.approx(r_f["accuracy"], abs=1e-4)
        assert r_s["budgets"] == pytest.approx(r_f["budgets"], rel=1e-5)
        actives.add(tuple(t["active"] for t in r_s["tasks"]))
    assert len(actives) > 1, "fleet never churned — parity test is vacuous"


@pytest.mark.slow
def test_rsu_outage_round_trip():
    """Coverage collapses to zero for the outage window and the task
    recovers afterwards (handoff storm: participation jumps back)."""
    R = 9   # third=3: RSU 0 dark for rounds 3..5, RSU 1 for rounds 5..7
    sim = IoVSimulator(_build("rsu-outage", engine="batched", rounds=R,
                              seed=0))
    h = sim.run(R)
    task0 = [r["tasks"][0]["active"] for r in h]
    assert task0[3:6] == [0, 0, 0], task0
    assert sum(task0[:3]) > 0, "no coverage before the outage"
    assert sum(task0[6:]) > 0, "no recovery after the outage"
    # empty outage rounds must not poison accounting
    for r in h:
        assert np.isfinite(r["energy"]) and np.isfinite(r["accuracy"])
