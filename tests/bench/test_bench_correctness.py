"""The comparison that decides a run's ``correct``, at small sizes on the
CPU: the driver runs its whole path (set-up, window, reference) without
the harness's look for a chip; a sound program passes, and the program
with a fault planted underneath it, or the reference computed in
bfloat16 in its place, fails the cell's own limits. The reference's
choice of arms and its comparison are also checked on hand-made
rounds."""
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]

FLEET_CELL = "fleet.vit-base.paper"


@pytest.fixture(scope="module")
def harness():
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    import bench.harness as h
    return h


def _run(harness, cell, config_file, traffic_file, model, traffic_edit,
         seconds, seed, check=None):
    import jax
    cfg = harness.load_json(ROOT / "bench" / "configs" / config_file)
    cfg["model"].update(model)
    tr = harness.load_json(ROOT / "bench" / "traffic" / traffic_file)
    traffic_edit(tr)
    driver = harness.load_module(
        ROOT / "bench" / "drivers" / f"{tr['driver']}.py")
    with harness.CompileLog() as log:
        run = harness.Run(
            cell={"name": cell, "chips": 1}, config=cfg, traffic=tr,
            limits=harness.load_json(ROOT / "bench" / "limits" /
                                     f"{cell}.json"),
            seed=seed, seconds=seconds, trace=False,
            devices=jax.devices(), compile_log=log,
            t_start=time.perf_counter())
        out = driver.run(run, check=check)
    return out


TINY_VIT = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                head_dim=16, d_ff=128, vocab_size=64)


def _small_fleet(tr):
    tr["sim"].update(num_vehicles=6, num_tasks=2)


def run_fleet(harness, seed=2 ** 31 + 101, check=None):
    return _run(harness, FLEET_CELL, "vit-base-paper.json",
                "fleet-paper.json", TINY_VIT, _small_fleet, 1.0, seed, check)


def ok(outcome):
    return bool(outcome.checks) and all(c.ok for c in outcome.checks)


def test_fleet_sound_program_is_correct(harness):
    out = run_fleet(harness)
    got = {c.name: c.value for c in out.checks}
    assert ok(out), out.checks
    assert got["rank_mismatch"] == 0
    assert out.metrics["round_s"] > 0 and out.attempted >= 1
    # the checked rounds reach exploitation and redistributed training
    assert out.counters["exploit_decisions"] > 0
    assert 0.0 < got["change_gap"] < 0.01


def test_fleet_round_returning_its_state_unchanged_fails(harness,
                                                         monkeypatch):
    from repro.federated.fused_engine import FusedRoundEngine
    inner = FusedRoundEngine._round_step

    def unchanged(self, carry, x, data):
        out, rec = inner(self, carry, x, data)
        return dict(out, merged=carry["merged"]), rec
    monkeypatch.setattr(FusedRoundEngine, "_round_step", unchanged)
    assert not ok(run_fleet(harness))


def test_fleet_half_batch_left_out_fails(harness, monkeypatch):
    from repro.models import transformer as T
    inner = T.loss_fn

    def half(params, adapters, cfg, lora, batch, **kw):
        # the loss of the first half of each batch: the mean over the rest
        b = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return inner(params, adapters, cfg, lora, b, **kw)
    monkeypatch.setattr(T, "loss_fn", half)
    out = run_fleet(harness)
    assert not ok(out)
    assert {c.name: c.ok for c in out.checks}["merged_rel"] is False


def _fleet_driver(harness):
    return harness.load_module(ROOT / "bench" / "drivers" /
                               "fleet_rounds.py")


def test_fleet_bfloat16_control_fails(harness):
    driver = _fleet_driver(harness)
    got = {}

    def check(run, env, recorded):
        got.update(driver.readings(run, env, recorded))
        return got["program"]
    run_fleet(harness, check=check)
    limits = harness.load_json(ROOT / "bench" / "limits" /
                               f"{FLEET_CELL}.json")
    assert any(got["control"][k] > limits[k] for k in limits)
    for fault in got["faults"].values():
        assert any(fault[k] > limits[k] for k in limits)


# -- the fleet's motion ------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 39, 2 ** 31 + 101])
def test_fixed_mobility_draws_as_the_simulator(harness, seed):
    """Drawn from the simulator's own seed, the motion that the driver
    puts in its place is the simulator's own: the same RSUs and the same
    vehicles in coverage, tick after tick."""
    from repro.config import get_arch
    from repro.sim.simulator import IoVSimulator, SimConfig
    mcfg = get_arch("vit-base-paper").with_overrides(**TINY_VIT)
    sim = IoVSimulator(SimConfig(seed=seed, num_vehicles=6, num_tasks=2,
                                 train_arch=mcfg, engine="fused"))
    own, own_groups = sim.mobility, sim.rsu_groups
    _fleet_driver(harness).fixed_mobility(sim, seed)
    assert sim.mobility is not own
    assert ([[r.xy for r in g] for g in sim.rsu_groups]
            == [[r.xy for r in g] for g in own_groups])
    for _ in range(8):
        own.step()
        sim.mobility.step()
        np.testing.assert_array_equal(sim.mobility.pos, own.pos)
        for g, og in zip(sim.rsu_groups, own_groups):
            np.testing.assert_array_equal(
                sim.mobility.round_view_group(g)["active"],
                own.round_view_group(og)["active"])


def test_fleet_motion_is_the_same_for_every_seed(harness):
    """Two seeds feed the checked rounds the same vehicles in coverage and
    different data."""
    driver = _fleet_driver(harness)
    fed = []

    def check(run, env, recorded):
        fed.append([r["x"] for r in recorded])
        return driver.check_recorded(run, env, recorded)
    for seed in (2 ** 31 + 101, 7):
        assert ok(run_fleet(harness, seed=seed, check=check))
    a, b = fed
    assert len(a) == len(b) > 0
    for ra, rb in zip(a, b):
        np.testing.assert_array_equal(ra["active"], rb["active"])
        np.testing.assert_array_equal(ra["counts"], rb["counts"])
    assert any(not np.array_equal(ra["tokens"], rb["tokens"])
               for ra, rb in zip(a, b))


# -- the reference's pieces on hand-made rounds -----------------------------

def _ucb(counts, reward_sum, energy_sum, lam=0.0, rnd=5.0):
    return {"counts": np.asarray(counts, np.float32),
            "reward_sum": np.asarray(reward_sum, np.float32),
            "energy_sum": np.asarray(energy_sum, np.float32),
            "lam": np.float32(lam), "round": np.float32(rnd)}


def test_arms_explore_in_order_then_exploit(harness):
    from bench.reference import fleet as ref
    z = np.zeros((3, 4))
    counts = [[0, 0, 0, 0], [1, 1, 0, 1], [2, 1, 1, 1]]
    rewards = [[0, 0, 0, 0], [0.1, 0.2, 0, 0.9], [0.2, 0.5, 0.1, 0.2]]
    energy = [[0, 0, 0, 0], [1, 1, 0, 1], [1.0, 1.0, 1.0, 0.2]]
    got = ref.select_arms(_ucb(counts, rewards, energy, lam=0.1), 2 ** 0.5,
                          np.asarray([True, True, True]))
    # unexplored arms first, lowest first; then the best score:
    # r/n - lam*e/n + eps*sqrt(ln 5/(n+1)) over the third vehicle's arms
    n = np.asarray(counts[2], float)
    score = (np.asarray(rewards[2]) - 0.1 * np.asarray(energy[2])) / n \
        + 2 ** 0.5 * np.sqrt(np.log(5.0) / (n + 1))
    assert got.tolist() == [0, 2, int(np.argmax(score))]
    off = ref.select_arms(_ucb(z, z, z), 1.0, np.asarray([False, True, False]))
    assert off.tolist() == [-1, 0, -1]


def _rounds():
    """Two rounds of one task with two targets: the first trains from the
    draws, the second from the first's merged update."""
    rng = np.random.default_rng(0)

    def tree(d):
        return {"segments": [{"attn": {k: {"delta": v}
                                       for k, v in d.items()}}]}

    m1 = {"q": rng.normal(size=(2, 4, 4)), "o": rng.normal(size=(2, 4, 4))}
    m2 = {k: v + 0.1 * rng.normal(size=v.shape) for k, v in m1.items()}
    x = {"active": np.ones((1, 3), bool)}
    recorded = [{"x": x, "has_merged_in": [False], "merged_out": [tree(m1)]},
                {"x": x, "has_merged_in": [True], "merged_out": [tree(m2)]}]
    view = [{"arms": [np.zeros(3, int)], "energy": [1.0], "merged": [m1]},
            {"arms": [np.ones(3, int)], "energy": [2.0], "merged": [m2]}]
    return recorded, view


def test_comparison_reads_each_number(harness):
    from bench.reference import fleet as ref
    recorded, want = _rounds()
    same = ref.compare(want, want, recorded)
    assert same == {"rank_mismatch": 0.0, "energy_rel": 0.0,
                    "merged_rel": 0.0, "change_gap": 0.0}
    m1 = want[0]["merged"][0]
    grown = [dict(want[0], merged=[{k: 1.5 * v for k, v in m1.items()}]),
             dict(want[1], energy=[2.2], arms=[np.asarray([1, 0, 1])],
                  merged=[{k: m1[k] + 1.2 * (v - m1[k]) for k, v in
                           want[1]["merged"][0].items()}])]
    got = ref.compare(grown, want, recorded)
    assert got["rank_mismatch"] == 1.0
    assert got["energy_rel"] == pytest.approx(0.1)
    assert got["merged_rel"] == pytest.approx(0.5)
    # a change 1.2 times the reference's, against its own norm or the
    # median target's, whichever is larger
    assert 0.1 < got["change_gap"] <= 0.2 + 1e-9
    still = ref.compare(ref.unchanged(want, recorded), want, recorded)
    assert still["merged_rel"] == 1.0 and still["change_gap"] == 1.0
