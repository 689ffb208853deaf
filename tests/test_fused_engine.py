"""Fused round engine: rank-padding invariants, equivalence against the
serial reference, the multi-round scan, and the single-compilation guard.

Fast tier: pure-function equivalence of the rank-padded aggregation /
redistribution primitives, engine resolution rules, and a one-round smoke
on the env-default engine (the CI matrix sets REPRO_SIM_ENGINE).
Slow tier: multi-round IoVSimulator regressions — the fused engine must
reproduce the serial engine's selected ranks, energy accounting and
aggregated adapters, per-round and under `run_scanned`.
"""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import LoRAConfig
from repro.core import aggregation as agg
from repro.core import lora as lora_lib
from repro.models import transformer as T

LORA = LoRAConfig(rank=4, max_rank=8, candidate_ranks=(2, 4, 8))


def _tiny_cfg(vocab=64):
    from repro.configs import vit_base_paper
    return vit_base_paper.vit_base_paper().with_overrides(
        name="vit-test-fused", num_layers=2, d_model=32, num_heads=2,
        num_kv_heads=2, head_dim=16, d_ff=64, vocab_size=vocab)


def _max_dev(tree_a, tree_b):
    return max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(tree_a), jax.tree_util.tree_leaves(tree_b)))


def _padded_clients(cfg, ranks, seed=0):
    """(stacked padded fleet tree, serial per-client truncated trees) whose
    unpadded contents are elementwise identical."""
    full = [T.init_adapters(jax.random.PRNGKey(seed + i), cfg, LORA,
                            rank=LORA.max_rank)
            for i in range(len(ranks))]
    # give B factors nonzero content (zero-init otherwise)
    full = [jax.tree_util.tree_map(
        lambda x, i=i: x + 0.01 * (i + 1), ad) for i, ad in enumerate(full)]
    mask = lora_lib.rank_arange_mask(jnp.asarray(ranks), LORA.max_rank)
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *full)
    padded = lora_lib.mask_adapter_tree(stacked, mask)
    serial = [lora_lib.truncate_adapter_tree(ad, r)
              for ad, r in zip(full, ranks)]
    return padded, serial


def test_padded_aggregation_matches_serial():
    """aggregate_merged_padded over the rank-padded fleet == aggregate_merged
    over per-client truncated trees (zero tails are exact no-ops)."""
    cfg = _tiny_cfg()
    ranks = [2, 4, 8, 4]
    padded, serial = _padded_clients(cfg, ranks)
    weights = [2.0, 1.0, 3.0, 0.5]
    ref = agg.aggregate_merged(serial, weights, LORA.scale)
    got = agg.aggregate_merged_padded(padded, jnp.asarray(weights),
                                      LORA.scale)
    assert _max_dev(ref, got) < 1e-5


def test_padded_aggregation_zero_weight_lanes_are_noops():
    cfg = _tiny_cfg()
    ranks = [2, 4, 8, 4]
    padded, serial = _padded_clients(cfg, ranks)
    ref = agg.aggregate_merged(serial[:2], [2.0, 1.0], LORA.scale)
    got = agg.aggregate_merged_padded(
        padded, jnp.asarray([2.0, 1.0, 0.0, 0.0]), LORA.scale)
    assert _max_dev(ref, got) < 1e-5


def test_shared_svd_factors_match_redistribute():
    """factors_for_ranks over one shared seeded SVD == redistribute at each
    vehicle's rank (the serial path recomputes the same seeded SVD per
    unique rank, so sharing it is exact)."""
    cfg = _tiny_cfg()
    ranks = [2, 4, 8]
    padded, serial = _padded_clients(cfg, ranks, seed=3)
    merged = agg.aggregate_merged(serial, [1.0, 2.0, 1.0], LORA.scale)
    svd = agg.merged_svd(merged, LORA.max_rank, seed=7)
    mask = lora_lib.rank_arange_mask(jnp.asarray(ranks), LORA.max_rank)
    fleet = agg.factors_for_ranks(svd, mask, LORA.scale)
    for i, r in enumerate(ranks):
        ref = agg.redistribute(merged, rank=r, scale=LORA.scale,
                               max_rank=LORA.max_rank, seed=7)
        lane = lora_lib.truncate_adapter_tree(
            jax.tree_util.tree_map(lambda x: x[i], fleet), r)
        assert _max_dev(ref, lane) < 1e-5, r
        # the padded tail beyond r must be identically zero
        if r < LORA.max_rank:
            padded_lane = jax.tree_util.tree_map(lambda x: x[i], fleet)
            for path in agg.tree_paths(padded_lane):
                ad = agg.tree_get(padded_lane, path)
                assert float(jnp.abs(ad["a"][..., r:]).max()) == 0.0
                assert float(jnp.abs(ad["b"][..., r:, :]).max()) == 0.0


def test_factors_full_matches_eval_adapters_view():
    cfg = _tiny_cfg()
    _, serial = _padded_clients(cfg, [4, 8], seed=5)
    merged = agg.aggregate_merged(serial, [1.0, 1.0], LORA.scale)
    ref = agg.redistribute(merged, rank=LORA.max_rank, scale=LORA.scale,
                           max_rank=LORA.max_rank, seed=0)
    got = agg.factors_full(agg.merged_svd(merged, LORA.max_rank, seed=0),
                           LORA.scale)
    assert _max_dev(ref, got) < 1e-5


def test_engine_resolution_rules(monkeypatch):
    """env-default engine falls back to batched for unsupported methods;
    an explicit fused choice raises instead of silently degrading."""
    from repro.sim.simulator import IoVSimulator, SimConfig

    monkeypatch.setenv("REPRO_SIM_ENGINE", "fused")
    cfg = SimConfig(method="homolora", num_vehicles=2, num_tasks=1,
                    train_arch=_tiny_cfg())
    assert IoVSimulator._resolve_engine(cfg) == "batched"
    with pytest.raises(ValueError, match="does not support"):
        IoVSimulator._resolve_engine(SimConfig(
            method="homolora", engine="fused", train_arch=_tiny_cfg()))
    monkeypatch.setenv("REPRO_SIM_ENGINE", "nonsense")
    with pytest.raises(ValueError, match="unknown engine"):
        IoVSimulator._resolve_engine(SimConfig(train_arch=_tiny_cfg()))
    # resolution never writes back into the caller's config: a reused
    # SimConfig keeps engine=None and re-resolves per simulator
    monkeypatch.delenv("REPRO_SIM_ENGINE", raising=False)
    cfg = SimConfig(method="ours", num_vehicles=2, num_tasks=1,
                    train_arch=_tiny_cfg())
    sim = IoVSimulator(cfg)
    assert sim.engine == "batched" and cfg.engine is None


def test_fused_check_rejects_run_scanned():
    """fused_check verifies round by round; a scanned run would silently
    skip the serial replay and must be refused."""
    from repro.sim.simulator import IoVSimulator, SimConfig

    sim = IoVSimulator(SimConfig(
        method="ours", rounds=1, num_vehicles=2, num_tasks=1, seed=2,
        local_steps=1, engine="fused_check", train_arch=_tiny_cfg(),
        lora=LORA))
    with pytest.raises(ValueError, match="round by round"):
        sim.run_scanned(1)


def test_default_engine_smoke():
    """One round on the env-default engine (the CI fast tier runs this
    under REPRO_SIM_ENGINE={batched,fused})."""
    from repro.sim.simulator import IoVSimulator, SimConfig

    sim = IoVSimulator(SimConfig(
        method="ours", rounds=1, num_vehicles=4, num_tasks=1, seed=2,
        local_steps=1, train_arch=_tiny_cfg(),
        lora=LoRAConfig(rank=4, max_rank=8, candidate_ranks=(2, 4, 8))))
    h = sim.run()
    assert len(h) == 1
    assert np.isfinite(h[0]["energy"])
    assert h[0]["energy"] >= 0


# ---------------------------------------------------------------------------
# Simulator-level regressions (slow tier)
# ---------------------------------------------------------------------------

def _sim(engine, rounds=3):
    from repro.sim.simulator import IoVSimulator, SimConfig
    return IoVSimulator(SimConfig(
        method="ours", rounds=rounds, num_vehicles=8, num_tasks=2,
        seed=3, local_steps=2, engine=engine))


def _assert_histories_match(hs, hf, rel=1e-4):
    for r_s, r_f in zip(hs, hf):
        for t_s, t_f in zip(r_s["tasks"], r_f["tasks"]):
            assert t_s["mean_rank"] == pytest.approx(t_f["mean_rank"],
                                                     abs=1e-5)
            assert t_s["comm_params"] == t_f["comm_params"], r_s["round"]
            assert t_s["active"] == t_f["active"]
            assert t_s["departing"] == t_f["departing"]
            assert t_s["energy"] == pytest.approx(t_f["energy"], rel=rel)
            assert t_s["lambda"] == pytest.approx(t_f["lambda"], abs=1e-4)
        assert r_s["energy"] == pytest.approx(r_f["energy"], rel=rel)
        assert r_s["accuracy"] == pytest.approx(r_f["accuracy"], abs=1e-4)
        assert r_s["budgets"] == pytest.approx(r_f["budgets"], rel=1e-5)


@pytest.mark.slow
def test_sim_regression_fused_matches_serial():
    """3-round IoVSimulator: the fused engine reproduces the serial
    engine's selected ranks, energy accounting and aggregated adapters."""
    s = _sim("serial")
    f = _sim("fused")
    _assert_histories_match(s.run(), f.run())
    # the aggregated server state (merged deltas) must match too; float
    # reassociation noise (~1e-6/round) compounds through the SVD→train→
    # aggregate loop, so the 3-round bound is looser than single-round
    # equivalence (which fused_check pins at <1e-5)
    for ti in range(2):
        ms, mf = s.servers[ti].merged, f.servers[ti].merged
        assert (ms is None) == (mf is None)
        if ms is not None:
            assert _max_dev(ms, mf) < 5e-3, ti


@pytest.mark.slow
def test_run_scanned_matches_per_round():
    """R rounds under lax.scan == R per-round fused calls (identical
    staging streams, same program body)."""
    a = _sim("fused")
    b = _sim("fused")
    ha = a.run()
    hb = b.run_scanned(3)
    _assert_histories_match(ha, hb, rel=1e-4)


@pytest.mark.slow
def test_fused_check_mode_deviation_bounded():
    """fused_check replays the serial LocalTrainer on the identical staged
    batches/adapters — the megastep's training must sit at float noise."""
    sim = _sim("fused_check", rounds=2)
    sim.run()
    assert sim.engine_check_dev < 1e-5


def test_remat_trains_the_same_adapters():
    """FusedRoundEngine.remat — on by default off the CPU, where the
    fleet's saved activations would not fit one chip — recomputes each
    layer's forward in the backward pass. It must train the same
    adapters as the saved-residual program the CPU runs by default."""
    saved = _sim("fused", rounds=1)
    remat = _sim("fused", rounds=1)
    assert not saved.fused.remat          # the CPU default
    remat.fused.remat = True              # read at trace time
    _assert_histories_match(saved.run(), remat.run(), rel=1e-6)
    for ti in range(2):
        ma, mb = saved.servers[ti].merged, remat.servers[ti].merged
        assert (ma is None) == (mb is None)
        if ma is not None:
            assert _max_dev(ma, mb) < 1e-6, ti


@pytest.mark.slow
def test_fused_round_compiles_exactly_once():
    """Recompile guard (jax.log_compiles): across rounds with varying
    active-vehicle sets and rank mixes, the fused round body compiles
    exactly ONE XLA program — the whole point of rank padding."""
    compiles = []

    class Capture(logging.Handler):
        def emit(self, record):
            msg = record.getMessage()
            if "Finished XLA compilation of jit(_round_step)" in msg:
                compiles.append(msg)

    handler = Capture()
    logger = logging.getLogger("jax._src.dispatch")
    logger.addHandler(handler)
    old_level = logger.level
    logger.setLevel(logging.DEBUG)
    try:
        with jax.log_compiles():
            sim = _sim("fused", rounds=5)
            sim.run()
    finally:
        logger.removeHandler(handler)
        logger.setLevel(old_level)
    assert len(compiles) == 1, compiles
    # the guard is only meaningful if the workload actually churned:
    # coverage and rank mixes must vary across the rounds
    actives = [tuple(t["active"] for t in r["tasks"]) for r in sim.history]
    mean_ranks = {round(t["mean_rank"], 3)
                  for r in sim.history for t in r["tasks"]}
    assert len(set(actives)) > 1 or len(mean_ranks) > 1
    assert len(mean_ranks) > 1
