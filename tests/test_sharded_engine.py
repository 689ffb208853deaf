"""Device-sharded fleet engine (ISSUE 5): fused_sharded must reproduce
the single-device fused engine, and the fleet slot-map / ShardSpec
machinery must hold its invariants.

The multi-device tests need a forced multi-device host
(XLA_FLAGS=--xla_force_host_platform_device_count=8 — CI's sharded-smoke
job exports it before pytest; device count must be set before jax
initializes, so it cannot be forced from inside the suite). They skip on
single-device hosts; the slot-map/ShardSpec/resolution tests always run.

Parity scope per the acceptance contract:
  merged ("ours")  — full engine parity, fused vs fused_sharded, on the
                     base config and a native hierarchy preset
                     (per-round AND scanned).
  hetlora          — the fused engine does not cover factor-averaging
                     baselines, so hetlora's sharded story is its
                     aggregation primitive: aggregate_hetlora_segmented
                     over fleet-mesh-sharded inputs must match the
                     single-device result (the batched engine consumes
                     that primitive unchanged).
"""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import LoRAConfig, ShardSpec
from repro.core import aggregation as agg
from repro.core import lora as lora_lib
from repro.federated.fused_engine import fleet_slots
from repro.models import transformer as T

LORA = LoRAConfig(rank=4, max_rank=8, candidate_ranks=(2, 4, 8))

multi_device = pytest.mark.skipif(
    jax.local_device_count() < 2,
    reason="needs a forced multi-device host (XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")


def _tiny_cfg():
    from repro.configs import vit_base_paper
    return vit_base_paper.vit_base_paper().with_overrides(
        name="vit-test-shard", num_layers=2, d_model=32, num_heads=2,
        num_kv_heads=2, head_dim=16, d_ff=64, vocab_size=64)


def _assert_parity(ha, hb, rel=2e-4):
    """Single-device fused history ha vs sharded history hb: integer
    trajectory facts exactly, float accounting to reassociation
    tolerance (the lane permutation and per-shard partial reductions
    reassociate the weighted sums)."""
    assert len(ha) == len(hb)
    for r_a, r_b in zip(ha, hb):
        for t_a, t_b in zip(r_a["tasks"], r_b["tasks"]):
            assert t_a["active"] == t_b["active"]
            assert t_a["departing"] == t_b["departing"]
            assert t_a["handoffs"] == t_b["handoffs"]
            assert t_a["comm_params"] == t_b["comm_params"]
            assert t_a["mean_rank"] == pytest.approx(t_b["mean_rank"],
                                                     abs=1e-5)
            assert t_a["energy"] == pytest.approx(t_b["energy"], rel=rel)
            assert t_a["lambda"] == pytest.approx(t_b["lambda"], abs=1e-4)
        assert r_a["energy"] == pytest.approx(r_b["energy"], rel=rel)
        # accuracy is quantized by the eval-set size; one borderline
        # argmax flip under float noise moves it ~1/N on the tiny arch
        assert r_a["accuracy"] == pytest.approx(r_b["accuracy"], abs=8e-3)
        assert r_a["budgets"] == pytest.approx(r_b["budgets"], rel=1e-5)


# ---------------------------------------------------------------------------
# Always-on: slot map + ShardSpec + engine resolution
# ---------------------------------------------------------------------------

def test_fleet_slots_roundrobin_balances_real_lanes():
    """Round-robin placement: each shard gets an equal (±1) share of
    real lanes, the map is injective, and padding spreads evenly."""
    for v_n, n in ((10, 4), (24, 8), (7, 3), (5, 8), (16, 1)):
        slot, vp = fleet_slots(v_n, n, "roundrobin")
        assert vp % n == 0 and vp >= v_n and vp - v_n < n
        assert len(set(slot.tolist())) == v_n          # injective
        per = vp // n
        shard_of = slot // per
        counts = np.bincount(shard_of, minlength=n)
        assert counts.max() - counts.min() <= 1, (v_n, n, counts)


def test_fleet_slots_block_keeps_order():
    slot, vp = fleet_slots(6, 4, "block")
    assert vp == 8
    assert np.array_equal(slot, np.arange(6))
    with pytest.raises(ValueError):
        fleet_slots(6, 4, "diagonal")
    with pytest.raises(ValueError):
        fleet_slots(6, 0)


def test_shard_spec_validation_and_resolution():
    assert ShardSpec().trivial
    assert not ShardSpec(num_shards=2).trivial
    assert not ShardSpec(num_shards=0).trivial   # 0 = all devices
    assert ShardSpec(num_shards=0).resolve() == jax.local_device_count()
    assert ShardSpec(num_shards=3).resolve() == 3
    with pytest.raises(ValueError):
        ShardSpec(num_shards=-1)
    with pytest.raises(ValueError):
        ShardSpec(placement="diagonal")
    with pytest.raises(ValueError):
        ShardSpec(axis_name="")


def test_engine_resolution_accepts_fused_sharded(monkeypatch):
    from repro.sim.simulator import IoVSimulator, SimConfig
    monkeypatch.setenv("REPRO_SIM_ENGINE", "fused_sharded")
    # env-auto choice falls back to batched for unsupported methods
    cfg = SimConfig(method="hetlora", train_arch=_tiny_cfg())
    assert IoVSimulator._resolve_engine(cfg) == "batched"
    cfg = SimConfig(method="ours", train_arch=_tiny_cfg())
    assert IoVSimulator._resolve_engine(cfg) == "fused_sharded"
    # explicit choice on an unsupported method raises
    with pytest.raises(ValueError, match="does not support"):
        IoVSimulator._resolve_engine(SimConfig(
            method="hetlora", engine="fused_sharded",
            train_arch=_tiny_cfg()))
    # an explicit non-fused engine refuses to silently drop an explicit
    # fleet sharding request
    with pytest.raises(ValueError, match="cannot shard"):
        IoVSimulator._resolve_engine(SimConfig(
            method="ours", engine="batched",
            shard=ShardSpec(num_shards=2), train_arch=_tiny_cfg()))
    # ...but the env-resolved engine matrix keeps working on sharded
    # configs (auto choice, not an explicit conflict)
    monkeypatch.setenv("REPRO_SIM_ENGINE", "batched")
    cfg = SimConfig(method="ours", shard=ShardSpec(num_shards=2),
                    train_arch=_tiny_cfg())
    assert IoVSimulator._resolve_engine(cfg) == "batched"
    # with NOTHING choosing an engine, an explicit shard request routes
    # the default to the fused (sharded) path instead of silently
    # dropping the spec on "batched"
    monkeypatch.delenv("REPRO_SIM_ENGINE", raising=False)
    assert IoVSimulator._resolve_engine(cfg) == "fused"
    cfg = SimConfig(method="hetlora", shard=ShardSpec(num_shards=2),
                    train_arch=_tiny_cfg())
    assert IoVSimulator._resolve_engine(cfg) == "batched"


def test_sharded_check_engine_rejected(monkeypatch):
    """fused_check replays lanes host-side in original order — an
    EXPLICIT fused_check + shard combo is refused at engine resolution,
    while an env-resolved check engine treats the spec as inert (like
    batched/serial: the CI engine matrix must not crash on sharded
    configs)."""
    from repro.sim.simulator import IoVSimulator, SimConfig
    with pytest.raises(ValueError, match="cannot shard|unsharded"):
        IoVSimulator(SimConfig(
            method="ours", num_vehicles=4, num_tasks=1, local_steps=1,
            engine="fused_check", shard=ShardSpec(num_shards=2),
            train_arch=_tiny_cfg(), lora=LORA))
    monkeypatch.setenv("REPRO_SIM_ENGINE", "fused_check")
    sim = IoVSimulator(SimConfig(
        method="ours", num_vehicles=4, num_tasks=1, local_steps=1,
        shard=ShardSpec(num_shards=2), train_arch=_tiny_cfg(), lora=LORA))
    assert sim.engine == "fused_check"
    assert sim.fused.n_shards == 1      # the spec is inert, not fatal


@pytest.mark.skipif(jax.local_device_count() != 1,
                    reason="needs a single-device host")
def test_fused_sharded_refuses_single_device_host():
    """engine='fused_sharded' on a 1-device host must raise, not
    silently run unsharded while claiming a sharded measurement."""
    from repro.sim.simulator import IoVSimulator, SimConfig
    with pytest.raises(ValueError, match="visible device"):
        IoVSimulator(SimConfig(
            method="ours", num_vehicles=4, num_tasks=1, local_steps=1,
            engine="fused_sharded", train_arch=_tiny_cfg(), lora=LORA))
    # num_shards=0 ("all devices") resolving to 1 must hit the same
    # guard, not silently run unsharded under the fused_sharded banner
    with pytest.raises(ValueError, match="visible device"):
        IoVSimulator(SimConfig(
            method="ours", num_vehicles=4, num_tasks=1, local_steps=1,
            engine="fused_sharded", shard=ShardSpec(num_shards=0),
            train_arch=_tiny_cfg(), lora=LORA))


def test_fleet_mesh_takes_eager_fresh_scatter():
    """make_fleet_mesh builds Auto axes, so the server's EAGER fresh-draw
    scatter into a mesh-sharded fleet template (RSUServer.fresh_padded,
    fused-engine staging) works outside any jax.set_mesh context. With
    JAX 0.9's default Explicit axes that scatter raises even on a
    one-device mesh, so this guards the axis-type choice on every host."""
    from jax.sharding import AxisType

    from repro.federated.server import RSUServer
    from repro.launch import sharding as sh
    from repro.launch.mesh import make_fleet_mesh
    mesh = make_fleet_mesh(1)
    assert mesh.axis_types == (AxisType.Auto,)
    cfg = _tiny_cfg()
    tmpl = T.init_adapters(jax.random.PRNGKey(0), cfg, LORA,
                           rank=LORA.max_rank)
    fleet = jax.tree_util.tree_map(
        lambda x: jnp.zeros((4,) + x.shape, x.dtype), tmpl)
    fleet = jax.device_put(fleet, sh.fleet_shardings(mesh, fleet,
                                                     fleet_size=4))
    srv = RSUServer(cfg, LORA, "ours", seed=0)
    out = srv.fresh_padded(2, fleet=fleet, slots=[1, 3])
    ref = RSUServer(cfg, LORA, "ours", seed=0).fresh_padded(2)
    for got, want in zip(jax.tree_util.tree_leaves(out),
                         jax.tree_util.tree_leaves(ref)):
        assert got.sharding.spec[0] == "fleet"
        np.testing.assert_array_equal(np.asarray(got[1::2]),
                                      np.asarray(want))
        assert not np.asarray(got[0::2]).any()


# ---------------------------------------------------------------------------
# Multi-device: engine parity (merged rule) + primitives (hetlora rule)
# ---------------------------------------------------------------------------

def _sim(engine, rounds=2, shard=None, **kw):
    from repro.sim.simulator import IoVSimulator, SimConfig
    cfg = SimConfig(
        method="ours", rounds=rounds, num_vehicles=6, num_tasks=2,
        seed=3, local_steps=1, engine=engine, train_arch=_tiny_cfg(),
        lora=LORA, **kw)
    if shard is not None:
        import dataclasses
        cfg = dataclasses.replace(cfg, shard=shard)
    return IoVSimulator(cfg)


def _scenario_sim(name, engine, rounds=2, seed=1, **kw):
    from repro.sim import scenarios
    return scenarios.build_sim(name, method="ours", rounds=rounds,
                               seed=seed, engine=engine,
                               train_arch=_tiny_cfg(), lora=LORA,
                               local_steps=1, **kw)


@multi_device
def test_sharded_matches_fused_base():
    """fused_sharded over every visible device == single-device fused on
    the base config, per-round (the V=6 fleet pads to the device count
    with zero-weight lanes)."""
    a = _sim("fused")
    b = _sim("fused_sharded")
    assert b.fused.n_shards == jax.local_device_count()
    assert b.fused.Vp % b.fused.n_shards == 0
    _assert_parity(a.run(), b.run())
    # merged server state must agree too (same tolerance story as
    # test_fused_engine.py's serial-vs-fused bound)
    for ta, tb in zip(a.servers, b.servers):
        assert (ta.merged is None) == (tb.merged is None)
        if ta.merged is not None:
            dev = max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(
                jax.tree_util.tree_leaves(ta.merged),
                jax.tree_util.tree_leaves(tb.merged)))
            assert dev < 5e-3


@multi_device
def test_sharded_knob_with_roundrobin_permutation():
    """A non-trivial ShardSpec on engine='fused' shards too, and a shard
    count that actually permutes lanes (V=6, N=4 → round-robin slots)
    still replays the unsharded trajectory."""
    spec = ShardSpec(num_shards=min(4, jax.local_device_count()))
    a = _sim("fused")
    b = _sim("fused", shard=spec)
    assert b.fused.n_shards == spec.num_shards
    if spec.num_shards == 4:
        assert not np.array_equal(b.fused.slot,
                                  np.arange(6))   # really permuted
    _assert_parity(a.run(), b.run())


@multi_device
def test_sharded_matches_fused_urban_grid():
    """The other fast-parity-subset preset (urban-grid, 1-RSU tier):
    sharding must also replay the trivial-tier program's trajectory."""
    a = _scenario_sim("urban-grid", "fused")
    b = _scenario_sim("urban-grid", "fused_sharded")
    _assert_parity(a.run(), b.run())


@multi_device
def test_sharded_matches_fused_hierarchy_preset():
    """Native multi-RSU preset (dense-rsu): per-RSU segment-sum
    partials, staleness syncs and handoff charges all shard."""
    a = _scenario_sim("dense-rsu", "fused")
    b = _scenario_sim("dense-rsu", "fused_sharded")
    _assert_parity(a.run(), b.run())
    for ta, tb in zip(a.servers, b.servers):
        assert np.allclose(ta.partial_w, tb.partial_w, rtol=1e-4)
        assert np.array_equal(ta.partial_age, tb.partial_age)


@multi_device
def test_sharded_matches_fused_semi_sync():
    """Semi-synchronous participation shards: the in-flight buffer (per-
    lane delta trees, weight/age/dest) rides the scan carry fleet-sharded
    and fused_sharded replays the unsharded semi_sync trajectory on the
    buffer-exercising preset."""
    from repro.config import ParticipationSpec
    part = ParticipationSpec(mode="semi_sync", max_delay=3)
    R = 8
    a = _scenario_sim("rsu-outage", "fused", rounds=R,
                      participation=part)
    b = _scenario_sim("rsu-outage", "fused_sharded", rounds=R,
                      participation=part)
    _assert_parity(a.run_scanned(R), b.run_scanned(R))
    # buffers mirror back in original lane order on both topologies
    for ta, tb in zip(a.servers, b.servers):
        assert sorted(ta.buffer) == sorted(tb.buffer)
        for v in ta.buffer:
            assert ta.buffer[v]["age"] == tb.buffer[v]["age"]
            assert ta.buffer[v]["dest"] == tb.buffer[v]["dest"]


@multi_device
def test_sharded_scanned_matches_per_round():
    """run_scanned under sharding == per-round sharded execution."""
    a = _sim("fused_sharded", rounds=3)
    b = _sim("fused_sharded", rounds=3)
    _assert_parity(a.run(), b.run_scanned(3))


@multi_device
def test_sharded_ucb_state_unpermuted_on_sync():
    """_sync_sim must hand host consumers per-vehicle UCB statistics in
    ORIGINAL lane order (engine switches / checkpointing read them)."""
    spec = ShardSpec(num_shards=min(4, jax.local_device_count()))
    a = _sim("fused")
    b = _sim("fused", shard=spec)
    a.run()
    b.run()
    for sa, sb in zip(a.ucb_states, b.ucb_states):
        assert sa.counts.shape == sb.counts.shape == (6, 3)
        assert np.allclose(np.asarray(sa.counts), np.asarray(sb.counts))
        assert np.allclose(np.asarray(sa.reward_sum),
                           np.asarray(sb.reward_sum), atol=1e-4)


@multi_device
def test_sharded_hetlora_segmented_primitive_parity():
    """aggregate_hetlora_segmented (and the merged twin) over
    fleet-mesh-sharded inputs == the single-device result — hetlora's
    sharded aggregation contract (the batched engine's server path
    consumes this primitive unchanged)."""
    from repro.launch import sharding as sh_rules
    from repro.launch.mesh import make_fleet_mesh

    cfg = _tiny_cfg()
    n = jax.local_device_count()
    V = 2 * n
    rng = np.random.default_rng(0)
    full = [T.init_adapters(jax.random.PRNGKey(i), cfg, LORA,
                            rank=LORA.max_rank) for i in range(V)]
    full = [jax.tree_util.tree_map(
        lambda x, i=i: x + 0.01 * (i + 1), ad) for i, ad in enumerate(full)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *full)
    ranks = jnp.asarray(rng.choice(LORA.candidate_ranks, V))
    stacked = lora_lib.mask_adapter_tree(
        stacked, lora_lib.rank_arange_mask(ranks, LORA.max_rank))
    weights = jnp.asarray(rng.uniform(0.5, 3.0, V), jnp.float32)
    assoc = jnp.asarray(rng.integers(-1, 3, V), jnp.int32)

    ref_h, ref_w = agg.aggregate_hetlora_segmented(
        stacked, weights, assoc, 3, LORA.max_rank)
    ref_m, _ = agg.aggregate_merged_padded_segmented(
        stacked, weights, assoc, 3, LORA.scale)

    mesh = make_fleet_mesh(n)
    sharded = jax.device_put(stacked, sh_rules.fleet_shardings(
        mesh, stacked, fleet_size=V))
    constrain = sh_rules.fleet_constrainer(mesh, V)
    got_h, got_w = jax.jit(lambda s, w, a: agg.aggregate_hetlora_segmented(
        s, w, a, 3, LORA.max_rank, constrain=constrain))(
        sharded, weights, assoc)
    got_m, _ = jax.jit(lambda s, w, a: agg.aggregate_merged_padded_segmented(
        s, w, a, 3, LORA.scale, constrain=constrain))(
        sharded, weights, assoc)

    assert np.allclose(np.asarray(ref_w), np.asarray(got_w), rtol=1e-5)
    for ref, got in ((ref_h, got_h), (ref_m, got_m)):
        for x, y in zip(jax.tree_util.tree_leaves(ref),
                        jax.tree_util.tree_leaves(got)):
            assert float(jnp.max(jnp.abs(x - y))) < 1e-5


@multi_device
def test_sharded_round_compiles_once_per_topology():
    """Recompile guard: across rounds with churn, the sharded round body
    compiles exactly ONE XLA program per device topology — the carry's
    output shardings are a fixed point of its input shardings."""
    compiles = []

    class Capture(logging.Handler):
        def emit(self, record):
            if ("Finished XLA compilation of jit(_round_step)"
                    in record.getMessage()):
                compiles.append(record.getMessage())

    handler = Capture()
    logger = logging.getLogger("jax._src.dispatch")
    logger.addHandler(handler)
    old_level = logger.level
    logger.setLevel(logging.DEBUG)
    try:
        with jax.log_compiles():
            sim = _sim("fused_sharded", rounds=4)
            sim.run()
    finally:
        logger.removeHandler(handler)
        logger.setLevel(old_level)
    assert len(compiles) == 1, compiles
    # vacuous unless the workload churned
    actives = {tuple(t["active"] for t in r["tasks"]) for r in sim.history}
    ranks = {round(t["mean_rank"], 3)
             for r in sim.history for t in r["tasks"]}
    assert len(actives) > 1 or len(ranks) > 1
