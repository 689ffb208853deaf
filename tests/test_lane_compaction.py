"""Active-lane compaction of the fused fleet megastep.

The local training and the local eval compute only the lanes in coverage,
``C = chunk_width(lanes)`` at a time. On every lane in coverage they must
equal the full-width vmap (one chunk over every lane, the program before
compaction) to float32 rounding; every other lane keeps its distributed
adapter bit for bit and reads accuracy 0; the loop runs
``ceil(n_active / C)`` chunks, none when no lane is in coverage.
"""
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import LoRAConfig
from repro.core import lora as lora_lib
from repro.federated import fused_engine as fe
from repro.models import runmode
from repro.models import transformer as T
from repro.optim import adam

LORA = LoRAConfig(rank=4, max_rank=8, candidate_ranks=(2, 4, 8))
V, STEPS, B, S = 12, 2, 4, 8          # lanes, local steps, batch, tokens
C = fe.chunk_width(V)                  # 4
N_ACTIVE = (0, 1, C - 1, C, C + 1, V)

multi_device = pytest.mark.skipif(
    jax.local_device_count() < 2,
    reason="needs a forced multi-device host (XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")


def _tiny_cfg():
    from repro.configs import vit_base_paper
    return vit_base_paper.vit_base_paper().with_overrides(
        name="vit-test-compact", num_layers=2, d_model=32, num_heads=2,
        num_kv_heads=2, head_dim=16, d_ff=64, vocab_size=64)


def _engine(cfg):
    """The engine state the two fleet functions read."""
    return types.SimpleNamespace(model_cfg=cfg, lora_max=LORA,
                                 opt=adam(5e-3), steps_full=STEPS,
                                 remat=False)


def _fleet(cfg, n_active, kernel, seed=0, lanes=V):
    """A staged task: masked adapters, per-lane (scale[, rank mask]),
    batches, step budgets (0 outside coverage, 1 or STEPS inside) and a
    scattered active mask with ``n_active`` lanes set."""
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    k_p, k_a, k_t = jax.random.split(key, 3)
    params = T.init_params(k_p, cfg, dtype=jnp.float32)
    ads = jax.vmap(lambda k: T.init_adapters(k, cfg, LORA,
                                             rank=LORA.max_rank))(
        jax.random.split(k_a, lanes))
    # give the B factors content, so that every factor trains
    ads = jax.tree_util.tree_map(lambda x: x + 0.01, ads)
    ranks = jnp.asarray(rng.choice(LORA.candidate_ranks, lanes), jnp.int32)
    rmask = lora_lib.rank_arange_mask(ranks, LORA.max_rank)
    ads = lora_lib.mask_adapter_tree(ads, rmask)
    scale = LORA.alpha / ranks.astype(jnp.float32)
    scales = (scale, rmask) if kernel else scale
    active = np.zeros((lanes,), bool)
    active[rng.permutation(lanes)[:n_active]] = True
    counts = np.where(active, rng.choice([1, STEPS], lanes), 0)
    tokens = jax.random.randint(k_t, (lanes, STEPS, B, S), 0,
                                cfg.vocab_size)
    labels = jnp.asarray(rng.integers(0, 10, (lanes, STEPS, B)), jnp.int32)
    batch = {"tokens": tokens[0, 0], "labels": labels[0, 0]}
    return (params, ads, scales, tokens, labels,
            jnp.asarray(counts, jnp.int32), jnp.asarray(active), batch)


def _full_width(monkeypatch, fn, *args):
    """``fn`` (traced afresh) as the program ran before compaction: one
    chunk of every lane, every lane gathered."""
    with monkeypatch.context() as m:
        m.setattr(fe, "chunk_width", lambda lanes: lanes)
        return jax.jit(lambda *a: fn(*a))(*args)


@pytest.fixture
def route(request):
    if request.param == "kernel":
        with runmode.overrides(USE_PALLAS_LORA=True, PALLAS_INTERPRET=True):
            yield True
    else:
        yield False


def test_chunk_width_divides_the_lanes():
    assert [fe.chunk_width(n) for n in (1, 3, 4, 6, 7, 12, 24, 26)] == [
        1, 3, 4, 6, 7, 4, 4, 13]
    for n in range(1, 65):
        c = fe.chunk_width(n)
        assert n % c == 0 and min(4, n) <= c <= n


def test_computed_lanes_rounds_each_shard_up_to_whole_chunks():
    active = np.zeros((2, 24), bool)
    active[0, [0, 5, 9]] = True                  # task 0: 3 lanes
    active[1, :7] = True                         # task 1: 7 lanes
    # one shard of 24 lanes, C = 4: 4 + 8
    assert fe.computed_lanes(active) == 12
    # three shards of 8 lanes, C = 4: task 0 → shards 0 (2), 1 (1) → 8;
    # task 1 → shard 0 (7) → 8
    assert fe.computed_lanes(active, 3) == 16
    # four shards of 6 lanes, C = 6: task 0 → shards 0, 1; task 1 → 0, 1
    assert fe.computed_lanes(active, 4) == 24
    assert fe.computed_lanes(np.zeros((3, 24), bool)) == 0


@pytest.mark.parametrize("n_active", N_ACTIVE)
def test_over_active_runs_one_trip_a_chunk(n_active):
    """The loop's trip count is ceil(n_active / C); the lanes in coverage,
    and the first lanes outside it in lane order that fill out the last
    chunk, are computed once; the rest keep ``init``."""
    trips = []
    active = np.zeros((V,), bool)
    active[np.random.default_rng(n_active).permutation(V)[:n_active]] = True

    def fn(x):
        jax.debug.callback(lambda: trips.append(1))
        return x * 2.0 + 1.0

    x = jnp.arange(V, dtype=jnp.float32)
    out = jax.jit(lambda a, x: fe.over_active(fn, a, (x,), -x))(
        jnp.asarray(active), x)
    jax.effects_barrier()
    n_trips = -(-n_active // C)
    assert len(trips) == n_trips
    order = np.concatenate([np.flatnonzero(active),
                            np.flatnonzero(~active)])
    computed = np.zeros((V,), bool)
    computed[order[:n_trips * C]] = True
    assert computed[active].all()
    np.testing.assert_array_equal(
        np.asarray(out), np.where(computed, 2.0 * np.arange(V) + 1.0,
                                  -np.arange(V)))


@pytest.mark.parametrize("route", ["jnp", "kernel"], indirect=True)
@pytest.mark.parametrize("n_active", N_ACTIVE)
def test_compacted_train_and_eval_equal_full_width(monkeypatch, route,
                                                   n_active):
    cfg = _tiny_cfg()
    eng = _engine(cfg)
    params, ads, scales, tok, lab, counts, active, batch = _fleet(
        cfg, n_active, route)
    train = functools.partial(fe.FusedRoundEngine._train_fleet, eng)
    evalf = functools.partial(fe.FusedRoundEngine._eval_fleet, eng)
    got = jax.jit(train)(params, ads, scales, tok, lab, counts, active)
    acc = jax.jit(evalf)(params, got, scales, batch, active)
    every = jnp.ones((V,), bool)
    ref = _full_width(monkeypatch, train, params, ads, scales, tok, lab,
                      counts, every)
    ref_acc = _full_width(monkeypatch, evalf, params, ref, scales, batch,
                          every)
    act = np.asarray(active)
    for g, r, d in zip(*(jax.tree_util.tree_leaves(t)
                         for t in (got, ref, ads))):
        g, r, d = np.asarray(g), np.asarray(r), np.asarray(d)
        np.testing.assert_allclose(g[act], r[act], rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(g[~act], d[~act])
        if act.any():                     # the lanes in coverage trained
            assert np.any(g[act] != d[act])
    np.testing.assert_allclose(np.asarray(acc)[act],
                               np.asarray(ref_acc)[act], atol=1e-6)
    np.testing.assert_array_equal(np.asarray(acc)[~act], 0.0)


def test_fused_check_round_keeps_inactive_lanes_as_distributed():
    """Through the whole round program (``fused_check`` hands back each
    task's distributed and trained fleet trees): lanes outside coverage
    come back as distributed, bit for bit, and the replayed serial trainer
    agrees with the lanes in coverage."""
    from repro.sim.simulator import IoVSimulator, SimConfig
    sim = IoVSimulator(SimConfig(
        method="ours", rounds=2, num_vehicles=8, num_tasks=2, seed=3,
        local_steps=2, engine="fused_check", train_arch=_tiny_cfg(),
        lora=LORA))
    seen = []
    inner = sim.fused._run_check

    def spy(x, check):
        seen.append((np.asarray(x["active"]), check))
        return inner(x, check)
    sim.fused._run_check = spy
    sim.run()
    assert len(seen) == 2
    assert any(not a.all() for a, _ in seen)      # some lane is out
    for active, check in seen:
        for ti in range(sim.fused.T):
            out = ~active[ti]
            for d, n in zip(jax.tree_util.tree_leaves(check["dist"][ti]),
                            jax.tree_util.tree_leaves(check["new"][ti])):
                np.testing.assert_array_equal(np.asarray(n)[out],
                                              np.asarray(d)[out])
    assert sim.engine_check_rel < 1e-3


@multi_device
def test_sharded_compaction_equals_full_width(monkeypatch):
    """fused_sharded: each shard compacts its own lanes with its own trip
    count, under shard_map, and gives the unsharded full-width result."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_fleet_mesh
    n = jax.local_device_count()
    # eight lanes a shard, so C = 4 and up to two trips a shard
    lanes = 8 * n
    cfg = _tiny_cfg()
    mesh = make_fleet_mesh(n, axis_name="fleet")
    eng = _engine(cfg)
    eng.mesh = mesh
    eng.shard_spec = types.SimpleNamespace(axis_name="fleet")
    eng._per_shard = functools.partial(fe.FusedRoundEngine._per_shard, eng)
    params, ads, scales, tok, lab, counts, active, batch = _fleet(
        cfg, lanes // 2, False, seed=5, lanes=lanes)
    assert len({-(-int(np.asarray(active)[s * 8:(s + 1) * 8].sum()) // 4)
                for s in range(n)}) > 1       # trip counts differ
    shard = NamedSharding(mesh, P("fleet"))
    put = functools.partial(jax.device_put, device=shard)
    train = jax.jit(eng._per_shard(
        functools.partial(fe.FusedRoundEngine._train_fleet, eng),
        fleet_args=(1, 2, 3, 4, 5, 6)))
    evalf = jax.jit(eng._per_shard(
        functools.partial(fe.FusedRoundEngine._eval_fleet, eng),
        fleet_args=(1, 2, 4)))
    got = train(params, *map(put, (ads, scales, tok, lab, counts, active)))
    acc = evalf(params, got, put(scales), batch, put(active))
    plain = _engine(cfg)
    every = jnp.ones((lanes,), bool)
    ref = _full_width(monkeypatch, functools.partial(
        fe.FusedRoundEngine._train_fleet, plain),
        params, ads, scales, tok, lab, counts, every)
    ref_acc = _full_width(monkeypatch, functools.partial(
        fe.FusedRoundEngine._eval_fleet, plain),
        params, ref, scales, batch, every)
    act = np.asarray(active)
    for g, r, d in zip(*(jax.tree_util.tree_leaves(t)
                         for t in (got, ref, ads))):
        g, r, d = np.asarray(g), np.asarray(r), np.asarray(d)
        np.testing.assert_allclose(g[act], r[act], rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(g[~act], d[~act])
    np.testing.assert_allclose(np.asarray(acc)[act],
                               np.asarray(ref_acc)[act], atol=1e-6)
    np.testing.assert_array_equal(np.asarray(acc)[~act], 0.0)
