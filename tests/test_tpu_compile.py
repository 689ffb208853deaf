"""Compile the main path for a described TPU v5e chip, with no chip attached.

The TPU compiler is installed next to the CPU backend, so a program can be
lowered and compiled for a ``v5e:2x2`` topology that is only described.
That catches what the Pallas interpreter cannot: block shapes the TPU
lowering rejects, VMEM overuse, and programs that do not fit in HBM. The
shapes are ViT-Base's (d_model 768, d_ff 3072, 12 heads of 64) at the
fused round's own sizes: 24 vehicles, batch 10, 24-token sequences, LoRA
rank padded to 32.

The topology is described inside a module fixture and never at import
time: only one process may load the TPU library at once, and every test
worker imports this file. All such compiles live in this one file so that
one worker holds the library.
"""
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.config import LoRAConfig, ServeSpec
from repro.configs.vit_base_paper import vit_base_paper
from repro.models import runmode
from repro.models import transformer as T

V, B, S, R = 24, 10, 24, 32          # vehicles, batch, tokens, max rank
M = B * S                            # rows of every LoRA GEMM in the round
HBM_BYTES = 16 * 10**9               # one v5e chip


@pytest.fixture(scope="module")
def v5e_2x2():
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")   # keep compiler logs off /tmp
    cache_was = jax.config.jax_enable_compilation_cache
    # a persistent-cache entry written for a described chip can never be
    # read back here; keep these compiles out of any configured cache
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies
    # the TPU compiler ships with the pinned jax; failing to describe the
    # chip is a fault of the installation, never a reason to skip
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    yield topo
    jax.config.update("jax_enable_compilation_cache", cache_was)
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


@pytest.fixture(scope="module")
def fleet_mesh(v5e_2x2):
    """The fused_sharded engine's 1-D fleet mesh over the four chips."""
    return Mesh(np.array(v5e_2x2.devices), ("fleet",),
                axis_types=(AxisType.Auto,))


@pytest.fixture
def tpu_kernels():
    """Trace with the compiled kernels, as "auto" resolves on a TPU host
    (here the backend is the CPU, so the default would pick jnp)."""
    with runmode.overrides(USE_PALLAS_ATTN=True, USE_PALLAS_LORA=True,
                           PALLAS_INTERPRET=False):
        yield


def _spec(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("k,n", [(768, 768), (768, 3072), (3072, 768)])
def test_lora_matmul_compiles(one_chip, k, n):
    """Forward at the round's GEMM shape, and forward + adapter grads under
    the fleet vmap with per-vehicle scales and rank masks."""
    from repro.kernels.lora_matmul import lora_matmul

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def fwd(x, w, a, b, scale, mask):
        return lora_matmul(x, w, a, b, scale=scale, rank_mask=mask,
                           interpret=False)

    c = _compile(fwd, f32(M, k), f32(k, n), f32(k, R), f32(R, n), f32(),
                 f32(R))
    assert "tpu_custom_call" in c.as_text()

    def fleet_loss(x, w, a, b, scale, mask):
        y = jax.vmap(fwd, in_axes=(0, None, 0, 0, 0, 0))(x, w, a, b,
                                                         scale, mask)
        return jnp.sum(y)

    c = _compile(jax.value_and_grad(fleet_loss, argnums=(2, 3)),
                 f32(V, M, k), f32(k, n), f32(V, k, R), f32(V, R, n),
                 f32(V), f32(V, R))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("lead,shape", [
    ((), (10, 197, 12, 64)),      # ViT-Base patch sequence
    ((V,), (B, S, 12, 64)),       # the round's own shape, under the vmap
])
def test_flash_attention_compiles(one_chip, lead, shape):
    from repro.kernels.flash_attention.ops import flash_attention

    def loss(q, k, v):
        fa = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                             interpret=False)
        for _ in lead:
            fa = jax.vmap(fa)
        return jnp.sum(fa(q, k, v))

    qkv = [jax.ShapeDtypeStruct(lead + shape, jnp.float32,
                                sharding=one_chip)] * 3
    c = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), *qkv)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("block_size", [0, 16])
def test_serve_decode_compiles(one_chip, tpu_kernels, block_size):
    """ServeEngine's decode program (dense and paged) at ViT-Base widths:
    4 lanes at slot rank 32."""
    from repro.launch.serve import ServeEngine
    cfg = vit_base_paper()
    lora = LoRAConfig(rank=8, max_rank=R, candidate_ranks=(2, 4, 8, 16, 32))
    params = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0),
                                                  cfg, dtype=jnp.float32))
    spec = ServeSpec(max_batch=4, cache_len=48, block_size=block_size)
    eng = ServeEngine(params, cfg, lora, spec)
    lanes = jnp.zeros((4,), jnp.int32)
    scales = jnp.zeros((4,), jnp.float32)
    if eng.paged:
        args = (params, eng._adapters, scales, lanes, eng._caches,
                eng._pools, eng.allocator.tables, lanes)
    else:
        args = (params, eng._adapters, scales, lanes, eng._caches, lanes)
    c = _compile(eng._decode.__wrapped__, *_spec(one_chip, args))
    assert "tpu_custom_call" in c.as_text()


def test_fleet_local_train_compiles_and_fits(one_chip, tpu_kernels):
    """The fused engine's whole-fleet local-training megastep (a loop
    over chunks of the 24 vehicles in coverage, vmap over a chunk × scan
    over 3 Adam steps) at full width fits one chip."""
    from repro.federated.fused_engine import FusedRoundEngine
    from repro.optim import adam
    cfg = vit_base_paper()
    lora = LoRAConfig(rank=R, max_rank=R, candidate_ranks=(2, 4, 8, 16, 32))
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: T.init_params(key, cfg,
                                                  dtype=jnp.float32))
    fleet = jax.eval_shape(lambda: jax.vmap(
        lambda k: T.init_adapters(k, cfg, lora, rank=R))(
            jax.random.split(key, V)))
    steps = 3
    # the engine state _train_fleet reads, as a TPU host resolves it
    engine = types.SimpleNamespace(model_cfg=cfg, lora_max=lora,
                                   opt=adam(5e-3), steps_full=steps,
                                   remat=True)
    args = (params, fleet,
            (jnp.zeros((V,), jnp.float32), jnp.zeros((V, R), jnp.float32)),
            jnp.zeros((V, steps, B, S), jnp.int32),
            jnp.zeros((V, steps, B), jnp.int32),
            jnp.zeros((V,), jnp.int32), jnp.zeros((V,), bool))
    c = _compile(lambda *a: FusedRoundEngine._train_fleet(engine, *a),
                 *_spec(one_chip, args))
    assert "tpu_custom_call" in c.as_text()
    mem = c.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, mem


def test_compacted_megastep_compiles_and_fits(one_chip, tpu_kernels):
    """The round's two compacted stages as the round program runs them,
    at the cell's shapes: local training of the lanes in coverage, then
    their eval on the task's 32 held-out sequences. Each is one loop
    whose traced trip count follows the active mask, so the program has
    one body per stage and fits one chip."""
    from repro.federated.fused_engine import FusedRoundEngine, chunk_width
    from repro.optim import adam
    cfg = vit_base_paper()
    lora = LoRAConfig(rank=R, max_rank=R, candidate_ranks=(2, 4, 8, 16, 32))
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: T.init_params(key, cfg,
                                                  dtype=jnp.float32))
    fleet = jax.eval_shape(lambda: jax.vmap(
        lambda k: T.init_adapters(k, cfg, lora, rank=R))(
            jax.random.split(key, V)))
    steps = 3
    engine = types.SimpleNamespace(model_cfg=cfg, lora_max=lora,
                                   opt=adam(5e-3), steps_full=steps,
                                   remat=True)

    def megastep(params, ads, scales, tok, lab, counts, active, batch):
        new = FusedRoundEngine._train_fleet(engine, params, ads, scales,
                                            tok, lab, counts, active)
        acc = FusedRoundEngine._eval_fleet(engine, params, new, scales,
                                           batch, active)
        return new, acc

    args = (params, fleet,
            (jnp.zeros((V,), jnp.float32), jnp.zeros((V, R), jnp.float32)),
            jnp.zeros((V, steps, B, S), jnp.int32),
            jnp.zeros((V, steps, B), jnp.int32),
            jnp.zeros((V,), jnp.int32), jnp.zeros((V,), bool),
            {"tokens": jnp.zeros((32, S), jnp.int32),
             "labels": jnp.zeros((32,), jnp.int32)})
    assert chunk_width(V) == 4
    c = _compile(megastep, *_spec(one_chip, args))
    text = c.as_text()
    assert "tpu_custom_call" in text
    assert text.count("while(") >= 2
    mem = c.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, mem


def test_sharded_round_pieces_compile(fleet_mesh, tpu_kernels):
    """The fused_sharded round's pieces that run a model on a four-chip
    fleet mesh: the local-training megastep (six lanes per chip), which
    must keep the compiled kernels — XLA cannot partition a Mosaic call,
    so the engine runs it under shard_map — and the global eval, whose
    SVD once crashed the Shardy partitioner when its seed was a literal.
    Reduced width: the failures are structural, and the reduced model
    has the round's six adapted matrices per layer stack."""
    from repro.configs.vit_base_paper import reduced
    from repro.federated.fused_engine import FusedRoundEngine
    from repro.optim import adam
    cfg = reduced()
    lora = LoRAConfig(rank=R, max_rank=R, candidate_ranks=(2, 4, 8, 16, 32))
    steps = 3
    engine = types.SimpleNamespace(
        model_cfg=cfg, lora_max=lora, opt=adam(5e-3), steps_full=steps,
        remat=True, mesh=fleet_mesh,
        shard_spec=types.SimpleNamespace(axis_name="fleet"), Rmax=R,
        S0=lora.scale, alpha=lora.alpha)
    engine._per_shard = functools.partial(FusedRoundEngine._per_shard,
                                          engine)
    lanes = NamedSharding(fleet_mesh, P("fleet"))
    repl = NamedSharding(fleet_mesh, P())
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: T.init_params(key, cfg,
                                                  dtype=jnp.float32))
    fleet = jax.eval_shape(lambda: jax.vmap(
        lambda k: T.init_adapters(k, cfg, lora, rank=R))(
            jax.random.split(key, V)))
    train = engine._per_shard(
        functools.partial(FusedRoundEngine._train_fleet, engine),
        fleet_args=(1, 2, 3, 4, 5, 6))
    c = _compile(train, _spec(repl, params), *_spec(lanes, (
        fleet,
        (jnp.zeros((V,), jnp.float32), jnp.zeros((V, R), jnp.float32)),
        jnp.zeros((V, steps, B, S), jnp.int32),
        jnp.zeros((V, steps, B), jnp.int32),
        jnp.zeros((V,), jnp.int32), jnp.zeros((V,), bool))))
    assert "tpu_custom_call" in c.as_text()

    merged = jax.eval_shape(lambda: FusedRoundEngine._merged_zeros_like(
        T.init_adapters(key, cfg, lora, rank=R)))
    batch = {"tokens": jnp.zeros((B, S), jnp.int32),
             "labels": jnp.zeros((B,), jnp.int32)}
    c = _compile(functools.partial(FusedRoundEngine._global_eval, engine),
                 *_spec(repl, (params, merged, batch,
                               jnp.zeros((), jnp.int32))))
    assert "tpu_custom_call" in c.as_text()
