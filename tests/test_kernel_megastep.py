"""Kernelized megastep (ISSUE 7): the fused round engine with
USE_PALLAS_LORA routes every unbiased LoRA linear through the fused
Pallas GEMM and must reproduce the plain fused engine BIT-exactly
(interpret mode, jit-vs-jit), per-round and scanned, on the base config
and the dense-rsu hierarchy — with exactly one round-body compile.

Fast tier: runmode.overrides semantics, unit parity of the kernelized
apply_lora_linear route, base-config engine parity, and the kernelized
round-body recompile guard (which also proves per-vehicle dynamic scales
cost zero extra compiles).
Slow tier: dense-rsu per-round + scanned parity, serial-reference
tolerance, fused_sharded parity, and the hypothesis rank-mask property.
"""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import LoRAConfig
from repro.core import lora as lora_lib
from repro.models import runmode

LORA = LoRAConfig(rank=4, max_rank=8, candidate_ranks=(2, 4, 8))


def _tiny_cfg(vocab=64):
    from repro.configs import vit_base_paper
    return vit_base_paper.vit_base_paper().with_overrides(
        name="vit-test-kernel", num_layers=2, d_model=32, num_heads=2,
        num_kv_heads=2, head_dim=16, d_ff=64, vocab_size=vocab)


def _sim(engine, rounds=2, **kw):
    from repro.sim.simulator import IoVSimulator, SimConfig
    base = dict(method="ours", rounds=rounds, num_vehicles=4, num_tasks=1,
                seed=3, local_steps=2, engine=engine,
                train_arch=_tiny_cfg(), lora=LORA)
    base.update(kw)
    return IoVSimulator(SimConfig(**base))


def _assert_trees_bitexact(a, b, where=""):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), where
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (
            f"{where}: max dev "
            f"{np.max(np.abs(np.asarray(x) - np.asarray(y)))}")


def _assert_histories_bitexact(ha, hb):
    assert len(ha) == len(hb)
    for ra, rb in zip(ha, hb):
        _assert_trees_bitexact(ra, rb, where=f"round {ra['round']}")


def _assert_servers_bitexact(sa, sb):
    for ti, (a, b) in enumerate(zip(sa.servers, sb.servers)):
        assert (a.merged is None) == (b.merged is None)
        if a.merged is not None:
            _assert_trees_bitexact(a.merged, b.merged, where=f"merged {ti}")


# ---------------------------------------------------------------------------
# runmode.overrides
# ---------------------------------------------------------------------------

def test_overrides_sets_and_restores():
    assert runmode.USE_PALLAS_LORA == "auto"
    with runmode.overrides(USE_PALLAS_LORA=True, DIRECT_ATTN_MAX_SEQ=0):
        assert runmode.USE_PALLAS_LORA is True
        assert runmode.DIRECT_ATTN_MAX_SEQ == 0
    assert runmode.USE_PALLAS_LORA == "auto"
    assert runmode.DIRECT_ATTN_MAX_SEQ == 64


def test_overrides_restores_on_exception():
    with pytest.raises(RuntimeError, match="boom"):
        with runmode.overrides(USE_PALLAS_ATTN=True):
            assert runmode.USE_PALLAS_ATTN is True
            raise RuntimeError("boom")
    assert runmode.USE_PALLAS_ATTN == "auto"


def test_overrides_rejects_unknown_and_lowercase_keys():
    with pytest.raises(ValueError, match="unknown runmode override"):
        with runmode.overrides(NO_SUCH_FLAG=1):
            pass
    with pytest.raises(ValueError, match="unknown runmode override"):
        with runmode.overrides(set_pallas_attn=True):
            pass


def test_set_pallas_lora_validates():
    with pytest.raises(ValueError, match="False/True/'auto'"):
        runmode.set_pallas_lora("yes")
    assert runmode.USE_PALLAS_LORA == "auto"
    with pytest.raises(ValueError, match="False/True/'auto'"):
        runmode.set_pallas_attn("oracle")
    assert runmode.USE_PALLAS_ATTN == "auto"
    # the 'auto' default resolves by backend: off-TPU both kernels stay
    # on the jnp path and the interpreter is never chosen implicitly
    on_tpu = runmode.kernel_backend() == "tpu"
    assert runmode.lora_kernel_enabled() == on_tpu
    assert runmode.attn_kernel_enabled() == on_tpu
    from repro.models.attention import attention_path
    assert attention_path(causal=True, Sq=24, Sk=24) == (
        "pallas_flash" if on_tpu else "direct")


# ---------------------------------------------------------------------------
# unit parity of the kernelized apply_lora_linear route
# ---------------------------------------------------------------------------

def _linear_operands(key=0, B=2, S=16, K=32, N=48, r=8):
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    x = jax.random.normal(ks[0], (B, S, K))
    base = {"w": jax.random.normal(ks[1], (K, N))}
    ad = {"a": jax.random.normal(ks[2], (K, r)) * 0.1,
          "b": jax.random.normal(ks[3], (r, N)) * 0.1}
    return x, base, ad


def test_apply_lora_linear_kernel_route_bit_exact():
    """jit(kernel route) == jit(jnp route), forward and adapter grads, to
    the bit — the invariant the engine-level parity below rests on."""
    x, base, ad = _linear_operands()
    mask = lora_lib.rank_arange_mask(jnp.int32(5), 8)
    ad_m = lora_lib.mask_adapter_tree(ad, mask)
    scale = jnp.float32(2.0)

    def fwd(x, ad, s, m):
        return lora_lib.apply_lora_linear(base, ad, x, (s, m))

    def loss(ad, x, s, m):
        y = lora_lib.apply_lora_linear(base, ad, x, (s, m))
        return jnp.sum(y * y)

    y_jnp = jax.jit(fwd)(x, ad_m, scale, mask)
    g_jnp = jax.jit(jax.grad(loss))(ad_m, x, scale, mask)
    with runmode.overrides(USE_PALLAS_LORA=True, PALLAS_INTERPRET=True):
        y_ker = jax.jit(fwd)(x, ad_m, scale, mask)
        g_ker = jax.jit(jax.grad(loss))(ad_m, x, scale, mask)
    assert bool(jnp.all(y_ker == y_jnp))
    _assert_trees_bitexact(g_ker, g_jnp, where="adapter grads")


def test_kernel_route_skips_biased_linear():
    """(x·W + bias) + adapter ≠ (x·W + adapter) + bias bitwise — biased
    linears must stay on the jnp path even with the kernel enabled."""
    x, base, ad = _linear_operands()
    base = dict(base, b=jax.random.normal(jax.random.PRNGKey(9),
                                          (base["w"].shape[1],)) * 0.1)
    y_jnp = jax.jit(lambda x: lora_lib.apply_lora_linear(
        base, ad, x, 1.5))(x)
    with runmode.overrides(USE_PALLAS_LORA=True, PALLAS_INTERPRET=True):
        assert not lora_lib._kernel_route_ok(base, ad)
        y_ker = jax.jit(lambda x: lora_lib.apply_lora_linear(
            base, ad, x, 1.5))(x)
    assert bool(jnp.all(y_ker == y_jnp))


# ---------------------------------------------------------------------------
# engine parity (fast tier: base config)
# ---------------------------------------------------------------------------

def test_kernelized_fused_matches_fused_base():
    """Kernelized fused engine vs plain fused engine: the full history
    (ranks/energy/accuracy/budgets) is BIT-exact; the aggregated server
    state sits at scan-transpose float noise. Vs the ORACLE route (same
    custom_vjp, jnp forward) EVERYTHING is bit-exact — isolating the
    Pallas kernel as a bitwise drop-in; the residual ~1e-9 vs plain is
    the custom_vjp recompute-vs-saved-residual strategy under the layer
    scan's transpose, present with or without the kernel."""
    plain = _sim("fused")
    hp = plain.run()
    with runmode.overrides(USE_PALLAS_LORA="oracle", PALLAS_INTERPRET=True):
        orac = _sim("fused")
        ho = orac.run()
    with runmode.overrides(USE_PALLAS_LORA=True, PALLAS_INTERPRET=True):
        kern = _sim("fused")
        hk = kern.run()
    # kernel vs oracle: bit-exact end to end, adapters included
    _assert_histories_bitexact(ho, hk)
    _assert_servers_bitexact(orac, kern)
    # kernel vs plain: history bit-exact, merged state at float noise
    _assert_histories_bitexact(hp, hk)
    for sp, sk in zip(plain.servers, kern.servers):
        if sp.merged is not None:
            dev = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
                jax.tree_util.tree_leaves(sp.merged),
                jax.tree_util.tree_leaves(sk.merged)))
            assert dev < 1e-6, dev


def test_kernelized_round_body_compiles_once():
    """With the kernel on, varying rank mixes and per-vehicle dynamic
    scales across rounds still compile ONE round body (scale is a traced
    SMEM operand — zero extra compiles from distinct scales)."""
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
        with runmode.overrides(USE_PALLAS_LORA=True, PALLAS_INTERPRET=True):
            with jax.log_compiles():
                sim = _sim("fused", rounds=4)
                sim.run()
    finally:
        logger.removeHandler(handler)
        logger.setLevel(old_level)
    assert len(compiles) == 1, compiles
    mean_ranks = {round(t["mean_rank"], 3)
                  for r in sim.history for t in r["tasks"]}
    assert len(mean_ranks) > 1  # the guard is vacuous on a rank monoculture


# ---------------------------------------------------------------------------
# engine parity (slow tier: dense-rsu, scanned, serial, sharded)
# ---------------------------------------------------------------------------

def _dense_rsu_sim(engine, rounds=2):
    from repro.sim import scenarios
    from repro.sim.simulator import IoVSimulator
    cfg = scenarios.build_config("dense-rsu", rounds=rounds, seed=1,
                                 engine=engine, train_arch=_tiny_cfg(),
                                 lora=LORA, local_steps=1)
    return IoVSimulator(cfg)


@pytest.mark.slow
def test_kernelized_fused_matches_fused_dense_rsu():
    """Parity holds through the two-tier RSU hierarchy (nearest-in-range
    association, periodic sync), per-round API: history bit-exact vs
    plain; everything bit-exact vs the oracle route."""
    plain = _dense_rsu_sim("fused")
    hp = plain.run()
    with runmode.overrides(USE_PALLAS_LORA="oracle", PALLAS_INTERPRET=True):
        orac = _dense_rsu_sim("fused")
        ho = orac.run()
    with runmode.overrides(USE_PALLAS_LORA=True, PALLAS_INTERPRET=True):
        kern = _dense_rsu_sim("fused")
        hk = kern.run()
    _assert_histories_bitexact(ho, hk)
    _assert_servers_bitexact(orac, kern)
    _assert_histories_bitexact(hp, hk)


@pytest.mark.slow
def test_kernelized_fused_scanned_matches_fused_scanned():
    """Parity under run_scanned: the lax.scan round body embeds the same
    kernelized megastep (history bit-exact vs plain; bit-exact vs
    oracle)."""
    plain = _sim("fused", rounds=3)
    hp = plain.run_scanned(3)
    with runmode.overrides(USE_PALLAS_LORA="oracle", PALLAS_INTERPRET=True):
        orac = _sim("fused", rounds=3)
        ho = orac.run_scanned(3)
    with runmode.overrides(USE_PALLAS_LORA=True, PALLAS_INTERPRET=True):
        kern = _sim("fused", rounds=3)
        hk = kern.run_scanned(3)
    _assert_histories_bitexact(ho, hk)
    _assert_histories_bitexact(hp, hk)


@pytest.mark.slow
def test_kernelized_fused_matches_serial():
    """Transitively: serial == fused (test_fused_engine) and fused ==
    kernelized (bit-exact above); this pins the direct serial comparison
    at the same float-noise tolerance the plain fused engine meets."""
    from test_fused_engine import _assert_histories_match
    from test_fused_engine import _sim as _ref_sim

    serial = _ref_sim("serial")
    with runmode.overrides(USE_PALLAS_LORA=True, PALLAS_INTERPRET=True):
        kern = _ref_sim("fused")
        hk = kern.run()
    _assert_histories_match(serial.run(), hk)


@pytest.mark.slow
@pytest.mark.skipif(
    jax.local_device_count() < 2,
    reason="needs >1 device (run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
def test_kernelized_fused_sharded_matches_oracle_sharded():
    """The kernelized megastep composes with the device-sharded fleet
    vmap: fused_sharded + kernel == fused_sharded + oracle, bit for bit
    (plain-vs-sharded parity is test_sharded_engine's job)."""
    with runmode.overrides(USE_PALLAS_LORA="oracle", PALLAS_INTERPRET=True):
        orac = _sim("fused_sharded")
        ho = orac.run()
    with runmode.overrides(USE_PALLAS_LORA=True, PALLAS_INTERPRET=True):
        kern = _sim("fused_sharded")
        hk = kern.run()
    _assert_histories_bitexact(ho, hk)
    _assert_servers_bitexact(orac, kern)


# ---------------------------------------------------------------------------
# hypothesis property: padded-masked kernel == truncated jnp, 0 ulp (f32)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_rank_mask_kernel_equals_truncated_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(data=st.data())
    def prop(data):
        max_rank = data.draw(st.sampled_from([4, 8, 16]), label="max_rank")
        rank = data.draw(st.integers(1, max_rank), label="rank")
        dtype = data.draw(st.sampled_from([jnp.float32, jnp.bfloat16]),
                          label="dtype")
        seed = data.draw(st.integers(0, 2**16), label="seed")
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        K, N = 32, 24
        x = jax.random.normal(ks[0], (3, 8, K)).astype(dtype)
        base = {"w": jax.random.normal(ks[1], (K, N)).astype(dtype)}
        ad = {"a": jax.random.normal(ks[2], (K, max_rank)) * 0.1,
              "b": jax.random.normal(ks[3], (max_rank, N)) * 0.1}
        mask = lora_lib.rank_arange_mask(jnp.int32(rank), max_rank)
        ad_m = lora_lib.mask_adapter_tree(ad, mask)
        ad_t = lora_lib.truncate_adapter_tree(ad_m, rank)
        scale = jnp.float32(1.0 + (seed % 7))

        y_trunc = jax.jit(lambda x, ad, s: lora_lib.apply_lora_linear(
            base, ad, x, s))(x, ad_t, scale)
        with runmode.overrides(USE_PALLAS_LORA=True, PALLAS_INTERPRET=True):
            y_kern = jax.jit(
                lambda x, ad, s, m: lora_lib.apply_lora_linear(
                    base, ad, x, (s, m)))(x, ad_m, scale, mask)
        if dtype == jnp.float32:
            # 0 ulp: the masked tail contributes exact ±0 rows
            assert bool(jnp.all(y_kern == y_trunc))
        else:
            # bf16 differs only in where the final cast lands (the kernel
            # accumulates in f32); bound it at one bf16 ulp
            dev = jnp.max(jnp.abs(y_kern.astype(jnp.float32)
                                  - y_trunc.astype(jnp.float32)))
            assert float(dev) < 2e-2

    prop()
