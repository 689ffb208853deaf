"""Global run-mode knobs.

COST_UNROLL: when True, every *internal* scan (flash-attention kv blocks,
WKV6/SSD chunk loops, inter-chunk state carries) is fully unrolled so that
XLA's HloCostAnalysis — which visits a while-loop body exactly once — counts
the true op totals. Used ONLY by the dry-run's cost-extrapolation compiles
(reduced layer counts); never for real execution.
"""
COST_UNROLL = False

# Pallas kernel dispatch (DESIGN.md §6). USE_PALLAS_ATTN routes aligned
# causal full-sequence attention through the flash kernel
# (repro.kernels.flash_attention); USE_PALLAS_LORA routes every unbiased
# LoRA-targeted linear through the fused base+adapter GEMM
# (repro.kernels.lora_matmul) — one output write, no second HBM read of
# the activations. States:
#   "auto"   — default: the compiled kernels on TPU hosts, the jnp paths
#              everywhere else (so CPU runs trace the jnp programs)
#   False    — jnp paths everywhere
#   True     — kernels everywhere; off-TPU that is the Pallas interpreter
#              (per PALLAS_INTERPRET), a validation tool that must be
#              asked for explicitly
#   "oracle" — (LoRA only) same dispatch and custom_vjp as True but the
#              forward is the jnp expression: the kernel's bit-exactness
#              reference
# The round engines and the serve step read these at TRACE time: set them
# before the first round runs; later flips do not retrace an
# already-compiled program.
USE_PALLAS_ATTN = "auto"
USE_PALLAS_LORA = "auto"
PALLAS_INTERPRET = True     # off-TPU kernels run in the interpreter


def kernel_backend() -> str:
    """The backend Pallas kernels would execute on ('tpu', 'cpu', 'gpu')."""
    import jax
    return jax.default_backend()


def set_pallas_attn(v, interpret: bool = True) -> None:
    """Flash-attention dispatch. v: False | True | "auto"."""
    global USE_PALLAS_ATTN, PALLAS_INTERPRET
    if v not in (False, True, "auto"):
        raise ValueError(f"USE_PALLAS_ATTN must be False/True/'auto', "
                         f"got {v!r}")
    USE_PALLAS_ATTN = v
    if v:
        PALLAS_INTERPRET = bool(interpret)


def set_pallas_lora(v, interpret: bool = True) -> None:
    """LoRA-GEMM dispatch. v: False | True | "auto" | "oracle"."""
    global USE_PALLAS_LORA, PALLAS_INTERPRET
    if v not in (False, True, "auto", "oracle"):
        raise ValueError(f"USE_PALLAS_LORA must be False/True/'auto'/"
                         f"'oracle', got {v!r}")
    USE_PALLAS_LORA = v
    if v:
        PALLAS_INTERPRET = bool(interpret)


def _enabled(v) -> bool:
    if v == "auto":
        return kernel_backend() == "tpu"
    return bool(v)


def attn_kernel_enabled() -> bool:
    return _enabled(USE_PALLAS_ATTN)


def lora_kernel_enabled() -> bool:
    return _enabled(USE_PALLAS_LORA)


def lora_kernel_oracle() -> bool:
    return USE_PALLAS_LORA == "oracle"


def kernel_interpret() -> bool:
    """TPU hosts always run the compiled kernels; everywhere else a kernel
    is only available through the Pallas interpreter."""
    if kernel_backend() == "tpu":
        return False
    return bool(PALLAS_INTERPRET)


# Expert-parallel MoE via shard_map (§Perf: the automatic-partitioner
# scatter dispatch replicates the token buffer — moe_sharded.py). Set by
# the launch factories; None → pure-pjit path (single-device smoke tests).
MOE_MESH = None
MOE_DP_AXES: tuple = ()


def set_moe_mesh(mesh, dp_axes=()) -> None:
    global MOE_MESH, MOE_DP_AXES
    MOE_MESH = mesh
    MOE_DP_AXES = tuple(dp_axes)

# FAST_DECODE: single-token decode computes attention directly over the
# cache (one grouped einsum, no materialized GQA head repeat) instead of
# the blocked flash path — the flash path's block reshape/transpose copies
# the whole cache every step. Production default True (§Perf pair 3:
# memory term 3–9×); the recorded baseline roofline table used False.
FAST_DECODE = True


def set_cost_unroll(v: bool) -> None:
    global COST_UNROLL
    COST_UNROLL = bool(v)


def set_fast_decode(v: bool) -> None:
    global FAST_DECODE
    FAST_DECODE = bool(v)


# DIRECT_ATTN_MAX_SEQ: full-sequence attention with Sq,Sk at or below this
# threshold skips the blocked online-softmax flash path and materializes the
# (Sq,Sk) scores directly — for short sequences the blocking machinery
# (kv-block scan + per-block checkpoint recompute in the backward) costs far
# more than the memory it saves, and its per-block einsums lower to looped
# tiny batched GEMMs under the round engine's vmap. 0 disables the path.
DIRECT_ATTN_MAX_SEQ = 64


def set_direct_attn_max_seq(n: int) -> None:
    global DIRECT_ATTN_MAX_SEQ
    DIRECT_ATTN_MAX_SEQ = int(n)


def inner_unroll(n_trips: int) -> int:
    return n_trips if COST_UNROLL else 1


import contextlib as _contextlib


@_contextlib.contextmanager
def overrides(**kw):
    """Temporarily set run-mode globals, restoring them on exit.

    Keys are the UPPERCASE module globals (USE_PALLAS_ATTN,
    USE_PALLAS_LORA, PALLAS_INTERPRET, DIRECT_ATTN_MAX_SEQ, ...).
    Restoration runs even when the body raises, so a failing test cannot
    leak kernel dispatch state into the rest of the suite.

        with runmode.overrides(USE_PALLAS_ATTN=True, PALLAS_INTERPRET=True):
            ...

    Only takes effect for traces entered inside the block: the fused
    engines read these globals at trace time, so an engine compiled
    outside the block keeps its original dispatch.
    """
    g = globals()
    unknown = [k for k in kw if k not in g or not k.isupper()]
    if unknown:
        raise ValueError(f"unknown runmode override(s): {unknown}")
    saved = {k: g[k] for k in kw}
    try:
        g.update(kw)
        yield
    finally:
        g.update(saved)
