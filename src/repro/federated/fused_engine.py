"""Fused on-device round engine: rank-padded fleet megastep + multi-round scan.

The batched engine (PR 1) removed per-vehicle dispatch but still fragments a
round into one jit call per (task, rank, bucket) group glued together by a
thread pool, with host round-trips between UCB-DUAL selection, training,
§III-C accounting and aggregation. This module compiles the ENTIRE round —

    ucb_dual.select_ranks  →  SVD redistribution at per-vehicle ranks
    →  vmap×scan local fine-tuning of the vehicles in coverage
    →  §III-C cost accounting + §IV-E fallback decisions
    →  rank-padded merged-delta aggregation  →  global eval
    →  ucb_dual.update + Algorithm-1 budget reallocation

— into ONE jit program with ONE cache key, regardless of fleet size, rank
mix, coverage or mobility churn. The trick is rank padding (core.lora):
every adapter lives in max(φ_η)-wide buffers whose tail is identically zero,
masked per vehicle, so no shape in the program depends on the round's rank
selection. ``run_scanned(R)`` then lifts R rounds into one ``lax.scan`` with
pre-staged mobility traces, channel draws and prefetched data batches — the
host touches arrays only at the scan boundary.

Exactness contract (regression-tested against the serial engine):
  * the host stages mobility, channel fades and data batches by consuming
    the SAME host RNG streams in the SAME order as the serial engine;
  * first-round fresh adapters are staged from the server's key stream
    (RSUServer draws at max_rank, rank-independently, see ``_fresh``);
  * everything else — rank selection, training, accounting, SVD
    redistribution, aggregation, dual updates — replays the serial maths
    in-program, so ranks/energies/adapters match to float tolerance.
  One caveat: if a task's FIRST round with coverage ends with zero kept
  uploads (every vehicle departs and abandons), the serial engine redraws
  fresh adapters next round; ``run_scanned`` has already committed its
  staging and reuses zeros instead (the per-round ``run_round`` path stages
  on demand and stays exact even then).

Two-tier RSU hierarchy (ISSUE 4): with a non-trivial
``SimConfig.rsu_tier`` the round program additionally (a) charges the
adapter-migration penalty to vehicles whose staged RSU association changed
(handoffs), (b) reduces uploads into per-RSU PARTIALS with one
association-one-hot segment-sum over the same rank-padded fleet tree, and
(c) merges the partials into the global adapter every ``sync_period``
rounds with staleness-discounted weights — all still one jit program with
one cache key (the tier is static). The trivial tier takes a statically
branched path whose program is the pre-hierarchy one, byte for byte; under
``run_scanned`` a non-trivial tier pre-stages fresh adapter draws for
EVERY round of a task that has no global model yet (the serial server
redraws per round until the first sync), so scanned and per-round
execution replay each other under hierarchies too.

Dynamic fleets (scenario subsystem, PR 3): arrival/departure slots are a
presence mask maintained by ``MobilityModel`` (trace replay) and folded
into the ``active`` mask that ``round_view`` hands to the staging below. An
absent vehicle is therefore a ZERO-WEIGHT LANE of the rank-padded fleet
arrays — zero step budget, zero aggregation weight, inactive in every
reduction — so churning fleets (rush-hour arrivals, staged departures,
RSU outages) reuse the exact-no-op padding invariants unchanged: no shape
in the program depends on who is present, and serial/fused parity holds in
churning-fleet regimes (tests/test_scenarios.py).

Active-lane compaction: the local training and the local eval compute
only the lanes in coverage. A stable permutation puts them first and a
loop runs over ``ceil(n_active / C)`` chunks of ``C`` lanes
(:func:`over_active`, ``C`` from :func:`chunk_width`), gathering each
chunk, vmapping the per-lane step over it and scattering the result back.
The trip count is traced, so the program still compiles once; a lane
outside coverage keeps its distributed adapter bit for bit and reads a
local accuracy of 0, which nothing uses (its reward, energy and
aggregation weight are masked, and ``ucb_dual.update`` skips arm −1).

Device-sharded fleets (ISSUE 5): with ``engine="fused_sharded"`` (or a
non-trivial ``SimConfig.shard``) the SAME round program runs with its
fleet axis sharded over a 1-D device mesh (``launch.mesh.make_fleet_mesh``)
under the ``launch.sharding`` fleet rules. The fleet is padded to a
multiple of the shard count with zero-weight lanes — the exact-no-op
padding invariant dynamic fleets already rely on — and real lanes are
dealt round-robin across shards (:func:`fleet_slots`), so every shard
carries an equal slice of live vehicles and rank mix. Each device trains
its lane slice of the vmap×scan megastep; the merged-delta / per-RSU
segment-sum reductions are the only cross-device collectives (one
all-reduce per target), and the program still compiles exactly once per
device topology. Parity contract: the sharded engine reproduces the
single-device fused engine's ranks/energy/handoffs to float-reassociation
tolerance (the lane permutation and per-shard partial sums reassociate
the weighted reductions; every per-lane computation is elementwise
identical) — regression-tested in tests/test_sharded_engine.py under a
forced multi-device CPU host.

Supported methods: the adaptive-rank "ours" family (ours, ours_no_energy,
ours_no_mobility). Baselines keep the batched/serial engines.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import telemetry
from repro.core import aggregation as agg
from repro.core import cost_model as cm
from repro.core import energy_alloc
from repro.core import lora as lora_lib
from repro.core import mobility as mob
from repro.core import ucb_dual
from repro.core.energy_alloc import AllocState
from repro.federated.batched_client import draw_batches
from repro.models import transformer as T
from repro.optim import adam, apply_updates

FUSED_METHODS = ("ours", "ours_no_energy", "ours_no_mobility")


def supports_method(method: str) -> bool:
    return method in FUSED_METHODS


def fleet_slots(num_vehicles: int, num_shards: int,
                placement: str = "roundrobin") -> Tuple[np.ndarray, int]:
    """Lane → slot map for the (padded) device-sharded fleet.

    Pads the fleet to ``Vp = ceil(V / N) · N`` lanes and returns
    ``(slot, Vp)`` where ``slot[v]`` is the padded-fleet position of real
    lane v. The mesh shards the slot axis in N contiguous blocks of
    ``Vp / N``; "block" placement keeps lanes in order (all padding lands
    on the last shard), "roundrobin" deals lane v to shard ``v % N`` so
    real lanes — and with them the round's rank-group mix — balance across
    shards and the padding spreads one lane at a time.
    """
    if num_shards < 1:
        raise ValueError("num_shards must be >= 1")
    vp = -(-num_vehicles // num_shards) * num_shards
    v = np.arange(num_vehicles)
    if placement == "block":
        return v, vp
    if placement != "roundrobin":
        raise ValueError(f"unknown placement {placement!r}")
    per_shard = vp // num_shards
    return (v % num_shards) * per_shard + v // num_shards, vp


def chunk_width(lanes: int) -> int:
    """Lanes per chunk of the compacted fleet megastep over ``lanes``
    lanes (the whole fleet, or one shard's slice of it): the smallest
    divisor of ``lanes`` that is at least 4, or ``lanes`` where it has
    none. A divisor, so the chunks partition the lanes and no lane is
    written twice. On a TPU v5e at ViT-Base width a lane's local training
    and eval cost less the narrower the chunk, from 24 lanes down to 4,
    the narrowest measured (PERF.md), and a narrow chunk computes fewer
    lanes outside coverage."""
    return next(d for d in range(min(4, lanes), lanes + 1)
                if lanes % d == 0)


def computed_lanes(active: np.ndarray, n_shards: int = 1) -> int:
    """Lanes the compacted megastep computes for a staged ``(T, Vp)``
    active mask whose lane axis splits into ``n_shards`` contiguous
    shards: each shard's lanes in coverage, rounded up to whole chunks."""
    per = np.asarray(active).reshape(active.shape[0], n_shards, -1)
    c = chunk_width(per.shape[-1])
    return int((c * -(-per.sum(axis=-1) // c)).sum())


def over_active(fn, active, lanes, init):
    """``fn`` run only on the lanes where ``active`` is set, ``C`` =
    :func:`chunk_width` lanes at a time. ``lanes`` is a tuple of trees
    whose leaves lead with the lane axis; ``fn`` maps the same tuple cut
    to ``C`` lanes to a tree of ``init``'s structure cut to ``C`` lanes.
    A stable permutation puts the active lanes first, in lane order; the
    loop gathers chunk i of it, applies ``fn`` and scatters the result
    into ``init``, for ``ceil(n_active / C)`` trips (zero when no lane is
    active). Lanes never gathered keep their ``init`` entries; inactive
    lanes that fill out the last chunk go through ``fn`` like the rest."""
    c = chunk_width(active.shape[0])
    order = jnp.argsort(jnp.logical_not(active), stable=True)
    trips = (jnp.sum(active, dtype=jnp.int32) + c - 1) // c

    def body(i, out):
        idx = jax.lax.dynamic_slice_in_dim(order, i * c, c)
        got = fn(*jax.tree_util.tree_map(lambda a: a[idx], lanes))
        return jax.tree_util.tree_map(
            lambda o, g: o.at[idx].set(g, unique_indices=True), out, got)

    return jax.lax.fori_loop(0, trips, body, init)


def adapter_deviation(got, ref, start) -> Tuple[float, float]:
    """How far adapter tree ``got`` lies from the reference ``ref``, both
    trained from ``start``: (max |got − ref| over every entry,
    ||got − ref|| / ||ref − start||). The second is the deviation as a
    share of the reference's own update, so it separates rounding noise
    (a small share) from a fault in what was trained — a wrong batch, a
    dropped half-batch, wrong labels — which changes the update itself
    (a share of order one). 0.0 when the reference did not move."""
    gl, rl, sl = (jax.tree_util.tree_leaves(t) for t in (got, ref, start))
    d_max = max(float(jnp.max(jnp.abs(g - r))) for g, r in zip(gl, rl))
    num = sum(float(jnp.sum(jnp.square(g - r))) for g, r in zip(gl, rl))
    den = sum(float(jnp.sum(jnp.square(r - s0))) for r, s0 in zip(rl, sl))
    return d_max, (float(np.sqrt(num / den)) if den > 0.0 else 0.0)


class FusedRoundEngine:
    """One-jit-program-per-round engine bound to an :class:`IoVSimulator`.

    Owns the device-resident round carry (UCB states, merged deltas,
    allocator state, round counter) and mirrors it back onto the simulator
    after every round so host-side consumers (history, checkpointing,
    ``server.eval_adapters``) stay coherent.
    """

    def __init__(self, sim, check: bool = False, sharded: bool = False):
        cfg = sim.cfg
        if not supports_method(cfg.method):
            raise ValueError(
                f"engine='fused' supports methods {FUSED_METHODS}, not "
                f"{cfg.method!r} — use the batched or serial engine")
        self.sim = sim
        self.cfg = cfg
        self.check = bool(check)
        # host spans and counters, shared with the simulator; the round
        # index (len(sim.history) when the round began) tags each round's
        # spans
        self.telemetry = sim.telemetry
        self._round_no = 0
        self.spec = sim.spec
        self.model_cfg = sim.model_cfg
        self.lora = cfg.lora
        self.V = cfg.num_vehicles
        self.T = cfg.num_tasks
        # ---- fleet-axis device sharding (ShardSpec / engine="fused_sharded")
        # The trivial topology (1 shard) takes the pre-sharding code path:
        # slot == arange, Vp == V, no mesh, every constraint fn an identity
        # — the traced round program is byte-identical to the unsharded one.
        from repro.launch import sharding as sh_rules
        shard_spec = cfg.shard
        self.shard_spec = shard_spec
        if self.check:
            if sharded:
                raise ValueError(
                    "fused_check replays lanes in original order on the "
                    "host; run the check engine unsharded")
            # an explicit fused_check + explicit shard combo is rejected
            # at engine resolution; an env-resolved check engine treats
            # the spec as inert (trivial topology), like batched/serial
            self.n_shards = 1
        elif not shard_spec.trivial:
            self.n_shards = shard_spec.resolve()
        elif sharded:   # engine="fused_sharded" + default spec: all devices
            self.n_shards = jax.local_device_count()
        else:
            self.n_shards = 1
        if sharded and self.n_shards < 2:
            # covers the default spec AND num_shards=0 ("all devices")
            # resolving to 1 on a host without forced devices
            raise ValueError(
                "engine='fused_sharded' needs >1 visible device but "
                f"resolved to {self.n_shards} — on CPU export XLA_FLAGS="
                "--xla_force_host_platform_device_count=N BEFORE python "
                "starts, or use engine='fused' (a silent single-device "
                "run would masquerade as sharded)")
        self.slot, self.Vp = fleet_slots(self.V, self.n_shards,
                                         shard_spec.placement)
        if self.n_shards > 1:
            from repro.launch.mesh import make_fleet_mesh
            self.mesh = make_fleet_mesh(self.n_shards,
                                        axis_name=shard_spec.axis_name)
        else:
            self.mesh = None
        self._constrain = sh_rules.fleet_constrainer(
            self.mesh, self.Vp, axis_name=shard_spec.axis_name)
        # two-tier RSU hierarchy: per-RSU partial aggregation + periodic
        # staleness-weighted sync. The trivial tier keeps the pre-hierarchy
        # round program byte-for-byte (static branch at trace time).
        self.tier = cfg.rsu_tier
        self.K = self.tier.num_rsus_per_task
        self.P = self.tier.sync_period
        self.tier_trivial = self.tier.trivial
        # semi-synchronous participation: in-flight upload buffer carried
        # through the round program. The sync policy keeps the pre-policy
        # program byte-for-byte (static branch at trace time, like the
        # trivial tier above).
        self.part = cfg.participation
        self.part_trivial = self.part.trivial
        self.Rmax = cfg.lora.max_rank
        self.steps = cfg.local_steps
        # Per-layer remat of the local-training loss on accelerators: the
        # fleet vmap multiplies every saved activation by the vehicle
        # count, and at ViT-Base width 24 vehicles save ~11 GB of
        # residuals, which with the round's state does not fit one 16 GB
        # chip (recomputing each layer keeps ~3.7 GB). Host memory does
        # not bind on the CPU, where the saved-residual program is the one
        # the bit-exact kernel/oracle parity tests pin.
        self.remat = jax.default_backend() != "cpu"
        self.opt = adam(cfg.lr)
        self.lora_max = dataclasses.replace(cfg.lora, rank=self.Rmax)
        self.S0 = cfg.lora.scale          # server-side merge/redistribute α/r₀
        self.alpha = cfg.lora.alpha
        train_dims = cm.target_dims_of(self.model_cfg, cfg.lora)
        min_dim = min(min(d) for d in train_dims) if train_dims else 0
        if self.Rmax > min_dim:
            import warnings
            warnings.warn(
                f"lora.max_rank={self.Rmax} exceeds the smallest LoRA "
                f"target dimension ({min_dim}): the serial engine's "
                "truncated-SVD rank saturates at min(d1,d2) and evaluates "
                f"with scale α/{min_dim} while the fused engine keeps "
                f"padded max_rank buffers at scale α/{self.Rmax} — the "
                "serial/fused equivalence contract does not hold for this "
                "config", stacklevel=3)

        # ---- per-arm lookup tables (exact: same floats the serial path
        # reads from g_cache / adapter_payload_params) ----
        cand = np.asarray(cfg.lora.candidate_ranks, np.int32)
        self.cand = jnp.asarray(cand)
        payload = np.asarray([cm.adapter_payload_params(sim.cost_dims, int(r))
                              for r in cand], np.int64)
        self.payload_arm_i = jnp.asarray(payload.astype(np.int32))
        self.payload_arm_f = jnp.asarray(payload.astype(np.float32))
        self.g_arm = jnp.asarray(
            [sim.g_cache[int(r)] for r in cand], jnp.float32)

        # ---- fleet device profiles (κ·f³ folded on host in f64 — the cube
        # of a >1e12 FLOP/s frequency overflows f32). Padding lanes copy
        # lane 0's profile: any FINITE value works (padding never has
        # `active` set, so its costs are masked out of every reduction),
        # but a zero frequency would put inf·0 = nan into the cost vectors.
        self.freq = self._pad_lanes(
            [p.freq for p in sim.dev_profiles])
        self.comp_power = self._pad_lanes(
            [p.kappa * p.freq ** 3 for p in sim.dev_profiles])
        self.dev_tx = self._pad_lanes(
            [p.tx_power for p in sim.dev_profiles])
        self.flops_ps = self._pad_lanes(
            [p.flops_per_sample for p in sim.dev_profiles])
        rsu = sim.rsu_profile
        self.rsu_tx = float(rsu.tx_power)
        self.agg_tau_pv = float(rsu.agg_flops_per_vehicle / rsu.freq)
        self.agg_e_pv = float(rsu.kappa * rsu.freq ** 3 * self.agg_tau_pv)

        # §IV-E step budgets / sample counts (serial: int() truncation)
        self.steps_full = cfg.local_steps
        self.steps_dep = max(1, int(round(cfg.local_steps
                                          * cfg.departure_fraction)))
        self.ns_full = int(cfg.batch_size * cfg.local_steps)
        self.ns_dep = int(cfg.batch_size * cfg.local_steps
                          * cfg.departure_fraction)

        # data-size aggregation weights (T, Vp) in slot order; padding
        # lanes carry weight 0 — exact no-ops in every reduction
        w_host = np.zeros((self.T, self.Vp), np.float32)
        w_host[:, self.slot] = [
            [float(len(sim.client_data[t][v])) for v in range(self.V)]
            for t in range(self.T)]
        self.weights = jnp.asarray(w_host)

        # fixed eval batches, device-resident once
        self.local_eval = [{k: jnp.asarray(v) for k, v in b.items()}
                           for b in sim.local_eval]
        self.eval_batches = [{k: jnp.asarray(v) for k, v in b.items()}
                             for b in sim.eval_batches]

        # zero templates: merged-delta tree and fleet-stacked fresh tree
        tmpl = T.init_adapters(jax.random.PRNGKey(0), self.model_cfg,
                               cfg.lora, rank=self.Rmax)
        self._zero_merged = self._merged_zeros_like(tmpl)
        self._zero_fleet = jax.tree_util.tree_map(
            lambda x: jnp.zeros((self.Vp,) + x.shape, x.dtype), tmpl)
        # per-task RSU partials: merged-delta tree with a leading (K,) axis
        # (non-trivial tiers only)
        self._zero_partials = None if self.tier_trivial else (
            jax.tree_util.tree_map(
                lambda x: jnp.zeros((self.K,) + x.shape, x.dtype),
                self._zero_merged))
        # per-lane buffered merged deltas (semi-sync participation): the
        # same merged-delta tree with a leading (Vp,) fleet axis, so the
        # buffer shards over the fleet mesh like every per-vehicle array.
        # Built only for the policy that reads it: at ViT-Base width it is
        # Vp full d_in×d_out deltas per layer (8 GB for 24 vehicles).
        self._zero_buf = None if self.part_trivial else (
            jax.tree_util.tree_map(
                lambda x: jnp.zeros((self.Vp,) + x.shape, x.dtype),
                self._zero_merged))
        if self.mesh is not None:
            # the fleet template lives sharded on the mesh, so everything
            # scattered into it (fresh staging) inherits the placement;
            # the frozen base params replicate once, up front
            self._zero_fleet = jax.device_put(
                self._zero_fleet, sh_rules.fleet_shardings(
                    self.mesh, self._zero_fleet, fleet_size=self.Vp,
                    axis_name=shard_spec.axis_name))
            self._params = jax.device_put(
                sim.params, jax.tree_util.tree_map(
                    lambda _: NamedSharding(self.mesh, P()), sim.params))
        else:
            self._params = sim.params

        self._carry = None
        self._has_merged_host = [False] * self.T
        self._jit_round = jax.jit(self._round_step)
        self._jit_scan: Dict[int, Any] = {}
        self.check_dev = 0.0
        self.check_rel = 0.0

    # ------------------------------------------------------------------
    @staticmethod
    def _merged_zeros_like(adapter_tree):
        out = adapter_tree
        for path in agg.tree_paths(adapter_tree):
            ad = agg.tree_get(out, path)
            shape = ad["a"].shape[:-1] + (ad["b"].shape[-1],)
            out = agg.tree_set(out, path,
                               {"delta": jnp.zeros(shape, jnp.float32)})
        return out

    # ------------------------------------------------------------------
    # Fleet padding / device placement (device-sharded topologies)
    # ------------------------------------------------------------------
    def _pad_lanes(self, values) -> jnp.ndarray:
        """(V,) per-vehicle host values → (Vp,) f32 table in slot order.
        Padding slots copy lane 0 (finite; masked out of every reduction
        by the `active` mask)."""
        arr = np.asarray(values, np.float64)
        out = np.full((self.Vp,), arr[0], np.float64)
        out[self.slot] = arr
        return jnp.asarray(out.astype(np.float32))

    def _replicate(self, tree):
        """Pin a tree replicated on the fleet mesh (identity unsharded).
        Applied to the carry's global state (merged deltas, RSU partials,
        allocator) so the round program's output shardings are a fixed
        point of its input shardings — one compile per topology."""
        if self.mesh is None:
            return tree
        s = NamedSharding(self.mesh, P())
        return jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(x, s), tree)

    def _place_x(self, x: Dict[str, Any], lead: int = 0) -> Dict[str, Any]:
        """Ship staged host arrays onto the fleet mesh: every array whose
        vehicle-lane dimension is present shards it, the rest replicate.
        lead=1 for `run_scanned` stacks (a scan axis precedes the usual
        layout). Identity on the trivial topology."""
        with self.telemetry.span("place", round=self._round_no):
            if self.mesh is None:
                return x
            from repro.launch import sharding as sh_rules
            an = self.shard_spec.axis_name
            out = dict(x)
            main = {k: v for k, v in x.items()
                    if k not in ("tokens", "labels", "fresh")}
            main = jax.device_put(main, sh_rules.fleet_shardings(
                self.mesh, main, axis_pos=1 + lead, axis_name=an,
                fleet_size=self.Vp))
            out.update(main)
            for k in ("tokens", "labels", "fresh"):
                if k in x:
                    out[k] = jax.device_put(x[k], sh_rules.fleet_shardings(
                        self.mesh, x[k], axis_pos=lead, axis_name=an,
                        fleet_size=self.Vp))
            return out

    def _place_carry(self, carry: Dict[str, Any]) -> Dict[str, Any]:
        """Initial carry placement: per-vehicle UCB statistics shard over
        the fleet axis, all global state replicates. After round 1 the
        in-program constraints keep the layout a fixed point."""
        if self.mesh is None:
            return carry
        an = self.shard_spec.axis_name
        fleet = NamedSharding(self.mesh, P(an, None))
        repl = NamedSharding(self.mesh, P())

        def put_repl(tree):
            return jax.device_put(tree, jax.tree_util.tree_map(
                lambda _: repl, tree))

        out = dict(carry)
        out["ucb"] = [ucb_dual.UCBDualState(
            counts=jax.device_put(s.counts, fleet),
            reward_sum=jax.device_put(s.reward_sum, fleet),
            energy_sum=jax.device_put(s.energy_sum, fleet),
            lam=jax.device_put(s.lam, repl),
            round=jax.device_put(s.round, repl)) for s in carry["ucb"]]
        for k in ("merged", "has_merged", "alloc", "round", "partials",
                  "partial_w", "partial_age"):
            if k in out:
                out[k] = put_repl(out[k])
        if "buf_delta" in out:
            # per-lane buffer state shards over the fleet axis (leading
            # Vp dimension) exactly like the staged fleet arrays
            from repro.launch import sharding as sh_rules
            for k in ("buf_delta", "buf_w", "buf_age", "buf_dest"):
                out[k] = jax.device_put(out[k], sh_rules.fleet_shardings(
                    self.mesh, out[k], axis_name=an, fleet_size=self.Vp))
        return out

    def _pad_ucb(self, state) -> ucb_dual.UCBDualState:
        """Adopt a (V, K) host UCB state into the (Vp, K) slot layout.
        Padding rows are zeros == fresh ``init_state`` rows; they never
        activate, so they never accrue counts."""
        if self.Vp == self.V and self.n_shards == 1:
            return ucb_dual.UCBDualState(*map(jnp.asarray, state))

        def pad(a):
            a = np.asarray(a, np.float32)
            out = np.zeros((self.Vp,) + a.shape[1:], np.float32)
            out[self.slot] = a
            return jnp.asarray(out)
        return ucb_dual.UCBDualState(
            counts=pad(state.counts), reward_sum=pad(state.reward_sum),
            energy_sum=pad(state.energy_sum),
            lam=jnp.asarray(state.lam), round=jnp.asarray(state.round))

    # ------------------------------------------------------------------
    def _init_carry(self):
        sim = self.sim
        self._carry = {
            "ucb": [self._pad_ucb(s) for s in sim.ucb_states],
            "merged": [self._zero_merged for _ in range(self.T)],
            "has_merged": jnp.zeros((self.T,), bool),
            "alloc": AllocState(
                budgets=jnp.asarray(sim.alloc.budgets, jnp.float32),
                difficulty=jnp.asarray(sim.alloc.difficulty, jnp.float32),
                round=jnp.asarray(sim.alloc.round, jnp.int32)),
            "round": jnp.asarray(sim.servers[0].round, jnp.int32),
        }
        self._has_merged_host = [sim.servers[t].merged is not None
                                 for t in range(self.T)]
        # adopt pre-existing server state (engine switch mid-run)
        for t in range(self.T):
            if self._has_merged_host[t]:
                self._carry["merged"][t] = sim.servers[t].merged
        self._carry["has_merged"] = jnp.asarray(self._has_merged_host)
        if not self.tier_trivial:
            parts, pw, page = [], [], []
            for t in range(self.T):
                srv = sim.servers[t]
                if srv.partials is not None:
                    parts.append(agg.stack_partials(
                        [p if p is not None else self._zero_merged
                         for p in srv.partials]))
                else:
                    parts.append(self._zero_partials)
                pw.append(np.asarray(srv.partial_w, np.float32))
                page.append(np.asarray(srv.partial_age, np.float32))
            self._carry["partials"] = parts
            self._carry["partial_w"] = jnp.asarray(np.stack(pw))
            self._carry["partial_age"] = jnp.asarray(np.stack(page))
        if not self.part_trivial:
            # adopt the host servers' in-flight buffers (engine switch or
            # checkpoint restore): vehicle ids scatter through self.slot
            bufs, bw, bage, bdest = [], [], [], []
            for t in range(self.T):
                srv = sim.servers[t]
                w = np.zeros((self.Vp,), np.float32)
                age = np.zeros((self.Vp,), np.float32)
                dest = np.full((self.Vp,), -1, np.int32)
                if srv.buffer:
                    host = jax.tree_util.tree_map(
                        lambda z: np.zeros((self.Vp,) + z.shape, np.float32),
                        self._zero_merged)
                    for v, ent in srv.buffer.items():
                        lane = int(self.slot[v])

                        def put(h, d, lane=lane):
                            h[lane] = np.asarray(d, np.float32)
                            return h
                        host = jax.tree_util.tree_map(put, host,
                                                      ent["delta"])
                        w[lane] = ent["w"]
                        age[lane] = ent["age"]
                        dest[lane] = ent["dest"]
                    bufs.append(jax.tree_util.tree_map(jnp.asarray, host))
                else:
                    bufs.append(self._zero_buf)
                bw.append(jnp.asarray(w))
                bage.append(jnp.asarray(age))
                bdest.append(jnp.asarray(dest))
            self._carry["buf_delta"] = bufs
            self._carry["buf_w"] = bw
            self._carry["buf_age"] = bage
            self._carry["buf_dest"] = bdest
        self._carry = self._place_carry(self._carry)

    # ------------------------------------------------------------------
    def reset_carry(self) -> None:
        """Drop the device carry so the next round re-adopts the
        simulator's host state through ``_init_carry`` → ``_place_carry``
        (the ``launch.sharding`` fleet rules). checkpoint.carry calls this
        after a restore: the rebuilt carry lands on whatever device
        topology THIS engine runs, so a resumed run may change the mesh or
        the engine and still replay the identical rounds."""
        self._carry = None
        self._has_merged_host = [self.sim.servers[t].merged is not None
                                 for t in range(self.T)]

    # ------------------------------------------------------------------
    # Host staging: consume the serial engine's RNG streams, same order
    # ------------------------------------------------------------------
    def _stage_round(self, allow_fresh: Sequence[bool]
                     ) -> Tuple[Dict[str, Any], List[Any]]:
        """Advance mobility one tick and stage every array the fused round
        program needs (:meth:`_stage_arrays`), under the host span
        ``repro.stage``. The span and the counters take the round's local
        steps staged (``staged_steps``: Σ counts), the lane-steps of the
        full fleet width (``lane_steps``: T · Vp · local_steps), those the
        compacted megastep computes (``computed_steps``: the lanes in
        coverage rounded up to whole chunks, :func:`computed_lanes`, times
        local_steps), the vehicles in coverage (``active``) and the fresh
        adapter draws (``fresh``), all from the host arrays just staged."""
        with self.telemetry.span("stage", round=self._round_no) as sp:
            x, fresh = self._stage_arrays(allow_fresh)
            active = x["active"].sum(axis=1)
            n = {"staged_steps": int(x["counts"].sum()),
                 "lane_steps": self.T * self.Vp * self.steps_full,
                 "computed_steps": self.steps_full * computed_lanes(
                     x["active"], self.n_shards),
                 "active": int(active.sum()),
                 "fresh": int(sum(a for a, f in zip(active, allow_fresh)
                                  if f))}
            sp.set(**n)
        tel = self.telemetry
        tel.add("staged_steps", n["staged_steps"])
        tel.add("lane_steps", n["lane_steps"])
        tel.add("computed_steps", n["computed_steps"])
        tel.add("fresh_draws", n["fresh"])
        return x, fresh

    def _stage_arrays(self, allow_fresh: Sequence[bool]
                      ) -> Tuple[Dict[str, Any], List[Any]]:
        """The staging itself. Returns (x, fresh_trees); fresh_trees[t] is
        a fleet-stacked max_rank draw (zeros when not staged this round).

        ``round_view``'s active mask is already presence-gated (dynamic
        fleets), so absent vehicles stage as inactive lanes: zero step
        count, no data/channel RNG consumption — the same streams, in the
        same order, as the serial planner sees."""
        sim = self.sim
        cfg = self.cfg
        sim.mobility.step()
        # staged arrays live in SLOT order at the padded fleet width Vp;
        # the host loop below works in original lane order (the RNG
        # contract) and scatters through self.slot. Trivial topology:
        # slot == arange(V), Vp == V — the scatter is the identity.
        slot = self.slot
        active = np.zeros((self.T, self.Vp), bool)
        departing = np.zeros((self.T, self.Vp), bool)
        handoff = np.zeros((self.T, self.Vp), bool)
        assoc = np.full((self.T, self.Vp), -1, np.int32)
        peer = np.zeros((self.T,), bool)
        rate_d = np.zeros((self.T, self.Vp), np.float64)
        rate_u = np.zeros((self.T, self.Vp), np.float64)
        counts = np.zeros((self.T, self.Vp), np.int32)
        tokens: List[np.ndarray] = []
        labels: List[np.ndarray] = []
        fresh: List[Any] = []
        dev_tx = np.asarray([p.tx_power for p in sim.dev_profiles])
        for t in range(self.T):
            view = sim.mobility.round_view_group(sim.rsu_groups[t])
            act, dep = view["active"], view["departing"]
            active[t, slot], departing[t, slot] = act, dep
            handoff[t, slot] = view["handoff"]
            assoc[t, slot] = view["assoc"]
            peer[t] = view["peer_available"]
            ids = np.where(act)[0]
            rate_d[t, slot], rate_u[t, slot] = sim.channel.round_rates(
                self.rsu_tx, dev_tx, view["distances"], sim.shadow, ids)
            cnt = np.where(act, np.where(dep, self.steps_dep,
                                         self.steps_full), 0)
            counts[t, slot] = cnt
            tok = None
            lab = None
            for v in ids:
                b = draw_batches(sim.client_data[t][v], int(cnt[v]),
                                 self.steps_full)
                if tok is None:
                    tok = np.zeros((self.Vp,) + b["tokens"].shape, np.int32)
                    lab = np.zeros((self.Vp,) + b["labels"].shape, np.int32)
                tok[slot[v]] = b["tokens"]
                lab[slot[v]] = b["labels"]
            if tok is None:   # no coverage this round: shape from eval set
                S = sim.task_data[t]["tokens"].shape[-1]
                tok = np.zeros((self.Vp, self.steps_full, cfg.batch_size, S),
                               np.int32)
                lab = np.zeros((self.Vp, self.steps_full, cfg.batch_size),
                               np.int32)
            tokens.append(tok)
            labels.append(lab)
            if allow_fresh[t] and len(ids):
                # the server scatters the draws into the fleet template so
                # the result inherits its (possibly mesh-sharded) placement
                fresh.append(sim.servers[t].fresh_padded(
                    len(ids), fleet=self._zero_fleet, slots=slot[ids]))
            else:
                fresh.append(self._zero_fleet)
        x = {"active": active, "departing": departing, "peer": peer,
             "assoc": assoc, "handoff": handoff,
             "rate_down": rate_d.astype(np.float32),
             "rate_up": rate_u.astype(np.float32),
             "counts": counts, "tokens": tokens, "labels": labels}
        return x, fresh

    # ------------------------------------------------------------------
    # The fused round program (traced once; one XLA cache entry)
    # ------------------------------------------------------------------
    def _train_fleet(self, params, adapters, scales, tokens, labels, counts,
                     active):
        """Whole-fleet local fine-tuning of the lanes in coverage
        (``active``), compacted by :func:`over_active`: vmap over a chunk's
        vehicles, scan over local steps, Adam on the rank-padded adapter
        tree (frozen base). Per-vehicle step budgets freeze updates past
        each budget (§IV-E), reproducing the serial dynamics; a lane with
        budget 0 (every inactive lane) returns its adapter bit for bit,
        whether it fills out a chunk or is never gathered. The rank-padded
        tail stays identically zero (see core.lora rank-padding
        invariant)."""
        cfg, lora_max, opt = self.model_cfg, self.lora_max, self.opt
        n_steps = self.steps_full

        def one(ad, scale, tok, lab, n_active):
            ost = opt.init(ad)

            def body(carry, xs):
                a, o = carry
                batch, si = xs

                def loss(p):
                    return T.loss_fn(params, p, cfg, lora_max, batch,
                                     scale=scale, remat=self.remat)

                (_, metrics), grads = jax.value_and_grad(
                    loss, has_aux=True)(a)
                updates, o2 = opt.update(grads, o, a)
                a2 = apply_updates(a, updates)
                live = si < n_active
                a = jax.tree_util.tree_map(
                    lambda n, old: jnp.where(live, n, old), a2, a)
                o = jax.tree_util.tree_map(
                    lambda n, old: jnp.where(live, n, old), o2, o)
                return (a, o), metrics

            (ad, _), _ = jax.lax.scan(
                body, (ad, ost),
                ({"tokens": tok, "labels": lab},
                 jnp.arange(n_steps, dtype=jnp.int32)))
            return ad

        return over_active(jax.vmap(one), active,
                           (adapters, scales, tokens, labels, counts),
                           adapters)

    def _per_shard(self, fn, fleet_args: Sequence[int]):
        """``fn`` (a whole-fleet function) run on each device's own lanes.
        On a fleet mesh the local training and eval are lane-parallel by
        construction, so they run under ``shard_map``: arguments at
        ``fleet_args`` split along their leading (Vp,) axis, the rest
        (frozen params, the shared eval batch) replicate, and each device
        compacts its own Vp / n_shards lanes, with its own trip count and
        no collective in the loop. With no fleet argument (the global
        eval) every device computes the same replicated result. The Pallas kernels inside then compile per
        device; XLA cannot partition a Mosaic call on its own. Identity on
        the trivial topology."""
        if self.mesh is None:
            return fn
        lanes = P(self.shard_spec.axis_name)
        out = lanes if fleet_args else P()

        def per_shard(*args):
            specs = tuple(lanes if i in fleet_args else P()
                          for i in range(len(args)))
            return jax.shard_map(fn, mesh=self.mesh, in_specs=specs,
                                 out_specs=out, check_vma=False)(*args)
        return per_shard

    def _eval_fleet(self, params, adapters, scales, batch, active):
        """Accuracy of each lane in coverage on the task's held-out local
        batch, compacted like :meth:`_train_fleet`; 0 on every other lane
        (nothing reads it there)."""
        def ev(ad, scale):
            _, m = T.loss_fn(params, ad, self.model_cfg, self.lora_max,
                             batch, scale=scale)
            return m["accuracy"]

        def chunk(ad, scale, act):
            return jnp.where(act, jax.vmap(ev)(ad, scale), 0.0)

        return over_active(chunk, active, (adapters, scales, active),
                           jnp.zeros(active.shape, jnp.float32))

    def _global_eval(self, params, merged, batch, round_idx):
        """Accuracy of a task's merged adapter on its held-out set, through
        the seed-0 SVD at max rank (the serial engine's eval_adapters
        view)."""
        # seed 0. On a fleet mesh it is computed from the traced round
        # index instead of written as a literal: a literal key makes every
        # layer's SVD sketch a compile-time constant, and several of those
        # in one program crash JAX 0.9's Shardy partitioner on a
        # multi-device mesh (SIGSEGV in ConstantOrScalarSplitterPass).
        # min(round, 0) is 0 in every round; the unsharded program keeps
        # the literal.
        seed = 0 if self.mesh is None else jnp.minimum(round_idx, 0)
        gad = agg.factors_full(agg.merged_svd(merged, self.Rmax, seed=seed),
                               self.S0)

        def accuracy(params, gad, batch):
            _, met = T.loss_fn(params, gad, self.model_cfg, self.lora_max,
                               batch, scale=self.alpha / self.Rmax)
            return met["accuracy"]

        return self._per_shard(accuracy, fleet_args=())(params, gad, batch)

    def _round_step(self, carry, x, data):
        """The round program's Python body. It runs only while JAX traces
        the program, so the ``retraces`` counter counts traces. Each task's
        stages run under device scopes ``task<t>/round.<stage>``, and the
        budget reallocation after the tasks under ``round.alloc``."""
        n = self.telemetry.add("retraces")
        with self.telemetry.span("retrace", retraces=n):
            outs = []
            for ti in range(self.T):
                with telemetry.scope(f"task{ti}"):
                    outs.append(self._task_step(ti, carry, x, data))
            rec = {k: [o["rec"][k] for o in outs] for k in outs[0]["rec"]}
            with telemetry.scope("round.alloc"):
                consumed = jnp.stack(rec["energy"])
                accs = jnp.stack(rec["accuracy"])
                alloc = carry["alloc"]
                if self.spec.energy_scheduler:
                    alloc = energy_alloc.step_scan(alloc, self.cfg.energy,
                                                   consumed, accs)
                else:
                    alloc = AllocState(budgets=alloc.budgets,
                                       difficulty=alloc.difficulty,
                                       round=alloc.round + 1)

            out_carry = {"ucb": [o["ucb"] for o in outs],
                         "merged": [o["merged"] for o in outs],
                         "has_merged": jnp.stack([o["has_m"] for o in outs]),
                         "alloc": alloc, "round": carry["round"] + 1}
            if not self.tier_trivial:
                out_carry["partials"] = [o["partials"] for o in outs]
                out_carry["partial_w"] = jnp.stack([o["pw"] for o in outs])
                out_carry["partial_age"] = jnp.stack([o["page"]
                                                      for o in outs])
            if not self.part_trivial:
                for k in ("buf_delta", "buf_w", "buf_age", "buf_dest"):
                    out_carry[k] = [o[k] for o in outs]
            out_rec = {k: jnp.stack(v) for k, v in rec.items()}
            out_rec["budgets"] = carry["alloc"].budgets
            if self.check:
                out_rec["check"] = {k: [o["check"][k] for o in outs]
                                    for k in ("dist", "new", "ranks")}
            return out_carry, out_rec

    def _task_step(self, ti, carry, x, data) -> Dict[str, Any]:
        """Task ``ti``'s part of the round program: its new carry entries
        and its record, each stage (the numbered comments) under its device
        scope."""
        cfg = self.cfg
        ucb_cfg = cfg.ucb
        mcfg = cfg.mobility
        params = data["params"]
        round_idx = carry["round"]
        state = carry["ucb"][ti]
        out: Dict[str, Any] = {}

        # 1. intra-task rank selection (Algorithm 2, vectorized)
        with telemetry.scope("round.select"):
            act = x["active"][ti]
            dep = x["departing"][ti]
            arms = ucb_dual.select_ranks(state, ucb_cfg, act)
            arm_c = jnp.clip(arms, 0, None)
            ranks = self.cand[arm_c]                       # (V,) int32
            scale_v = self.alpha / jnp.maximum(
                ranks.astype(jnp.float32), 1.0)
            rmask = lora_lib.rank_arange_mask(ranks, self.Rmax)
            # Kernelized route: thread (scale, rank_mask) per vehicle so the
            # fused GEMM's epilogue masks the rank tail on-device. Read at
            # TRACE time (like USE_PALLAS_ATTN) — flip runmode before the
            # first round; later flips don't retrace a compiled round body.
            # The mask multiply is a bitwise no-op on the pre-masked
            # adapters, so this is parity-neutral on the jnp fallback too.
            from repro.models import runmode
            if runmode.lora_kernel_enabled():
                scale_arg = (scale_v, rmask)
            else:
                scale_arg = scale_v

        # 2. adapter distribution: shared seeded SVD of the merged
        #    delta, truncated per vehicle by rank mask — or the staged
        #    fresh draws while no aggregate exists yet
        with telemetry.scope("round.redistribute"):
            def dist_svd(m):
                svd = agg.merged_svd(m, self.Rmax, seed=round_idx)
                return agg.factors_for_ranks(svd, rmask, self.S0)

            def dist_fresh(_):
                return lora_lib.mask_adapter_tree(data["fresh"][ti], rmask)

            dist = jax.lax.cond(carry["has_merged"][ti], dist_svd,
                                dist_fresh, carry["merged"][ti])
            # sharded topologies: pin the distributed fleet tree and the
            # trained result to the fleet mesh so the vmap megastep stays
            # lane-parallel (identity on the trivial topology)
            dist = self._constrain(dist)

        # 3. fleet megastep: local fine-tuning + held-out local eval of
        #    the lanes in coverage, compacted (each shard its own lanes)
        with telemetry.scope("round.train"):
            new_ads = self._constrain(self._per_shard(
                self._train_fleet, fleet_args=(1, 2, 3, 4, 5, 6))(
                params, dist, scale_arg, x["tokens"][ti], x["labels"][ti],
                x["counts"][ti], act))
        with telemetry.scope("round.local_eval"):
            local_acc = self._per_shard(self._eval_fleet,
                                        fleet_args=(1, 2, 4))(
                params, new_ads, scale_arg, self.local_eval[ti], act)

        # 4. §III-C four-stage costs over the staged channel
        with telemetry.scope("round.costs"):
            costs = cm.vehicle_round_costs_vec(
                freq=self.freq, comp_power=self.comp_power,
                tx_power=self.dev_tx, flops_per_sample=self.flops_ps,
                rsu_tx_power=self.rsu_tx,
                payload_params=self.payload_arm_f[arm_c],
                bytes_per_param=cfg.bytes_per_param,
                rate_down=x["rate_down"][ti], rate_up=x["rate_up"][ti],
                num_samples=jnp.where(dep, self.ns_dep, self.ns_full),
                g=self.g_arm[arm_c])

            # 5. §IV-E fallback decisions for predicted departures
            if self.spec.mobility_aware:
                q_star = mcfg.accuracy_threshold
                c0 = ucb_cfg.gamma * jnp.maximum(0.0, q_star - local_acc)
                c1 = jnp.where(x["peer"][ti],
                               ucb_cfg.alpha * mcfg.migration_latency
                               + mcfg.beta * mcfg.migration_energy,
                               jnp.inf)
                c2 = mcfg.beta * costs["e_comp"] + ucb_cfg.gamma * q_star
                strat = jnp.argmin(
                    jnp.stack([c0, jnp.broadcast_to(c1, c0.shape), c2],
                              axis=-1), axis=-1)
                migrate = dep & (strat == mob.MIGRATE)
                abandon = dep & (strat == mob.ABANDON)
                extra_e = jnp.where(migrate, mcfg.migration_energy, 0.0)
                extra_tau = jnp.where(migrate, mcfg.migration_latency, 0.0)
                contribute = act & ~abandon
                fb = jnp.sum((act & dep)[:, None]
                             * jax.nn.one_hot(strat, 3, dtype=jnp.int32),
                             axis=0)
            else:
                contribute = act & ~dep
                extra_e = extra_tau = jnp.zeros((self.Vp,), jnp.float32)
                fb = jnp.zeros((3,), jnp.int32)

            hoff = act & x["handoff"][ti]
            if not self.tier_trivial:
                # adapter-migration penalty for re-associated vehicles
                # (static gate: the trivial program stays byte-identical)
                ho_tau, ho_e = cm.handoff_costs(
                    self.tier.handoff_latency, self.tier.handoff_energy,
                    hoff.astype(jnp.float32))
                extra_e = extra_e + ho_e
                extra_tau = extra_tau + ho_tau

            e_v = costs["energy"] + extra_e
            tau_v = costs["latency"] + extra_tau
            per_v_energy = jnp.where(act, e_v, 0.0)
            per_v_reward = jnp.where(
                act, ucb_dual.reward(ucb_cfg, local_acc, tau_v), 0.0)
            n_active = jnp.sum(act)
            n_kept = jnp.sum(contribute)

        # 6. rank-padded fleet aggregation (zero-weight lanes are exact
        #    no-ops); empty rounds leave the merged delta alone. Trivial
        #    tier: one global reduction, synced every round. Non-trivial
        #    tier: segment-sum per-RSU partials, then a staleness-weighted
        #    merge into the global adapter every sync_period rounds — all
        #    inside this same jit program.
        rec: Dict[str, Any] = {}
        with telemetry.scope("round.aggregate"):
            if not self.part_trivial:
                # --- semi-sync participation: age → release → drop →
                # admit, all dense masked lane algebra (host mirror:
                # server.release_buffered / admit_buffered)
                bw = carry["buf_w"][ti]
                bage = carry["buf_age"][ti]
                bdest = carry["buf_dest"][ti]
                bdelta = carry["buf_delta"][ti]
                occ = bw > 0.0
                age1 = jnp.where(occ, bage + 1.0, 0.0)
                within = occ & (age1 <= float(self.part.max_delay))
                release = act & within          # vehicle back in coverage
                keep_buf = within & ~act        # still in flight
                relw = jnp.where(
                    release, bw * agg.staleness_weights(
                        age1, self.part.vehicle_staleness_decay), 0.0)
                any_rel = jnp.sum(relw) > 0.0
                # buffered partials follow the vehicle to its CURRENT RSU
                # (buffer_handoffs) or land at the recorded destination —
                # a static python bool, not a traced branch
                if self.part.buffer_handoffs:
                    dest_eff = x["assoc"][ti]
                else:
                    dest_eff = bdest
                mig = (migrate if self.spec.mobility_aware
                       else jnp.zeros((self.Vp,), bool))
                if self.part.max_delay > 0:
                    # the upload of a departing (non-migrating) contributor
                    # does not complete this round: defer it to the buffer
                    defer = contribute & dep & ~mig
                else:
                    # max_delay=0 degenerates to sync bit-exactly: the
                    # defer/release sets are statically empty
                    defer = jnp.zeros((self.Vp,), bool)
                w = jnp.where(contribute & ~defer, self.weights[ti], 0.0)
                keep = (jnp.sum(w) > 0.0) | any_rel
            else:
                w = jnp.where(contribute, self.weights[ti], 0.0)
                keep = n_kept > 0
            # self._constrain is the identity on the trivial topology, so
            # passing it unconditionally keeps one code path
            if self.tier_trivial:
                merged_new = agg.aggregate_merged_padded(
                    new_ads, w, self.S0, constrain=self._constrain)
                if not self.part_trivial:
                    # fold released buffer entries into the live merge in
                    # raw-weight space; rounds without releases keep the
                    # plain merge bit-for-bit (the where selects it)
                    rel_raw, rel_tot = agg.buffer_release_sum(bdelta, relw)
                    combined = agg.combine_with_released(
                        merged_new, jnp.sum(w), rel_raw, rel_tot)
                    merged_new = jax.tree_util.tree_map(
                        lambda c, n: jnp.where(any_rel, c, n),
                        combined, merged_new)
                merged_out = self._replicate(jax.tree_util.tree_map(
                    lambda n, o: jnp.where(keep, n, o), merged_new,
                    carry["merged"][ti]))
                has_m = carry["has_merged"][ti] | keep
            else:
                # uploads carry the RSU association of the vehicle that
                # produced them (assoc == -1 lanes have weight 0 already)
                part_new, seg_w = agg.aggregate_merged_padded_segmented(
                    new_ads, w, jnp.where(contribute, x["assoc"][ti], -1),
                    self.K, self.S0, constrain=self._constrain)
                if not self.part_trivial:
                    # released buffer entries land at their destination
                    # RSU's partial (host mirror: _tier_fold_released);
                    # release-free segments keep the plain segment merge
                    rel_raw_k, rel_w_k = agg.segment_buffer_release(
                        bdelta, relw, jnp.where(release, dest_eff, -1),
                        self.K)
                    comb_k = agg.combine_with_released(
                        part_new, seg_w, rel_raw_k, rel_w_k)
                    has_rel_k = rel_w_k > 0.0               # (K,)

                    def fold(c, n):
                        r = has_rel_k.reshape(
                            (self.K,) + (1,) * (c.ndim - 1))
                        return jnp.where(r, c, n)

                    part_new = jax.tree_util.tree_map(fold, comb_k,
                                                      part_new)
                    seg_w = seg_w + rel_w_k
                refreshed = seg_w > 0                       # (K,)

                def upd(n, o):
                    r = refreshed.reshape((self.K,) + (1,) * (n.ndim - 1))
                    return jnp.where(r, n, o)

                parts_out = self._replicate(jax.tree_util.tree_map(
                    upd, part_new, carry["partials"][ti]))
                pw_old = carry["partial_w"][ti]
                page_old = carry["partial_age"][ti]
                pw = jnp.where(refreshed, seg_w, pw_old)
                page = jnp.where(refreshed, 0.0,
                                 jnp.where(pw_old > 0, page_old + 1.0,
                                           page_old))
                is_sync = ((round_idx + 1) % self.P) == 0
                omega = pw * agg.staleness_weights(page,
                                                   self.tier.staleness_decay)
                candidate = agg.merge_partials(
                    parts_out, pw, page, self.tier.staleness_decay)
                do_merge = is_sync & (jnp.sum(omega) > 0)
                merged_out = self._replicate(jax.tree_util.tree_map(
                    lambda n, o: jnp.where(do_merge, n, o), candidate,
                    carry["merged"][ti]))
                has_m = carry["has_merged"][ti] | do_merge
                # a synced window resets: only new uploads count next time
                out["partials"] = parts_out
                out["pw"] = jnp.where(is_sync, 0.0, pw)
                out["page"] = jnp.where(is_sync, 0.0, page)

            if not self.part_trivial:
                # buffer state out: deferred lanes admit this round's
                # merged delta at age 0; in-flight lanes age; released and
                # overdue lanes zero their weight (the stale delta tree is
                # an exact no-op at weight 0 in every release einsum)
                new_delta = agg.merge_delta_fleet(
                    new_ads, self.S0, constrain=self._constrain)
                buf_delta_out = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(
                        defer.reshape((self.Vp,) + (1,) * (n.ndim - 1)),
                        n, o),
                    new_delta, bdelta)
                buf_w_out = jnp.where(defer, self.weights[ti],
                                      jnp.where(keep_buf, bw, 0.0))
                buf_age_out = jnp.where(defer, 0.0,
                                        jnp.where(keep_buf, age1, 0.0))
                buf_dest_out = jnp.where(
                    defer, x["assoc"][ti],
                    jnp.where(keep_buf, bdest, -1)).astype(jnp.int32)
                out["buf_delta"] = self._constrain(buf_delta_out)
                out["buf_w"] = self._constrain(buf_w_out)
                out["buf_age"] = self._constrain(buf_age_out)
                out["buf_dest"] = self._constrain(buf_dest_out)
                rec["deferred"] = jnp.sum(defer).astype(jnp.int32)
                rec["released"] = jnp.sum(release).astype(jnp.int32)
                rec["rel_weight"] = jnp.sum(relw).astype(jnp.float32)

        # 7. global eval on the task's held-out set
        with telemetry.scope("round.global_eval"):
            def do_eval(m):
                return self._global_eval(params, m, self.eval_batches[ti],
                                         round_idx)

            # serial evals only when this round kept uploads AND a global
            # model exists — for non-trivial tiers the global only appears
            # at a sync round, so gate on has_m as well (trivial tier:
            # keep already implies has_m)
            eval_gate = keep if self.tier_trivial else (keep & has_m)
            acc = jax.lax.cond(eval_gate, do_eval,
                               lambda m: jnp.zeros((), jnp.float32),
                               merged_out)

        # 8. dual update with the task's current budget
        with telemetry.scope("round.dual"):
            state_new, info = ucb_dual.update(
                state, ucb_cfg, arms, per_v_reward, per_v_energy,
                carry["alloc"].budgets[ti].astype(jnp.float32))
            # per-vehicle bandit statistics stay fleet-sharded round over
            # round (their (Vp, K) leaves hit the fleet rule; the scalar
            # dual state is untouched)
            state_new = self._constrain(state_new)

            tau_agg = self.agg_tau_pv * n_kept
            e_agg = self.agg_e_pv * n_kept

            def mmax(a):
                return jnp.max(jnp.where(act, a, -jnp.inf))

            lat = jnp.where(
                n_active > 0,
                mmax(costs["tau_down"]) + mmax(costs["tau_comp"])
                + mmax(costs["tau_up"]) + tau_agg, 0.0)
            e_t = jnp.sum(per_v_energy) + e_agg
            reward_t = (ucb_cfg.gamma * acc
                        - ucb_cfg.alpha * lat / ucb_cfg.latency_ref)
            mean_rank = jnp.where(
                n_active > 0,
                jnp.sum(jnp.where(act, ranks, 0)).astype(jnp.float32)
                / jnp.maximum(n_active, 1), 0.0)
            comm = jnp.sum(jnp.where(contribute, self.payload_arm_i[arm_c],
                                     0))

        out.update(ucb=state_new, merged=merged_out, has_m=has_m)
        out["rec"] = dict(
            rec, accuracy=acc, latency=lat, energy=e_t, reward=reward_t,
            mean_rank=mean_rank, active=n_active.astype(jnp.int32),
            departing=jnp.sum(dep).astype(jnp.int32),
            handoffs=jnp.sum(hoff).astype(jnp.int32), fallbacks=fb,
            comm_params=comm, n_kept=n_kept.astype(jnp.int32), has_m=has_m,
            **{"lambda": info["lambda"]})
        if self.check:
            out["check"] = {"dist": dist, "new": new_ads, "ranks": ranks}
        return out

    # ------------------------------------------------------------------
    # Public API
    # ------------------------------------------------------------------
    def run_round(self) -> Dict[str, Any]:
        """One communication round through the single jitted round program.
        Host work is staging (mobility tick, channel draws, data batches)
        and the small record fetch — no per-group dispatch, no thread pool,
        no recompilation under churn."""
        tel = self.telemetry
        rnd = self._round_no = len(self.sim.history)
        with tel.step("round", rnd):
            if self._carry is None:
                self._init_carry()
            x, fresh = self._stage_round(
                [not hm for hm in self._has_merged_host])
            x = self._place_x(x)
            data = {"params": self._params, "fresh": fresh}
            # the call site, not the method: a caller may replace
            # _jit_round (the benchmark's recorder does)
            with tel.span("dispatch", round=rnd):
                self._carry, rec = self._jit_round(self._carry, x, data)
            if self.check:
                self._run_check(x, rec.pop("check"))
            with tel.span("wait", round=rnd):
                host = jax.device_get({k: v for k, v in rec.items()
                                       if k != "check"})
            out = self._record(host)
            self._sync_sim()
        return out

    def run_scanned(self, rounds: int) -> List[Dict[str, Any]]:
        """R rounds in ONE ``lax.scan``-wrapped XLA call: all mobility
        traces, channel draws and data batches are pre-staged, so the host
        is not consulted between rounds at all.

        Successive calls with the same ``rounds`` reuse ONE compiled scan
        program (``_jit_scan`` keys on the horizon): the simulator's
        checkpoint-chunked ``run_scanned`` exploits this, scanning a long
        horizon in equal interval-sized chunks with a checkpoint at every
        boundary and no added cache keys (DESIGN.md §7). The staging RNG
        streams are consumed in round order either way, so chunked and
        monolithic scans stage identical rounds — the one caveat is the
        trivial-tier zero-kept-uploads corner already documented in the
        module docstring (fresh staging is local to a call), which resets
        per chunk instead of per horizon."""
        if self.check:
            # the serial replay needs per-round host control (and scanning
            # would stack every round's fleet adapter trees into the scan
            # outputs) — fail loudly rather than report check_dev=0.0
            raise ValueError("engine='fused_check' verifies round by round;"
                             " use run()/run_round(), not run_scanned()")
        if self._carry is None:
            self._init_carry()
        tel = self.telemetry
        base = len(self.sim.history)
        xs_list: List[Dict[str, Any]] = []
        fresh_list: List[List[Any]] = []
        # trivial tier only: ONE staged draw per task (its first covered
        # round), shipped as a scan constant selected by round index. The
        # hierarchy path instead ships per-round draws via xs (pre-sync
        # rounds each redraw, like the serial server) and never reads
        # these three.
        fresh_const = None
        fresh_round = np.full((self.T,), -1, np.int64)
        staged = [False] * self.T
        for r in range(rounds):
            if self.tier_trivial:
                allow = [not self._has_merged_host[t] and not staged[t]
                         for t in range(self.T)]
            else:
                # stage fresh for EVERY round of a task that has no global
                # model yet; post-sync the program ignores them
                allow = [not self._has_merged_host[t]
                         for t in range(self.T)]
            # each round's step span covers its staging; the device runs
            # every round in the one call under repro.dispatch below
            self._round_no = base + r
            with tel.step("round", base + r):
                x, fresh = self._stage_round(allow)
            fresh_list.append(fresh)
            if self.tier_trivial:
                for t in range(self.T):
                    if allow[t] and x["active"][t].any():
                        staged[t] = True
                        fresh_round[t] = (int(np.asarray(
                            self._carry["round"])) + r)
                        if fresh_const is None:
                            fresh_const = [self._zero_fleet] * self.T
                        fresh_const = list(fresh_const)
                        fresh_const[t] = fresh[t]
            xs_list.append(x)
        if self.tier_trivial and fresh_const is None:
            fresh_const = [self._zero_fleet] * self.T
        xs = {
            "active": np.stack([x["active"] for x in xs_list]),
            "departing": np.stack([x["departing"] for x in xs_list]),
            "peer": np.stack([x["peer"] for x in xs_list]),
            "rate_down": np.stack([x["rate_down"] for x in xs_list]),
            "rate_up": np.stack([x["rate_up"] for x in xs_list]),
            "counts": np.stack([x["counts"] for x in xs_list]),
            "assoc": np.stack([x["assoc"] for x in xs_list]),
            "handoff": np.stack([x["handoff"] for x in xs_list]),
            "tokens": [np.stack([x["tokens"][t] for x in xs_list])
                       for t in range(self.T)],
            "labels": [np.stack([x["labels"][t] for x in xs_list])
                       for t in range(self.T)],
        }
        staged_fresh = tuple(not hm for hm in self._has_merged_host)
        if not self.tier_trivial:
            # per-round fleet-stacked fresh trees ride along as scan xs —
            # ONLY for tasks that still lack a global model at scan start
            # (tasks already past their first sync never read fresh, so
            # shipping (rounds, V, ...) zero stacks for them would waste
            # device memory and transfer for nothing)
            xs["fresh"] = [jax.tree_util.tree_map(
                lambda *leaves: jnp.stack(leaves),
                *[fresh_list[r][t] for r in range(rounds)])
                for t in range(self.T) if staged_fresh[t]]
        self._round_no = base
        xs = self._place_x(xs, lead=1)
        if self.tier_trivial:
            data = {"params": self._params, "fresh": fresh_const,
                    "fresh_round": jnp.asarray(fresh_round, jnp.int32)}
        else:
            # the hierarchy body reads only params — fresh rides in xs
            data = {"params": self._params}
        fn = self._scan_fn(rounds, staged_fresh)
        with tel.span("dispatch", round=base, rounds=rounds):
            self._carry, recs = fn(self._carry, xs, data)
        with tel.span("wait", round=base, rounds=rounds):
            host = jax.device_get(recs)
        outs = []
        for r in range(rounds):
            self._round_no = base + r
            outs.append(self._record(jax.tree_util.tree_map(
                lambda a: a[r], host)))
        self._sync_sim()
        return outs

    def _scan_fn(self, rounds: int, staged_fresh: Tuple[bool, ...]):
        # staged_fresh is part of the cache key: which tasks carry
        # per-round fresh stacks in xs is baked into the traced body, and
        # it can change between run_scanned calls (a task syncs mid-run).
        # The trivial tier ignores it (fresh rides in `data`), so key on
        # rounds alone there to keep one scan program per horizon.
        key = (rounds, None if self.tier_trivial else staged_fresh)
        if key not in self._jit_scan:
            def body_of(data):
                def body(carry, x):
                    if not self.tier_trivial:
                        # per-round staged fresh trees (pre-sync rounds
                        # redraw, exactly like the serial server); tasks
                        # already merged at scan start never read fresh,
                        # so they get the zero template
                        staged = iter(x.pop("fresh"))
                        fresh = [next(staged) if staged_fresh[t]
                                 else self._zero_fleet
                                 for t in range(self.T)]
                        d = {"params": data["params"], "fresh": fresh}
                        return self._round_step(carry, x, d)
                    usef = ((~carry["has_merged"])
                            & (carry["round"] == data["fresh_round"]))
                    fresh = [jax.tree_util.tree_map(
                        lambda f: f * usef[t].astype(f.dtype),
                        data["fresh"][t]) for t in range(self.T)]
                    d = {"params": data["params"], "fresh": fresh}
                    return self._round_step(carry, x, d)
                return body

            @jax.jit
            def run(carry, xs, data):
                return jax.lax.scan(body_of(data), carry, xs)

            self._jit_scan[key] = run
        return self._jit_scan[key]

    # ------------------------------------------------------------------
    def _record(self, h: Dict[str, Any]) -> Dict[str, Any]:
        """Shape one round's device outputs into the serial history schema."""
        with self.telemetry.span("record", round=self._round_no):
            sim = self.sim
            tasks = []
            for ti in range(self.T):
                tasks.append({
                    "task": sim.tasks[ti].name,
                    "accuracy": float(h["accuracy"][ti]),
                    "latency": float(h["latency"][ti]),
                    "energy": float(h["energy"][ti]),
                    "reward": float(h["reward"][ti]),
                    "lambda": float(h["lambda"][ti]),
                    "mean_rank": float(h["mean_rank"][ti]),
                    "active": int(h["active"][ti]),
                    "departing": int(h["departing"][ti]),
                    "handoffs": int(h["handoffs"][ti]),
                    "fallbacks": {i: int(h["fallbacks"][ti][i])
                                  for i in range(3)},
                    "comm_params": int(h["comm_params"][ti]),
                    "budget": float(h["budgets"][ti]),
                })
                if "deferred" in h:
                    # buffer dynamics, mirroring the serial _finish_task record
                    tasks[-1]["deferred"] = int(h["deferred"][ti])
                    tasks[-1]["released"] = int(h["released"][ti])
                    tasks[-1]["rel_weight"] = float(h["rel_weight"][ti])
                # non-trivial tiers only gain a global model at a sync round,
                # so mirror the program's has_merged flag (for the trivial
                # tier it is equivalent to n_kept > 0)
                if bool(h["has_m"][ti]):
                    self._has_merged_host[ti] = True
            rec = {
                "round": len(sim.history),
                "tasks": tasks,
                "budgets": [float(b) for b in h["budgets"]],
                "reward": float(sum(t["reward"] for t in tasks)),
                "energy": float(sum(t["energy"] for t in tasks)),
                "latency": float(max((t["latency"] for t in tasks),
                                     default=0.0)),
                "accuracy": float(np.mean([t["accuracy"] for t in tasks])),
            }
            sim.history.append(rec)
            self.telemetry.add("rounds")
            return rec

    def _sync_sim(self) -> None:
        """Mirror the device carry back onto the simulator so host-side
        consumers (checkpointing, eval_adapters, summary) stay coherent."""
        with self.telemetry.span("sync", round=self._round_no):
            sim = self.sim
            c = self._carry
            if self.n_shards == 1:
                sim.ucb_states = list(c["ucb"])
            else:
                # un-permute the (Vp, K) slot layout back to original lanes so
                # host consumers (checkpointing, engine switches) see the same
                # per-vehicle state an unsharded engine would hand them
                idx = jnp.asarray(self.slot, jnp.int32)
                sim.ucb_states = [ucb_dual.UCBDualState(
                    counts=s.counts[idx], reward_sum=s.reward_sum[idx],
                    energy_sum=s.energy_sum[idx], lam=s.lam, round=s.round)
                    for s in c["ucb"]]
            sim.alloc = AllocState(budgets=c["alloc"].budgets,
                                   difficulty=c["alloc"].difficulty,
                                   round=int(c["alloc"].round))
            r = int(c["round"])
            for t in range(self.T):
                if self._has_merged_host[t]:
                    sim.servers[t].load_merged(c["merged"][t], r)
                else:
                    sim.servers[t].round = r
                if not self.tier_trivial:
                    sim.servers[t].load_partials(
                        agg.unstack_partials(c["partials"][t], self.K),
                        np.asarray(c["partial_w"][t]),
                        np.asarray(c["partial_age"][t]))
                if not self.part_trivial:
                    # un-permute slot → vehicle order (lane_array[slot[v]] is
                    # vehicle v's lane; trivial topology: identity)
                    sl = self.slot
                    sim.servers[t].load_buffer(
                        jax.tree_util.tree_map(
                            lambda a: np.asarray(a)[sl], c["buf_delta"][t]),
                        np.asarray(c["buf_w"][t])[sl],
                        np.asarray(c["buf_age"][t])[sl],
                        np.asarray(c["buf_dest"][t])[sl])

    # ------------------------------------------------------------------
    def _run_check(self, x, check) -> None:
        """fused_check: replay the serial LocalTrainer on the identical
        staged batches and distributed adapters; record the deviation (the
        batched_check machinery, extended to fused): the max adapter-entry
        difference and, per vehicle, the relative one of
        :func:`adapter_deviation`."""
        sim = self.sim
        dev = rel = 0.0
        for ti in range(self.T):
            ids = np.where(x["active"][ti])[0]
            if not len(ids):
                continue
            ranks = np.asarray(check["ranks"][ti])
            for v in ids:
                r = int(ranks[v])
                lane = jax.tree_util.tree_map(lambda a: a[v],
                                              check["dist"][ti])
                ref_in = lora_lib.truncate_adapter_tree(lane, r)
                n = int(x["counts"][ti][v])
                per_step = [{"tokens": x["tokens"][ti][v][si],
                             "labels": x["labels"][ti][v][si]}
                            for si in range(n)]
                ref_ad, _ = sim.trainer.finetune(
                    sim.params, ref_in, None, n, batches=per_step)
                got = lora_lib.truncate_adapter_tree(
                    jax.tree_util.tree_map(lambda a: a[v],
                                           check["new"][ti]), r)
                d_max, d_rel = adapter_deviation(got, ref_ad, ref_in)
                dev = max(dev, d_max)
                rel = max(rel, d_rel)
        self.check_dev = max(self.check_dev, dev)
        self.check_rel = max(self.check_rel, rel)
        self.sim.engine_check_dev = max(self.sim.engine_check_dev, dev)
        self.sim.engine_check_rel = max(self.sim.engine_check_rel, rel)
