"""Large-scale IoV multi-task federated fine-tuning simulator (paper §V).

Drives, per communication round:
  1. vehicle mobility (trajectory step, RSU coverage, departure prediction),
  2. inter-task energy budgets (Algorithm 1 — cloud),
  3. intra-task rank selection (UCB-DUAL — vehicles; or baseline rules),
  4. distribution → local fine-tuning (real JAX training of the task model)
     → upload → aggregation (per-method: ours/HomoLoRA/HetLoRA/FedRA),
  5. §III-C four-stage cost accounting over the Shannon channel,
  6. §IV-E mobility fallbacks for predicted departures.

Training dynamics use a reduced backbone (container is 1-core CPU);
cost accounting uses the FULL paper backbone's dimensions (ViT-Base by
default) so latency/energy magnitudes stay paper-faithful. Both archs are
configurable (DESIGN.md §4, EXPERIMENTS.md records settings).

Mobility regimes beyond the default synthetic map — trace replay, dynamic
fleets (arrival/departure slots), RSU layouts and outage windows — are
declared on ``SimConfig.mobility_sim`` and packaged as named presets in
``repro.sim.scenarios`` (README "Scenarios").
"""
from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import (CheckpointSpec, EnergyAllocConfig, LoRAConfig,
                          MobilityConfig, ModelConfig, ParticipationSpec,
                          RSUTierSpec, ShardSpec, UCBDualConfig, get_arch)
from repro.core import aggregation as agg
from repro.core import cost_model as cm
from repro.core import energy_alloc, mobility as mob
from repro.core import ucb_dual
from repro.data import ClientDataset, DEFAULT_TASKS, dirichlet_partition, make_task
from repro.federated.baselines import (METHODS, capability_ranks,
                                       is_residual, server_method)
from repro.federated.batched_client import (BatchedLocalTrainer, draw_batches,
                                            take_lanes)
from repro.federated.client import LocalTrainer
from repro.federated.server import RSUServer
from repro.models import transformer as T
from repro.sim.channel import ChannelConfig, ChannelModel
from repro.sim.mobility_model import MobilityModel, MobilitySimConfig
from repro.telemetry import Telemetry


@dataclass
class SimConfig:
    method: str = "ours"
    num_tasks: int = 3
    num_vehicles: int = 24
    rounds: int = 60
    local_steps: int = 3
    batch_size: int = 10
    lr: float = 5e-3
    seed: int = 0
    train_arch: Optional[ModelConfig] = None     # default: reduced ViT
    cost_arch_id: str = "vit-base-paper"         # cost-model dimensions
    lora: LoRAConfig = field(default_factory=lambda: LoRAConfig(
        rank=8, max_rank=32, candidate_ranks=(2, 4, 8, 16, 32)))
    ucb: UCBDualConfig = field(default_factory=UCBDualConfig)
    energy: EnergyAllocConfig = field(default_factory=lambda:
                                      EnergyAllocConfig(e_total=900.0))
    mobility: MobilityConfig = field(default_factory=MobilityConfig)
    mobility_sim: MobilitySimConfig = field(default_factory=MobilitySimConfig)
    # two-tier RSU hierarchy: RSUs per task, association handoffs, periodic
    # staleness-weighted global sync. The trivial default (1 RSU per task,
    # sync every round) is regression-pinned to the pre-hierarchy engines.
    rsu_tier: RSUTierSpec = field(default_factory=RSUTierSpec)
    # round-participation policy (repro.config.ParticipationSpec): WHEN an
    # upload lands. The trivial default ("sync") keeps strict round
    # synchrony bit-exactly on every engine; "semi_sync" parks missed
    # uploads in an in-flight buffer and lands them k rounds late at
    # decay**k weight (buffered handoffs follow the vehicle across RSUs).
    participation: ParticipationSpec = field(
        default_factory=ParticipationSpec)
    channel: ChannelConfig = field(default_factory=ChannelConfig)
    departure_fraction: float = 0.5   # fraction of local steps done at exit
    bytes_per_param: int = 4
    # round engine:
    #   "fused"   — ONE jit program per round over the whole rank-padded
    #               fleet (federated.fused_engine; "ours"-family methods);
    #   "fused_sharded" — the fused program with its fleet axis sharded
    #               over a 1-D device mesh (see `shard` below; DESIGN.md
    #               §3). With the default trivial ShardSpec it uses every
    #               visible device;
    #   "batched" — one vmap×scan jit call per (task, rank) group plus
    #               grouped aggregation;
    #   "serial"  — the per-vehicle reference loop;
    #   "batched_check"/"fused_check" — run the engine, then replay the
    #               serial reference on identical data and record the max
    #               adapter deviation (self.engine_check_dev).
    # None (default) resolves to $REPRO_SIM_ENGINE or "batched"; the
    # resolved auto choice falls back from fused to batched for methods the
    # fused engine does not cover (an EXPLICIT engine="fused" raises).
    engine: Optional[str] = None
    # fleet-axis device sharding (repro.config.ShardSpec). A non-trivial
    # spec shards the fused engine even under engine="fused"; the trivial
    # default keeps the single-device program byte-for-byte.
    shard: ShardSpec = field(default_factory=ShardSpec)
    # resumable horizons (repro.checkpoint.carry; DESIGN.md §7): an enabled
    # spec makes run()/run_scanned() emit an atomic full-state checkpoint
    # every `interval` rounds; run_scanned scans in interval-sized chunks
    # (equal chunks share one compiled scan program). Like `shard`, the
    # spec never alters the simulated trajectory — it is exempt from the
    # restore fingerprint, so resumes may change it freely.
    checkpoint: CheckpointSpec = field(default_factory=CheckpointSpec)
    # bookkeeping label set by repro.sim.scenarios.build_config; the actual
    # scenario recipe (trace, RSU layout, outages) lives in mobility_sim
    scenario: Optional[str] = None


class IoVSimulator:
    def __init__(self, cfg: SimConfig):
        self.cfg = cfg
        self.spec = METHODS[cfg.method]
        rng = np.random.default_rng(cfg.seed)
        self.rng = rng

        # --- model (shared frozen base across tasks; adapters per task) ---
        # the default train arch resolves onto the SIMULATOR, never back
        # into the caller's SimConfig (same no-mutation contract as engine:
        # a SimConfig reused across simulators must stay as authored)
        if cfg.train_arch is None:
            from repro.configs import vit_base_paper
            self.model_cfg = vit_base_paper.reduced()
        else:
            self.model_cfg = cfg.train_arch
        key = jax.random.PRNGKey(cfg.seed)
        self.params = T.init_params(key, self.model_cfg, dtype=jnp.float32)
        # resolved choice lives on the simulator — never written back into
        # the caller's config (a reused SimConfig must keep engine=None so
        # later sims still pick up $REPRO_SIM_ENGINE)
        self.engine = self._resolve_engine(cfg)
        self.trainer = LocalTrainer(self.model_cfg, cfg.lora, lr=cfg.lr)
        self.batched_trainer = BatchedLocalTrainer(
            self.model_cfg, cfg.lora, lr=cfg.lr, max_steps=cfg.local_steps)
        self.engine_check_dev = 0.0   # batched_check: max |batched − serial|
        # fused_check: max over vehicles of ||fused − serial|| / ||serial
        # − start|| (fused_engine.adapter_deviation)
        self.engine_check_rel = 0.0

        # --- cost model (full-dimension backbone) ---
        self.cost_cfg = get_arch(cfg.cost_arch_id)
        tokens_per_sample = 200  # ViT-Base: 196 patches + cls + margin
        n_active = self.cost_cfg.param_counts()["active"]
        self.base_flops_per_sample = 4.0 * n_active * tokens_per_sample
        self.cost_dims = cm.target_dims_of(self.cost_cfg, cfg.lora)
        self.g_cache = {r: cm.g_factor(self.cost_cfg, cfg.lora, r)
                        for r in cfg.lora.candidate_ranks}
        self.dev_profiles = cm.default_device_profiles(
            rng, cfg.num_vehicles, self.base_flops_per_sample)
        # κ recalibrated for ~15–40 W vehicular compute (DESIGN.md §4)
        self.dev_profiles = [dataclasses.replace(p, kappa=float(
            rng.uniform(2.0, 5.0) * 1e-36)) for p in self.dev_profiles]
        self.rsu_profile = cm.default_rsu_profile()
        # persistent per-vehicle log-normal shadowing (σ≈5 dB): strong,
        # stable channel heterogeneity — the regime where per-vehicle rank
        # adaptation matters (paper §III challenge 1)
        self.shadow = np.exp(rng.normal(0.0, 1.2, cfg.num_vehicles))

        # --- tasks, data, partitions ---
        self.tasks = list(DEFAULT_TASKS[:cfg.num_tasks])
        while len(self.tasks) < cfg.num_tasks:   # task-scalability runs
            base = DEFAULT_TASKS[len(self.tasks) % len(DEFAULT_TASKS)]
            self.tasks.append(dataclasses.replace(
                base, name=f"{base.name}{len(self.tasks)}"))
        self.task_data = [make_task(t, seed=cfg.seed + ti)
                          for ti, t in enumerate(self.tasks)]
        self.client_data: List[List[ClientDataset]] = []
        for ti, (spec_t, data) in enumerate(zip(self.tasks, self.task_data)):
            parts = dirichlet_partition(data["labels"], cfg.num_vehicles,
                                        alpha=0.5, seed=cfg.seed + ti)
            self.client_data.append([
                ClientDataset(data["tokens"][idx], data["labels"][idx],
                              cfg.batch_size, seed=cfg.seed + 31 * v)
                for v, idx in enumerate(parts)])
        self.eval_batches = [
            {"tokens": d["eval_tokens"], "labels": d["eval_labels"]}
            for d in self.task_data]
        # fixed-size local eval batches (q_v^t must be rank-sensitive:
        # train-batch accuracy saturates on tiny shards; held-out accuracy
        # reflects the truncation quality of the received rank)
        self.local_eval = []
        for d in self.task_data:
            n = min(32, len(d["eval_labels"]))
            idx = rng.choice(len(d["eval_labels"]), n, replace=False)
            self.local_eval.append({"tokens": d["eval_tokens"][idx],
                                    "labels": d["eval_labels"][idx]})

        # --- infrastructure ---
        ms = dataclasses.replace(cfg.mobility_sim,
                                 num_vehicles=cfg.num_vehicles,
                                 seed=cfg.seed)
        all_rsus = MobilityModel.place_rsus(
            cfg.num_tasks, ms.area, ms.coverage_radius, seed=cfg.seed,
            layout=ms.rsu_layout,
            num_per_task=cfg.rsu_tier.num_rsus_per_task)
        # rsu_groups[ti] is task ti's RSU tier; self.rsus keeps the primary
        # per task (for the trivial tier this is exactly the legacy list)
        self.rsu_groups = [[r for r in all_rsus if r.task_id == t]
                           for t in range(cfg.num_tasks)]
        self.rsus = [g[0] for g in self.rsu_groups]
        self.mobility = MobilityModel(ms, all_rsus)
        self.channel = ChannelModel(cfg.channel, seed=cfg.seed + 3)
        self.servers = [RSUServer(self.model_cfg, cfg.lora,
                                  server_method(cfg.method),
                                  seed=cfg.seed + 7 * t,
                                  residual=is_residual(cfg.method),
                                  tier=cfg.rsu_tier,
                                  participation=cfg.participation)
                        for t in range(cfg.num_tasks)]
        K = len(cfg.lora.candidate_ranks)
        self.ucb_states = [ucb_dual.init_state(cfg.num_vehicles, K)
                           for _ in range(cfg.num_tasks)]
        self.alloc = energy_alloc.init_alloc(cfg.energy, cfg.num_tasks)
        self.history: List[Dict[str, Any]] = []
        self._het_ranks = capability_ranks(
            cfg.lora.candidate_ranks,
            np.array([p.freq for p in self.dev_profiles]))

        # host spans and counters (repro.telemetry): the fused engine
        # shares them, so sim.fused.telemetry is this object
        self.telemetry = Telemetry()
        # --- fused engine (one jit program per round; see fused_engine) ---
        self.fused = None
        if self.engine in ("fused", "fused_check", "fused_sharded"):
            from repro.federated.fused_engine import FusedRoundEngine
            self.fused = FusedRoundEngine(
                self, check=(self.engine == "fused_check"),
                sharded=(self.engine == "fused_sharded"))

    # ------------------------------------------------------------------
    @staticmethod
    def _resolve_engine(cfg: SimConfig) -> str:
        from repro.federated.fused_engine import supports_method
        env = os.environ.get("REPRO_SIM_ENGINE")
        engine = cfg.engine or env or "batched"
        known = ("serial", "batched", "batched_check", "fused",
                 "fused_check", "fused_sharded")
        if engine not in known:
            raise ValueError(f"unknown engine {engine!r}; have {known}")
        if (engine in ("fused", "fused_check", "fused_sharded")
                and not supports_method(cfg.method)):
            if cfg.engine is None:   # auto (env) choice: fall back
                return "batched"
            raise ValueError(
                f"engine={engine!r} does not support method "
                f"{cfg.method!r}; use engine='batched' or 'serial'")
        if (not cfg.shard.trivial
                and engine not in ("fused", "fused_sharded")):
            if cfg.engine is not None:
                # an explicitly chosen non-fused engine would silently
                # ignore an explicitly requested fleet sharding; refuse
                raise ValueError(
                    f"engine={engine!r} cannot shard the fleet axis; "
                    f"SimConfig.shard={cfg.shard} needs engine='fused' "
                    "or 'fused_sharded' (or the trivial ShardSpec)")
            if env is None and supports_method(cfg.method):
                # nothing chose an engine: honor the explicit shard
                # request instead of silently dropping it on the default
                return "fused"
            # env-resolved engines keep working (the CI engine matrix
            # must not trip over sharded configs); the spec stays inert
        return engine

    # ------------------------------------------------------------------
    def _select_ranks(self, ti: int, active: np.ndarray) -> np.ndarray:
        cfg = self.cfg
        cand = np.asarray(cfg.lora.candidate_ranks)
        if self.spec.adaptive_rank:
            arms = np.asarray(ucb_dual.select_ranks(
                self.ucb_states[ti], cfg.ucb, jnp.asarray(active)))
            ranks = np.where(arms >= 0, cand[np.clip(arms, 0, None)], -1)
            return ranks, arms
        if cfg.method == "hetlora":
            ranks = np.where(active, self._het_ranks, -1)
        else:   # homolora / fedra: uniform fixed rank
            ranks = np.where(active, cfg.lora.rank, -1)
        return ranks, None

    # ------------------------------------------------------------------
    def run_round(self) -> Dict[str, Any]:
        """One communication round, in three phases:

        1. plan   — per task: coverage, rank selection, adapter
                    distribution, §IV-E step budgets (no training);
        2. train  — local fine-tuning for every task; the batched engine
                    dispatches all (task, rank) groups as concurrent
                    vmap×scan jit calls;
        3. finish — per task: §III-C cost accounting over the channel,
                    §IV-E fallbacks, aggregation, global eval, UCB-DUAL.

        The channel fading RNG is consumed only in phase 3, in a fixed
        per-task, per-vehicle order — so the serial and batched engines see
        identical randomness (regression-tested).

        The fused engine replaces all three phases with one jit-compiled
        round program (federated.fused_engine) and only shares the host
        staging (mobility tick, channel draws, data batches) with this
        path — consuming identical RNG streams, so engines can be compared
        round-for-round and even switched mid-run.
        """
        cfg = self.cfg
        if self.fused is not None:
            return self.fused.run_round()
        self.mobility.step()
        budgets = np.asarray(self.alloc.budgets)
        rec: Dict[str, Any] = {"round": len(self.history), "tasks": []}
        consumed = np.zeros(cfg.num_tasks)
        accuracies = np.zeros(cfg.num_tasks)

        plans = [self._plan_task(ti) for ti in range(cfg.num_tasks)]
        trains = self._train_plans(plans)
        for ti, (plan, tr) in enumerate(zip(plans, trains)):
            trec = self._finish_task(plan, tr, budgets[ti])
            consumed[ti] = trec["energy"]
            accuracies[ti] = trec["accuracy"]
            rec["tasks"].append(trec)

        if self.spec.energy_scheduler:
            self.alloc, _ = energy_alloc.step(
                self.alloc, cfg.energy, jnp.asarray(consumed),
                jnp.asarray(accuracies))
        rec["budgets"] = budgets.tolist()
        rec["reward"] = float(sum(t["reward"] for t in rec["tasks"]))
        rec["energy"] = float(consumed.sum())
        rec["latency"] = float(max((t["latency"] for t in rec["tasks"]),
                                   default=0.0))
        rec["accuracy"] = float(np.mean(accuracies))
        self.history.append(rec)
        return rec

    # ------------------------------------------------------------------
    def _plan_task(self, ti: int) -> Dict[str, Any]:
        """Phase 1: everything a task round needs before training starts."""
        cfg = self.cfg
        rsu = self.rsus[ti]
        # same snapshot the fused engine stages; for the trivial tier the
        # group view reduces exactly to round_view(rsu)
        view = self.mobility.round_view_group(self.rsu_groups[ti])
        active = view["active"]
        ranks, arms = self._select_ranks(ti, active)
        active_ids = np.where(active)[0]
        departing = view["departing"]
        staying = view["staying"]
        adapters_list = self.servers[ti].distribute(
            [int(ranks[v]) for v in active_ids])
        fedra_masks = (self.servers[ti].masks if cfg.method == "fedra" else
                       [None] * len(active_ids))
        # §IV-E: departing vehicles fine-tune a reduced number of steps
        steps_list, frac_list = [], []
        for v in active_ids:
            if bool(departing[v]):
                steps_list.append(max(1, int(round(
                    cfg.local_steps * cfg.departure_fraction))))
                frac_list.append(cfg.departure_fraction)
            else:
                steps_list.append(cfg.local_steps)
                frac_list.append(1.0)
        return {"ti": ti, "rsu": rsu, "active_ids": active_ids,
                "ranks": ranks, "arms": arms, "departing": departing,
                "staying": staying, "adapters_list": adapters_list,
                "fedra_masks": fedra_masks, "steps_list": steps_list,
                "frac_list": frac_list, "distances": view["distances"],
                "assoc": view["assoc"], "handoff": view["handoff"]}

    # ------------------------------------------------------------------
    def _train_serial(self, plan: Dict[str, Any]) -> Dict[str, Any]:
        """Reference engine: the per-vehicle LocalTrainer loop."""
        ti = plan["ti"]
        fm = plan["fedra_masks"]
        ads: List[Any] = []
        accs: List[float] = []
        for i, v in enumerate(plan["active_ids"]):
            mask = fm[i] if i < len(fm) else None
            ad, metrics = self.trainer.finetune(
                self.params, plan["adapters_list"][i],
                self.client_data[ti][v], plan["steps_list"][i],
                eval_batch=self.local_eval[ti], layer_mask=mask)
            ads.append(ad)
            accs.append(metrics.get("eval_accuracy",
                                    metrics.get("accuracy", 0.0)))
        return {"ads_list": ads, "groups": None,
                "accs": np.asarray(accs, np.float32)}

    # ------------------------------------------------------------------
    def _train_plans(self, plans: Sequence[Dict[str, Any]]
                     ) -> List[Dict[str, Any]]:
        """Phase 2: local fine-tuning for all tasks (engine dispatch).

        serial: the per-vehicle reference loop, task by task.
        batched: every (task, rank) group becomes one vmap×scan jit job;
            all jobs run concurrently on the trainer's thread pool and the
            results stay stacked for grouped aggregation.
        batched_check: batched, then the serial reference is replayed on
            the identical pre-drawn batches and the max adapter deviation
            recorded in self.engine_check_dev.
        """
        cfg = self.cfg
        if self.engine == "serial":
            return [self._train_serial(p) for p in plans]

        results: List[Dict[str, Any]] = []
        jobs: List[Dict[str, Any]] = []
        slots: List[Tuple[int, int, List[int]]] = []
        for pi, plan in enumerate(plans):
            ti = plan["ti"]
            n = len(plan["active_ids"])
            res = {"ads_list": None, "groups": {},
                   "accs": np.zeros(n, np.float32)}
            results.append(res)
            if n == 0:
                continue
            # pre-draw every vehicle's batches — identical per-shard RNG
            # stream as the serial engine
            batches = [draw_batches(self.client_data[ti][v],
                                    plan["steps_list"][i], cfg.local_steps)
                       for i, v in enumerate(plan["active_ids"])]
            plan["batches"] = batches
            by_rank: Dict[int, List[int]] = {}
            for i, v in enumerate(plan["active_ids"]):
                by_rank.setdefault(int(plan["ranks"][v]), []).append(i)
            fm = plan["fedra_masks"]
            for r in sorted(by_rank):
                idxs = by_rank[r]
                jobs.append({
                    "adapters_list": [plan["adapters_list"][i]
                                      for i in idxs],
                    "batches_list": [batches[i] for i in idxs],
                    "step_counts": [plan["steps_list"][i] for i in idxs],
                    "eval_batch": self.local_eval[ti],
                    "layer_masks": [fm[i] if i < len(fm) else None
                                    for i in idxs]})
                slots.append((pi, r, idxs))

        outs = self.batched_trainer.run_jobs(self.params, jobs)
        for (pi, r, idxs), (stacked, marr) in zip(slots, outs):
            res = results[pi]
            res["groups"][r] = (stacked, idxs)
            accs = marr.get("eval_accuracy", marr.get("accuracy"))
            for j, i in enumerate(idxs):
                res["accs"][i] = accs[j]
        if self.engine == "batched_check":
            self._check_against_serial(plans, results)
        return results

    # ------------------------------------------------------------------
    def _check_against_serial(self, plans, results) -> None:
        """batched_check: replay the serial reference on the SAME pre-drawn
        batches and record the max |batched − serial| adapter deviation."""
        dev = 0.0
        for plan, res in zip(plans, results):
            if not len(plan["active_ids"]):
                continue
            lanes = {}
            for r, (stacked, idxs) in res["groups"].items():
                for j, i in enumerate(idxs):
                    lanes[i] = (stacked, j)
            fm = plan["fedra_masks"]
            for i, v in enumerate(plan["active_ids"]):
                per_step = [{k: arr[si]
                             for k, arr in plan["batches"][i].items()}
                            for si in range(plan["steps_list"][i])]
                ref_ad, _ = self.trainer.finetune(
                    self.params, plan["adapters_list"][i], None,
                    plan["steps_list"][i],
                    eval_batch=self.local_eval[plan["ti"]],
                    layer_mask=fm[i] if i < len(fm) else None,
                    batches=per_step)
                stacked, j = lanes[i]
                for a, b in zip(jax.tree_util.tree_leaves(stacked),
                                jax.tree_util.tree_leaves(ref_ad)):
                    dev = max(dev, float(jnp.max(jnp.abs(a[j] - b))))
        self.engine_check_dev = max(self.engine_check_dev, dev)

    # ------------------------------------------------------------------
    def _finish_task(self, plan: Dict[str, Any], tr: Dict[str, Any],
                     budget: float) -> Dict[str, Any]:
        """Phase 3: §III-C accounting, §IV-E fallbacks, aggregation,
        global eval and the UCB-DUAL dual update for one task."""
        cfg = self.cfg
        ti = plan["ti"]
        rsu = plan["rsu"]
        server = self.servers[ti]
        active_ids = plan["active_ids"]
        ranks, arms = plan["ranks"], plan["arms"]
        departing, staying = plan["departing"], plan["staying"]
        handoff = plan["handoff"]
        tier = cfg.rsu_tier
        # distances to each vehicle's ASSOCIATED RSU (the primary for the
        # trivial tier — bitwise the legacy distances_to(rsu) array);
        # one canonical pass over the fading RNG (shared with the fused
        # engine's staging — identical draws in identical order)
        dists = plan["distances"]
        rate_down_v, rate_up_v = self.channel.round_rates(
            self.rsu_profile.tx_power,
            np.asarray([p.tx_power for p in self.dev_profiles]),
            dists, self.shadow, active_ids)

        kept_idx: List[int] = []         # positions within the active list
        kept_weights: List[float] = []
        kept_masks: List[Any] = []
        kept_adapters: List[Any] = []    # serial engine only
        kept_assoc: List[int] = []       # associated RSU per kept client
        # semi_sync: active-list positions whose upload DEFERS into the
        # in-flight buffer (departing non-migrating contributors — the
        # vehicle exits coverage before its upload completes). With
        # max_delay=0 the buffer cannot hold a round, so every upload
        # lands in its own round: sync semantics, bit-exactly.
        part = cfg.participation
        deferrable = not part.trivial and part.max_delay > 0
        deferred_idx: List[int] = []
        per_v_reward = np.zeros(cfg.num_vehicles, np.float32)
        per_v_energy = np.zeros(cfg.num_vehicles, np.float32)
        costs_list: List[cm.RoundCosts] = []
        comm_params = 0
        n_fallback = {0: 0, 1: 0, 2: 0}

        for i, v in enumerate(active_ids):
            rank = int(ranks[v])
            dep = bool(departing[v])
            frac = plan["frac_list"][i]
            mask = (plan["fedra_masks"][i]
                    if i < len(plan["fedra_masks"]) else None)
            local_acc = float(tr["accs"][i])

            # §III-C costs over the real channel (fades pre-drawn above)
            devp = self.dev_profiles[v]
            rate_d = float(rate_down_v[v])
            rate_u = float(rate_up_v[v])
            payload = cm.adapter_payload_params(self.cost_dims, rank)
            g = self.g_cache.get(rank, cm.g_factor(self.cost_cfg, cfg.lora,
                                                   rank))
            if cfg.method == "fedra":
                # FedRA clients train (and upload) only their layer subset
                fr = server.fedra_fraction
                payload = int(payload * fr)
                g = g * (0.4 + 0.6 * fr)
            costs = cm.vehicle_round_costs(
                devp, self.rsu_profile, rank=rank, payload_params=payload,
                bytes_per_param=cfg.bytes_per_param, rate_down=rate_d,
                rate_up=rate_u,
                num_samples=int(cfg.batch_size * cfg.local_steps * frac),
                g=g)

            contribute = True
            migrated = False
            extra_energy = 0.0
            extra_latency = 0.0
            if not tier.trivial and bool(handoff[v]):
                # adapter migration between RSUs of the task's tier
                ho_lat, ho_e = cm.handoff_costs(
                    tier.handoff_latency, tier.handoff_energy, True)
                extra_energy += float(ho_e)
                extra_latency += float(ho_lat)
            if dep and self.spec.mobility_aware:
                peer = self.mobility.nearby_peer(rsu, v, staying)
                dec = mob.decide_fallback(
                    cfg.mobility, cfg.ucb, local_accuracy=local_acc,
                    energy_spent=costs.e_comp,
                    migration_available=peer is not None)
                n_fallback[dec.strategy] += 1
                if dec.strategy == mob.ABANDON:
                    contribute = False
                elif dec.strategy == mob.MIGRATE:
                    migrated = True
                    extra_energy += cfg.mobility.migration_energy
                    extra_latency += cfg.mobility.migration_latency
            elif dep:   # baseline: departure loses the update
                contribute = False

            e_total = costs.energy + extra_energy
            tau = costs.latency + extra_latency
            per_v_energy[v] = e_total
            per_v_reward[v] = float(ucb_dual.reward(
                cfg.ucb, jnp.asarray(local_acc), jnp.asarray(tau)))
            costs_list.append(costs)
            if contribute:
                comm_params += payload
                # semi_sync: a departing contributor that did not migrate
                # exits coverage before its upload lands — the upload
                # defers into the buffer (a migrating vehicle paid the
                # §IV-E penalty precisely so its update lands NOW)
                if deferrable and dep and not migrated:
                    deferred_idx.append(i)
                    continue
                kept_idx.append(i)
                kept_weights.append(float(len(self.client_data[ti][v])))
                kept_assoc.append(int(plan["assoc"][v]))
                if mask is not None:
                    kept_masks.append(mask)
                if tr["ads_list"] is not None:
                    kept_adapters.append(tr["ads_list"][i])

        # RSU-side aggregation cost covers every upload PRODUCED this
        # round (deferred ones transit late but still get processed; the
        # sync path has no deferrals, so this is exactly len(kept_idx))
        agg_costs = cm.rsu_agg_costs(self.rsu_profile,
                                     len(kept_idx) + len(deferred_idx))
        summary = cm.task_round_summary(costs_list, agg_costs)

        # semi_sync participation: collect the buffered uploads landing
        # this round (vehicle back in coverage, within max_delay) BEFORE
        # aggregating, then park this round's missed uploads afterwards —
        # the same age→release→drop→admit ordering the fused engine's
        # scan-carry buffer step uses (DESIGN.md §8)
        released: List[Any] = []
        if not part.trivial:
            active_mask = np.zeros(cfg.num_vehicles, bool)
            active_mask[active_ids] = True
            released = server.release_buffered(active_mask, plan["assoc"])

        self._aggregate_task(server, plan, tr, kept_idx, kept_weights,
                             kept_masks, kept_adapters, kept_assoc,
                             released=released)

        if deferred_idx:
            entries = []
            for i in deferred_idx:
                v = active_ids[i]
                ad = self._trained_adapter(tr, i)
                delta = agg.aggregate_merged([ad], [1.0], cfg.lora.scale)
                entries.append((int(v), delta,
                                float(len(self.client_data[ti][v])),
                                int(plan["assoc"][v])))
            server.admit_buffered(entries)

        # global accuracy on the held-out task eval set
        gad = server.eval_adapters()
        if gad is not None and (kept_idx or released):
            m = self.trainer.evaluate(self.params, gad,
                                      self.eval_batches[ti])
            acc = m["accuracy"]
        else:
            acc = 0.0

        # UCB-DUAL update with the task's current budget
        if self.spec.adaptive_rank and arms is not None:
            self.ucb_states[ti], info = ucb_dual.update(
                self.ucb_states[ti], cfg.ucb, jnp.asarray(arms),
                jnp.asarray(per_v_reward), jnp.asarray(per_v_energy),
                jnp.asarray(budget, jnp.float32))
            lam = float(info["lambda"])
        else:
            lam = 0.0

        tau_t = summary["latency"]
        e_t = float(per_v_energy.sum()) + agg_costs[1]
        reward_t = (cfg.ucb.gamma * acc
                    - cfg.ucb.alpha * tau_t / cfg.ucb.latency_ref)
        mean_rank = float(np.mean([int(r) for r in ranks[active_ids]])
                          ) if len(active_ids) else 0.0
        trec = {"task": self.tasks[ti].name, "accuracy": acc,
                "latency": tau_t, "energy": e_t, "reward": reward_t,
                "lambda": lam, "mean_rank": mean_rank,
                "active": int(len(active_ids)),
                "departing": int(departing.sum()),
                "handoffs": int((handoff[active_ids]).sum())
                if len(active_ids) else 0,
                "fallbacks": dict(n_fallback),
                "comm_params": int(comm_params),
                "budget": float(budget)}
        if not part.trivial:
            # buffer dynamics (semi_sync only, so sync history stays
            # byte-identical to the pinned pre-participation fixtures)
            trec["deferred"] = len(deferred_idx)
            trec["released"] = len(released)
            trec["rel_weight"] = float(sum(r[1] for r in released))
        return trec

    # ------------------------------------------------------------------
    def _trained_adapter(self, tr: Dict[str, Any], i: int) -> Any:
        """Trained adapter tree of active-list position `i` — per-client
        list for the serial engine, lane-extracted from the stacked rank
        group for the batched one (missed-upload buffering)."""
        if tr["ads_list"] is not None:
            return tr["ads_list"][i]
        for r in sorted(tr["groups"]):
            stacked, idxs = tr["groups"][r]
            for j, ii in enumerate(idxs):
                if ii == i:
                    return jax.tree_util.tree_map(lambda x: x[j], stacked)
        raise KeyError(f"active position {i} not found in rank groups")

    # ------------------------------------------------------------------
    def _aggregate_task(self, server, plan, tr, kept_idx, kept_weights,
                        kept_masks, kept_adapters, kept_assoc,
                        released=None) -> None:
        """Upload + aggregation. The batched engine hands the server the
        kept clients as stacked per-rank groups (one lane-gather per group);
        the serial engine keeps the per-client list path. kept_assoc routes
        each upload into its RSU partial under non-trivial tiers; released
        carries the semi_sync buffer's late uploads landing this round."""
        if tr["groups"] is None or not kept_idx:
            server.aggregate(kept_adapters, kept_weights or [1.0],
                             masks=kept_masks if kept_masks else None,
                             indices=kept_idx, assoc=kept_assoc,
                             released=released)
            return
        keep = set(kept_idx)
        w_of = dict(zip(kept_idx, kept_weights))
        a_of = dict(zip(kept_idx, kept_assoc))
        mask_of = dict(zip(kept_idx, kept_masks)) if kept_masks else {}
        gspecs = []
        for r in sorted(tr["groups"]):
            stacked, idxs = tr["groups"][r]
            lanes = [j for j, i in enumerate(idxs) if i in keep]
            if not lanes:
                continue
            gi = [idxs[j] for j in lanes]
            # pad each group to a power-of-two lane count with ZERO-WEIGHT
            # copies of lane 0 — exact no-ops in every weighted reduction,
            # but they bound the shape set the aggregation einsums see
            # (otherwise every new kept-count recompiles them)
            npad = (1 << max(len(lanes) - 1, 0).bit_length()) - len(lanes)
            sub = take_lanes(stacked, lanes + [lanes[0]] * npad)
            weights = np.asarray([w_of[i] for i in gi] + [0.0] * npad,
                                 np.float32)
            masks = None
            if mask_of:
                zero = np.zeros_like(np.asarray(mask_of[gi[0]], np.float32))
                masks = np.stack([np.asarray(mask_of[i]) for i in gi]
                                 + [zero] * npad)
            gspecs.append({
                "adapters": sub,
                "weights": weights,
                "masks": masks,
                "indices": gi + [gi[0]] * npad,
                # padded lanes replicate lane 0's association; their zero
                # weight keeps them exact no-ops in the segment sums
                "assoc": np.asarray([a_of[i] for i in gi]
                                    + [a_of[gi[0]]] * npad, np.int32)})
        server.aggregate_grouped(gspecs, released=released)

    # ------------------------------------------------------------------
    def run_scanned(self, rounds: Optional[int] = None
                    ) -> List[Dict[str, Any]]:
        """Fused engine only: execute `rounds` communication rounds as ONE
        `lax.scan`-wrapped XLA call. Mobility traces, channel draws and data
        batches are pre-staged on the host (consuming the same RNG streams
        as per-round execution), then the device runs every round without
        host involvement. Appends to and returns self.history.

        With an enabled ``SimConfig.checkpoint`` the horizon is scanned in
        ``interval``-sized chunks with an atomic full-state checkpoint
        (repro.checkpoint.carry) at every boundary. Equal chunks reuse ONE
        compiled scan program — the fused engine keys its scan cache on the
        chunk length, so chunking adds no cache keys; only a non-multiple
        tail chunk compiles a second (shorter) program. The staging RNG
        streams are consumed in round order either way, so the chunked
        trajectory replays the per-round one."""
        if self.fused is None:
            raise ValueError(
                "run_scanned requires engine='fused' "
                f"(engine={self.engine!r})")
        n = rounds or self.cfg.rounds
        ck = self.cfg.checkpoint
        if not ck.enabled:
            return self.fused.run_scanned(n)
        from repro.checkpoint.carry import save_checkpoint
        out: List[Dict[str, Any]] = []
        done = 0
        while done < n:
            chunk = min(ck.interval, n - done)
            out.extend(self.fused.run_scanned(chunk))
            done += chunk
            # tagged with the last round it holds
            with self.telemetry.span("checkpoint",
                                     round=len(self.history) - 1):
                save_checkpoint(self)
        return out

    # ------------------------------------------------------------------
    def run(self, rounds: Optional[int] = None, log_every: int = 0
            ) -> List[Dict[str, Any]]:
        n = rounds or self.cfg.rounds
        ck = self.cfg.checkpoint
        for i in range(n):
            rec = self.run_round()
            if log_every and (i % log_every == 0):
                print(f"[{self.cfg.method}] round {i:3d} "
                      f"acc={rec['accuracy']:.3f} reward={rec['reward']:.2f} "
                      f"E={rec['energy']:.0f}J lat={rec['latency']:.1f}s")
            if ck.enabled and len(self.history) % ck.interval == 0:
                from repro.checkpoint.carry import save_checkpoint
                with self.telemetry.span("checkpoint",
                                         round=len(self.history) - 1):
                    save_checkpoint(self)
        return self.history

    # ------------------------------------------------------------------
    def summary(self, tail: int = 10) -> Dict[str, float]:
        h = self.history
        if not h:   # before any round: empty-history-safe, not ValueError
            return {"method": self.cfg.method, "rounds": 0,
                    "cum_reward": 0.0, "best_accuracy": 0.0,
                    "avg_latency": 0.0, "avg_energy": 0.0,
                    "avg_comm_params": 0.0}
        tail_h = h[-tail:]
        best_acc = max(r["accuracy"] for r in h)
        return {
            "method": self.cfg.method,
            "rounds": len(h),
            "cum_reward": float(sum(r["reward"] for r in h)),
            "best_accuracy": float(best_acc),
            "avg_latency": float(np.mean([r["latency"] for r in tail_h])),
            "avg_energy": float(np.mean([r["energy"] for r in tail_h])),
            "avg_comm_params": float(np.mean(
                [sum(t["comm_params"] for t in r["tasks"]) for r in tail_h])),
        }
