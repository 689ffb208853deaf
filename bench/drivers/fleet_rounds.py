"""Driver ``fleet_rounds``: communication rounds of the fused fleet engine.

Set-up builds one ``IoVSimulator`` from the traffic file and the seed,
hands it base weights drawn by the harness (``bench/weights.py``) and the
RSUs and vehicles moving as the traffic file's ``mobility_seed`` draws
them, and drives it through its first ``checked_rounds`` rounds with the
window's own call, ``sim.run(1)``. The first of these compiles the round
program. What each of those rounds was fed (the staged batches, masks and
channel rates), the bandit statistics it started from and returned, and its
energy are copied to the host; for the first ``trained_rounds`` also the
adapter draws and the merged updates. The checked rounds reach past
exploration: from the sixth on, a vehicle that has been in coverage every
round has tried every arm and exploits.

The window calls ``sim.run(1)`` until ``--seconds`` have passed and ends
when the round running at that moment has finished, so ``round_s`` is the
window over whole rounds; each round ends when its record reaches the
host. A round's work follows the vehicles in coverage, so every seed
gets the same motion: the seed draws the weights, the data, the devices
and the channels, and the window's rounds compute the same lanes whatever
it is.

After the window the device memory peak is read, the simulator is freed,
and the plain reference (``bench/reference/fleet.py``) checks each
recorded round from the state it started in, the harness's weights and
the recorded feed.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import weights
from bench.harness import BenchError, Outcome, Run, peak_bytes


def model_config(cfg_file: Dict[str, Any]):
    from repro.config import get_arch
    return get_arch(cfg_file["arch"]).with_overrides(**cfg_file["model"])


def sim_config(run: Run, mcfg):
    """The simulator configuration the traffic file describes."""
    from repro.config import LoRAConfig
    from repro.sim.simulator import SimConfig
    tr = run.traffic
    lora = LoRAConfig(**{k: (tuple(v) if isinstance(v, list) else v)
                         for k, v in tr["lora"].items()})
    return SimConfig(train_arch=mcfg, seed=run.seed, lora=lora,
                     engine=tr["engine"], **tr["sim"])


def fixed_mobility(sim, seed: int) -> None:
    """Put in the simulator's place RSUs and vehicles that move as the
    simulator would draw them from ``seed``, before its first round."""
    from repro.sim.mobility_model import MobilityModel
    cfg = sim.cfg
    ms = dataclasses.replace(cfg.mobility_sim, num_vehicles=cfg.num_vehicles,
                             seed=seed)
    rsus = MobilityModel.place_rsus(
        cfg.num_tasks, ms.area, ms.coverage_radius, seed=seed,
        layout=ms.rsu_layout, num_per_task=cfg.rsu_tier.num_rsus_per_task)
    sim.rsu_groups = [[r for r in rsus if r.task_id == t]
                      for t in range(cfg.num_tasks)]
    sim.rsus = [g[0] for g in sim.rsu_groups]
    sim.mobility = MobilityModel(ms, rsus)


def _host(tree):
    import jax
    return jax.tree_util.tree_map(np.asarray, jax.device_get(tree))


def _wrap_spans(run: Run, fused, staged: List[np.ndarray]) -> None:
    """Host spans around the engine's host stages, so that the trace puts
    each idle gap of the device down to one of them; ``staged`` receives
    each round's per-task sums of the local steps staged."""
    for name in ("_stage_round", "_place_x", "_jit_round", "_record",
                 "_sync_sim"):
        fn = getattr(fused, name)

        def wrapped(*a, _fn=fn, _name=name.strip("_"), **kw):
            with run.spans.span(_name):
                out = _fn(*a, **kw)
            if _name == "stage_round":
                staged.append(np.asarray(out[0]["counts"]).sum(axis=1))
            return out
        setattr(fused, name, wrapped)


def round_work(sim, staged_counts: np.ndarray) -> Dict[str, Any]:
    """The last round's work, as ``bench/work/fleet_round.py`` counts it."""
    rec = sim.history[-1]
    active = np.asarray([t["active"] for t in rec["tasks"]], np.float64)
    rank = np.asarray([t["mean_rank"] for t in rec["tasks"]], np.float64)
    n_local = len(sim.local_eval[0]["labels"])
    n_global = [len(b["labels"]) for b in sim.eval_batches]
    return {
        "seq": int(sim.eval_batches[0]["tokens"].shape[-1]),
        "batch": int(sim.cfg.batch_size),
        "trained_steps": int(staged_counts.sum()),
        "trained_rank_steps": float(np.sum(staged_counts * rank)),
        "local_eval_seqs": int(n_local * active.sum()),
        "local_eval_rank_seqs": float(n_local * np.sum(active * rank)),
        "global_eval_seqs": int(sum(n for n, a in zip(n_global, active)
                                    if a > 0)),
        "max_rank": int(sim.cfg.lora.max_rank),
    }


class Recorder:
    """Wraps the engine's jitted round and keeps, for the first ``n``
    rounds, the round's feed, the state it started in and what it returned
    on the host; the merged updates and the adapter draws only for the
    first ``n_train`` rounds, whose training the reference replays."""

    def __init__(self, fused, n: int, n_train: int):
        self.fused = fused
        self.n, self.n_train = n, n_train
        self.rounds: List[Dict[str, Any]] = []
        self.inner = fused._jit_round
        fused._jit_round = self

    @staticmethod
    def _ucb(states):
        return [{k: np.asarray(v) for k, v in s._asdict().items()}
                for s in _host(states)]

    def __call__(self, carry, x, data):
        k = len(self.rounds)
        if k >= self.n:
            return self.inner(carry, x, data)
        trained = k < self.n_train
        has_in = [bool(h) for h in np.asarray(carry["has_merged"])]
        ucb_in = self._ucb(carry["ucb"])
        out_carry, rec = self.inner(carry, x, data)
        self.rounds.append({
            "x": _host({key: x[key] for key in (
                "active", "departing", "counts", "tokens", "labels",
                "rate_down", "rate_up")}),
            "trained": trained,
            "has_merged_in": has_in,
            "ucb_in": ucb_in,
            "ucb_out": self._ucb(out_carry["ucb"]),
            "fresh": [_host(f) if trained and not h else None
                      for f, h in zip(data["fresh"], has_in)],
            "merged_out": [_host(m) if trained else None
                           for m in out_carry["merged"]],
            "energy": _host(rec["energy"]),
        })
        return out_carry, rec

    def detach(self) -> None:
        self.fused._jit_round = self.inner


def environment(sim) -> Dict[str, Any]:
    """The simulated world the rounds are priced in: device profiles, the
    RSU profile and each vehicle's data size (the aggregation weight)."""
    cfg = sim.cfg
    rsu = sim.rsu_profile
    return {
        "freq": np.asarray([p.freq for p in sim.dev_profiles], np.float64),
        "kappa": np.asarray([p.kappa for p in sim.dev_profiles], np.float64),
        "tx_power": np.asarray([p.tx_power for p in sim.dev_profiles],
                               np.float64),
        "flops_per_sample": np.asarray(
            [p.flops_per_sample for p in sim.dev_profiles], np.float64),
        "rsu_tx_power": float(rsu.tx_power),
        "rsu_agg_flops_per_vehicle": float(rsu.agg_flops_per_vehicle),
        "rsu_freq": float(rsu.freq),
        "rsu_kappa": float(rsu.kappa),
        "ucb_epsilon": float(cfg.ucb.epsilon),
        "data_sizes": np.asarray(
            [[len(sim.client_data[t][v]) for v in range(cfg.num_vehicles)]
             for t in range(cfg.num_tasks)], np.float64),
        # the cost model prices adapters of its own architecture
        "cost_model": {k: getattr(sim.cost_cfg, k) for k in (
            "num_layers", "d_model", "num_heads", "num_kv_heads",
            "head_dim", "d_ff", "vocab_size", "activation",
            "tie_embeddings")},
        "sim": {"local_steps": cfg.local_steps, "batch_size": cfg.batch_size,
                "lr": cfg.lr, "departure_fraction": cfg.departure_fraction,
                "bytes_per_param": cfg.bytes_per_param,
                "mobility_aware": bool(sim.spec.mobility_aware)},
    }


def check_recorded(run: Run, env, recorded) -> Dict[str, float]:
    """The compared numbers of the recorded rounds."""
    from bench.reference import fleet as ref
    return ref.check_rounds(run.seed, run.config["model"],
                            run.traffic["lora"], env, recorded)


def readings(run: Run, env, recorded) -> Dict[str, Any]:
    """Program, control, rounding and fault readings of one run: the
    compared numbers of the program; of the plain reference in its place
    computed in bfloat16 (the control) and at the default matrix-product
    precision (one bfloat16 pass on the TPU, as the program's float32
    products take: how far rounding alone moves each number); and of that
    reference with a fault planted: half of every batch left out (the loss
    the mean over the rest), and rounds that hand back the state they
    started from."""
    import jax.numpy as jnp
    from bench.reference import fleet as ref
    seed, model, lora = run.seed, run.config["model"], run.traffic["lora"]

    def replay(**kw):
        return ref.replay(seed, model, lora, env, recorded, **kw)

    honest = replay()
    against = lambda view: ref.compare(view, honest, recorded)
    return {"program": against(ref.program_view(recorded)),
            "control": against(replay(dt=jnp.bfloat16)),
            "default": against(replay(precision="default")),
            "faults": {"half_batch": against(replay(half_batch=True)),
                       "state_unchanged": against(
                           ref.unchanged(honest, recorded))}}


def run(run: Run, check: Optional[Callable] = None) -> Outcome:
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as T
    from repro.sim.simulator import IoVSimulator

    tr = run.traffic
    mcfg = model_config(run.config)
    scfg = sim_config(run, mcfg)
    if tr["engine"] != "fused":
        raise BenchError(f"fleet_rounds drives engine 'fused', not "
                         f"{tr['engine']!r}")

    with run.spans.span("build"):
        sim = IoVSimulator(scfg)
        fixed_mobility(sim, int(tr["mobility_seed"]))
        shapes = jax.eval_shape(lambda: T.init_params(
            jax.random.PRNGKey(0), mcfg, dtype=jnp.float32))
        base = weights.draw_tree(shapes, run.seed, stream=0)
        # the harness's weights replace the program's own draw before the
        # first round; the engine reads them from here every round
        sim.params = base
        sim.fused._params = base
        del base
    n_check = int(tr["checked_rounds"])
    recorder = Recorder(sim.fused, n_check, int(tr["trained_rounds"]))
    t_build = time.perf_counter() - run.t_start
    t_rounds = []
    with run.spans.span("warmup"):
        for _ in range(n_check):
            t = time.perf_counter()
            sim.run(1)
            t_rounds.append(time.perf_counter() - t)
    recorder.detach()
    staged: List[np.ndarray] = []
    _wrap_spans(run, sim.fused, staged)
    env = environment(sim)
    setup_s = time.perf_counter() - run.t_start
    print(f"setup: {setup_s:.1f} s: start and build {t_build:.1f} s, "
          f"checked rounds {', '.join(f'{t:.1f}' for t in t_rounds)} s; "
          f"round program compiles {len([n for n, _ in run.compile_log.compiles if 'round_step' in n])}"
          f", cache hits {len([n for n in run.compile_log.hits if 'round_step' in n])}",
          file=sys.stderr)

    mark = run.compile_log.mark()
    work: List[Dict[str, Any]] = []

    def window():
        t0 = time.perf_counter()
        while True:
            with run.spans.span("round"):
                sim.run(1)
            work.append(round_work(sim, staged[-1]))
            if time.perf_counter() - t0 >= run.window_seconds:
                break
        return t0, time.perf_counter()

    run.window = run.traced(window)
    t0, t1 = run.window
    in_window = run.compile_log.since(mark)
    run.memory_peak_bytes = peak_bytes(run.devices)
    history = sim.history[n_check:]
    finite = all(np.isfinite(r[k]) for r in history
                 for k in ("accuracy", "energy", "reward"))
    recorded = recorder.rounds

    # free the program's state before the reference runs
    del sim, recorder
    gc.collect()

    from bench.reference import fleet as ref
    ref.use(run.config["reference"])
    exploits = ref.exploit_decisions(recorded)
    t_ref = time.perf_counter()
    numbers = (check or check_recorded)(run, env, recorded)
    rounds = len(work)
    print(f"fleet: {rounds} rounds in {t1 - t0:.3f} s; compiles in the "
          f"window {len(in_window)}; checked rounds {len(recorded)} "
          f"({exploits} choices by exploitation); reference "
          f"{time.perf_counter() - t_ref:.1f} s", file=sys.stderr)
    return Outcome(
        metrics={"setup_s": setup_s, "round_s": (t1 - t0) / rounds},
        attempted=rounds, failed=0 if finite else rounds,
        checks=[run.check(k, v) for k, v in numbers.items()],
        counters={"window_rounds": work,
                  "compiles_in_window": len(in_window),
                  "exploit_decisions": exploits})
