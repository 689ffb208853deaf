"""Device-sharded fleet engine: weak/strong-scaling sweep (ISSUE 5).

Measures round throughput of ``engine="fused_sharded"`` as a function of
device count on a forced multi-device CPU host
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``). Because the
device count must be fixed BEFORE jax initializes, every cell runs in its
own subprocess (``--worker``); the parent sweeps topologies and writes
``benchmarks/results/BENCH_sharded_fleet.json``.

Two sweeps:
  weak    — fleet size grows with the device count (fixed per-device
            fleet slice): vehicles = per_device × devices. The headline
            "round throughput scaling with device count" claim: trained
            vehicle-lanes per second should grow with devices while
            s/round stays near-flat.
  strong  — fixed total fleet, more devices: s/round should fall (until
            the per-device slice is too thin to amortize the collective).

Every worker also counts XLA compilations of the round body — the
acceptance claim is exactly ONE compile per device topology regardless
of churn (the rank-padding + fixed-point-sharding invariants).

Caveat for absolute numbers: forced host devices SHARE the machine's
physical cores. On the 2-core CI container, scaling beyond 2 devices
measures partitioning overhead, not parallel speedup — the committed
JSON records the host's cpu count so readers can interpret the curve.

Usage:
    PYTHONPATH=src python -m benchmarks.sharded_fleet [--smoke]
        [--devices 1,2,4,8] [--per-device 3] [--arch fleet|reduced]

Writes benchmarks/results/BENCH_sharded_fleet.json (``--smoke``:
BENCH_sharded_fleet_smoke.json, archived by CI's sharded-smoke job).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List

SMOKE_RANKS = (4, 8)
FULL_RANKS = (2, 4, 8, 16)


def run_worker(devices: int, shards: int, vehicles: int, tasks: int,
               settle: int, measure: int, arch: str, ranks, seed: int,
               coverage: float) -> Dict[str, Any]:
    """One (topology, fleet) cell in a fresh subprocess with the forced
    device count baked into XLA_FLAGS before jax init."""
    env = dict(os.environ)
    # replace only the device-count flag; any other XLA_FLAGS the caller
    # exported keep applying to the workers
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    with tempfile.NamedTemporaryFile("r", suffix=".json") as out:
        cmd = [sys.executable, "-m", "benchmarks.sharded_fleet", "--worker",
               "--out", out.name, "--devices-forced", str(devices),
               "--shards", str(shards), "--vehicles", str(vehicles),
               "--tasks", str(tasks), "--settle", str(settle),
               "--measure", str(measure), "--arch", arch,
               "--ranks", ",".join(str(r) for r in ranks),
               "--seed", str(seed), "--coverage", str(coverage)]
        subprocess.run(cmd, env=env, check=True)
        return json.load(out)


def worker_main(a) -> None:
    import logging

    import jax

    from repro.config import (EnergyAllocConfig, LoRAConfig, ShardSpec)
    from repro.configs import vit_base_paper
    from repro.sim.mobility_model import MobilitySimConfig
    from repro.sim.simulator import IoVSimulator, SimConfig

    assert jax.local_device_count() == a.devices_forced, (
        jax.local_device_count(), a.devices_forced)
    ranks = tuple(int(r) for r in a.ranks.split(","))
    if a.arch == "fleet":
        train_arch, batch_size = vit_base_paper.fleet(), 4
    else:
        train_arch, batch_size = None, 10

    compiles = []

    class Counter(logging.Handler):
        def emit(self, record):
            msg = record.getMessage()
            # per-round driving compiles jit(_round_step); run_scanned
            # compiles the jit(run) scan wrapper around the same body —
            # either way, ONE program per topology (and per scan horizon)
            if ("Finished XLA compilation of jit(_round_step)" in msg
                    or "Finished XLA compilation of jit(run)" in msg):
                compiles.append(1)

    counter = Counter()
    logger = logging.getLogger("jax._src.dispatch")
    logger.addHandler(counter)
    logger.setLevel(logging.DEBUG)

    engine = "fused_sharded" if a.shards > 1 else "fused"
    sim = IoVSimulator(SimConfig(
        method="ours", rounds=a.settle + a.measure, num_vehicles=a.vehicles,
        num_tasks=a.tasks, local_steps=3, seed=a.seed, engine=engine,
        shard=ShardSpec(num_shards=a.shards) if a.shards > 1 else ShardSpec(),
        train_arch=train_arch, batch_size=batch_size,
        # budget scaled with the fleet so the dual stays healthy and rank
        # selection remains heterogeneous (same story as fused_round)
        energy=EnergyAllocConfig(e_total=125.0 * a.vehicles * a.tasks),
        mobility_sim=MobilitySimConfig(coverage_radius=a.coverage),
        lora=LoRAConfig(rank=8, max_rank=32, candidate_ranks=ranks)))

    with jax.log_compiles():
        sim.run_scanned(a.settle)          # compile + settle
        settle_compiles = len(compiles)
        t0 = time.time()
        sim.run_scanned(a.measure)
        elapsed = time.time() - t0
    logger.removeHandler(counter)

    trained = sum(sum(t["active"] for t in r["tasks"])
                  for r in sim.history[a.settle:])
    out = {
        "devices": a.devices_forced,
        "shards": a.shards,
        "vehicles": a.vehicles,
        "tasks": a.tasks,
        "padded_fleet": int(sim.fused.Vp),
        "rounds": a.measure,
        "round_s": elapsed / a.measure,
        "vehicle_trainings": int(trained),
        "round_vehicles_per_s": trained / max(elapsed, 1e-9),
        # the scan program (run_scanned) wraps the same round body; one
        # compile per topology total, none during the measured window
        "round_program_compiles_settle": settle_compiles,
        "round_program_compiles_measure": len(compiles) - settle_compiles,
    }
    with open(a.out, "w") as f:
        json.dump(out, f)


def main(*, smoke: bool, devices: List[int], per_device: int, arch: str,
         coverage: float) -> Dict[str, Any]:
    from benchmarks.harness import emit_csv, save_bench_json

    devices = sorted(set(devices))
    if smoke:
        devices = [d for d in devices if d <= 2] or [1, 2]
        per_device, tasks, settle, measure, ranks = 2, 1, 2, 2, SMOKE_RANKS
        strong_fleet = 4
    else:
        tasks, settle, measure, ranks = 2, 2, 2, FULL_RANKS
        strong_fleet = per_device * max(devices)

    weak: List[Dict[str, Any]] = []
    strong: List[Dict[str, Any]] = []
    for n in devices:
        r = run_worker(n, n, per_device * n, tasks, settle, measure, arch,
                       ranks, seed=0, coverage=coverage)
        r["sweep"] = "weak"
        weak.append(r)
        print(f"# weak  n={n}: {r['round_s']:.3f} s/round, "
              f"{r['round_vehicles_per_s']:.2f} veh/s, compiles "
              f"{r['round_program_compiles_settle']}"
              f"/{r['round_program_compiles_measure']}")
    for n in devices:
        r = run_worker(n, n, strong_fleet, tasks, settle, measure, arch,
                       ranks, seed=0, coverage=coverage)
        r["sweep"] = "strong"
        strong.append(r)
        print(f"# strong n={n}: {r['round_s']:.3f} s/round, "
              f"{r['round_vehicles_per_s']:.2f} veh/s, compiles "
              f"{r['round_program_compiles_settle']}"
              f"/{r['round_program_compiles_measure']}")

    base = weak[0]   # devices sorted above: the smallest topology
    throughput_scaling = {
        str(r["devices"]): round(
            r["round_vehicles_per_s"]
            / max(base["round_vehicles_per_s"], 1e-9), 3) for r in weak}
    compiles_ok = all(r["round_program_compiles_settle"] == 1
                      and r["round_program_compiles_measure"] == 0
                      for r in weak + strong)

    rows = [dict(r, name=f"{r['sweep']}_n{r['devices']}")
            for r in weak + strong]
    emit_csv(f"sharded_fleet [{arch} arch] (weak/strong scaling over "
             "forced host devices)",
             rows, ["devices", "vehicles", "round_s",
                    "round_vehicles_per_s", "round_program_compiles_measure"])
    out = {
        "weak_scaling": weak,
        "strong_scaling": strong,
        "weak_throughput_vs_min_devices": throughput_scaling,
        "weak_baseline_devices": devices[0],
        "round_program_compiled_once_per_topology": compiles_ok,
        "config": {"arch": arch, "per_device_vehicles": per_device,
                   "tasks": tasks, "settle_rounds": settle,
                   "measure_rounds": measure, "devices": devices,
                   "candidate_ranks": list(ranks),
                   "coverage_radius": coverage, "smoke": smoke,
                   "note": ("forced host devices share physical cores; "
                            "interpret the curve against host.cpus")},
    }
    name = "sharded_fleet_smoke" if smoke else "sharded_fleet"
    path = save_bench_json(name, out)
    print(f"# weak-scaling throughput vs {devices[0]} device(s): "
          f"{throughput_scaling}")
    print(f"# round body compiled once per topology: {compiles_ok}")
    print(f"# wrote {path}")
    return out


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="CI scale: ≤2 devices, tiny fleet")
    p.add_argument("--devices", default="1,2,4,8",
                   help="comma-separated forced device counts")
    p.add_argument("--per-device", type=int, default=3,
                   help="weak-scaling vehicles per device")
    p.add_argument("--arch", choices=("fleet", "reduced"), default="fleet")
    p.add_argument("--coverage", type=float, default=2600.0)
    # worker-only flags (one cell inside the forced-device subprocess)
    p.add_argument("--worker", action="store_true")
    p.add_argument("--out")
    p.add_argument("--devices-forced", type=int, default=1)
    p.add_argument("--shards", type=int, default=1)
    p.add_argument("--vehicles", type=int, default=4)
    p.add_argument("--tasks", type=int, default=1)
    p.add_argument("--settle", type=int, default=2)
    p.add_argument("--measure", type=int, default=2)
    p.add_argument("--ranks", default="4,8")
    p.add_argument("--seed", type=int, default=0)
    a = p.parse_args()
    if a.worker:
        worker_main(a)
    else:
        main(smoke=a.smoke,
             devices=[int(d) for d in a.devices.split(",")],
             per_device=a.per_device, arch=a.arch, coverage=a.coverage)
