"""End-to-end driver: multi-task federated fine-tuning over the IoV
simulator — the paper's full system (UCB-DUAL rank scheduling, Algorithm 1
energy budgeting, mobility fault tolerance, truncated-SVD distribution).

    PYTHONPATH=src python examples/multi_task_iov.py \
        [--method ours|homolora|hetlora|fedra] [--rounds 40] [--vehicles 12]

Scenario presets (repro.sim.scenarios) swap the default synthetic map for a
named mobility regime — trace-driven fleets, RSU layouts, outages:

    PYTHONPATH=src python examples/multi_task_iov.py --scenario rush-hour
    PYTHONPATH=src python examples/multi_task_iov.py --list-scenarios

Round engines (README "Engines"): ``--engine`` pins one explicitly —
including ``fused_sharded``, the device-sharded fleet (force host devices
with XLA_FLAGS=--xla_force_host_platform_device_count=N on CPU). Without
the flag the engine resolves from $REPRO_SIM_ENGINE, then "batched":

    PYTHONPATH=src python examples/multi_task_iov.py --engine fused
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python examples/multi_task_iov.py \
        --engine fused_sharded

Resumable horizons (README "Resuming runs"): ``--checkpoint-every N``
writes an atomic full-state checkpoint every N rounds into
``--checkpoint-dir``; ``--resume`` restores the latest one and finishes
the remaining rounds bit-identically to an uninterrupted run:

    PYTHONPATH=src python examples/multi_task_iov.py --rounds 40 \
        --checkpoint-every 10 --checkpoint-dir /tmp/iov-ckpt
    PYTHONPATH=src python examples/multi_task_iov.py --rounds 40 \
        --checkpoint-every 10 --checkpoint-dir /tmp/iov-ckpt --resume
"""
import argparse

from repro.config import CheckpointSpec, EnergyAllocConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.sim import scenarios
from repro.sim.simulator import IoVSimulator, SimConfig

ENGINES = ("serial", "batched", "batched_check", "fused", "fused_check",
           "fused_sharded")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--method", default="ours")
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--vehicles", type=int, default=12)
    ap.add_argument("--tasks", type=int, default=3)
    ap.add_argument("--budget", type=float, default=900.0,
                    help="global per-round energy budget E_total (J)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", default=None, choices=ENGINES,
                    help="round engine; omitted = $REPRO_SIM_ENGINE, then "
                         "'batched' (an explicit flag beats the env var)")
    ap.add_argument("--scenario", default=None,
                    help="named preset from repro.sim.scenarios "
                         "(overrides fleet/area/budget defaults)")
    ap.add_argument("--list-scenarios", action="store_true")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="write a full-state checkpoint every N rounds "
                         "(0 = off; needs --checkpoint-dir)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="directory for round_*.npz checkpoints")
    ap.add_argument("--keep-last", type=int, default=0,
                    help="prune to the newest K checkpoints (0 = keep all)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest checkpoint from "
                         "--checkpoint-dir, then finish the remaining "
                         "rounds (bit-identical to an uninterrupted run)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.list_scenarios:
        for name in scenarios.list_scenarios():
            print(f"  {name:18s} {scenarios.get_scenario(name).description}")
        return

    ckpt = CheckpointSpec(interval=args.checkpoint_every,
                          dir=args.checkpoint_dir,
                          keep_last=args.keep_last)
    if args.resume and not args.checkpoint_dir:
        ap.error("--resume needs --checkpoint-dir")

    if args.scenario:
        # flags left at their defaults defer to the preset; explicitly
        # given ones override it (never silently ignored)
        overrides = {"checkpoint": ckpt}
        if args.vehicles != ap.get_default("vehicles"):
            overrides["num_vehicles"] = args.vehicles
        if args.tasks != ap.get_default("tasks"):
            overrides["num_tasks"] = args.tasks
        if args.budget != ap.get_default("budget"):
            overrides["energy"] = EnergyAllocConfig(e_total=args.budget,
                                                    warmup_q=4)
        # engine=None stays None in the config, so the simulator still
        # resolves $REPRO_SIM_ENGINE per run (flag > env var > batched)
        if args.engine is not None:
            overrides["engine"] = args.engine
        cfg = scenarios.build_config(args.scenario, method=args.method,
                                     rounds=args.rounds, seed=args.seed,
                                     **overrides)
        print(f"scenario {args.scenario}: {cfg.num_vehicles} vehicles, "
              f"{cfg.num_tasks} tasks, {cfg.rounds} rounds, "
              f"E_total={cfg.energy.e_total:g}J")
    else:
        cfg = SimConfig(
            method=args.method, rounds=args.rounds,
            num_vehicles=args.vehicles, num_tasks=args.tasks,
            seed=args.seed, engine=args.engine, checkpoint=ckpt,
            energy=EnergyAllocConfig(e_total=args.budget, warmup_q=4))
    sim = IoVSimulator(cfg)
    print(f"engine: {sim.engine}")
    done = 0
    if args.resume:
        from repro.checkpoint import latest_checkpoint, restore_checkpoint
        if latest_checkpoint(args.checkpoint_dir) is not None:
            done = restore_checkpoint(sim, args.checkpoint_dir)
            print(f"resumed from round {done} "
                  f"({args.checkpoint_dir})")
        else:
            print(f"no checkpoint in {args.checkpoint_dir}; "
                  "starting from round 0")
    if done < args.rounds:
        sim.run(args.rounds - done, log_every=2)

    s = sim.summary()
    print("\n== summary ==")
    for k, v in s.items():
        print(f"  {k}: {v}")
    last = sim.history[-1]
    print("  final per-task:",
          [(t['task'], round(t['accuracy'], 3), f"rank {t['mean_rank']:.1f}")
           for t in last["tasks"]])


if __name__ == "__main__":
    main()
