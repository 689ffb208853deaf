"""Model FLOP/s utilization of the fleet round: the operations the rounds
of the traced window need (``bench/work/fleet_round.py``: training of the
vehicles in coverage over the steps they train, local and global evals;
no recomputation, no discarded lanes), over the window on the host clock
and the chip's peak. Moves ``round_s``."""
from bench.work import fleet_round


def read(ro):
    rounds = ro.counters.get("window_rounds") or []
    t0, t1 = ro.run.window
    if not rounds or t1 <= t0:
        return None
    model = ro.run.config["model"]
    flops = sum(fleet_round.round_flops(model, **r) for r in rounds)
    return 100.0 * flops / (t1 - t0) / ro.peaks["bf16_flops_per_s"]
