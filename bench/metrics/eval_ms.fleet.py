"""Device self time of the fleet round's evaluations, the operations under
the round program's scopes ``round.local_eval`` (every vehicle's adapter on
its held-out batch) and ``round.global_eval`` (each task's merged adapter,
with its max-rank SVD), per run of the round program in the traced window,
in ms. Moves ``round_s``."""
from bench import program_trace

SCOPES = ("round.local_eval", "round.global_eval")


def read(ro):
    return program_trace.per_run_ms(ro, SCOPES)
