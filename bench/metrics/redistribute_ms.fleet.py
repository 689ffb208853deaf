"""Device self time of the redistribution of each task's merged adapter
(the seeded truncated SVD, or the fresh draws before a merge exists), the
operations under the round program's scope ``round.redistribute``, per run
of the round program in the traced window, in ms. Moves ``round_s``."""
from bench import program_trace

SCOPES = ("round.redistribute",)


def read(ro):
    return program_trace.per_run_ms(ro, SCOPES)
