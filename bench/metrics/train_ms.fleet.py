"""Device self time of the fleet's local training, the operations under
the round program's scope ``round.train``, per run of the round program in
the traced window, in ms. Moves ``round_s``. Also prints the round
program's scope coverage and per-stage table on standard error."""
from bench import program_trace

SCOPES = ("round.train",)


def read(ro):
    program_trace.report(ro)
    return program_trace.per_run_ms(ro, SCOPES)
