"""Mean host time of the simulator's staging of a round (mobility tick,
channel draws, data batches, fresh adapters): the program's host spans
``repro.stage`` that start in the traced window, in ms. Moves
``round_s``."""
from bench import program_trace

SPAN = "repro.stage"


def read(ro):
    spans = program_trace.window_spans(ro, SPAN)
    if spans is None:
        return None
    if not spans:
        return 0.0
    return sum(s.dur for s in spans) / len(spans) / 1e6
