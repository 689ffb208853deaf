"""Share of the full fleet width's lane-steps that the compacted megastep
computes: the lanes in coverage rounded up to whole chunks, times the
step budget (``computed_steps``), over the lanes times the step budget
(``lane_steps``, T · Vp · local_steps), summed over the program's
``repro.stage`` spans that start in the traced window, in %. A span with
no ``computed_steps`` comes from a program that computes every lane and
counts its ``lane_steps``. Lower is better. Moves ``round_s``."""
from bench import program_trace

SPAN = "repro.stage"


def read(ro):
    spans = program_trace.window_spans(ro, SPAN)
    if spans is None:
        return None
    lanes = sum(s.args.get("lane_steps", 0) for s in spans)
    if not lanes:
        return None if spans else 0.0
    computed = sum(s.args.get("computed_steps", s.args.get("lane_steps", 0))
                   for s in spans)
    return 100.0 * computed / lanes
