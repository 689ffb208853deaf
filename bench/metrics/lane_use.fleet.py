"""Share of the lane-steps the fleet megastep computes that train a
vehicle in coverage: the local steps staged (``staged_steps``, Σ counts)
over the lanes times the step budget (``lane_steps``, T · Vp ·
local_steps), summed over the program's ``repro.stage`` spans that start
in the traced window, in %. Moves ``round_s``."""
from bench import program_trace

SPAN = "repro.stage"


def read(ro):
    spans = program_trace.window_spans(ro, SPAN)
    if spans is None:
        return None
    lanes = sum(s.args.get("lane_steps", 0) for s in spans)
    if not lanes:
        return None if spans else 0.0
    return 100.0 * sum(s.args.get("staged_steps", 0) for s in spans) / lanes
