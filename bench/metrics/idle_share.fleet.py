"""Share of the traced window in which no operation ran on the device
(1 − union of busy intervals / window), in a fleet cell. Moves
``round_s``."""


def read(ro):
    idle = ro.trace.idle_share()
    return None if idle is None else 100.0 * idle
