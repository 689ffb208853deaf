"""Engine-parity sweep across scenario presets × aggregation rules ×
multi-RSU tiers (ISSUE 4 satellite).

Every cell runs the serial reference against an engine-under-test on the
SAME preset/seed and asserts the histories replay each other: selected
ranks, comm volume, active/departing/handoff counts, §III-C energy, global
accuracy and budgets — plus the engine's serial-replay deviation
(``engine_check_dev``) where the *_check engine exists:

  merged ("ours")  — serial vs fused_check (the fused engine covers the
                     ours family; fused_check replays the serial
                     LocalTrainer on the identical staged batches)
  hetlora          — serial vs batched_check (the fused engine does not
                     cover factor-averaging baselines; the batched engine
                     is the vectorized path for them)

Fast tier: two representative cells (kept small — the CI fast tier has a
2-minute budget). Full grid (every preset × both rules × tier on/off):
@slow, the merged rule here and hetlora in test_hierarchy_parity_hetlora.py
(cells shared through tests/hierarchy_cells.py).
"""
import logging

import jax
import pytest

from hierarchy_cells import (TIER_OFF, TIER_ON, _assert_parity, _run_cell,
                             _sim)
from repro.sim import scenarios

# ---------------------------------------------------------------------------
# Fast subset
# ---------------------------------------------------------------------------

def test_parity_dense_rsu_merged_fast():
    """Native multi-RSU preset, merged rule, serial vs fused."""
    _run_cell("dense-rsu", "ours", TIER_ON)


def test_parity_urban_grid_hetlora_tier_fast():
    """Tier override on a 1-RSU preset, hetlora rule, serial vs batched."""
    _run_cell("urban-grid", "hetlora", TIER_ON)


# ---------------------------------------------------------------------------
# Full grid (slow): every preset × merged rule × tier on/off (the hetlora
# half lives in test_hierarchy_parity_hetlora.py)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("name", scenarios.list_scenarios())
@pytest.mark.parametrize("method", ["ours"])
@pytest.mark.parametrize("tier", [TIER_OFF, TIER_ON],
                         ids=["tier-off", "tier-on"])
def test_parity_grid(name, method, tier):
    hs = _run_cell(name, method, tier, rounds=3)
    if not tier.trivial:
        # the sweep is only meaningful if the hierarchy engaged somewhere:
        # at minimum the association machinery ran every round (active
        # counts come from the group view)
        assert all(isinstance(t["handoffs"], int)
                   for r in hs for t in r["tasks"])


@pytest.mark.slow
def test_parity_handoff_storm_scanned_after_sync():
    """run_scanned on a native multi-RSU preset replays per-round fused
    execution (per-round fresh staging keeps pre-sync rounds exact)."""
    R = 4
    a = _sim("handoff-storm", "fused", "ours",
             TIER_ON, R)
    b = _sim("handoff-storm", "fused", "ours",
             TIER_ON, R)
    ha = a.run()
    hb = b.run_scanned(R)
    _assert_parity(ha, hb)


@pytest.mark.slow
@pytest.mark.parametrize("name", ["dense-rsu", "handoff-storm"])
def test_fused_round_compiles_once_on_hierarchy_presets(name):
    """Recompile guard extended to the multi-RSU presets: the segmented
    partial aggregation, staleness sync and handoff accounting must stay
    inside the ONE jit round program."""
    compiles = []

    class Capture(logging.Handler):
        def emit(self, record):
            if ("Finished XLA compilation of jit(_round_step)"
                    in record.getMessage()):
                compiles.append(record.getMessage())

    handler = Capture()
    logger = logging.getLogger("jax._src.dispatch")
    logger.addHandler(handler)
    old_level = logger.level
    logger.setLevel(logging.DEBUG)
    try:
        with jax.log_compiles():
            sim = _sim(name, "fused", "ours", TIER_ON, 4, seed=1)
            sim.run()
    finally:
        logger.removeHandler(handler)
        logger.setLevel(old_level)
    assert len(compiles) == 1, compiles
    # the guard is vacuous unless the hierarchy actually churned
    total_handoffs = sum(t["handoffs"] for r in sim.history
                         for t in r["tasks"])
    actives = {tuple(t["active"] for t in r["tasks"]) for r in sim.history}
    assert total_handoffs > 0 or len(actives) > 1
