"""Engine-parity sweep, hetlora half: serial vs batched_check on every
preset × tier on/off (the merged-rule half and the fast subset are in
test_hierarchy_parity.py; see its docstring for the contract)."""
import pytest

from hierarchy_cells import TIER_OFF, TIER_ON, _run_cell
from repro.sim import scenarios


@pytest.mark.slow
@pytest.mark.parametrize("name", scenarios.list_scenarios())
@pytest.mark.parametrize("method", ["hetlora"])
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
