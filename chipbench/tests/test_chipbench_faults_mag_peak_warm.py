"""Faults planted under a rehearsal of mag-peak.warm: each makes correct false,
where the same rehearsal without a fault is correct (test_chipbench_cells).
(The cell runs on one chip, so it has no exchange between chips to lose.)"""
import pytest

from chipbench.tests._faults import FAULTS
from chipbench.tests._rehearse import rehearse

pytestmark = pytest.mark.filterwarnings("ignore::RuntimeWarning")


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_caught(monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    out = rehearse(monkeypatch, "mag-peak.warm")
    assert not out["correct"], out["compared"]
