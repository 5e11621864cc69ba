"""The two mag history-depth cells rehearsed end to end on the CPU at a
tiny scale, and one planted fault in each that makes ``correct`` false.

mag-peak.deep keeps its 16 earlier runs at a tenth of Table I (pools of
about 850-1450 rows, capacity 1024-2048); mag-peak.cold starts every pool
empty, so its first decisions are the user's presets."""
import pytest

from chipbench.harness import spec
from chipbench.tests import _rehearse
from chipbench.tests._faults import FAULTS
from chipbench.tests._rehearse import rehearse

pytestmark = pytest.mark.filterwarnings("ignore::RuntimeWarning")

CELLS = {"mag-peak.deep": {"scale": 0.1, "run_scale": 0.3, "steps": 100},
         "mag-peak.cold": {"scale": 1.0, "run_scale": 0.3, "steps": 100}}
# the fault each cell is the one to catch: a pool whose observe leaves its
# state stale (deep: every completion refits a 16-run pool), and the
# preset-or-model gate broken (cold: young pools decide from presets)
FAULT = {"mag-peak.deep": "stale_state", "mag-peak.cold": "all_presets"}


@pytest.fixture
def cells(monkeypatch):
    for name, c in CELLS.items():
        monkeypatch.setitem(_rehearse.CELLS, name, c)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_cell_rehearsal(monkeypatch, cells, name):
    out = rehearse(monkeypatch, name)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    m = out["metrics"]
    assert set(m) == {"tasks_per_s", "wave_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in m.values())
    assert list(out)[-1] == "compared"


def test_deep_config_is_mag_peak_with_its_history():
    """The deep deployment runs mag-peak's program unchanged; what sets it
    apart is the history it keeps, served by its traffic's prior runs."""
    deep, base = (spec.load_cell(n) for n in ("mag-peak.deep",
                                               "mag-peak.warm"))
    for key in ("workflow", "method", "cluster", "service", "reduced"):
        assert deep.config[key] == base.config[key], key
    assert deep.config["history"] == {"earlier_runs": 16, "window": None}
    assert deep.traffic["prior_runs"] == deep.config["history"][
        "earlier_runs"]


@pytest.mark.parametrize("name", sorted(CELLS))
def test_fault_is_caught(monkeypatch, cells, name):
    FAULTS[FAULT[name]](monkeypatch)
    out = rehearse(monkeypatch, name)
    assert not out["correct"], out["compared"]
