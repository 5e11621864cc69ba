"""Each cell rehearsed end to end on the CPU at a tiny scale."""
import pytest

from chipbench.tests._rehearse import rehearse

pytestmark = pytest.mark.filterwarnings("ignore::RuntimeWarning")


@pytest.mark.parametrize("name", ["mag-peak.warm", "chipseq-temporal.warm"])
def test_cell_rehearsal(monkeypatch, name):
    out = rehearse(monkeypatch, name)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    m = out["metrics"]
    assert set(m) == {"tasks_per_s", "wave_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in m.values())
    assert out["device"]["platform"] == "cpu"
    # the comparison is the last key of the result line
    assert list(out)[-1] == "compared"
