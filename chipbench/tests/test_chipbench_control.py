"""The control — the reference computed with three-pass bfloat16 dot
products (and bfloat16 usage profiles), put in the program's place — comes
out not correct under each configuration's limits, where the program
comes out correct."""
import pytest

from chipbench import calibrate
from chipbench.harness import check
from chipbench.tests._rehearse import rehearse

pytestmark = pytest.mark.filterwarnings("ignore::RuntimeWarning")


@pytest.mark.parametrize("name", ["mag-peak.warm", "chipseq-temporal.warm"])
def test_control_is_not_correct(monkeypatch, name):
    out = rehearse(monkeypatch, name, after=calibrate.control)
    assert out["correct"], out["compared"]
    limits = {k: v["limit"] for k, v in out["compared"].items()}
    ok, rows = check.verdict(out["after"], limits)
    assert not ok, rows
