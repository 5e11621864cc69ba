"""End-to-end behaviour tests for the paper's system (paper §III claims)."""
import numpy as np
import pytest

# full workflow replays: minutes of wall time — excluded from the fast loop
# (`pytest -m "not slow"`); the fused decision path is still covered there
# by test_fused_predictor.py and the benchmark smoke test.
pytestmark = pytest.mark.slow

from repro import obs
from repro.baselines import make_method
from repro.baselines.sizey_method import SizeyMethod
from repro.core import SizeyConfig
from repro.workflow import generate_workflow, simulate


def _train_times_s(trace, method) -> list[float]:
    """Wall seconds of each fused fit or refresh, through the device."""
    with obs.tracing() as col:
        simulate(trace, method, ttf=1.0)
    return [s[2] * 1e-9 for s in col.spans if s[0] in ("observe", "refresh")]


@pytest.fixture(scope="module")
def mag_results():
    """Run Sizey + the two strongest baselines on a reduced mag trace."""
    trace = generate_workflow("mag", scale=0.15)
    out = {}
    for name, method in [
        ("sizey", SizeyMethod(SizeyConfig(), ttf=1.0)),
        ("witt_wastage", make_method("witt_wastage")),
        ("witt_lr", make_method("witt_lr")),
        ("workflow_presets", make_method("workflow_presets")),
    ]:
        out[name] = simulate(trace, method, ttf=1.0)
    return out


def test_sizey_beats_baselines(mag_results):
    """Paper Fig. 8a / Table II: Sizey has the lowest wastage over time."""
    sizey = mag_results["sizey"].wastage_gbh
    assert sizey < mag_results["witt_wastage"].wastage_gbh
    assert sizey < mag_results["witt_lr"].wastage_gbh
    assert sizey < mag_results["workflow_presets"].wastage_gbh


def test_presets_waste_an_order_of_magnitude_more(mag_results):
    """Paper Fig. 8a: presets waste ~an order of magnitude more than Sizey."""
    ratio = (mag_results["workflow_presets"].wastage_gbh
             / mag_results["sizey"].wastage_gbh)
    assert ratio > 4.0


def test_presets_have_zero_failures(mag_results):
    assert mag_results["workflow_presets"].n_failures == 0


def test_sizey_runtime_overhead_is_bounded(mag_results):
    """Paper §III-E: wastage reduction costs some extra runtime, but little."""
    t_sizey = mag_results["sizey"].total_runtime_h
    t_presets = mag_results["workflow_presets"].total_runtime_h
    assert t_sizey < 1.35 * t_presets


def test_online_error_decreases():
    """Paper Fig. 12: the RAW relative prediction error (no offsetting,
    straight from the prequential log — exactly what Fig. 12 plots)
    shrinks with the number of executions of the clustered prokka task."""
    trace = generate_workflow("mag", scale=0.3)
    method = SizeyMethod(SizeyConfig(), ttf=1.0)
    simulate(trace, method, ttf=1.0)
    pool = method.predictor.db.pool("prokka", "epyc128")
    n = pool.log_count
    assert n > 40
    err = np.abs(pool.log_agg[:n] - pool.log_actual[:n]) \
        / np.maximum(pool.log_actual[:n], 1e-9)
    early = float(np.median(err[: n // 3]))
    late = float(np.median(err[-n // 3:]))
    assert late < early  # online learning reduces error over time


def test_incremental_mode_is_much_faster():
    """Paper Fig. 9 / §III-D: incremental updates cut training time ~98%."""
    trace = generate_workflow("iwd", scale=0.2)
    t_full = np.median(_train_times_s(
        trace, SizeyMethod(SizeyConfig(incremental=False), ttf=1.0)))
    t_inc = np.median(_train_times_s(
        trace, SizeyMethod(SizeyConfig(incremental=True), ttf=1.0)))
    assert t_inc < 0.5 * t_full


def test_incremental_wastage_close_to_full():
    """Paper §III-D: incremental training costs only ~6% extra wastage."""
    trace = generate_workflow("mag", scale=0.15)
    r_full = simulate(trace, SizeyMethod(SizeyConfig(incremental=False),
                                         ttf=1.0), ttf=1.0)
    r_inc = simulate(trace, SizeyMethod(SizeyConfig(incremental=True),
                                        ttf=1.0), ttf=1.0)
    assert r_inc.wastage_gbh < 1.6 * r_full.wastage_gbh


def test_adaptive_alpha_runs_and_stays_competitive():
    """Beyond-paper extension (paper §III-E future work): per-pool adaptive
    alpha selection stays within 15% of the best fixed alpha."""
    trace = generate_workflow("rnaseq", scale=0.2)
    fixed = [simulate(trace, SizeyMethod(SizeyConfig(alpha=a), ttf=1.0),
                      ttf=1.0).wastage_gbh for a in (0.0, 0.5, 1.0)]
    adaptive = simulate(trace, SizeyMethod(
        SizeyConfig(adaptive_alpha=True), ttf=1.0), ttf=1.0).wastage_gbh
    assert adaptive < 1.15 * min(fixed)
    assert adaptive < max(fixed)  # never the worst


def test_model_selection_uses_multiple_classes():
    """Paper Fig. 11: several model classes get selected across a workflow."""
    trace = generate_workflow("rnaseq", scale=0.25)
    method = SizeyMethod(SizeyConfig(strategy="argmax"), ttf=1.0)
    simulate(trace, method, ttf=1.0)
    counts = method.predictor.model_select_counts
    assert counts.sum() > 0
    assert (counts > 0).sum() >= 2  # more than one class wins somewhere
