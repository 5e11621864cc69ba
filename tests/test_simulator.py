"""Simulator semantics: strict limits, ttf accounting, retry ladders."""
import os

import pytest

from chaos import assert_results_equal, kill_and_resume, run_journaled
from repro.baselines import ALL_BASELINES, make_method
from repro.core.provenance import ProvenanceDB
from repro.workflow import generate_workflow, simulate, simulate_cluster
from repro.workflow.simulator import SizingMethod, simulate as _sim
from repro.workflow.trace import TaskInstance, WorkflowTrace


class FixedMethod(SizingMethod):
    """Always allocates a fixed amount; doubles on failure."""
    name = "fixed"

    def __init__(self, gb):
        self.gb = gb
        self.completed = []

    def allocate(self, task):
        return self.gb

    def retry(self, task, attempt, last):
        return last * 2

    def complete(self, task, first_alloc, attempts):
        self.completed.append((task.task_type, attempts))


def _one_task_trace(actual=10.0, runtime=1.0):
    t = TaskInstance("wf", "A", "m", 1.0, actual, runtime, 64.0, 0, 0)
    return WorkflowTrace("wf", [t], machine_cap_gb=128.0)


def test_success_wastage_is_overshoot_times_runtime():
    r = _sim(_one_task_trace(actual=10.0, runtime=2.0), FixedMethod(16.0))
    assert r.wastage_gbh == pytest.approx((16 - 10) * 2.0)
    assert r.n_failures == 0
    assert r.total_runtime_h == pytest.approx(2.0)


def test_failure_burns_alloc_for_ttf_runtime():
    # 8 < 10 fails once; retry 16 succeeds
    r = _sim(_one_task_trace(actual=10.0, runtime=2.0), FixedMethod(8.0),
             ttf=0.5)
    # failed attempt: 8 GB * (0.5 * 2 h) = 8 GBh; success: (16-10)*2 = 12
    assert r.wastage_gbh == pytest.approx(8 * 1.0 + 12.0)
    assert r.n_failures == 1
    assert r.total_runtime_h == pytest.approx(1.0 + 2.0)


def test_doubling_ladder_reaches_success():
    r = _sim(_one_task_trace(actual=100.0, runtime=1.0), FixedMethod(4.0))
    # 4, 8, 16, 32, 64 fail (5 failures), 128 succeeds
    assert r.n_failures == 5
    assert r.outcomes[0].final_alloc_gb == 128.0


def test_ttf_one_matches_paper_semantics():
    r10 = _sim(_one_task_trace(), FixedMethod(8.0), ttf=1.0)
    r05 = _sim(_one_task_trace(), FixedMethod(8.0), ttf=0.5)
    assert r10.wastage_gbh > r05.wastage_gbh  # earlier failures waste less


def test_presets_never_fail_on_generated_traces():
    trace = generate_workflow("chipseq", scale=0.1)
    r = simulate(trace, make_method("workflow_presets"))
    assert r.n_failures == 0


def test_generated_trace_matches_table1_shape():
    trace = generate_workflow("mag", scale=1.0)
    s = trace.summary()
    assert s["n_task_types"] == 8
    assert 500 <= s["avg_instances_per_type"] <= 940  # Table I: 720 +/- 30%
    for t in trace.tasks:
        assert 0 < t.actual_peak_gb < trace.machine_cap_gb
        assert t.user_preset_gb >= t.actual_peak_gb  # presets conservative


def test_wastage_over_time_monotone():
    trace = generate_workflow("iwd", scale=0.1)
    r = simulate(trace, make_method("witt_lr"))
    curve = r.wastage_over_time()
    assert all(b[1] >= a[1] for a, b in zip(curve, curve[1:]))


def test_wastage_over_time_serial_is_event_timestamped():
    """Regression pin for the serial 1-node case: the curve's x-axis is the
    per-task completion timestamp, which serially equals the running sum of
    wall times (the pre-cluster behaviour)."""
    trace = generate_workflow("iwd", scale=0.1)
    r = simulate(trace, make_method("witt_lr"))
    curve = r.wastage_over_time()
    t = w = 0.0
    for o, (ct, cw) in zip(r.outcomes, curve):
        t += o.runtime_h
        w += o.wastage_gbh
        assert ct == pytest.approx(t)
        assert cw == pytest.approx(w)
        assert o.finish_h == pytest.approx(t)
    assert curve[-1] == (pytest.approx(r.total_runtime_h),
                         pytest.approx(r.wastage_gbh))
    assert r.makespan_h == pytest.approx(r.total_runtime_h)


def test_summary_reports_float_load_and_machine_cap():
    trace = generate_workflow("mag", scale=1.0)
    s = trace.summary()
    assert isinstance(s["avg_instances_per_type"], float)
    assert s["avg_instances_per_type"] == pytest.approx(
        len(trace.tasks) / s["n_task_types"])
    assert s["machine_cap_gb"] == trace.machine_cap_gb


# ------------------------------------------------------ the SizingMethod seam
class MinimalMethod(SizingMethod):
    """Defines only allocate/retry/complete: every other hook the engines
    call is the base class's default. Sizes one task type in three under
    its peak, so the retry ladder runs; keeps a provenance db only when
    given a journal path."""
    name = "minimal"

    def __init__(self, persist_path=None):
        if persist_path is not None:
            self.db = ProvenanceDB(persist_path=persist_path)
        self.n_allocated = 0
        self.completed = []

    def allocate(self, task):
        self.n_allocated += 1
        return task.actual_peak_gb * (0.75 if task.index % 3 == 0 else 1.25)

    def retry(self, task, attempt, last):
        return last * 2.0

    def complete(self, task, first_alloc, attempts):
        self.completed.append(task.key)


_CRASHES = dict(n_nodes=4, fail_rate_per_node_h=0.2, straggler_rate=0.1)


@pytest.mark.parametrize("run", ["simulate", "simulate_cluster_crashes",
                                 "journaled_warm_resume"])
def test_minimal_method_runs_on_the_base_defaults(run, tmp_path):
    trace = generate_workflow("eager", seed=3, scale=0.04,
                              machine_cap_gb=64.0)
    if run == "simulate":
        m = MinimalMethod()
        r = simulate(trace, m)
        assert m.n_allocated == len(trace.tasks)
        # batch_stages changes nothing for a method that sizes per task
        assert simulate(trace, MinimalMethod(),
                        batch_stages=True).outcomes == r.outcomes
    elif run == "simulate_cluster_crashes":
        m = MinimalMethod()
        r = simulate_cluster(trace, m, **_CRASHES)
        assert r.cluster.n_node_failures > 0
        assert sum(o.interruptions for o in r.outcomes) > 0
        # retry_same never re-sizes: one size call per task
        assert r.cluster.n_size_calls == m.n_allocated
        assert r.cluster.failure_strategy == "retry_same"
    else:
        path = str(tmp_path / "run.jsonl")
        r = run_journaled(trace, MinimalMethod, path, snapshot_every=8,
                          **_CRASHES)
        size = os.path.getsize(path)
        resumed, _eng = kill_and_resume(path, size // 2, trace,
                                        MinimalMethod,
                                        scratch=str(tmp_path / "cut.jsonl"))
        assert_results_equal(r, resumed)
        assert resumed.cluster.n_recoveries == 1
        m = MinimalMethod()
        assert r.outcomes == simulate_cluster(trace, m, **_CRASHES).outcomes
    assert r.n_failures > 0
    done = [o.task.key for o in r.outcomes if not o.aborted]
    assert sorted(m.completed) == sorted(done)


METHOD_NAMES = ("sizey", "sizey_risk", "sizey_risk_temporal",
                "sizey_argmax", "sizey_temporal") + ALL_BASELINES


@pytest.mark.parametrize("name", METHOD_NAMES)
def test_every_method_derives_from_the_base(name):
    assert isinstance(make_method(name, machine_cap_gb=64.0), SizingMethod)
