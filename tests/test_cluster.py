"""Event-driven cluster engine: serial equivalence, concurrency, dependency
ordering, ready-wave dispatch bounds, placement policies, abort paths."""
import dataclasses

import pytest

from repro import obs
from repro.baselines import make_method
from repro.baselines.sizey_method import SizeyMethod
from repro.core import SizeyConfig
from repro.core.predictor import DISPATCH_COUNTS
from repro.workflow import generate_workflow, simulate, simulate_cluster
from repro.workflow.accounting import MAX_ATTEMPTS
from repro.workflow.cluster import PLACEMENT_POLICIES
from repro.workflow.simulator import SizingMethod
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


def _task(tt="A", idx=0, actual=10.0, runtime=1.0, deps=(), arrival=0.0,
          preset=64.0):
    return TaskInstance("wf", tt, "m", 1.0, actual, runtime, preset, 0, idx,
                        arrival_h=arrival, deps=deps)


def _assert_outcomes_equal(serial, cluster):
    assert len(serial.outcomes) == len(cluster.outcomes)
    for a, b in zip(serial.outcomes, cluster.outcomes):
        assert a.task.key == b.task.key
        assert a.first_alloc_gb == b.first_alloc_gb
        assert a.final_alloc_gb == b.final_alloc_gb
        assert a.attempts == b.attempts
        assert a.failures == b.failures
        assert a.aborted == b.aborted
        assert a.wastage_gbh == pytest.approx(b.wastage_gbh)
        assert a.finish_h == pytest.approx(b.finish_h)
    assert serial.wastage_gbh == pytest.approx(cluster.wastage_gbh)
    assert serial.n_failures == cluster.n_failures


# ------------------------------------------------- serial equivalence
@pytest.mark.parametrize("policy", sorted(PLACEMENT_POLICIES))
def test_one_node_sequential_matches_serial_fixed(policy):
    # the homogeneous / failure-free / 1-node configuration must stay
    # bitwise-equal to the serial replay under EVERY placement policy
    tasks = [_task(idx=i, actual=4.0 + 3 * i, runtime=0.5 + 0.25 * i)
             for i in range(6)]  # later tasks OOM the 8 GB first allocation
    trace = WorkflowTrace("wf", tasks, machine_cap_gb=128.0)
    serial = simulate(trace, FixedMethod(8.0), ttf=0.5)
    cluster = simulate_cluster(trace.sequentialized(), FixedMethod(8.0),
                               ttf=0.5, n_nodes=1, policy=policy)
    _assert_outcomes_equal(serial, cluster)
    assert cluster.cluster.makespan_h == pytest.approx(serial.total_runtime_h)
    assert cluster.cluster.policy == policy
    assert cluster.cluster.n_preemptions == 0
    assert cluster.cluster.n_node_failures == 0


@pytest.mark.parametrize("policy", sorted(PLACEMENT_POLICIES))
def test_one_node_sequential_matches_serial_baseline(policy):
    trace = generate_workflow("iwd", scale=0.1)
    serial = simulate(trace, make_method("witt_lr"))
    cluster = simulate_cluster(trace.sequentialized(),
                               make_method("witt_lr"), n_nodes=1,
                               policy=policy)
    _assert_outcomes_equal(serial, cluster)


@pytest.mark.parametrize("policy", ["backfill", "best_fit", "preemptive"])
def test_one_node_sequential_matches_serial_sizey(policy):
    # the cluster path sizes each 1-task ready wave through allocate_batch;
    # decisions must be bitwise-identical to the serial predict path — on
    # the legacy backfill path and on the new-policy code paths alike
    trace = generate_workflow("iwd", scale=0.05)
    serial = simulate(trace, SizeyMethod(SizeyConfig()))
    cluster = simulate_cluster(trace.sequentialized(),
                               SizeyMethod(SizeyConfig()), n_nodes=1,
                               policy=policy)
    _assert_outcomes_equal(serial, cluster)


# ------------------------------------------------- concurrency + metrics
def test_multi_node_concurrency_and_metrics():
    trace = generate_workflow("iwd", scale=0.1)
    serial = simulate(trace, make_method("witt_lr"))
    r = simulate_cluster(trace, make_method("witt_lr"), n_nodes=4)
    m = r.cluster
    assert len(r.outcomes) == len(trace.tasks)
    assert m.makespan_h < serial.total_runtime_h  # concurrency helps
    assert m.makespan_h == pytest.approx(r.makespan_h)
    assert 0.0 < m.peak_reserved_gb <= m.n_nodes * m.node_cap_gb
    for util in m.node_util.values():
        assert 0.0 <= util <= 1.0 + 1e-9
    assert m.mean_queue_delay_h >= 0.0
    assert m.n_waves >= 1
    # event-timestamped wastage curve: monotone in both axes, same final
    # total as the serial accounting
    curve = r.wastage_over_time()
    assert all(b[0] >= a[0] and b[1] >= a[1]
               for a, b in zip(curve, curve[1:]))
    assert curve[-1][1] == pytest.approx(r.wastage_gbh)
    assert curve[-1][0] == pytest.approx(m.makespan_h)


def test_dependencies_gate_start_times():
    trace = generate_workflow("chipseq", scale=0.05)
    assert any(t.deps for t in trace.tasks)  # generator emits instance edges
    r = simulate_cluster(trace, make_method("witt_percentile"), n_nodes=4)
    finish = {o.task.key: o.finish_h for o in r.outcomes}
    by_key = {o.task.key: o for o in r.outcomes}
    for o in r.outcomes:
        for dep in o.task.deps:
            assert o.start_h >= finish[dep] - 1e-9, \
                f"{o.task.key} started before dep {dep} finished"
            assert by_key[dep] is not None


def test_arrival_process_gates_roots():
    trace = generate_workflow("iwd", scale=0.05, arrival_rate_per_h=50.0)
    roots = [t for t in trace.tasks if not t.deps]
    assert all(t.arrival_h > 0 for t in roots)
    assert all(t.arrival_h == 0.0 for t in trace.tasks if t.deps)
    r = simulate_cluster(trace, make_method("workflow_presets"), n_nodes=2)
    started = {o.task.key: o.start_h for o in r.outcomes}
    for t in roots:
        assert started[t.key] >= t.arrival_h - 1e-9


def test_capacity_contention_queues_tasks():
    # 4 tasks of 60 GB on one 128 GB node: only two run at a time
    tasks = [_task(idx=i, actual=50.0, runtime=1.0) for i in range(4)]
    trace = WorkflowTrace("wf", tasks, machine_cap_gb=128.0)
    r = simulate_cluster(trace, FixedMethod(60.0), n_nodes=1)
    m = r.cluster
    assert m.peak_reserved_gb == pytest.approx(120.0)
    assert m.makespan_h == pytest.approx(2.0)  # two waves of two
    assert m.mean_queue_delay_h > 0.0


# ------------------------------------------------- placement policies
def test_backfill_beats_fifo_head_of_line_blocking():
    # head task needs 100 GB (must wait for the 60 GB runner to finish);
    # the small tasks behind it fit now — backfill runs them, FIFO stalls
    tasks = [_task("big", 0, actual=90.0, runtime=1.0),
             *[_task("small", i, actual=5.0, runtime=1.0)
               for i in range(1, 5)]]
    # a long-running 60 GB occupant forces the queue to form
    occupant = _task("occ", 9, actual=55.0, runtime=10.0)
    trace = WorkflowTrace("wf", [occupant, *tasks], machine_cap_gb=128.0)

    class PresetLike(FixedMethod):
        def allocate(self, task):
            return {"occ": 60.0, "big": 100.0, "small": 6.0}[task.task_type]

    fifo = simulate_cluster(trace, PresetLike(0), n_nodes=1, policy="fifo")
    back = simulate_cluster(trace, PresetLike(0), n_nodes=1,
                            policy="backfill")
    small_fifo = max(o.finish_h for o in fifo.outcomes
                     if o.task.task_type == "small")
    small_back = max(o.finish_h for o in back.outcomes
                     if o.task.task_type == "small")
    assert small_back < small_fifo   # backfilled around the blocked head
    assert fifo.wastage_gbh == pytest.approx(back.wastage_gbh)


def test_unknown_policy_rejected():
    trace = WorkflowTrace("wf", [_task()], machine_cap_gb=128.0)
    with pytest.raises(ValueError, match="placement policy"):
        simulate_cluster(trace, FixedMethod(16.0), policy="sjf")
    assert set(PLACEMENT_POLICIES) == {"fifo", "backfill", "best_fit",
                                       "spread", "preemptive"}


# ------------------------------------------------- ready-wave dispatch bound
def test_ready_wave_bursts_bound_device_dispatches():
    trace = generate_workflow("iwd", scale=0.05)
    n_pools = len({(t.task_type, t.machine) for t in trace.tasks})
    method = SizeyMethod(SizeyConfig())
    with obs.scoped_counters(DISPATCH_COUNTS) as dc:
        r = simulate_cluster(trace, method, n_nodes=4)
        dispatches = dc["predict_pool"]
        decisions = dc["decisions"]
    m = r.cluster
    assert len(r.outcomes) == len(trace.tasks)
    # each wave launches at most one fused program per pool present in it
    assert dispatches <= m.n_waves * n_pools
    # and the whole run needs far fewer launches than decisions served
    # (the serial per-task path costs one launch per model-sized task)
    assert dispatches < decisions
    assert m.n_size_calls == m.n_waves  # one allocate_batch per wave


# ------------------------------------------------- abort paths
def test_max_attempts_safety_valve():
    class StubbornMethod(FixedMethod):
        def retry(self, task, attempt, last):
            return last  # never increases: only the valve can stop it

    trace = WorkflowTrace("wf", [_task(actual=10.0)], machine_cap_gb=128.0)
    serial = simulate(trace, StubbornMethod(8.0))
    o = serial.outcomes[0]
    assert o.aborted
    assert o.attempts == MAX_ATTEMPTS
    assert o.failures == MAX_ATTEMPTS
    cluster = simulate_cluster(trace.sequentialized(), StubbornMethod(8.0),
                               n_nodes=1)
    _assert_outcomes_equal(serial, cluster)


def test_allocation_at_cap_abort():
    # actual peak above the machine capacity: the ladder reaches the cap,
    # fails there, and the task is aborted
    trace = WorkflowTrace("wf", [_task(actual=200.0)], machine_cap_gb=128.0)
    serial = simulate(trace, FixedMethod(32.0))
    o = serial.outcomes[0]
    assert o.aborted
    assert o.final_alloc_gb == 128.0
    assert o.failures == 3  # 32, 64, 128 all die
    cluster = simulate_cluster(trace.sequentialized(), FixedMethod(32.0),
                               n_nodes=1)
    _assert_outcomes_equal(serial, cluster)


def test_abandon_leaves_no_pending_after_aborted_burst():
    # one impossible task (actual > cap) inside a same-pool burst: the
    # abort must pop its pending decision; completions pop the rest
    tasks = [_task("A", 0, actual=4.0, runtime=0.1),
             _task("A", 1, actual=200.0, runtime=0.1),
             _task("A", 2, actual=5.0, runtime=0.1)]
    trace = WorkflowTrace("wf", tasks, machine_cap_gb=128.0)
    method = SizeyMethod(SizeyConfig())
    r = simulate(trace, method, batch_stages=True)
    assert sum(o.aborted for o in r.outcomes) == 1
    assert method._pending == {}

    method2 = SizeyMethod(SizeyConfig())
    r2 = simulate_cluster(trace, method2, n_nodes=2)
    assert sum(o.aborted for o in r2.outcomes) == 1
    assert method2._pending == {}


def test_unplaceable_request_rejected_at_admission():
    # a request larger than every node is rejected when sized, so it never
    # head-of-line blocks the placeable tasks behind it
    class HugeHead(FixedMethod):
        def allocate(self, task):
            return 500.0 if task.task_type == "big" else 8.0

    tasks = [_task("big", 0, actual=600.0),
             _task("A", 0, actual=4.0, runtime=1.0)]
    trace = WorkflowTrace("wf", tasks, machine_cap_gb=128.0)
    r = simulate_cluster(trace, HugeHead(0), n_nodes=2, node_cap_gb=128.0,
                         policy="fifo")
    by_type = {o.task.task_type: o for o in r.outcomes}
    big = by_type["big"]
    assert big.aborted
    assert big.runtime_h == 0.0 and big.wastage_gbh == 0.0
    assert big.finish_h == 0.0           # rejected immediately, not at drain
    assert by_type["A"].start_h == 0.0   # no head-of-line blocking
    assert not by_type["A"].aborted


def test_abort_unlocks_dependents():
    # A's peak exceeds the capacity so it aborts after the ladder; B (its
    # dependent) and C must still run — every instance gets an outcome
    a = _task("A", 0, actual=200.0, runtime=1.0)
    b = _task("B", 0, actual=4.0, runtime=1.0, deps=(("A", 0),))
    c = _task("C", 0, actual=4.0, runtime=1.0)
    trace = WorkflowTrace("wf", [a, b, c], machine_cap_gb=128.0)
    r = simulate_cluster(trace, FixedMethod(32.0), n_nodes=1)
    assert len(r.outcomes) == 3
    by_type = {o.task.task_type: o for o in r.outcomes}
    assert by_type["A"].aborted
    assert not by_type["B"].aborted and not by_type["C"].aborted
    assert by_type["B"].start_h >= by_type["A"].finish_h - 1e-9
