"""Heterogeneous cluster engine (PR 3): node classes + per-attempt caps,
best-fit / spread / preemptive placement, node-failure injection, and the
pinned bugfix regressions (exact-fit float-drift stall, queue-delay skew
from never-dispatched tasks, MAX_ATTEMPTS valve boundary)."""
import dataclasses
import warnings

import pytest

from repro.baselines import make_method
from repro.baselines.sizey_method import SizeyMethod
from repro.core import SizeyConfig
from repro.workflow import generate_workflow, simulate, simulate_cluster
from repro.workflow.accounting import MAX_ATTEMPTS, AttemptLedger
from repro.workflow.cluster import Node, NodeSpec, node_specs_from_caps
from repro.workflow.simulator import SizingMethod
from repro.workflow.trace import TaskInstance, WorkflowTrace


def _task(tt="A", idx=0, actual=10.0, runtime=1.0, deps=(), arrival=0.0,
          preset=64.0, machine="m", machine_cap=None):
    return TaskInstance("wf", tt, machine, 1.0, actual, runtime, preset, 0,
                        idx, arrival_h=arrival, deps=deps,
                        machine_cap_gb=machine_cap)


class MapMethod(SizingMethod):
    """Allocates a fixed amount per task type; doubles on failure."""
    name = "map"

    def __init__(self, allocs: dict):
        self.allocs = allocs

    def allocate(self, task):
        return self.allocs[task.task_type]

    def retry(self, task, attempt, last):
        return last * 2

    def complete(self, task, first_alloc, attempts):
        pass


# ------------------------------------------- bugfix: exact-fit float drift
DRIFT_ALLOCS = {"a": 8.4, "b": 37.12, "c": 59.236}  # 40 overlapped
# reserve/release rounds of these drift the pre-PR incremental free_gb
# accumulator to 127.99999999999886 on a 128 GB node


def test_node_reservations_exact_after_many_cycles():
    node = Node(NodeSpec("n0", 128.0))
    t = 0.0
    for _ in range(40):
        for tok, gb in enumerate(DRIFT_ALLOCS.values()):
            node.reserve(t, tok, gb)
        for tok in range(len(DRIFT_ALLOCS)):
            node.release(t, tok)
        t += 1.0
    assert node.free_gb == 128.0   # exact, no epsilon


def test_exact_fit_placement_after_drift_cycles():
    """Regression (fails on the pre-PR engine with a 'scheduler stalled'
    RuntimeError): after many overlapping reserve/release cycles, a task
    allocating exactly the node capacity — which shipped methods produce
    via capacity clamping — must still place on the now-idle node."""
    tasks = []
    prev_round: list[TaskInstance] = []
    for r in range(40):
        deps = tuple(t.key for t in prev_round)
        prev_round = [_task(tt, r, actual=5.0, runtime=1.0, deps=deps)
                      for tt in DRIFT_ALLOCS]
        tasks.extend(prev_round)
    tasks.append(_task("full", 0, actual=100.0, runtime=1.0,
                       deps=tuple(t.key for t in prev_round)))
    trace = WorkflowTrace("wf", tasks, machine_cap_gb=128.0)
    method = MapMethod({**DRIFT_ALLOCS, "full": 128.0})
    r = simulate_cluster(trace, method, n_nodes=1)   # pre-PR: RuntimeError
    assert len(r.outcomes) == len(tasks)
    assert not any(o.aborted for o in r.outcomes)


# --------------------------------------- bugfix: queue-delay skew on aborts
def test_admission_rejections_excluded_from_queue_delay():
    """Regression: never-dispatched (admission-rejected) tasks used to get a
    synthetic start_h and drag mean_queue_delay_h toward zero. They are now
    counted in n_aborted and excluded from the delay aggregates."""
    tasks = [_task("occ", 0, actual=50.0, runtime=1.0),     # fills the node
             _task("wait", 0, actual=40.0, runtime=1.0),    # queues 1 h
             _task("huge", 0, actual=600.0, runtime=1.0)]   # rejected
    trace = WorkflowTrace("wf", tasks, machine_cap_gb=128.0)
    r = simulate_cluster(
        trace, MapMethod({"occ": 100.0, "wait": 50.0, "huge": 500.0}),
        n_nodes=1, policy="fifo")
    m = r.cluster
    assert sum(o.aborted for o in r.outcomes) == 1
    assert m.n_aborted == 1
    # occ starts immediately (delay 0), wait starts at t=1 (delay 1);
    # the rejected task contributes no synthetic zero-delay sample
    assert m.mean_queue_delay_h == pytest.approx(0.5)
    assert m.max_queue_delay_h == pytest.approx(1.0)


# --------------------------------------- bugfix sweep: MAX_ATTEMPTS valve
def test_max_attempts_valve_fires_after_exactly_max_attempts():
    """Boundary pin: `attempts` counts dispatched attempts (starts at 1) and
    apply_retry increments only when a further attempt is granted, so the
    valve must trip on the MAX_ATTEMPTS-th failure — never one late."""
    class Stubborn:
        def retry(self, task, attempt, last):
            return last   # never increases: only the valve can stop it

    led = AttemptLedger(_task(actual=10.0), 8.0, 128.0, 1.0)
    for i in range(MAX_ATTEMPTS - 1):
        assert not led.record_failure(), \
            f"valve fired early, after {i + 1} failed attempts"
        led.apply_retry(Stubborn())
    assert led.attempts == MAX_ATTEMPTS
    assert led.record_failure()   # the MAX_ATTEMPTS-th attempt trips it
    assert led.aborted
    assert led.attempts == MAX_ATTEMPTS
    assert led.failures == MAX_ATTEMPTS


# ------------------------------------------------- placement-policy tables
# one wave of five tasks on three idle nodes (caps 100/100/50), runtime 1 h:
# each policy's documented choice yields a distinct utilization signature
_POLICY_TABLE = {
    # first-fit packs node00 to the brim, overflow lands on node01
    "fifo":     {"node00": 1.0, "node01": 0.4, "node02": 0.0},
    "backfill": {"node00": 1.0, "node01": 0.4, "node02": 0.0},
    # best-fit seeks the tightest leftover: 40 into the 50 GB node first
    "best_fit": {"node00": 0.9, "node01": 0.0, "node02": 1.0},
    # spread minimizes post-placement utilization: load is balanced
    "spread":   {"node00": 0.8, "node01": 0.4, "node02": 0.4},
}


@pytest.mark.parametrize("policy,expected", sorted(_POLICY_TABLE.items()))
def test_policy_placement_table(policy, expected):
    tasks = [_task(f"t{i}", 0, actual=1.0, runtime=1.0) for i in range(5)]
    trace = WorkflowTrace("wf", tasks, machine_cap_gb=100.0)
    allocs = {"t0": 40.0, "t1": 40.0, "t2": 40.0, "t3": 10.0, "t4": 10.0}
    specs = [NodeSpec("node00", 100.0), NodeSpec("node01", 100.0),
             NodeSpec("node02", 50.0)]
    r = simulate_cluster(trace, MapMethod(allocs), node_specs=specs,
                         policy=policy)
    m = r.cluster
    assert m.makespan_h == pytest.approx(1.0)
    for name, util in expected.items():
        assert m.node_util[name] == pytest.approx(util), \
            f"{policy}: {name} utilization {m.node_util[name]} != {util}"


def test_preemptive_evicts_lowest_priority_for_dag_critical_head():
    # a low-priority 90 GB occupant holds the single 100 GB node for 10 h;
    # a DAG-critical 90 GB task (it gates a child) arrives at t=1. The
    # preemptive policy evicts the occupant (non-OOM requeue), backfill
    # would make the critical task wait out the occupant.
    def build():
        occ = _task("low", 0, actual=50.0, runtime=10.0)
        crit = _task("crit", 0, actual=60.0, runtime=1.0, arrival=1.0)
        child = _task("child", 0, actual=2.0, runtime=1.0,
                      deps=(("crit", 0),))
        return WorkflowTrace("wf", [occ, crit, child], machine_cap_gb=100.0)

    allocs = {"low": 90.0, "crit": 90.0, "child": 5.0}
    pre = simulate_cluster(build(), MapMethod(allocs), n_nodes=1,
                           node_cap_gb=100.0, policy="preemptive")
    back = simulate_cluster(build(), MapMethod(allocs), n_nodes=1,
                            node_cap_gb=100.0, policy="backfill")
    by = {o.task.task_type: o for o in pre.outcomes}
    assert pre.cluster.n_preemptions == 1
    assert back.cluster.n_preemptions == 0
    assert by["crit"].finish_h == pytest.approx(2.0)      # 1 h after arrival
    crit_back = next(o for o in back.outcomes if o.task.task_type == "crit")
    assert crit_back.finish_h == pytest.approx(11.0)      # waited out 10 h
    # the victim is an interruption, not an OOM failure: same allocation,
    # partial hour burned as wastage, full re-run afterwards
    low = by["low"]
    assert low.failures == 0 and not low.aborted
    assert low.interruptions == 1
    assert low.final_alloc_gb == 90.0
    assert low.runtime_h == pytest.approx(11.0)           # 1 h lost + 10 h
    assert low.wastage_gbh == pytest.approx(90.0 * 1.0 + (90.0 - 50.0) * 10.0)
    assert low.finish_h == pytest.approx(12.0)


def test_preemptive_never_evicts_for_leaf_tasks():
    # the arriving task gates nothing -> no eviction, plain backfill wait
    occ = _task("low", 0, actual=50.0, runtime=10.0)
    leaf = _task("leaf", 0, actual=60.0, runtime=1.0, arrival=1.0)
    trace = WorkflowTrace("wf", [occ, leaf], machine_cap_gb=100.0)
    r = simulate_cluster(trace, MapMethod({"low": 90.0, "leaf": 90.0}),
                         n_nodes=1, node_cap_gb=100.0, policy="preemptive")
    assert r.cluster.n_preemptions == 0
    leaf_o = next(o for o in r.outcomes if o.task.task_type == "leaf")
    assert leaf_o.start_h == pytest.approx(10.0)


@pytest.mark.parametrize("policy", ["fifo", "backfill", "best_fit",
                                    "spread", "preemptive"])
def test_no_policy_overcommits_any_node(policy, monkeypatch):
    """Property: whatever the policy, mix of node sizes, and crash schedule,
    a node's outstanding reservations never exceed its capacity."""
    import repro.workflow.cluster as cluster_mod

    class CheckedNode(Node):
        def reserve(self, t, token, gb):
            super().reserve(t, token, gb)
            assert self.free_gb >= -1e-6, \
                f"{self.name} over-committed: free={self.free_gb}"

    monkeypatch.setattr(cluster_mod, "Node", CheckedNode)
    trace = generate_workflow("iwd", scale=0.05)
    specs = node_specs_from_caps([16.0, 32.0, 64.0], n_nodes=5)
    r = simulate_cluster(trace, make_method("witt_lr"), node_specs=specs,
                         policy=policy, fail_rate_per_node_h=0.5,
                         repair_h=0.05, fail_seed=3)
    assert len(r.outcomes) == len(trace.tasks)
    for name, util in r.cluster.node_util.items():
        assert 0.0 <= util <= 1.0 + 1e-9


# ------------------------------------------------- heterogeneity end-to-end
def test_node_specs_from_caps_cycles_classes():
    specs = node_specs_from_caps([16, 32], n_nodes=5)
    assert [s.cap_gb for s in specs] == [16.0, 32.0, 16.0, 32.0, 16.0]
    assert [s.machine for s in specs] == ["m16", "m32", "m16", "m32", "m16"]
    assert len(node_specs_from_caps([16, 32, 64])) == 3
    with pytest.raises(ValueError):
        node_specs_from_caps([])
    # dropping a node class would strand its trace tasks on hardware that
    # does not exist -> must be loud, not silent admission rejections
    with pytest.raises(ValueError, match="drops node classes"):
        node_specs_from_caps([16, 32, 64], n_nodes=2)


def test_mean_util_is_capacity_weighted():
    # one 10 GB task for 1 h on each node class: the small node is 10/16
    # busy, the big one 10/64 -> the capacity-weighted aggregate is total
    # reserved GBh over total capacity, not the mean of the two fractions
    specs = [NodeSpec("n16", 16.0, "m16"), NodeSpec("n64", 64.0, "m64")]
    tasks = [_task("a", 0, actual=8.0, machine="m16", machine_cap=16.0),
             _task("b", 0, actual=8.0, machine="m64", machine_cap=64.0)]
    trace = WorkflowTrace("wf", tasks, machine_cap_gb=64.0)
    r = simulate_cluster(trace, MapMethod({"a": 10.0, "b": 10.0}),
                         node_specs=specs)
    m = r.cluster
    assert m.mean_util == pytest.approx(20.0 / 80.0)
    assert m.mean_util != pytest.approx(
        sum(m.node_util.values()) / 2)   # weighting matters on this mix


def test_generator_emits_heterogeneous_machine_caps():
    caps = {"m16": 16.0, "m32": 32.0, "m64": 64.0}
    trace = generate_workflow("iwd", scale=0.05, machine_caps_gb=caps)
    assert trace.machine_cap_gb == 64.0
    seen = set()
    for t in trace.tasks:
        assert t.machine in caps
        assert t.machine_cap_gb == caps[t.machine]
        assert t.actual_peak_gb <= 0.9 * caps[t.machine] + 1e-9
        seen.add(t.machine)
    assert len(seen) > 1   # the trace really mixes machine classes
    assert trace.summary()["machine_caps_gb"] == caps


def test_machine_affinity_constrains_placement_and_admission():
    specs = [NodeSpec("n16", 16.0, "m16"), NodeSpec("n64", 64.0, "m64")]
    tasks = [_task("a", 0, actual=8.0, machine="m16", machine_cap=16.0),
             _task("b", 0, actual=8.0, machine="m64", machine_cap=64.0),
             # 20 GB on the m16 class: no eligible node can EVER fit it,
             # even though the m64 node has room -> admission reject
             _task("c", 0, actual=30.0, machine="m16", machine_cap=16.0)]
    trace = WorkflowTrace("wf", tasks, machine_cap_gb=64.0)
    with pytest.warns(RuntimeWarning):   # class-constrained rejection warns
        r = simulate_cluster(
            trace, MapMethod({"a": 10.0, "b": 10.0, "c": 20.0}),
            node_specs=specs, policy="fifo")
    m = r.cluster
    # first-fit without affinity would stack both tasks on n16; the class
    # labels force one task onto each node
    assert m.node_util["n16"] > 0.0
    assert m.node_util["n64"] > 0.0
    by = {o.task.task_type: o for o in r.outcomes}
    assert by["c"].aborted and by["c"].runtime_h == 0.0
    assert m.n_aborted == 1
    assert set(m.class_util) == {"m16", "m64"}
    assert set(m.node_caps_gb) == {"n16", "n64"}


def test_eligibility_blocked_tasks_do_not_starve_other_classes():
    """The backfill skip budget is per node: a long run of tasks blocked on
    their own saturated node class must not close an idle node of a class
    they could never have used (pre-fix: the global skip counter starved
    the m64 tasks behind 38 blocked m16 entries until t=3h)."""
    specs = [NodeSpec("n16", 16.0, "m16"), NodeSpec("n64", 64.0, "m64")]
    tasks = [_task("a", i, actual=6.0, runtime=1.0, machine="m16",
                   machine_cap=16.0) for i in range(40)]
    tasks += [_task("b", i, actual=6.0, runtime=1.0, machine="m64",
                    machine_cap=64.0) for i in range(4)]
    trace = WorkflowTrace("wf", tasks, machine_cap_gb=64.0)
    r = simulate_cluster(trace, MapMethod({"a": 8.0, "b": 8.0}),
                         node_specs=specs, policy="backfill",
                         backfill_depth=32)
    m64_starts = [o.start_h for o in r.outcomes if o.task.task_type == "b"]
    assert max(m64_starts) == pytest.approx(0.0)   # idle class runs at once
    assert not any(o.aborted for o in r.outcomes)


def test_admission_mismatch_warns_loudly():
    # a legacy homogeneous trace (128 GB machine cap) on a node set whose
    # largest node is 64 GB: methods size for hardware that does not
    # exist -> the mass rejection must raise a RuntimeWarning
    specs = node_specs_from_caps([16.0, 32.0, 64.0], n_nodes=3)
    t = _task("a", 0, actual=50.0, machine="epyc128")   # unconstrained
    trace = WorkflowTrace("wf", [t], machine_cap_gb=128.0)
    with pytest.warns(RuntimeWarning, match="machine_caps_gb"):
        r = simulate_cluster(trace, MapMethod({"a": 100.0}),
                             node_specs=specs)
    assert r.cluster.n_aborted == 1
    # a request beyond even the trace cap is a plain admission rejection
    # (hand-built trace), not a configuration mismatch: no warning
    trace2 = WorkflowTrace("wf", [dataclasses.replace(t, index=1)],
                           machine_cap_gb=64.0)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        r2 = simulate_cluster(trace2, MapMethod({"a": 100.0}),
                              node_specs=specs)
    assert r2.cluster.n_aborted == 1


def test_unlabeled_machine_runs_anywhere_on_labeled_cluster():
    # legacy homogeneous traces (machine label matching no node class) keep
    # running on every node of a labeled cluster
    specs = [NodeSpec("n16", 16.0, "m16"), NodeSpec("n64", 64.0, "m64")]
    tasks = [_task("a", i, actual=5.0, machine="epyc128") for i in range(4)]
    trace = WorkflowTrace("wf", tasks, machine_cap_gb=64.0)
    r = simulate_cluster(trace, MapMethod({"a": 10.0}), node_specs=specs,
                         policy="spread")
    assert not any(o.aborted for o in r.outcomes)
    assert all(u > 0.0 for u in r.cluster.node_util.values())


def test_sizey_pools_clamp_to_their_machine_class():
    caps = {"m16": 16.0, "m32": 32.0, "m64": 64.0}
    trace = generate_workflow("iwd", scale=0.05, machine_caps_gb=caps)
    specs = node_specs_from_caps(caps.values(), n_nodes=6)
    r = simulate_cluster(trace, SizeyMethod(SizeyConfig()), node_specs=specs,
                         policy="best_fit")
    assert len(r.outcomes) == len(trace.tasks)
    for o in r.outcomes:
        cap = caps[o.task.machine]
        assert o.first_alloc_gb <= cap + 1e-6
        assert o.final_alloc_gb <= cap + 1e-6
        assert not o.aborted
    assert set(r.cluster.class_util) == set(caps)


def test_serial_replay_respects_per_task_machine_cap():
    # retry ladder on a heterogeneous trace clamps at the task's own class
    # cap (16 GB), not the trace-wide 128 GB machine
    class Fixed(SizingMethod):
        name = "fixed"

        def allocate(self, task):
            return 12.0

        def retry(self, task, attempt, last):
            return last * 2

        def complete(self, task, first_alloc, attempts):
            pass

    t = _task("A", 0, actual=14.0, machine="m16", machine_cap=16.0)
    trace = WorkflowTrace("wf", [t], machine_cap_gb=128.0)
    serial = simulate(trace, Fixed())
    o = serial.outcomes[0]
    assert not o.aborted
    assert o.final_alloc_gb == 16.0   # 12 -> 24 clamped to the class cap
    # and the 1-node cluster special case agrees bitwise
    cluster = simulate_cluster(
        trace.sequentialized(), Fixed(),
        node_specs=[NodeSpec("n0", 16.0, "m16")])
    co = cluster.outcomes[0]
    assert (co.final_alloc_gb, co.attempts, co.failures, co.wastage_gbh) == \
        (o.final_alloc_gb, o.attempts, o.failures, pytest.approx(o.wastage_gbh))


# ------------------------------------------------- node-failure injection
def test_failure_injection_deterministic_and_non_oom():
    trace = generate_workflow("iwd", scale=0.05)

    def run():
        return simulate_cluster(trace, make_method("workflow_presets"),
                                n_nodes=2, fail_rate_per_node_h=2.0,
                                repair_h=0.1, fail_seed=11)

    r1, r2 = run(), run()
    assert len(r1.outcomes) == len(trace.tasks)
    assert r1.cluster.n_node_failures >= 1
    assert sum(o.interruptions for o in r1.outcomes) >= 1
    # presets never OOM on generated traces: crashes must not masquerade
    # as failures, abort anything, or change the allocation
    for o in r1.outcomes:
        assert o.failures == 0 and not o.aborted
        assert o.final_alloc_gb == o.first_alloc_gb
    # seeded schedule: bit-identical replay
    for a, b in zip(r1.outcomes, r2.outcomes):
        assert a.task.key == b.task.key
        assert a.interruptions == b.interruptions
        assert a.wastage_gbh == b.wastage_gbh
        assert a.finish_h == b.finish_h
    assert r1.cluster.n_node_failures == r2.cluster.n_node_failures
    assert r1.cluster.makespan_h == r2.cluster.makespan_h
    # downtime is tracked per node
    assert sum(r1.cluster.node_downtime_h.values()) > 0.0


def test_failure_free_run_matches_zero_rate():
    trace = generate_workflow("iwd", scale=0.05)
    base = simulate_cluster(trace, make_method("witt_lr"), n_nodes=2)
    zero = simulate_cluster(trace, make_method("witt_lr"), n_nodes=2,
                            fail_rate_per_node_h=0.0)
    assert base.wastage_gbh == zero.wastage_gbh
    assert base.cluster.makespan_h == zero.cluster.makespan_h
    assert zero.cluster.n_node_failures == 0


def test_crash_kills_are_charged_as_partial_wastage():
    # single node, one 4 h task; the node crashes mid-run (seeded schedule),
    # the attempt re-runs after repair: wastage gains alloc * elapsed
    trace = WorkflowTrace("wf", [_task("A", 0, actual=5.0, runtime=4.0)],
                          machine_cap_gb=128.0)
    r = simulate_cluster(trace, MapMethod({"A": 10.0}), n_nodes=1,
                         fail_rate_per_node_h=0.4, repair_h=0.25,
                         fail_seed=1)
    o = r.outcomes[0]
    assert not o.aborted and o.failures == 0
    if o.interruptions:   # the seeded schedule does hit the 4 h window
        assert o.runtime_h > 4.0
        assert o.wastage_gbh > (10.0 - 5.0) * 4.0
        assert r.cluster.makespan_h >= 4.0 + 0.25
    assert o.interruptions >= 1   # pinned: seed 1 crashes inside 4 h
