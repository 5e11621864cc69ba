"""Failure-model expansion (PR 5): correlated rack failures, straggler
injection, Ponder-style failure strategies (retry_same / retry_scaled /
checkpoint), crash-aware sizing, waste attribution by cause, and the
per-node vs per-event failure-count regression."""
import math

import pytest

from repro.baselines import make_method
from repro.baselines.sizey_method import SizeyMethod
from repro.core import SizeyConfig
from repro.workflow import generate_workflow, simulate, simulate_cluster
from repro.workflow.accounting import (DEFAULT_CHECKPOINT_FRAC,
                                       FAILURE_STRATEGIES, AttemptLedger)
from repro.workflow.cluster import (NodeSpec, node_specs_from_caps,
                                    node_specs_from_racks)
from repro.workflow.simulator import SizingMethod
from repro.workflow.trace import TaskInstance, WorkflowTrace


def _task(tt="A", idx=0, actual=10.0, runtime=1.0, deps=(), arrival=0.0,
          preset=64.0, machine="m"):
    return TaskInstance("wf", tt, machine, 1.0, actual, runtime, preset, 0,
                        idx, arrival_h=arrival, deps=deps)


class MapMethod(SizingMethod):
    """Allocates a fixed amount per task type; doubles on failure."""
    name = "map"

    def __init__(self, allocs, failure_strategy="retry_same"):
        self.allocs = allocs
        self.failure_strategy = failure_strategy

    def allocate(self, task):
        return self.allocs[task.task_type]

    def retry(self, task, attempt, last):
        return last * 2

    def complete(self, task, first_alloc, attempts):
        pass


# ------------------------------------------------------ rack topology
def test_node_specs_from_caps_assigns_racks_in_blocks():
    specs = node_specs_from_caps([16, 32], n_nodes=6, n_racks=2)
    assert [s.rack for s in specs] == ["rack00"] * 3 + ["rack01"] * 3
    # contiguous blocks: every rack still carries every node class (an
    # i % n_racks assignment would alias with the cap cycle)
    for rack in ("rack00", "rack01"):
        assert {s.cap_gb for s in specs if s.rack == rack} == {16.0, 32.0}
    assert all(s.rack is None for s in node_specs_from_caps([16, 32]))
    with pytest.raises(ValueError, match="n_racks"):
        node_specs_from_caps([16], n_nodes=2, n_racks=0)
    # more racks than nodes would silently yield fewer failure domains
    with pytest.raises(ValueError, match="n_racks"):
        node_specs_from_caps([16], n_nodes=2, n_racks=3)


def test_node_specs_from_racks_explicit_topology():
    specs = node_specs_from_racks([[16, 32], [64]])
    assert [(s.cap_gb, s.machine, s.rack) for s in specs] == [
        (16.0, "m16", "rack00"), (32.0, "m32", "rack00"),
        (64.0, "m64", "rack01")]
    assert [s.name for s in specs] == ["node00", "node01", "node02"]
    with pytest.raises(ValueError, match="rack 1"):
        node_specs_from_racks([[16], []])
    with pytest.raises(ValueError):
        node_specs_from_racks([])


def test_rack_rate_requires_rack_labels():
    trace = WorkflowTrace("wf", [_task()], machine_cap_gb=128.0)
    with pytest.raises(ValueError, match="rack-labeled"):
        simulate_cluster(trace, MapMethod({"A": 16.0}), n_nodes=2,
                         rack_fail_rate_per_h=0.5)


def test_unknown_failure_strategy_rejected():
    trace = WorkflowTrace("wf", [_task()], machine_cap_gb=128.0)
    with pytest.raises(ValueError, match="failure strategy"):
        simulate_cluster(trace, MapMethod({"A": 16.0}, "resurrect"),
                         n_nodes=1)
    with pytest.raises(ValueError, match="failure strategy"):
        make_method("witt_lr", failure_strategy="resurrect")
    with pytest.raises(ValueError, match="failure strategy"):
        SizeyMethod(SizeyConfig(), failure_strategy="resurrect")
    assert FAILURE_STRATEGIES == ("retry_same", "retry_scaled", "checkpoint")


# ------------------------------------- per-node vs per-event counts (bugfix)
def test_independent_failures_count_one_event_per_node():
    """Regression: with only independent node faults, the per-event and
    per-node axes must agree — one injected event downs exactly one node."""
    trace = generate_workflow("iwd", scale=0.05)
    r = simulate_cluster(trace, make_method("workflow_presets"), n_nodes=2,
                         fail_rate_per_node_h=2.0, repair_h=0.1,
                         fail_seed=11)
    m = r.cluster
    assert m.n_node_failures >= 1
    assert m.n_failure_events == m.n_node_failures
    assert m.n_rack_failures == 0


def test_rack_event_counts_once_per_event_and_per_node():
    """A rack outage is ONE failure event but downs every node of the rack:
    correlated and independent runs are comparable on either axis."""
    # both nodes in one rack; tasks keep the cluster busy long enough for
    # the seeded schedule to fire several outages
    specs = [NodeSpec("n0", 64.0, rack="rackA"),
             NodeSpec("n1", 64.0, rack="rackA")]
    tasks = [_task("A", i, actual=5.0, runtime=3.0) for i in range(8)]
    trace = WorkflowTrace("wf", tasks, machine_cap_gb=64.0)
    r = simulate_cluster(trace, MapMethod({"A": 8.0}), node_specs=specs,
                         rack_fail_rate_per_h=1.0, rack_repair_h=0.2,
                         fail_seed=3)
    m = r.cluster
    assert m.n_rack_failures >= 1
    # nodes recover together, so every outage finds both nodes up
    assert m.n_node_failures == 2 * m.n_rack_failures
    assert m.n_failure_events == m.n_rack_failures
    assert sum(m.rack_downtime_h.values()) > 0.0
    assert set(m.rack_downtime_h) == {"rackA"}
    assert sum(o.interruptions for o in r.outcomes) >= 1
    # rack kills are interruptions, never OOM failures
    assert all(o.failures == 0 and not o.aborted for o in r.outcomes)
    assert r.interruption_wastage_gbh > 0.0
    assert r.oom_wastage_gbh == 0.0


def test_rack_downtime_attributes_only_rack_outages():
    """Regression: rack_downtime_h must count node-hours held down by
    RACK outages — independent per-node faults on a rack-labeled cluster
    contribute to node_downtime_h only."""
    specs = node_specs_from_caps([128.0], n_nodes=2, n_racks=2)
    trace = generate_workflow("iwd", scale=0.05)
    r = simulate_cluster(trace, make_method("workflow_presets"),
                         node_specs=specs, fail_rate_per_node_h=2.0,
                         repair_h=0.1, fail_seed=11)
    m = r.cluster
    assert m.n_node_failures >= 1
    assert sum(m.node_downtime_h.values()) > 0.0
    assert sum(m.rack_downtime_h.values()) == 0.0   # no rack outage ran


def test_rack_outage_crashes_whole_rack_at_once():
    # two racks; when a rack fires, the OTHER rack keeps running: the two
    # nodes of the failed rack go down at the same instant
    specs = [NodeSpec("a0", 64.0, rack="rackA"),
             NodeSpec("a1", 64.0, rack="rackA"),
             NodeSpec("b0", 64.0, rack="rackB"),
             NodeSpec("b1", 64.0, rack="rackB")]
    tasks = [_task("A", i, actual=5.0, runtime=4.0) for i in range(4)]
    trace = WorkflowTrace("wf", tasks, machine_cap_gb=64.0)
    r = simulate_cluster(trace, MapMethod({"A": 8.0}), node_specs=specs,
                         rack_fail_rate_per_h=0.6, rack_repair_h=0.3,
                         fail_seed=0)
    m = r.cluster
    assert m.n_rack_failures >= 1
    down = {n: h for n, h in m.node_downtime_h.items() if h > 0.0}
    # downtime lands on whole racks: the downed node set is a union of
    # racks ({a0,a1} and/or {b0,b1}), never half a rack
    racks = {"rackA": {"a0", "a1"}, "rackB": {"b0", "b1"}}
    hit = {r_ for r_, members in racks.items() if members & set(down)}
    for r_ in hit:
        assert racks[r_] <= set(down)
        # both members crashed together -> identical downtime
        a, b = sorted(racks[r_])
        assert m.node_downtime_h[a] == pytest.approx(m.node_downtime_h[b])


# ------------------------------------------------------ determinism / seeds
def test_failure_and_straggler_schedules_deterministic():
    trace = generate_workflow("iwd", scale=0.05)
    specs = node_specs_from_caps([128.0], n_nodes=3, n_racks=3)

    def run():
        return simulate_cluster(
            trace, make_method("witt_lr"), node_specs=specs,
            fail_rate_per_node_h=1.0, repair_h=0.1,
            rack_fail_rate_per_h=0.8, rack_repair_h=0.2,
            straggler_rate=0.3, straggler_factor=3.0, fail_seed=9)

    r1, r2 = run(), run()
    assert r1.cluster.n_failure_events == r2.cluster.n_failure_events
    assert r1.cluster.n_rack_failures == r2.cluster.n_rack_failures
    assert r1.cluster.n_straggler_attempts == r2.cluster.n_straggler_attempts
    assert r1.cluster.n_straggler_attempts >= 1
    assert r1.cluster.straggler_extra_h == r2.cluster.straggler_extra_h
    for a, b in zip(r1.outcomes, r2.outcomes):
        assert a.task.key == b.task.key
        assert a.interruptions == b.interruptions
        assert a.wastage_gbh == b.wastage_gbh        # bitwise
        assert a.tw_gbh == b.tw_gbh
        assert a.oom_gbh == b.oom_gbh
        assert a.interruption_gbh == b.interruption_gbh
        assert a.finish_h == b.finish_h
    assert r1.cluster.makespan_h == r2.cluster.makespan_h


def test_fail_seed_changes_schedule_but_not_trace():
    """PR 4 seed-isolation pattern: the failure/straggler seed perturbs
    ONLY the injection schedules — the trace ground truth the two runs
    execute is bit-identical, and trace generation never consumes the
    failure seed at all."""
    t1 = generate_workflow("iwd", scale=0.05, seed=0)
    t2 = generate_workflow("iwd", scale=0.05, seed=0)
    assert t1.tasks == t2.tasks   # trace gen independent of any fail seed

    def run(seed):
        return simulate_cluster(
            trace=t1, method=make_method("workflow_presets"), n_nodes=2,
            fail_rate_per_node_h=2.0, repair_h=0.1,
            straggler_rate=0.3, fail_seed=seed)

    r1, r2 = run(11), run(12)
    # same ground truth per task (outcome ORDER may differ — completion
    # order depends on the schedule, the task set does not)...
    assert {o.task.key: o.task for o in r1.outcomes} \
        == {o.task.key: o.task for o in r2.outcomes}
    # ...but a different injected schedule
    assert (
        [o.interruptions for o in r1.outcomes]
        != [o.interruptions for o in r2.outcomes]
        or r1.cluster.n_straggler_attempts != r2.cluster.n_straggler_attempts
        or r1.cluster.n_failure_events != r2.cluster.n_failure_events)


def test_straggler_seed_defaults_to_fail_seed_and_is_separable():
    trace = generate_workflow("iwd", scale=0.05)

    def run(**kw):
        return simulate_cluster(trace, make_method("workflow_presets"),
                                n_nodes=2, straggler_rate=0.3, **kw)

    base = run(fail_seed=4)
    dflt = run(fail_seed=4, straggler_seed=4)
    assert base.cluster.n_straggler_attempts \
        == dflt.cluster.n_straggler_attempts
    assert base.cluster.straggler_extra_h == dflt.cluster.straggler_extra_h
    other = run(fail_seed=4, straggler_seed=5)
    assert (other.cluster.n_straggler_attempts
            != base.cluster.n_straggler_attempts
            or other.cluster.straggler_extra_h
            != base.cluster.straggler_extra_h)


# ------------------------------------------------------ straggler semantics
def test_straggler_stretches_attempt_and_charges_reservation():
    # one task, straggler_rate=1: the single attempt straggles, wall time
    # and reservation GB*h scale by the drawn slowdown
    t = _task("A", 0, actual=5.0, runtime=2.0)
    trace = WorkflowTrace("wf", [t], machine_cap_gb=128.0)
    r = simulate_cluster(trace, MapMethod({"A": 8.0}), n_nodes=1,
                         straggler_rate=1.0, straggler_factor=3.0,
                         fail_seed=0)
    o = r.outcomes[0]
    m = r.cluster
    assert m.n_straggler_attempts == 1
    s = o.runtime_h / 2.0
    assert s > 1.0
    assert o.wastage_gbh == pytest.approx((8.0 - 5.0) * 2.0 * s)
    assert o.tw_gbh == pytest.approx(o.wastage_gbh)
    assert m.makespan_h == pytest.approx(2.0 * s)
    assert m.straggler_extra_h == pytest.approx(2.0 * s - 2.0)


def test_straggler_free_run_is_bitwise_unchanged():
    trace = generate_workflow("iwd", scale=0.05)
    base = simulate_cluster(trace, make_method("witt_lr"), n_nodes=2)
    zero = simulate_cluster(trace, make_method("witt_lr"), n_nodes=2,
                            straggler_rate=0.0)
    for a, b in zip(base.outcomes, zero.outcomes):
        assert a.wastage_gbh == b.wastage_gbh
        assert a.finish_h == b.finish_h
    assert zero.cluster.n_straggler_attempts == 0
    assert zero.cluster.straggler_extra_h == 0.0


def test_straggler_stretches_temporal_resize_boundaries():
    # a temporal (multi-segment) method under 100% stragglers still
    # resizes and completes; tw integrals scale with the stretch
    trace = generate_workflow("mag", scale=0.02, curve_shapes=("ramp",))
    m = make_method("ks_plus", k_segments=3)
    base = simulate_cluster(trace, m, n_nodes=2)
    m2 = make_method("ks_plus", k_segments=3)
    stretched = simulate_cluster(trace, m2, n_nodes=2, straggler_rate=1.0,
                                 straggler_factor=2.0, fail_seed=1)
    assert stretched.cluster.n_resizes >= 1
    assert stretched.cluster.makespan_h > base.cluster.makespan_h
    assert stretched.temporal_wastage_gbh > base.temporal_wastage_gbh
    assert len(stretched.outcomes) == len(trace.tasks)
    assert not any(o.aborted for o in stretched.outcomes)


# ------------------------------------------------- waste attribution split
def test_oom_waste_attributed_per_cause():
    class Fixed(MapMethod):
        pass

    # actual 10 at alloc 8: one OOM burn (ttf-scaled), then success at 16
    t = _task("A", 0, actual=10.0, runtime=1.0)
    trace = WorkflowTrace("wf", [t], machine_cap_gb=128.0)
    r = simulate(trace, Fixed({"A": 8.0}), ttf=0.5)
    o = r.outcomes[0]
    assert o.oom_gbh == pytest.approx(8.0 * 0.5 * 1.0)
    assert o.interruption_gbh == 0.0
    # headroom = total - oom
    assert o.wastage_gbh - o.oom_gbh == pytest.approx((16.0 - 10.0) * 1.0)
    assert r.oom_wastage_gbh == pytest.approx(o.oom_gbh)
    assert r.failure_wastage_gbh == pytest.approx(o.oom_gbh)


def test_grow_denial_not_charged_as_interruption_waste():
    """Regression: a temporal grow DENIAL burns through the interruption
    arithmetic but is placement congestion, not a failure event — a
    crash-free temporal run must report zero failure waste."""
    from repro.core.temporal.segments import ReservationPlan
    led = AttemptLedger(_task(actual=8.0, runtime=1.0), 8.0, 128.0, 1.0)
    led.set_plan(ReservationPlan(((0.5, 4.0), (1.0, 8.0))))
    assert led.temporal_active
    led.record_grow_failure(0.5)
    assert led.grow_failures == 1
    assert led.wastage_gbh > 0.0          # the partial plan integral burns
    assert led.interruption_gbh == 0.0    # ...but not as failure waste
    assert led.oom_gbh == 0.0
    # a real crash on the same ledger DOES charge the failure axis
    led.record_interruption(0.25)
    assert led.interruption_gbh > 0.0


def test_crash_waste_attributed_as_interruption():
    trace = WorkflowTrace("wf", [_task("A", 0, actual=5.0, runtime=4.0)],
                          machine_cap_gb=128.0)
    r = simulate_cluster(trace, MapMethod({"A": 10.0}), n_nodes=1,
                         fail_rate_per_node_h=0.4, repair_h=0.25,
                         fail_seed=1)
    o = r.outcomes[0]
    assert o.interruptions >= 1   # pinned: seed 1 crashes inside 4 h
    assert o.interruption_gbh > 0.0
    assert o.oom_gbh == 0.0
    # headroom + interruption == total
    assert o.interruption_gbh + (10.0 - 5.0) * 4.0 \
        == pytest.approx(o.wastage_gbh)


# ------------------------------------------------- strategy: serial bitwise
@pytest.mark.parametrize("strategy", FAILURE_STRATEGIES)
def test_failure_free_cluster_bitwise_equals_serial_under_strategy(strategy):
    """Acceptance: homogeneous failure-free runs are bitwise-equal to the
    serial simulator under EVERY failure strategy (the strategies only
    change what an interruption costs — and nothing ever interrupts)."""
    trace = generate_workflow("iwd", scale=0.05)
    serial = simulate(trace, make_method("witt_lr"))
    cluster = simulate_cluster(
        trace.sequentialized(),
        make_method("witt_lr", failure_strategy=strategy), n_nodes=1)
    assert cluster.cluster.failure_strategy == strategy
    for a, b in zip(serial.outcomes, cluster.outcomes):
        assert a.task.key == b.task.key
        assert a.first_alloc_gb == b.first_alloc_gb
        assert a.final_alloc_gb == b.final_alloc_gb
        assert a.attempts == b.attempts
        assert a.failures == b.failures
        assert a.wastage_gbh == b.wastage_gbh       # bitwise, not approx
        assert a.tw_gbh == b.tw_gbh
        assert a.oom_gbh == b.oom_gbh
        assert a.runtime_h == b.runtime_h


@pytest.mark.parametrize("strategy", FAILURE_STRATEGIES)
def test_failure_free_sizey_bitwise_under_strategy(strategy):
    trace = generate_workflow("iwd", scale=0.02)
    serial = simulate(trace, SizeyMethod(SizeyConfig()))
    cluster = simulate_cluster(
        trace.sequentialized(),
        SizeyMethod(SizeyConfig(), failure_strategy=strategy), n_nodes=1)
    for a, b in zip(serial.outcomes, cluster.outcomes):
        assert a.first_alloc_gb == b.first_alloc_gb
        assert a.final_alloc_gb == b.final_alloc_gb
        assert a.wastage_gbh == b.wastage_gbh
        assert a.tw_gbh == b.tw_gbh


# ------------------------------------------------- strategy: checkpoint
def test_checkpoint_ledger_retains_prefix():
    # alloc 8 covers actual 5 (will succeed); interrupted 0.6 of the way
    # through a 1 h run with checkpoints every 0.25: retained 0.5, only
    # the 0.1 h since the last checkpoint is truly lost
    led = AttemptLedger(_task(actual=5.0, runtime=1.0), 8.0, 128.0, 1.0,
                        failure_strategy="checkpoint", checkpoint_frac=0.25)
    led.record_interruption(0.6)
    assert led.completed_frac == pytest.approx(0.5)
    assert led.interruption_gbh == pytest.approx(8.0 * 0.1)
    # wastage: lost 0.1 h at full alloc + headroom on the retained 0.5 h
    assert led.wastage_gbh == pytest.approx(8.0 * 0.1 + (8.0 - 5.0) * 0.5)
    assert led.interruptions == 1
    assert led.failures == 0
    # the re-run executes only the remaining half
    assert led.attempt_duration_h == pytest.approx(0.5)
    led.record_success()
    assert led.runtime_h == pytest.approx(0.6 + 0.5)
    assert led.wastage_gbh == pytest.approx(
        8.0 * 0.1 + (8.0 - 5.0) * 0.5 + (8.0 - 5.0) * 0.5)
    assert led.tw_gbh == pytest.approx(led.wastage_gbh)


def test_checkpoint_doomed_attempt_burns_in_full():
    # alloc below the peak: the attempt was running over-limit, so its
    # "progress" is an artifact — no retention, full interruption burn
    led = AttemptLedger(_task(actual=10.0, runtime=1.0), 8.0, 128.0, 1.0,
                        failure_strategy="checkpoint", checkpoint_frac=0.25)
    led.record_interruption(0.6)
    assert led.completed_frac == 0.0
    assert led.interruption_gbh == pytest.approx(8.0 * 0.6)
    # and an OOM kill resets any retention
    led2 = AttemptLedger(_task(actual=5.0, runtime=1.0), 8.0, 128.0, 1.0,
                         failure_strategy="checkpoint", checkpoint_frac=0.25)
    led2.record_interruption(0.3)
    assert led2.completed_frac == pytest.approx(0.25)
    led2.alloc_gb = 4.0      # force a doomed retry state
    led2.record_failure()
    assert led2.completed_frac == 0.0


def test_checkpoint_beats_retry_same_on_interruption_waste():
    # the pinned crash scenario (seed 1 crashes inside the 4 h window):
    # checkpointing loses only the since-checkpoint segment and re-runs
    # only the suffix, so both the burned GB*h and the wall time shrink
    def run(strategy):
        trace = WorkflowTrace(
            "wf", [_task("A", 0, actual=5.0, runtime=4.0)],
            machine_cap_gb=128.0)
        return simulate_cluster(
            trace, MapMethod({"A": 10.0}, strategy), n_nodes=1,
            fail_rate_per_node_h=0.4, repair_h=0.25, fail_seed=1)

    same = run("retry_same")
    ckpt = run("checkpoint")
    assert same.outcomes[0].interruptions >= 1
    assert ckpt.interruption_wastage_gbh < same.interruption_wastage_gbh
    assert ckpt.wastage_gbh < same.wastage_gbh
    assert ckpt.outcomes[0].runtime_h < same.outcomes[0].runtime_h
    assert ckpt.cluster.makespan_h <= same.cluster.makespan_h


# ------------------------------------------------- strategy: retry_scaled
def test_retry_scaled_resizes_through_method_after_crash():
    class Shrinking(SizingMethod):
        """First sizing says 20 GB; every re-sizing tightens to 8 GB."""
        name = "shrinking"
        failure_strategy = "retry_scaled"

        def __init__(self):
            self.calls = 0

        def allocate(self, task):
            self.calls += 1
            return 20.0 if self.calls == 1 else 8.0

        def retry(self, task, attempt, last):
            return last * 2

        def complete(self, task, first_alloc, attempts):
            pass

    trace = WorkflowTrace("wf", [_task("A", 0, actual=5.0, runtime=4.0)],
                          machine_cap_gb=128.0)
    method = Shrinking()
    r = simulate_cluster(trace, method, n_nodes=1,
                         fail_rate_per_node_h=0.4, repair_h=0.25,
                         fail_seed=1)
    o = r.outcomes[0]
    assert o.interruptions >= 1
    assert method.calls >= 2          # the crash triggered a re-sizing
    assert o.first_alloc_gb == 20.0
    assert o.final_alloc_gb == 8.0    # the re-run used the fresh estimate
    assert o.failures == 0            # re-sizing is not a ladder step
    # the re-sized run wastes less than staying at 20 GB would have
    same = simulate_cluster(
        WorkflowTrace("wf", [_task("A", 0, actual=5.0, runtime=4.0)],
                      machine_cap_gb=128.0),
        MapMethod({"A": 20.0}), n_nodes=1,
        fail_rate_per_node_h=0.4, repair_h=0.25, fail_seed=1)
    assert r.wastage_gbh < same.wastage_gbh


# ------------------------------------------------- crash-aware sizing fold
def test_crash_aware_offset_fold_shrinks_allocations():
    def trained(strategy):
        m = SizeyMethod(SizeyConfig(), failure_strategy=strategy)
        trace = generate_workflow("iwd", scale=0.05)
        simulate(trace, m)   # build pool history -> model decisions
        return m

    base = trained("checkpoint")
    crashy = trained("checkpoint")
    probe = generate_workflow("iwd", scale=0.05).tasks[-1]
    a_before = base.allocate(probe)
    # a heavy observed interruption rate must shrink the offset...
    for _ in range(30):
        crashy.note_interruption(probe, 0.05)
    a_after = crashy.allocate(probe)
    d = crashy._pending[id(probe)]
    if d.source == "model" and d.offset_gb > 0:
        assert a_after < a_before
        # ...but never undercut the aggregate prediction itself
        assert a_after >= d.agg_pred_gb - 1e-12
    # retry_same never folds, whatever it observed
    plain = trained("retry_same")
    for _ in range(30):
        plain.note_interruption(probe, 0.05)
    assert plain.allocate(probe) == a_before


def test_crash_aware_fold_inert_without_interruptions():
    trace = generate_workflow("iwd", scale=0.02)
    a = simulate(trace, SizeyMethod(SizeyConfig()))
    b = simulate(trace, SizeyMethod(SizeyConfig(),
                                    failure_strategy="checkpoint"))
    for x, y in zip(a.outcomes, b.outcomes):
        assert x.first_alloc_gb == y.first_alloc_gb
        assert x.wastage_gbh == y.wastage_gbh


def test_baselines_carry_strategy_and_note_interruptions():
    m = make_method("witt_lr", failure_strategy="checkpoint")
    assert m.failure_strategy == "checkpoint"
    assert m.checkpoint_frac == DEFAULT_CHECKPOINT_FRAC
    m.note_interruption(_task(), 0.5)
    assert m.n_interruptions == 1
    assert make_method("witt_lr").failure_strategy == "retry_same"


# ------------------------------------------------- engine-level integration
@pytest.mark.parametrize("strategy", FAILURE_STRATEGIES)
def test_full_injection_mix_completes_under_every_strategy(strategy):
    trace = generate_workflow("iwd", scale=0.05)
    specs = node_specs_from_caps([16.0, 32.0, 64.0], n_nodes=6, n_racks=2)
    r = simulate_cluster(
        trace, make_method("witt_percentile", failure_strategy=strategy),
        node_specs=specs, policy="best_fit",
        fail_rate_per_node_h=0.8, repair_h=0.1,
        rack_fail_rate_per_h=0.5, rack_repair_h={"rack00": 0.3,
                                                 "rack01": 0.1},
        straggler_rate=0.2, fail_seed=13)
    assert len(r.outcomes) == len(trace.tasks)
    m = r.cluster
    assert m.failure_strategy == strategy
    assert m.n_failure_events >= m.n_rack_failures
    total = r.wastage_gbh
    assert r.oom_wastage_gbh + r.interruption_wastage_gbh <= total + 1e-9
    for util in m.node_util.values():
        assert 0.0 <= util <= 1.0 + 1e-9


def test_per_rack_repair_mapping_validated():
    specs = node_specs_from_caps([64.0], n_nodes=2, n_racks=2)
    tasks = [_task("A", i, actual=5.0, runtime=3.0) for i in range(4)]
    trace = WorkflowTrace("wf", tasks, machine_cap_gb=64.0)
    with pytest.raises(ValueError, match="repair"):
        simulate_cluster(trace, MapMethod({"A": 8.0}), node_specs=specs,
                         rack_fail_rate_per_h=5.0,
                         rack_repair_h={"rack00": 0.1}, fail_seed=0)
