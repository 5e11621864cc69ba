"""Temporal memory subsystem: segment math, usage-curve traces, plan-aware
ledger arithmetic, RESIZE execution, k=1 bitwise equivalence, batched
observe dispatch bounds, and checkpoint round-trips."""
import dataclasses

import numpy as np
import pytest

from repro import obs
from repro.baselines import make_method
from repro.baselines.sizey_method import SizeyMethod
from repro.core import SizeyConfig
from repro.core.predictor import DISPATCH_COUNTS, SizeyPredictor
from repro.core.temporal.segments import (ReservationPlan, fit_boundaries,
                                          grid_profile, segment_peaks,
                                          uniform_boundaries)
from repro.workflow import generate_workflow, simulate, simulate_cluster
from repro.workflow.accounting import (MAX_GROW_FAILURES, AttemptLedger)
from repro.workflow.simulator import SizingMethod
from repro.workflow.trace import TaskInstance, WorkflowTrace


def _cfg(**kw):
    kw.setdefault("mlp_train_steps", 30)
    return SizeyConfig(**kw)


def _task(tt="A", idx=0, actual=10.0, runtime=1.0, curve=(), preset=64.0,
          deps=(), input_gb=1.0):
    return TaskInstance("wf", tt, "m", input_gb, actual, runtime, preset, 0,
                        idx, deps=deps, usage_curve=curve)


class FixedPlanMethod(SizingMethod):
    """Allocates a fixed reservation plan; doubles flat on failure."""
    name = "fixed_plan"

    def __init__(self, segs):
        self.segs = tuple(segs)

    def allocate(self, task):
        return max(g for _, g in self.segs)

    def plan_for(self, task):
        return ReservationPlan(self.segs)

    def retry(self, task, attempt, last):
        return last * 2

    def complete(self, task, first_alloc, attempts):
        pass


# ------------------------------------------------------------ segment math
def test_plan_invariants_and_integrals():
    p = ReservationPlan(((0.5, 2.0), (1.0, 4.0)))
    assert p.k == 2 and p.peak_gb == 4.0 and p.start_gb == 2.0
    assert p.integral_frac() == pytest.approx(3.0)
    assert p.integral_frac(0.75) == pytest.approx(2.0)
    assert p.gbh(2.0) == pytest.approx(6.0)
    with pytest.raises(ValueError):
        ReservationPlan(((0.5, 2.0), (0.5, 3.0)))   # non-increasing
    with pytest.raises(ValueError):
        ReservationPlan(((0.5, 2.0),))               # does not reach 1.0


def test_plan_violation_against_curves():
    p = ReservationPlan(((0.5, 2.0), (1.0, 4.0)))
    assert p.covers(((0.5, 1.5), (1.0, 3.9)))
    assert p.first_violation(((0.25, 2.5), (1.0, 3.0))) == 0.0
    assert p.first_violation(((0.5, 1.0), (1.0, 5.0))) == 0.5
    # the pure-math layer treats an empty curve as unconstrained; the
    # LEDGER substitutes flat-at-peak (see the curveless test below)
    assert p.covers(())


def test_plan_simplify_collapses_equal_segments():
    flat = ReservationPlan(((0.25, 2.0), (0.5, 2.0), (1.0, 2.0)))
    assert flat.simplify().k == 1
    keep = ReservationPlan(((0.5, 2.0), (1.0, 3.0)))
    assert keep.simplify() is keep


def test_grid_profile_exact_for_step_curves():
    g = grid_profile(((0.25, 1.0), (0.6, 3.0), (1.0, 2.0)), 8)
    assert np.allclose(g, [1, 1, 3, 3, 3, 2, 2, 2])
    # empty curve: flat at the peak
    assert np.allclose(grid_profile((), 4, peak_gb=7.0), 7.0)


def test_changepoint_sweep_recovers_step_boundary():
    profs = np.stack([grid_profile(((0.5, 1.0), (1.0, 3.0)), 16)] * 4)
    assert fit_boundaries(profs, 2) == (0.5, 1.0)
    assert np.allclose(segment_peaks(profs[0], (0.5, 1.0)), [1.0, 3.0])
    # degenerate inputs stay well-formed
    assert fit_boundaries(np.ones((3, 8)), 3)[-1] == 1.0
    assert fit_boundaries(profs, 1) == (1.0,)
    assert uniform_boundaries(4) == (0.25, 0.5, 0.75, 1.0)


def test_changepoint_sweep_beats_uniform_on_skewed_ramp():
    # a late steep ramp: uniform quarters over-reserve the long flat head;
    # the sweep must place boundaries at least as well as uniform
    curve = ((0.75, 1.0), (0.85, 4.0), (1.0, 9.0))
    profs = np.stack([grid_profile(curve, 32)] * 3)

    def over_reservation(bounds):
        total, lo = 0.0, 0.0
        for end, pk in zip(bounds, segment_peaks(profs[0], bounds)):
            total += sum(pk - v for v in profs[0][int(lo * 32):int(end * 32)])
            lo = end
        return total

    fitted = fit_boundaries(profs, 4)
    assert over_reservation(fitted) <= over_reservation(
        uniform_boundaries(4)) + 1e-9


# ------------------------------------------------------ usage-curve traces
def test_generator_curves_calibrated_and_isolated():
    t_on = generate_workflow("iwd", scale=0.05)
    t_off = generate_workflow("iwd", scale=0.05, usage_curves=False)
    # separate rng stream: peaks/runtimes identical with curves on or off
    for a, b in zip(t_on.tasks, t_off.tasks):
        assert a.actual_peak_gb == b.actual_peak_gb
        assert a.runtime_h == b.runtime_h
        assert b.usage_curve == ()
    for t in t_on.tasks:
        assert t.usage_curve[-1][0] == pytest.approx(1.0)
        assert max(g for _, g in t.usage_curve) == \
            pytest.approx(t.actual_peak_gb)
        # the integral metric the subsystem optimizes is well-defined
        assert 0.0 < t.usage_gbh() <= t.actual_peak_gb * t.runtime_h + 1e-9
    assert t_on.summary()["has_usage_curves"]
    assert not t_off.summary()["has_usage_curves"]


def test_generator_curves_thread_seed_and_shapes():
    a = generate_workflow("iwd", scale=0.05, seed=1, curve_shapes=("ramp",))
    b = generate_workflow("iwd", scale=0.05, seed=2, curve_shapes=("ramp",))
    c = generate_workflow("iwd", scale=0.05, seed=1, curve_shapes=("ramp",))
    assert any(x.usage_curve != y.usage_curve
               for x, y in zip(a.tasks, b.tasks))
    assert all(x.usage_curve == y.usage_curve
               for x, y in zip(a.tasks, c.tasks))
    # ramps rise: the back half of the curve carries the peak and sits
    # well above the front half on average (noise may jitter single cells)
    for t in a.tasks:
        gbs = [g for _, g in t.usage_curve]
        half = len(gbs) // 2
        assert max(gbs[half:]) == pytest.approx(t.actual_peak_gb)
        assert np.mean(gbs[half:]) > np.mean(gbs[:half])


# --------------------------------------------------- plan-aware accounting
def test_ledger_temporal_success_and_failure_arithmetic():
    curve = ((0.5, 4.0), (1.0, 10.0))
    task = _task(actual=10.0, runtime=1.0, curve=curve)
    led = AttemptLedger(task, 10.0, 128.0, 1.0)
    led.set_plan(ReservationPlan(((0.5, 5.0), (1.0, 10.0))))
    assert led.temporal_active and led.start_alloc_gb == 5.0
    assert led.will_succeed
    led.record_success()
    assert led.tw_gbh == pytest.approx(7.5 - 7.0)
    assert led.wastage_gbh == pytest.approx(led.tw_gbh)

    # under-covering plan dies at the crossing, burns the partial integral
    led2 = AttemptLedger(task, 8.0, 128.0, 1.0)
    led2.set_plan(ReservationPlan(((0.5, 5.0), (1.0, 8.0))))
    assert not led2.will_succeed
    assert led2.violation_frac == 0.5
    assert led2.attempt_duration_h == pytest.approx(0.5)   # not ttf-scaled
    assert not led2.record_failure()
    assert led2.wastage_gbh == pytest.approx(2.5)
    assert led2.runtime_h == pytest.approx(0.5)

    class Doubler:
        def retry(self, task, attempt, last):
            return last * 2
    led2.apply_retry(Doubler())
    assert led2.plan is None          # retries are flat
    assert led2.will_succeed          # 16 GB covers the 10 GB peak


def test_ledger_single_segment_plan_is_flat_path():
    task = _task(actual=10.0, runtime=1.0,
                 curve=((0.5, 2.0), (1.0, 10.0)))
    led = AttemptLedger(task, 8.0, 128.0, 0.5)
    led.set_plan(ReservationPlan(((1.0, 8.0),)))
    assert not led.temporal_active
    assert led.attempt_duration_h == pytest.approx(0.5 * 1.0)  # ttf applies
    led.record_failure()
    assert led.wastage_gbh == pytest.approx(8.0 * 0.5)


def test_ledger_grow_failure_flattens_after_limit():
    task = _task(actual=10.0, runtime=1.0, curve=((0.5, 4.0), (1.0, 10.0)))
    led = AttemptLedger(task, 10.0, 128.0, 1.0)
    for i in range(MAX_GROW_FAILURES):
        led.set_plan(ReservationPlan(((0.5, 5.0), (1.0, 10.0))))
        led.record_grow_failure(0.5)
    assert led.plan is None           # flattened: guaranteed progress
    assert led.grow_failures == MAX_GROW_FAILURES
    assert led.failures == 0          # interruptions, not OOMs
    assert led.interruptions == MAX_GROW_FAILURES
    assert led.tw_gbh == pytest.approx(MAX_GROW_FAILURES * 2.5)


def test_multisegment_plan_on_curveless_task_must_cover_peak():
    # empty usage_curve = flat at the peak: a multi-segment plan whose
    # peak under-covers actual_peak_gb must OOM, not "succeed" with
    # negative waste (review regression)
    task = _task(actual=10.0, runtime=1.0, curve=())
    led = AttemptLedger(task, 4.0, 128.0, 1.0)
    led.set_plan(ReservationPlan(((0.5, 2.0), (1.0, 4.0))))
    assert not led.will_succeed
    assert led.violation_frac == 0.0
    # a plan covering the flat peak everywhere succeeds with tw >= 0
    led2 = AttemptLedger(task, 12.0, 128.0, 1.0)
    led2.set_plan(ReservationPlan(((0.5, 12.0), (1.0, 10.0))))
    assert led2.will_succeed
    led2.record_success()
    assert led2.tw_gbh == pytest.approx(1.0)


def test_tw_equals_wastage_on_curveless_traces():
    trace = generate_workflow("iwd", scale=0.05, usage_curves=False)
    r = simulate(trace, make_method("witt_lr"))
    for o in r.outcomes:
        assert o.tw_gbh == pytest.approx(o.wastage_gbh)
    assert r.temporal_wastage_gbh == pytest.approx(r.wastage_gbh)


# ------------------------------------------------------- RESIZE execution
def test_cluster_resize_shrink_grow_and_exact_accounting():
    curve = ((0.5, 4.0), (1.0, 10.0))
    t = _task(actual=10.0, runtime=1.0, curve=curve)
    trace = WorkflowTrace("wf", [t], machine_cap_gb=128.0)
    r = simulate_cluster(trace, FixedPlanMethod(((0.5, 5.0), (1.0, 10.0))),
                         n_nodes=1)
    assert r.cluster.n_resizes == 1
    assert r.cluster.n_grow_failures == 0
    o = r.outcomes[0]
    assert o.failures == 0 and not o.aborted
    assert o.tw_gbh == pytest.approx(0.5)
    # serial and cluster agree on temporal arithmetic
    rs = simulate(trace, FixedPlanMethod(((0.5, 5.0), (1.0, 10.0))))
    assert rs.outcomes[0].tw_gbh == pytest.approx(o.tw_gbh)
    assert rs.outcomes[0].wastage_gbh == pytest.approx(o.wastage_gbh)


def test_cluster_grow_failure_requeues_and_completes():
    # two growers on one 16 GB node: the second grow is denied, requeues,
    # re-runs, and both finish without any OOM accounting
    curve = ((0.5, 3.0), (1.0, 11.0))
    tasks = [_task(idx=i, actual=11.0, runtime=1.0, curve=curve)
             for i in range(2)]
    trace = WorkflowTrace("wf", tasks, machine_cap_gb=16.0)
    r = simulate_cluster(trace, FixedPlanMethod(((0.5, 4.0), (1.0, 12.0))),
                         n_nodes=1, node_cap_gb=16.0)
    c = r.cluster
    assert c.n_grow_failures == 1 and c.n_resizes == 2
    assert all(o.failures == 0 and not o.aborted for o in r.outcomes)
    assert sum(o.interruptions for o in r.outcomes) == 1
    assert sum(o.grow_failures for o in r.outcomes) == 1
    assert c.makespan_h == pytest.approx(1.5)   # denied grower serialized


def test_cluster_temporal_oom_dies_at_crossing():
    curve = ((0.5, 4.0), (1.0, 10.0))
    t = _task(actual=10.0, runtime=1.0, curve=curve)
    trace = WorkflowTrace("wf", [t], machine_cap_gb=128.0)
    r = simulate_cluster(trace, FixedPlanMethod(((0.5, 5.0), (1.0, 8.0))),
                         n_nodes=1)
    o = r.outcomes[0]
    assert o.failures == 1 and not o.aborted
    # burned the plan integral up to the 0.5 crossing, then flat 16 GB
    assert o.wastage_gbh == pytest.approx(2.5 + (16.0 - 10.0) * 1.0)
    assert o.finish_h == pytest.approx(0.5 + 1.0)
    assert r.cluster.n_resizes == 0   # died at the boundary


def test_resize_disabled_matches_legacy_engine_bitwise():
    # a 1-segment plan must take the EXACT legacy path: same events, same
    # arithmetic, zero resize machinery
    tasks = [_task(idx=i, actual=4.0 + 3 * i, runtime=0.5 + 0.25 * i,
                   curve=((0.5, 2.0 + i), (1.0, 4.0 + 3 * i)))
             for i in range(4)]
    trace = WorkflowTrace("wf", tasks, machine_cap_gb=128.0)

    flat = simulate_cluster(trace, FixedPlanMethod(((1.0, 8.0),)), ttf=0.5,
                            n_nodes=2)

    class Legacy(SizingMethod):
        name = "legacy"

        def allocate(self, task):
            return 8.0

        def retry(self, task, attempt, last):
            return last * 2

        def complete(self, *a):
            pass

    legacy = simulate_cluster(trace, Legacy(), ttf=0.5, n_nodes=2)
    assert flat.cluster.n_resizes == 0
    for a, b in zip(flat.outcomes, legacy.outcomes):
        assert a.wastage_gbh == b.wastage_gbh    # bitwise, not approx
        assert a.tw_gbh == b.tw_gbh
        assert a.attempts == b.attempts
        assert a.finish_h == b.finish_h


# ------------------------------------------- temporal Sizey, k=1 bitwise
def test_temporal_k1_bitwise_equals_peak_sizey_serial_and_cluster():
    trace = generate_workflow("iwd", scale=0.05)
    peak = simulate(trace, SizeyMethod(_cfg()))
    k1 = simulate(trace, SizeyMethod(_cfg(), temporal_k=1))
    for a, b in zip(peak.outcomes, k1.outcomes):
        assert a.first_alloc_gb == b.first_alloc_gb   # bitwise
        assert a.final_alloc_gb == b.final_alloc_gb
        assert a.wastage_gbh == b.wastage_gbh
        assert a.tw_gbh == b.tw_gbh
        assert a.attempts == b.attempts

    cpeak = simulate_cluster(trace, SizeyMethod(_cfg()), n_nodes=4)
    ck1 = simulate_cluster(trace, SizeyMethod(_cfg(), temporal_k=1),
                           n_nodes=4)
    assert ck1.cluster.n_resizes == 0
    for a, b in zip(cpeak.outcomes, ck1.outcomes):
        assert a.first_alloc_gb == b.first_alloc_gb
        assert a.wastage_gbh == b.wastage_gbh
        assert a.finish_h == b.finish_h


def test_temporal_sizey_reduces_time_integrated_waste_on_ramps():
    # the acceptance headline, at test scale: k-segment Sizey wastes less
    # GB·h than peak-based Sizey on ramp-shaped traces (the bench tracks
    # the same number at larger scale in BENCH_temporal.json)
    trace = generate_workflow("mag", scale=0.03, curve_shapes=("ramp",))
    peak = simulate(trace, SizeyMethod(_cfg()))
    temp = simulate(trace, SizeyMethod(_cfg(), temporal_k=4))
    assert temp.temporal_wastage_gbh < peak.temporal_wastage_gbh
    # and the win comes from following the ramp, not from under-covering:
    # aborts would show up as runaway failures
    assert temp.n_failures < 4 * len(trace.tasks)


def test_temporal_sizey_resizes_on_cluster():
    trace = generate_workflow("mag", scale=0.02, curve_shapes=("ramp",))
    r = simulate_cluster(trace, SizeyMethod(_cfg(), temporal_k=4),
                         n_nodes=4)
    assert r.cluster.n_resizes > 0
    assert len(r.outcomes) == len(trace.tasks)


def test_ks_plus_emits_plans_and_beats_presets_on_ramps():
    trace = generate_workflow("iwd", scale=0.1, curve_shapes=("ramp",))
    ks = simulate(trace, make_method("ks_plus"))
    presets = simulate(trace, make_method("workflow_presets"))
    assert ks.temporal_wastage_gbh < presets.temporal_wastage_gbh
    m = make_method("ks_plus")
    # warm the pool, then check an actual multi-segment plan comes out
    for t in trace.tasks[:20]:
        m.allocate(t)
        m.complete(t, t.actual_peak_gb, 1)
    warm = next(t for t in trace.tasks
                if len(m._profiles.get((t.task_type, t.machine), ())) >= 3)
    m.allocate(warm)
    plan = m.plan_for(warm)
    assert plan is not None and plan.k > 1
    assert plan.peak_gb <= 128.0


def test_ks_plus_keeps_learning_after_window_saturates(monkeypatch):
    # review regression: the segment-model cache must invalidate per
    # completion, not key on len(profiles) — the window saturates there
    import repro.baselines.ks_plus as ks_mod
    monkeypatch.setattr(ks_mod, "PROFILE_WINDOW", 4)
    m = make_method("ks_plus")

    def feed(peak, n, start):
        for i in range(n):
            t = _task(idx=start + i, actual=peak, runtime=1.0,
                      curve=((0.5, 0.4 * peak), (1.0, peak)),
                      input_gb=2.0 + 0.01 * i)
            m.complete(t, peak, 1)

    feed(2.0, 6, 0)            # saturate the window at small peaks
    probe = _task(idx=90, actual=2.0, input_gb=2.0)
    m.allocate(probe)
    small = m.plan_for(probe).peak_gb
    feed(50.0, 6, 10)          # regime shift AFTER saturation
    m.allocate(probe)
    assert m.plan_for(probe).peak_gb > small * 5, \
        "segment models froze after the profile window saturated"


# ----------------------------------------------- batched observe dispatch
def test_completion_wave_batches_observe_dispatches():
    # 12 same-type tasks, same runtime, 12 nodes: all finish in ONE event
    # drain -> one complete_batch -> ONE fused observe dispatch
    tasks = [dataclasses.replace(_task(idx=i, actual=4.0, runtime=1.0),
                                 input_size_gb=1.0 + 0.1 * i)
             for i in range(12)]
    trace = WorkflowTrace("wf", tasks, machine_cap_gb=128.0)
    method = SizeyMethod(_cfg())
    with obs.scoped_counters(DISPATCH_COUNTS) as dc:
        r = simulate_cluster(trace, method, n_nodes=12)
        observed = dc["observe_pool"]
    assert r.cluster.n_complete_waves == 1
    assert observed == 1   # 12 completions, one fused fit
    # the sequential path would have paid one dispatch per post-warmup task
    assert method.predictor._fit_serial[("A", "m")] == 10


def test_observe_dispatches_bounded_by_completion_waves():
    trace = generate_workflow("iwd", scale=0.05)
    n_pools = len({(t.task_type, t.machine) for t in trace.tasks})
    with obs.scoped_counters(DISPATCH_COUNTS) as dc:
        r = simulate_cluster(trace, SizeyMethod(_cfg()), n_nodes=4)
        observed = dc["observe_pool"]
    m = r.cluster
    assert m.n_complete_waves >= 1
    assert observed <= m.n_complete_waves * n_pools


def test_observe_batch_bitwise_matches_sequential_observes():
    cfg = _cfg()
    rng = np.random.default_rng(0)
    p_seq = SizeyPredictor(cfg)
    p_bat = SizeyPredictor(cfg)
    for _ in range(4):
        wave = [(float(x), float(2 * x + 1 + rng.normal(0, 0.1)), 0.5)
                for x in rng.uniform(1, 8, 3)]
        d_seq = [p_seq.predict("t", "m", (x,), 32.0) for x, _, _ in wave]
        d_bat = [p_bat.predict("t", "m", (x,), 32.0) for x, _, _ in wave]
        for d, (x, y, rt) in zip(d_seq, wave):
            p_seq.observe(d, y, rt)
        p_bat.observe_batch([(d, y, rt, 1, "")
                             for d, (x, y, rt) in zip(d_bat, wave)])
    a = p_seq.predict("t", "m", (4.5,), 32.0)
    b = p_bat.predict("t", "m", (4.5,), 32.0)
    assert a.allocation_gb == b.allocation_gb    # bitwise
    assert a.offset_gb == b.offset_gb
    assert p_seq._fit_serial == p_bat._fit_serial


# ------------------------------------------------- checkpoint round-trip
def test_temporal_checkpoint_roundtrip_resumes_warm(tmp_path):
    """Satellite: JSONL persistence of temporal segment state — a restore
    must resume with warm per-segment offsets, the fitted boundaries, and
    an intact prequential log."""
    from repro.core.temporal.predictor import TemporalSizeyPredictor

    path = str(tmp_path / "prov.jsonl")
    cfg = _cfg()
    rng = np.random.default_rng(2)
    p = TemporalSizeyPredictor(cfg, k_segments=3, persist_path=path)
    tasks = []
    for i, x in enumerate(rng.uniform(1, 8, 10)):
        peak = float(2 * x + 1)
        tasks.append(_task(idx=i, actual=peak, runtime=0.5, input_gb=float(x),
                           curve=((0.4, 0.3 * peak), (0.8, 0.7 * peak),
                                  (1.0, peak))))
    for t in tasks:
        d = p.predict(t)
        p.observe(d, t, 1)

    probe = _task(idx=99, actual=9.0, runtime=0.5, input_gb=4.0)
    live = p.predict(probe)
    key = (probe.task_type, probe.machine)
    pool = p.db.pool(*key)
    assert pool.log_count > 0

    p2 = TemporalSizeyPredictor(cfg, k_segments=3, persist_path=path)
    pool2 = p2.db.pool(*key)
    # intact buffers + prequential log
    assert pool2.count == pool.count
    assert pool2.log_count == pool.log_count
    np.testing.assert_array_equal(np.asarray(pool2.log_agg),
                                  np.asarray(pool.log_agg))
    # boundary fits resume from the replayed profiles
    assert p2.boundaries(*key) == p.boundaries(*key)
    # warm per-segment offsets: the restored decision cache reproduces the
    # live predictor's plan bitwise (same offsets, same gated aggregates)
    restored = p2.predict(probe)
    assert restored.plan.segments == live.plan.segments
    assert [d.offset_gb for d in restored.seg_decisions] == \
        [d.offset_gb for d in live.seg_decisions]
    assert restored.source == "model"


def test_sizey_method_temporal_persistence_wiring(tmp_path):
    path = str(tmp_path / "m.jsonl")
    m = SizeyMethod(_cfg(), temporal_k=2, persist_path=path)
    trace = generate_workflow("iwd", scale=0.03, curve_shapes=("ramp",))
    simulate(trace, m)
    import os
    assert os.path.getsize(path) > 0
    m2 = SizeyMethod(_cfg(), temporal_k=2, persist_path=path)
    t = trace.tasks[0]
    alloc = m2.allocate(t)
    assert alloc > 0
    assert m2.plan_for(t) is not None


def test_persistence_restores_warm_for_peak_and_k1(tmp_path):
    # review regression: persist_path must be honored by the NON-temporal
    # branch too, and a temporal_k=1 checkpoint (no curve rows) must
    # restore warm exactly like the peak predictor's
    trace = generate_workflow("iwd", scale=0.03)
    probe = trace.tasks[0]
    allocs = {}
    for label, kw in (("peak", {}), ("k1", {"temporal_k": 1})):
        path = str(tmp_path / f"{label}.jsonl")
        simulate(trace, SizeyMethod(_cfg(), persist_path=path, **kw))
        m2 = SizeyMethod(_cfg(), persist_path=path, **kw)
        allocs[label] = m2.allocate(probe)
        pool = m2.predictor.db.pool(probe.task_type, probe.machine)
        assert pool.count >= 3
        assert m2._pending[id(probe)].source == "model", \
            f"{label} restore must resume warm, not preset"
    assert allocs["peak"] == allocs["k1"]   # bitwise, both warm


# --------------------------------------------- fused temporal sizing path
def _curve_task(idx, peak, input_gb):
    return _task(idx=idx, actual=peak, runtime=1.0, input_gb=input_gb,
                 curve=((0.4, 0.3 * peak), (0.8, 0.7 * peak), (1.0, peak)))


def test_boundary_cache_one_fit_per_pool_generation():
    """Retries and same-wave siblings must hit the generation-keyed
    boundary cache; only an observed completion (generation bump) may
    trigger a refit."""
    from repro.core.temporal.predictor import (BOUNDARY_COUNTS,
                                               TemporalSizeyPredictor)
    p = TemporalSizeyPredictor(_cfg(), k_segments=3)
    for i in range(4):
        t = _curve_task(i, 4.0 + i, 1.0 + i)
        p.observe(p.predict(t), t, 1)

    with obs.scoped_counters(BOUNDARY_COUNTS) as bc:
        b1 = p.boundaries("A", "m")          # stale after the observes
        assert bc["fit"] == 1
        assert p.boundaries("A", "m") == b1  # retry of the same attempt
        assert bc["fit"] == 1
        assert bc["hit"] == 1
        # a wave of siblings: one boundaries() ask per task, zero refits
        wave = [_curve_task(10 + i, 6.0, 2.0) for i in range(3)]
        ds = p.predict_batch(wave)
        assert all(d.boundaries == b1 for d in ds)
        assert bc["fit"] == 1
        assert bc["hit"] == 4
        # an observed completion bumps the generation: exactly one refit
        p.observe_batch([(ds[0], wave[0], 1)])
        p.boundaries("A", "m")
        p.boundaries("A", "m")
        assert bc["fit"] == 2


def test_warm_start_rebuilds_boundary_cache(tmp_path):
    """A restored predictor must come up with a WARM boundary cache: the
    ctor refits each replayed pool once, so the first scheduling wave
    after a resume pays zero boundary fits."""
    from repro.core.temporal.predictor import (BOUNDARY_COUNTS,
                                               TemporalSizeyPredictor)
    path = str(tmp_path / "prov.jsonl")
    cfg = _cfg()
    p = TemporalSizeyPredictor(cfg, k_segments=3, persist_path=path)
    for i in range(5):
        t = _curve_task(i, 3.0 + i, 1.0 + 0.5 * i)
        p.observe(p.predict(t), t, 1)
    b_live = p.boundaries("A", "m")

    p2 = TemporalSizeyPredictor(cfg, k_segments=3, persist_path=path)
    with obs.scoped_counters(BOUNDARY_COUNTS) as bc:
        assert p2.boundaries("A", "m") == b_live
        assert bc["fit"] == 0, \
            "restore must pre-fit the cache, not defer to the first ask"
        assert bc["hit"] == 1


def test_amortized_refit_schedule_bounds_full_retrains():
    """With ``refit_growth = r`` the observe half may fully retrain only
    when the history grew by the fraction r since the last fit (or the
    buffers grew); every other completion costs one cheap refresh. The
    dispatch counters must replay that schedule exactly — and come out
    sublinear in n, which is the whole point."""
    import math
    cfg = _cfg(refit_growth=0.5)
    p = SizeyPredictor(cfg)
    rng = np.random.default_rng(0)
    n = 40
    exp_fits = exp_refreshes = 0
    fitted, fit_cap, next_fit = False, None, 0
    with obs.scoped_counters(DISPATCH_COUNTS) as dc:
        for i, x in enumerate(rng.uniform(1, 8, n)):
            d = p.predict("t", "m", (float(x),), 32.0)
            p.observe(d, float(2 * x + 1), 1.0, 1)
            pool = p.db.pool("t", "m")
            if pool.count < cfg.min_history:
                continue                     # below min_history: no work
            if not fitted or fit_cap != pool.cap or pool.count >= next_fit:
                exp_fits += 1
                fitted, fit_cap = True, pool.cap
                next_fit = pool.count + max(
                    1, math.ceil(cfg.refit_growth * pool.count))
            else:
                exp_refreshes += 1
        fits = dc["observe_pool"]
        refreshes = dc["refresh_pool"]
    assert fits == exp_fits
    assert refreshes == exp_refreshes
    assert fits + refreshes == n - (cfg.min_history - 1)
    assert fits < refreshes                  # sublinear: fits are O(log n)


def test_refit_stride_refresh_keeps_decisions_seen():
    """Between full retrains the fused refresh must still fold every
    completion into offsets/decisions: a prediction after a refresh-only
    observe differs from one made before it (the pool saw the data)."""
    cfg = _cfg(refit_growth=1.0)             # long stride: mostly refresh
    p = SizeyPredictor(cfg)
    rng = np.random.default_rng(1)
    for x in rng.uniform(1, 8, 12):
        d = p.predict("t", "m", (float(x),), 32.0)
        p.observe(d, float(2 * x + 1), 1.0, 1)
    before = p.predict("t", "m", (4.0,), 32.0)
    # a surprising completion, observed in the refresh-only regime
    d = p.predict("t", "m", (4.0,), 32.0)
    p.observe(d, 30.0, 1.0, 1)
    after = p.predict("t", "m", (4.0,), 32.0)
    assert after.source == before.source == "model"
    assert after.allocation_gb != before.allocation_gb


def test_cluster_coalesces_same_clock_resize_wave():
    """Same-clock RESIZE events must drain as ONE wave: with three
    identical plan-driven tasks starting together on three nodes, the
    engine applies all three boundary crossings in a single wave while
    still counting every individual resize."""
    plan = ((0.5, 5.0), (1.0, 10.0))
    curve = ((0.5, 4.0), (1.0, 10.0))
    tasks = [_task(idx=i, actual=10.0, runtime=1.0, curve=curve)
             for i in range(3)]
    trace = WorkflowTrace("wf", tasks, machine_cap_gb=64.0)
    r = simulate_cluster(trace, FixedPlanMethod(plan), n_nodes=3)
    assert r.cluster.n_resizes == 3
    assert r.cluster.n_resize_waves == 1
    assert r.cluster.n_grow_failures == 0
    assert r.n_failures == 0


# ------------------------------------------- zero-width segment regression
def test_plan_tolerates_and_simplifies_zero_width_segments():
    """Coincident grid boundaries (duplicate breakpoints in the usage
    curve) may produce zero-width segments; the plan must construct,
    ``simplify()`` must drop them, and plan-aware accounting must keep
    the temporal machinery active."""
    p = ReservationPlan(((0.4, 2.0), (0.4, 6.0), (1.0, 3.0)))
    assert p.simplify().segments == ((0.4, 2.0), (1.0, 3.0))
    # a zero-width head segment drops too
    q = ReservationPlan(((0.0, 9.0), (1.0, 3.0)))
    assert q.simplify().segments == ((1.0, 3.0),)
    # decreasing ends and all-zero-width plans stay rejected
    with pytest.raises(ValueError):
        ReservationPlan(((0.5, 2.0), (0.4, 3.0)))
    with pytest.raises(ValueError):
        ReservationPlan(((0.0, 2.0), (0.0, 3.0)))   # all zero-width
    # the ledger keeps a simplified two-segment plan temporal
    task = _task(actual=2.5, runtime=1.0,
                 curve=((0.4, 2.0), (1.0, 2.5)))
    led = AttemptLedger(task, 6.0, 128.0, 1.0)
    led.set_plan(ReservationPlan(((0.4, 6.0), (0.4, 5.0), (1.0, 3.0))))
    assert led.plan is not None and led.plan.k == 2

    # duplicate breakpoints in a usage curve fit cleanly end to end
    dup_curve = ((0.3, 1.0), (0.3, 4.0), (1.0, 4.0))
    profs = np.stack([grid_profile(dup_curve, 32) for _ in range(4)])
    bounds = fit_boundaries(profs, 4)
    assert bounds[-1] == 1.0
    assert all(b2 > b1 for b1, b2 in zip(bounds, bounds[1:]))
