"""Durable scheduler (PR 6): event journal + crash-resume + chaos sweep,
mid-plan resumption of temporal attempts, atomic provenance writes,
combined failure modes (crash during RESIZE waves / unrepaired rack
outages), and the multi-tenant scheduler service."""
import asyncio
import json
import os

import pytest

from chaos import (assert_results_equal, kill_and_resume, kill_at,
                   kill_points, run_journaled)
from repro.baselines.sizey_method import SizeyMethod
from repro.core.provenance import (ProvenanceDB, atomic_rewrite_jsonl,
                                   read_jsonl_lines)
from repro.core.temporal.segments import ReservationPlan
from repro.serving.scheduler_service import (AdmissionError,
                                             SchedulerService,
                                             TransientRejection)
from repro.workflow import generate_workflow
from repro.workflow.accounting import AttemptLedger
from repro.workflow.cluster import (_RESIZE, ClusterEngine,
                                    simulate_cluster)
from repro.workflow.journal import Journal, recover_run
from repro.workflow.simulator import SizingMethod
from repro.workflow.trace import TaskInstance, WorkflowTrace

CAP = 64.0
SCALE = 0.04


def _task(tt="A", idx=0, actual=10.0, runtime=1.0, deps=(), arrival=0.0,
          preset=64.0, curve=()):
    return TaskInstance("wf", tt, "m", 1.0, actual, runtime, preset, 0,
                        idx, arrival_h=arrival, deps=deps,
                        usage_curve=curve)


def make_peak(path=None):
    return SizeyMethod(machine_cap_gb=CAP, persist_path=path)


def make_temporal_ckpt(path=None):
    return SizeyMethod(machine_cap_gb=CAP, persist_path=path,
                       temporal_k=4, failure_strategy="checkpoint")


FAIL_KW = dict(n_nodes=4, fail_rate_per_node_h=0.05, straggler_rate=0.1)
RACK_KW = dict(node_cap_gb=CAP, policy="backfill",
               fail_rate_per_node_h=0.04, rack_fail_rate_per_h=0.8,
               rack_repair_h=3.0, straggler_rate=0.1)


@pytest.fixture(scope="module")
def peak_run(tmp_path_factory):
    """One journaled failure-injected run + its unjournaled twin."""
    trace = generate_workflow("eager", seed=3, scale=SCALE,
                              machine_cap_gb=CAP)
    d = tmp_path_factory.mktemp("chaos_peak")
    path = str(d / "run.jsonl")
    baseline = run_journaled(trace, make_peak, path, snapshot_every=8,
                             **FAIL_KW)
    return trace, path, baseline


@pytest.fixture(scope="module")
def temporal_run(tmp_path_factory):
    """Journaled temporal/checkpoint run with rack outages: in-flight
    ReservationPlans, RESIZE events, and crash-ownership tokens all end
    up in snapshots."""
    from repro.workflow.cluster import node_specs_from_caps
    trace = generate_workflow("eager", seed=5, scale=SCALE,
                              machine_cap_gb=CAP)
    specs = node_specs_from_caps([CAP], n_nodes=4, n_racks=2)
    d = tmp_path_factory.mktemp("chaos_temporal")
    path = str(d / "run.jsonl")
    kw = dict(RACK_KW, node_specs=specs)
    kw.pop("node_cap_gb")
    # snapshot after EVERY step: the combined-failure tests below cut the
    # file right after a snapshot exposing the state they target
    baseline = run_journaled(trace, make_temporal_ckpt, path,
                             snapshot_every=1, **kw)
    return trace, path, baseline, kw


# --------------------------------------------- journaling is observation
def test_journaled_run_is_bitwise_unjournaled(peak_run):
    trace, _path, baseline = peak_run
    plain = simulate_cluster(trace, make_peak(), **FAIL_KW)
    assert_results_equal(plain, baseline, allow=())
    assert baseline.cluster.n_recoveries == 0
    assert baseline.cluster.n_replayed_steps == 0


# --------------------------------------------------- kill-point sweep
@pytest.mark.parametrize("point", range(8))
def test_warm_resume_bitwise_at_any_kill_point(peak_run, tmp_path, point):
    # seeded sweep over byte offsets: step boundaries, mid-step orphans,
    # torn lines — every one must recover to the EXACT uninterrupted
    # SimResult (only the recovery counters may differ)
    trace, path, baseline = peak_run
    cuts = kill_points(path, 8, seed=11)
    cut = cuts[point % len(cuts)]
    res, eng = kill_and_resume(path, cut, trace, make_peak,
                               scratch=str(tmp_path / "cut.jsonl"))
    assert_results_equal(baseline, res)
    assert res.cluster.n_recoveries == 1


@pytest.mark.parametrize("point", range(5))
def test_warm_resume_bitwise_temporal_checkpoint(temporal_run, tmp_path,
                                                 point):
    trace, path, baseline, _kw = temporal_run
    cuts = kill_points(path, 5, seed=7)
    cut = cuts[point % len(cuts)]
    res, _eng = kill_and_resume(path, cut, trace, make_temporal_ckpt,
                                scratch=str(tmp_path / "cut.jsonl"))
    assert_results_equal(baseline, res)


def test_double_crash_recovery(peak_run, tmp_path):
    trace, path, baseline = peak_run
    scratch = str(tmp_path / "double.jsonl")
    size = os.path.getsize(path)
    kill_at(path, size // 3, scratch)
    eng = recover_run(scratch, trace, make_peak, snapshot_every=8)
    for _ in range(6):                      # make some post-recovery progress
        if not eng.step():
            break
    blob = open(scratch, "rb").read()       # second SIGKILL, torn mid-line
    open(scratch, "wb").write(blob[:-11])
    res = recover_run(scratch, trace, make_peak, snapshot_every=8).run()
    assert_results_equal(baseline, res)
    assert res.cluster.n_recoveries == 2


def test_cold_resume_reenters_inflight_through_failure_strategy(
        peak_run, tmp_path):
    # the crash took the workers too: in-flight attempts are interrupted
    # at the recovery clock and re-run per the failure strategy — every
    # task still completes, and the interruptions show up in the ledgers
    trace, path, baseline = peak_run
    scratch = str(tmp_path / "cold.jsonl")
    kill_at(path, (2 * os.path.getsize(path)) // 3, scratch)
    eng = recover_run(scratch, trace, make_peak, resume="cold",
                      snapshot_every=8)
    n_interrupted = sum(1 for e in eng.queue
                        if e.ledger is not None and e.ledger.interruptions)
    res = eng.run()
    assert len(res.outcomes) == len(baseline.outcomes)
    assert {o.task.key for o in res.outcomes} == \
        {o.task.key for o in baseline.outcomes}
    assert not any(o.aborted for o in res.outcomes)
    assert res.cluster.n_recoveries == 1
    if n_interrupted:
        assert sum(o.interruptions for o in res.outcomes) \
            > sum(o.interruptions for o in baseline.outcomes)


def test_recover_completed_journal_raises(peak_run):
    trace, path, _baseline = peak_run
    with pytest.raises(ValueError, match="already completed"):
        recover_run(path, trace, make_peak)


def test_recover_wrong_trace_or_method_raises(peak_run, tmp_path):
    trace, path, _baseline = peak_run
    scratch = str(tmp_path / "cut.jsonl")
    kill_at(path, os.path.getsize(path) // 2, scratch)
    other = generate_workflow("eager", seed=99, scale=SCALE,
                              machine_cap_gb=CAP)
    with pytest.raises(ValueError, match="different trace"):
        recover_run(scratch, other, make_peak)
    Journal.repair(scratch)

    def wrong(path):
        return SizeyMethod(machine_cap_gb=CAP, persist_path=path,
                           name="not_the_one")
    with pytest.raises(ValueError, match="written by method"):
        recover_run(scratch, trace, wrong)


# ------------------------------------- combined failure modes (satellite 3)
def _cut_after_snapshot(path, tmp_path, want_state):
    """Cut the journal right after the first snapshot row whose engine
    state satisfies ``want_state``, then recover from the truncated file —
    the recovered (pre-continue) engine restores exactly that snapshot.
    Fails if no snapshot exposes the state: the fixture then isn't
    exercising the targeted failure mode at all."""
    offset = 0
    with open(path) as f:
        for line in f:
            offset += len(line.encode())
            d = json.loads(line)
            if d.get("kind") == "snap" and want_state(d["state"]):
                scratch = str(tmp_path / "probe.jsonl")
                kill_at(path, offset, scratch)
                return scratch
    pytest.fail("no snapshot exposed the wanted engine state")


def test_crash_during_inflight_resize_wave(temporal_run, tmp_path):
    # scheduler dies while RESIZE events for dispatched multi-segment
    # plans are still in the heap: they must survive the journal
    # round-trip and fire identically after resume
    trace, path, baseline, _kw = temporal_run
    scratch = _cut_after_snapshot(
        path, tmp_path,
        lambda s: any(ev[2] == _RESIZE for ev in s["events"]))
    eng = recover_run(scratch, trace, make_temporal_ckpt,
                      snapshot_every=1)
    n_resize = sum(1 for ev in eng.events if ev[2] == _RESIZE)
    assert n_resize >= 1
    out = eng.run()
    assert_results_equal(baseline, out)
    assert out.cluster.n_resizes == baseline.cluster.n_resizes


def test_recovery_with_unrepaired_rack_outage(temporal_run, tmp_path):
    # scheduler dies while a rack outage is still unrepaired: the
    # crash-ownership tokens and downed nodes must survive the journal
    # round-trip, and the rack must come back exactly on schedule
    trace, path, baseline, _kw = temporal_run
    scratch = _cut_after_snapshot(
        path, tmp_path,
        lambda s: s["down_token"] and any(not n["up"] for n in s["nodes"]))
    eng = recover_run(scratch, trace, make_temporal_ckpt,
                      snapshot_every=1)
    assert eng.down_token and eng.down_due
    down_names = [n.name for n in eng.nodes if not n.up]
    out = eng.run()
    assert_results_equal(baseline, out)
    # downed nodes recovered and served work after the outage
    assert all(out.cluster.node_downtime_h[n] > 0 for n in down_names)
    assert out.cluster.rack_downtime_h == baseline.cluster.rack_downtime_h


# -------------------------------- mid-plan resumption (satellite 1) ------
def test_temporal_checkpoint_retains_to_segment_boundary():
    # 1 h task, plan segments ending at 0.25/0.5/1.0, usage under plan
    # everywhere (will succeed). Interrupted at 0.6: under checkpoint the
    # attempt retains to the last plan boundary <= 0.6 (0.5), keeps the
    # plan, and resumes reserving the POST-boundary segment value.
    curve = ((0.25, 2.0), (0.5, 4.0), (1.0, 6.0))
    task = _task(actual=6.0, runtime=1.0, curve=curve)
    led = AttemptLedger(task, 8.0, 128.0, 1.0,
                        failure_strategy="checkpoint",
                        checkpoint_frac=0.25)
    led.set_plan(ReservationPlan(((0.25, 3.0), (0.5, 5.0), (1.0, 7.0))))
    assert led.temporal_active and led.start_alloc_gb == 3.0
    led.record_interruption(0.6)
    assert led.completed_frac == pytest.approx(0.5)
    assert led.plan is not None          # plan survives the interruption
    assert led.interruptions == 1 and led.failures == 0
    # lost work: the reserved integral over (0.5, 0.6] — 0.1 h at 7 GB
    assert led.interruption_gbh == pytest.approx(7.0 * 0.1)
    # wastage adds the retained prefix's headroom (plan minus usage):
    # (3-2)*0.25 + (5-4)*0.25 over [0, 0.5]
    assert led.wastage_gbh == pytest.approx(0.7 + 0.25 + 0.25)
    # the resumed attempt reserves the plan value AT the boundary (the
    # suffix segment), not the plan start and not the flat peak
    assert led.start_alloc_gb == pytest.approx(7.0)
    # and only the remaining fraction of wall time
    assert led.attempt_duration_h == pytest.approx(0.5)
    led.record_success()
    assert led.runtime_h == pytest.approx(0.6 + 0.5)
    # suffix waste: (7-6)*0.5 h headroom over the resumed segment
    assert led.tw_gbh == pytest.approx(0.25 + 0.25 + 7.0 * 0.1 + 0.5)


def test_temporal_checkpoint_no_boundary_restarts_flat():
    # interrupted before the first plan boundary: nothing to retain —
    # the attempt re-runs from scratch with the plan intact
    curve = ((0.5, 4.0), (1.0, 6.0))
    task = _task(actual=6.0, runtime=1.0, curve=curve)
    led = AttemptLedger(task, 8.0, 128.0, 1.0,
                        failure_strategy="checkpoint",
                        checkpoint_frac=0.25)
    led.set_plan(ReservationPlan(((0.5, 5.0), (1.0, 7.0))))
    led.record_interruption(0.3)
    assert led.completed_frac == 0.0
    assert led.plan is not None
    assert led.start_alloc_gb == 5.0     # back to the first segment
    assert led.interruption_gbh == pytest.approx(5.0 * 0.3)
    assert led.attempt_duration_h == pytest.approx(1.0)


def test_temporal_retry_same_never_retains():
    # non-checkpoint strategies: unchanged PR 5 semantics — temporal
    # attempts burn the partial plan integral and restart in full
    curve = ((0.5, 4.0), (1.0, 6.0))
    task = _task(actual=6.0, runtime=1.0, curve=curve)
    led = AttemptLedger(task, 8.0, 128.0, 1.0,
                        failure_strategy="retry_same")
    led.set_plan(ReservationPlan(((0.5, 5.0), (1.0, 7.0))))
    led.record_interruption(0.8)
    assert led.completed_frac == 0.0
    assert led.attempt_duration_h == pytest.approx(1.0)


def test_resumed_plan_schedules_only_remaining_boundaries():
    # engine-level: a checkpoint-retained temporal attempt re-dispatches
    # reserving the boundary segment's value and schedules RESIZE events
    # only for boundaries PAST the resume point, offset by the completed
    # prefix (wall clock: (end - base) * runtime)
    curve = ((0.25, 2.0), (0.5, 4.0), (1.0, 6.0))
    task = _task(actual=6.0, runtime=1.0, curve=curve)

    class PlanMethod(SizingMethod):
        name = "plan"
        failure_strategy = "checkpoint"
        checkpoint_frac = 0.25

        def allocate(self, t):
            return 7.0

        def plan_for(self, t):
            return ReservationPlan(((0.25, 3.0), (0.5, 5.0), (1.0, 7.0)))

        def retry(self, t, attempt, last):
            return last * 2

        def complete(self, t, first, attempts):
            pass

    trace = WorkflowTrace("wf", [task], machine_cap_gb=128.0)
    eng = ClusterEngine(trace, PlanMethod(), n_nodes=1,
                        node_cap_gb=128.0)
    eng.step()                       # arrive + dispatch at clock 0
    assert len(eng.running) == 1
    token = next(iter(eng.running))
    # 2 RESIZE events: boundaries 0.25 and 0.5
    assert sum(1 for ev in eng.events if ev[2] == _RESIZE) == 2
    eng.step()                       # first RESIZE fires at 0.25
    eng._interrupt(token, 0.6)       # crash 0.6 h in -> retained to 0.5
    entry = eng.queue[-1]
    assert entry.ledger.completed_frac == pytest.approx(0.5)
    assert entry.ledger.plan is not None
    eng.step()                       # stale RESIZE drains; re-dispatch
    resizes = [ev for ev in eng.events if ev[2] == _RESIZE]
    assert resizes == []             # no boundary remains past 0.5
    [(e2, n2, started)] = eng.running.values()
    assert n2.held_gb(next(iter(eng.running))) == pytest.approx(7.0)
    res = eng.run()
    [o] = res.outcomes
    assert not o.aborted and o.interruptions == 1


# ------------------------------ atomic provenance writes (satellite 2) ---
def test_read_jsonl_tolerates_torn_final_line(tmp_path):
    p = str(tmp_path / "t.jsonl")
    rows = [json.dumps({"kind": "aux_t", "i": i}) for i in range(4)]
    with open(p, "w") as f:
        f.write("\n".join(rows) + "\n")
        f.write('{"kind": "aux_t", "i": 4, "tr')      # torn mid-write
    lines, torn = read_jsonl_lines(p)
    assert torn and lines == rows
    # the db restores from the intact prefix, loudly
    with pytest.warns(RuntimeWarning, match="torn final"):
        db = ProvenanceDB(persist_path=p)
    assert [r["i"] for r in db.aux["aux_t"]] == [0, 1, 2, 3]


def test_read_jsonl_rejects_midfile_corruption(tmp_path):
    p = str(tmp_path / "t.jsonl")
    with open(p, "w") as f:
        f.write('{"kind": "aux_t", "i": 0}\n')
        f.write('GARBAGE NOT JSON\n')
        f.write('{"kind": "aux_t", "i": 1}\n')
    with pytest.raises(ValueError, match="corrupt"):
        read_jsonl_lines(p)


def test_atomic_rewrite_jsonl(tmp_path):
    p = str(tmp_path / "t.jsonl")
    with open(p, "w") as f:
        f.write("old\n" * 5)
    atomic_rewrite_jsonl(p, ["a", "b"])
    assert open(p).read() == "a\nb\n"
    # no temp litter in the directory
    assert os.listdir(str(tmp_path)) == ["t.jsonl"]


def test_journal_repair_truncates_orphans(peak_run, tmp_path):
    # cut right after a provenance row that FOLLOWS the last WAL row:
    # repair must drop the orphans (rows of the partially executed step)
    trace, path, _baseline = peak_run
    lines = open(path).read().splitlines()
    kinds = [json.loads(ln).get("kind") for ln in lines]
    cut_line = next(i + 1 for i in range(1, len(lines))
                    if kinds[i] not in ("wal", "snap")
                    and kinds[i - 1] == "wal")
    p2 = str(tmp_path / "orphans.jsonl")
    with open(p2, "w") as f:
        f.write("\n".join(lines[:cut_line]) + "\n")
    stats = Journal.repair(p2)
    assert stats["repaired"] and stats["dropped_rows"] >= 1
    last = json.loads(open(p2).read().splitlines()[-1])
    assert last["kind"] in ("wal", "snap")
    # idempotent: repairing a repaired file changes nothing
    assert Journal.repair(p2) == {"repaired": False, "dropped_rows": 0,
                                  "torn_final_line": False}


def test_journal_repair_keeps_completed_run(peak_run, tmp_path):
    trace, path, _baseline = peak_run
    p2 = str(tmp_path / "done.jsonl")
    open(p2, "w").write(open(path).read())
    stats = Journal.repair(p2)
    assert stats == {"repaired": False, "dropped_rows": 0,
                     "torn_final_line": False}
    assert open(p2).read() == open(path).read()


# --------------------------------------------- scheduler service ---------
def _small_trace(seed=2, scale=0.02):
    return generate_workflow("eager", seed=seed, scale=scale,
                             machine_cap_gb=CAP)


def test_service_runs_workflows_to_completion(tmp_path):
    trace = _small_trace()
    jd = str(tmp_path / "journals")

    async def main():
        svc = SchedulerService(max_concurrent=4, journal_dir=jd,
                               snapshot_every=16)
        svc.add_tenant("a")
        svc.add_tenant("b")
        async with svc:
            ha = await svc.submit("a", trace, method_factory=make_peak,
                                  engine_kwargs={"n_nodes": 4})
            hb = await svc.submit("b", trace, method_factory=make_peak,
                                  engine_kwargs={"n_nodes": 4})
            return await asyncio.gather(ha, hb)

    ra, rb = asyncio.run(main())
    assert len(ra.outcomes) == len(trace.tasks)
    assert len(rb.outcomes) == len(trace.tasks)
    # identical submissions, independent engines: identical results
    assert ra.wastage_gbh == rb.wastage_gbh
    # both ran journaled to completion
    assert SchedulerService.scan_unfinished(jd) == []
    assert len(os.listdir(jd)) == 2


def test_service_weighted_fair_share():
    # same workload, weight 3 vs 1: the heavy tenant gets ~3x the engine
    # steps per scheduling pass, so it finishes first
    trace = _small_trace(scale=0.03)

    async def main():
        svc = SchedulerService(max_concurrent=4)
        svc.add_tenant("heavy", weight=3.0)
        svc.add_tenant("light", weight=1.0)
        order = []
        async with svc:
            hh = await svc.submit("heavy", trace, make_peak(),
                                  engine_kwargs={"n_nodes": 4})
            hl = await svc.submit("light", trace, make_peak(),
                                  engine_kwargs={"n_nodes": 4})
            for h, tag in ((hh, "heavy"), (hl, "light")):
                async def watch(h=h, tag=tag):
                    await h
                    order.append(tag)
                asyncio.ensure_future(watch())
            await asyncio.gather(hh, hl)
            await asyncio.sleep(0)
        return order, svc.stats()

    order, stats = asyncio.run(main())
    assert order[0] == "heavy"
    # both did the same work in total (identical workloads)
    assert stats["heavy"]["steps_granted"] == stats["light"]["steps_granted"]


def test_service_oom_storm_cannot_starve_other_tenant():
    # tenant "storm" burns steps on OOM retries (under-allocating method,
    # x2 retry ladder); tenant "calm" runs a small clean workload. Equal
    # weights: calm's completion must not wait for the storm to drain.
    storm_trace = _small_trace(seed=7, scale=0.06)
    calm_trace = _small_trace(seed=2, scale=0.02)

    class StormMethod(SizingMethod):
        name = "storm"

        def allocate(self, task):
            return max(task.actual_peak_gb / 8.0, 0.1)   # always OOMs

        def retry(self, task, attempt, last):
            return last * 2.0

        def complete(self, task, first, attempts):
            pass

    async def main():
        svc = SchedulerService(max_concurrent=4)
        svc.add_tenant("storm")
        svc.add_tenant("calm")
        async with svc:
            hs = await svc.submit("storm", storm_trace, StormMethod(),
                                  engine_kwargs={"n_nodes": 2})
            hc = await svc.submit("calm", calm_trace, make_peak(),
                                  engine_kwargs={"n_nodes": 2})
            rc = await hc
            storm_still_running = not hs.done
            rs = await hs
        return rc, rs, storm_still_running, svc.stats()

    rc, rs, storm_still_running, stats = asyncio.run(main())
    assert storm_still_running       # calm finished while the storm raged
    assert not any(o.aborted for o in rc.outcomes)
    assert rs.n_failures > 0         # the storm really was a storm
    # calm paid only its own steps: its grant equals a solo run's count
    solo = 0
    eng = ClusterEngine(calm_trace, make_peak(), n_nodes=2)
    while eng.step():
        solo += 1
    assert stats["calm"]["steps_granted"] == solo + 1   # + terminal step


def test_service_admission_backoff_and_rejection():
    big = _small_trace(seed=1, scale=0.05)
    small = _small_trace(seed=2, scale=0.02)

    async def main():
        svc = SchedulerService(max_concurrent=1, max_retries=2,
                               backoff_base_s=0.001, backoff_cap_s=0.002)
        svc.add_tenant("t", max_active=1)
        with pytest.raises(TransientRejection):
            # direct (non-backoff) admission probe while at the cap
            async with svc:
                h1 = await svc.submit("t", big, make_peak(),
                                      engine_kwargs={"n_nodes": 1})
                svc._admit(svc._tenants["t"])
        svc2 = SchedulerService(max_concurrent=1, max_retries=2,
                                backoff_base_s=0.001, backoff_cap_s=0.002)
        svc2.add_tenant("t", max_active=1)
        async with svc2:
            h1 = await svc2.submit("t", big, make_peak(),
                                   engine_kwargs={"n_nodes": 1})
            with pytest.raises(AdmissionError):
                await svc2.submit("t", small, make_peak(),
                                  engine_kwargs={"n_nodes": 1})
            await h1
            # slot freed: the bounded backoff now admits within budget
            h2 = await svc2.submit("t", small, make_peak(),
                                   engine_kwargs={"n_nodes": 1})
            await h2
        assert svc2.stats()["t"]["n_rejected_final"] == 1
        assert svc2.stats()["t"]["n_completed"] == 2

    asyncio.run(main())


def test_service_crash_scan_and_resume(tmp_path):
    # a service crash leaves unfinished journals behind; scan_unfinished
    # lists them and resume() re-admits each mid-workflow — final result
    # bitwise the uninterrupted run
    trace = _small_trace(seed=4, scale=0.03)
    jd = str(tmp_path / "journals")
    os.makedirs(jd)
    base_path = os.path.join(jd, "t-eager-0001.jsonl")
    baseline = run_journaled(trace, make_peak, base_path, snapshot_every=8,
                             n_nodes=2)
    # "crash": truncate the journal to a prefix (and tear the last line)
    blob = open(base_path, "rb").read()
    open(base_path, "wb").write(blob[:len(blob) // 2 + 9])

    async def main():
        assert SchedulerService.scan_unfinished(jd) == [base_path]
        svc = SchedulerService(max_concurrent=2, journal_dir=jd,
                               snapshot_every=8)
        svc.add_tenant("t")
        async with svc:
            h = await svc.resume("t", trace, make_peak, base_path)
            return await h

    res = asyncio.run(main())
    assert_results_equal(baseline, res)
    assert SchedulerService.scan_unfinished(jd) == []
