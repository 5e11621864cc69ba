"""Observability plane: metrics registry back-compat, scoped counters,
span tracing (null cost, parent links, profiler mirroring, determinism,
Perfetto export), prediction-quality telemetry, journal interplay, and
service scrape."""
import asyncio
import collections
import json
import os
import subprocess
import sys

import pytest

from chaos import assert_results_equal, kill_at, run_journaled
from repro import obs
from repro.baselines.sizey_method import SizeyMethod
from repro.core.predictor import DISPATCH_COUNTS, TRACE_COUNTS
from repro.core.temporal.predictor import BOUNDARY_COUNTS
from repro.obs import trace as obs_trace
from repro.obs.quality import (QUALITY_FIELDS, read_quality_rows,
                               summarize_pools)
from repro.obs.trace import _NULL_SPAN
from repro.serving.scheduler_service import SchedulerService
from repro.workflow import generate_workflow, simulate, simulate_cluster
from repro.workflow.journal import Journal

CAP = 64.0


def _small_trace(seed=3, scale=0.02):
    return generate_workflow("eager", seed=seed, scale=scale,
                             machine_cap_gb=CAP)


# ------------------------------------------------------ metrics registry
def test_legacy_counters_are_registry_families():
    # the process globals are genuine Counters (all legacy call sites —
    # dict() snapshots, diff-after reads, jit-time += — keep working)
    # AND registered families (one scrape endpoint sees them)
    for fam, name in ((TRACE_COUNTS, "predictor_trace_total"),
                      (DISPATCH_COUNTS, "predictor_dispatch_total"),
                      (BOUNDARY_COUNTS, "temporal_boundary_total")):
        assert isinstance(fam, obs.CounterFamily)
        assert isinstance(fam, collections.Counter)
        assert fam.name == name
        assert obs.counter(name) is fam   # get-or-create returns the same
    text = obs.scrape()
    assert "# TYPE predictor_dispatch_total counter" in text


def test_registry_kind_mismatch_raises():
    with pytest.raises(TypeError, match="already registered"):
        obs.default_registry().gauge("predictor_dispatch_total")


def test_gauge_set_get_expose():
    g = obs.gauge("test_obs_gauge", "a gauge")
    g.set(3, tenant="a")
    g.set(7.5, tenant="b")
    assert g.get(tenant="a") == 3.0
    assert g.get(tenant="missing") is None
    lines = g.expose()
    assert "# TYPE test_obs_gauge gauge" in lines
    assert 'test_obs_gauge{tenant="b"} 7.5' in lines


def test_scoped_counters_restores_process_totals():
    c = obs.counter("test_obs_scoped_total")
    c["x"] += 5
    with obs.scoped_counters(c) as sc:
        assert sc is c
        assert c["x"] == 0             # counts from zero inside
        c["x"] += 2
    assert c["x"] == 7                 # pre-scope + in-scope


def test_back_to_back_simulations_report_independent_counts():
    """The counter-bleed regression pinned: two identical simulate()
    calls, each bracketed, must report the SAME dispatch counts — not a
    cumulative process total the second run inherits."""
    trace = _small_trace()
    runs = []
    for _ in range(2):
        with obs.scoped_counters(DISPATCH_COUNTS) as dc:
            simulate(trace, SizeyMethod(machine_cap_gb=CAP))
            runs.append((dc["predict_pool"], dc["observe_pool"],
                         dc["decisions"]))
    assert runs[0] == runs[1]
    assert runs[0][1] > 0              # real activity, not two zeros


# --------------------------------------------------------- span tracing
def test_span_is_null_singleton_when_off():
    assert not obs.tracing_active()
    assert obs.span("predict", k=3) is _NULL_SPAN
    with obs.span("predict"):          # still a working context manager
        pass


def test_span_off_creates_no_annotation_and_obs_imports_no_jax(
        monkeypatch):
    made = []
    monkeypatch.setattr(obs_trace, "_ANNOTATION",
                        lambda name: made.append(name))
    assert obs.span("cluster/step", step=0) is _NULL_SPAN
    with obs.async_span("service/admit") as sp:
        sp.set(bytes=1)                # a no-op on the null span
    assert made == []
    code = ("import sys, repro.obs; "
            "sys.exit(int(any(m == 'jax' or m.startswith('jax.') "
            "for m in sys.modules)))")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=60).returncode == 0


def test_spans_link_to_their_parent():
    with obs.tracing() as col:
        with obs.span("root"):
            with obs.span("child") as c:
                with obs.span("leaf"):
                    pass
                c.set(bytes=7)
            with obs.span("sibling"):
                pass
        with obs.span("second_root"):
            pass
    by = {s[0]: s for s in col.spans}
    assert [s[0] for s in col.spans] == ["leaf", "child", "sibling", "root",
                                         "second_root"]
    assert len({s[4] for s in col.spans}) == 5
    assert by["root"][5] is None and by["second_root"][5] is None
    assert by["child"][5] == by["sibling"][5] == by["root"][4]
    assert by["leaf"][5] == by["child"][4]
    assert by["child"][3] == {"bytes": 7}
    # the first four fields keep their meaning: name, start, dur, args
    assert by["root"][1] <= by["child"][1] <= by["leaf"][1]
    assert by["root"][2] >= by["child"][2] >= by["leaf"][2] >= 0
    ev = {e["name"]: e for e in col.to_chrome_trace()["traceEvents"]}
    assert ev["leaf"]["args"] == {"id": by["leaf"][4],
                                  "parent": by["child"][4]}


def test_interleaved_asyncio_tasks_nest_under_their_own_spans():
    async def task(i, gate):
        with obs.async_span("task", i=i):
            await gate.wait()          # both tasks hold their span open
            with obs.span("child", i=i):
                await asyncio.sleep(0)

    async def main():
        gate = asyncio.Event()
        jobs = [asyncio.create_task(task(i, gate)) for i in range(2)]
        await asyncio.sleep(0)
        with obs.span("main"):
            gate.set()
            await asyncio.gather(*jobs)

    with obs.tracing() as col:
        asyncio.run(main())
    tasks = {s[3]["i"]: s for s in col.spans if s[0] == "task"}
    children = [s for s in col.spans if s[0] == "child"]
    assert len(tasks) == 2 and len(children) == 2
    for c in children:
        assert c[5] == tasks[c[3]["i"]][4]
    # a task copies its context when created: no span was open then
    assert all(t[5] is None for t in tasks.values())


def _host_events(log_dir: str) -> list:
    import glob
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, int(e.start_ns), int(e.duration_ns))
                           for e in line.events)
    return out


def test_spans_mirror_as_profiler_annotations(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obs.tracing() as col:
            with obs.span("mirror/anchor"):
                pass
            for i in range(3):
                with obs.span("mirror/step", i=i):
                    with obs.span("mirror/work"):
                        f(x).block_until_ready()
            with obs.async_span("mirror/unmirrored"):
                pass
    finally:
        jax.profiler.stop_trace()
    host = [e for e in _host_events(str(tmp_path))
            if e[0].startswith("mirror/")]
    counts = collections.Counter(e[0] for e in host)
    assert counts == {"mirror/anchor": 1, "mirror/step": 3,
                      "mirror/work": 3}
    # one anchor maps perf_counter_ns onto the profiler's clock
    anchor = next(s for s in col.spans if s[0] == "mirror/anchor")
    shift = next(e[1] for e in host if e[0] == "mirror/anchor") - anchor[1]
    for name in ("mirror/step", "mirror/work"):
        mine = sorted(s[1:3] for s in col.spans if s[0] == name)
        theirs = sorted(e[1:] for e in host if e[0] == name)
        for (start, dur), (pstart, pdur) in zip(mine, theirs):
            assert abs(start + shift - pstart) < 100_000
            assert abs(dur - pdur) < 100_000


def test_observe_span_closes_after_block_until_ready(monkeypatch):
    import jax
    real = jax.block_until_ready

    def waited(x):
        with obs.span("test/block_until_ready"):
            return real(x)
    monkeypatch.setattr(jax, "block_until_ready", waited)
    with obs.tracing() as col:
        simulate(_small_trace(), SizeyMethod(machine_cap_gb=CAP))
    observes = {s[4] for s in col.spans if s[0] == "observe"}
    waits = {s[5] for s in col.spans if s[0] == "test/block_until_ready"}
    assert observes and observes <= waits


def _journaled_temporal_spans(tmp_path, tag):
    trace = _small_trace()
    path = str(tmp_path / f"{tag}.jsonl")

    def factory(p):
        return SizeyMethod(machine_cap_gb=CAP, persist_path=p, temporal_k=4)
    with obs.tracing() as col:
        res = run_journaled(trace, factory, path, snapshot_every=8,
                            n_nodes=4)
    return col, res, path


def test_step_journal_and_history_span_counts_are_deterministic(tmp_path):
    runs = [_journaled_temporal_spans(tmp_path, t) for t in ("a", "b")]
    (col, _, path), (col_b, _, _) = runs
    assert col.span_counts == col_b.span_counts
    n = col.span_counts
    steps = [s for s in col.spans if s[0] == "cluster/step"]
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    wal_steps = sum(r.get("kind") == "wal" and r.get("rec") == "step"
                    for r in rows)
    # one step span per engine step call; the last writes the end marker
    assert n["journal/append"] == wal_steps == n["cluster/step"] - 1
    assert n["cluster/export_state"] == n["journal/snapshot"] \
        == wal_steps // 8
    # per completion wave: one append of its curves (n tasks), then one
    # of each pool's rows (k = 4 segment rows a task): never per task
    waves = {s[4]: s[3]["n"] for s in col.spans
             if s[0] == "engine/complete_wave"}
    appends = collections.defaultdict(list)
    for s in col.spans:
        if s[0] == "history/append":
            appends[s[5]].append(s[3]["n"])
    assert set(appends) == set(waves)
    for wave, n_tasks in waves.items():
        assert appends[wave][0] == n_tasks
        assert sum(appends[wave][1:]) == 4 * n_tasks
        assert 1 <= len(appends[wave]) - 1 <= n_tasks
    step_ids = {s[4] for s in steps}
    for s in col.spans:
        if s[0] in ("journal/append", "cluster/export_state",
                    "journal/snapshot", "engine/complete_wave",
                    "engine/sizing_wave"):
            assert s[5] in step_ids, s
    # each snapshot span carries the bytes of the row it wrote
    with open(path) as f:
        sizes = [len(line) for line in f if '"kind": "snap"' in line]
    assert len(sizes) == n["journal/snapshot"]
    assert [s[3]["bytes"] for s in col.spans
            if s[0] == "journal/snapshot"] == sizes


def test_tracing_scope_restores_previous_collector():
    with obs.tracing() as outer:
        with obs.span("a"):
            pass
        with obs.tracing() as inner:
            with obs.span("b"):
                pass
        assert inner.span_counts == {"b": 1}
        # outer collector is active again after the nested scope
        with obs.span("a"):
            pass
        assert outer.span_counts == {"a": 2}
    assert not obs.tracing_active()


def test_span_counts_deterministic_and_chrome_trace_valid(tmp_path):
    trace = _small_trace()
    counts = []
    for _ in range(2):
        with obs.tracing() as col:
            simulate_cluster(trace, SizeyMethod(machine_cap_gb=CAP),
                             n_nodes=4)
        counts.append(dict(col.span_counts))
    assert counts[0] == counts[1]      # pure function of (trace, config)
    assert counts[0]["engine/complete_wave"] >= 1
    assert counts[0]["observe"] >= 1   # fused predictor dispatches traced

    path = str(tmp_path / "trace.json")
    col.write_chrome_trace(path)
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    assert len(doc["traceEvents"]) == col.total_spans()
    ev = doc["traceEvents"][0]
    assert ev["ph"] == "X" and ev["dur"] >= 0 and ev["ts"] >= 0
    names = {e["name"] for e in doc["traceEvents"]}
    assert "engine/sizing_wave" in names


def test_tracing_is_bitwise_side_effect_free():
    trace = _small_trace()
    res_off = simulate_cluster(trace, SizeyMethod(machine_cap_gb=CAP),
                               n_nodes=4)
    with obs.tracing():
        res_on = simulate_cluster(
            trace, SizeyMethod(machine_cap_gb=CAP, quality=True), n_nodes=4)
    assert_results_equal(res_off, res_on)


# --------------------------------------------------- quality telemetry
def test_quality_rows_one_per_task_with_schema():
    # large enough that pools cross min_history into model-sourced sizing
    trace = _small_trace(scale=0.06)
    method = SizeyMethod(machine_cap_gb=CAP, quality=True)
    simulate(trace, method)
    rows = read_quality_rows(method.predictor.db)
    assert len(rows) == len(trace.tasks)
    assert [r["seq"] for r in rows] == list(range(len(rows)))
    for r in rows:
        assert set(QUALITY_FIELDS) <= set(r)
        assert r["t_h"] == 0.0         # serial runs have no virtual clock
        assert r["under"] in (0, 1)
        assert r["alloc_gb"] > 0 and r["peak_gb"] > 0
    # model-sourced rows carry the selected-model telemetry
    modeled = [r for r in rows if r["raq"] is not None]
    assert modeled, "no model-sourced decisions in the whole run"
    for r in modeled:
        assert r["model"] and r["agg_pred_gb"] is not None
    summary = summarize_pools(rows)
    assert sum(s["n"] for s in summary.values()) == len(rows)


def test_quality_rows_deterministic_and_clock_stamped():
    trace = _small_trace()

    def run():
        m = SizeyMethod(machine_cap_gb=CAP, quality=True)
        simulate_cluster(trace, m, n_nodes=4)
        return read_quality_rows(m.predictor.db)

    a, b = run(), run()
    assert a == b                      # bitwise reproducible
    assert any(r["t_h"] > 0.0 for r in a)   # virtual-clock stamped


def test_quality_rows_survive_journal_repair(tmp_path):
    """A crash mid-journal leaves a byte prefix; after Journal.repair the
    surviving quality rows must be exactly a prefix of the full stream
    (no torn/reordered rows)."""
    from chaos import _quality_method_factory
    trace = _small_trace()
    path = str(tmp_path / "run.jsonl")
    run_journaled(trace, _quality_method_factory, path, n_nodes=4)
    base = read_quality_rows(path)
    assert base
    cut_path = kill_at(path, int(os.path.getsize(path) * 0.6),
                       str(tmp_path / "cut.jsonl"))
    Journal.repair(cut_path)
    got = read_quality_rows(cut_path)
    assert len(got) < len(base)
    assert got == base[:len(got)]


def test_quality_off_by_default_emits_nothing():
    trace = _small_trace()
    method = SizeyMethod(machine_cap_gb=CAP)
    simulate(trace, method)
    assert read_quality_rows(method.predictor.db) == []


# ------------------------------------------------------- service scrape
def test_service_scrape_exposes_tenant_gauges():
    trace = _small_trace()

    async def main():
        svc = SchedulerService(max_concurrent=4)
        svc.add_tenant("genomics", weight=2.0)
        async with svc:
            h = await svc.submit("genomics", trace,
                                 SizeyMethod(machine_cap_gb=CAP),
                                 engine_kwargs={"n_nodes": 4})
            await h
        return svc.scrape()

    text = asyncio.run(main())
    assert "# TYPE scheduler_steps_granted gauge" in text
    assert 'tenant="genomics"' in text
    # the one endpoint also carries the predictor counter families
    assert "predictor_dispatch_total" in text
