"""The readers of the program's engine-step spans, and the device's idle
time that no program span explains."""
import os
import types

import pytest

from chipbench.harness import spec
from chipbench.harness import trace_reduce as tr
from chipbench.harness.serve import Window

MS = 1_000_000
DATA = os.path.join(os.path.dirname(__file__), "data")
CHIPSEQ = os.path.join(DATA, "tpu_v5_lite_chipseq-temporal.json.gz")


def read(name, ctx):
    return spec.metric_reader(name)(ctx)


def _ctx(spans, start=0.0, trace=None, trace_obj=None):
    return types.SimpleNamespace(win=Window(start=start), spans=spans,
                                 trace=trace, trace_obj=trace_obj)


def _steps():
    # (name, start_ns, dur_ns, args, id, parent) on perf_counter_ns: two
    # engine steps under service grants; step 2 holds a snapshot
    return [
        ("engine/sizing_wave", 1 * MS, 3 * MS, {"n": 4}, 3, 2),
        ("predict", 1 * MS, 2 * MS, {"k": 4}, 4, 3),
        ("engine/complete_wave", 2 * MS, 4 * MS, {"n": 2}, 5, 2),
        ("history/append", 2 * MS, 1 * MS, {"n": 2}, 6, 5),
        ("journal/append", 7 * MS, 1 * MS, {"step": 0}, 7, 2),
        ("cluster/step", 0, 10 * MS, {"step": 0}, 2, 1),
        ("service/grant", 0, 11 * MS, {}, 1, None),
        ("journal/append", 21 * MS, 1 * MS, {"step": 1}, 10, 9),
        ("cluster/export_state", 22 * MS, 2 * MS, {"step": 2}, 11, 9),
        ("journal/snapshot", 24 * MS, 3 * MS, {"step": 2, "bytes": 3072},
         12, 9),
        ("cluster/step", 20 * MS, 8 * MS, {"step": 1}, 9, 8),
        ("service/grant", 20 * MS, 9 * MS, {}, 8, None),
    ]


def test_engine_step_self_time_is_the_step_less_its_direct_children():
    # step 1: 10 ms less the union [1, 6) + [7, 8) of its children (the
    # predict and history spans inside them are not counted again) = 4 ms;
    # step 2: 8 ms less [21, 27) = 2 ms
    assert read("engine_step_self_ms", _ctx(_steps())) == pytest.approx(3.0)


def test_journal_snapshot_and_history_time_per_step():
    ctx = _ctx(_steps())
    assert read("journal_append_ms_per_step", ctx) == pytest.approx(1.0)
    assert read("snapshot_ms_per_step", ctx) == pytest.approx(2.5)
    assert read("history_append_ms_per_step", ctx) == pytest.approx(0.5)
    assert read("snapshot_kib", ctx) == pytest.approx(3.0)


def test_observe_wall_is_the_mean_observe_span():
    spans = [("observe", 0, 20 * MS, {}, 1, None),
             ("observe", 30 * MS, 30 * MS, {}, 2, None)]
    assert read("observe_wall_ms", _ctx(spans)) == pytest.approx(25.0)
    assert read("observe_wall_ms", _ctx(spans[:0])) is None


@pytest.mark.parametrize("name", ["engine_step_self_ms",
                                  "journal_append_ms_per_step",
                                  "snapshot_ms_per_step", "snapshot_kib",
                                  "history_append_ms_per_step"])
def test_a_program_without_step_spans_reads_nothing(name):
    # spans as a program without span ids records them: the reader finds
    # nothing and does not raise
    old = [s[:4] for s in _steps()
           if s[0] not in ("cluster/export_state", "journal/append")]
    old = [s if s[0] != "journal/snapshot" else s[:3] + ({"step": 2},)
           for s in old]
    assert read(name, _ctx(old)) is None
    assert read(name, _ctx(None)) is None


def _chip():
    t = tr.load_extract(CHIPSEQ)
    return t, tr.reduce(t)


def test_idle_unspanned_without_program_spans_is_the_device_idle_share():
    t, red = _chip()
    ctx = _ctx([], start=12.5, trace=red, trace_obj=t)
    assert read("idle_unspanned_pct", ctx) == pytest.approx(
        read("device_idle_pct", ctx), abs=1e-9)
    assert read("device_idle_pct", ctx) > 50


def test_idle_unspanned_is_zero_under_a_root_leaf_span_over_the_window():
    t, red = _chip()
    start = 12.5                      # Window.start on perf_counter
    s0 = round(start * 1e9)
    whole = ("journal/append", s0, red["hi"] - red["lo"], {}, 1, None)
    ctx = _ctx([whole], start=start, trace=red, trace_obj=t)
    assert read("idle_unspanned_pct", ctx) == pytest.approx(0.0, abs=1e-9)


def test_idle_unspanned_leaves_out_the_step_wrappers_and_maps_by_anchor():
    t, red = _chip()
    start = 7.25
    s0 = round(start * 1e9)
    width = red["hi"] - red["lo"]
    idle = read("device_idle_pct", _ctx([], trace=red, trace_obj=t))
    wrappers = [("service/grant", s0, width, {}, 1, None),
                ("cluster/step", s0, width, {}, 2, 1)]
    ctx = _ctx(wrappers, start=start, trace=red, trace_obj=t)
    assert read("idle_unspanned_pct", ctx) == pytest.approx(idle, abs=1e-9)
    # a leaf over the window's first half, on the profiler's clock by the
    # anchor: what stays idle is the second half's idle time
    half = ("history/append", s0, width // 2, {}, 3, 2)
    ctx = _ctx(wrappers + [half], start=start, trace=red, trace_obj=t)
    mid = red["lo"] + width // 2
    second = tr.busy_ns(t, mid, red["hi"])
    want = 100.0 * (red["hi"] - mid - second) / width
    assert read("idle_unspanned_pct", ctx) == pytest.approx(want, abs=1e-6)


def test_readers_on_a_traced_journaled_engine_run(tmp_path):
    from repro import obs
    from repro.baselines.sizey_method import SizeyMethod
    from repro.workflow import generate_workflow
    from repro.workflow.cluster import ClusterEngine
    from repro.workflow.journal import Journal
    trace = generate_workflow("eager", seed=3, scale=0.02,
                              machine_cap_gb=64.0)
    method = SizeyMethod(machine_cap_gb=64.0, temporal_k=4,
                         persist_path=str(tmp_path / "run.jsonl"))
    engine = ClusterEngine(trace, method, n_nodes=4,
                           journal=Journal.attach(method, snapshot_every=8))
    with obs.tracing() as col:
        engine.run()
    ctx = _ctx(col.spans)
    steps = [s[2] * 1e-6 for s in col.spans if s[0] == "cluster/step"]
    mean_step = sum(steps) / len(steps)
    parts = [read(n, ctx) for n in ("engine_step_self_ms",
                                    "journal_append_ms_per_step",
                                    "snapshot_ms_per_step")]
    assert all(p is not None and p > 0 for p in parts)
    assert sum(parts) <= mean_step
    assert read("history_append_ms_per_step", ctx) > 0
    assert read("snapshot_kib", ctx) > 1
    assert read("observe_wall_ms", ctx) > 0
