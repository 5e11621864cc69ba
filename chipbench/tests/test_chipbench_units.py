"""Arithmetic of the benchmark's metrics, its tables and its data files."""
import json
import os

import pytest

from chipbench.harness import bench, device, flops, spec
from chipbench.harness import traffic as tr
from chipbench.harness.serve import Window


def _window(durations, completed, seconds):
    w = Window(start=0.0, end=seconds, completed=completed)
    t = 0.0
    for d in durations:
        w.steps.append((t, t + d, True))
        t += d
    return w


def test_tasks_per_s_counts_all_work_over_all_time():
    w = _window([0.01] * 100, completed=250, seconds=5.0)
    m = bench.end_to_end(w, setup_s=12.5)
    assert m["tasks_per_s"] == (50.0, "tasks/s")
    assert m["setup_s"] == (12.5, "s")


def test_wave_p95_is_nearest_rank_and_moves_with_a_stalled_step():
    base = [0.010] * 95 + [0.020] * 5
    assert bench.end_to_end(_window(base, 1, 2.0), 0.0)["wave_p95_ms"][0] \
        == pytest.approx(10.0)
    # one more slow step puts a 20 ms wave at the 95th percentile
    stalled = [0.010] * 94 + [0.020] * 6
    assert bench.end_to_end(_window(stalled, 1, 2.0), 0.0)["wave_p95_ms"][0] \
        == pytest.approx(20.0)
    assert bench.p95([3.0]) == 3.0


def test_only_sizing_steps_enter_the_wave_tail():
    w = _window([0.005] * 20, 1, 1.0)
    w.steps.append((1.0, 1.5, False))           # a step that sized nothing
    assert bench.end_to_end(w, 0.0)["wave_p95_ms"][0] == pytest.approx(5.0)


def test_mlp_forward_counts_by_hand():
    # 4 rows, d=2, h=3: x W1 (2*2*3 per row), + b1 (3), tanh (3),
    # h W2 (2*3), + b2 (1) -> 12 + 3 + 3 + 6 + 1 = 25 per row
    f, b = flops.mlp_forward(4, 2, 3)
    assert f == 4 * 25
    # x (4x2), W1 (2x3), b1 (3), W2 (3), b2 (1), out (4) in float32
    assert b == 4 * (8 + 6 + 3 + 3 + 1 + 4)


def test_full_fit_observe_counts_by_hand():
    m = {"mlp_hidden": 2, "model_classes": ["linear", "knn", "mlp", "forest"],
         "hpo": False, "mlp_train_steps": 1, "forest_trees": 1,
         "forest_depth": 1}
    c, L, d = 128, 128, 1
    f, b = flops.full_fit_observe(c, L, d, m)
    p = d * 2 + 2 * 2 + 1
    step = 4 * c * d * 2 + 5 * c * 2 + 6 * c * 2 + 8 * c + 12 * p
    mlp = step + 2 * c * d * 2 + 4 * c * 2 + 5 * c + 10 * c
    knn = c * c * (4 * d + 2)
    forest = 6 * c * 16 + 3 * c + c + c * 7 + c * 3
    linear = 2 * c * 4 + 4 * c * 2
    mlp_in = c * (2 * d * 2 + 2 + 2 + 4 + 1)
    gate = 5 * 4 * c + 2 * 4 * c
    sel = 2 * 6 * 128 + 2 * 128 * 7 + 128 * 128
    assert f == pytest.approx(mlp + knn + forest + linear + mlp_in + gate
                              + 2 * sel)
    inputs = c * (d + 3) + L * (4 + 4)
    outputs = c * (d + 2) + 4 * c + p + (2 + 2)
    assert b == 4 * (inputs + outputs)


def test_least_time_names_its_bound():
    peak = device.peaks("TPU v5 lite")
    assert flops.least_seconds(197e12, 1.0, peak) == (1.0, "compute")
    assert flops.least_seconds(1.0, 819e9, peak) == (1.0, "memory")


def test_peaks_table_is_keyed_by_device_kind():
    assert device.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")


def test_no_tpu_is_refused():
    with pytest.raises(device.NoChip):
        device.require_tpu(1)


def test_every_cell_finds_its_files_by_name():
    bench_json = spec.load_benchmark()
    names = {c["name"] for c in bench_json["configs"]}
    for w in bench_json["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"] and w["config"] in names
        assert cell.traffic["name"] == w["traffic"]
        assert {"tasks_per_s", "wave_p95_ms", "setup_s"} <= {
            m["name"] for m in cell.end_to_end}
        assert cell.per_layer
    for c in bench_json["configs"]:
        assert os.path.exists(os.path.join(spec.ROOT, c["file"]))
    for m in bench_json["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    with pytest.raises(SystemExit):
        spec.load_cell("no-such-cell")


def test_traffic_same_seed_same_inputs_and_every_seed_same_work():
    cfg = spec.load_cell("chipseq-temporal.warm").config
    wf = cfg["workflow"]
    cal = tr.calibrate(wf, 0.2)
    seed = 2**33 + 5
    a = tr.generate_run(wf, cal, tr.derived_seed(seed, "run"))
    b = tr.generate_run(wf, cal, tr.derived_seed(seed, "run"))
    c = tr.generate_run(wf, cal, tr.derived_seed(seed + 1, "run"))
    assert a.tasks == b.tasks
    assert a.tasks != c.tasks

    def per_pool(run):
        out = {}
        for t in run.tasks:
            out[(t.task_type, t.machine)] = out.get(
                (t.task_type, t.machine), 0) + 1
        return out
    assert per_pool(a) == per_pool(c)
    assert len({t.machine for t in a.tasks}) == 3
    for t in a.tasks:
        assert max(g for _, g in t.usage_curve) == pytest.approx(
            t.actual_peak_gb)


def test_prior_provenance_rows(tmp_path):
    cfg = spec.load_cell("chipseq-temporal.warm").config
    wf, m = cfg["workflow"], cfg["method"]
    cal = tr.calibrate(wf, 0.1)
    runs = [tr.generate_run(wf, cal, s) for s in (1, 2)]
    path = str(tmp_path / "p.jsonl")
    counts = tr.write_prior_provenance(path, wf, m, runs)
    rows = [json.loads(line) for line in open(path)]
    k = m["temporal_k"]
    n_tasks = sum(len(r.tasks) for r in runs)
    assert sum(1 for r in rows if r.get("kind") == "curve") == n_tasks
    assert sum(1 for r in rows if "kind" not in r) == k * n_tasks
    assert sum(n for n, _, _ in counts.values()) == k * n_tasks
    # the first min_history completions of a pool were presets: no log row
    assert all(n - nl == k * m["min_history"]
               for n, nl, _ in counts.values())
    assert all(len(r["features"]) == 2 for r in rows if "kind" not in r)


def test_derived_seeds_take_any_size():
    s = tr.derived_seed(2**62 + 3, "run")
    assert 0 <= s < 2**31
    assert s != tr.derived_seed(2**62 + 4, "run")


def test_a_preset_where_the_reference_decides_by_model_is_counted():
    from chipbench.harness import check
    m = {"model_classes": ["linear", "knn", "mlp", "forest"], "beta": 16.0,
         "min_alloc_gb": 0.125}
    ones = [1.0] * 4
    model = check._model(1.5, 1.0, 0.5, ones, ones, [0.25] * 4, 64.0)
    preset = check._preset(8.0)
    a = [[((1.0,), [preset]), ((1.0,), [model])]]
    b = [[((1.0,), [model]), ((1.0,), [model])]]
    out = check.compare(a, b, m, [False])
    assert out["source_mismatch"] == 1 and out["ends_mismatch"] == 0
    assert out["tasks"] == 2 and out["decisions"] == 1
    assert out["alloc_gap_max"] == 0.0
    ok, rows = check.verdict(out, {"alloc_gap_max": 1e-6,
                                   "source_mismatch": 0})
    assert not ok
    # segment ends that differ are counted apart
    c = [[((0.5, 1.0), [model, model]), ((1.0,), [model])]]
    d = [[((0.25, 1.0), [model, model]), ((1.0,), [model])]]
    out = check.compare(c, d, m, [False])
    assert (out["ends_mismatch"], out["source_mismatch"]) == (1, 0)


def test_mlp_gaps_are_taken_per_group_and_by_quantile():
    from chipbench.harness import check
    m = {"model_classes": ["linear", "knn", "mlp", "forest"], "beta": 16.0,
         "min_alloc_gb": 0.125}
    ones = [1.0] * 4

    def dec(mlp_pred):
        return check._model(2.0, 1.0, 0.0, [1.0, 1.0, mlp_pred, 1.0], ones,
                            [0.25] * 4, 64.0)
    ref = dec(1.0)
    # ten groups of one task; group i's MLP prediction is off by i/100 of
    # the reference's allocation (2.0)
    a = [[((1.0,), [dec(1.0 + 2.0 * i / 100)])] for i in range(10)]
    b = [[((1.0,), [ref])] for _ in range(10)]
    out = check.compare(a, b, m, [False] * 10)
    gaps = [i / 100 for i in range(10)]
    assert out["mlp_by_group"] == pytest.approx(gaps)
    assert out["mlp_dev_median"] == pytest.approx(0.045)
    assert out["mlp_dev_q25"] == pytest.approx(0.0225)
    assert out["mlp_dev_q10"] == pytest.approx(0.009)
    # a group with no model decision has no MLP gap
    out = check.compare(a + [[((1.0,), [check._preset(8.0)])]],
                        b + [[((1.0,), [check._preset(8.0)])]], m,
                        [False] * 11)
    assert out["mlp_by_group"][-1] is None
    assert out["groups_fitted"] == 10
