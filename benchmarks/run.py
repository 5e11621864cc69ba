"""Benchmark harness: one function per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run [--scale 0.35] [--ttf 1.0 0.5]

Prints ``name,metric=value,...`` CSV lines (and human-readable tables) and
writes results/bench_results.json for EXPERIMENTS.md. Scale 1.0 replays
the paper's full Table I instance counts; the default 0.35 keeps the whole
suite ~10 minutes on CPU while preserving every qualitative result.

  fig8a  wastage over time, ttf=1.0, aggregated over the six workflows
  fig8b  wastage over time, ttf=0.5
  fig8c  task-failure distribution by task type
  fig8d  aggregated task runtimes
  table2 per-workflow wastage for all methods
  fig9   full vs incremental (re)training time
  fig10  alpha sweep on two rnaseq task types
  fig11  model-class selection shares (argmax)
  fig12  relative prediction-error trend over task executions
  roofline  three-term roofline per (arch x shape x mesh) from the dry-run

``--smoke`` additionally runs the predictor and cluster-engine
microbenchmarks (benchmarks/predictor_bench.py, benchmarks/cluster_bench.py)
at the same scale.
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np

from benchmarks._util import dump_json
from benchmarks.roofline import csv_rows, load_rows
from repro import obs
from repro.baselines import make_method
from repro.baselines.sizey_method import SizeyMethod
from repro.core import SizeyConfig
from repro.utils import enable_compilation_cache
from repro.workflow import WORKFLOWS, generate_workflow, simulate

METHODS = ("sizey", "witt_wastage", "witt_lr", "tovar_ppm",
           "witt_percentile", "workflow_presets")


def _method(name: str, ttf: float):
    if name == "sizey":
        return SizeyMethod(SizeyConfig(), ttf=ttf)
    if name == "sizey_incremental":
        return SizeyMethod(SizeyConfig(incremental=True), ttf=ttf,
                           name="sizey_incremental")
    if name == "sizey_argmax":
        return SizeyMethod(SizeyConfig(strategy="argmax"), ttf=ttf,
                           name="sizey_argmax")
    return make_method(name, ttf=ttf)


class SimGrid:
    """Runs (workflow x method x ttf) once; figures share the results."""

    def __init__(self, scale: float, ttfs: tuple[float, ...]):
        self.scale = scale
        self.ttfs = ttfs
        self.results: dict[tuple, object] = {}
        self.methods_store: dict[tuple, object] = {}

    def run(self):
        for wf in WORKFLOWS:
            trace = generate_workflow(wf, scale=self.scale)
            for ttf in self.ttfs:
                for m in METHODS:
                    t0 = time.time()
                    method = _method(m, ttf)
                    r = simulate(trace, method, ttf=ttf)
                    self.results[(wf, m, ttf)] = r
                    self.methods_store[(wf, m, ttf)] = method
                    print(f"# sim {wf:10s} {m:18s} ttf={ttf} "
                          f"wastage={r.wastage_gbh:10.2f} "
                          f"fail={r.n_failures:4d} "
                          f"({time.time()-t0:.1f}s)", flush=True)
        return self

    def agg_wastage(self, method: str, ttf: float) -> float:
        return sum(self.results[(wf, method, ttf)].wastage_gbh
                   for wf in WORKFLOWS)

    def agg_runtime(self, method: str, ttf: float) -> float:
        return sum(self.results[(wf, method, ttf)].total_runtime_h
                   for wf in WORKFLOWS)

    def failures_by_type(self, method: str, ttf: float) -> list[int]:
        out = []
        for wf in WORKFLOWS:
            out.extend(self.results[(wf, method, ttf)]
                       .failures_by_type().values())
        return out


# ------------------------------------------------------------- figures
def bench_fig8ab(grid: SimGrid, ttf: float, out: dict):
    name = "fig8a" if ttf == 1.0 else "fig8b"
    rows = {m: grid.agg_wastage(m, ttf) for m in METHODS}
    best_baseline = min(v for k, v in rows.items() if k != "sizey")
    red = 100 * (1 - rows["sizey"] / best_baseline)
    out[name] = {"wastage_gbh": rows, "sizey_vs_best_baseline_pct": red}
    for m, v in rows.items():
        print(f"{name}/{m},wastage_gbh={v:.2f}")
    print(f"{name}/sizey_reduction,pct={red:.2f} "
          f"(paper: {64.58 if ttf == 1.0 else 60.60})")


def bench_fig8c(grid: SimGrid, out: dict):
    res = {}
    for m in METHODS:
        fails = grid.failures_by_type(m, 1.0)
        res[m] = {"median": float(np.median(fails)),
                  "q3": float(np.percentile(fails, 75)),
                  "total": int(np.sum(fails))}
        print(f"fig8c/{m},median_failures_per_type={res[m]['median']:.1f},"
              f"total={res[m]['total']}")
    out["fig8c"] = res


def bench_fig8d(grid: SimGrid, out: dict):
    res = {m: grid.agg_runtime(m, 1.0) for m in METHODS}
    out["fig8d"] = res
    for m, v in res.items():
        print(f"fig8d/{m},runtime_h={v:.2f}")


def bench_table2(grid: SimGrid, out: dict):
    table = {}
    for wf in WORKFLOWS:
        table[wf] = {m: grid.results[(wf, m, 1.0)].wastage_gbh
                     for m in METHODS}
        best_baseline = min(v for k, v in table[wf].items() if k != "sizey")
        win = table[wf]["sizey"] < best_baseline
        print(f"table2/{wf}," + ",".join(
            f"{m}={v:.2f}" for m, v in table[wf].items())
            + f",sizey_best={win}")
    wins = sum(table[wf]["sizey"] < min(v for k, v in table[wf].items()
                                        if k != "sizey")
               for wf in WORKFLOWS)
    print(f"table2/summary,sizey_best_in={wins}_of_{len(WORKFLOWS)} "
          f"(paper: 5 of 6)")
    out["table2"] = table
    out["table2_wins"] = wins


def _train_times_s(trace, name: str) -> list[float]:
    """Wall seconds of each fused fit or refresh of one replay, taken from
    the ``observe`` / ``refresh`` spans (they close after the device)."""
    with obs.tracing() as col:
        simulate(trace, _method(name, 1.0), ttf=1.0)
    return [s[2] * 1e-9 for s in col.spans if s[0] in ("observe", "refresh")]


def bench_fig9(scale: float, out: dict):
    trace = generate_workflow("methylseq", scale=scale)
    t_full = float(np.median(_train_times_s(trace, "sizey"))) * 1e3
    t_inc = float(np.median(_train_times_s(trace, "sizey_incremental"))) * 1e3
    red = 100 * (1 - t_inc / t_full)
    out["fig9"] = {"full_ms": t_full, "incremental_ms": t_inc,
                   "reduction_pct": red}
    print(f"fig9/full,median_train_ms={t_full:.2f}")
    print(f"fig9/incremental,median_train_ms={t_inc:.2f}")
    print(f"fig9/reduction,pct={red:.1f} (paper: 98.39, 1090ms -> 17.5ms)")


def bench_fig10(scale: float, out: dict):
    trace = generate_workflow("rnaseq", scale=scale)
    tasks = ("fastqc", "markduplicates")
    res: dict[str, dict] = {t: {} for t in tasks}
    for alpha in (0.0, 0.25, 0.5, 0.75, 1.0):
        method = SizeyMethod(SizeyConfig(alpha=alpha), ttf=1.0)
        r = simulate(trace, method, ttf=1.0)
        per_type: dict[str, float] = {}
        for o in r.outcomes:
            per_type[o.task.task_type] = per_type.get(o.task.task_type, 0) \
                + o.wastage_gbh
        for t in tasks:
            res[t][str(alpha)] = per_type.get(t, 0.0)
        print(f"fig10/alpha={alpha}," + ",".join(
            f"{t}={per_type.get(t, 0):.2f}" for t in tasks))
    out["fig10"] = res


def bench_fig11(grid: SimGrid, out: dict):
    # argmax run across all workflows: which model class wins (Fig. 11)
    counts = np.zeros(4)
    names = None
    for wf in WORKFLOWS:
        trace = generate_workflow(wf, scale=grid.scale)
        method = _method("sizey_argmax", 1.0)
        simulate(trace, method, ttf=1.0)
        counts = counts + method.predictor.model_select_counts
        names = method.predictor.models
    shares = counts / max(counts.sum(), 1)
    out["fig11"] = dict(zip(names, map(float, shares)))
    print("fig11/shares," + ",".join(
        f"{n}={s*100:.1f}%" for n, s in zip(names, shares))
        + "  (paper: mlp=42.7%, knn=29.1%, forest=19.4%, linear=8.8%)")


def bench_fig12(scale: float, out: dict):
    trace = generate_workflow("mag", scale=scale)
    method = _method("sizey", 1.0)
    simulate(trace, method, ttf=1.0)
    # raw aggregate predictions (no offset) from the prequential log
    pool = method.predictor.db.pool("prokka", "epyc128")
    n = pool.log_count
    err = np.abs(pool.log_agg[:n] - pool.log_actual[:n]) \
        / np.maximum(pool.log_actual[:n], 1e-9)
    half = n // 2
    early, late = float(np.median(err[:half])), float(np.median(err[half:]))
    slope = float(np.polyfit(np.arange(n), err, 1)[0])
    out["fig12"] = {"n": int(n), "early_median_rel_err": early,
                    "late_median_rel_err": late, "slope_per_task": slope}
    print(f"fig12/prokka,n={n},early_err={early:.4f},late_err={late:.4f},"
          f"slope={slope:.2e} (paper: decreasing trend)")


def bench_roofline(out: dict):
    rows = load_rows()
    if not rows:
        print("roofline,missing=results/dryrun.jsonl")
        return
    for line in csv_rows(rows):
        print(line)
    ok = [r for r in rows if "skipped" not in r]
    out["roofline_cells"] = len(ok)
    out["roofline_skipped"] = len(rows) - len(ok)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float,
                    default=float(os.environ.get("REPRO_BENCH_SCALE", 0.35)))
    ap.add_argument("--ttf", type=float, nargs="+", default=[1.0, 0.5])
    ap.add_argument("--skip-sims", action="store_true",
                    help="only the roofline table")
    ap.add_argument("--smoke", action="store_true",
                    help="smoke mode: --scale 0.05, ttf=1.0 only, plus the "
                         "predictor microbenchmark at the same scale — a "
                         "minutes-long end-to-end pass over every bench "
                         "path for the fast test loop")
    ap.add_argument("--out", default="results/bench_results.json",
                    help="output JSON path (CI writes into results/fresh/ "
                         "so the committed baseline stays intact for the "
                         "check_regression gate)")
    args = ap.parse_args()
    enable_compilation_cache()
    if args.smoke:
        args.scale = 0.05
        args.ttf = [1.0]

    out: dict = {"scale": args.scale}
    t0 = time.time()
    if not args.skip_sims:
        grid = SimGrid(args.scale, tuple(args.ttf)).run()
        bench_fig8ab(grid, 1.0, out)
        if 0.5 in args.ttf:
            bench_fig8ab(grid, 0.5, out)
        bench_fig8c(grid, out)
        bench_fig8d(grid, out)
        bench_table2(grid, out)
        bench_fig9(args.scale, out)
        bench_fig10(args.scale, out)
        bench_fig11(grid, out)
        bench_fig12(max(args.scale, 0.3), out)
    if args.smoke:
        from benchmarks.predictor_bench import run as predictor_bench_run
        out["predictor_bench"] = predictor_bench_run(scale=args.scale,
                                                     out_path="")
        from benchmarks.cluster_bench import run as cluster_bench_run
        out["cluster_bench"] = cluster_bench_run(scale=args.scale,
                                                 out_path="")
    bench_roofline(out)

    dump_json(args.out, out)
    print(f"# total bench wall: {time.time()-t0:.0f}s; "
          f"wrote {args.out}")


if __name__ == "__main__":
    main()
