"""One run of one cell: set up, serve a measured window, check, report.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1>

The last line of standard output is the result object; the numbers the
check compared, each with its limit, are also the last lines of standard
error. With ``--trace 1`` the window is traced by the profiler (its
length capped at ``TRACE_SECONDS``) and the metrics are the cell's
per-layer ones.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time
import types

TRACE_SECONDS = 6.0


def p95(values) -> float:
    """Nearest-rank 95th percentile: the smallest sample with at least
    95% of the samples at or below it."""
    s = sorted(values)
    if not s:
        raise ValueError("no samples")
    return s[max(0, -(-95 * len(s) // 100) - 1)]


def end_to_end(win, setup_s: float) -> dict:
    waves = [(b - a) * 1e3 for a, b, sized in win.steps if sized]
    return {"tasks_per_s": (win.completed / win.seconds, "tasks/s"),
            "wave_p95_ms": (p95(waves), "ms"),
            "setup_s": (setup_s, "s")}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-trace", default=None,
                    help="with --trace 1: also write the reduced trace's "
                         "intervals to this .json.gz file")
    return ap.parse_args(argv)


def setup_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    whatever the environment names."""
    import jax
    from chipbench.harness.spec import BENCH_DIR
    path = os.path.join(BENCH_DIR, ".cache", "jax")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run(args, t_process: float, require=None, cell=None, after=None
        ) -> dict:
    """Set up, serve, check; returns the result object. ``require`` stands
    in for the chip check and ``cell`` for the cell named by ``args``
    (tests on the CPU); ``after(journal, method, seed, numbers)``, where
    given, runs on the window's journal once the check is done (``numbers``:
    the check's readings) and its result is returned under ``"after"`` (the
    control's readings, see ``chipbench/calibrate.py``)."""
    import jax
    from chipbench.harness import check, device, serve, spec, trace_reduce
    from chipbench.harness import traffic as tr

    cell = cell or spec.load_cell(args.workload)
    setup_cache()
    dev = (require or device.require_tpu)(cell.chips)
    peak = device.peaks(dev["kind"]) if dev["platform"] == "tpu" else None
    clock = device.CompileClock()
    config, traffic = cell.config, cell.traffic
    wf, method = config["workflow"], config["method"]
    workdir = tempfile.mkdtemp(prefix="chipbench-")
    trace_on = bool(args.trace)
    seconds = min(args.seconds, TRACE_SECONDS) if trace_on else args.seconds
    try:
        cal = tr.calibrate(wf, traffic["run_scale"])
        run_trace = tr.generate_run(wf, cal, tr.derived_seed(args.seed,
                                                             "run"),
                                    traffic.get("root_span_h", 0.0))
        prior_path, prior = None, {}
        if traffic["prior_runs"]:
            pcal = tr.calibrate(wf, traffic["prior_scale"])
            runs = [tr.generate_run(wf, pcal, tr.derived_seed(
                args.seed, "prior", i)) for i in range(traffic["prior_runs"])]
            prior_path = os.path.join(workdir, "prior.jsonl")
            prior = tr.write_prior_provenance(prior_path, wf, method, runs)
        shapes = serve.warm_shapes(config, serve.reach(config, prior,
                                                       run_trace))
        prof_dir = os.path.join(workdir, "profile")
        state = {}

        def after_submit(handle):
            state["probes"] = serve.warm_up(config, shapes, workdir)
            if trace_on:
                from repro.obs.trace import start_tracing
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(prof_dir, profiler_options=opts)
                state["spans"] = start_tracing()
            state["setup_compile"] = dict(clock.secs)

        win, journal, handle = serve.serve_window(
            config, run_trace, prior_path, seconds, trace_on, clock,
            workdir, args.seed, after_submit)
        setup_s = win.start - t_process
        spans = None
        if trace_on:
            from repro.obs.trace import stop_tracing
            stop_tracing()
            jax.profiler.stop_trace()
            spans = [s for s in state["spans"].spans
                     if s[1] * 1e-9 >= win.start]
        mem = device.memory_peak_bytes(cell.chips)
        del handle
        gc.collect()
        sc = state["setup_compile"]
        log(f"[setup] setup_s={setup_s!r} trace_s={sc['trace']!r} "
            f"lower_s={sc['lower']!r} compile_s={sc['compile']!r} "
            f"cache_read_s={sc['cache_read']!r} probes={state['probes']} "
            f"shapes={json.dumps(shapes)}")
        log(f"[window] seconds={win.seconds!r} steps={len(win.steps)} "
            f"sizing_steps={sum(s[2] for s in win.steps)} "
            f"completed={win.completed} sized={win.sized_tasks} "
            f"aborted={win.aborted} errors={win.errors} "
            f"compiles={win.compiles} journal_bytes={win.journal_bytes}")
        numbers, picked, rp = check.run_check(journal, method, args.seed)
        correct, rows = check.verdict(numbers, cell.limits)
        correct = correct and not win.errors
        out = {"correct": bool(correct), "attempted": win.sized_tasks,
               "failed": win.aborted + len(win.errors)}
        if trace_on:
            traced = trace_reduce.load_xplane(
                trace_reduce.find_xplane(prof_dir))
            if args.save_trace:
                trace_reduce.save_extract(traced, args.save_trace)
            red = trace_reduce.reduce(traced)
            ctx = types.SimpleNamespace(
                win=win, spans=spans, trace=red, trace_obj=traced,
                replay=rp, method=method, peak=peak,
                d=1 + (1 if method.get("temporal_k") else 0))
            metrics = {}
            for m in cell.per_layer:
                v = spec.metric_reader(m["name"])(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": float(v),
                                          "unit": m["unit"]}
            out["metrics"] = metrics
            out["device"] = dict(dev, memory_peak_bytes=mem,
                                 busy_s=red["busy_s"],
                                 window_s=red["window_s"])
            out["breakdown"] = {
                "device_ops": trace_reduce.top(red["programs"]),
                "idle_gaps": trace_reduce.top(red["idle_by_host"])}
        else:
            e2e = end_to_end(win, setup_s)
            log(f"[window] wave samples={sum(s[2] for s in win.steps)}")
            out["metrics"] = {m["name"]: {"value": e2e[m["name"]][0],
                                          "unit": e2e[m["name"]][1]}
                              for m in cell.end_to_end}
            out["device"] = dict(dev, memory_peak_bytes=mem)
        log("[check] " + " ".join(f"{k}={v!r}" for k, v in numbers.items()
                                  if k != "mlp_by_group"))
        for name, value, limit in rows:
            log(f"[check] {name}={value!r} limit={limit!r}")
        if after is not None:
            out["after"] = after(journal, method, args.seed, numbers)
        out["compared"] = {name: {"value": value, "limit": limit}
                           for name, value, limit in rows}
        return out
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None, t_process: float | None = None) -> int:
    t_process = time.perf_counter() if t_process is None else t_process
    args = parse(argv)
    from chipbench.harness.device import NoChip
    try:
        out = run(args, t_process)
    except NoChip as e:
        log(str(e))
        return 3
    print(json.dumps(out), flush=True)
    return 0
