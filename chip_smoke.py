#!/usr/bin/env python3
"""Smoke run of the served sizing path on one TPU chip.

    python3 chip_smoke.py [--scale 1.0] [--out chiprun_out/chip_smoke]

One process, three phases; any failure exits non-zero.

1. Device: print the platform, device kind and count, and fail unless the
   first device is a TPU. The predictor must pick the Pallas route.
2. Served path: one ``SchedulerService`` with two tenants at weights 2:1,
   journaled to disk. ``facility`` submits ``mag`` (8 task types, 5096
   tasks at scale 1) with peak Sizey on 8 nodes of 128 GB; ``core``
   submits ``chipseq`` (30 types, 2469 tasks) with the k=4 temporal Sizey
   on the rack cluster ``16,32,64;16,32,64`` with node crashes at 0.01 per
   node-hour. Every handle is awaited. Each workflow must finish every
   task, with no abort, finite waste, the MLP forward in the
   ``ensemble_mlp`` kernel, and a journal that reached its end marker.
3. Chip vs CPU: the first 300 completions of mag's largest pool are
   replayed, in order, through one predictor on the TPU and one on the
   host CPU (jnp forward), in lockstep (see ``replay_lockstep``). The
   relative deviation of the allocations and the share of decisions whose
   offset strategy or best model differ must stay within the bounds below.

Seed 0 throughout. The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import collections
import json
import math
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

try:
    from repro.baselines.sizey_method import SizeyMethod
    from repro.core import SizeyConfig
    from repro.core.predictor import (DISPATCH_COUNTS, TRACE_COUNTS,
                                      SizeyPredictor)
    from repro.serving.scheduler_service import SchedulerService
    from repro.utils import enable_compilation_cache
    from repro.workflow import generate_workflow
    from repro.workflow.cluster import machine_label, node_specs_from_racks
except ImportError as e:
    raise SystemExit(f"chip_smoke: the repro package is not next to this "
                     f"script ({e})") from None

# chip-vs-CPU bounds (justified in CHANGES.md): relative deviation of
# allocation_gb, median and maximum over the replayed decisions, and the
# share of decisions whose offset strategy or best model differ
BOUNDS = {"median_rel_dev": 1e-3, "max_rel_dev": 2e-2,
          "offset_diff_share": 0.02, "best_model_diff_share": 0.05}
N_COMPARE = 300
RACK_CAPS = ((16.0, 32.0, 64.0), (16.0, 32.0, 64.0))


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering, compiling and reading the
    persistent cache, summed from ``jax.monitoring`` duration events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event in self.EVENTS:
            self.secs += float(duration)


def phase_device() -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"[device] platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX's first device is "
                         f"{dev['platform']!r}); nothing was run")
    use_pallas = SizeyPredictor().use_pallas
    log(f"[device] SizeyPredictor.use_pallas={use_pallas}")
    if not use_pallas:
        raise SystemExit("chip_smoke: the predictor did not pick the Pallas "
                         "route on a TPU")
    return dev


def count_steps(engine) -> dict:
    """Attribute the process-wide trace and dispatch counters to the
    workflow whose engine step bumped them (the service interleaves both
    workflows' steps). Returns the per-workflow deltas, filled as it runs."""
    deltas = {"trace": collections.Counter(),
              "dispatch": collections.Counter()}
    step = engine.step

    def counted_step():
        t0, d0 = (collections.Counter(TRACE_COUNTS),
                  collections.Counter(DISPATCH_COUNTS))
        try:
            return step()
        finally:
            deltas["trace"].update(collections.Counter(TRACE_COUNTS) - t0)
            deltas["dispatch"].update(
                collections.Counter(DISPATCH_COUNTS) - d0)

    engine.step = counted_step
    return deltas


async def serve(mag, chipseq, journal_dir: str) -> dict:
    svc = SchedulerService(journal_dir=journal_dir)
    svc.add_tenant("facility", weight=2.0)
    svc.add_tenant("core", weight=1.0)
    runs = {}
    t0 = time.perf_counter()

    async def finish(name):
        runs[name]["result"] = await runs[name]["handle"]
        runs[name]["wall_s"] = time.perf_counter() - t0
        log(f"[served] {name} finished after {runs[name]['wall_s']:.3f} s")

    async with svc:
        handles = {
            "mag": await svc.submit(
                "facility", mag,
                method_factory=lambda p: SizeyMethod(
                    SizeyConfig(), machine_cap_gb=mag.machine_cap_gb,
                    persist_path=p),
                engine_kwargs={"n_nodes": 8, "node_cap_gb": 128.0}),
            "chipseq": await svc.submit(
                "core", chipseq,
                method_factory=lambda p: SizeyMethod(
                    SizeyConfig(), temporal_k=4,
                    machine_cap_gb=chipseq.machine_cap_gb, persist_path=p),
                engine_kwargs={
                    "node_specs": node_specs_from_racks(RACK_CAPS),
                    "fail_rate_per_node_h": 0.01, "fail_seed": 0}),
        }
        for name, h in handles.items():
            runs[name] = {"handle": h, "deltas": count_steps(h.engine)}
        # gather raises the first engine exception: a failed workflow
        # fails the phase
        await asyncio.gather(*(finish(name) for name in handles))
    return runs


def check_served(runs: dict, traces: dict, journal_dir: str) -> None:
    for name, trace in traces.items():
        run, res = runs[name], runs[name]["result"]
        done = sum(not o.aborted for o in res.outcomes)
        aborts = res.cluster.n_aborted
        waste, fails = res.wastage_gbh, res.n_failures
        trace_counts = run["deltas"]["trace"]
        log(f"[served] {name}: completed={done}/{len(trace.tasks)} "
            f"aborts={aborts} wastage_gbh={waste!r} failures={fails} "
            f"wall_s={run['wall_s']:.3f}")
        log(f"[served] {name}: trace_counts="
            f"{json.dumps(dict(sorted(trace_counts.items())))}")
        log(f"[served] {name}: dispatch_counts="
            f"{json.dumps(dict(sorted(run['deltas']['dispatch'].items())))}")
        if done != len(trace.tasks) or aborts:
            raise SystemExit(f"chip_smoke: {name} completed {done} of "
                             f"{len(trace.tasks)} tasks with {aborts} aborts")
        if not (math.isfinite(waste) and math.isfinite(fails)):
            raise SystemExit(f"chip_smoke: {name} waste {waste!r} or "
                             f"failures {fails!r} not finite")
        if trace_counts["mlp_jnp"] or not trace_counts["mlp_pallas"]:
            raise SystemExit(f"chip_smoke: {name} did not run the MLP "
                             f"forward through the ensemble_mlp kernel")
    files = os.listdir(journal_dir)
    unfinished = SchedulerService.scan_unfinished(journal_dir)
    mib = sum(os.path.getsize(os.path.join(journal_dir, f))
              for f in files) / 2**20
    log(f"[served] journals: {len(files)} files, {mib:.1f} MiB, "
        f"unfinished={len(unfinished)}")
    if unfinished:
        raise SystemExit(f"chip_smoke: journals never reached their end "
                         f"marker: {unfinished}")


def phase_served(scale: float, out_dir: str, clock: CompileClock):
    """Returns the mag trace and its method's provenance DB."""
    mag = generate_workflow("mag", seed=0, scale=scale)
    caps = sorted({c for rack in RACK_CAPS for c in rack})
    chipseq = generate_workflow(
        "chipseq", seed=0, scale=scale,
        machine_caps_gb={machine_label(c): c for c in caps})
    log(f"[served] scale={scale}: mag {len(mag.tasks)} tasks, "
        f"chipseq {len(chipseq.tasks)} tasks")
    journal_dir = os.path.join(out_dir, "journals")
    shutil.rmtree(journal_dir, ignore_errors=True)
    c0, t0 = clock.secs, time.perf_counter()
    try:
        runs = asyncio.run(serve(mag, chipseq, journal_dir))
        wall, compile_s = time.perf_counter() - t0, clock.secs - c0
        check_served(runs, {"mag": mag, "chipseq": chipseq}, journal_dir)
    finally:
        shutil.rmtree(journal_dir, ignore_errors=True)   # ~240 MiB
    log(f"[served] phase wall_s={wall:.3f} compile_s={compile_s:.3f}")
    return mag, runs["mag"]["handle"].engine.method.predictor.db


def replay_lockstep(records, preset_gb: float, cap_gb: float) -> list:
    """Replay ``records`` through a TPU predictor (Pallas route) and a
    host-CPU predictor (jnp forward) in lockstep: both size each task from
    the same state, then both observe the CPU's decision, so their
    histories and prequential logs stay identical and every pair of
    decisions differs by one step's numerics only. In a closed loop, where
    each logs its own decision, one offset choice that flips on a near-tie
    changes every later decision."""
    on_cpu = lambda: jax.default_device(jax.devices("cpu")[0])
    tpu = SizeyPredictor(SizeyConfig(), default_machine_cap_gb=cap_gb,
                         use_pallas=True)
    with on_cpu():
        cpu = SizeyPredictor(SizeyConfig(), default_machine_cap_gb=cap_gb,
                             use_pallas=False)
    pairs = []
    for r in records:
        a = tpu.predict(r.task_type, r.machine, r.features, preset_gb)
        with on_cpu():
            b = cpu.predict(r.task_type, r.machine, r.features, preset_gb)
        tpu.observe(b, r.peak_mem_gb, r.runtime_h, r.attempts, r.workflow)
        with on_cpu():
            cpu.observe(b, r.peak_mem_gb, r.runtime_h, r.attempts,
                        r.workflow)
        pairs.append((a, b))
    return pairs


def phase_compare(mag, db, out_dir: str) -> dict:
    key = max(db.pools, key=lambda k: db.pools[k].count)
    records = [r for r in db.records
               if (r.task_type, r.machine) == key][:N_COMPARE]
    preset = next(t.user_preset_gb for t in mag.tasks
                  if (t.task_type, t.machine) == key)
    log(f"[compare] pool {key[0]}@{key[1]}: replaying {len(records)} "
        f"completions (pool holds {db.pools[key].count})")
    t0 = time.perf_counter()
    pairs = replay_lockstep(records, preset, mag.machine_cap_gb)
    wall = time.perf_counter() - t0
    if any(a.source != b.source for a, b in pairs):
        raise SystemExit("chip_smoke: the TPU and CPU predictors disagree "
                         "on which decisions are model decisions")
    rows = [(a, b) for a, b in pairs if a.source == "model"]
    rel = np.asarray([abs(a.allocation_gb - b.allocation_gb)
                      / b.allocation_gb for a, b in rows])
    best = np.asarray([(int(np.argmax(a.raq)), int(np.argmax(b.raq)))
                       for a, b in rows])
    stats = {"decisions": len(rows), "median_rel_dev": float(np.median(rel)),
             "max_rel_dev": float(np.max(rel)),
             "offset_diff_share": float(np.mean(
                 [a.offset_idx != b.offset_idx for a, b in rows])),
             "best_model_diff_share": float(np.mean(best[:, 0] != best[:, 1]))}
    with open(os.path.join(out_dir, "compare.csv"), "w") as f:
        f.write("i,alloc_tpu_gb,alloc_cpu_gb,rel_dev,offset_tpu,offset_cpu,"
                "best_tpu,best_cpu\n")
        for i, ((a, b), r, (bt, bc)) in enumerate(zip(rows, rel, best)):
            f.write(f"{i},{a.allocation_gb!r},{b.allocation_gb!r},{r!r},"
                    f"{a.offset_idx},{b.offset_idx},{bt},{bc}\n")
    log(f"[compare] {json.dumps(stats)} wall_s={wall:.3f}")
    over = {k: (stats[k], b) for k, b in BOUNDS.items() if stats[k] > b}
    if over:
        raise SystemExit(f"chip_smoke: chip vs CPU over bound "
                         f"(value, bound): {over}")
    return stats


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=float, default=1.0,
                    help="trace scale of both workflows (1.0: the paper's "
                         "Table I instance counts)")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out",
                                                  "chip_smoke"),
                    help="directory for the journals (removed at the end "
                         "of the phase) and compare.csv")
    args = ap.parse_args()
    cache_dir = enable_compilation_cache()
    dev = phase_device()
    log(f"[device] compilation cache: {cache_dir}")
    os.makedirs(args.out, exist_ok=True)
    clock = CompileClock()
    mag, db = phase_served(args.scale, args.out, clock)
    phase_compare(mag, db, args.out)
    n_cached = sum(len(fs) for _, _, fs in os.walk(cache_dir))
    log(f"[device] compilation cache holds {n_cached} files; "
        f"compile_s total={clock.secs:.3f}")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
