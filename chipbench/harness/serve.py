"""The system under test, set up from a configuration and driven through
its served path for one measured window.

Set-up builds the method (warm-started from the provenance of earlier
runs through the user's own path, ``SizeyMethod(persist_path=...)``),
submits the run to a journaled ``SchedulerService`` and warms every
program shape the window can reach through the same public calls the
engine makes (``allocate_batch``, ``complete_batch``) on scratch pools of
those shapes. The window then drives ``SchedulerService`` -> engine steps
for a fixed number of seconds. Wrappers in this file time each layer from
outside the program.
"""
from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import json
import os
import shutil
import time

import jax
import numpy as np

from chipbench.harness import traffic as tr
from chipbench.harness.reference import capacity


class WindowClosed(Exception):
    """Raised by the step wrapper once the window's time is up."""


class TraceExhausted(SystemExit):
    pass


def sizey_config(method: dict):
    from repro.core import SizeyConfig
    temporal = bool(method.get("temporal_k"))
    return SizeyConfig(
        alpha=method["alpha"], beta=method["beta"],
        strategy=method["strategy"], hpo=method["hpo"],
        model_classes=tuple(method["model_classes"]),
        min_history=method["min_history"], mlp_hidden=method["mlp_hidden"],
        mlp_train_steps=method["mlp_train_steps"],
        forest_trees=method["forest_trees"],
        forest_depth=method["forest_depth"], knn_k=method["knn_k"],
        ridge_lambda=method["ridge_lambda"],
        min_alloc_gb=method["min_alloc_gb"], seed=method["seed"],
        refit_growth=0.0 if temporal else method["refit_growth"])


def make_method(config: dict, path: str | None):
    from repro.baselines.sizey_method import SizeyMethod
    m = config["method"]
    return SizeyMethod(sizey_config(m), ttf=m["ttf"],
                       temporal_k=m.get("temporal_k"),
                       machine_cap_gb=max(
                           config["workflow"]["machine_classes"].values()),
                       persist_path=path)


def engine_kwargs(config: dict, seed: int) -> dict:
    from repro.workflow.cluster import node_specs_from_racks
    c = config["cluster"]
    out = {"fail_rate_per_node_h": c["fail_rate_per_node_h"],
           "fail_seed": tr.derived_seed(seed, "node-crashes")}
    if c.get("racks"):
        out["node_specs"] = node_specs_from_racks(
            tuple(tuple(r) for r in c["racks"]))
    else:
        out["n_nodes"], out["node_cap_gb"] = c["n_nodes"], c["node_cap_gb"]
    return out


# ------------------------------------------------------------- warm-up
@dataclasses.dataclass
class Reach:
    """Shapes one pool can reach in the window: rows, log rows and
    profiles at the start, and the rows one run of the trace adds (also
    the largest wave one pool can size: every task of its type at once)."""
    rows0: int
    log0: int
    profiles0: int
    added: int


def reach(config: dict, prior: dict, run) -> dict:
    k = config["method"].get("temporal_k") or 1
    counts: dict = {}
    for t in run.tasks:
        key = (t.task_type, t.machine)
        counts[key] = counts.get(key, 0) + 1
    return {key: Reach(*prior.get(key, (0, 0, 0)), added=k * n)
            for key, n in counts.items()}


def _buckets(lo: int, hi: int) -> list[int]:
    out, b = [], lo
    while b <= hi:
        out.append(b)
        b *= 2
    return out


def warm_shapes(config: dict, reaches: dict) -> dict:
    """The (rows capacity, log capacity) pairs, batch buckets and profile
    buckets the window can reach, from each pool's start and the rows the
    trace can add to it."""
    k = config["method"].get("temporal_k") or 1
    window = config["method"]["profile_window"]
    pairs, caps, logs, prof = set(), set(), set(), set()
    waves = 1
    for r in reaches.values():
        cs = _buckets(capacity(r.rows0), capacity(r.rows0 + r.added))
        ls = _buckets(capacity(r.log0), capacity(r.log0 + r.added))
        caps.update(cs)
        logs.update(ls)
        pairs.update((c, l) for c in cs for l in ls if l <= c)
        waves = max(waves, r.added)
        if k > 1:
            n_prof = r.profiles0 + r.added // k
            prof.update(_buckets(capacity(max(r.profiles0, 3), 1),
                                 capacity(min(n_prof, window), 1)))
    # a temporal decision is k rows of one batch
    batches = _buckets(capacity(k, 1), capacity(waves, 1))
    return {"pairs": sorted(pairs), "caps": sorted(caps),
            "logs": sorted(logs), "batches": batches,
            "profiles": sorted(prof)}


def _probe_rows(key, n_rows, n_log, n_prof, k, n_models, rng) -> list[str]:
    rows = []
    for i in range(n_prof):
        rows.append(json.dumps({"kind": "curve", "task_type": key[0],
                                "machine": key[1],
                                "profile": list(rng.uniform(0.5, 2.0, 32))}))
    for i in range(n_rows):
        feats = [float(rng.uniform(0.1, 6.0))]
        if k > 1:
            feats.append((i % k + 0.5) / k)
        peak = float(rng.uniform(0.5, 4.0))
        rows.append(json.dumps({"task_type": key[0], "machine": key[1],
                                "features": feats, "peak_mem_gb": peak,
                                "runtime_h": 0.1, "attempts": 1,
                                "workflow": "warmup"}))
        if i < n_log:
            rows.append(json.dumps({
                "kind": "log", "task_type": key[0], "machine": key[1],
                "model_preds": [peak] * n_models, "agg": peak,
                "actual": peak, "runtime_h": 0.1}))
    return rows


def warm_up(config: dict, shapes: dict, workdir: str) -> int:
    """Run every reachable shape once through the public method calls on
    scratch pools: the warm start fits each (rows, log) capacity pair,
    ``allocate_batch`` decides each batch bucket on each capacity and
    ``complete_batch`` observes into each pool (one pool of each kind is
    full, so the buffers grow). Returns the number of scratch pools."""
    from repro.workflow.trace import TaskInstance
    m = config["method"]
    k = m.get("temporal_k") or 1
    n_models = len(m["model_classes"])
    machine, cap_gb = sorted(config["workflow"]["machine_classes"].items())[0]
    rng = np.random.default_rng(0)
    pools = []          # (key, rows, log rows, profiles)
    profs = list(shapes["profiles"]) or [0]
    for i, (c, l) in enumerate(shapes["pairs"]):
        pools.append((c - k, min(l - k, c - k), profs[i % len(profs)]))
    for c in shapes["caps"][:-1]:            # rows grow past c
        pools.append((c, min(c, shapes["logs"][0]) - k, 0))
    for l in shapes["logs"][:-1]:            # log grows past l
        c = next(x for x in shapes["caps"] if x >= l + k)
        pools.append((c - k, l, 0))
    for p in profs[len(shapes["pairs"]):]:
        pools.append((shapes["caps"][0] - k, 0, p))
    path = os.path.join(workdir, "warmup.jsonl")
    keys = []
    with open(path, "w") as f:
        for i, (n, nl, npr) in enumerate(pools):
            key = (f"warmup{i:03d}", machine)
            keys.append(key)
            for row in _probe_rows(key, n, max(nl, 0), npr, k, n_models,
                                   rng):
                f.write(row + "\n")
    method = make_method(config, path)       # warm start: every pair

    def task(key, j):
        return TaskInstance(workflow="warmup", task_type=key[0],
                            machine=key[1], input_size_gb=1.0 + 0.01 * j,
                            actual_peak_gb=2.0, runtime_h=0.1,
                            user_preset_gb=8.0, stage=0, index=j,
                            machine_cap_gb=cap_gb if k > 1 else None,
                            usage_curve=((0.5, 1.0), (1.0, 2.0)))
    first = None
    for b in shapes["batches"]:
        tasks = [task(key, j) for key in keys for j in range(b // k)]
        method.allocate_batch(tasks)
        if first is None:
            first = tasks
        else:
            for t in tasks:
                method.abandon(t)
    method.complete_batch([(t, 2.0, 1) for t in first])
    jax.effects_barrier()
    del method
    os.remove(path)
    return len(pools)


# -------------------------------------------------------------- window
@dataclasses.dataclass
class Window:
    start: float = 0.0
    end: float = 0.0
    steps: list = dataclasses.field(default_factory=list)  # (t0, t1, sized)
    sized_tasks: int = 0
    completed: int = 0
    aborted0: int = 0
    aborted: int = 0
    errors: list = dataclasses.field(default_factory=list)
    journal_s: float = 0.0
    journal_bytes: int = 0
    counters0: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    compiles: int = 0

    @property
    def seconds(self) -> float:
        return self.end - self.start


def _annotate(on: bool, name: str):
    return jax.profiler.TraceAnnotation(name) if on else \
        contextlib.nullcontext()


def counters() -> dict:
    from repro.core.predictor import DISPATCH_COUNTS
    from repro.core.temporal.predictor import BOUNDARY_COUNTS
    out = {f"dispatch.{k}": v for k, v in DISPATCH_COUNTS.items()}
    out.update({f"boundary.{k}": v for k, v in BOUNDARY_COUNTS.items()})
    return out


def instrument(handle, win: Window, seconds: float, annotate: bool):
    """Wrap the handle's engine step and method, and the journal's writes:
    the step wrapper closes the window; the others time and count their
    layer. Returns a function that takes the journal wrappers off."""
    from repro.workflow.journal import Journal
    engine = handle.engine
    method = engine.method
    step, alloc = engine.step, method.allocate_batch
    complete = method.complete_batch
    append, snap = Journal.append_step, Journal.maybe_snapshot

    def timed_step():
        t0 = time.perf_counter()
        if t0 >= win.start + seconds:
            win.end = t0
            raise WindowClosed()
        calls = engine.n_size_calls
        with _annotate(annotate, "bench.engine_step"):
            alive = step()
        win.steps.append((t0, time.perf_counter(),
                          engine.n_size_calls > calls))
        return alive

    def timed_alloc(tasks):
        win.sized_tasks += len(tasks)
        with _annotate(annotate, "bench.allocate_batch"):
            return alloc(tasks)

    def timed_complete(items):
        win.completed += len(items)
        with _annotate(annotate, "bench.complete_batch"):
            return complete(items)

    def timed_append(self, rec):
        t0 = time.perf_counter()
        with _annotate(annotate, "bench.journal_append"):
            append(self, rec)
        win.journal_s += time.perf_counter() - t0

    def timed_snap(self, step_idx, state_fn):
        t0 = time.perf_counter()
        with _annotate(annotate, "bench.journal_snapshot"):
            snap(self, step_idx, state_fn)
        win.journal_s += time.perf_counter() - t0

    engine.step = timed_step
    method.allocate_batch = timed_alloc
    method.complete_batch = timed_complete
    Journal.append_step, Journal.maybe_snapshot = timed_append, timed_snap

    def restore():
        Journal.append_step, Journal.maybe_snapshot = append, snap
    return restore


async def _serve(config, run, factory, seconds, annotate, clock, on_ready):
    from repro.serving.scheduler_service import SchedulerService
    win = Window()
    svc = SchedulerService(journal_dir=on_ready["journal_dir"],
                           snapshot_every=config["service"]["snapshot_every"])
    svc.add_tenant("facility", weight=1.0)
    async with svc:
        handle = await svc.submit("facility", run, method_factory=factory,
                                  engine_kwargs=on_ready["engine_kwargs"])
        restore = instrument(handle, win, seconds, annotate)
        path = handle.engine.method.predictor.db.persist_path
        on_ready["after_submit"](handle)
        win.aborted0 = handle.engine.n_aborted
        win.counters0 = counters()
        size0 = os.path.getsize(path)
        clock.window = True
        win.start = time.perf_counter()
        with _annotate(annotate, "bench.window"):
            try:
                await handle
            except WindowClosed:
                pass
            except Exception as e:          # an engine fault: counted
                win.end = time.perf_counter()
                win.errors.append(repr(e))
            else:
                raise TraceExhausted(
                    "chipbench: the trace ran out before the window ended; "
                    "the cell's run_scale is too small for this rate")
        clock.window = False
        win.compiles = clock.window_events
        win.aborted = handle.engine.n_aborted - win.aborted0
        win.counters = counters()
        win.journal_bytes = os.path.getsize(path) - size0
        restore()
    return win, path, handle


def serve_window(config, run, prior_path, seconds, annotate, clock,
                 workdir, seed, after_submit):
    """Submit ``run`` (warm-started from ``prior_path`` when given), call
    ``after_submit(handle)`` for the rest of set-up, then serve for
    ``seconds``. Returns (Window, journal path, handle)."""
    def factory(path):
        if prior_path:
            shutil.copyfile(prior_path, path)
        return make_method(config, path)
    ctx = {"journal_dir": os.path.join(workdir, "journals"),
           "engine_kwargs": engine_kwargs(config, seed),
           "after_submit": after_submit}
    return asyncio.run(_serve(config, run, factory, seconds, annotate,
                              clock, ctx))
