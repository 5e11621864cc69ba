"""Traffic: the served workflow run and the provenance of earlier runs.

The generator follows the Sizey paper's calibration of the nf-core
workflows (arXiv 2407.16353, Table I and Figs. 1, 2, 7): per task type a
memory ~ input relationship family, a base memory, a runtime scale and an
instance count, and per instance an input size, a noisy peak, a runtime
and a memory-over-time curve. It is a copy of the program's own generator
(``repro.workflow.generators``) with one change of seeding: everything that
sets the amount of work (the relationship family, base memory, runtime
scale, instance count and preset of each task type) is drawn from the
configuration's fixed ``calibration_seed``, and only what varies from one
run of a pipeline to the next (input sizes, measurement noise, runtimes,
curves, gather edges, submission order) from the run's seed. So every
``--seed`` serves the same number of tasks per type into pools of the same
sizes, in another order and with other values.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json

import numpy as np

# the standard resource ladder workflow developers pick presets from
PRESET_LADDER_GB = (0.5, 1, 2, 4, 6, 8, 12, 16, 24, 32, 48, 64, 96, 128)
CURVE_SHAPES = ("ramp", "plateau", "spike", "flat")
CURVE_POINTS = 8


def stable_hash(s: str) -> int:
    """Deterministic 63-bit hash of a string."""
    return int.from_bytes(hashlib.sha256(s.encode()).digest()[:8], "big") >> 1


def derived_seed(seed: int, *parts) -> int:
    """A 31-bit seed derived from the run's seed and a label; any size of
    ``seed`` is taken."""
    return stable_hash(f"{seed}:" + ":".join(str(p) for p in parts)) % 2**31


@dataclasses.dataclass(frozen=True)
class TypeCalibration:
    name: str
    rel: str
    base_gb: float
    span: float
    rt_mean_h: float
    count: int
    machine: str
    cap_gb: float
    preset_gb: float
    shape: str
    params: dict


def _mem_params(rel: str, rng: np.random.Generator, base: float,
                span: float, in_hi: float) -> dict:
    p = {"slope": span * rng.uniform(0.5, 1.5) / max(in_hi, 1e-6),
         "noise": rng.uniform(0.02, 0.10)}
    if rel == "sqrt":
        p["c"] = span * rng.uniform(0.5, 1.5) / max(np.sqrt(in_hi), 1e-6)
    elif rel == "quadratic":
        p["c"] = 3.0 * span * rng.uniform(0.8, 1.6) / max(in_hi ** 2, 1e-6)
    elif rel == "step":
        p["chunk"] = in_hi / rng.integers(3, 6)
        p["c"] = span * rng.uniform(0.5, 1.2) / max(in_hi, 1e-6) * p["chunk"]
    elif rel == "clustered":
        p["period"] = in_hi / rng.uniform(2.0, 4.0)
        p["hi_gain"] = rng.uniform(1.8, 3.0)
    return p


def _mem(rel: str, p: dict, base: float, x: np.ndarray,
         rng: np.random.Generator) -> np.ndarray:
    noise = rng.normal(0.0, 1.0, x.shape)
    if rel == "linear":
        return base + p["slope"] * x + noise * p["noise"] * base
    if rel == "sqrt":
        return base + p["c"] * np.sqrt(x) + noise * p["noise"] * base
    if rel == "quadratic":
        return base + p["c"] * x * x + noise * p["noise"] * base
    if rel == "constant":
        return base * (1.0 + noise * 2.5 * p["noise"])
    if rel == "step":
        return (base + p["c"] * np.ceil(x / p["chunk"])
                + noise * p["noise"] * base)
    if rel == "clustered":
        gain = np.where(np.floor(x / p["period"]).astype(int) % 2 == 1,
                        p["hi_gain"], 1.0)
        return (base + p["slope"] * x) * gain + noise * p["noise"] * base
    raise ValueError(f"unknown relationship {rel!r}")


def _usage_curve(shape: str, rng: np.random.Generator, peak_gb: float):
    """One piecewise-constant usage curve whose max is ``peak_gb``."""
    n = CURVE_POINTS
    grid = (np.arange(n) + 1.0) / n
    mids = grid - 0.5 / n
    if shape == "ramp":
        start, gamma = rng.uniform(0.15, 0.45), rng.uniform(0.7, 1.6)
        level = start + (1.0 - start) * mids ** gamma
    elif shape == "plateau":
        rise, fall = rng.uniform(0.1, 0.3), rng.uniform(0.0, 0.2)
        tail = rng.uniform(0.4, 0.8)
        level = np.ones(n)
        level[mids < rise] = 0.3 + 0.7 * mids[mids < rise] / rise
        level[mids > 1.0 - fall] = tail
    elif shape == "spike":
        base, width = rng.uniform(0.2, 0.5), rng.uniform(0.1, 0.25)
        center = rng.uniform(0.3, 0.9)
        level = np.full(n, base)
        level[np.abs(mids - center) <= width / 2] = 1.0
        level[int(np.argmin(np.abs(mids - center)))] = 1.0
    elif shape == "flat":
        level = np.ones(n)
    else:
        raise ValueError(f"unknown curve shape {shape!r}")
    level = np.clip(level * (1.0 + rng.normal(0, 0.05, n) * level), 0.05,
                    None)
    level = level / level.max()
    return tuple((float(g), float(v * peak_gb)) for g, v in zip(grid, level))


def _preset_for(max_actual: float, factor: float) -> float:
    target = max_actual * factor
    return float(next((p for p in PRESET_LADDER_GB if p >= target),
                      PRESET_LADDER_GB[-1]))


def calibrate(wf: dict, scale: float) -> list[TypeCalibration]:
    """Per task type calibration of the workflow ``wf`` (a configuration's
    ``workflow`` object) at ``scale`` times its Table I instance counts;
    a function of the configuration alone."""
    names = list(wf["named_types"])[: wf["n_task_types"]]
    names += [f"{wf['name']}_t{i:02d}"
              for i in range(len(names), wf["n_task_types"])]
    classes = wf["machine_classes"]
    out = []
    for ti, tname in enumerate(names):
        rng = np.random.default_rng(derived_seed(
            wf["calibration_seed"], wf["name"], tname, "calibration"))
        rel = wf["rel_mix"][ti % len(wf["rel_mix"])]
        lo, hi = wf["mem_base_gb"]
        base = rng.uniform(lo, hi)
        span = wf["mem_span"] * base / hi
        params = _mem_params(rel, rng, base, span, wf["input_gb"][1])
        rt_mean = rng.uniform(*wf["runtime_h"])
        count = max(3, int(wf["avg_instances"] * rng.uniform(0.7, 1.3)
                           * scale))
        machine = sorted(classes)[ti % len(classes)]
        cap = float(classes[machine])
        # the developer's preset: the ladder step above preset_factor x the
        # worst case of a calibration sample of the type
        xs = _inputs(wf, rng, 256)
        worst = float(np.clip(_mem(rel, params, base, xs, rng), 0.05,
                              cap * 0.9).max())
        out.append(TypeCalibration(
            tname, rel, float(base), float(span), float(rt_mean), count,
            machine, cap, _preset_for(worst, wf["preset_factor"]),
            CURVE_SHAPES[ti % len(CURVE_SHAPES)], params))
    return out


def _inputs(wf: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    lo, hi = wf["input_gb"]
    return np.clip(rng.lognormal(np.log((lo + hi) / 4.0), 0.8, n), lo, hi)


def stages(n_types: int, width: int) -> list[int]:
    """Stage of each type in the nf-core chain: type i waits on i-width."""
    return [i // width for i in range(n_types)]


def generate_run(wf: dict, cal: list[TypeCalibration], seed: int,
                 root_span_h: float = 0.0):
    """One run of the pipeline: a ``WorkflowTrace`` whose root instances
    arrive evenly spaced over the first ``root_span_h`` hours, in the
    seed's order (all at t=0 where it is 0), and whose other instances wait
    on ``fan_in`` upstream shards of the type ``dag_width`` places before
    them."""
    from repro.workflow.trace import TaskInstance, WorkflowTrace
    width, fan_in = wf["dag_width"], wf["fan_in"]
    st = stages(len(cal), width)
    hetero = len(wf["machine_classes"]) > 1
    tasks = []
    for ti, c in enumerate(cal):
        rng = np.random.default_rng(derived_seed(seed, wf["name"], c.name))
        xs = _inputs(wf, rng, c.count)
        peaks = np.clip(_mem(c.rel, c.params, c.base_gb, xs, rng), 0.05,
                        c.cap_gb * 0.9)
        rts = (c.rt_mean_h * (0.4 + 0.6 * xs / max(wf["input_gb"][1], 1e-6))
               * np.exp(rng.normal(0, 0.2, c.count)))
        crng = np.random.default_rng(derived_seed(seed, wf["name"], c.name,
                                                  "curves"))
        for k in range(c.count):
            deps = ()
            if ti >= width:
                up = cal[ti - width]
                erng = np.random.default_rng(derived_seed(
                    seed, wf["name"], up.name, c.name, k))
                ups = {k * up.count // c.count}
                ups |= {int(erng.integers(up.count))
                        for _ in range(fan_in - 1)}
                deps = tuple((up.name, u) for u in sorted(ups))
            tasks.append(TaskInstance(
                workflow=wf["name"], task_type=c.name, machine=c.machine,
                input_size_gb=float(xs[k]), actual_peak_gb=float(peaks[k]),
                runtime_h=float(rts[k]), user_preset_gb=c.preset_gb,
                stage=st[ti], index=k, deps=deps,
                machine_cap_gb=c.cap_gb if hetero else None,
                usage_curve=_usage_curve(c.shape, crng, float(peaks[k]))))
    order = np.random.default_rng(derived_seed(seed, wf["name"], "order"))
    keys = order.random(len(tasks))
    tasks = [t for _, t in sorted(zip(keys, tasks),
                                  key=lambda p: (p[1].stage, p[0]))]
    n_roots = sum(1 for t in tasks if not t.deps)
    tasks = [dataclasses.replace(t, arrival_h=root_span_h * i / n_roots)
             if not t.deps else t for i, t in enumerate(tasks)]
    cap = max(c.cap_gb for c in cal)
    return WorkflowTrace(name=wf["name"], tasks=tasks, machine_cap_gb=cap)


# ------------------------------------------------------------ provenance
GRID = 32


def grid_profile(curve, n_grid: int = GRID) -> np.ndarray:
    """Max of a piecewise-constant curve over each of ``n_grid`` cells."""
    out = np.zeros(n_grid, np.float64)
    prev = 0.0
    for end, gb in curve:
        g0 = int(np.floor(prev * n_grid + 1e-9))
        g1 = int(np.ceil(float(end) * n_grid - 1e-9))
        if g1 > g0:
            out[g0:g1] = np.maximum(out[g0:g1], float(gb))
        prev = float(end)
    return out


def write_prior_provenance(path: str, wf: dict, method: dict,
                           runs: list) -> dict:
    """Write the provenance file of earlier runs (``runs``: traces) in the
    format the program's ``ProvenanceDB`` restores: one ``task`` row per
    completion (per plan segment for the temporal method, with its usage
    profile as a ``curve`` row) and one prequential ``log`` row per model
    decision. The earlier runs' model predictions are not known, so their
    log rows carry each model's prediction as the actual peak times a
    seeded lognormal error (``prior_log_error``), and the aggregate as their
    mean. Returns per pool the rows written: ``{(type, machine): (n_rows,
    n_log_rows, n_profiles)}``."""
    from chipbench.harness.reference import fit_cuts
    k = method.get("temporal_k") or 1
    min_hist = method["min_history"]
    sigma = method["prior_log_error"]
    n_models = len(method["model_classes"])
    counts: dict = {}
    rows: list[str] = []
    by_pool: dict = {}
    for run in runs:
        for t in run.tasks:
            by_pool.setdefault((t.task_type, t.machine), []).append(t)
    for key in sorted(by_pool):
        tasks = by_pool[key]
        rng = np.random.default_rng(derived_seed(0, "prior-log", *key,
                                                 len(tasks)))
        profiles = [grid_profile(t.usage_curve) for t in tasks]
        if k > 1:
            cuts = fit_cuts(np.asarray(profiles[-512:], np.float32), k)
            bounds = []
            for c in cuts:
                f = float(c) / GRID
                if not bounds or f > bounds[-1] + 1e-9:
                    bounds.append(f)
        else:
            bounds = [1.0]
        n = n_log = 0
        for i, t in enumerate(tasks):
            if k > 1:
                rows.append(json.dumps({
                    "kind": "curve", "task_type": key[0], "machine": key[1],
                    "profile": [float(v) for v in profiles[i]]}))
                lo, segs = 0, []
                for j, end in enumerate(bounds):
                    hi = min(GRID, max(lo + 1, int(np.ceil(end * GRID
                                                           - 1e-9))))
                    prev = bounds[j - 1] if j else 0.0
                    segs.append(((t.input_size_gb, 0.5 * (prev + end)),
                                 float(np.max(profiles[i][lo:hi]))))
                    lo = hi
            else:
                segs = [((t.input_size_gb,), t.actual_peak_gb)]
            model = i >= min_hist
            for feats, peak in segs:
                rows.append(json.dumps({
                    "task_type": key[0], "machine": key[1],
                    "features": list(feats), "peak_mem_gb": peak,
                    "runtime_h": t.runtime_h, "attempts": 1,
                    "workflow": t.workflow}))
                n += 1
                if model:
                    preds = peak * np.exp(rng.normal(0.0, sigma, n_models))
                    rows.append(json.dumps({
                        "kind": "log", "task_type": key[0],
                        "machine": key[1],
                        "model_preds": [float(p) for p in preds],
                        "agg": float(np.mean(preds)), "actual": peak,
                        "runtime_h": t.runtime_h}))
                    n_log += 1
        counts[key] = (n, n_log, len(profiles) if k > 1 else 0)
    with open(path, "w") as f:
        for r in rows:
            f.write(r + "\n")
    return counts
