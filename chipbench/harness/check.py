"""The check that decides ``correct``.

The run's journal is what the timed path produced: every sizing decision
of the window, as the engine journaled it with each task's decision
blob, and, before each step's record, the provenance rows of the
completions that step observed. Replaying the file in order gives the
pool state each decision was made from: the pool's records and log rows
before the record of the step that sized it, and, for the temporal
method, its usage profiles. A sample of (step, pool) groups drawn from the
seed, with the largest wave of the window always in it, is recomputed by
the plain reference (``reference.py``) from that same state, after the
window has closed, and every decision of a sampled group is compared.
"""
from __future__ import annotations

import dataclasses
import json
import math

import jax
import numpy as np

from chipbench.harness import reference as ref

SAMPLE_GROUPS = 48
BLOCK = 64


@dataclasses.dataclass
class Pool:
    rows: list = dataclasses.field(default_factory=list)  # (feats, y, rt)
    logs: list = dataclasses.field(default_factory=list)  # (preds, agg, y, rt)
    profiles: list = dataclasses.field(default_factory=list)
    fit_n: int = 0            # rows of the fit the model states come from
    fit_cap: int = 0
    next_fit: int = 0
    dirty: bool = False
    observed: bool = False


@dataclasses.dataclass
class Group:
    """Decisions of one sizing call (a ready wave, or a re-sizing of
    crash-interrupted tasks) in one pool, with the pool state they were
    made from."""
    step: int
    field: str
    key: tuple
    n: int
    m: int
    n_prof: int
    fit_n: int
    blobs: list
    observed: bool = False    # the pool observed completions in the window


@dataclasses.dataclass
class Replay:
    pools: dict
    groups: list      # Group, in journal order
    observes: list    # (step, key, rows, log rows, "fit" | "refresh")


def replay(path: str, method: dict) -> Replay:
    """Pools as the journal left them, the decision groups and the model
    updates of the served steps, in journal order. Model refits follow the
    configuration's schedule: every observe (``refit_growth`` 0), or once
    the history has grown by that fraction or the buffers doubled."""
    k = method.get("temporal_k") or 1
    min_hist = method["min_history"] * k
    growth = method["refit_growth"] if k > 1 else 0.0
    pools: dict = {}
    groups: list = []
    observes: list = []

    def pool(key):
        return pools.setdefault(key, Pool())

    def observe(p: Pool, first: bool) -> str | None:
        n = len(p.rows)
        if n < min_hist:
            return None
        cap = ref.capacity(n)
        if (growth <= 0.0 or p.fit_n == 0 or cap != p.fit_cap
                or n >= p.next_fit or first):
            p.fit_n, p.fit_cap = n, cap
            p.next_fit = n + max(1, math.ceil(growth * n))
            return "fit"
        return "refresh"

    with open(path) as f:
        for line in f:
            d = json.loads(line)
            kind = d.get("kind", "task")
            if kind == "task":
                p = pool((d["task_type"], d["machine"]))
                p.rows.append((d["features"], d["peak_mem_gb"],
                               d["runtime_h"]))
                p.dirty = True
            elif kind == "log":
                pool((d["task_type"], d["machine"])).logs.append(
                    (d["model_preds"], d["agg"], d["actual"],
                     d["runtime_h"]))
            elif kind == "curve":
                pool((d["task_type"], d["machine"])).profiles.append(
                    d["profile"])
            elif kind == "wal" and d.get("rec") == "begin":
                for p in pools.values():          # the warm start
                    observe(p, True)
                    p.dirty = False
            elif kind == "wal" and d.get("rec") == "step":
                for key, p in pools.items():
                    if p.dirty:
                        p.observed = True
                        how = observe(p, False)
                        if how:
                            observes.append((d["step"], key, len(p.rows),
                                             len(p.logs), how))
                        p.dirty = False
                for field in ("sized", "refresh"):
                    wave: dict = {}
                    for _key, _alloc, blob in (s[:3] for s in d[field]):
                        wave.setdefault((blob["task_type"], blob["machine"]),
                                        []).append(blob)
                    for key, blobs in wave.items():
                        p = pool(key)
                        groups.append(Group(d["step"], field, key,
                                            len(p.rows), len(p.logs),
                                            len(p.profiles), p.fit_n, blobs,
                                            p.observed))
    return Replay(pools, groups, observes)


def sample(groups: list, seed: int, n: int = SAMPLE_GROUPS) -> list:
    """The largest wave, then groups drawn from the seed: first among those
    made after their pool observed completions in the window (they depend
    on the window's observe and refresh programs), then among the rest."""
    if not groups:
        return []
    big = max(range(len(groups)), key=lambda i: len(groups[i].blobs))
    rng = np.random.default_rng(seed % 2**63)
    picked = [big]
    for observed in (True, False):
        pool = [i for i in range(len(groups))
                if i != big and groups[i].observed == observed]
        take = min(n - len(picked), len(pool))
        picked += [pool[i] for i in rng.choice(len(pool), size=take,
                                               replace=False)]
    return [groups[i] for i in sorted(picked)]


def ref_cfg(method: dict) -> tuple:
    keys = ("model_classes", "alpha", "beta", "ttf", "mlp_hidden",
            "mlp_train_steps", "forest_trees", "forest_depth", "knn_k",
            "ridge_lambda", "min_alloc_gb")
    return tuple((k, tuple(method[k]) if isinstance(method[k], list)
                  else method[k]) for k in keys)


def _padded(rows: list, cap: int) -> np.ndarray:
    a = np.asarray(rows, np.float32)
    out = np.zeros((cap,) + a.shape[1:], np.float32)
    out[:len(a)] = a
    return out


def _model(alloc, agg, offset, preds, raq, weights, cap) -> dict:
    return {"source": "model", "alloc": float(alloc), "agg": float(agg),
            "offset": float(offset), "preds": np.asarray(preds, np.float64),
            "raq": np.asarray(raq, np.float64),
            "weights": np.asarray(weights, np.float64), "cap": float(cap)}


def _preset(alloc) -> dict:
    return {"source": "preset", "alloc": float(alloc)}


def reference_decisions(group: Group, pools: dict, method: dict, grid: int,
                        prec) -> list:
    """What the reference decides for each task of ``group``: a list of
    (segment ends, [decision per segment])."""
    k = method.get("temporal_k") or 1
    p = pools[group.key]
    rows = p.rows[:group.n]
    if k > 1:
        ends = ref.segment_ends(
            p.profiles[:group.n_prof][-method["profile_window"]:], k, grid,
            prec)
    else:
        ends = (1.0,)
    tasks = []
    for blob in group.blobs:
        segs = blob["seg_decisions"] if blob["kind"] == "temporal" \
            else [blob]
        base = tuple(segs[0]["features"][:len(segs[0]["features"])
                                         - (k > 1)])
        feats, prev = [], 0.0
        for e in ends:
            feats.append(base + (0.5 * (prev + e),) if k > 1 else base)
            prev = e
        tasks.append((feats, segs[0]["user_preset_gb"],
                      segs[0]["machine_cap_gb"]))
    if group.fit_n < method["min_history"] * k:
        return [(ends, [_preset(min(preset, cap))] * len(feats))
                for feats, preset, cap in tasks]
    cap_rows = ref.capacity(group.n)
    xs = _padded([r[0] for r in rows], cap_rows)
    ys = _padded([r[1] for r in rows], cap_rows)
    rts = _padded([r[2] for r in rows], cap_rows)
    mask = _padded([1.0] * group.n, cap_rows)
    fit_mask = _padded([1.0] * group.fit_n, cap_rows)
    logs = p.logs[:group.m]
    cap_log = ref.capacity(group.m)
    n_models = len(method["model_classes"])
    if logs:
        lp = _padded([r[0] for r in logs], cap_log).T
        la, ly, lr = (_padded([r[i] for r in logs], cap_log)
                      for i in (1, 2, 3))
    else:
        lp = np.zeros((n_models, cap_log), np.float32)
        la = ly = lr = np.zeros(cap_log, np.float32)
    lm = _padded([1.0] * group.m, cap_log)
    seed = ref.fit_seed(group.key, group.fit_n, method["min_history"] * k,
                        method["seed"])
    cfg = ref_cfg(method)
    models, acc, offset, _ = ref.pool_state(
        xs, ys, rts, mask, fit_mask, lp, la, ly, lr, lm, seed, cfg=cfg,
        prec=prec)
    q = np.asarray([f for feats, _, _ in tasks for f in feats], np.float32)
    caps = np.asarray([c for feats, _, c in tasks for _ in feats],
                      np.float32)
    blocks = []
    for i in range(0, len(q), BLOCK):
        qb, cb = q[i:i + BLOCK], caps[i:i + BLOCK]
        pad = BLOCK - len(qb)
        qb = np.concatenate([qb, np.repeat(qb[-1:], pad, 0)])
        cb = np.concatenate([cb, np.repeat(cb[-1:], pad)])
        blocks.append(np.asarray(ref.decide(models, acc, offset, qb, cb,
                                            cfg=cfg, prec=prec))[:len(qb)
                                                                 - pad])
    dec = np.concatenate(blocks)
    n = n_models
    out, j = [], 0
    for feats, _, _ in tasks:
        segs = []
        for _ in feats:
            r = dec[j]
            segs.append(_model(r[0], r[1], r[2], r[5:5 + n],
                               r[5 + n:5 + 2 * n], r[5 + 2 * n:], caps[j]))
            j += 1
        out.append((ends, segs))
    return out


def program_decisions(group: Group) -> list:
    """The journaled decisions of ``group`` in the same layout."""
    out = []
    for blob in group.blobs:
        if blob["kind"] == "temporal":
            ends, segs = tuple(blob["boundaries"]), blob["seg_decisions"]
        else:
            ends, segs = (1.0,), [blob]
        out.append((ends, [
            _preset(s["allocation_gb"]) if s["source"] != "model" else
            _model(s["allocation_gb"], s["agg_pred_gb"], s["offset_gb"],
                   s["model_preds"]["a"], s["raq"]["a"], s["weights"]["a"],
                   s["machine_cap_gb"]) for s in segs]))
    return out


YOUNG_LOG = 5     # below this many logged predictions the offset comes
#                   from the in-sample errors of the ensemble (section II-E)


def compare(a: list, b: list, method: dict, young: list) -> dict:
    """Deviations of decisions ``a`` from the reference's ``b``, group by
    group (one group: one pool state, one fit).

    * ``alloc_gap_max``: per decision, the gap of the allocation from the
      reference's ensemble rebuilt with ``a``'s own MLP prediction and MLP
      score in place of the reference's (``w = softmax(beta x RAQ)``, plus
      the reference's offset, clipped), relative to it; the largest over
      all decisions. It holds every part of a decision to the reference
      but the MLP's fit, which a 300-step Adam run amplifies from round-off
      into a few percent now and then. Where the pool's log is young
      (``young[g]``), the offset comes from the in-sample ensemble, so
      through the MLP too, and ``a``'s own offset is used.
      ``alloc_gap_median``, ``alloc_gap_q90``, ``alloc_gap_mean``: the
      median, the 90th percentile and the mean of the same gaps.
    * ``mlp_dev_median``: per group, the largest gap of the MLP prediction
      (and, in a young pool, of the offset) relative to the reference's
      allocation; the median over groups, so a few fits that Adam carries
      apart do not decide it. ``mlp_dev_q25`` and ``mlp_dev_q10``: the
      lower quartile and the tenth percentile of the same per-group gaps.
    * ``ends_mismatch``: tasks whose segment ends differ.
    * ``source_mismatch``: tasks with the same ends whose decision source
      (the user's preset or the models) differs.
    * ``alloc_dev_median`` / ``alloc_dev_max``: the plain relative gap of
      the allocations over decisions, reported beside them.
    * ``mlp_by_group``: each group's MLP gap (None where the group has no
      model decision), for the calibration's record.
    """
    mlp = list(method["model_classes"]).index("mlp")
    beta, lo = method["beta"], method["min_alloc_gb"]
    gaps, plain, mlp_groups, by_group, n_tasks = [], [], [], [], 0
    bad_ends = bad_source = 0
    for ga, gb, yg in zip(a, b, young):
        worst = None
        for (ea, sa), (eb, sb) in zip(ga, gb):
            n_tasks += 1
            if (len(ea) != len(eb)
                    or not np.allclose(ea, eb, rtol=0, atol=1e-9)):
                bad_ends += 1
                continue
            if [x["source"] for x in sa] != [x["source"] for x in sb]:
                bad_source += 1
                continue
            for xa, xb in zip(sa, sb):
                plain.append(abs(xa["alloc"] - xb["alloc"]) / xb["alloc"])
                if xa["source"] != "model":
                    gaps.append(plain[-1])
                    continue
                q, r = xb["preds"].copy(), xb["raq"].copy()
                q[mlp], r[mlp] = xa["preds"][mlp], xa["raq"][mlp]
                w = np.exp(beta * (r - r.max()))
                w /= w.sum()
                off = xa["offset"] if yg else xb["offset"]
                rebuilt = min(max(float(w @ q) + off, lo), xb["cap"])
                gaps.append(abs(xa["alloc"] - rebuilt) / rebuilt)
                d = abs(xa["preds"][mlp] - xb["preds"][mlp]) / xb["alloc"]
                if yg:
                    d = max(d, abs(xa["offset"] - xb["offset"]) / xb["alloc"])
                worst = d if worst is None else max(worst, d)
        if worst is not None:
            mlp_groups.append(worst)
        by_group.append(worst)
    z = np.zeros(1)
    return {"decisions": len(plain), "tasks": n_tasks,
            "groups_fitted": len(mlp_groups),
            "alloc_gap_max": float(np.max(gaps if gaps else z)),
            "alloc_gap_median": float(np.median(gaps if gaps else z)),
            "alloc_gap_q90": float(np.quantile(gaps if gaps else z, 0.9)),
            "alloc_gap_mean": float(np.mean(gaps if gaps else z)),
            "mlp_dev_median": float(np.median(mlp_groups if mlp_groups
                                              else z)),
            "mlp_dev_q25": float(np.quantile(mlp_groups if mlp_groups
                                             else z, 0.25)),
            "mlp_dev_q10": float(np.quantile(mlp_groups if mlp_groups
                                             else z, 0.10)),
            "ends_mismatch": bad_ends, "source_mismatch": bad_source,
            "alloc_dev_median": float(np.median(plain if plain else z)),
            "alloc_dev_max": float(np.max(plain if plain else z)),
            "mlp_by_group": by_group}


def run_check(journal: str, method: dict, seed: int, grid: int = 32,
              prec: str = "highest", n_groups: int = SAMPLE_GROUPS
              ) -> tuple[dict, list, Replay]:
    """Replay ``journal``, recompute a sample of its groups with the
    reference at precision ``prec`` and compare. Returns (numbers, the
    sampled groups, the replay)."""
    rp = replay(journal, method)
    picked = sample(rp.groups, seed, n_groups)
    with jax.default_matmul_precision("highest"):
        refs = [reference_decisions(g, rp.pools, method, grid, prec)
                for g in picked]
    prog = [program_decisions(g) for g in picked]
    out = compare(prog, refs, method, [g.m < YOUNG_LOG for g in picked])
    out["groups"] = len(picked)
    return out, picked, rp


def verdict(numbers: dict, limits: dict) -> tuple[bool, list]:
    """(correct, [(name, number, limit)]) against the configuration's
    ``check.limits``: every number at or below its limit, and at least
    one task checked."""
    rows = [(k, numbers[k], lim) for k, lim in limits.items()]
    return all(v <= lim for _, v, lim in rows) and numbers["tasks"] > 0, rows
