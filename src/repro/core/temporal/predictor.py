"""TemporalSizeyPredictor — k-segment memory-over-time prediction on top of
the fused Sizey ensemble.

The peak pipeline answers "how much will this task ever need"; this one
answers "how much will it need DURING each phase". Design:

  * **Segment boundaries** per (task_type, machine) pool are fit by the
    vectorized change-point sweep over the pool's observed usage profiles
    (:func:`repro.core.temporal.segments.fit_boundaries`), refreshed as
    completions stream in. With no history the k segments are uniform.
  * **Per-segment peaks ride the existing fused ensemble.** Each segment
    becomes one row of the inner :class:`SizeyPredictor`'s feature space —
    the base task features plus the segment's center time fraction — and
    the per-segment history lives in the same device-resident
    ``_PoolBuffers``. A prediction stacks the k segment queries (for a
    whole scheduling wave: K·k queries) into ``predict_batch``, which
    groups them per pool: ONE fused device dispatch per pool decides every
    segment of every task, with RAQ gating and the dynamic offset applied
    per segment row by the same XLA program the peak path compiles.
  * **k = 1 is the peak predictor, bitwise.** No segment feature is
    appended, ``min_history`` is not scaled, the single "segment" spans
    the whole runtime, and the emitted plan collapses to a constant
    reservation that the engines run on the legacy path — so disabling
    resizing reproduces peak-based Sizey exactly (asserted in
    ``tests/test_temporal.py``).
  * **Persistence**: the inner provenance JSONL carries the per-segment
    task records and prequential log; grid-sampled usage profiles ride the
    same file as ``kind="curve"`` aux rows. A restore replays profiles
    (boundary fits resume where they were), bulk-loads the buffers, and
    ``warm_start`` rebuilds model states and the per-pool decision cache —
    so per-segment offsets resume warm (asserted in the checkpoint
    round-trip test).

``min_history`` is scaled by k for the inner predictor (each completion
contributes k rows), so the preset-vs-model switchover happens after the
same number of COMPLETED TASKS as the peak predictor's.
"""
from __future__ import annotations

import collections
import dataclasses

import numpy as np

from repro.core.config import SizeyConfig
from repro.core.predictor import SizeyPredictor, SizingDecision, TaskQuery
from repro.core.provenance import ProvenanceDB
from repro.core.temporal.segments import (PROFILE_WINDOW, ReservationPlan,
                                          fit_boundaries, grid_profile,
                                          segment_peaks, uniform_boundaries)
from repro.obs import metrics as _obs_metrics
from repro.obs.trace import span as _span

__all__ = ["TemporalDecision", "TemporalSizeyPredictor"]

# aux-row kind for usage profiles in the provenance JSONL (the file keeps
# every row; restore re-trims to the shared PROFILE_WINDOW)
CURVE_KIND = "curve"

# default amortized-refit growth factor the temporal predictor passes down
# to the inner SizeyPredictor for k > 1 (see SizeyConfig.refit_growth):
# full ensemble retrains happen once a pool's history grows 25% past the
# last fit; in between, a cheap fused refresh keeps offsets and the
# decision cache current. k = 1 never sets it — that configuration stays
# bitwise-identical to the peak predictor's every-observe fit schedule.
TEMPORAL_REFIT_GROWTH = 0.25

# process-wide boundary-fit accounting, TRACE_COUNTS-style: "fit" counts
# change-point sweeps actually run, "hit" counts cache servings (retries,
# same-wave siblings), "uniform" counts no-history defaults. Tests and the
# bench assert the refit bound with these (fits <= observe generations).
# Registry-backed (repro.obs) since PR 9; still a collections.Counter.
BOUNDARY_COUNTS: collections.Counter = _obs_metrics.counter(
    "temporal_boundary_total", "segment-boundary fit events by kind")


@dataclasses.dataclass
class TemporalDecision:
    """What the temporal predictor decided for one task submission: one
    sizing decision per segment, stitched into a reservation plan."""
    task_type: str
    machine: str
    boundaries: tuple[float, ...]          # segment end fractions
    seg_decisions: list[SizingDecision]    # one per segment, same order
    plan: ReservationPlan

    @property
    def allocation_gb(self) -> float:
        """What a plan-unaware engine should reserve: the plan peak."""
        return self.plan.peak_gb

    @property
    def source(self) -> str:
        return self.seg_decisions[0].source

    @property
    def peak_decision(self) -> SizingDecision:
        """The segment decision carrying the plan's peak (drives the
        retry ladder: its pool max_seen/cap are the relevant ones)."""
        return max(self.seg_decisions, key=lambda d: d.allocation_gb)


class TemporalSizeyPredictor:
    """k-segment piecewise-constant memory-over-time predictor composed
    from the fused Sizey ensemble (see module docstring)."""

    def __init__(self, cfg: SizeyConfig | None = None, *,
                 k_segments: int = 4, n_grid: int = 32,
                 n_features: int = 1, ttf: float = 1.0,
                 default_machine_cap_gb: float = 128.0,
                 persist_path: str | None = None, fused: bool = True,
                 use_pallas: bool | None = None,
                 refit_growth: float | None = None):
        if k_segments < 1:
            raise ValueError("k_segments must be >= 1")
        if n_grid < k_segments:
            raise ValueError("n_grid must be >= k_segments")
        cfg = cfg or SizeyConfig()
        self.k = int(k_segments)
        self.n_grid = int(n_grid)
        self.base_features = int(n_features)
        # k=1: NO segment feature, NO min_history scaling and NO refit
        # stride — the inner predictor sees exactly what the peak
        # predictor would (bitwise). k>1 pools carry k rows per completion
        # and amortize the full ensemble retrain (TEMPORAL_REFIT_GROWTH)
        # unless the caller pins refit_growth (0.0 = fit every observe).
        inner_features = n_features + (1 if self.k > 1 else 0)
        if self.k > 1:
            inner_cfg = dataclasses.replace(
                cfg, min_history=cfg.min_history * self.k,
                refit_growth=(TEMPORAL_REFIT_GROWTH if refit_growth is None
                              else float(refit_growth)))
        elif refit_growth is not None:
            inner_cfg = dataclasses.replace(
                cfg, refit_growth=float(refit_growth))
        else:
            inner_cfg = cfg
        db = ProvenanceDB(n_features=inner_features,
                          n_models=len(cfg.model_classes),
                          persist_path=persist_path)
        self.predictor = SizeyPredictor(
            inner_cfg, db, n_features=inner_features, ttf=ttf,
            default_machine_cap_gb=default_machine_cap_gb, fused=fused,
            use_pallas=use_pallas)
        self.cfg = inner_cfg
        # host-side pool state: grid-sampled usage profiles + boundary
        # fits. The boundary cache is keyed by pool GENERATION (bumped on
        # every observe of the pool): retries and same-wave siblings hit
        # the cached fit, a completion invalidates it, and nothing else
        # does — so change-point sweeps run at most once per (pool,
        # generation) however many tasks a wave schedules.
        self._profiles: dict[tuple[str, str], list[np.ndarray]] = {}
        self._gen: dict[tuple[str, str], int] = {}
        self._boundaries: dict[tuple[str, str],
                               tuple[int, tuple[float, ...]]] = {}
        # checkpoint restore: replay profiles (k=1 checkpoints carry none),
        # then rebuild model states + decision caches from the bulk-loaded
        # buffers so the per-segment offsets resume warm, and pre-fit the
        # boundary cache so the first post-restore wave is served warm too
        for row in db.aux.get(CURVE_KIND, ()):
            self._profiles.setdefault(
                (row["task_type"], row["machine"]), []).append(
                    np.asarray(row["profile"], np.float64))
        for profs in self._profiles.values():
            del profs[:-PROFILE_WINDOW]
        if db.records:
            self.predictor.warm_start()
        for key in self._profiles:
            self._fit_pool(key)

    @property
    def db(self) -> ProvenanceDB:
        return self.predictor.db

    # --------------------------------------------------------- boundaries
    def _fit_pool(self, key: tuple[str, str]) -> tuple[float, ...]:
        """Fit (or default) the pool's boundaries and cache them under its
        current generation."""
        profs = self._profiles.get(key)
        if not profs or len(profs) < 3:
            bounds = uniform_boundaries(self.k)
            BOUNDARY_COUNTS["uniform"] += 1
        else:
            with _span("boundary_fit", pool=f"{key[0]}@{key[1]}",
                       n=len(profs)):
                bounds = fit_boundaries(np.stack(profs), self.k)
            BOUNDARY_COUNTS["fit"] += 1
        self._boundaries[key] = (self._gen.get(key, 0), bounds)
        return bounds

    def boundaries(self, task_type: str, machine: str) -> tuple[float, ...]:
        """Current segment end fractions for one pool: the change-point
        fit over its observed profiles (uniform until enough history),
        served from the generation-keyed cache — one fit per (pool,
        generation) no matter how many submissions, retries, or same-wave
        siblings ask."""
        if self.k == 1:
            return (1.0,)
        key = (task_type, machine)
        cached = self._boundaries.get(key)
        if cached is not None and cached[0] == self._gen.get(key, 0):
            BOUNDARY_COUNTS["hit"] += 1
            return cached[1]
        return self._fit_pool(key)

    def _seg_features(self, feats: tuple[float, ...],
                      bounds: tuple[float, ...]) -> list[tuple[float, ...]]:
        if self.k == 1:
            return [feats]
        rows, prev = [], 0.0
        for end in bounds:
            rows.append(feats + (0.5 * (prev + end),))
            prev = end
        return rows

    # ------------------------------------------------------------ predict
    def predict_batch(self, tasks) -> list[TemporalDecision]:
        """Decide a burst of submissions: every segment of every task is
        one row of a single ``predict_batch`` call, so the whole wave
        costs one fused dispatch per pool — the peak path's launch bound,
        unchanged by the factor-k fan-out."""
        queries: list[TaskQuery] = []
        metas = []
        for t in tasks:
            bounds = self.boundaries(t.task_type, t.machine)
            feats = tuple(float(f) for f in np.atleast_1d(t.features))
            cap = getattr(t, "machine_cap_gb", None)
            for row in self._seg_features(feats, bounds):
                queries.append(TaskQuery(t.task_type, t.machine, row,
                                         float(t.user_preset_gb), cap))
            metas.append((t, bounds))
        decisions = self.predictor.predict_batch(queries)
        out: list[TemporalDecision] = []
        pos = 0
        for t, bounds in metas:
            segs = decisions[pos:pos + len(bounds)]
            pos += len(bounds)
            plan = ReservationPlan(tuple(
                (end, d.allocation_gb) for end, d in zip(bounds, segs)))
            out.append(TemporalDecision(t.task_type, t.machine, bounds,
                                        segs, plan))
        return out

    def predict(self, task) -> TemporalDecision:
        return self.predict_batch([task])[0]

    # ------------------------------------------------------------- failure
    def retry_allocation(self, decision: TemporalDecision, attempt: int,
                         last_alloc_gb: float) -> float:
        """Retries are flat: the ladder climbs from the pool's max seen
        segment peak (== max task peak: the segment holding the global
        peak records it) exactly like the peak predictor's."""
        return self.predictor.retry_allocation(decision.peak_decision,
                                               attempt, last_alloc_gb)

    # ------------------------------------------------------------- observe
    def observe_batch(self, completions) -> None:
        """Observe completed tasks: ``completions`` is a sequence of
        ``(decision, task, attempts)`` with ``task`` exposing
        ``usage_curve`` / ``actual_peak_gb`` / ``runtime_h`` /
        ``workflow``. Appends each task's grid profile (persisted as a
        ``curve`` aux row), computes the per-segment actual peaks against
        the boundaries the decision was made with, and feeds ALL segment
        observations of the wave through the inner ``observe_batch`` —
        one fused fit dispatch per pool."""
        obs = []
        with _span("history/append", n=len(completions)):
            for decision, task, attempts in completions:
                key = (decision.task_type, decision.machine)
                profile = grid_profile(task.usage_curve, self.n_grid,
                                       peak_gb=task.actual_peak_gb)
                if self.k > 1:
                    profs = self._profiles.setdefault(key, [])
                    profs.append(profile)
                    del profs[:-PROFILE_WINDOW]       # bounded fit window
                    # bump the pool generation: the cached boundary fit is
                    # stale from here; the next boundaries() call refits
                    self._gen[key] = self._gen.get(key, 0) + 1
                    self.db.add_aux(CURVE_KIND, {
                        "task_type": key[0], "machine": key[1],
                        "profile": [float(v) for v in profile]})
                    peaks = segment_peaks(profile, decision.boundaries)
                else:
                    peaks = np.asarray([task.actual_peak_gb])
                for d, seg_peak in zip(decision.seg_decisions, peaks):
                    obs.append((d, float(seg_peak), float(task.runtime_h),
                                attempts, task.workflow))
        self.predictor.observe_batch(obs)

    def observe(self, decision: TemporalDecision, task,
                attempts: int = 1) -> None:
        self.observe_batch([(decision, task, attempts)])
