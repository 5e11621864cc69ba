"""SizeyPredictor — the paper's online memory-prediction engine (§II).

Pipeline per submitted task (paper Fig. 3):
  1  retrieve the (task_type × machine) pool from the provenance DB;
  2.1 every model in the pool predicts;    2.2 RAQ-gated aggregation;
  2.3 dynamic offset;  -> allocation submitted to the resource manager;
  3  on completion, the provenance DB and all models are updated online
     (full retrain or incremental, cfg.incremental).

Performance architecture (single-dispatch decision loop)
--------------------------------------------------------
The decision loop is the system's hottest path: every submission runs a
multi-model predict -> RAQ gate -> offset selection, and every completion a
retrain. Both halves are collapsed to **one jitted device dispatch each**:

  * Provenance buffers (``repro.core.provenance``) are device-resident jax
    arrays appended in place by donated-buffer jitted setters — the history
    is never re-uploaded from the host on the hot path.
  * ``predict`` calls one fused compiled function per (config, shape
    bucket): all model forwards (the MLP routed through the Pallas
    ``ensemble_mlp`` kernel on TPU/GPU, identical-numerics jnp on CPU), the
    RAQ gate, and the offset selector run as a single XLA program; a single
    ``device_get`` brings back the packed scalars of the decision.
  * ``observe`` fuses the all-model fit/update AND the in-sample prediction
    refresh (Eq. 1 inputs) into one compiled call — no intermediate
    ``np.stack`` host round-trip.
  * ``predict_batch`` vmaps the fused decision over K same-pool submissions
    (grouped across pools, K padded to power-of-two buckets) so a burst of
    task submissions costs one dispatch per pool, not one per task.

Compile-count guarantee: buffers grow geometrically (doubling, provenance
GROWTH), batch sizes are bucketed to powers of two, and every fused builder
is lru-cached on the frozen config — each pool compiles O(log history) +
O(log max-batch) times per feature dimension, independent of the number of
decisions served.
``TRACE_COUNTS`` records retraces so tests can assert the bound.

The pre-fusion per-model-loop implementation is retained behind
``SizeyPredictor(fused=False)`` as a numerical reference and benchmark
baseline (see ``benchmarks/predictor_bench.py``).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import SizeyConfig
from repro.core.failure import retry_allocation
from repro.core.gating import gate_predictions, gate_weights
from repro.core.models import MODEL_MODULES
from repro.core.offsets import select_offset
from repro.core.provenance import ProvenanceDB, TaskRecord
from repro.core.raq import accuracy_score, efficiency_scores, raq_scores
from repro.obs import metrics as _obs_metrics
from repro.obs.trace import span as _span
from repro.utils.misc import stable_hash

# retrace observability: bumped at trace time by every fused builder, so
# tests can assert the O(log history) compile-count guarantee. Registry-
# backed (repro.obs) since PR 9, but still a genuine collections.Counter
# so existing snapshot/diff consumers work verbatim.
TRACE_COUNTS: collections.Counter = _obs_metrics.counter(
    "predictor_trace_total", "fused-builder retrace events by kind")

# dispatch observability: bumped once per *device launch* on the decision
# path (each fused pool-predict call sizes a whole batch in one program;
# "observe_pool" counts the fused fit/update launches of the observe
# half), so cluster tests/benches can assert the O(waves x pools) bounds
# on BOTH directions of the loop.
DISPATCH_COUNTS: collections.Counter = _obs_metrics.counter(
    "predictor_dispatch_total", "fused device launches by kind")

# aux-row kind journaling full-retrain horizons under the amortized-refit
# schedule (cfg.refit_growth > 0): one row per FULL fit, carrying the pool
# count the fit ran at, so warm_start can replay the exact fit whatever
# wave shapes produced it. O(log n) rows per pool.
FIT_KIND = "fit"


def pallas_available() -> bool:
    """Whether the default backend compiles Pallas kernels (TPU, GPU).

    This picks ``SizeyPredictor.use_pallas`` when the caller does not: the
    MLP forward then runs the ``ensemble_mlp`` kernel. On CPU Pallas would
    run in interpret mode, far slower than plain jnp, so the CPU takes the
    jnp forward. The route taken is counted at trace time in
    ``TRACE_COUNTS["mlp_pallas"]`` / ``TRACE_COUNTS["mlp_jnp"]``."""
    return jax.default_backend() in ("tpu", "gpu")


@dataclasses.dataclass
class SizingDecision:
    """What Sizey decided for one task submission."""
    task_type: str
    machine: str
    features: tuple[float, ...]
    source: str                      # "preset" | "model"
    allocation_gb: float
    user_preset_gb: float
    machine_cap_gb: float
    model_preds: np.ndarray | None = None   # (N_models,)
    raq: np.ndarray | None = None
    weights: np.ndarray | None = None
    agg_pred_gb: float = 0.0
    offset_gb: float = 0.0
    offset_idx: int = -1


@dataclasses.dataclass(frozen=True)
class TaskQuery:
    """One pending submission for the batched scheduler API.

    Any object with these attributes (e.g. ``workflow.trace.TaskInstance``)
    is accepted by ``SizeyPredictor.predict_batch`` — this class is the
    minimal standalone carrier.
    """
    task_type: str
    machine: str
    features: tuple[float, ...]
    user_preset_gb: float
    machine_cap_gb: float | None = None


# ------------------------------------------------------------------ legacy
# Per-model jitted helpers: the pre-fusion reference path (fused=False).

@functools.lru_cache(maxsize=None)
def _jit_fit(model: str, cfg: SizeyConfig):
    mod = MODEL_MODULES[model]
    return jax.jit(functools.partial(mod.fit, cfg=cfg))


@functools.lru_cache(maxsize=None)
def _jit_update(model: str, cfg: SizeyConfig):
    mod = MODEL_MODULES[model]
    return jax.jit(functools.partial(mod.update, cfg=cfg))


@functools.lru_cache(maxsize=None)
def _jit_predict(model: str, cfg: SizeyConfig):
    mod = MODEL_MODULES[model]
    if model == "knn":
        return jax.jit(functools.partial(mod.predict, k=cfg.knn_k))
    return jax.jit(mod.predict)


@functools.lru_cache(maxsize=None)
def _jit_predict_batch(model: str, cfg: SizeyConfig):
    """vmapped in-sample prediction over the whole history buffer."""
    mod = MODEL_MODULES[model]
    if model == "knn":
        fn = functools.partial(mod.predict, k=cfg.knn_k)
    else:
        fn = mod.predict
    return jax.jit(jax.vmap(fn, in_axes=(None, 0)))


# candidate grid for the adaptive-alpha extension (paper §III-E future work)
ALPHA_GRID = (0.0, 0.25, 0.5, 0.75, 1.0)


def _select_alpha(acc, log_model_preds, log_actual, log_runtime, log_mask,
                  strategy: str, beta: float, ttf: float):
    """Retrospectively score each candidate alpha: re-gate the LOGGED
    per-model predictions with (current AS, per-instance ES) and pick the
    alpha whose aggregate would have wasted the least (offset-free replay —
    relative comparison only)."""
    from repro.core.offsets import retrospective_wastage
    # per-instance efficiency scores of the logged predictions: (N, L)
    p = jnp.maximum(log_model_preds, 0.0)
    eff_log = 1.0 - p / jnp.maximum(jnp.max(p, axis=0, keepdims=True), 1e-9)
    max_seen = jnp.max(jnp.where(log_mask > 0, log_actual, 0.0))

    def waste_of(alpha):
        raq = (1.0 - alpha) * acc[:, None] + alpha * eff_log     # (N, L)
        if strategy == "argmax":
            w = jax.nn.one_hot(jnp.argmax(raq, 0), raq.shape[0]).T
        else:
            w = jax.nn.softmax(beta * raq, axis=0)
        agg = jnp.sum(w * log_model_preds, axis=0)               # (L,)
        return retrospective_wastage(jnp.asarray(0.0), agg, log_actual,
                                     log_runtime, log_mask, max_seen, ttf)

    alphas = jnp.asarray(ALPHA_GRID)
    wastes = jax.vmap(waste_of)(alphas)
    return alphas[jnp.argmin(wastes)]


def _decision_cache_core(strategy: str, alpha: float, beta: float,
                         ttf: float, adaptive_alpha: bool, insample_preds,
                         ys, runtimes, mask, log_agg, log_actual,
                         log_runtime, log_mask, log_model_preds):
    """The task-INDEPENDENT half of the decision: accuracy scores (Eq. 1),
    the effective alpha, and the dynamic offset (§II-E).

    Everything here depends only on pool state (history buffers, in-sample
    predictions, prequential log), which changes exclusively at observe
    time — so the fused path computes it once per completion inside the
    observe dispatch and caches (acc, alpha, offset, offset_idx), keeping
    the per-prediction program free of the O(CAP log CAP) offset-selector
    sorts. Returns (acc (N,), alpha_eff, offset, offset_idx).
    """
    # AS from the models' in-sample predictions over the history buffer
    # (refreshed after every fit/update).
    acc = accuracy_score(insample_preds, ys, mask)
    if adaptive_alpha:
        a = _select_alpha(acc, log_model_preds, log_actual, log_runtime,
                          log_mask, strategy, beta, ttf)
        a = jnp.where(jnp.sum(log_mask) >= 5, a, alpha)
    else:
        a = jnp.asarray(alpha, jnp.float32)
    # offset from the *prequential* aggregate errors actually experienced;
    # while the log is young (< 5 predictions) fall back to the in-sample
    # errors of an accuracy-weighted aggregate so the very first model
    # predictions already carry a fault-tolerance offset (§II-E).
    off_log, idx_log = select_offset(log_actual - log_agg, log_agg,
                                     log_actual, log_runtime, log_mask,
                                     ttf)
    acc_w = gate_weights(raq_scores(acc, jnp.zeros_like(acc), 0.0),
                         strategy, beta)
    ins_agg = jnp.dot(acc_w, insample_preds,
                      precision=jax.lax.Precision.HIGHEST)
    off_ins, idx_ins = select_offset(ys - ins_agg, ins_agg, ys, runtimes,
                                     mask, ttf)
    young = jnp.sum(log_mask) < 5
    offset = jnp.where(young, jnp.maximum(off_ins, off_log), off_log)
    off_idx = jnp.where(young, idx_ins, idx_log)
    return acc, a, offset, off_idx


def _apply_gate(strategy: str, beta: float, model_preds, acc, alpha_eff):
    """The task-DEPENDENT half: ES from the current predictions, RAQ, and
    the gated aggregate (Eq. 2-4)."""
    eff = efficiency_scores(model_preds)
    raq = raq_scores(acc, eff, alpha_eff)
    weights = gate_weights(raq, strategy, beta)
    agg = gate_predictions(model_preds, raq, strategy, beta)
    return agg, raq, weights


def _combine_core(strategy: str, alpha: float, beta: float, ttf: float,
                  adaptive_alpha: bool, model_preds, insample_preds, ys,
                  runtimes, mask, log_agg, log_actual, log_runtime, log_mask,
                  log_model_preds):
    """RAQ -> gating -> offset (Eq. 1-4 + §II-E), recomputed inline — the
    legacy per-model-loop formulation. The fused path splits this into
    ``_decision_cache_core`` (at observe) + ``_apply_gate`` (at predict);
    both paths share those helpers so their numerics are identical."""
    acc, a, offset, off_idx = _decision_cache_core(
        strategy, alpha, beta, ttf, adaptive_alpha, insample_preds, ys,
        runtimes, mask, log_agg, log_actual, log_runtime, log_mask,
        log_model_preds)
    agg, raq, weights = _apply_gate(strategy, beta, model_preds, acc, a)
    return agg, raq, weights, offset, off_idx


@functools.lru_cache(maxsize=None)
def _jit_combine(strategy: str, alpha: float, beta: float, ttf: float,
                 adaptive_alpha: bool = False):
    """Legacy standalone combine (one of the N+1 dispatches of the
    per-model-loop path)."""

    def combine(model_preds, insample_preds, ys, runtimes, mask, log_agg,
                log_actual, log_runtime, log_mask, log_model_preds):
        return _combine_core(strategy, alpha, beta, ttf, adaptive_alpha,
                             model_preds, insample_preds, ys, runtimes, mask,
                             log_agg, log_actual, log_runtime, log_mask,
                             log_model_preds)

    return jax.jit(combine)


# ------------------------------------------------------------------- fused
def _pool_model_preds(models: tuple[str, ...], cfg: SizeyConfig,
                      use_pallas: bool, states, xb, *, insample=False):
    """All models' predictions over a (K, d) feature block ->
    ((N, K), knn_fell_back).

    The model states are heterogeneous pytrees, so the "vmap over models"
    of the paper's loop is realized as compiler-level fusion: each model's
    batched forward is emitted into ONE XLA program (one dispatch), with the
    MLP routed through the fused Pallas ensemble kernel on accelerators.
    ``insample``: ``xb`` is the pool's buffer, one query per row of the
    states' buffers, so the k-NN takes its in-sample path, whose fallback
    flag is returned.
    """
    cols = []
    fell_back = jnp.bool_(False)
    for i, m in enumerate(models):
        mod = MODEL_MODULES[m]
        with jax.named_scope(m):
            if m == "knn" and insample:
                col, fell_back = mod.insample_batch(states[i], xb,
                                                    k=cfg.knn_k)
                cols.append(col)
            elif m == "knn":
                cols.append(mod.predict_batch(states[i], xb, k=cfg.knn_k))
            elif m == "mlp":
                TRACE_COUNTS["mlp_pallas" if use_pallas else "mlp_jnp"] += 1
                cols.append(mod.predict_batch(states[i], xb,
                                              use_pallas=use_pallas))
            else:
                cols.append(mod.predict_batch(states[i], xb))
    return jnp.stack(cols), fell_back


@functools.lru_cache(maxsize=None)
def _fused_predict(models: tuple[str, ...], cfg: SizeyConfig, ttf: float,
                   use_pallas: bool):
    """One compiled function = the whole decision for K same-pool tasks.

    Consumes the per-pool decision cache (acc, alpha, offset, offset_idx)
    precomputed by the observe dispatch, so the per-prediction program is
    just the model forwards + the RAQ gate. Input and output are each ONE
    array so a decision costs exactly one host->device upload (features ||
    cap) and one device->host fetch.

    ``xc`` is (K, d+1): features with the machine cap appended per row.
    Returns (K, 5 + 3N) rows of
    [allocation, agg, offset, offset_idx, best_model, preds, raq, weights].
    """

    def predict_fn(states, xc, acc, alpha_eff, offset, off_idx):
        TRACE_COUNTS["predict"] += 1
        xb, caps = xc[:, :-1], xc[:, -1]
        preds, _ = _pool_model_preds(models, cfg, use_pallas, states, xb)

        def one(p, cap):
            agg, raq, weights = _apply_gate(cfg.strategy, cfg.beta, p, acc,
                                            alpha_eff)
            alloc = jnp.clip(agg + offset, cfg.min_alloc_gb, cap)
            head = jnp.stack([alloc, agg, offset,
                              off_idx.astype(jnp.float32),
                              jnp.argmax(raq).astype(jnp.float32)])
            return jnp.concatenate([head, p, raq, weights])

        with jax.named_scope("combine"):
            return jax.vmap(one, in_axes=(1, 0))(preds, caps)

    return jax.jit(predict_fn)


@functools.lru_cache(maxsize=None)
def _fused_observe_all(models: tuple[str, ...], cfg: SizeyConfig,
                       ttf: float, use_pallas: bool, incremental: bool):
    """All-model fit (or incremental update) + in-sample refresh + decision
    cache, one dispatch. ``incremental=False`` is the paper's default
    full-retrain mode (incl. MLP HPO); ``incremental=True`` takes the
    previous states and the newest buffer slot. Also returns the in-sample
    k-NN's fallback flag (``knn.insample_batch``)."""

    def observe_fn(states, xs, ys, runtimes, mask, new_idx, seed, log_agg,
                   log_actual, log_runtime, log_mask, log_model_preds):
        TRACE_COUNTS["update" if incremental else "fit"] += 1
        rng = jax.random.PRNGKey(seed)
        new_states = []
        for i, m in enumerate(models):
            mod = MODEL_MODULES[m]
            # the forest's split histograms take a kernel where Pallas
            # compiles, like the MLP's forward
            kw = {"use_pallas": use_pallas} if m == "forest" else {}
            with jax.named_scope(m):
                new_states.append(
                    mod.update(states[i], xs, ys, mask, new_idx, rng, cfg,
                               **kw)
                    if incremental else mod.fit(xs, ys, mask, rng, cfg, **kw))
        new_states = tuple(new_states)
        insample, fell_back = _pool_model_preds(
            models, cfg, use_pallas, new_states, xs, insample=True)
        with jax.named_scope("combine"):
            cache = _decision_cache_core(
                cfg.strategy, cfg.alpha, cfg.beta, ttf, cfg.adaptive_alpha,
                insample, ys, runtimes, mask, log_agg, log_actual,
                log_runtime, log_mask, log_model_preds)
        return new_states, insample, cache, fell_back

    return jax.jit(observe_fn)


@functools.lru_cache(maxsize=None)
def _fused_refresh_all(models: tuple[str, ...], cfg: SizeyConfig,
                       ttf: float, use_pallas: bool):
    """In-sample refresh + decision cache against EXISTING model states —
    the cheap half of the observe dispatch, used between the amortized
    full retrains of the ``refit_growth`` schedule. Newly appended history
    and prequential-log rows flow into the accuracy score and the offset
    selector immediately; only the model parameters stay at their last-fit
    values. One dispatch, no training step. Also returns the in-sample
    k-NN's fallback flag."""

    def refresh_fn(states, xs, ys, runtimes, mask, log_agg, log_actual,
                   log_runtime, log_mask, log_model_preds):
        TRACE_COUNTS["refresh"] += 1
        insample, fell_back = _pool_model_preds(
            models, cfg, use_pallas, states, xs, insample=True)
        with jax.named_scope("combine"):
            cache = _decision_cache_core(
                cfg.strategy, cfg.alpha, cfg.beta, ttf, cfg.adaptive_alpha,
                insample, ys, runtimes, mask, log_agg, log_actual,
                log_runtime, log_mask, log_model_preds)
        return insample, cache, fell_back

    return jax.jit(refresh_fn)


def _batch_bucket(k: int) -> int:
    """Round a batch size up to the next power of two (bounds compiles)."""
    b = 1
    while b < k:
        b *= 2
    return b


class SizeyPredictor:
    """Online multi-model memory predictor (the paper's contribution).

    ``fused=True`` (default) runs the single-dispatch decision loop;
    ``fused=False`` keeps the pre-fusion per-model-loop path for numerical
    reference and benchmarking.
    """

    def __init__(self, cfg: SizeyConfig | None = None,
                 db: ProvenanceDB | None = None, *, n_features: int = 1,
                 ttf: float = 1.0, default_machine_cap_gb: float = 128.0,
                 fused: bool = True, use_pallas: bool | None = None):
        self.cfg = cfg or SizeyConfig()
        self.n_features = n_features
        self.models = tuple(self.cfg.model_classes)
        self.db = db or ProvenanceDB(n_features=n_features,
                                     n_models=len(self.models))
        self.ttf = float(ttf)
        self.default_machine_cap_gb = default_machine_cap_gb
        self.fused = fused
        self.use_pallas = pallas_available() if use_pallas is None \
            else use_pallas
        # per-pool model states: key -> tuple of states in self.models order
        self.states: dict[tuple[str, str], tuple] = {}
        # per-pool decision cache (acc, alpha_eff, offset, offset_idx),
        # refreshed by every fused observe dispatch (task-independent half
        # of the decision — see _decision_cache_core)
        self._cache: dict[tuple[str, str], tuple] = {}
        # predict-view of the states: fields predict() never reads are
        # dropped (None leaves) so the hot dispatch flattens fewer arrays
        self._pview: dict[tuple[str, str], tuple] = {}
        self._predict_fn = None
        self._fit_serial: dict[tuple[str, str], int] = {}
        # amortized-refit bookkeeping (cfg.refit_growth > 0): the history
        # count a pool must reach before its next full retrain, and the
        # buffer capacity its states were fit at (capacity growth forces a
        # refit so every fit runs at the pool's current padded shape)
        self._next_fit_at: dict[tuple[str, str], int] = {}
        self._fit_cap: dict[tuple[str, str], int] = {}
        self.model_select_counts = np.zeros(len(self.models), np.int64)

    # ------------------------------------------------------------- predict
    def predict(self, task_type: str, machine: str, features,
                user_preset_gb: float,
                machine_cap_gb: float | None = None) -> SizingDecision:
        """Size one task: ensemble predict -> RAQ gate -> offset -> clamp.

        Deterministic given the pool's observation history — no rng, no
        wall clock — so a journal warm start that replays the same
        observations reproduces every decision bitwise. Pools younger
        than ``cfg.min_history`` return the user preset
        (``source != "model"``) untouched by models, offsets or risk
        bands."""
        cap_gb = (self.default_machine_cap_gb if machine_cap_gb is None
                  else machine_cap_gb)
        feats = tuple(float(f) for f in np.atleast_1d(features))
        pool = self.db.pool(task_type, machine)
        key = (task_type, machine)

        if pool.count < self.cfg.min_history or key not in self.states:
            # unknown/young task type -> user preset straight to the RM (§I)
            return self._preset_decision(task_type, machine, feats,
                                         user_preset_gb, cap_gb)
        if not self.fused:
            return self._predict_loop(key, pool, feats, user_preset_gb,
                                      cap_gb)
        return self._predict_pool(
            key, pool, np.asarray([feats], np.float32),
            np.asarray([cap_gb], np.float32), [user_preset_gb])[0]

    def predict_batch(self, tasks) -> list[SizingDecision]:
        """Batched scheduler API: decide a burst of submissions at once.

        ``tasks`` is any sequence of objects exposing ``task_type``,
        ``machine``, ``features``, ``user_preset_gb`` and (optionally)
        ``machine_cap_gb`` — e.g. ``TaskQuery`` or ``TaskInstance``.
        Submissions are grouped per (task_type, machine) pool; each group is
        decided by ONE fused vmapped dispatch (batch padded to a power-of-
        two bucket), so K decisions cost one launch per pool instead of K.
        Decisions are returned in submission order and are numerically
        identical to calling :meth:`predict` per task.
        """
        out: list[SizingDecision | None] = [None] * len(tasks)
        groups: dict[tuple[str, str], list[int]] = {}
        for i, t in enumerate(tasks):
            groups.setdefault((t.task_type, t.machine), []).append(i)
        for key, idxs in groups.items():
            pool = self.db.pool(*key)
            caps = np.asarray(
                [self.default_machine_cap_gb
                 if getattr(tasks[i], "machine_cap_gb", None) is None
                 else tasks[i].machine_cap_gb for i in idxs], np.float32)
            presets = [float(tasks[i].user_preset_gb) for i in idxs]
            featrows = [tuple(float(f) for f in
                              np.atleast_1d(tasks[i].features))
                        for i in idxs]
            if pool.count < self.cfg.min_history or key not in self.states:
                for j, i in enumerate(idxs):
                    out[i] = self._preset_decision(key[0], key[1],
                                                   featrows[j], presets[j],
                                                   float(caps[j]))
            elif not self.fused:
                for j, i in enumerate(idxs):
                    out[i] = self._predict_loop(key, pool, featrows[j],
                                                presets[j], float(caps[j]))
            else:
                xb = np.asarray(featrows, np.float32)
                for i, d in zip(idxs,
                                self._predict_pool(key, pool, xb, caps,
                                                   presets)):
                    out[i] = d
        return out  # type: ignore[return-value]

    @staticmethod
    def _preset_decision(task_type: str, machine: str, feats,
                         user_preset_gb: float,
                         cap_gb: float) -> SizingDecision:
        """Cold pool / young task type: the user preset goes straight to
        the resource manager, clamped to the machine cap (§I)."""
        return SizingDecision(task_type, machine, feats, "preset",
                              min(user_preset_gb, cap_gb), user_preset_gb,
                              cap_gb)

    def _predict_pool(self, key, pool, xb: np.ndarray, caps: np.ndarray,
                      presets) -> list[SizingDecision]:
        """One fused dispatch deciding K tasks of one pool."""
        k = xb.shape[0]
        kpad = _batch_bucket(k)
        if kpad != k:
            xb = np.concatenate([xb, np.repeat(xb[-1:], kpad - k, axis=0)])
            caps = np.concatenate([caps, np.repeat(caps[-1:], kpad - k)])
        fn = self._predict_fn
        if fn is None:
            fn = self._predict_fn = _fused_predict(self.models, self.cfg,
                                                   self.ttf, self.use_pallas)
        acc, alpha_eff, offset, off_idx = self._cache[key]
        xc = np.concatenate([xb, caps[:, None]], axis=1)
        # one upload in, one dispatch, one fetch out
        DISPATCH_COUNTS["predict_pool"] += 1
        DISPATCH_COUNTS["decisions"] += k
        with _span("predict", pool=f"{key[0]}@{key[1]}", k=k):
            out = np.asarray(fn(self._pview[key], jnp.asarray(xc), acc,
                                alpha_eff, offset, off_idx))
        n = len(self.models)
        decisions = []
        for j in range(k):
            row = out[j]
            self.model_select_counts[int(row[4])] += 1
            decisions.append(SizingDecision(
                key[0], key[1], tuple(float(v) for v in xb[j]), "model",
                float(row[0]), float(presets[j]), float(caps[j]),
                model_preds=row[5:5 + n], raq=row[5 + n:5 + 2 * n],
                weights=row[5 + 2 * n:5 + 3 * n],
                agg_pred_gb=float(row[1]), offset_gb=float(row[2]),
                offset_idx=int(row[3])))
        return decisions

    def _predict_loop(self, key, pool, feats, user_preset_gb: float,
                      cap_gb: float) -> SizingDecision:
        """Pre-fusion reference: one dispatch per model + a combine call,
        with the full pool re-uploaded from host every prediction (the
        seed implementation's cost model)."""
        x = jnp.asarray(feats, jnp.float32)
        preds = jnp.stack([
            _jit_predict(m, self.cfg)(self.states[key][i], x)
            for i, m in enumerate(self.models)
        ])
        combine = _jit_combine(self.cfg.strategy, self.cfg.alpha,
                               self.cfg.beta, self.ttf,
                               self.cfg.adaptive_alpha)
        up = lambda a: jnp.asarray(np.asarray(a))   # host round-trip
        agg, raq, weights, offset, off_idx = combine(
            preds, up(pool.insample_preds), up(pool.ys), up(pool.runtimes),
            up(pool.mask), up(pool.log_agg), up(pool.log_actual),
            up(pool.log_runtime), up(pool.log_mask),
            up(pool.log_model_preds))

        alloc = float(np.clip(float(agg) + float(offset),
                              self.cfg.min_alloc_gb, cap_gb))
        self.model_select_counts[int(np.argmax(np.asarray(raq)))] += 1
        return SizingDecision(key[0], key[1], tuple(feats), "model", alloc,
                              user_preset_gb, cap_gb,
                              model_preds=np.asarray(preds),
                              raq=np.asarray(raq),
                              weights=np.asarray(weights),
                              agg_pred_gb=float(agg),
                              offset_gb=float(offset),
                              offset_idx=int(off_idx))

    # ------------------------------------------------------------- failure
    def retry_allocation(self, decision: SizingDecision, attempt: int,
                         last_alloc_gb: float) -> float:
        """Retry-ladder step after an OOM kill: a pure function of
        (attempt index, last allocation, pool max-seen, machine cap), so
        journal replay re-derives the same ladder without re-asking."""
        pool = self.db.pool(decision.task_type, decision.machine)
        return retry_allocation(attempt, last_alloc_gb, pool.max_seen_gb,
                                decision.machine_cap_gb)

    # ------------------------------------------------------------- observe
    def observe(self, decision: SizingDecision, peak_mem_gb: float,
                runtime_h: float, attempts: int = 1,
                workflow: str = "") -> None:
        """Task completed: update provenance, prequential log, and models."""
        key = (decision.task_type, decision.machine)
        self.db.add(TaskRecord(decision.task_type, decision.machine,
                               decision.features, float(peak_mem_gb),
                               float(runtime_h), attempts, workflow))
        pool = self.db.pool(*key)
        if decision.source == "model":
            self.db.add_log(decision.task_type, decision.machine,
                            decision.model_preds, decision.agg_pred_gb,
                            float(peak_mem_gb), float(runtime_h))
        if pool.count < self.cfg.min_history:
            return

        serial = self._fit_serial.get(key, 0)
        seed = (stable_hash(f"{key}") + serial + self.cfg.seed) % (2**31)
        if not self.fused:
            self._observe_loop(key, pool, seed)
        else:
            self._maybe_refit(key, pool, seed)
        self._fit_serial[key] = serial + 1

    def observe_batch(self, observations) -> None:
        """Observe a wave of simultaneous completions in ONE fused observe
        dispatch per pool (the cluster engine's completion-wave path).

        ``observations`` is a sequence of ``(decision, peak_mem_gb,
        runtime_h, attempts, workflow)`` tuples, in completion order. Per
        pool, all records and prequential-log rows are appended first and
        the models are then refit ONCE. In the default full-retrain mode
        the refit is seeded exactly as the LAST of the sequential fits
        ``observe`` would have run, and a fit over the full history is a
        function of the final buffers only — so the resulting model
        states, decision cache, and in-sample predictions are bitwise
        those of the sequential path (a batch of one IS the sequential
        path, which keeps the cluster engine's serial-equivalence
        invariant). Incremental mode folds records in one at a time by
        construction, so it falls back to per-record observes.
        """
        if not self.fused or self.cfg.incremental:
            for decision, peak, rt, attempts, workflow in observations:
                self.observe(decision, peak, rt, attempts, workflow)
            return
        groups: dict[tuple[str, str], list] = {}
        for obs in observations:
            d = obs[0]
            groups.setdefault((d.task_type, d.machine), []).append(obs)
        for key, obs_list in groups.items():
            pool = self.db.pool(*key)
            c0 = pool.count
            with _span("history/append", n=len(obs_list)):
                for decision, peak, rt, attempts, workflow in obs_list:
                    self.db.add(TaskRecord(key[0], key[1], decision.features,
                                           float(peak), float(rt), attempts,
                                           workflow))
                    if decision.source == "model":
                        self.db.add_log(key[0], key[1], decision.model_preds,
                                        decision.agg_pred_gb, float(peak),
                                        float(rt))
            # how many of the sequential observes would have refit: record
            # j (1-based) fits iff c0 + j >= min_history
            n = len(obs_list)
            m = n - max(0, min(self.cfg.min_history - c0 - 1, n))
            if m <= 0:
                continue
            serial = self._fit_serial.get(key, 0)
            seed = (stable_hash(f"{key}") + serial + (m - 1)
                    + self.cfg.seed) % (2**31)
            self._maybe_refit(key, pool, seed)
            self._fit_serial[key] = serial + m

    def warm_start(self) -> None:
        """Refit every pool restored from a JSONL checkpoint so prediction
        resumes warm (model states + decision cache, i.e. offsets and
        adaptive alpha, straight from the restored buffers and prequential
        log). Exact for the full-retrain mode: the rebuilt states use the
        same seed as the original's last fit. Under the amortized-refit
        schedule (``cfg.refit_growth > 0``) the original's last FULL fit
        generally predates its newest records; its horizon is journaled
        as a ``fit`` aux row on the same JSONL, so the restore replays
        exactly that fit (the seed is a function of the fit-time count,
        the mask truncated to the fit-time horizon) and then runs one
        refresh over the full buffers — states, in-sample predictions,
        and decision cache all land bitwise where the live process left
        them, whatever the observe-wave shapes were."""
        stride = (self.fused and not self.cfg.incremental
                  and self.cfg.refit_growth > 0.0)
        for key, pool in self.db.pools.items():
            if pool.count < self.cfg.min_history or key in self.states:
                continue
            m = max(pool.count - self.cfg.min_history + 1,
                    self._fit_serial.get(key, 0) + 1)
            c_f = self._last_fit_count(key, pool) if stride else pool.count
            seed = (stable_hash(f"{key}") + (c_f - self.cfg.min_history)
                    + self.cfg.seed) % (2**31)
            if not self.fused:
                self._observe_loop(key, pool, seed)
            elif c_f < pool.count:
                trunc = np.zeros(pool.cap, np.float32)
                trunc[:c_f] = 1.0
                self._refit_fused(key, pool, seed, mask=jnp.asarray(trunc))
                fn = _fused_refresh_all(self.models, self.cfg, self.ttf,
                                        self.use_pallas)
                insample, cache, fell_back = fn(
                    self.states[key], pool.xs, pool.ys, pool.runtimes,
                    pool.mask, pool.log_agg, pool.log_actual,
                    pool.log_runtime, pool.log_mask, pool.log_model_preds)
                self._cache[key] = cache
                pool.insample_preds = insample
                self._count_dispatch("refresh_pool", pool, fell_back)
            else:
                self._refit_fused(key, pool, seed)
            self._fit_serial[key] = m
            if stride:
                self._fit_cap[key] = pool.cap
                self._next_fit_at[key] = c_f + max(
                    1, math.ceil(self.cfg.refit_growth * c_f))

    def _maybe_refit(self, key, pool, seed: int) -> None:
        """Observe-half dispatcher under the amortized-refit schedule.

        ``refit_growth == 0`` (default) retrains on every observe — the
        paper's online loop, bitwise-pinned by the regression tests. With
        ``refit_growth = r > 0`` a pool fully retrains only once its
        history has grown by the fraction ``r`` since the last fit (or its
        buffers grew, so every fit runs at the current padded shape); in
        between, one cheap fused refresh recomputes the in-sample
        predictions and the decision cache against the existing states, so
        offsets and accuracy scores still see every completion. O(log n)
        retrains per pool instead of O(n)."""
        if (self.cfg.refit_growth <= 0.0 or self.cfg.incremental
                or key not in self.states
                or self._fit_cap.get(key) != pool.cap
                or pool.count >= self._next_fit_at.get(key, 0)):
            self._refit_fused(key, pool, seed)
            self._note_fit(key, pool)
            return
        fn = _fused_refresh_all(self.models, self.cfg, self.ttf,
                                self.use_pallas)
        with _span("refresh", pool=f"{key[0]}@{key[1]}", n=pool.count,
                   cap=pool.cap):
            insample, cache, fell_back = fn(
                self.states[key], pool.xs, pool.ys, pool.runtimes,
                pool.mask, pool.log_agg, pool.log_actual, pool.log_runtime,
                pool.log_mask, pool.log_model_preds)
            self._cache[key] = cache
            pool.insample_preds = insample
            jax.block_until_ready(insample)
        self._count_dispatch("refresh_pool", pool, fell_back)

    def _count_dispatch(self, kind: str, pool, fell_back) -> None:
        """Count one finished observe or refresh launch, and the distances
        its in-sample k-NN evaluated (``knn.insample_pairs``). Where the
        k-NN took its sorted path the launch's fallback flag is read:
        ``knn_fallback`` counts the launches that took the brute force."""
        DISPATCH_COUNTS[kind] += 1
        if "knn" in self.models:
            knn = MODEL_MODULES["knn"]
            d = pool.xs.shape[1]
            fell = knn.sorted_insample(pool.cap, d) and bool(fell_back)
            DISPATCH_COUNTS["knn_fallback"] += int(fell)
            DISPATCH_COUNTS["knn_pairs"] += knn.insample_pairs(
                pool.cap, d, self.cfg.knn_k, fell)

    def _note_fit(self, key, pool) -> None:
        self._fit_cap[key] = pool.cap
        self._next_fit_at[key] = pool.count + max(
            1, math.ceil(self.cfg.refit_growth * pool.count))
        if self.cfg.refit_growth > 0.0 and not self.cfg.incremental:
            # journal the fit horizon (O(log n) rows per pool): which
            # count the last FULL retrain ran at is a function of the
            # observe-wave shapes, not of the count alone, so a restore
            # reads it back instead of guessing (see warm_start)
            self.db.add_aux(FIT_KIND, {"task_type": key[0],
                                       "machine": key[1],
                                       "count": pool.count})

    def _last_fit_count(self, key, pool) -> int:
        """The history count at which the amortized-refit schedule last
        fully retrained this pool: the newest journaled fit row (falls
        back to the full count for checkpoints predating the stride,
        which then simply refit at the horizon — self-consistent, and
        journaled again on the next fit)."""
        c_f = pool.count
        for row in self.db.aux.get(FIT_KIND, ()):
            if (row["task_type"], row["machine"]) == key:
                c_f = int(row["count"])
        return min(c_f, pool.count)

    def _refit_fused(self, key, pool, seed: int, mask=None) -> None:
        """One fused dispatch: all-model fit/update + in-sample refresh +
        decision cache. The single device launch of the observe half.
        ``mask`` overrides the pool mask (warm-start reconstruction of a
        fit that ran before the newest records arrived)."""
        incremental = key in self.states and self.cfg.incremental
        fn = _fused_observe_all(self.models, self.cfg, self.ttf,
                                self.use_pallas, incremental)
        with _span("observe", pool=f"{key[0]}@{key[1]}", n=pool.count,
                   cap=pool.cap):
            states, insample, cache, fell_back = fn(
                self.states[key] if incremental else None, pool.xs, pool.ys,
                pool.runtimes, pool.mask if mask is None else mask,
                pool.count - 1, seed,
                pool.log_agg, pool.log_actual, pool.log_runtime,
                pool.log_mask, pool.log_model_preds)
            self.states[key] = states
            self._cache[key] = cache
            self._pview[key] = tuple(
                s._replace(**{f: None for f in MODEL_MODULES[m].PREDICT_DROP})
                if MODEL_MODULES[m].PREDICT_DROP else s
                for m, s in zip(self.models, states))
            pool.insample_preds = insample
            jax.block_until_ready(insample)
        self._count_dispatch("observe_pool", pool, fell_back)

    def _observe_loop(self, key, pool, seed: int) -> None:
        """Pre-fusion reference: per-model fit/update dispatches plus an
        np.stack host round-trip for the in-sample refresh."""
        xs = jnp.asarray(np.asarray(pool.xs))
        ys = jnp.asarray(np.asarray(pool.ys))
        mask = jnp.asarray(np.asarray(pool.mask))
        rng = jax.random.PRNGKey(seed)
        if key not in self.states or not self.cfg.incremental:
            states = tuple(_jit_fit(m, self.cfg)(xs, ys, mask, rng)
                           for m in self.models)
        else:
            new_idx = jnp.asarray(pool.count - 1)
            states = tuple(
                _jit_update(m, self.cfg)(self.states[key][i], xs, ys, mask,
                                         new_idx, rng)
                for i, m in enumerate(self.models))
        self.states[key] = states
        # refresh in-sample predictions for the accuracy score (Eq. 1)
        pool.insample_preds = jnp.asarray(np.stack([
            np.asarray(_jit_predict_batch(m, self.cfg)(states[i], xs))
            for i, m in enumerate(self.models)
        ]))
        jax.block_until_ready(self.states[key])
