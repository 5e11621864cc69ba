"""k-nearest-neighbours regression over the masked history buffer.

TPU adaptation: sklearn's KDTree is pointer-chasing. A prediction for a
few novel queries is brute force: every buffer row's distance, then one
``top_k`` (``predict_batch``). The in-sample refresh of every fit asks
the same of each buffer row against the buffer, CAP² work, which at CAP
16384 dominates the fit. With one feature the k nearest rows lie next to
the query in sorted order, so ``insample_batch`` sorts the buffer once
and selects from a short window around each run of equal values,
exactly, falling back to the brute force where it cannot show that the
window holds the answer. The pairwise distances + k-select are also
provided as a Pallas kernel (repro/kernels/knn); this module is the
model-pool wrapper and stores normalization state.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.config import SizeyConfig

_EPS = 1e-9
# distinct query values that match no valid row (masked rows' pads) the
# sorted path answers by brute force before it falls back
_FOREIGN = 8
# smallest buffer the sorted path takes: below it the brute force is as
# fast on a TPU v5e (fused observe at CAP 1024: 2.17 ms brute, 2.37 sorted;
# the k-NN alone at 2048: 0.85 ms brute, 0.40 sorted)
_SORTED_MIN_CAP = 2048
# a TPU divide is not correctly rounded, so a distance may step back by an
# ulp or two outward; the window's boundary rows must clear the k-th
# selected distance by this much
_MARGIN = 16 * float(jnp.finfo(jnp.float32).eps)


class KNNState(NamedTuple):
    xs: jnp.ndarray     # (CAP, d) raw features
    ys: jnp.ndarray     # (CAP,)
    mask: jnp.ndarray   # (CAP,)
    scale: jnp.ndarray  # (d,) per-feature std for distance normalization


PREDICT_DROP: tuple[str, ...] = ()  # instance-based: predict reads it all


def init(d: int, cfg: SizeyConfig) -> KNNState:
    return KNNState(jnp.zeros((0, d)), jnp.zeros((0,)), jnp.zeros((0,)),
                    jnp.ones((d,)))


def _feature_scale(xs: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    n = jnp.maximum(jnp.sum(mask), 1.0)
    mu = jnp.sum(xs * mask[:, None], 0) / n
    var = jnp.sum(((xs - mu) ** 2) * mask[:, None], 0) / n
    return jnp.sqrt(var) + _EPS


def fit(xs: jnp.ndarray, ys: jnp.ndarray, mask: jnp.ndarray, key,
        cfg: SizeyConfig) -> KNNState:
    return KNNState(xs, ys, mask, _feature_scale(xs, mask))


def update(state: KNNState, xs: jnp.ndarray, ys: jnp.ndarray,
           mask: jnp.ndarray, new_idx: jnp.ndarray, key,
           cfg: SizeyConfig) -> KNNState:
    # KNN is instance-based: "update" = take the refreshed buffers.
    return KNNState(xs, ys, mask, _feature_scale(xs, mask))


def predict_batch(state: KNNState, xs: jnp.ndarray, *,
                  k: int = 5) -> jnp.ndarray:
    """Vectorized predict over a (K, d) feature block -> (K,)."""
    return jax.vmap(lambda x: predict(state, x, k=k))(xs)


def pairs(n_queries: int, n_rows: int) -> int:
    """Distances ``predict_batch`` evaluates for ``n_queries`` query rows
    against a state buffer of ``n_rows`` rows: every query, masked or not,
    against every buffer row."""
    return n_queries * n_rows


def _sq_dists(xs: jnp.ndarray, x: jnp.ndarray,
              scale: jnp.ndarray) -> jnp.ndarray:
    """Scaled squared distances of rows ``xs`` (..., d) to ``x`` (..., d)."""
    return jnp.sum(((xs - x) / scale) ** 2, -1)


def _mean_of_nearest(d2: jnp.ndarray, ys: jnp.ndarray) -> jnp.ndarray:
    """Mean of the ``ys`` whose selected distance ``d2`` is finite; masked
    rows sit at +inf and get weight 0."""
    valid = jnp.isfinite(d2)
    n = jnp.maximum(jnp.sum(valid, -1), 1.0)
    return jnp.sum(jnp.where(valid, ys, 0.0), -1) / n


def predict(state: KNNState, x: jnp.ndarray, *, k: int = 5) -> jnp.ndarray:
    d2 = _sq_dists(state.xs, x[None, :], state.scale[None, :])
    d2 = jnp.where(state.mask > 0, d2, jnp.inf)
    # top-k smallest distances, ties to the lower row index
    neg, nn_idx = jax.lax.top_k(-d2, min(k, d2.shape[0]))
    return _mean_of_nearest(-neg, state.ys[nn_idx])


def sorted_insample(n_rows: int, d: int) -> bool:
    """Whether ``insample_batch`` takes its sorted path for a buffer of
    ``n_rows`` rows of d features."""
    return d == 1 and n_rows >= _SORTED_MIN_CAP


def _offsets(k: int) -> range:
    """A run's candidates on the sorted path, as kept slots from its first:
    the 2k before it and the 3k from it on (the run itself is at most
    k)."""
    return range(-2 * k, 3 * k)


def insample_pairs(n_rows: int, d: int, k: int, fell_back: bool) -> int:
    """Distances one ``insample_batch`` launch evaluated: on the sorted
    path a window of 5k rows for each of the ``n_rows`` sorted slots, and
    the ``_FOREIGN`` brute-force values against every row; every row
    against every row off that path or where the launch fell back."""
    if not sorted_insample(n_rows, d) or fell_back:
        return pairs(n_rows, n_rows)
    return n_rows * (len(_offsets(k)) + _FOREIGN)


def _shift(v: jnp.ndarray, s: int, fill) -> jnp.ndarray:
    """``out[i] = v[i + s]``, ``fill`` past either end."""
    n = v.shape[0]
    if abs(s) >= n:
        return jnp.full_like(v, fill)
    pad = jnp.full((abs(s),), fill, v.dtype)
    return (jnp.concatenate([v[s:], pad]) if s >= 0
            else jnp.concatenate([pad, v[:n + s]]))


def insample_batch(state: KNNState, xs: jnp.ndarray, *,
                   k: int = 5) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``predict_batch(state, xs)`` for a pool's in-sample block, one query
    per buffer row (its rows may be newer than the state's) -> ((CAP,),
    fell_back), bit for bit.

    On the sorted path (``sorted_insample``: d = 1 and at least
    ``_SORTED_MIN_CAP`` rows) a query's answer depends on its value
    alone. The state's valid rows are sorted by (x, row); each run of
    equal x is cut to its first k rows (no later row of a run can be
    selected) and the kept rows are compacted. Each run is answered
    once, in that order, from the 2k kept rows before it and the 3k from
    it on: the first k by (distance, row), as ``top_k`` breaks ties.
    Distances grow outward from the run, so that window holds the answer
    when the kept rows just outside it are missing or strictly farther
    than the k-th selected. A query takes its run's answer; a query whose
    value no valid row has (a masked row's pad) takes one of ``_FOREIGN``
    brute-force answers. Where a run cannot show its window exact, a
    query is left unanswered, or a valid x is not finite, the whole block
    takes the brute force and ``fell_back`` is set. Windows are static
    shifts of the sorted rows, not gathers: a TPU gathers one element at
    a time."""
    cap, d = state.xs.shape
    if not sorted_insample(cap, d):
        return predict_batch(state, xs, k=k), jnp.bool_(False)
    pos = jnp.arange(cap, dtype=jnp.int32)
    valid = state.mask > 0
    # the sort orders -0.0 before 0.0; their distances are equal
    canon = lambda v: jnp.where(v == 0, 0.0, v)
    ref = canon(state.xs[:, 0])
    skey, srow, sy = jax.lax.sort(
        (jnp.where(valid, ref, jnp.inf), pos, state.ys), num_keys=2)
    new_run = jnp.concatenate([jnp.ones((1,), bool), skey[1:] != skey[:-1]])
    run_start = jax.lax.cummax(jnp.where(new_run, pos, 0))
    keep = (skey < jnp.inf) & (pos - run_start < k)
    _, ckey, crow, cy = jax.lax.sort(
        (jnp.where(keep, pos, cap + pos), jnp.where(keep, skey, jnp.inf),
         srow, sy), num_keys=1)

    # each run from its first kept slot; a slot past either end is empty
    def dist(s):
        other = _shift(ckey, s, jnp.inf)
        d2 = _sq_dists(other[:, None], ckey[:, None], state.scale)
        return jnp.where(jnp.isfinite(other), d2, jnp.inf)
    offs = _offsets(k)
    d2 = jnp.stack([dist(s) for s in offs], 1)
    rows = jnp.stack([_shift(crow, s, cap) for s in offs], 1)
    ys = jnp.stack([_shift(cy, s, 0.0) for s in offs], 1)
    picked = jnp.zeros(d2.shape, bool)
    sel_d2, sel_y = [], []
    for _ in range(min(k, cap)):   # k rounds of the (distance, row) minimum
        free = jnp.where(picked, jnp.inf, d2)
        low = jnp.min(free, 1, keepdims=True)
        tie = (free == low) & ~picked
        first = jnp.min(jnp.where(tie, rows, 2 * cap), 1, keepdims=True)
        pick = tie & (rows == first)
        picked = picked | pick
        sel_d2.append(low[:, 0])
        sel_y.append(jnp.sum(jnp.where(pick, ys, 0.0), 1))
    sel_d2 = jnp.stack(sel_d2, 1)
    answer = _mean_of_nearest(sel_d2, jnp.stack(sel_y, 1))
    bar = sel_d2[:, -1] * (1.0 + _MARGIN)
    run_ok = jnp.ones((cap,), bool)
    for s in (offs[0] - 1, offs[-1] + 1):   # the kept rows just outside
        outside = _shift(ckey, s, jnp.inf)
        run_ok &= ~jnp.isfinite(outside) | (dist(s) > bar)
    is_run = jnp.isfinite(ckey) & (ckey != _shift(ckey, -1, jnp.nan))

    # queries: the answer of the run with their value, else a brute-force
    # answer for one of the first _FOREIGN other values
    xq = canon(xs[:, 0])
    lo = jnp.minimum(jnp.searchsorted(ckey, xq, side="left",
                                      method="sort"), cap - 1)
    in_run = ckey[lo] == xq
    other, values = ~in_run, [-jnp.inf]
    for _ in range(_FOREIGN):      # the smallest distinct other values
        values.append(jnp.min(jnp.where(other & (xq > values[-1]), xq,
                                        jnp.inf)))
    values = jnp.stack(values[1:])
    brute = predict_batch(state, values[:, None], k=k)
    out, covered = answer[lo], in_run
    for j in range(_FOREIGN):
        hit = other & (xq == values[j])
        out = jnp.where(hit, brute[j], out)
        covered |= hit
    exact = (jnp.all(run_ok | ~is_run) & jnp.all(covered)
             & jnp.all(jnp.isfinite(ref) | ~valid))
    out = jax.lax.cond(exact, lambda: out,
                       lambda: predict_batch(state, xs, k=k))
    return out, ~exact
