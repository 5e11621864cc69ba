"""Random-forest regression as an ensemble of *oblivious* trees.

Hardware adaptation (DESIGN.md §3): classic CART forests are pointer-chasing
and do not vectorize on TPU. We replace them with oblivious regression trees
— one (feature, threshold) pair per level shared across the whole level — so

  * prediction is a bit-packed comparison + a 2^depth leaf-table gather,
    pure jnp, batchable over (trees × tasks);
  * training is an exhaustive search over candidate thresholds (16 masked
    quantiles per feature), all trees at once, with per-tree Poisson
    bootstrap weights for ensemble diversity. Each level's histogram (the
    weighted count, sum and sum of squares of every child of every
    candidate split, in every tree) is one dense masked accumulation: each
    row's statistics, one-hot on its child segment, are added to all
    (tree, candidate, segment) sums at once, row after row, in the
    ``split_hist`` Pallas kernel. So a fit lowers with no scatter, whose
    per-row updates serialize on an accelerator, and the sums keep a
    scatter-add's row order bit for bit.
    The last level's sums under the chosen split are the leaf sums.

The incremental update keeps the grown structure and refreshes the leaf
means from the full buffer (structure-frozen leaf refit) — O(CAP * trees),
the forest analogue of the paper's lightweight online step.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.config import SizeyConfig
from repro.kernels.split_hist import split_hist

_EPS = 1e-9
N_QUANTILES = 16


class ForestState(NamedTuple):
    feat: jnp.ndarray       # (T, D) int32 — split feature per tree level
    thresh: jnp.ndarray     # (T, D) float32 — split threshold per tree level
    leaf_vals: jnp.ndarray  # (T, 2^D) float32 — leaf means
    global_mean: jnp.ndarray


# state fields predict() never reads — dropped (set to None) from the
# hot-path dispatch pytree by the fused predictor
PREDICT_DROP = ("global_mean",)


def init(d: int, cfg: SizeyConfig) -> ForestState:
    t, dep = cfg.forest_trees, cfg.forest_depth
    return ForestState(jnp.zeros((t, dep), jnp.int32),
                       jnp.zeros((t, dep), jnp.float32),
                       jnp.zeros((t, 2 ** dep), jnp.float32),
                       jnp.zeros(()))


def _candidate_thresholds(xs: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """(d, Q) candidate thresholds = masked per-feature quantiles."""
    qs = jnp.linspace(0.05, 0.95, N_QUANTILES)
    xm = jnp.where(mask[:, None] > 0, xs, jnp.nan)
    return jnp.nanquantile(xm, qs, axis=0).T  # (d, Q)


def _hist(seg: jnp.ndarray, stats: jnp.ndarray, n_seg: int,
          use_pallas: bool = False) -> jnp.ndarray:
    """Per-segment sums of row statistics, in row order.

    seg (CAP, T, C) int32: each row's segment under each tree and column;
    stats (CAP, T, S). Returns (T, C, S, n_seg). Every (tree, column,
    statistic, segment) sum adds each row's statistic where the segment is
    the row's and 0.0 elsewhere, one row after another: the sums of a
    scatter-add in row order, bit for bit, without its serialized updates.
    The order matters: the split SSE below cancels, and candidates whose
    SSE differ by less than the round-off would swap under another one.
    ``use_pallas`` compiles the ``split_hist`` kernel (a TPU); elsewhere
    the Pallas interpreter runs it, faster on a CPU than an XLA row loop.
    """
    with jax.named_scope("split_hist"):
        cap, t, c = seg.shape
        s = stats.shape[2]
        # (tree, column) on the minor axis: the sums stay lane-dense
        val = jnp.broadcast_to(stats.transpose(2, 0, 1)[..., None],
                               (s, cap, t, c)).reshape(s, cap, t * c)
        acc = split_hist(seg.reshape(cap, t * c), val, n_seg=n_seg,
                         interpret=not use_pallas)
        return acc.reshape(s, n_seg, t, c).transpose(2, 3, 0, 1)


def _grow(w: jnp.ndarray, xs: jnp.ndarray, ys: jnp.ndarray,
          cands: jnp.ndarray, depth: int, use_pallas: bool):
    """Grow T oblivious trees on sample weights w (T, CAP).

    Returns feat (T, depth), thresh (T, depth) and the weighted count and
    sum of ys in each leaf, (T, 2^depth) each.
    """
    t, cap = w.shape
    # go-right bit of every candidate, index f * Q + q: shared by all trees
    go = (xs[:, :, None] > cands[None]).reshape(cap, -1).astype(jnp.int32)
    stats = jnp.stack([w, w * ys, w * ys * ys], axis=-1).swapaxes(0, 1)
    leaf = jnp.zeros((cap, t), jnp.int32)
    feats, threshs = [], []

    for level in range(depth):
        h = _hist(leaf[:, :, None] * 2 + go[:, None, :], stats,
                  2 ** (level + 1), use_pallas)                   # (T,C,3,K)
        sw, swy, swy2 = h[:, :, 0], h[:, :, 1], h[:, :, 2]
        sse = jnp.sum(swy2 - swy * swy / jnp.maximum(sw, _EPS), axis=-1)
        best = jnp.argmin(sse, axis=1)  # ties go to the first candidate
        bf = best // cands.shape[1]
        feats.append(bf)
        threshs.append(cands[bf, best % cands.shape[1]])
        leaf = leaf * 2 + go[:, best]

    # the last level's sums under the chosen split are the leaf sums
    pick = jnp.take_along_axis(h, best[:, None, None, None], axis=1)[:, 0]
    return (jnp.stack(feats, axis=1), jnp.stack(threshs, axis=1),
            pick[:, 0], pick[:, 1])


def _leaf_means(sw: jnp.ndarray, swy: jnp.ndarray,
                fallback: jnp.ndarray) -> jnp.ndarray:
    return jnp.where(sw > _EPS, swy / jnp.maximum(sw, _EPS), fallback)


def fit(xs: jnp.ndarray, ys: jnp.ndarray, mask: jnp.ndarray, key,
        cfg: SizeyConfig, use_pallas: bool = False) -> ForestState:
    t, depth = cfg.forest_trees, cfg.forest_depth
    cands = _candidate_thresholds(xs, mask)
    cands = jnp.nan_to_num(cands, nan=0.0)
    n = jnp.maximum(jnp.sum(mask), 1.0)
    gmean = jnp.sum(ys * mask) / n
    # Poisson(1) bootstrap weights per tree (masked-out rows weigh 0)
    boot = jax.random.poisson(key, 1.0, (t, xs.shape[0])).astype(jnp.float32)
    boot = boot * mask[None, :]
    feat, thresh, sw, swy = _grow(boot, xs, ys, cands, depth, use_pallas)
    return ForestState(feat, thresh, _leaf_means(sw, swy, gmean), gmean)


def _leaf_index(feat: jnp.ndarray, thresh: jnp.ndarray,
                x: jnp.ndarray) -> jnp.ndarray:
    """Bit-pack the level comparisons into a leaf index. feat/thresh: (D,)."""
    bits = (x[feat] > thresh).astype(jnp.int32)  # (D,)
    weights = 2 ** jnp.arange(bits.shape[0] - 1, -1, -1)
    return jnp.sum(bits * weights)


def update(state: ForestState, xs: jnp.ndarray, ys: jnp.ndarray,
           mask: jnp.ndarray, new_idx: jnp.ndarray, key,
           cfg: SizeyConfig, use_pallas: bool = False) -> ForestState:
    """Structure-frozen leaf refresh from the full (unweighted) buffer."""
    depth = state.feat.shape[1]
    n = jnp.maximum(jnp.sum(mask), 1.0)
    gmean = jnp.sum(ys * mask) / n

    leaf = jax.vmap(lambda f, th: jax.vmap(
        lambda x: _leaf_index(f, th, x))(xs))(state.feat, state.thresh)
    stats = jnp.broadcast_to(jnp.stack([mask, mask * ys], axis=-1)[:, None],
                             (xs.shape[0], leaf.shape[0], 2))
    sums = _hist(leaf.T[:, :, None], stats, 2 ** depth, use_pallas)[:, 0]
    vals = _leaf_means(sums[:, 0], sums[:, 1], gmean)
    return ForestState(state.feat, state.thresh, vals, gmean)


def predict(state: ForestState, x: jnp.ndarray) -> jnp.ndarray:
    def one(feat, thresh, vals):
        return vals[_leaf_index(feat, thresh, x)]

    preds = jax.vmap(one)(state.feat, state.thresh, state.leaf_vals)
    return jnp.mean(preds)


def predict_batch(state: ForestState, xs: jnp.ndarray) -> jnp.ndarray:
    """Vectorized predict over a (K, d) feature block -> (K,)."""
    return jax.vmap(lambda x: predict(state, x))(xs)
