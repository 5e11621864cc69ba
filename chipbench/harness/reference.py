"""Plain reference of a Sizey sizing decision, for the check of ``correct``.

Written from the paper (arXiv 2407.16353, section II) and the temporal
k-segment model (KS+, arXiv 2408.12290), independently of the program
under test: it imports nothing of ``repro`` and takes nothing the program
made. From one pool state (the completed records, the prequential log,
the usage profiles) it recomputes what Sizey decides for a task:

* four models fit on the history (ridge regression, k nearest neighbours,
  an MLP trained by full-batch Adam with a search over three learning
  rates, a forest of oblivious trees on Poisson bootstrap weights);
* each model's in-sample predictions give its accuracy score (Eq. 1);
* the offset is the candidate with the least retrospective wastage over
  the prequential log (section II-E), over the in-sample errors while the
  log is young;
* the ensemble prediction is the softmax(beta x RAQ) mix (Eq. 2-4), plus
  the offset, clipped to [min_alloc, machine cap];
* with k > 1 segments the pool's segment ends are the change-point DP
  over its usage profiles, and each segment is one decision.

Random draws (the MLP's initial weights, the forest's bootstrap weights)
come from ``jax.random`` with the seed the configuration fixes per pool
and history length, and the buffers are padded to the same power-of-two
capacities, since the draws depend on the key and on the shape. Every dot
product takes the precision ``prec``: ``"highest"`` is the configuration's
float32; ``"bf16x3"`` is the control, the next precision below: each
float32 operand split into a bfloat16 head and tail and the product taken
as three bfloat16 passes (head x head + head x tail + tail x head), as a
TPU's ``Precision.HIGH`` does, on any backend and in the gradients too.
"""
from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

HPO_LRS = (0.03, 0.01, 0.003)
OFFSET_MULTIPLIERS = (1.0, 1.5, 2.0, 3.0)
N_QUANTILES = 16
ADAM = (0.9, 0.999, 1e-8)


def capacity(n: int, initial: int = 128) -> int:
    """Power-of-two buffer capacity (from ``initial``) that holds n rows."""
    cap = initial
    while cap < n:
        cap *= 2
    return cap


def fit_seed(key: tuple, n_fit: int, min_history: int, cfg_seed: int) -> int:
    """The seed of the fit over the first ``n_fit`` rows of a pool: one
    more per record since the pool reached ``min_history``."""
    h = int.from_bytes(hashlib.sha256(str(key).encode()).digest()[:8],
                       "big") >> 1
    return (h + n_fit - min_history + cfg_seed) % 2**31


PRECISIONS = ("highest", "bf16x3")


# -------------------------------------------------------------- models
def _bf16(a):
    # rounded to bfloat16 and kept in float32: ``reduce_precision``, since a
    # compiler that allows excess precision may drop a round trip of casts
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    hi = _bf16(a)
    return hi, _bf16(a - hi)


def _dot3(a, b):
    exact = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    (ah, al), (bh, bl) = _split(a), _split(b)
    return exact(ah, bh) + (exact(ah, bl) + exact(al, bh))


@jax.custom_vjp
def _dot3_2d(a, b):
    return _dot3(a, b)


def _dot3_fwd(a, b):
    return _dot3(a, b), (a, b)


def _dot3_bwd(res, g):
    a, b = res
    return _dot3(g, b.T), _dot3(a.T, g)


_dot3_2d.defvjp(_dot3_fwd, _dot3_bwd)


def _dot(a, b, prec):
    if prec == "highest":
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)
    if prec != "bf16x3":
        raise ValueError(f"unknown precision {prec!r}")
    return _dot3_2d(a, b) if a.ndim == b.ndim == 2 else _dot3(a, b)


def _with_bias(xs):
    return jnp.concatenate([xs, jnp.ones((xs.shape[0], 1), xs.dtype)], 1)


def _moments(v, mask):
    n = jnp.maximum(jnp.sum(mask), 1.0)
    mean = jnp.sum(v * mask, 0) / n
    return mean, jnp.sqrt(jnp.sum((v - mean) ** 2 * mask, 0) / n)


def ridge_fit(xs, ys, mask, lam, prec):
    a = _with_bias(xs) * mask[:, None]
    lhs = _dot(a.T, a, prec) + lam * jnp.eye(a.shape[1], dtype=a.dtype)
    return jnp.linalg.solve(lhs, _dot(a.T, ys * mask, prec))


def ridge_predict(w, xq, prec):
    return _dot(_with_bias(xq), w, prec)


def knn_predict(xs, ys, mask, xq, k):
    _, sd = _moments(xs, mask[:, None])
    scale = sd + 1e-9

    def one(x):
        d2 = jnp.sum(((xs - x) / scale) ** 2, -1)
        d2 = jnp.where(mask > 0, d2, jnp.inf)
        order = jnp.argsort(d2, stable=True)[:k]
        ok = jnp.isfinite(d2[order])
        return (jnp.sum(jnp.where(ok, ys[order], 0.0))
                / jnp.maximum(jnp.sum(ok), 1.0))
    return jax.vmap(one)(xq)


def _mlp_apply(p, x, prec):
    w1, b1, w2, b2 = p
    return (_dot(jnp.tanh(_dot(x, w1, prec) + b1), w2, prec) + b2)[:, 0]


def mlp_fit(xs, ys, mask, key, hidden, steps, prec):
    """Normalise, draw the initial weights from ``key``, run ``steps`` of
    full-batch Adam per learning rate; keep the lowest final loss."""
    d = xs.shape[1]
    mu_x, sd_x = _moments(xs, mask[:, None])
    mu_y, sd_y = _moments(ys, mask)
    sd_x, sd_y = sd_x + 1e-6, sd_y + 1e-6
    xn, yn = (xs - mu_x) / sd_x, (ys - mu_y) / sd_y
    n = jnp.maximum(jnp.sum(mask), 1.0)
    k1, k2 = jax.random.split(key)
    p0 = (jax.random.normal(k1, (d, hidden)) * (1.0 / jnp.sqrt(d)),
          jnp.zeros((hidden,)),
          jax.random.normal(k2, (hidden, 1)) * (1.0 / jnp.sqrt(hidden)),
          jnp.zeros((1,)))

    def loss(p):
        return jnp.sum((_mlp_apply(p, xn, prec) - yn) ** 2 * mask) / n

    b1, b2, eps = ADAM

    def train(lr):
        def step(carry, _):
            p, m, v, t = carry
            g = jax.grad(loss)(p)
            t = t + 1.0
            m = [b1 * mi + (1 - b1) * gi for mi, gi in zip(m, g)]
            v = [b2 * vi + (1 - b2) * gi * gi for vi, gi in zip(v, g)]
            p = tuple(pi - lr * (mi / (1 - b1 ** t))
                      / (jnp.sqrt(vi / (1 - b2 ** t)) + eps)
                      for pi, mi, vi in zip(p, m, v))
            return (p, m, v, t), None
        zeros = [jnp.zeros_like(q) for q in p0]
        (p, _, _, _), _ = jax.lax.scan(
            step, (p0, zeros, zeros, jnp.zeros((), jnp.float32)), None,
            length=steps)
        return p, loss(p)

    fits = [train(jnp.float32(lr)) for lr in HPO_LRS]
    best = jnp.argmin(jnp.stack([f[1] for f in fits]))
    params = tuple(jnp.stack([f[0][i] for f in fits])[best]
                   for i in range(4))
    return params, (mu_x, sd_x, mu_y, sd_y)


def mlp_predict(params, norm, xq, prec):
    mu_x, sd_x, mu_y, sd_y = norm
    return _mlp_apply(params, (xq - mu_x) / sd_x, prec) * sd_y + mu_y


def forest_fit(xs, ys, mask, key, trees, depth):
    """Oblivious trees: one (feature, threshold) per level, thresholds from
    16 masked quantiles per feature, least weighted SSE per level."""
    cap, d = xs.shape
    qs = jnp.linspace(0.05, 0.95, N_QUANTILES)
    cands = jnp.nan_to_num(jnp.nanquantile(
        jnp.where(mask[:, None] > 0, xs, jnp.nan), qs, axis=0).T, nan=0.0)
    n = jnp.maximum(jnp.sum(mask), 1.0)
    gmean = jnp.sum(ys * mask) / n
    boot = jax.random.poisson(key, 1.0, (trees, cap)).astype(jnp.float32)
    boot = boot * mask[None, :]

    def sums(seg, w, n_seg):
        return (jnp.zeros(n_seg).at[seg].add(w),
                jnp.zeros(n_seg).at[seg].add(w * ys),
                jnp.zeros(n_seg).at[seg].add(w * ys * ys))

    def tree(w):
        leaf = jnp.zeros((cap,), jnp.int32)
        feats, threshs = [], []
        for level in range(depth):
            n_seg = 2 ** (level + 1)
            sse = []
            for f in range(d):
                for q in range(N_QUANTILES):
                    seg = leaf * 2 + (xs[:, f] > cands[f, q]).astype(
                        jnp.int32)
                    sw, swy, swy2 = sums(seg, w, n_seg)
                    sse.append(jnp.sum(swy2 - swy * swy
                                       / jnp.maximum(sw, 1e-9)))
            best = jnp.argmin(jnp.stack(sse))
            f, q = best // N_QUANTILES, best % N_QUANTILES
            t = cands[f, q]
            feats.append(f)
            threshs.append(t)
            leaf = leaf * 2 + (xs[:, f] > t).astype(jnp.int32)
        sw, swy, _ = sums(leaf, w, 2 ** depth)
        vals = jnp.where(sw > 1e-9, swy / jnp.maximum(sw, 1e-9), gmean)
        return jnp.stack(feats), jnp.stack(threshs), vals

    return jax.vmap(tree)(boot)


def forest_predict(forest, xq):
    feat, thresh, vals = forest
    weights = 2 ** jnp.arange(feat.shape[1] - 1, -1, -1)

    def one(x):
        bits = (x[feat] > thresh).astype(jnp.int32)            # (T, D)
        idx = jnp.sum(bits * weights, 1)
        return jnp.mean(jnp.take_along_axis(vals, idx[:, None], 1))
    return jax.vmap(one)(xq)


# ------------------------------------------------------------- decision
def _masked_median(x, mask):
    n = jnp.sum(mask).astype(jnp.int32)
    s = jnp.sort(jnp.where(mask > 0, x, jnp.inf))
    lo, hi = jnp.maximum((n - 1) // 2, 0), jnp.maximum(n // 2, 0)
    return jnp.where(n > 0, 0.5 * (s[lo] + s[hi]), 0.0)


def least_waste_offset(pred, actual, runtime, mask, ttf):
    """Section II-E: four candidate offsets (std and median of all and of
    the under-predicted errors) times four multipliers; the one that would
    have wasted least on the history. Returns (offset, candidate index)."""
    err = actual - pred
    under = mask * (err > 0)
    cands = jnp.maximum(jnp.stack([
        _moments(err, mask)[1], _moments(err, under)[1],
        _masked_median(jnp.abs(err), mask),
        _masked_median(err, under)]), 0.0)
    grid = (cands[:, None] * jnp.asarray(OFFSET_MULTIPLIERS)).reshape(-1)
    max_seen = jnp.max(jnp.where(mask > 0, actual, 0.0))

    def waste(o):
        alloc = pred + o
        w = jnp.where(alloc >= actual, (alloc - actual) * runtime,
                      alloc * (ttf * runtime)
                      + jnp.maximum(max_seen - actual, 0.0) * runtime)
        return jnp.sum(w * mask)
    i = jnp.argmin(jax.vmap(waste)(grid))
    return grid[i], i // len(OFFSET_MULTIPLIERS)


@functools.partial(jax.jit, static_argnames=("cfg", "prec"))
def pool_state(xs, ys, rts, mask, fit_mask, log_preds, log_agg, log_actual,
               log_rt, log_mask, seed, *, cfg, prec):
    """Fit every model on the rows of ``fit_mask`` and compute the pool's
    decision inputs over the rows of ``mask``: returns (models, acc,
    offset, offset_idx). ``cfg``: a tuple of (name, value) pairs."""
    c = dict(cfg)
    key = jax.random.PRNGKey(seed)
    w = ridge_fit(xs, ys, fit_mask, c["ridge_lambda"], prec)
    mlp = mlp_fit(xs, ys, fit_mask, key, c["mlp_hidden"],
                  c["mlp_train_steps"], prec)
    forest = forest_fit(xs, ys, fit_mask, key, c["forest_trees"],
                        c["forest_depth"])
    knn = (xs, ys, fit_mask)
    models = (w, knn, mlp, forest)
    insample = predict_models(models, xs, cfg=cfg, prec=prec)   # (N, C)
    rel = jnp.minimum(jnp.abs(insample - ys) / jnp.maximum(ys, 1e-9), 1.0)
    acc = 1.0 - jnp.sum(rel * mask, 1) / jnp.maximum(jnp.sum(mask), 1.0)
    off_log, idx_log = least_waste_offset(log_agg, log_actual, log_rt,
                                          log_mask, c["ttf"])
    ins_agg = _dot(jax.nn.softmax(c["beta"] * acc), insample, prec)
    off_ins, idx_ins = least_waste_offset(ins_agg, ys, rts, mask, c["ttf"])
    young = jnp.sum(log_mask) < 5
    offset = jnp.where(young, jnp.maximum(off_ins, off_log), off_log)
    return models, acc, offset, jnp.where(young, idx_ins, idx_log)


def predict_models(models, xq, *, cfg, prec):
    c = dict(cfg)
    w, knn, mlp, forest = models
    cols = {"linear": ridge_predict(w, xq, prec),
            "knn": knn_predict(*knn, xq, c["knn_k"]),
            "mlp": mlp_predict(*mlp, xq, prec),
            "forest": forest_predict(forest, xq)}
    return jnp.stack([cols[m] for m in c["model_classes"]])


@functools.partial(jax.jit, static_argnames=("cfg", "prec"))
def decide(models, acc, offset, xq, caps, *, cfg, prec):
    """Decisions for query rows ``xq``: (K, 5 + 3N) rows of [allocation,
    ensemble prediction, offset, 0, 0, predictions, RAQ, weights]."""
    c = dict(cfg)
    preds = predict_models(models, xq, cfg=cfg, prec=prec).T      # (K, N)
    pos = jnp.maximum(preds, 0.0)
    eff = 1.0 - pos / jnp.maximum(jnp.max(pos, 1, keepdims=True), 1e-9)
    raq = (1.0 - c["alpha"]) * acc + c["alpha"] * eff
    weights = jax.nn.softmax(c["beta"] * raq, axis=1)
    agg = jnp.sum(preds * weights, 1)
    alloc = jnp.clip(agg + offset, c["min_alloc_gb"], caps)
    zeros = jnp.zeros_like(agg)
    head = jnp.stack([alloc, agg, jnp.broadcast_to(offset, agg.shape),
                      zeros, zeros], 1)
    return jnp.concatenate([head, preds, raq, weights], 1)


# ------------------------------------------------------------ segments
def segment_cost(P: np.ndarray) -> np.ndarray:
    """Over-reservation of covering grid columns [i, j) with one segment
    at its max, summed over the profiles (float32, sequential sums)."""
    m, g = P.shape
    cost = np.full((g + 1, g + 1), np.inf, np.float32)
    widths = np.arange(1, g + 1, dtype=np.float32)
    for i in range(g):
        tail = P[:, i:]
        top = np.maximum.accumulate(tail, axis=1)
        run = np.cumsum(tail, axis=1, dtype=np.float32)
        per = top * widths[None, :g - i] - run
        total = np.zeros(g - i, np.float32)
        for row in per:
            total += row
        cost[i, i + 1:] = total
    return cost


def fit_cuts(P: np.ndarray, k: int) -> list[int]:
    """End columns of the k segments of least total over-reservation."""
    P = np.asarray(P, np.float32)
    g = P.shape[1]
    k = max(1, min(k, g))
    cost = segment_cost(P)
    best = np.full(g + 1, np.inf, np.float32)
    best[0] = 0.0
    back = []
    for _ in range(k):
        cand = best[:, None] + cost
        arg = np.argmin(cand, axis=0)
        back.append(arg)
        best = cand[arg, np.arange(g + 1)]
    cuts, j = [], g
    for arg in reversed(back):
        cuts.append(j)
        j = int(arg[j])
    return cuts[::-1]


def segment_ends(profiles: list, k: int, grid: int,
                 prec: str = "highest") -> tuple:
    """Segment end fractions of a pool: uniform until three profiles are
    known, then the DP over them; coincident cuts are dropped. The control
    (``prec="bf16x3"``) rounds the profiles to bfloat16 first."""
    if len(profiles) < 3:
        return tuple((i + 1) / k for i in range(k))
    P = np.asarray(profiles, np.float32)
    if prec != "highest":
        P = np.asarray(jnp.asarray(P).astype(jnp.bfloat16), np.float32)
    out: list[float] = []
    for c in fit_cuts(P, k):
        f = float(c) / grid
        if not out or f > out[-1] + 1e-9:
            out.append(f)
    return tuple(out)
