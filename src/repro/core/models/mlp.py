"""MLP regression trained with full-batch Adam in pure jnp.

Models complex non-linear memory ~ input relationships (paper Fig. 5,
"e.g. memory that grows as the square of the input"). Full retrain re-inits
and runs ``mlp_train_steps`` Adam steps via lax.scan; the optional HPO vmaps
the whole training over a small learning-rate grid and keeps the best
(paper §III-A "caches the best hyperparameters" — we carry the winning lr in
the state). The incremental update runs ``mlp_incremental_steps`` Adam steps
from the current weights with refreshed normalization statistics — this is
the 98%-cheaper online step of paper §III-D/Fig. 9.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.config import SizeyConfig

_EPS = 1e-6
HPO_LRS = (0.03, 0.01, 0.003)
# f32 dots at full precision: the TPU's default is one bf16 pass
_HIGHEST = jax.lax.Precision.HIGHEST


class MLPState(NamedTuple):
    w1: jnp.ndarray   # (d, h)
    b1: jnp.ndarray   # (h,)
    w2: jnp.ndarray   # (h, 1)
    b2: jnp.ndarray   # (1,)
    m: tuple          # Adam first moments (same tree as params)
    v: tuple          # Adam second moments
    step: jnp.ndarray
    mu_x: jnp.ndarray
    sd_x: jnp.ndarray
    mu_y: jnp.ndarray
    sd_y: jnp.ndarray
    lr: jnp.ndarray   # winning learning rate from HPO


# state fields predict() never reads — dropped (set to None) from the
# hot-path dispatch pytree by the fused predictor
PREDICT_DROP = ("m", "v", "step", "lr")


def _params(state: MLPState):
    return (state.w1, state.b1, state.w2, state.b2)


def _forward(params, x):
    w1, b1, w2, b2 = params
    h = jnp.tanh(jnp.dot(x, w1, precision=_HIGHEST) + b1)
    return (jnp.dot(h, w2, precision=_HIGHEST) + b2)[..., 0]


def _norm_stats(xs, ys, mask):
    n = jnp.maximum(jnp.sum(mask), 1.0)
    mu_x = jnp.sum(xs * mask[:, None], 0) / n
    sd_x = jnp.sqrt(jnp.sum(((xs - mu_x) ** 2) * mask[:, None], 0) / n) + _EPS
    mu_y = jnp.sum(ys * mask) / n
    sd_y = jnp.sqrt(jnp.sum(((ys - mu_y) ** 2) * mask) / n) + _EPS
    return mu_x, sd_x, mu_y, sd_y


def _loss(params, xn, yn, mask):
    pred = _forward(params, xn)
    n = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(((pred - yn) ** 2) * mask) / n


def _adam_steps(params, m, v, step0, xn, yn, mask, lr, n_steps):
    """n_steps of full-batch Adam via lax.scan (jit-friendly, unrolled=1)."""
    b1, b2, eps = 0.9, 0.999, 1e-8

    def body(carry, _):
        params, m, v, t = carry
        g = jax.grad(_loss)(params, xn, yn, mask)
        t = t + 1
        m = jax.tree.map(lambda mi, gi: b1 * mi + (1 - b1) * gi, m, g)
        v = jax.tree.map(lambda vi, gi: b2 * vi + (1 - b2) * gi * gi, v, g)
        mhat = jax.tree.map(lambda mi: mi / (1 - b1 ** t), m)
        vhat = jax.tree.map(lambda vi: vi / (1 - b2 ** t), v)
        params = jax.tree.map(
            lambda p, mh, vh: p - lr * mh / (jnp.sqrt(vh) + eps),
            params, mhat, vhat)
        return (params, m, v, t), None

    with jax.named_scope("adam_scan"):
        (params, m, v, t), _ = jax.lax.scan(
            body, (params, m, v, step0), None, length=n_steps)
    return params, m, v, t


def _init_params(key, d, h):
    k1, k2 = jax.random.split(key)
    s1 = 1.0 / jnp.sqrt(d)
    s2 = 1.0 / jnp.sqrt(h)
    return (jax.random.normal(k1, (d, h)) * s1, jnp.zeros((h,)),
            jax.random.normal(k2, (h, 1)) * s2, jnp.zeros((1,)))


def init(d: int, cfg: SizeyConfig) -> MLPState:
    params = _init_params(jax.random.PRNGKey(cfg.seed), d, cfg.mlp_hidden)
    zeros = jax.tree.map(jnp.zeros_like, params)
    return MLPState(*params, zeros, zeros, jnp.zeros((), jnp.float32),
                    jnp.zeros((d,)), jnp.ones((d,)), jnp.zeros(()),
                    jnp.ones(()), jnp.asarray(0.01))


def fit(xs: jnp.ndarray, ys: jnp.ndarray, mask: jnp.ndarray, key,
        cfg: SizeyConfig) -> MLPState:
    d = xs.shape[-1]
    mu_x, sd_x, mu_y, sd_y = _norm_stats(xs, ys, mask)
    xn = (xs - mu_x) / sd_x
    yn = (ys - mu_y) / sd_y
    params0 = _init_params(key, d, cfg.mlp_hidden)
    zeros = jax.tree.map(jnp.zeros_like, params0)

    def train_with_lr(lr):
        p, m, v, t = _adam_steps(params0, zeros, zeros,
                                 jnp.zeros((), jnp.float32), xn, yn, mask,
                                 lr, cfg.mlp_train_steps)
        return p, m, v, t, _loss(p, xn, yn, mask)

    lrs = jnp.asarray(HPO_LRS if cfg.hpo else (0.01,))
    p, m, v, t, losses = jax.vmap(train_with_lr)(lrs)
    best = jnp.argmin(losses)
    take = lambda tree: jax.tree.map(lambda a: a[best], tree)
    return MLPState(*take(p), take(m), take(v), t[best],
                    mu_x, sd_x, mu_y, sd_y, lrs[best])


def update(state: MLPState, xs: jnp.ndarray, ys: jnp.ndarray,
           mask: jnp.ndarray, new_idx: jnp.ndarray, key,
           cfg: SizeyConfig) -> MLPState:
    mu_x, sd_x, mu_y, sd_y = _norm_stats(xs, ys, mask)
    xn = (xs - mu_x) / sd_x
    yn = (ys - mu_y) / sd_y
    p, m, v, t = _adam_steps(_params(state), state.m, state.v, state.step,
                             xn, yn, mask, state.lr,
                             cfg.mlp_incremental_steps)
    return MLPState(*p, m, v, t, mu_x, sd_x, mu_y, sd_y, state.lr)


def predict(state: MLPState, x: jnp.ndarray) -> jnp.ndarray:
    xn = (x - state.mu_x) / state.sd_x
    yn = _forward(_params(state), xn[None, :])[0]
    return yn * state.sd_y + state.mu_y


def predict_batch(state: MLPState, xs: jnp.ndarray, *,
                  use_pallas: bool = False) -> jnp.ndarray:
    """Vectorized predict over a (K, d) feature block -> (K,).

    ``use_pallas`` routes the forward through the fused ensemble-MLP Pallas
    kernel (repro/kernels/ensemble_mlp) — the compiled path on TPU/GPU. The
    plain-jnp path computes the identical fp32 math and is the right choice
    on CPU, where Pallas only runs in (slow) interpret mode.
    """
    xn = (xs - state.mu_x) / state.sd_x
    if use_pallas:
        from repro.kernels.ensemble_mlp.ops import ensemble_mlp_forward
        yn = ensemble_mlp_forward(xn[None], state.w1[None], state.b1[None],
                                  state.w2[None], state.b2[None])[0]
    else:
        yn = _forward(_params(state), xn)
    return yn * state.sd_y + state.mu_y
