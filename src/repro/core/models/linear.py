"""Ridge linear regression via incremental sufficient statistics.

TPU adaptation of the paper's sklearn LinearRegression: we maintain
X'X / X'y in GB units and solve the (d+1)x(d+1) normal equations with a
jitted Cholesky. The online update is a rank-1 accumulation + re-solve —
O(d^2) per completed task, the "lightweight update step" of paper §II-A c.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.config import SizeyConfig

# f32 dots at full precision: the TPU's default is one bf16 pass
_HIGHEST = jax.lax.Precision.HIGHEST


class LinearState(NamedTuple):
    xtx: jnp.ndarray  # (d+1, d+1) sufficient statistic
    xty: jnp.ndarray  # (d+1,)
    w: jnp.ndarray    # (d+1,) solved ridge weights (bias last)


# state fields predict() never reads — dropped (set to None) from the
# hot-path dispatch pytree by the fused predictor
PREDICT_DROP = ("xtx", "xty")


def _aug(xs: jnp.ndarray) -> jnp.ndarray:
    """Append the bias column."""
    return jnp.concatenate([xs, jnp.ones((*xs.shape[:-1], 1), xs.dtype)], -1)


def _solve(xtx: jnp.ndarray, xty: jnp.ndarray, lam: float) -> jnp.ndarray:
    d = xtx.shape[0]
    a = xtx + lam * jnp.eye(d, dtype=xtx.dtype)
    # Cholesky solve; ridge guarantees positive definiteness.
    l = jnp.linalg.cholesky(a)
    z = jnp.linalg.solve(l, xty[:, None])
    return jnp.linalg.solve(l.T, z)[:, 0]


def init(d: int, cfg: SizeyConfig) -> LinearState:
    return LinearState(
        xtx=jnp.zeros((d + 1, d + 1), jnp.float32),
        xty=jnp.zeros((d + 1,), jnp.float32),
        w=jnp.zeros((d + 1,), jnp.float32),
    )


def fit(xs: jnp.ndarray, ys: jnp.ndarray, mask: jnp.ndarray, key,
        cfg: SizeyConfig) -> LinearState:
    xa = _aug(xs) * mask[:, None]
    xtx = jnp.dot(xa.T, xa, precision=_HIGHEST)
    xty = jnp.dot(xa.T, ys * mask, precision=_HIGHEST)
    return LinearState(xtx, xty, _solve(xtx, xty, cfg.ridge_lambda))


def update(state: LinearState, xs: jnp.ndarray, ys: jnp.ndarray,
           mask: jnp.ndarray, new_idx: jnp.ndarray, key,
           cfg: SizeyConfig) -> LinearState:
    """Rank-1 update with the newest sample (buffer slot ``new_idx``)."""
    x = _aug(xs[new_idx][None, :])[0]
    xtx = state.xtx + jnp.outer(x, x)
    xty = state.xty + x * ys[new_idx]
    return LinearState(xtx, xty, _solve(xtx, xty, cfg.ridge_lambda))


def predict(state: LinearState, x: jnp.ndarray) -> jnp.ndarray:
    return jnp.dot(_aug(x[None, :])[0], state.w, precision=_HIGHEST)


def predict_batch(state: LinearState, xs: jnp.ndarray) -> jnp.ndarray:
    """Vectorized predict over a (K, d) feature block -> (K,)."""
    return jnp.dot(_aug(xs), state.w, precision=_HIGHEST)
