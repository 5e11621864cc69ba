"""k-nearest-neighbours regression over the masked history buffer.

TPU adaptation: sklearn's KDTree is pointer-chasing; at workflow history
sizes (<= a few thousand rows) blocked brute-force distance + top-k on the
VPU/MXU wins. The hot loop (pairwise distances + k-select) is also provided
as a Pallas kernel (repro/kernels/knn) for batched prediction; this module
is the model-pool wrapper and stores normalization state.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.config import SizeyConfig

_EPS = 1e-9


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


def predict(state: KNNState, x: jnp.ndarray, *, k: int = 5) -> jnp.ndarray:
    d2 = jnp.sum(((state.xs - x[None, :]) / state.scale[None, :]) ** 2, -1)
    d2 = jnp.where(state.mask > 0, d2, jnp.inf)
    # top-k smallest distances; masked rows sit at +inf and get weight 0
    neg, nn_idx = jax.lax.top_k(-d2, min(k, d2.shape[0]))
    valid = jnp.isfinite(-neg)
    n = jnp.maximum(jnp.sum(valid), 1.0)
    return jnp.sum(jnp.where(valid, state.ys[nn_idx], 0.0)) / n
