"""Jitted segment-boundary fit: cost build + DP in one device program.

Mirrors ``ref.py`` operation-for-operation (see its docstring for the
shared numerics recipe): float32, exact running max, sequential left
folds (``lax.scan``) for the profile sum and the column cumsum, and
first-index argmin in the DP — so the returned cut indices are bitwise
those of the numpy reference, whatever the data.

The profile axis is padded to power-of-two buckets (``profile_bucket``)
so a pool compiles O(log window) programs as its history grows; zero rows
cost exactly 0 everywhere, so the padding does not perturb the fold. On
TPU/GPU the O(M·G²) cost build can be routed through the Pallas kernel
(``use_pallas=True``); the jnp path is the identical-numerics CPU
fallback, same pattern as ``repro.kernels.ensemble_mlp``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["fit_cuts", "profile_bucket", "cost_matrix_jnp"]


def profile_bucket(m: int) -> int:
    """Round a profile count up to the next power of two (compile bound)."""
    b = 1
    while b < m:
        b *= 2
    return b


def cost_matrix_jnp(P: jnp.ndarray) -> jnp.ndarray:
    """(M, G) float32 -> (G+1, G+1) cost with ``inf`` where ``j <= i``.

    Vectorized over start columns: for each i, profiles are masked below
    i, a running max builds the segment allocation and a sequential
    column scan the running sum, the per-(m, column) over-reservation
    ``rmax·width - csum`` is formed elementwise (exactly 0.0 on the zero
    rows of bucket padding), and profiles are folded sequentially — every
    scalar op in the same order as the numpy reference.
    """
    m, g = P.shape
    idx = jnp.arange(g)
    started = idx[:, None, None] <= idx[None, None, :]    # (G_i, 1, G)
    masked = jnp.where(started, P[None, :, :], -jnp.inf)  # (G_i, M, G)
    rmax = jnp.where(started, jax.lax.cummax(masked, axis=2), 0.0)

    def fold_g(acc, col):          # col: (G_i, M) — one grid column
        acc = acc + col            # pre-start entries add exactly 0.0
        return acc, acc
    _, csums = jax.lax.scan(
        fold_g, jnp.zeros((g, m), jnp.float32),
        jnp.moveaxis(jnp.where(started, P[None, :, :], 0.0), 2, 0))
    csum = jnp.moveaxis(csums, 0, 2)                      # (G_i, M, G)

    widths = (idx[None, None, :] - idx[:, None, None] + 1
              ).astype(jnp.float32)                       # exact small ints
    val = jnp.where(started, rmax * widths - csum, 0.0)

    def fold_m(acc, row):          # row: (G_i, G) — one profile, all starts
        return acc + row, None
    colsum, _ = jax.lax.scan(fold_m, jnp.zeros((g, g), jnp.float32),
                             jnp.moveaxis(val, 1, 0))

    cost = jnp.full((g + 1, g + 1), jnp.inf, jnp.float32)
    valid = idx[None, :] >= idx[:, None]                  # j-1 >= i
    return cost.at[:g, 1:].set(jnp.where(valid, colsum, jnp.inf))


@functools.partial(jax.jit, static_argnames=("k", "use_pallas", "interpret"))
def _fit_cuts_jit(P, *, k: int, use_pallas: bool = False,
                  interpret: bool = False):
    g = P.shape[1]
    with jax.named_scope("segment_cost"):
        if use_pallas:
            from repro.kernels.segment_dp.kernel import segment_cost_blocked
            cost = segment_cost_blocked(P, interpret=interpret)
        else:
            cost = cost_matrix_jnp(P)

    with jax.named_scope("segment_dp"):
        dp0 = jnp.full(g + 1, jnp.inf, jnp.float32).at[0].set(0.0)

        def dp_step(dp_prev, _):
            cand = dp_prev[:, None] + cost                # (g+1, g+1)
            bk = jnp.argmin(cand, axis=0)                 # first index
            return cand[bk, jnp.arange(g + 1)], bk
        _, back = jax.lax.scan(dp_step, dp0, None, length=k)  # (k, g+1)

        def walk(j, s):                                   # s = k-1 .. 0
            return back[s, j], j
        _, cuts = jax.lax.scan(walk, jnp.asarray(g, back.dtype),
                               jnp.arange(k - 1, -1, -1))
    return cuts[::-1]                                     # ends, last == g


def fit_cuts(profiles: np.ndarray, k: int, *, use_pallas: bool = False,
             interpret: bool = False) -> np.ndarray:
    """Fit ``k`` cut columns over (M, G) profiles on device; returns the
    (k,) end-column indices (host numpy, last == G). ``k`` must already
    be clamped to [1, G]. Pads M to a power-of-two bucket."""
    P = np.asarray(profiles, np.float32)
    m, g = P.shape
    mp = profile_bucket(m)
    if mp != m:
        P = np.concatenate([P, np.zeros((mp - m, g), np.float32)])
    cuts = _fit_cuts_jit(jnp.asarray(P), k=int(k), use_pallas=use_pallas,
                         interpret=interpret)
    return np.asarray(cuts)
