"""Public split-histogram wrapper: padding over the row dim."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.split_hist.kernel import split_hist_blocked
from repro.utils.misc import round_up


@functools.partial(jax.jit, static_argnames=("n_seg", "block_rows",
                                             "interpret"))
def split_hist(seg, val, *, n_seg: int, block_rows: int = 512,
               interpret: bool = False):
    """seg: (R, L) int32 segment of each row in each lane; val: (S, R, L).

    Returns (S, n_seg, L): per lane, the sums of val over the rows of each
    segment, added in row order."""
    rows = seg.shape[0]
    br = min(block_rows, round_up(rows, 8))
    pad = round_up(rows, br) - rows  # padded rows hit no segment
    seg = jnp.pad(seg, ((0, pad), (0, 0)), constant_values=-1)
    val = jnp.pad(val, ((0, 0), (0, pad), (0, 0)))
    return split_hist_blocked(seg, val, n_seg, block_rows=br,
                              interpret=interpret)
