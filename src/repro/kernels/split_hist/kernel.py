"""Row-order split histograms for the forest fit (core/models/forest.py).

Every lane l of row i adds ``val[s, i, l]`` to bin ``(s, seg[i, l], l)``,
one row after another, into an accumulator block that stays in VMEM while
the grid walks the row blocks in order. The sums are those of a
scatter-add in row order, bit for bit, where the TPU would serialize the
scatter's updates; here a row is a few vector ops over all lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def _body(seg_ref, val_ref, o_ref, *, unroll: int):
    @pl.when(pl.program_id(0) == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    n_stat, n_seg, lanes = o_ref.shape
    segs = lax.broadcasted_iota(jnp.int32, (n_seg, lanes), 0)

    def rows(i, acc):
        for j in range(unroll):  # rows i * unroll + j, in order
            r = pl.ds(i * unroll + j, 1)
            hit = seg_ref[r, :] == segs                         # (K, L)
            acc = tuple(a + jnp.where(hit, val_ref[s, r, :], 0.0)
                        for s, a in enumerate(acc))
        return acc

    acc = lax.fori_loop(0, seg_ref.shape[0] // unroll, rows,
                        tuple(o_ref[s] for s in range(n_stat)))
    for s in range(n_stat):
        o_ref[s] = acc[s]


def split_hist_blocked(seg, val, n_seg: int, *, block_rows: int,
                       unroll: int = 8, interpret: bool = False):
    """seg: (R, L) int32; val: (S, R, L) f32; R a multiple of block_rows,
    and block_rows of unroll.

    Returns (S, n_seg, L) f32 row-order sums (ops.py pads)."""
    rows, lanes = seg.shape
    n_stat = val.shape[0]
    return pl.pallas_call(
        functools.partial(_body, unroll=unroll),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
            pl.BlockSpec((n_stat, block_rows, lanes), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((n_stat, n_seg, lanes), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_stat, n_seg, lanes), jnp.float32),
        interpret=interpret,
    )(seg, val)

