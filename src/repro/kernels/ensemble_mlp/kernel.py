"""Fused (models x tasks) MLP forward for Sizey's predictor pool.

The paper trains/evaluates N sklearn models in a Python loop; DESIGN.md §3
lays the whole pool out as ONE batched program: every (model, task-block)
tile computes tanh(x W1 + b1) W2 + b2 in VMEM with no per-model Python
dispatch. Grid: (models, task_blocks).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# f32 dots at full precision: the TPU's default is one bf16 pass
_HIGHEST = jax.lax.Precision.HIGHEST


def _mlp_body(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref):
    x = x_ref[0].astype(jnp.float32)          # (bt, d)
    w1 = w1_ref[0].astype(jnp.float32)        # (d, h)
    b1 = b1_ref[0].astype(jnp.float32)        # (h,)
    w2 = w2_ref[0].astype(jnp.float32)        # (h, 1)
    b2 = b2_ref[0].astype(jnp.float32)        # (1,)
    hid = jnp.tanh(jax.lax.dot_general(
        x, w1, (((1,), (0,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32) + b1[None, :])
    out = jax.lax.dot_general(hid, w2, (((1,), (0,)), ((), ())),
                              precision=_HIGHEST,
                              preferred_element_type=jnp.float32)
    o_ref[0] = (out[:, 0] + b2[0]).astype(o_ref.dtype)


def ensemble_mlp_blocked(x, w1, b1, w2, b2, *, bt: int = 128,
                         interpret: bool = False):
    """x: (M, T, d); w1: (M, d, h); b1: (M, h); w2: (M, h, 1); b2: (M, 1).

    Returns (M, T) fp32 predictions. T must divide bt (ops.py pads)."""
    m, t, d = x.shape
    h = w1.shape[-1]
    return pl.pallas_call(
        _mlp_body,
        grid=(m, t // bt),
        in_specs=[
            pl.BlockSpec((1, bt, d), lambda im, it: (im, it, 0)),
            pl.BlockSpec((1, d, h), lambda im, it: (im, 0, 0)),
            pl.BlockSpec((1, h), lambda im, it: (im, 0)),
            pl.BlockSpec((1, h, 1), lambda im, it: (im, 0, 0)),
            pl.BlockSpec((1, 1), lambda im, it: (im, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt), lambda im, it: (im, it)),
        out_shape=jax.ShapeDtypeStruct((m, t), jnp.float32),
        interpret=interpret,
    )(x, w1, b1, w2, b2)
