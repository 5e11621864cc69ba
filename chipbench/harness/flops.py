"""Operations and bytes of the fused programs, counted from their shapes.

Counts are of the arithmetic the algorithm needs at the buffer shapes the
program runs (rows padded to the pool's capacity): a multiply-add is two
operations, a compare, select, transcendental or division one. Bytes are
the least the program must move: every input read once and every output
written once, in float32. Both are lower bounds of what a chip must do, so
the least time they imply is a floor, and a share of it cannot pass 100%.
"""
from __future__ import annotations

import math

F32 = 4


def mlp_forward(rows: int, d: int, hidden: int) -> tuple[float, float]:
    """One MLP forward over ``rows`` inputs: tanh(x W1 + b1) W2 + b2."""
    flops = rows * (2 * d * hidden + hidden + hidden + 2 * hidden + 1)
    params = d * hidden + 2 * hidden + 1
    return float(flops), float(F32 * (rows * d + params + rows))


def _select_offset(n: int) -> float:
    """Candidate offsets (two std, two medians by sort) and the replay of
    16 candidates over ``n`` logged predictions."""
    return 2 * 6 * n + 2 * n * math.log2(max(n, 2)) + 16 * 8 * n


def full_fit_observe(cap: int, log_cap: int, d: int, m: dict
                     ) -> tuple[float, float]:
    """The full-fit observe of one pool: every model fit on ``cap`` rows,
    every model's in-sample prediction, accuracy, and both offset
    selections (over the ``log_cap`` log and the ``cap`` rows). ``m``: the
    configuration's method object."""
    c, h = cap, m["mlp_hidden"]
    n_models = len(m["model_classes"])
    lrs = 3 if m["hpo"] else 1
    p = d * h + 2 * h + 1
    step = (4 * c * d * h + 5 * c * h      # the dots, forward and back
            + 6 * c * h + 8 * c             # tanh, its derivative, loss
            + 12 * p)                       # Adam
    mlp = lrs * (m["mlp_train_steps"] * step + 2 * c * d * h + 4 * c * h
                 + 5 * c) + 10 * c
    knn = c * c * (4 * d + 2)               # distances, mask, selection
    t, depth, q = m["forest_trees"], m["forest_depth"], 16
    forest = (6 * c * t * depth * d * q + 3 * c * t + c * t
              + d * c * math.log2(c) + c * t * (2 * depth + 1))
    linear = 2 * c * (d + 1) ** 2 + 4 * c * (d + 1)
    mlp_in = mlp_forward(c, d, h)[0]
    gate = 5 * n_models * c + 2 * n_models * c
    flops = (mlp + knn + forest + linear + mlp_in + gate
             + _select_offset(log_cap) + _select_offset(c))
    inputs = c * (d + 3) + log_cap * (n_models + 4)
    outputs = c * (d + 2) + n_models * c + p + t * (2 * depth + 2 ** depth)
    return float(flops), float(F32 * (inputs + outputs))


def least_seconds(flops: float, nbytes: float, peak: dict
                  ) -> tuple[float, str]:
    """The larger of the two floors, and which bound it is."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
