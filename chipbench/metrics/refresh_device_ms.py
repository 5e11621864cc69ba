"""Fused programs: mean device time of the refresh program (in-sample
predictions and decision cache without a refit)."""
from chipbench.metrics._common import program_mean_ms

PROGRAM = "jit_refresh_fn"


def read(ctx):
    return program_mean_ms(ctx, PROGRAM)
