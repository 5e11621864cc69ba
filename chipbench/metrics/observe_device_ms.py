"""Fused programs: mean device time of the full-fit observe program."""
from chipbench.metrics._common import program_mean_ms

PROGRAM = "jit_observe_fn"


def read(ctx):
    return program_mean_ms(ctx, PROGRAM)
