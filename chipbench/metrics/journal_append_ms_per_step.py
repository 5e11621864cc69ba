"""Journal: ``journal/append`` time (the step's WAL row) per engine step."""
from chipbench.metrics._steps import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "journal/append")
