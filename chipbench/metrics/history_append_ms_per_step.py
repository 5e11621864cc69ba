"""Method and predictor host: ``history/append`` time (the provenance,
log and curve rows of each pool's completions) per engine step."""
from chipbench.metrics._steps import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "history/append")
