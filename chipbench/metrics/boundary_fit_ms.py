"""Temporal: mean wall time of the program's ``boundary_fit`` span (the
segment DP over a pool's usage profiles)."""
from chipbench.metrics._common import span_durations


def read(ctx):
    d = span_durations(ctx, "boundary_fit")
    return 1e3 * sum(d) / len(d) if d else None
