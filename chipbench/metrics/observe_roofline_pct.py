"""Kernels: the full-fit observe's least time at its shapes (operations
and bytes from ``harness/flops.py`` against the chip's peaks) over its
device time, as a share."""
from chipbench.harness import flops
from chipbench.harness.reference import capacity
from chipbench.metrics._common import program_mean_ms

PROGRAM = "jit_observe_fn"


def read(ctx):
    fits = [o for o in ctx.replay.observes if o[4] == "fit"]
    mean_ms = program_mean_ms(ctx, PROGRAM)
    if not fits or mean_ms is None or ctx.peak is None:
        return None
    floors = [flops.least_seconds(*flops.full_fit_observe(
        capacity(n), capacity(m), ctx.d, ctx.method), ctx.peak)[0]
        for _, _, n, m, _ in fits]
    return 100.0 * (sum(floors) / len(floors)) / (mean_ms * 1e-3)
