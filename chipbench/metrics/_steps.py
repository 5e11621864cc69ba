"""Helpers of the readers of the program's engine-step spans.

Each program span is ``(name, start_ns, dur_ns, args, id, parent)``, with
``start_ns`` on ``perf_counter_ns``; a program that records no span ids
gives 4-tuples, and a reader of this module then finds nothing."""
from __future__ import annotations

STEP = "cluster/step"


def steps(ctx) -> list:
    """The window's ``cluster/step`` spans."""
    if ctx.spans is None:
        return []
    return [s for s in ctx.spans if s[0] == STEP and len(s) > 5]


def ms_per_step(ctx, *names):
    """Milliseconds of the named spans in the window per engine step."""
    n = len(steps(ctx))
    if not n:
        return None
    return 1e-6 * sum(s[2] for s in ctx.spans if s[0] in names) / n
