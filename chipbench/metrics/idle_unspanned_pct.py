"""Device: share of the traced window in which the first chip is idle and
no program span below the step wrappers (``service/grant``,
``cluster/step``) is open: idle time no span of the program explains.

Program spans are stamped on ``perf_counter_ns``; they are laid on the
profiler's clock by the window's anchor, ``Window.start`` taken a few
microseconds before the ``bench.window`` annotation opens."""
from chipbench.harness.trace_reduce import merge

WRAPPERS = ("service/grant", "cluster/step")


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace["lo"], ctx.trace["hi"]
    ops = ctx.trace_obj.ops
    busy = list(ops[min(ops)]) if ops else []
    shift = lo - round(ctx.win.start * 1e9)
    spanned = [(s[1] + shift, s[1] + s[2] + shift) for s in ctx.spans or ()
               if s[0] not in WRAPPERS]
    covered = sum(b - a for a, b in merge(busy + spanned, lo, hi))
    return 100.0 * (1.0 - covered / max(hi - lo, 1))
