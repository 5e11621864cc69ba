"""Event engine: mean step wall time less what its child spans cover
(``engine/*`` waves) and the journal writes."""


def read(ctx):
    if ctx.spans is None or not ctx.win.steps:
        return None
    steps = ctx.win.steps
    lo, hi = steps[0][0], steps[-1][1]
    child = sum(s[2] * 1e-9 for s in ctx.spans
                if s[0].startswith("engine/") and lo <= s[1] * 1e-9 <= hi)
    total = sum(b - a for a, b, _ in steps)
    return 1e3 * (total - child - ctx.win.journal_s) / len(steps)
