"""Journal: mean wall time per step of ``Journal.append_step`` and
``Journal.maybe_snapshot`` (which writes the ``journal/snapshot`` rows)."""


def read(ctx):
    if not ctx.win.steps:
        return None
    return 1e3 * ctx.win.journal_s / len(ctx.win.steps)
