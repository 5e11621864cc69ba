"""Journal: growth of the journal file over the window, per step."""


def read(ctx):
    if not ctx.win.steps:
        return None
    return ctx.win.journal_bytes / 1024.0 / len(ctx.win.steps)
