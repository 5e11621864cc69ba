"""Device: compile and persistent-cache-read events inside the window."""


def read(ctx):
    return ctx.win.compiles
