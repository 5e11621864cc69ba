"""Service loop: share of the window's wall time outside ``engine.step``
(the scheduler service's own work between steps)."""


def read(ctx):
    w = ctx.win
    inside = sum(b - a for a, b, _ in w.steps)
    return 100.0 * (w.seconds - inside) / w.seconds
