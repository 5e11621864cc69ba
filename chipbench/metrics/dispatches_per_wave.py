"""Method and predictor host: device launches of the predictor
(``DISPATCH_COUNTS`` predict, observe and refresh) plus boundary fits
(``BOUNDARY_COUNTS["fit"]``), over the window, per sizing step."""

KEYS = ("dispatch.predict_pool", "dispatch.observe_pool",
        "dispatch.refresh_pool", "boundary.fit")


def read(ctx):
    w = ctx.win
    waves = sum(1 for s in w.steps if s[2])
    if not waves:
        return None
    return sum(w.counters.get(k, 0) - w.counters0.get(k, 0)
               for k in KEYS) / waves
