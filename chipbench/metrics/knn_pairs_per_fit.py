"""Method and predictor host: distances the in-sample k-NN evaluates per
observe or refresh launch in the window (``DISPATCH_COUNTS["knn_pairs"]``:
each launch adds what ``knn.pairs`` gives for its shapes, today every
buffer row against every buffer row). None where the program has no such
counter."""

KEYS = ("dispatch.observe_pool", "dispatch.refresh_pool")
PAIRS = "dispatch.knn_pairs"


def read(ctx):
    w = ctx.win
    if PAIRS not in w.counters:
        return None
    fits = sum(w.counters.get(k, 0) - w.counters0.get(k, 0) for k in KEYS)
    if not fits:
        return None
    return (w.counters[PAIRS] - w.counters0.get(PAIRS, 0)) / fits
