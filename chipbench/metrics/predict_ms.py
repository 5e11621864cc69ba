"""Method and predictor host: mean wall time of the program's ``predict``
span (upload, dispatch, device and fetch of one fused decision)."""
from chipbench.metrics._common import span_durations


def read(ctx):
    d = span_durations(ctx, "predict")
    return 1e3 * sum(d) / len(d) if d else None
