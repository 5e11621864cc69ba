"""Method and predictor host: mean wall time of the program's ``observe``
span, one full fit of a pool from dispatch until its device work is
done."""
from chipbench.metrics._common import span_durations


def read(ctx):
    d = span_durations(ctx, "observe")
    return 1e3 * sum(d) / len(d) if d else None
