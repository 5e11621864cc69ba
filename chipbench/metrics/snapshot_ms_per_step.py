"""Journal: snapshot time per engine step, the state build
(``cluster/export_state``) and its write (``journal/snapshot``)."""
from chipbench.metrics._steps import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "cluster/export_state", "journal/snapshot")
