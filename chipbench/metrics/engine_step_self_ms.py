"""Event engine: mean wall time of a ``cluster/step`` span less the union
of its direct children (the ``engine/*`` waves, the journal append, the
snapshot's state build and write)."""
import collections

from chipbench.harness.trace_reduce import merge
from chipbench.metrics._steps import steps


def read(ctx):
    found = steps(ctx)
    if not found:
        return None
    children = collections.defaultdict(list)
    for s in ctx.spans:
        if len(s) > 5 and s[5] is not None:
            children[s[5]].append((s[1], s[1] + s[2]))
    self_ns = 0
    for s in found:
        a, b = s[1], s[1] + s[2]
        self_ns += s[2] - sum(hi - lo for lo, hi in
                              merge(children.get(s[4], ()), a, b))
    return 1e-6 * self_ns / len(found)
