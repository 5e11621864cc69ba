"""Kernels: the ``ensemble_mlp`` Pallas kernel's least time (the MLP
forward at each call's shapes against the chip's peaks) over the device
time of its events, as a share. Calls: one per fused predict (rows padded
to the kernel's 128-row blocks) and one per observe or refresh (the
in-sample forward over the pool's buffer)."""
from chipbench.harness import flops, trace_reduce
from chipbench.harness.reference import capacity

# the kernel's own operation, by its instruction name (the custom call
# ``%ensemble_mlp_forward.N``, or the kernel body): not the fusions that
# take its output as an operand
KERNEL = r"^%?(ensemble_mlp|_mlp_body)"


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    k = ctx.method.get("temporal_k") or 1
    min_rows = ctx.method["min_history"] * k
    h = ctx.method["mlp_hidden"]
    rows = [-(-capacity(sum(len(b.get("seg_decisions", [b]))
                           for b in g.blobs), 1) // 128) * 128
            for g in ctx.replay.groups if g.fit_n >= min_rows]
    rows += [capacity(n) for _, _, n, _, _ in ctx.replay.observes]
    n_ev, secs = trace_reduce.op_times(ctx.trace_obj, ctx.trace["lo"],
                                       ctx.trace["hi"], KERNEL)
    if not n_ev or not rows:
        return None
    floors = [flops.least_seconds(*flops.mlp_forward(r, ctx.d, h),
                                  ctx.peak)[0] for r in rows]
    return 100.0 * (sum(floors) / len(floors)) / (secs / n_ev)
