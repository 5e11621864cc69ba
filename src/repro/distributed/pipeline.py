"""Pipeline parallelism: GPipe schedule via shard_map + collective_permute.

Optional axis for >2-pod scale-out (DESIGN.md §5): layers are split into S
stages laid out on a "stage" mesh axis; microbatches stream through with a
collective_permute shift per tick (T = M + S - 1 ticks total). The
assigned dry-run meshes use FSDP x TP only; this module is exercised at
toy scale by tests/test_distributed.py.

The schedule is the textbook fill-drain GPipe: bubble fraction
(S - 1) / (M + S - 1); choose M >= 4 S to keep it under 20%.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn, stage_params, x_microbatches, *,
                   mesh: Mesh, axis: str = "stage"):
    """Run microbatches through S pipeline stages.

    stage_fn:          (params_one_stage, x (mb, d)) -> (mb, d)
    stage_params:      pytree stacked on the leading STAGE dim (S, ...)
    x_microbatches:    (M, mb, d)
    Returns (M, mb, d) outputs after all S stages.
    """
    n_stages = mesh.shape[axis]
    m, mb, d = x_microbatches.shape
    ticks = m + n_stages - 1

    def shmapped(params_local, x_all):
        # params_local: (1, ...) this stage's slice; x_all: full (M, mb, d)
        params_here = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            buf, out = carry          # buf: (mb, d) input for this tick
            # stage 0 ingests microbatch t (garbage past M; masked later)
            mb_idx = jnp.clip(t, 0, m - 1)
            feed = jax.lax.dynamic_index_in_dim(x_all, mb_idx, 0, False)
            x_in = jnp.where(stage == 0, feed, buf)
            y = stage_fn(params_here, x_in)
            # last stage retires microbatch (t - S + 1); where-select keeps
            # shard_map's varying-axis types consistent across branches
            out_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            take = (stage == n_stages - 1) & (t >= n_stages - 1)
            updated = jax.lax.dynamic_update_index_in_dim(out, y, out_idx, 0)
            out = jnp.where(take, updated, out)
            # shift activations one stage down the ring
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, out), None

        # initial carries are device-varying (each stage evolves its own)
        buf0 = jax.lax.pvary(jnp.zeros((mb, d), x_all.dtype), (axis,))
        out0 = jax.lax.pvary(jnp.zeros((m, mb, d), x_all.dtype), (axis,))
        (_, out), _ = jax.lax.scan(tick, (buf0, out0), jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast via psum
        out = jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out))
        return jax.lax.psum(out, axis)

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(shmapped, in_specs=(spec_params, P()), out_specs=P())
    # shard_map takes its mesh from the context: jax.set_mesh also places
    # the scan's loop counters on it when the mesh's axes are Explicit
    with jax.set_mesh(mesh):
        return fn(stage_params, x_microbatches)


def split_stages(layer_params, n_stages: int):
    """Reshape (L, ...)-stacked layer params into (S, L/S, ...) stages."""
    def one(a):
        l = a.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return a.reshape(n_stages, l // n_stages, *a.shape[1:])

    return jax.tree.map(one, layer_params)


def make_stage_fn(layer_fn):
    """Stage = sequential application of this stage's layer slice."""
    def stage_fn(stage_params, x):
        def body(h, lp):
            return layer_fn(lp, h), None
        h, _ = jax.lax.scan(body, x, stage_params)
        return h

    return stage_fn
