"""Distribution tests: sharded train step on a real (2,2) mesh, elastic
re-meshing 8->4->8, and the scaled-down dry-run — all in subprocesses with
forced host device counts (the main pytest process stays single-device)."""
import json
import os
import subprocess
import sys
import tempfile

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(script: str, devices: int = 8, timeout: int = 600):
    # JAX_PLATFORMS=cpu: the forced host devices are CPU devices, and a
    # child must never reach for an accelerator its parent may hold
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(REPO, "src"))
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_sharded_train_step_runs_on_mesh():
    r = run_py("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.distributed.sharding import axis_rules, param_specs, batch_specs
from repro.launch.mesh import make_test_mesh
from repro.models.model import init_params
from repro.train.optimizer import make_optimizer
from repro.train.step import make_train_step

cfg = get_config("granite-3-2b").reduced()
mesh = make_test_mesh(2, 2)
params = init_params(cfg, jax.random.PRNGKey(0))
opt = make_optimizer("adamw")
opt_state = opt.init(params)
p_specs = param_specs(params, mesh)
ns = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                               is_leaf=lambda x: isinstance(x, P))
params = jax.device_put(params, ns(p_specs))
opt_state = jax.device_put(opt_state, ns({"m": p_specs, "v": p_specs,
                                          "step": P()}))
batch = {"tokens": jnp.zeros((4, 32), jnp.int32)}
with axis_rules(mesh):
    step = jax.jit(make_train_step(cfg, opt))
    m, params, opt_state = step(params, opt_state, batch)
wq = params["blocks"]["attn"]["wq"]
assert len(wq.sharding.device_set) == 4, wq.sharding
assert np.isfinite(float(m["loss"]))
print("OK", float(m["loss"]))
""")
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_elastic_rescale_8_4_8():
    r = run_py("""
import jax, jax.numpy as jnp
from repro.launch.elastic import ElasticController, largest_mesh

state = {"w_in": jnp.ones((64, 64)), "bias": jnp.zeros((8,))}
ctl = ElasticController(state)
n0 = ctl.mesh.size
assert ctl.maybe_rescale(jax.devices()[:4])   # lose half the fleet
assert ctl.mesh.size == 4
assert not ctl.maybe_rescale(jax.devices()[:4])  # no change -> no-op
assert ctl.maybe_rescale(jax.devices())       # fleet recovers
assert ctl.mesh.size == n0
assert ctl.events == [(n0, 4), (4, n0)]
import numpy as np
np.testing.assert_array_equal(np.asarray(ctl.state["w_in"]),
                              np.ones((64, 64)))
print("OK")
""")
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_compressed_psum_shard_map():
    r = run_py("""
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.train.compression import compressed_psum

mesh = jax.make_mesh((8,), ("data",))
g = jnp.linspace(-1, 1, 8 * 32).reshape(8, 32)

@partial(shard_map, mesh=mesh, in_specs=P("data", None),
         out_specs=P("data", None))
def allreduce(x):
    out = compressed_psum({"g": x}, "data", jax.random.PRNGKey(0))
    return out["g"]

got = allreduce(g)
want = jnp.broadcast_to(jnp.sum(g, 0, keepdims=True), g.shape)
err = float(jnp.max(jnp.abs(got - want)))
assert err < 0.15, err   # int8 wire precision
print("OK", err)
""")
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_gpipe_pipeline_matches_sequential():
    r = run_py("""
import jax, jax.numpy as jnp
from repro.distributed.pipeline import (pipeline_apply, split_stages,
                                        make_stage_fn)
mesh = jax.make_mesh((4,), ("stage",))
L, d, mb, M = 8, 16, 4, 8
ws = jax.random.normal(jax.random.PRNGKey(0), (L, d, d)) * 0.3
layer_fn = lambda w, x: jnp.tanh(x @ w)
x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))
got = pipeline_apply(make_stage_fn(layer_fn), split_stages(ws, 4), x,
                     mesh=mesh)
def seq(xb):
    h = xb
    for i in range(L):
        h = layer_fn(ws[i], h)
    return h
want = jax.vmap(seq)(x)
err = float(jnp.max(jnp.abs(got - want)))
assert err < 1e-5, err
print("OK", err)
""")
    assert "OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_dryrun_small_grid():
    """Scaled-down dry-run: one arch, train+decode, single+multi mesh."""
    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(td, "dry.jsonl")
        env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_DRYRUN_DEVICES="8",
                   PYTHONPATH=os.path.join(REPO, "src"))
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--test-mesh",
             "--arch", "granite-3-2b", "--shape", "train_4k,decode_32k",
             "--mesh", "both", "--out", out],
            env=env, capture_output=True, text=True, timeout=900)
        assert r.returncode == 0, r.stdout + r.stderr
        rows = [json.loads(l) for l in open(out)]
        assert len(rows) == 4
        for row in rows:
            assert row["status"] == "ok", row
            assert row["cost"]["flops"] > 0
            assert row["roofline"]["bottleneck"] in ("compute", "memory",
                                                     "collective")
