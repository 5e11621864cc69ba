"""Compile-only guards: the served path's device programs compile for a
described TPU v5e chip, at the shapes the benchmark reaches.

Nothing runs and no chip is needed: the TPU compiler is installed, and a
topology described by ``get_topology_desc`` stands in for the device. The
default backend here is the CPU, so each test passes ``use_pallas=True``
itself, as the predictor does on a TPU. Each test prints the program's
``memory_analysis()``; the kernel's presence is read from the compiled HLO
(``tpu_custom_call``), and no f32 dot may run at default precision.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.config import SizeyConfig
from repro.core.models import MODEL_MODULES
from repro.core.predictor import _fused_observe_all, _fused_predict
from repro.core.temporal.segments import PROFILE_WINDOW
from repro.kernels.ensemble_mlp.ops import ensemble_mlp_forward
from repro.kernels.segment_dp.ops import _fit_cuts_jit

CFG = SizeyConfig()
MODELS = tuple(CFG.model_classes)
N_GRID = 32           # TemporalSizeyPredictor's default grid
K_SEGMENTS = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU compiler would otherwise write its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off:
    entries compiled for a described chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(name, fn, *args, **static):
    lowered = fn.lower(*args, **static)
    # a TPU runs an f32 dot at default precision as one bf16 pass
    assert "precision = [DEFAULT" not in lowered.as_text(), name
    compiled = lowered.compile()
    print(f"{name}: {compiled.memory_analysis()}")
    return compiled


def _observe_args(sharding, cap, d=1):
    n = len(MODELS)
    vec = _spec(sharding, (cap,))
    return (None, _spec(sharding, (cap, d)), vec, vec, vec,
            _spec(sharding, (), jnp.int32), _spec(sharding, (), jnp.int32),
            vec, vec, vec, vec, _spec(sharding, (n, cap)))


def test_ensemble_mlp_kernel_compiles(one_chip):
    hidden = CFG.mlp_hidden
    compiled = _compile(
        "ensemble_mlp (1, 1024, 1) x 32", ensemble_mlp_forward,
        _spec(one_chip, (1, 1024, 1)), _spec(one_chip, (1, 1, hidden)),
        _spec(one_chip, (1, hidden)), _spec(one_chip, (1, hidden, 1)),
        _spec(one_chip, (1, 1)))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("cap", [1024, 4096, 16384])
def test_fused_observe_full_fit_compiles(one_chip, cap):
    fn = _fused_observe_all(MODELS, CFG, 1.0, True, False)
    compiled = _compile(f"fused observe (full fit) cap={cap}", fn,
                        *_observe_args(one_chip, cap))
    assert "tpu_custom_call" in compiled.as_text()
    # the in-sample k-NN never holds its CAP x CAP distances (1 GiB alone
    # at CAP 16384): the compiler fuses them into the top-k
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


@pytest.mark.parametrize("cap,d", [(4096, 1), (2048, 2)])
def test_forest_fit_has_no_scatter(one_chip, cap, d):
    # the split histograms run the row-order kernel: a scatter-add
    # serializes its updates on a TPU
    vec = _spec(one_chip, (cap,))
    fn = jax.jit(MODEL_MODULES["forest"].fit, static_argnums=(4, 5))
    compiled = _compile(f"forest fit cap={cap} d={d}", fn,
                        _spec(one_chip, (cap, d)), vec, vec,
                        _spec(one_chip, (2,), jnp.uint32), CFG, True)
    assert " scatter(" not in compiled.as_text()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_predict_compiles(one_chip):
    cap, k = 1024, 64
    observe = _fused_observe_all(MODELS, CFG, 1.0, True, False)
    states, _, cache = jax.eval_shape(observe, *_observe_args(one_chip, cap))
    # the predictor dispatches its predict view of the states: the fields
    # predict never reads are dropped (SizeyPredictor._pview)
    pview = tuple(
        s._replace(**{f: None for f in MODEL_MODULES[m].PREDICT_DROP})
        for m, s in zip(MODELS, states))
    place = lambda a: _spec(one_chip, a.shape, a.dtype)
    fn = _fused_predict(MODELS, CFG, 1.0, True)
    compiled = _compile(f"fused predict K={k}", fn,
                        jax.tree.map(place, pview), _spec(one_chip, (k, 2)),
                        *jax.tree.map(place, cache))
    assert "tpu_custom_call" in compiled.as_text()


def test_segment_dp_compiles(one_chip):
    _compile(f"segment DP {PROFILE_WINDOW} x {N_GRID}, k={K_SEGMENTS}",
             _fit_cuts_jit, _spec(one_chip, (PROFILE_WINDOW, N_GRID)),
             k=K_SEGMENTS)
