"""Small shared utilities."""
from __future__ import annotations

import hashlib
import os

import jax
import numpy as np

GB = 1024**3
MB = 1024**2

# <repo>/.jax_cache: a fixed path, because the cache key includes it
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(x: int, m: int) -> int:
    """Round x up to the next multiple of m."""
    return ceil_div(x, m) * m


def tree_bytes(tree) -> int:
    """Total bytes of all arrays / ShapeDtypeStructs in a pytree."""
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(int(np.prod(l.shape)) * l.dtype.itemsize for l in leaves)


def stable_hash(s: str) -> int:
    """Deterministic 63-bit hash (python's hash() is salted per-process)."""
    return int.from_bytes(hashlib.sha256(s.encode()).digest()[:8], "big") >> 1


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other path is set here; otherwise the cache lives at ``<repo>/.jax_cache``.
    The predictor compiles many small programs (a few per buffer and batch
    bucket), most under JAX's default one-second floor, so every compiled
    program is cached. Call from entry points only, never at import."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
