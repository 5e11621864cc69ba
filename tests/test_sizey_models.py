"""Per-model-class tests: each regressor learns its designed relationship."""
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.config import SizeyConfig
from repro.core.models import MODEL_MODULES, forest, knn, linear, mlp

CFG = SizeyConfig()


def _buffers(fn, n=64, cap=128, d=1, seed=0):
    rng = np.random.default_rng(seed)
    xs = np.zeros((cap, d), np.float32)
    ys = np.zeros((cap,), np.float32)
    xs[:n, 0] = rng.uniform(0.1, 8.0, n)
    ys[:n] = [fn(x) for x in xs[:n, 0]]
    mask = np.zeros((cap,), np.float32)
    mask[:n] = 1.0
    return jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(mask)


KEY = jax.random.PRNGKey(0)


def test_linear_recovers_line():
    xs, ys, mask = _buffers(lambda x: 3.0 * x + 2.0)
    st = linear.fit(xs, ys, mask, KEY, CFG)
    for x in (1.0, 4.0, 7.5):
        got = float(linear.predict(st, jnp.asarray([x])))
        assert got == pytest.approx(3.0 * x + 2.0, rel=1e-3)


def test_linear_incremental_matches_full_fit():
    xs, ys, mask = _buffers(lambda x: 2.0 * x + 1.0, n=32)
    full = linear.fit(xs, ys, mask, KEY, CFG)
    # build the same state by rank-1 updates
    inc = linear.init(1, CFG)
    for i in range(32):
        m = jnp.zeros_like(mask).at[: i + 1].set(1.0)
        inc = linear.update(inc, xs, ys, m, jnp.asarray(i), KEY, CFG)
    np.testing.assert_allclose(np.asarray(full.w), np.asarray(inc.w),
                               rtol=1e-4)


def test_knn_interpolates_locally():
    xs, ys, mask = _buffers(lambda x: 10.0 if x > 4.0 else 1.0, n=64)
    st = knn.fit(xs, ys, mask, KEY, CFG)
    assert float(knn.predict(st, jnp.asarray([7.0]), k=5)) == pytest.approx(10.0, abs=0.5)
    assert float(knn.predict(st, jnp.asarray([1.0]), k=5)) == pytest.approx(1.0, abs=0.5)


def test_knn_ignores_masked_rows():
    xs, ys, mask = _buffers(lambda x: 1.0, n=8)
    ys = ys.at[20].set(1e9)  # poison a masked row
    st = knn.fit(xs, ys, mask, KEY, CFG)
    assert float(knn.predict(st, jnp.asarray([4.0]), k=5)) < 10.0


def _knn_pool(cap, d, n, seed):
    """A seeded pool of ``n`` valid rows in a ``cap``-row buffer; the
    masked rows hold junk that must not leak into any prediction."""
    rng = np.random.default_rng(seed)
    xs = rng.uniform(0.1, 8.0, (cap, d)).astype(np.float32)
    ys = (xs.sum(1) + rng.normal(0.0, 0.3, cap)).astype(np.float32)
    mask = np.zeros(cap, np.float32)
    mask[rng.permutation(cap)[:n]] = 1.0
    ys[mask == 0] = 1e9
    return jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(mask)


@pytest.mark.parametrize("d", [1, 2])
def test_knn_predict_batch_matches_reference(d):
    from chipbench.harness import reference
    xs, ys, mask = _knn_pool(512, d, 300, seed=d)
    st = knn.fit(xs, ys, mask, KEY, CFG)
    got = jax.jit(lambda q: knn.predict_batch(st, q, k=CFG.knn_k))(xs)
    ref = reference.knn_predict(xs, ys, mask, xs, CFG.knn_k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)
    assert float(jnp.max(got)) < 1e3           # no masked row leaked


@pytest.mark.parametrize("queries,cap", [(128, 128), (16, 512)])
def test_knn_pairs_is_what_the_top_k_reads(queries, cap):
    """``knn.pairs`` counts the distances the traced program selects from:
    the ``top_k`` operand is one distance per (query, buffer row)."""
    xs, ys, mask = _knn_pool(cap, 1, cap // 2, seed=cap)
    st = knn.fit(xs, ys, mask, KEY, CFG)
    jaxpr = jax.make_jaxpr(
        lambda q: knn.predict_batch(st, q, k=CFG.knn_k))(xs[:queries])
    (shape,) = [e.invars[0].aval.shape for e in jaxpr.jaxpr.eqns
                if e.primitive.name == "top_k"]
    assert shape == (queries, cap)
    assert math.prod(shape) == knn.pairs(queries, cap)


@pytest.mark.parametrize("refit_growth,span", [(0.0, "observe"),
                                               (0.5, "refresh")])
def test_knn_pairs_count_and_span_cap(refit_growth, span):
    from repro import obs
    from repro.core.predictor import DISPATCH_COUNTS, SizeyPredictor
    cfg = dataclasses.replace(CFG, mlp_train_steps=8, hpo=False,
                              refit_growth=refit_growth)
    pred = SizeyPredictor(cfg, default_machine_cap_gb=64.0)

    def complete(x):
        d = pred.predict("t", "m", [x], 8.0)
        pred.observe(d, 1.0 + 0.1 * x, 0.1)
    for i in range(10):
        complete(1.0 + i)
    pool = pred.db.pool("t", "m")
    with obs.scoped_counters(DISPATCH_COUNTS) as dc, obs.tracing() as col:
        complete(2.5)
        assert dc[span + "_pool"] == 1
        assert dc["knn_pairs"] == pool.cap * pool.cap == 128 * 128
    (args,) = [s[3] for s in col.spans if s[0] == span]
    assert args["n"] == 11 and args["cap"] == 128


def test_mlp_learns_quadratic():
    xs, ys, mask = _buffers(lambda x: 0.5 * x * x + 1.0, n=96)
    st = mlp.fit(xs, ys, mask, KEY, CFG)
    err = [abs(float(mlp.predict(st, jnp.asarray([x]))) - (0.5 * x * x + 1.0))
           for x in (1.0, 3.0, 6.0)]
    assert max(err) < 1.5  # within ~8% of the 18.9 peak


def test_mlp_incremental_improves_or_holds_loss():
    xs, ys, mask = _buffers(lambda x: 2.0 * x, n=48)
    st = mlp.fit(xs, ys, mask, KEY, CFG)
    before = abs(float(mlp.predict(st, jnp.asarray([4.0]))) - 8.0)
    for _ in range(5):
        st = mlp.update(st, xs, ys, mask, jnp.asarray(47), KEY, CFG)
    after = abs(float(mlp.predict(st, jnp.asarray([4.0]))) - 8.0)
    assert after <= before + 0.5


def test_forest_learns_step_function():
    xs, ys, mask = _buffers(lambda x: 8.0 if x > 4.0 else 2.0, n=96)
    st = forest.fit(xs, ys, mask, KEY, CFG)
    assert float(forest.predict(st, jnp.asarray([6.5]))) == pytest.approx(8.0, abs=1.0)
    assert float(forest.predict(st, jnp.asarray([1.5]))) == pytest.approx(2.0, abs=1.0)


def test_forest_update_refreshes_leaves():
    xs, ys, mask = _buffers(lambda x: 5.0, n=32)
    st = forest.fit(xs, ys, mask, KEY, CFG)
    ys2 = ys * 2.0
    st2 = forest.update(st, xs, ys2, mask, jnp.asarray(31), KEY, CFG)
    assert float(forest.predict(st2, jnp.asarray([4.0]))) == pytest.approx(10.0, abs=1.0)
    # structure unchanged
    np.testing.assert_array_equal(np.asarray(st.feat), np.asarray(st2.feat))


def _scatter_fit(xs, ys, mask, key, cfg):
    """Oracle: the forest grown with per-tree, per-candidate segment sums."""
    t, depth = cfg.forest_trees, cfg.forest_depth
    cands = jnp.nan_to_num(forest._candidate_thresholds(xs, mask), nan=0.0)
    gmean = jnp.sum(ys * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    boot = jax.random.poisson(key, 1.0, (t, xs.shape[0])).astype(jnp.float32)
    boot = boot * mask[None, :]
    d, q = cands.shape

    def sums(seg, w, n):
        return [jax.ops.segment_sum(v, seg, num_segments=n)
                for v in (w, w * ys, w * ys * ys)]

    def tree(w):
        leaf = jnp.zeros(xs.shape[0], jnp.int32)
        feats, threshs = [], []
        for level in range(depth):
            sse = []
            for f in range(d):
                for qi in range(q):
                    go = (xs[:, f] > cands[f, qi]).astype(jnp.int32)
                    seg = leaf * 2 + go
                    sw, swy, swy2 = sums(seg, w, 2 ** (level + 1))
                    sse.append(jnp.sum(swy2 - swy * swy
                                       / jnp.maximum(sw, forest._EPS)))
            best = jnp.argmin(jnp.stack(sse))
            f, qi = best // q, best % q
            feats.append(f)
            threshs.append(cands[f, qi])
            leaf = leaf * 2 + (xs[:, f] > cands[f, qi]).astype(jnp.int32)
        sw, swy, _ = sums(leaf, w, 2 ** depth)
        vals = jnp.where(sw > forest._EPS,
                         swy / jnp.maximum(sw, forest._EPS), gmean)
        return jnp.stack(feats), jnp.stack(threshs), vals

    return jax.vmap(tree)(boot)


def _forest_pool(d, seed):
    """A pool with masked rows holding junk, Poisson-zero rows, a discrete
    first feature whose repeated quantiles give identical partitions, and
    a capacity that is not a whole number of row-loop steps."""
    rng = np.random.default_rng(seed)
    cap, n = 200, 150
    xs = rng.uniform(-50.0, 50.0, (cap, d)).astype(np.float32)
    xs[:n, 0] = rng.integers(1, 7, n)
    if d > 1:
        xs[:n, 1] = rng.uniform(0.0, 4.0, n)
    ys = rng.uniform(-100.0, 100.0, cap).astype(np.float32)
    ys[:n] = (2.0 * xs[:n, 0] + 3.0 * (xs[:n, -1] > 2.0)
              + rng.normal(0.0, 0.5, n))
    mask = np.zeros((cap,), np.float32)
    mask[:n] = 1.0
    return jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(mask)


@pytest.mark.parametrize("d,depth", [(1, 1), (1, 3), (1, 4), (2, 1),
                                     (2, 3), (2, 4)])
def test_forest_split_hist_matches_segment_sum(monkeypatch, d, depth):
    # blocks of 64 rows: the kernel's grid walks several, the last padded
    monkeypatch.setattr(forest, "split_hist", functools.partial(
        forest.split_hist, block_rows=64))
    cfg = dataclasses.replace(CFG, forest_depth=depth)
    xs, ys, mask = _forest_pool(d, seed=10 * d + depth)
    key = jax.random.PRNGKey(depth)
    cands = jnp.nan_to_num(forest._candidate_thresholds(xs, mask), nan=0.0)
    assert len(np.unique(np.asarray(cands[0]))) < cands.shape[1]
    w = jax.random.poisson(key, 1.0, (cfg.forest_trees, xs.shape[0]))
    w = w.astype(jnp.float32) * mask
    assert np.any((np.asarray(w) == 0) & (np.asarray(mask) > 0))
    go = (xs[:, :, None] > cands[None]).reshape(xs.shape[0], -1)
    go = go.astype(jnp.int32)
    stats = jnp.stack([w, w * ys, w * ys * ys], axis=-1).swapaxes(0, 1)
    rng = np.random.default_rng(depth)
    for level in range(depth + 1):
        n_seg = 2 ** (level + 1)
        leaf = jnp.asarray(rng.integers(0, n_seg // 2, w.shape[::-1]),
                           jnp.int32)
        seg = leaf[:, :, None] * 2 + go[:, None, :]             # (CAP, T, C)
        want = jax.vmap(jax.vmap(jax.vmap(
            lambda sg, v: jax.ops.segment_sum(v, sg, num_segments=n_seg),
            in_axes=(None, 1)), in_axes=(1, None)), in_axes=(1, 1))(
                seg, stats)
        got = forest._hist(seg, stats, n_seg)                   # (T, C, S, K)
        # the row order of a scatter-add, so equal to the last bit
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    st = forest.fit(xs, ys, mask, key, cfg)
    feat, thresh, vals = _scatter_fit(xs, ys, mask, key, cfg)
    np.testing.assert_array_equal(np.asarray(st.feat), np.asarray(feat))
    np.testing.assert_array_equal(np.asarray(st.thresh), np.asarray(thresh))
    np.testing.assert_array_equal(np.asarray(st.leaf_vals), np.asarray(vals))


@pytest.mark.parametrize("name", list(MODEL_MODULES))
def test_all_models_finite_on_tiny_history(name):
    mod = MODEL_MODULES[name]
    xs, ys, mask = _buffers(lambda x: x + 1.0, n=3)
    st = mod.fit(xs, ys, mask, KEY, CFG)
    val = float(mod.predict(st, jnp.asarray([2.0])))
    assert np.isfinite(val)
