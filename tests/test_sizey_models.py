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


@pytest.fixture
def sorted_from(monkeypatch):
    """Sets, for one test, the smallest buffer that takes the k-NN's sorted
    in-sample path; the fused programs retrace on both sides of it."""
    from repro.core.predictor import _fused_observe_all, _fused_refresh_all

    def clear():
        _fused_observe_all.cache_clear()
        _fused_refresh_all.cache_clear()

    def set_to(n_rows):
        monkeypatch.setattr(knn, "_SORTED_MIN_CAP", n_rows)
        clear()
    yield set_to
    clear()


def _insample_pool(case, cap, seed=0):
    """One-feature buffers for the in-sample exactness cases, masked rows
    scattered through the buffer as in ``_knn_pool``. They hold zeros, as
    provenance pads do, except in ``junk_pads``: more distinct values
    than the sorted path answers by brute force."""
    k = CFG.knn_k
    rng = np.random.default_rng(seed)
    xs = rng.uniform(0.1, 8.0, cap).astype(np.float32)
    ys = rng.uniform(1.0, 64.0, cap).astype(np.float32)
    n = {"valid_0": 0, "valid_1": 1, "valid_k-1": k - 1,
         "boundary": 3 * k + 1}.get(case, 2 * cap // 3)
    valid = rng.permutation(cap)[:n]
    if case in ("lognormal_runs", "refresh"):
        # the traffic's input sizes: runs of identical rows at the clip
        # edges, far longer than k
        xs[valid] = np.clip(rng.lognormal(np.log(1.525), 0.8, n), 0.1, 6.0)
        assert np.sum(xs[valid] == 6.0) > 4 * k
    elif case == "all_equal":
        xs[:] = 2.5
    elif case == "grid":
        xs[valid] = np.arange(n) % 9 * 0.25    # symmetric ties
    elif case == "boundary":
        # a run of k at -1 and two more a few ulp beyond it: the row at 0
        # selects from the first run while the next one, just outside its
        # window, is within the check's margin of the k-th distance
        steps = np.nextafter(np.float32(-1.0), np.float32(-2.0))
        outer = np.nextafter(steps, np.float32(-2.0))
        xs[valid] = np.repeat(np.float32([0.0, -1.0, steps, outer]),
                              [1, k, k, k])
    mask = np.zeros(cap, np.float32)
    mask[valid] = 1.0
    ys[mask == 0] = 1e9
    if case != "junk_pads":
        xs[mask == 0] = 0.0
    queries = xs.copy()
    if case == "refresh":
        # a refresh queries the current buffer against the last fit's
        # states: rows appended since then are pads there, and masked
        queries[rng.permutation(np.flatnonzero(mask == 0))[:k]] = \
            rng.uniform(0.1, 6.0, k)
    return (jnp.asarray(xs[:, None]), jnp.asarray(ys), jnp.asarray(mask),
            jnp.asarray(queries[:, None]))


@pytest.mark.parametrize("case,cap", [
    ("random_mask", 16), ("random_mask", 512), ("random_mask", 4096),
    ("lognormal_runs", 4096), ("all_equal", 128), ("grid", 256),
    ("valid_0", 16), ("valid_1", 64), ("valid_k-1", 64), ("refresh", 1024),
    ("boundary", 64), ("junk_pads", 512)])
def test_knn_insample_is_the_brute_force_bit_for_bit(case, cap,
                                                    sorted_from):
    """The sorted in-sample path gives today's brute force and the plain
    reference bit for bit at every row, masked rows included; it falls
    back only where its boundary check fails or queries are left
    unanswered."""
    from chipbench.harness import reference
    sorted_from(0)            # the sorted path at every buffer size
    xs, ys, mask, queries = _insample_pool(case, cap)
    st = knn.fit(xs, ys, mask, KEY, CFG)
    got, fell_back = jax.jit(
        lambda s, q: knn.insample_batch(s, q, k=CFG.knn_k))(st, queries)
    brute = jax.jit(
        lambda s, q: knn.predict_batch(s, q, k=CFG.knn_k))(st, queries)
    ref = reference.knn_predict(xs, ys, mask, queries, CFG.knn_k)
    assert np.array_equal(np.asarray(got), np.asarray(brute))
    assert np.array_equal(np.asarray(got), np.asarray(ref))
    assert bool(fell_back) == (case in ("boundary", "junk_pads"))


@pytest.mark.parametrize("d,case", [(1, "sorted"), (2, "brute"),
                                    (1, "fallback"), (1, "small")])
def test_knn_pairs_and_fallback_per_launch(d, case, sorted_from):
    """Each observe adds the distances its in-sample k-NN evaluated: on the
    sorted path a window of 5k per sorted slot and 8 brute-force values
    against every row; CAP² for d = 2, below the sorted path's smallest
    buffer, and where the launch fell back. ``knn_fallback`` counts only
    the fallbacks."""
    from repro import obs
    from repro.core.predictor import DISPATCH_COUNTS, SizeyPredictor
    k = CFG.knn_k
    if case != "small":       # "small" keeps 128 rows below the real gate
        sorted_from(0)
    cfg = dataclasses.replace(CFG, mlp_train_steps=8, hpo=False)
    pred = SizeyPredictor(cfg, n_features=d, default_machine_cap_gb=64.0)
    if case == "fallback":     # _insample_pool's "boundary" rows
        steps = np.nextafter(np.float32(-1.0), np.float32(-2.0))
        outer = np.nextafter(steps, np.float32(-2.0))
        xs = np.repeat(np.float32([0.0, -1.0, steps, outer]), [1, k, k, k])
    else:
        xs = 1.0 + np.arange(12, dtype=np.float32)

    def complete(x):
        dec = pred.predict("t", "m", [float(x)] * d, 8.0)
        pred.observe(dec, 1.0 + 0.1 * abs(float(x)), 0.1)
    for x in xs:
        complete(x)
    cap = pred.db.pool("t", "m").cap
    with obs.scoped_counters(DISPATCH_COUNTS) as dc:
        complete(0.0)
        assert dc["observe_pool"] == 1
        assert dc["knn_pairs"] == (cap * (5 * k + 8) if case == "sorted"
                                   else cap * cap)
        assert dc["knn_fallback"] == (case == "fallback")


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
