"""Packed-forest serving engine (ISSUE 5): depth-bounded traversal,
device-side binning, incremental packing, batch bucketing, the raw
(loaded-model) route, the model-generation counter, and the sklearn
``device=`` passthrough."""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.analysis import guards
from lightgbm_tpu.core.tree import host_tree_to_arrays, max_leaf_depth
from lightgbm_tpu.ops import forest as forest_mod
from lightgbm_tpu.ops.forest import (DeviceBinner, bucket_rows, f32_floor,
                                     _host_tree_to_raw)
from lightgbm_tpu.ops.predict import (depth_steps, forest_leaf_bins,
                                      tree_leaf_bins, tree_leaf_raw)


def _train(rng, n=600, f=6, missing=None, n_round=8, cat=False, **params):
    X = rng.normal(size=(n, f)).astype(np.float32).astype(np.float64)
    kw = {}
    if missing == "nan":
        X[rng.uniform(size=X.shape) < 0.08] = np.nan
    elif missing == "zero":
        X[rng.uniform(size=X.shape) < 0.15] = 0.0
        kw["zero_as_missing"] = True
    elif missing == "none":
        kw["use_missing"] = False
    if cat:
        X[:, f - 1] = rng.integers(0, 8, size=n)
    y = np.nan_to_num(X[:, 0]) + 0.5 * np.nan_to_num(X[:, 1])
    p = {"objective": "regression", "num_leaves": 31, "verbose": -1,
         "min_data_in_leaf": 5, **kw, **params}
    ds = lgb.Dataset(X, label=y,
                     categorical_feature=[f - 1] if cat else "auto")
    return lgb.train(p, ds, num_boost_round=n_round), X


def _adversarial(rng, X):
    """Request batch exercising NaN, exact zeros, +-inf and the
    kZeroThreshold edge (float32(1e-35) rounds UP past 1e-35 — the value
    that misroutes at zero-missing nodes if the device compares against
    a naively-cast constant)."""
    Xq = X.copy()
    n = len(Xq)
    Xq[: n // 8] = np.nan
    Xq[n // 8: n // 4] = 0.0
    Xq[n // 4: 3 * n // 8] = np.inf
    Xq[3 * n // 8: n // 2] = -np.inf
    zt = np.float32(1e-35).astype(np.float64)     # > 1e-35, f32-exact
    Xq[n // 2: 9 * n // 16] = zt
    Xq[9 * n // 16: 5 * n // 8] = -zt
    return Xq


def _engine_meta(eng):
    from lightgbm_tpu.ops.split import FeatureMeta
    return FeatureMeta.from_mappers(eng.train_set.used_bin_mappers())


# ---------------------------------------------------------------------------
# depth-bounded traversal
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_depth_bounded_identical_to_exhaustive_on_ragged_forest(rng):
    """Trees of different depths (natural raggedness from min_data
    constraints): the depth-bounded loop must land every row in exactly
    the leaf the L-1 exhaustive loop lands it in."""
    import jax.numpy as jnp
    bst, X = _train(rng, n=900, n_round=10, num_leaves=63)
    eng = bst._engine
    meta = _engine_meta(eng)
    bins_dev = jnp.asarray(eng.train_set.ensure_logical_bins()
                           if eng.train_set.bins is None
                           else eng.train_set.bins)
    L = eng.config.num_leaves
    depths = [t.max_depth for t in eng.models]
    assert len(set(depths)) > 1, "forest is not ragged — weak test data"
    assert max(depths) < L - 1
    for t in eng.models:
        arrs = host_tree_to_arrays(t, L)
        assert int(arrs.max_depth) == t.max_depth
        exhaustive = tree_leaf_bins(arrs, bins_dev, meta.num_bin,
                                    meta.missing_type, meta.default_bin,
                                    num_steps=L - 1)
        bounded = tree_leaf_bins(arrs, bins_dev, meta.num_bin,
                                 meta.missing_type, meta.default_bin,
                                 num_steps=depth_steps(t.max_depth, L))
        np.testing.assert_array_equal(np.asarray(exhaustive),
                                      np.asarray(bounded))


def test_max_leaf_depth_units():
    # root splits into two leaves: 1 decision
    assert max_leaf_depth([-1], [-2], 2) == 1
    # chain: node0 -> (leaf, node1), node1 -> (leaf, leaf)
    assert max_leaf_depth([-1, -2], [1, -3], 3) == 2
    assert max_leaf_depth([], [], 1) == 0
    # corrupted (cyclic) pointers fall back to the exhaustive bound
    assert max_leaf_depth([1, 0], [1, 0], 3) == 2


def test_depth_steps_bucketing():
    assert depth_steps(0, 255) == 0
    assert depth_steps(1, 255) == 4
    assert depth_steps(13, 255) == 16
    assert depth_steps(16, 255) == 16
    assert depth_steps(17, 255) == 20
    assert depth_steps(999, 255) == 254
    assert depth_steps(None, 255) == 254


# ---------------------------------------------------------------------------
# parity matrix: leaf-identical across missing types and adversarial values
# ---------------------------------------------------------------------------

# one fast representative (nan: the adversarial missing type); the
# other two cells behind -m slow (predict_smoke.py gates all three
# missing types every check.sh run)
@pytest.mark.parametrize("missing", [
    pytest.param("none", marks=pytest.mark.slow),
    pytest.param("zero", marks=pytest.mark.slow), "nan"])
def test_leaf_parity_matrix_binned_and_raw(rng, missing):
    """Bit-identical per-tree LEAF INDICES between the host walk, the
    device binned route (device binning + forest_leaf_bins) and the raw
    route (tree_leaf_raw over f32_floor thresholds), with NaN, zeros and
    +-inf in the request batch."""
    import jax.numpy as jnp
    bst, X = _train(rng, missing=missing, n_round=6)
    eng = bst._engine
    Xq = _adversarial(rng, X)
    L = eng.config.num_leaves
    mappers = eng.train_set.used_bin_mappers()
    binner = DeviceBinner(mappers, eng.train_set.used_feature_map)
    bins_dev = binner.bins(Xq)
    meta = _engine_meta(eng)
    pack = forest_mod.ForestPack(L)
    pack.sync(eng.models, gen=0, mappers=mappers)
    for i, t in enumerate(eng.models):
        host_leaf = t.predict_leaf(Xq)
        arrs = host_tree_to_arrays(t, L)
        # generic binned body over DEVICE-computed bins
        dev_generic = tree_leaf_bins(arrs, bins_dev, meta.num_bin,
                                     meta.missing_type, meta.default_bin)
        np.testing.assert_array_equal(host_leaf, np.asarray(dev_generic))
        # serving body (special/flip folded at pack time)
        import jax
        p = jax.tree.map(lambda x: x[i], pack.stacked)
        dev_serving = forest_leaf_bins(
            p.tree, p.special, p.flip, bins_dev,
            num_steps=depth_steps(t.max_depth, L))
        np.testing.assert_array_equal(host_leaf, np.asarray(dev_serving))
        # raw route (per-node missing from decision_type)
        raw = _host_tree_to_raw(t, L)
        dev_raw = tree_leaf_raw(raw, jnp.asarray(Xq, jnp.float32))
        np.testing.assert_array_equal(host_leaf, np.asarray(dev_raw))


def test_f64_only_requests_never_misroute(rng):
    """A request value one f64-ulp above a bin bound rounds BELOW it in
    f32 (the observed sklearn flake): the binned route must re-bin such
    columns with the host mapper, the raw route must refuse and fall
    back — device and host predictions stay identical either way."""
    bst, X = _train(rng, n_round=5)
    eng = bst._engine
    m = eng.train_set.used_bin_mappers()[0]
    b = float(m.bin_upper_bound[len(m.bin_upper_bound) // 2])
    Xq = X.copy()
    Xq[:, 0] = np.nextafter(b, np.inf)           # f64-only, straddles in f32
    assert np.float32(Xq[0, 0]).astype(np.float64) != Xq[0, 0]
    host = bst.predict(Xq, raw_score=True)
    dev = bst.predict(Xq, device=True, raw_score=True)
    np.testing.assert_allclose(dev, host, rtol=1e-5, atol=1e-6)
    # per-tree leaf parity (bit-identical) through the serving engine
    mappers = eng.train_set.used_bin_mappers()
    binner = DeviceBinner(mappers, eng.train_set.used_feature_map)
    bins_dev = np.asarray(binner.bins(Xq))
    for i, (fi, mp) in enumerate(zip(eng.train_set.used_feature_map,
                                     mappers)):
        np.testing.assert_array_equal(
            bins_dev[i], mp.value_to_bin(np.asarray(Xq[:, fi])))
    # raw route refuses f64-only values -> loaded booster host fallback
    loaded = lgb.Booster(model_str=bst.model_to_string())
    np.testing.assert_array_equal(loaded.predict(Xq, device=True),
                                  loaded.predict(Xq))


def test_f32_floor_exact_boundary():
    v = np.asarray([1.0, 1.0 + 1e-12, -1.0 - 1e-12, np.inf, -np.inf,
                    1e300, -1e300, 0.0])
    out = f32_floor(v)
    assert out.dtype == np.float32
    # the defining property: f64(out) <= v, and the next f32 up is > v
    ok = np.isfinite(v)
    assert (out[ok].astype(np.float64) <= v[ok]).all()
    nxt = np.nextafter(out[ok], np.float32(np.inf))
    assert (nxt.astype(np.float64) > v[ok]).all()
    assert out[3] == np.inf and out[4] == -np.inf


@pytest.mark.slow
def test_device_binning_matches_host_mapper(rng):
    bst, X = _train(rng, missing="nan", cat=True, n_round=3)
    eng = bst._engine
    Xq = _adversarial(rng, X)
    mappers = eng.train_set.used_bin_mappers()
    used = eng.train_set.used_feature_map
    binner = DeviceBinner(mappers, used)
    dev = np.asarray(binner.bins(Xq))
    for i, (fi, m) in enumerate(zip(used, mappers)):
        host = m.value_to_bin(np.asarray(Xq[:, fi], np.float64))
        np.testing.assert_array_equal(dev[i], host, err_msg=f"feature {fi}")


# ---------------------------------------------------------------------------
# stale cache (satellite 1) + generation counter
# ---------------------------------------------------------------------------

def test_stale_cache_after_rollback_and_retrain(rng):
    """THE regression: predict(device) -> rollback_one_iter -> retrain
    back to the SAME model count with different gradients. A cache keyed
    only on (window, len(models)) serves the pre-rollback forest; the
    generation counter must not."""
    X = rng.normal(size=(400, 5))
    y = X[:, 0] * 2 + rng.normal(scale=0.1, size=400)
    ds = lgb.Dataset(X, label=y)
    bst = lgb.Booster({"objective": "regression", "num_leaves": 15,
                       "verbose": -1, "min_data_in_leaf": 5}, ds)
    for _ in range(3):
        bst.update()
    before = bst.predict(X, device=True)
    bst.rollback_one_iter()

    def fobj(preds, _):
        grad = np.asarray(preds - y * 3.0, np.float32)  # NOT the mse grad
        return grad, np.ones_like(grad)

    bst.update(fobj=fobj)
    assert bst.current_iteration() == 3          # same count as before
    host = bst.predict(X)
    dev = bst.predict(X, device=True)
    np.testing.assert_allclose(dev, host, rtol=1e-5, atol=1e-6)
    assert np.abs(dev - before).max() > 1e-4, \
        "retrained tree is indistinguishable — the regression cannot bite"


def test_model_generation_counter_semantics(rng):
    bst, X = _train(rng, n_round=3)
    eng = bst._engine
    g0 = eng._model_gen
    eng.models.append(eng.models[0].copy())      # tail append: NO bump
    assert eng._model_gen == g0
    del eng.models[-1:]                          # destructive: bump
    assert eng._model_gen > g0
    g1 = eng._model_gen
    eng.models[0] = eng.models[0].copy()         # replacement: bump
    assert eng._model_gen > g1
    g2 = eng._model_gen
    eng.invalidate_serving_cache()               # in-place content edit
    assert eng._model_gen > g2
    g3 = eng._model_gen
    eng.models = list(eng.models)                # wholesale assignment
    assert eng._model_gen > g3


def test_incremental_pack_appends_only_new_trees(rng, monkeypatch):
    bst, X = _train(rng, n_round=3)
    eng = bst._engine
    calls = []
    orig = forest_mod.ForestPack._pack_tree

    def spy(self, t):
        calls.append(t)
        return orig(self, t)

    monkeypatch.setattr(forest_mod.ForestPack, "_pack_tree", spy)
    bst.predict(X, device=True)
    assert len(calls) == 3
    pack = eng._serving.pack
    assert pack.count == 3
    gen_after_first = pack.gen
    for _ in range(2):
        bst.update()                             # appends, no gen bump
    bst.predict(X, device=True)
    assert len(calls) == 5, "window growth restacked the whole forest"
    assert pack.count == 5 and pack.gen == gen_after_first
    # narrower window: same pack, sliced — no new tree packing
    bst.predict(X, device=True, num_iteration=2)
    assert len(calls) == 5


# ---------------------------------------------------------------------------
# batch bucketing + compile budget
# ---------------------------------------------------------------------------

def test_bucket_rows_properties():
    sizes = list(range(1, 20001, 7))
    buckets = {bucket_rows(r) for r in sizes}
    assert all(bucket_rows(r) >= r for r in sizes)
    assert len(buckets) < 30
    for r in sizes:
        if r > 4096:
            assert bucket_rows(r) / r <= 1.15
    # idempotent: a bucket maps to itself
    for b in buckets:
        assert bucket_rows(b) == b


def test_mixed_size_predict_compile_budget(rng):
    """Steady state: after warming the (few) buckets, 5 mixed-size
    predict calls must not trace a single new program."""
    bst, X = _train(rng, n_round=4)
    for warm in (500, 140):                      # buckets 512 and 256
        bst.predict(X[:warm], device=True)
    with guards.CompileCounter() as counter:
        for r in (500, 400, 300, 140, 450):
            bst.predict(X[:r], device=True)
    assert counter.count == 0, counter.names


def test_bucketing_off_exact_shapes(rng):
    bst, X = _train(rng, n_round=2, tpu_predict_buckets=False)
    host = bst.predict(X[:123])
    dev = bst.predict(X[:123], device=True)
    np.testing.assert_allclose(dev, host, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# raw route: loaded model without mappers (satellite 2)
# ---------------------------------------------------------------------------

def test_loaded_model_serves_on_device(rng):
    bst, X = _train(rng, missing="nan", n_round=5)
    Xq = _adversarial(rng, X)
    loaded = lgb.Booster(model_str=bst.model_to_string())
    host = loaded.predict(Xq, raw_score=True)
    dev = loaded.predict(Xq, device=True, raw_score=True)
    np.testing.assert_allclose(dev, host, rtol=1e-5, atol=1e-6)
    # the device path actually ran (no silent host fallback)
    eng = loaded._engine
    assert eng._serving is not None
    assert eng._serving.raw_pack.count == len(eng.models)


def test_loaded_categorical_model_falls_back_to_host(rng):
    n = 600
    X = rng.normal(size=(n, 4))
    X[:, 3] = rng.integers(0, 6, size=n)
    y = (X[:, 3] % 2) * 3.0 + X[:, 0]            # cat splits are learned
    bst = lgb.train({"objective": "regression", "num_leaves": 15,
                     "verbose": -1, "min_data_in_leaf": 5},
                    lgb.Dataset(X, label=y, categorical_feature=[3]),
                    num_boost_round=4)
    assert any(t.num_cat > 0 for t in bst._engine.models)
    loaded = lgb.Booster(model_str=bst.model_to_string())
    host = loaded.predict(X)
    dev = loaded.predict(X, device=True)         # warns, host path
    np.testing.assert_array_equal(dev, host)
    assert loaded._engine._serving is None       # raw route refused


def test_raw_servability_is_window_scoped(rng):
    """One categorical tree OUTSIDE the requested window must not defeat
    device serving for a servable window (packing is tolerant; the
    servability check applies to the window, not the whole list)."""
    import pytest as _pytest
    n = 500
    Xc = rng.normal(size=(n, 4))
    Xc[:, 3] = rng.integers(0, 6, size=n)
    bst_cat = lgb.train({"objective": "regression", "num_leaves": 15,
                         "verbose": -1, "min_data_in_leaf": 5},
                        lgb.Dataset(Xc, label=(Xc[:, 3] % 2) * 3.0,
                                    categorical_feature=[3]),
                        num_boost_round=1)
    bst_num, X = _train(rng, n_round=1)
    cat_tree = bst_cat._engine.models[0]
    num_tree = bst_num._engine.models[0]
    assert cat_tree.num_cat > 0
    srv = forest_mod.ServingEngine(31, 1)
    with _pytest.raises(ValueError):
        srv.predict_raw([cat_tree, num_tree], 0, X, 0, 2)
    out = srv.predict_raw([cat_tree, num_tree], 0, X, 1, 2)
    np.testing.assert_allclose(out[0], num_tree.predict(X),
                               rtol=1e-5, atol=1e-6)


def test_loaded_model_set_leaf_output_invalidates(rng):
    bst, X = _train(rng, n_round=3)
    loaded = lgb.Booster(model_str=bst.model_to_string())
    before = loaded.predict(X, device=True, raw_score=True)
    loaded.set_leaf_output(0, 0, loaded.get_leaf_output(0, 0) + 7.0)
    after = loaded.predict(X, device=True, raw_score=True)
    host = loaded.predict(X, raw_score=True)
    np.testing.assert_allclose(after, host, rtol=1e-5, atol=1e-6)
    assert np.abs(after - before).max() > 1.0


# ---------------------------------------------------------------------------
# sklearn passthrough (satellite 6)
# ---------------------------------------------------------------------------

def test_sklearn_device_passthrough(rng):
    X = rng.normal(size=(400, 5))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(int)
    clf = lgb.LGBMClassifier(n_estimators=6, num_leaves=15, verbose=-1,
                             min_child_samples=5)
    clf.fit(X, y)
    proba_host = clf.predict_proba(X)
    proba_dev = clf.predict_proba(X, device=True)
    # f32 raw-margin accumulation passes through the sigmoid: tolerance
    # is on the margin, not the leaf decisions (leaf parity is exact)
    np.testing.assert_allclose(proba_dev, proba_host, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(clf.predict(X, device=True),
                                  clf.predict(X))
    reg = lgb.LGBMRegressor(n_estimators=6, num_leaves=15, verbose=-1,
                            min_child_samples=5)
    reg.fit(X, X[:, 0])
    np.testing.assert_allclose(reg.predict(X, device=True), reg.predict(X),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# multiclass window arithmetic through the packed engine
# ---------------------------------------------------------------------------

def test_multiclass_windows_and_iteration_ranges(rng):
    n = 500
    X = rng.normal(size=(n, 6))
    y = (np.abs(X[:, 0]) + np.abs(X[:, 1]) * 2).astype(int) % 3
    bst = lgb.train({"objective": "multiclass", "num_class": 3,
                     "num_leaves": 15, "verbose": -1,
                     "min_data_in_leaf": 5},
                    lgb.Dataset(X, label=y), num_boost_round=6)
    for kw in ({}, {"num_iteration": 3},
               {"start_iteration": 2, "num_iteration": 3}):
        host = bst.predict(X, **kw)
        dev = bst.predict(X, device=True, **kw)
        np.testing.assert_allclose(dev, host, rtol=1e-5, atol=1e-6)
