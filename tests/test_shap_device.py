"""Device-side TreeSHAP through the packed path tensors (ISSUE 20):
the parity matrix vs the f64 host ``predict_contrib`` walk
(missing-route x multiclass x raw-route loaded models x iteration
windows, on the missing-value adversarial request batch), per-row
additivity, incremental-append ≡ full-repack bit identity, the
steady-state trace budget over mixed request sizes, SHAP-pack
eviction/rebuild bit identity in the fleet, and the eligibility
regression (linear / categorical models answer by the host walk,
loudly once)."""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.analysis import guards
from lightgbm_tpu.core.shap import (_decisions_all, predict_contrib,
                                    shap_tree_batch)
from lightgbm_tpu.ops import forest, shap_pack

from test_packed_forest import _adversarial, _train

RTOL, ATOL = 1e-4, 1e-5      # f32 EXTEND/UNWIND vs the f64 host walk


def _host_ref(bst, X, start=0, num=None):
    eng = bst._engine
    K = eng.num_tree_per_iteration
    n_iter = len(eng.models) // max(K, 1)
    end = n_iter if num is None else min(start + num, n_iter)
    return predict_contrib(eng, X, start, end)


# ---------------------------------------------------------------------------
# parity matrix: missing routes x adversarial requests
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("missing", ["none", "zero", "nan"])
def test_parity_missing_routes_adversarial(rng, missing):
    """Each missing route's trained model, explained on the NaN / 0 /
    +-inf / kZeroThreshold adversarial batch: within f32-accumulation
    tolerance of the f64 host walk, and additive per row."""
    bst, X = _train(rng, missing=missing)
    Xq = _adversarial(rng, X[:96])
    dev = bst.predict(Xq, pred_contrib=True, device=True)
    host = _host_ref(bst, Xq)
    np.testing.assert_allclose(dev, host, rtol=RTOL, atol=ATOL)
    raw = bst.predict(Xq, raw_score=True)
    np.testing.assert_allclose(dev.sum(axis=1), raw, rtol=RTOL,
                               atol=ATOL)


def test_parity_multiclass_blocks(rng):
    """K>1: per-class blocks of F+1 (bias last), each block anchored
    against the host walk and additive against that class's raw
    score."""
    X = rng.normal(size=(500, 6)).astype(np.float32).astype(np.float64)
    y = (np.abs(X[:, 0]) * 1.5).astype(int) % 3
    bst = lgb.train({"objective": "multiclass", "num_class": 3,
                     "num_leaves": 15, "verbose": -1,
                     "min_data_in_leaf": 5},
                    lgb.Dataset(X, label=y), num_boost_round=5)
    Xq = _adversarial(rng, X[:64])
    dev = np.asarray(bst.predict(Xq, pred_contrib=True, device=True))
    host = np.asarray(_host_ref(bst, Xq)).reshape(dev.shape)
    np.testing.assert_allclose(dev, host, rtol=RTOL, atol=ATOL)
    raw = bst.predict(Xq, raw_score=True)
    phi = dev.reshape(len(Xq), 3, -1)
    np.testing.assert_allclose(phi.sum(axis=2), raw, rtol=RTOL,
                               atol=ATOL)


def test_parity_raw_route_loaded_model(rng):
    """A model round-tripped through text has no bin mappers: the raw
    path pack serves (f32_floor thresholds, decision_type missing
    routes) and must agree with the host walk on the adversarial
    batch."""
    bst, X = _train(rng, missing="nan")
    loaded = lgb.Booster(model_str=bst.model_to_string())
    Xq = _adversarial(rng, X[:96])
    dev = loaded.predict(Xq, pred_contrib=True, device=True)
    host = _host_ref(loaded, Xq)
    np.testing.assert_allclose(dev, host, rtol=RTOL, atol=ATOL)
    # the raw SHAP pack actually served (no silent host fallback)
    srv = loaded._engine._serving
    assert srv is not None and srv.raw_shap_pack is not None
    assert srv.raw_shap_pack.count == len(loaded._engine.models)


@pytest.mark.parametrize("start,num", [(0, 3), (2, 4), (5, 3)])
def test_parity_iteration_windows(rng, start, num):
    """start_iteration / num_iteration windows slice the packed window
    exactly like the host walk slices its tree loop."""
    bst, X = _train(rng, n_round=8)
    Xq = X[:80]
    dev = bst.predict(Xq, pred_contrib=True, device=True,
                      start_iteration=start, num_iteration=num)
    host = _host_ref(bst, Xq, start, num)
    np.testing.assert_allclose(dev, host, rtol=RTOL, atol=ATOL)


def test_additivity_f32_exact(rng):
    """phi.sum(axis=1) (bias included) reproduces the raw score to f32
    exactness per row — the TreeSHAP conservation law, on the device
    accumulation order."""
    bst, X = _train(rng, n_round=10)
    Xq = _adversarial(rng, X[:128])
    dev = np.asarray(bst.predict(Xq, pred_contrib=True, device=True))
    raw = bst.predict(Xq, raw_score=True)
    np.testing.assert_allclose(dev.sum(axis=1), raw, rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# incremental append == full repack, bit for bit
# ---------------------------------------------------------------------------

def test_incremental_append_matches_full_repack_bits(rng):
    """Growing the SHAP pack incrementally across update() generations
    must produce bit-identical windows to packing the final model from
    scratch — the serving tier hot-swaps on this invariant."""
    X = rng.normal(size=(600, 6)).astype(np.float32).astype(np.float64)
    y = X[:, 0] + 0.5 * X[:, 1]
    bst = lgb.train({"objective": "regression", "num_leaves": 31,
                     "verbose": -1, "min_data_in_leaf": 5},
                    lgb.Dataset(X, label=y), num_boost_round=4,
                    keep_training_booster=True)
    eng = bst._engine
    Xq = X[:64]
    outs = [bst.predict(Xq, pred_contrib=True, device=True)]
    for _ in range(3):
        bst.update()
        outs.append(bst.predict(Xq, pred_contrib=True, device=True))
    # incremental pack state after 3 appends
    inc_pack = eng._serving.shap_pack
    assert inc_pack.count == len(eng.models)
    inc_win, _ = inc_pack.window(0, inc_pack.count)
    # fresh engine: full repack of the same final model
    fresh = forest.ServingEngine(eng.config.num_leaves,
                                 eng.num_tree_per_iteration)
    snap = fresh.snapshot_shap(
        eng.models, 0, 0, len(eng.models), eng.max_feature_idx + 1,
        eng.train_set.used_bin_mappers(),
        eng.train_set.used_feature_map)
    full_win, _ = fresh.shap_pack.window(0, fresh.shap_pack.count)
    for a, b in zip(inc_win, full_win):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the served contributions replay bit-identically
    again = bst.predict(Xq, pred_contrib=True, device=True)
    np.testing.assert_array_equal(np.asarray(outs[-1]),
                                  np.asarray(again))


# ---------------------------------------------------------------------------
# steady-state trace budget over mixed request sizes
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_trace_budget_mixed_request_sizes(rng):
    """After warming the row-bucket family, explain requests of mixed
    sizes compile at most 2 new programs (the pow2/octave bucket rule —
    the same budget the score route honors)."""
    bst, X = _train(rng, n_round=6)
    for warm in (32, 64, 128, 256, 512):
        bst.predict(X[:warm], pred_contrib=True, device=True)
    with guards.CompileCounter() as counter:
        for n in (32, 48, 96, 200, 256, 500, 130, 70):
            bst.predict(X[:n], pred_contrib=True, device=True)
    assert counter.count <= 2, (counter.count, counter.names)


# ---------------------------------------------------------------------------
# fleet: SHAP-pack eviction / rebuild bit identity
# ---------------------------------------------------------------------------

def test_fleet_shap_eviction_rebuild_bit_identity(rng):
    """Evicting a resident SHAP mega-pack (HBM budget pressure) and
    lazily rebuilding it on the next explain must reproduce the SAME
    bits; the eviction/rebuild events land in the counters."""
    X = rng.normal(size=(700, 6)).astype(np.float32).astype(np.float64)
    y = X[:, 0] + 0.5 * X[:, 1]
    bst = lgb.train({"objective": "regression", "num_leaves": 15,
                     "verbose": -1, "min_data_in_leaf": 5},
                    lgb.Dataset(X, label=y), num_boost_round=5,
                    keep_training_booster=True)
    with lgb.serve_fleet({"t0": bst}, linger_ms=2.0) as fleet:
        before = fleet.explain("t0", X[:40])
        with fleet._publish_lock:
            freed = fleet._evict_shap(1 << 60)
        assert freed > 0
        assert all(sb.dev is None for sb in fleet._shap_cache.values())
        after = fleet.explain("t0", X[:40])
        np.testing.assert_array_equal(before, after)
        assert fleet.counters.get("evictions") >= 1
        assert fleet.counters.get("rebuilds") >= 1
        assert fleet.stats()["resident_shap_bytes"] > 0


# ---------------------------------------------------------------------------
# eligibility: linear / categorical models answer by the host walk
# ---------------------------------------------------------------------------

def _cat_model(rng):
    """A model that ACTUALLY splits on its categorical feature (the
    label depends on it — ``_train(cat=True)``'s label does not, which
    trains a fully numerical forest that never falls back)."""
    X = rng.normal(size=(600, 6)).astype(np.float32).astype(np.float64)
    X[:, 5] = rng.integers(0, 8, size=600)
    y = (X[:, 5] % 3) * 2.0 + 0.1 * X[:, 0]
    bst = lgb.train({"objective": "regression", "num_leaves": 31,
                     "verbose": -1, "min_data_in_leaf": 5},
                    lgb.Dataset(X, label=y, categorical_feature=[5]),
                    num_boost_round=8)
    assert any(t.num_cat > 0 for t in bst._engine.models)
    return bst, X


def test_categorical_model_falls_back_to_host(rng, caplog):
    """Categorical splits are not device-explainable: check_explainable
    refuses, the Booster answers the host walk BIT-identically, and the
    SHAP pack is never built."""
    bst, X = _cat_model(rng)
    with pytest.raises(ValueError, match="categorical"):
        shap_pack.check_explainable(bst._engine.models)
    dev = bst.predict(X[:50], pred_contrib=True, device=True)
    host = _host_ref(bst, X[:50])
    np.testing.assert_array_equal(dev, host)
    srv = bst._engine._serving
    assert srv is None or srv.shap_pack is None


def test_deep_trees_are_refused():
    """The f32 unwind is accurate to MAX_SHAP_DEPTH; one deeper tree
    sends the whole model to the host walk instead of wrong numbers."""
    import types
    tree = types.SimpleNamespace(is_linear=False, num_cat=0,
                                 num_leaves=255,
                                 max_depth=shap_pack.MAX_SHAP_DEPTH)
    shap_pack.check_explainable([tree, tree])
    deep = types.SimpleNamespace(**{**vars(tree),
                                    "max_depth": tree.max_depth + 1})
    with pytest.raises(ValueError, match="depth"):
        shap_pack.check_explainable([tree, deep])


def test_linear_model_falls_back_to_host(rng):
    X = rng.normal(size=(400, 5)).astype(np.float32).astype(np.float64)
    y = X[:, 0] * 2.0 + X[:, 1]
    bst = lgb.train({"objective": "regression", "num_leaves": 7,
                     "verbose": -1, "linear_tree": True,
                     "min_data_in_leaf": 20},
                    lgb.Dataset(X, label=y), num_boost_round=3)
    with pytest.raises(ValueError, match="linear"):
        shap_pack.check_explainable(bst._engine.models)
    dev = bst.predict(X[:30], pred_contrib=True, device=True)
    host = _host_ref(bst, X[:30])
    np.testing.assert_array_equal(dev, host)


def test_host_fallback_logs_once(rng):
    """The ineligibility notice is INFO and fires ONCE per message —
    serving loops must not drown in per-call fallback spam."""
    from lightgbm_tpu.utils import log as _log
    bst, X = _cat_model(rng)
    _log.logged_once -= {m for m in _log.logged_once
                         if "device explanation unavailable" in m}
    got = []
    _log.register_logger(got.append)
    prev_level = _log._level
    _log.set_verbosity(_log.INFO)
    try:
        bst.predict(X[:10], pred_contrib=True, device=True)
        bst.predict(X[:10], pred_contrib=True, device=True)
        bst.predict(X[:10], pred_contrib=True, device=True)
    finally:
        _log.register_logger(None)
        _log.set_verbosity(prev_level)
    hits = [m for m in got if "device explanation unavailable" in m]
    assert len(hits) == 1, hits
    assert "[Info]" in hits[0]


# ---------------------------------------------------------------------------
# host-path decision precompute (the satellite fix)
# ---------------------------------------------------------------------------

def test_predict_contrib_decisions_precompute_bits(rng):
    """Passing precomputed _decisions_all matrices must not change a
    single bit of the numpy host walk (chunk slicing included)."""
    bst, X = _train(rng, missing="nan", n_round=5)
    eng = bst._engine
    Xq = _adversarial(rng, X[:200])
    dec = {i: _decisions_all(t, Xq) for i, t in enumerate(eng.models)}
    a = predict_contrib(eng, Xq, 0, 5)
    b = predict_contrib(eng, Xq, 0, 5, decisions=dec)
    c = predict_contrib(eng, Xq, 0, 5, row_chunk=64, decisions=dec)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, c)


def test_shap_tree_batch_goes_left_param(rng):
    bst, X = _train(rng, n_round=2)
    t = bst._engine.models[0]
    Xq = X[:50]
    gl = _decisions_all(t, Xq)
    a = shap_tree_batch(t, Xq, 6)
    b = shap_tree_batch(t, Xq, 6, goes_left=gl)
    np.testing.assert_array_equal(a, b)
