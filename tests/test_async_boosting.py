"""Async boosting fast path (tpu_async_boosting) vs the synchronous path.

The async path keeps grown trees on device and defers HostTree
materialization (models/gbdt.py _train_one_iter_async). It must produce
the same ensemble as the sync path BIT-FOR-BIT: both paths accumulate
the identical f32 leaf product through the same jitted delta/traversal
dispatches (gbdt.py _leaf_delta — the product rounds separately from
the accumulate so FMA fusion cannot introduce a half-ulp skew), and
stop conditions must be detected exactly despite the batched check.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb


def _data(n=2000, f=10, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] +
         0.1 * rng.normal(size=n) > 0).astype(np.float32)
    return X, y


def _train_pair(extra, n_round=30, n=2000, seed=0):
    X, y = _data(n=n, seed=seed)
    base = dict(objective="binary", num_leaves=15, learning_rate=0.1,
                verbose=-1, seed=7, **extra)
    m_sync = lgb.train(dict(base, tpu_async_boosting="false"),
                       lgb.Dataset(X, label=y), num_boost_round=n_round)
    m_async = lgb.train(dict(base, tpu_async_boosting="true"),
                        lgb.Dataset(X, label=y), num_boost_round=n_round)
    return X, m_sync, m_async


def _structure(model):
    """Split structure only (feature, threshold, counts) — excludes the
    f32-rounding-sensitive value fields."""
    out = []
    for t in model._engine.models:
        out.append((t.num_leaves, t.split_feature.tolist(),
                    t.threshold_bin.tolist(), t.leaf_count.tolist()))
    return out


def test_async_matches_sync_plain():
    X, m_sync, m_async = _train_pair({})
    assert _structure(m_sync) == _structure(m_async)
    np.testing.assert_allclose(m_sync.predict(X), m_async.predict(X),
                               atol=1e-5)


def test_async_matches_sync_bagging_feature_fraction():
    X, m_sync, m_async = _train_pair(dict(
        bagging_fraction=0.8, bagging_freq=1, feature_fraction=0.9))
    assert _structure(m_sync) == _structure(m_async)
    np.testing.assert_allclose(m_sync.predict(X), m_async.predict(X),
                               atol=1e-5)


def test_async_matches_sync_multiclass():
    rng = np.random.default_rng(3)
    X = rng.normal(size=(1500, 8)).astype(np.float32)
    y = (np.digitize(X[:, 0] + 0.3 * X[:, 1],
                     [-0.5, 0.5])).astype(np.float32)
    base = dict(objective="multiclass", num_class=3, num_leaves=7,
                learning_rate=0.1, verbose=-1)
    m_sync = lgb.train(dict(base, tpu_async_boosting="false"),
                       lgb.Dataset(X, label=y), num_boost_round=10)
    m_async = lgb.train(dict(base, tpu_async_boosting="true"),
                        lgb.Dataset(X, label=y), num_boost_round=10)
    assert _structure(m_sync) == _structure(m_async)
    np.testing.assert_allclose(m_sync.predict(X), m_async.predict(X),
                               atol=1e-5)


def test_async_valid_set_eval_matches():
    X, y = _data()
    Xv, yv = _data(n=600, seed=1)
    evals = {}
    base = dict(objective="binary", num_leaves=15, learning_rate=0.1,
                verbose=-1)
    r = {}
    for mode in ("false", "true"):
        ds = lgb.Dataset(X, label=y)
        rec = {}
        lgb.train(dict(base, tpu_async_boosting=mode), ds,
                  num_boost_round=15,
                  valid_sets=[lgb.Dataset(Xv, label=yv, reference=ds)],
                  valid_names=["v"],
                  callbacks=[lgb.record_evaluation(rec)])
        r[mode] = rec["v"]["binary_logloss"]
    np.testing.assert_allclose(r["false"], r["true"], atol=1e-5)


def test_async_stop_detection_exact():
    """Training that runs out of splits mid-window must stop with the
    same model as the sync path (rollback + sync replay)."""
    rng = np.random.default_rng(5)
    # tiny discrete dataset: only a handful of distinct split points, so
    # boosting exhausts valid splits quickly (min_gain filters the rest)
    X = rng.integers(0, 3, size=(200, 3)).astype(np.float32)
    y = (X[:, 0] > 1).astype(np.float32)
    base = dict(objective="binary", num_leaves=4, learning_rate=0.5,
                min_data_in_leaf=5, min_gain_to_split=1e-3, verbose=-1,
                tpu_stop_check_interval=7)
    m_sync = lgb.train(dict(base, tpu_async_boosting="false"),
                       lgb.Dataset(X, label=y), num_boost_round=60)
    m_async = lgb.train(dict(base, tpu_async_boosting="true"),
                        lgb.Dataset(X, label=y), num_boost_round=60)
    assert m_sync.num_trees() == m_async.num_trees()
    assert _structure(m_sync) == _structure(m_async)
    np.testing.assert_allclose(m_sync.predict(X), m_async.predict(X),
                               atol=1e-5)


def test_async_stop_detected_via_flush():
    """A consumer flushing models between periodic checks must not let
    degenerate iterations slip through as constant trees."""
    rng = np.random.default_rng(5)
    X = rng.integers(0, 3, size=(200, 3)).astype(np.float32)
    y = (X[:, 0] > 1).astype(np.float32)
    base = dict(objective="binary", num_leaves=4, learning_rate=0.5,
                min_data_in_leaf=5, min_gain_to_split=1e-3, verbose=-1,
                tpu_stop_check_interval=1000)   # never checks periodically
    counts = {}
    for mode in ("false", "true"):
        b = lgb.Booster(dict(base, tpu_async_boosting=mode),
                        lgb.Dataset(X, label=y))
        for _ in range(60):
            b.update()
            n = b.num_trees()        # flushes pending every iteration
        counts[mode] = n
    assert counts["true"] == counts["false"]


def test_async_first_iteration_degenerate_terminal_flush():
    """No valid split at iteration 0 + the flush happening only AFTER
    training (predict/save) must still keep the sync path's
    boost-from-average constant tree."""
    rng = np.random.default_rng(2)
    X = rng.normal(size=(400, 4)).astype(np.float32)
    y = (rng.uniform(size=400) < 0.75).astype(np.float32)
    base = dict(objective="binary", num_leaves=4, verbose=-1,
                min_gain_to_split=1e6)     # nothing can split
    out = {}
    for mode in ("false", "true"):
        b = lgb.train(dict(base, tpu_async_boosting=mode),
                      lgb.Dataset(X, label=y), num_boost_round=10)
        out[mode] = (b.num_trees(), float(b.predict(X[:1])[0]))
    assert out["true"] == out["false"]
    assert abs(out["false"][1] - 0.75) < 0.05   # base rate, not 0.5


@pytest.mark.slow
def test_async_randomized_config_sweep():
    """Property sweep: random hyperparameter combinations must produce
    equivalent models in async and sync modes. Exact threshold-bin
    equality is NOT asserted: the async path's f32 device score update
    (vs the sync path's f64 host shrink) can flip gain TIES between
    adjacent thresholds over empty bins — observed as e.g. threshold 80
    vs 81 with identical row partitions. The invariants that must hold:
    same split features, same leaf row counts, same predictions to f32
    noise."""
    rng = np.random.default_rng(123)
    X, y = _data(n=1500, f=8)
    for trial in range(8):
        params = dict(
            objective="binary", verbose=-1,
            num_leaves=int(rng.integers(4, 32)),
            learning_rate=float(rng.uniform(0.05, 0.5)),
            min_data_in_leaf=int(rng.integers(5, 60)),
            feature_fraction=float(rng.uniform(0.6, 1.0)),
            bagging_fraction=float(rng.uniform(0.6, 1.0)),
            bagging_freq=int(rng.integers(0, 3)),
            lambda_l1=float(rng.choice([0.0, 0.5])),
            lambda_l2=float(rng.choice([0.0, 2.0])),
            min_gain_to_split=float(rng.choice([0.0, 1e-3])),
            tpu_stop_check_interval=int(rng.integers(3, 20)),
            seed=int(rng.integers(0, 1000)),
        )
        if trial == 6:       # quantized int8 path through async
            params.update(use_quantized_grad=True,
                          stochastic_rounding=False,
                          quant_train_renew_leaf=False)
        if trial == 7:       # per-node column sampling through async
            params.update(feature_fraction_bynode=0.7)
        out = {}
        for mode in ("false", "true"):
            b = lgb.train(dict(params, tpu_async_boosting=mode),
                          lgb.Dataset(X, label=y), num_boost_round=10)
            out[mode] = (
                [(t.num_leaves, t.split_feature.tolist(),
                  t.leaf_count.tolist())
                 for t in b._engine.models],
                b.predict(X))
        assert out["true"][0] == out["false"][0], (trial, params)
        np.testing.assert_allclose(out["true"][1], out["false"][1],
                                   atol=1e-4, err_msg=str((trial, params)))


def test_async_model_io_roundtrip():
    X, _, m_async = _train_pair({}, n_round=12)
    s = m_async.model_to_string()
    m2 = lgb.Booster(model_str=s)
    np.testing.assert_allclose(m_async.predict(X), m2.predict(X),
                               atol=1e-6)


@pytest.mark.slow
def test_async_fallback_features_use_sync():
    """Features requiring per-iteration host work silently fall back
    (DART runs on the async path: tests/test_dart.py)."""
    X, y = _data()
    for extra in (dict(linear_tree=True),):
        params = dict(objective="binary", num_leaves=7, verbose=-1,
                      tpu_async_boosting="true", **extra)
        b = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=25)
        assert b.num_trees() > 0
        eng = b._engine
        assert not eng._pending  # nothing left on device


@pytest.mark.slow
def test_async_goss_device_sampling():
    """GOSS stays on the async path via the device sampler (stateless
    jax keys — a valid GOSS draw, not bit-identical to the host RNG).
    The model must train to a comparable fit."""
    X, y = _data(n=4000)
    params = dict(objective="binary", num_leaves=15, learning_rate=0.2,
                  data_sample_strategy="goss", top_rate=0.2,
                  other_rate=0.2, verbose=-1)
    fits = {}
    for mode in ("false", "true"):
        b = lgb.train(dict(params, tpu_async_boosting=mode),
                      lgb.Dataset(X, label=y), num_boost_round=30)
        assert b.num_trees() == 30
        p = b.predict(X)
        fits[mode] = float(np.mean((p > 0.5) == (y > 0)))
    assert fits["true"] > 0.9 and fits["false"] > 0.9
    # async mode really did stay async (engine flag resolved true)
    # (re-train to inspect, since predict flushed the first one)
    ds = lgb.Dataset(X, label=y)
    b2 = lgb.Booster(dict(params, tpu_async_boosting="true"), ds)
    for _ in range(12):
        b2.update()
    assert b2._engine._async_mode is True
    assert b2._engine._pending          # trees still on device


import pytest


_SHARD_HIST_XFAIL = pytest.mark.xfail(
    strict=False,
    reason="serial vs sharded f32 histogram accumulation order: the "
           "data/voting learners psum 8 per-shard histograms while the "
           "serial grower sums all rows in one kernel; the reassociated "
           "f32 sums differ by ulps and can flip near-tie splits on "
           "this image's XLA CPU backend (pre-existing at the seed "
           "commit; root-caused in PR 2 — the FMA/shrink channels were "
           "fixed there, this reassociation channel is inherent to f32 "
           "sharded reduction; bit-exactness across worker counts is "
           "only promised for the int32 quantized-histogram path, see "
           "test_quantized.py and test_injected_collectives.py)")


@pytest.mark.parametrize("learner", [
    pytest.param("data", marks=_SHARD_HIST_XFAIL),
    pytest.param("voting", marks=(_SHARD_HIST_XFAIL,
                                   pytest.mark.slow)),
    pytest.param("feature", marks=pytest.mark.slow),
])
@pytest.mark.slow
def test_async_distributed_learners_match_serial_sync(learner):
    """Async composes with every sharded learner: async on the 8-device
    mesh must match serial sync structure-for-structure (the learners'
    collectives live inside the jitted grower; the returned device trees
    are replicated, so deferred materialization is learner-agnostic)."""
    X, y = _data(n=4000)
    base = dict(objective="binary", num_leaves=15, learning_rate=0.1,
                verbose=-1, min_data_in_leaf=20)
    m_ref = lgb.train(dict(base, tpu_async_boosting="false"),
                      lgb.Dataset(X, label=y), num_boost_round=12)
    m_dp = lgb.train(dict(base, tpu_async_boosting="true",
                          tree_learner=learner),
                     lgb.Dataset(X, label=y), num_boost_round=12)
    assert _structure(m_ref) == _structure(m_dp)   # flushes pending
    np.testing.assert_allclose(m_ref.predict(X), m_dp.predict(X),
                               atol=1e-5)


def test_async_partial_degenerate_multiclass_keeps_iteration_budget():
    """A first-iteration per-class degeneracy must not cost the fixed
    boosting-round budget: async ends with the same tree count as sync
    (regression: the stop-check replayed only ONE of the rolled-back
    window's iterations)."""
    rng = np.random.default_rng(11)
    X = rng.normal(size=(1200, 6)).astype(np.float32)
    y = rng.integers(0, 3, size=1200).astype(np.float32)
    base = dict(objective="multiclass", num_class=3, num_leaves=4,
                min_data_in_leaf=590, min_gain_to_split=5.0, verbose=-1,
                tpu_stop_check_interval=16)
    out = {}
    for mode in ("false", "true"):
        b = lgb.train(dict(base, tpu_async_boosting=mode),
                      lgb.Dataset(X, label=y), num_boost_round=30)
        out[mode] = (b.num_trees(),
                     np.asarray(b.predict(X[:5])).round(6).tolist())
    assert out["true"] == out["false"]


def test_async_continued_training_matches_sync():
    """init_model + async: training continues on top of a loaded model
    with the same result as the sync path."""
    X, y = _data()
    base = dict(objective="binary", num_leaves=15, verbose=-1)
    first = lgb.train(dict(base, tpu_async_boosting="true"),
                      lgb.Dataset(X, label=y), num_boost_round=8)
    s = first.model_to_string()
    out = {}
    for mode in ("false", "true"):
        cont = lgb.train(dict(base, tpu_async_boosting=mode),
                         lgb.Dataset(X, label=y), num_boost_round=6,
                         init_model=lgb.Booster(model_str=s))
        out[mode] = (cont.num_trees(), _structure(cont))
    assert out["true"][0] == 14
    assert out["true"] == out["false"]


@pytest.mark.slow
def test_async_early_stopping_flow():
    """early_stopping callback over a valid set stops at the same
    iteration in async and sync modes."""
    X, y = _data()
    Xv, yv = _data(n=800, seed=9)
    base = dict(objective="binary", num_leaves=31, learning_rate=0.3,
                verbose=-1)
    best = {}
    for mode in ("false", "true"):
        ds = lgb.Dataset(X, label=y)
        b = lgb.train(dict(base, tpu_async_boosting=mode), ds,
                      num_boost_round=60,
                      valid_sets=[lgb.Dataset(Xv, label=yv,
                                              reference=ds)],
                      callbacks=[lgb.early_stopping(5, verbose=False)])
        best[mode] = b.best_iteration
    assert best["true"] == best["false"]


@pytest.mark.slow
def test_async_device_bagging_optin():
    """tpu_device_bagging: the mask draws on device (approximate
    fraction, stateless keys); the model still trains well and the
    bagging_freq window reuses one mask (deterministic re-derivation)."""
    X, y = _data(n=3000)
    params = dict(objective="binary", num_leaves=15, verbose=-1,
                  bagging_fraction=0.7, bagging_freq=2,
                  tpu_device_bagging=True, tpu_async_boosting="true")
    b = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=20)
    assert b.num_trees() == 20
    p = b.predict(X)
    acc = float(np.mean((p > 0.5) == (y > 0)))
    assert acc > 0.9
    # determinism: same seed -> same model
    b2 = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=20)
    np.testing.assert_array_equal(p, b2.predict(X))
    # the sync path derives the SAME stateless-key mask, so async and
    # sync device-bagging runs match structure-for-structure
    b3 = lgb.train(dict(params, tpu_async_boosting="false"),
                   lgb.Dataset(X, label=y), num_boost_round=20)
    assert _structure(b) == _structure(b3)


def test_async_rollback_one_iter():
    X, y = _data()
    params = dict(objective="binary", num_leaves=15, verbose=-1,
                  tpu_async_boosting="true")
    ds = lgb.Dataset(X, label=y)
    b = lgb.Booster(params, ds)
    for _ in range(5):
        b.update()
    p5 = np.asarray(b._engine.score)   # copy: update() donates the buffer
    b.update()
    b.rollback_one_iter()
    assert b.current_iteration() == 5
    np.testing.assert_allclose(p5, np.asarray(b._engine.score), atol=1e-6)
