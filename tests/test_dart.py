"""DART (``boosting=dart``) against a plain DART reference, on the
asynchronous device path and the synchronous one.

The reference (``benchmark/references/gbdt_dart.py``: ``drop_sets``,
``leaves``, ``replay``) takes the program's trees as shapes only and does
DART's arithmetic itself in float64: the draw (``drop_seed``'s generator in
dart.hpp's order), the dropped trees taken out before the gradients, each
new leaf's value ``-G / H`` from those gradients times ``lr / (k + 1)``
(``lr / (lr + k)`` in xgboost_dart_mode), the dropped trees back at
``k / (k + 1)`` (``k / (k + lr)``) and the weights. The rows reach their
leaves by the reference's own walk over the table's codes, the missing code
sent by ``default_left``. The program has to agree with it on every drop
set, every leaf value of the final model and every training score.
"""
import functools
import importlib.util
import os
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.utils import timer
from lightgbm_tpu.utils.timer import global_timer

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                     "benchmark")


def _bench_module(name, rel_path):
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(BENCH, rel_path))
        sys.modules[name] = module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    return sys.modules[name]


tablegen = _bench_module("bench_tablegen", "tablegen.py")
ref = _bench_module("bench_gbdt_dart", "references/gbdt_dart.py")

ROWS, ITERATIONS = 20_000, 24
# 48 count columns, half their cells missing, and 48 grid columns
COLUMNS = [{"kind": "count", "n": 48, "scale": 6.0, "cap": 24,
            "missing": 0.5},
           {"kind": "grid", "n": 48, "levels": 32}]
LABEL = {"columns": [0, 3, 7, 20, 50, 66],
         "coefficients": [0.9, -0.7, 0.5, 0.8, -0.6, 0.4, 0.5, -0.3],
         "noise": 1.0, "noise_seed": 7, "missing_as": 6.0}
TABLE_SEED = 20261017
PARAMS = {"objective": "binary", "boosting": "dart", "num_leaves": 63,
          "learning_rate": 0.1, "min_data_in_leaf": 1,
          "min_sum_hessian_in_leaf": 5.0, "max_bin": 255,
          "tpu_hist_dtype": "float32", "drop_rate": 0.3, "skip_drop": 0.25,
          "verbose": -1}
COMPACT = {"tpu_row_scheduling": "compact"}
CASES = {
    # the grower's packed words, read four bins a word, on the async path
    "packed": {**COMPACT, "tpu_packed_bins": "true",
               "tpu_async_boosting": "true"},
    # row-major bytes, on the synchronous path
    "unpacked": {**COMPACT, "tpu_packed_bins": "false",
                 "tpu_async_boosting": "false"},
    "uniform_drop": {**COMPACT, "tpu_packed_bins": "true",
                     "tpu_async_boosting": "true", "uniform_drop": True},
    "xgboost_dart_mode": {**COMPACT, "tpu_packed_bins": "true",
                          "tpu_async_boosting": "true",
                          "xgboost_dart_mode": True},
}


@functools.lru_cache(maxsize=None)
def table(rows=ROWS):
    codes = np.asarray(tablegen.codes(COLUMNS, TABLE_SEED, rows))
    y = tablegen.labels(COLUMNS, LABEL, TABLE_SEED, rows)
    return codes, tablegen.values_table(COLUMNS, codes), y


def code_values():
    return [tablegen.code_values(s) for s in tablegen.column_specs(COLUMNS)]


def trained(params, rows=ROWS, iterations=ITERATIONS):
    """(booster, the trees each iteration dropped, the training scores)."""
    codes, X, y = table(rows)
    booster = lgb.Booster(params, lgb.Dataset(X, label=y))
    drops = []
    for _ in range(iterations):
        booster.update()
        drops.append(list(booster._engine.drop_index))
    return booster, drops, np.asarray(booster._engine.score[0], np.float64)


@functools.lru_cache(maxsize=None)
def case(name):
    params = {**PARAMS, **CASES[name]}
    booster, drops, score = trained(params)
    codes, _, y = table()
    trees = ref.trees_from_text(booster.model_to_string(), code_values())
    values, want = ref.replay(trees, ref.leaves(trees, codes), y, params)
    return {"booster": booster, "drops": drops, "score": score,
            "trees": trees, "want": (ref.drop_sets(params, ITERATIONS),
                                     values, want),
            "params": params}


@pytest.mark.parametrize("name", list(CASES))
def test_dart_is_the_plain_reference(name):
    c = case(name)
    eng = c["booster"]._engine
    assert bool(eng._async_on()) == (CASES[name]["tpu_async_boosting"]
                                     == "true")
    assert bool(eng._packed_cols) == (CASES[name]["tpu_packed_bins"]
                                      == "true")
    want_drops, want_values, want_score = c["want"]
    # the draw: the same trees drop as the reference's, in at least 8 of
    # the 24 iterations
    assert c["drops"] == want_drops
    assert sum(bool(d) for d in want_drops) >= 8
    # the NaN bin goes both ways among the splits on columns with missing
    # values, and each way agrees with the reference's walk
    sides = {bool(dl) for t in c["trees"][1:] if len(t["split_feature"])
             for f, dl in zip(t["split_feature"], t["default_left"])
             if f < 48}
    assert sides == {True, False}
    # a leaf's float32 sums, made by the histogram subtraction from sums
    # over thousands of rows, are off by a few 1e-5 of the leaf's hessian:
    # up to 2e-5 on values of 0.01 to 0.1 here
    for t, v in zip(c["trees"], want_values):
        np.testing.assert_allclose(t["leaf_value"], v, rtol=0, atol=5e-5)
    np.testing.assert_allclose(c["score"], want_score, rtol=0, atol=1e-4)
    # the model predicts the training score it kept
    _, X, _ = table()
    np.testing.assert_allclose(c["booster"].predict(X, raw_score=True),
                               c["score"], rtol=0, atol=1e-4)


def small(**extra):
    rng = np.random.default_rng(3)
    X = rng.normal(size=(3000, 10)).astype(np.float32)
    X[rng.random(X.shape) < 0.3] = np.nan
    y = (np.nan_to_num(X[:, 0]) + np.nan_to_num(X[:, 1]) * X[:, 2]
         > 0).astype(np.float32)
    y[np.isnan(X[:, 2])] = 1.0
    params = {"objective": "binary", "boosting": "dart", "num_leaves": 15,
              "drop_rate": 0.3, "skip_drop": 0.2, "verbose": -1, **COMPACT,
              "tpu_packed_bins": "true", **extra}
    return X, y, params


def without_parameters(model: str) -> str:
    return model.split("\nparameters:")[0]


def test_async_and_sync_give_the_same_scores_and_model():
    X, y, params = small()
    out = {}
    for mode in ("false", "true"):
        b = lgb.Booster({**params, "tpu_async_boosting": mode},
                        lgb.Dataset(X, label=y))
        for _ in range(20):
            b.update()
        assert bool(b._engine._async_on()) == (mode == "true")
        if mode == "true":
            assert len(b._engine._pending) == 20    # nothing flushed
        out[mode] = (np.asarray(b._engine.score), b.model_to_string())
    np.testing.assert_array_equal(out["true"][0], out["false"][0])
    assert without_parameters(out["true"][1]) == \
        without_parameters(out["false"][1])


def test_rollback_across_a_dropped_iteration():
    """A stop check that finds a tree without a split rolls the window
    back, dropped iterations with it, and replays them synchronously:
    the model and the scores are the synchronous path's."""
    rng = np.random.default_rng(6)
    X = rng.integers(0, 3, size=(300, 3)).astype(np.float32)
    y = (X[:, 0] > 1).astype(np.float32)
    # the 23rd iteration finds no split; the check after the 24th rolls
    # both back, each having dropped two trees
    base = {"objective": "binary", "boosting": "dart", "num_leaves": 4,
            "learning_rate": 1.0, "min_data_in_leaf": 5,
            "min_gain_to_split": 1.0, "drop_rate": 0.3, "skip_drop": 0.0,
            "max_drop": 2, "tpu_stop_check_interval": 8, "verbose": -1}
    out = {}
    for mode in ("false", "true"):
        b = lgb.Booster({**base, "tpu_async_boosting": mode},
                        lgb.Dataset(X, label=y))
        eng = b._engine
        rolled, undone = [], []
        if mode == "true":
            back = eng._async_rollback_from

            def noted(it0):
                undone.append([r["dropped"] for r in eng._undo
                               if r["it"] >= it0])
                rolled.append(it0)
                back(it0)
            eng._async_rollback_from = noted
        stopped = False
        for _ in range(40):
            if b.update():
                stopped = True
                break
        out[mode] = (stopped, b.num_trees(), np.asarray(eng.score),
                     b.model_to_string())
        if mode == "true":
            assert rolled == [22]
            assert [len(d) for d in undone[0]] == [2, 2]
    assert out["true"][:2] == out["false"][:2]
    assert out["true"][0]
    # the undone sums leave an ulp in the scores; the model is the same
    np.testing.assert_allclose(out["true"][2], out["false"][2], rtol=0,
                               atol=1e-6)
    assert without_parameters(out["true"][3]) == \
        without_parameters(out["false"][3])


def test_no_compile_after_warm_up_as_the_drop_count_varies(compile_budget):
    X, y, params = small(tpu_async_boosting="true")
    b = lgb.Booster(params, lgb.Dataset(X, label=y))
    eng = b._engine
    counts = []
    while not any(counts):          # warm up to the first drop
        b.update()
        counts.append(len(eng.drop_index))
    b.update()
    counts = []
    with compile_budget(0, "DART iterations"):
        for _ in range(14):
            b.update()
            counts.append(len(eng.drop_index))
    assert len(set(counts)) >= 3, counts


def test_walk_counts_and_stage():
    """Each dropping iteration counts its trees and their rows, and the
    re-scoring's program carries the ``dart_rescore`` stage."""
    X, y, params = small(tpu_async_boosting="true")
    before = dict(global_timer.counters)
    b = lgb.Booster(params, lgb.Dataset(X, label=y))
    dropped = 0
    for _ in range(10):
        b.update()
        dropped += len(b._engine.drop_index)
    got = {k: global_timer.counters.get(k, 0) - before.get(k, 0)
           for k in ("dart_trees_rescored", "dart_rows_rescored")}
    assert dropped > 0
    assert got == {"dart_trees_rescored": dropped,
                   "dart_rows_rescored": dropped * len(y)}
    assert "dart_rescore" in set(timer.stage_map().values())


def test_rollback_one_iter_leaves_the_slots_aligned():
    """After ``rollback_one_iter`` the next trees take the freed slots:
    the model still predicts the training score the program keeps."""
    X, y, params = small(tpu_async_boosting="true")
    b = lgb.Booster(params, lgb.Dataset(X, label=y))
    for _ in range(8):
        b.update()
    b.rollback_one_iter()
    for _ in range(8):
        b.update()
    assert b.current_iteration() == 15
    np.testing.assert_allclose(b.predict(X, raw_score=True),
                               np.asarray(b._engine.score[0]), rtol=0,
                               atol=1e-5)


def storage_table():
    """Four mutually exclusive one-hot columns (EFB bundles them), a
    categorical column, a column with missing values and four normal ones."""
    rng = np.random.default_rng(7)
    n = 2000
    X = rng.normal(size=(n, 10)).astype(np.float32)
    X[:, 3] = rng.integers(0, 7, size=n)
    X[rng.random(n) < 0.2, 0] = np.nan
    X[:, 6:10] = 0.0
    X[np.arange(n), 6 + rng.integers(0, 4, size=n)] = 1.0
    y = ((X[:, 3] % 2 == 0) ^ (np.nan_to_num(X[:, 0]) > 0.5) ^
         (X[:, 7] > 0)).astype(np.float32)
    return X, y


STORAGES = {
    # EFB's bundles in the grower's row-major table, and feature-major
    "bundled_rows": ({"enable_bundle": True, "tpu_sparse_storage": "none",
                      **COMPACT},
                     lambda eng: eng._bundle is not None
                     and eng.bins_rf is not None),
    "bundled_features": ({"enable_bundle": True,
                          "tpu_sparse_storage": "none",
                          "tpu_row_scheduling": "full"},
                         lambda eng: eng._bundle is not None
                         and eng.bins_rf is None),
    # multi-value sparse rows
    "sparse": ({"tpu_sparse_storage": "multival", **COMPACT},
               lambda eng: eng._bins_mv_dev is not None),
}


@pytest.mark.parametrize("storage", list(STORAGES))
def test_walk_reads_every_storage(storage):
    """The re-scoring reads the leaves the grower gave every row, whatever
    the table's storage, with a categorical split among the trees: the
    model predicts the training score the program keeps, and the
    feature-major copy of the table is never made."""
    extra, stored = STORAGES[storage]
    X, y = storage_table()
    params = {"objective": "binary", "boosting": "dart", "num_leaves": 15,
              "min_data_in_leaf": 5, "drop_rate": 0.5, "skip_drop": 0.0,
              "verbose": -1, "tpu_async_boosting": "true", **extra}
    data = X
    if storage == "sparse":
        import scipy.sparse
        data = scipy.sparse.csr_matrix(np.nan_to_num(X, nan=0.0))
    b = lgb.Booster(params, lgb.Dataset(data, label=y,
                                        categorical_feature=[3]))
    dropped = 0
    for _ in range(12):
        b.update()
        dropped += len(b._engine.drop_index)
    eng = b._engine
    assert stored(eng) and dropped > 10
    assert eng._bins_dev_cache is None
    assert any(t.num_cat > 0 for t in eng.models)
    np.testing.assert_allclose(b.predict(data, raw_score=True),
                               np.asarray(eng.score[0]), rtol=0, atol=1e-5)


@pytest.mark.parametrize("mode", ["true", "false"])
def test_validation_scores_follow_the_drops(mode):
    """A validation set given from the start and one added after six
    iterations keep the scores the final model predicts for their rows,
    on the asynchronous path and the synchronous one."""
    X, y, params = small(tpu_async_boosting=mode)
    rng = np.random.default_rng(11)
    Xv = rng.normal(size=(700, 10)).astype(np.float32)
    Xv[rng.random(Xv.shape) < 0.3] = np.nan
    yv = (np.nan_to_num(Xv[:, 0]) > 0).astype(np.float32)
    train = lgb.Dataset(X, label=y)
    b = lgb.Booster(params, train)
    b.add_valid(lgb.Dataset(Xv, label=yv, reference=train), "first")
    dropped = 0
    for it in range(16):
        if it == 6:
            b.add_valid(lgb.Dataset(Xv[:300], label=yv[:300],
                                    reference=train), "later")
        b.update()
        dropped += len(b._engine.drop_index)
    eng = b._engine
    assert bool(eng._async_on()) == (mode == "true") and dropped > 5
    want = b.predict(Xv, raw_score=True)
    np.testing.assert_allclose(np.asarray(eng.valid_sets[0].score[0]),
                               want, rtol=0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(eng.valid_sets[1].score[0]),
                               want[:300], rtol=0, atol=1e-5)
