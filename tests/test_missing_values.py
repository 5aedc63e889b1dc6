"""Missing values, program against the benchmark's plain reference.

The plain reference (``benchmark/references/gbdt_leafwise.py``: jax.numpy,
nothing of the program) tries the missing on either side of every threshold
and keeps the side with the larger gain. Here the program's first three
trees are laid against its trees split by split (feature, threshold,
``default_left``), leaf by leaf (the rows ``predict(pred_leaf=True)`` sends
each leaf, their count, the leaf's value) and score by score, on seeded
tables of ``benchmark/tablegen.py`` whose count columns hold no, a fifth and
four fifths missing values, through the CPU's path (``auto``: scatter
histograms on unpacked bins) and through the chip's (the Pallas kernel on
packed words, interpreted here, the sort partition, the first split in
place, async boosting).

This is the guard of ROADMAP B1 ("a wrong leaf when any column has missing
values", read once at PR 24 on 400,000 x 67 and on no tree since): the
fault's shape was a split on a column WITHOUT missing values in a table
that has them, whose leaf came out with a value no -G/H could give.
"""
import functools
import importlib.util
import os
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
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
ref = _bench_module("bench_gbdt_leafwise", "references/gbdt_leafwise.py")

STEPS = 3
PARAMS = {"objective": "binary", "learning_rate": 0.1, "max_bin": 255,
          "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 5.0,
          "tpu_hist_dtype": "float32", "verbose": -1}
# what ``auto`` resolves to on a chip (core/plan.py), asked for by name
CHIP = {"tpu_hist_kernel": "pallas", "tpu_packed_bins": "true",
        "tpu_partition_mode": "sort", "tpu_row_scheduling": "compact",
        "tpu_async_boosting": "true"}
# path -> (its parameters, rows, leaves): the interpreter pays by the row
PATHS = {"cpu": ({}, 20_000, 63), "chip": (CHIP, 8_000, 31)}
# `criteo-share`'s shape, with fewer values a column so that each keeps a
# bin of its own at these row counts: 13 count columns, 54 grid columns
LABEL = {"columns": [0, 3, 7, 20, 40, 66],
         "coefficients": [0.9, -0.7, 0.5, 0.8, -0.6, 0.4, 0.5, -0.3],
         "noise": 1.0, "noise_seed": 7, "missing_as": 6.0}
TABLE_SEED = 20261005


def columns(missing):
    count = {"kind": "count", "n": 13, "scale": 6.0, "cap": 24}
    if missing:
        count["missing"] = missing
    return [count, {"kind": "grid", "n": 54, "levels": 32}]


def reference_trees(codes, y, params, steps):
    """The reference's trees, one dict a step: its splits in the order it
    made them (feature, code threshold, whether the missing go left, whether
    the split leaf held a missing value of that column), every row's leaf,
    the leaves' values and counts, the scores after the step. The splits
    are read off the reference's own ``apply_split`` calls; the boosting
    loop is ``ref.train``'s."""
    import jax.numpy as jnp
    F, R = codes.shape
    prog = dict(ref._programs(F, R, "float32"))
    apply_split, made = prog["apply_split"], []

    def noted(leaf_id, codes_t, f, t, goes_left, parent, new):
        held = np.asarray(leaf_id) == int(parent)
        gone = np.asarray(codes_t[int(f)]) == ref.MISSING
        made.append((int(f), int(t), bool(goes_left),
                     bool(np.any(held & gone))))
        return apply_split(leaf_id, codes_t, f, t, goes_left, parent, new)

    prog["apply_split"] = noted
    hp = (0.0, float(params["min_data_in_leaf"]),
          float(params["min_sum_hessian_in_leaf"]))
    codes_t, y_dev = jnp.asarray(codes), jnp.asarray(y, jnp.float32)
    used = jnp.ones(R, bool)
    score = jnp.full(R, ref.init_score(y), jnp.float32)
    out = []
    for _ in range(steps):
        del made[:]
        g, h = prog["grads"](score, y_dev)
        leaf_id, sums = ref.grow_tree(prog, codes_t, g, h, used, hp,
                                      int(params["num_leaves"]))
        values = -params["learning_rate"] * sums[:, 0] / np.maximum(
            sums[:, 1], 1e-30)
        score = prog["add_leaves"](score, leaf_id,
                                   jnp.asarray(values, jnp.float32))
        out.append({"splits": list(made), "leaf_id": np.asarray(leaf_id),
                    "values": values, "counts": sums[:, 2].astype(np.int64),
                    "score": np.asarray(score, np.float64)})
    return out


@functools.lru_cache(maxsize=None)
def table(missing, rows):
    cols = columns(missing)
    codes = np.asarray(tablegen.codes(cols, TABLE_SEED, rows))
    y = tablegen.labels(cols, LABEL, TABLE_SEED, rows)
    return codes, tablegen.values_table(cols, codes), y


@functools.lru_cache(maxsize=None)
def grown(path, missing):
    """The program's trees, its scores after each step, its leaf of every
    row, what it resolved to and counted, the reference's trees and the
    labels, for one path and share of missing."""
    extra, rows, leaves = PATHS[path]
    params = {**PARAMS, "num_leaves": leaves}
    codes, X, y = table(missing, rows)
    before = dict(global_timer.counters)
    booster = lgb.Booster({**params, **extra}, lgb.Dataset(X, label=y))
    engine = booster._engine
    resolved = {"packed": bool(engine._packed_cols),
                "kernel": engine.grower_cfg.hist_rm_backend,
                "partition": engine.grower_cfg.partition_mode,
                "async": bool(engine._async_on()),
                "scan_directions": global_timer.counters["scan_directions"]}
    scores = []
    for _ in range(STEPS):
        booster.update()
        scores.append(np.asarray(engine.score, np.float64).reshape(-1))
    trees = list(engine.models)
    counted = {k: global_timer.counters[k] - before.get(k, 0)
               for k in ("trees", "splits", "splits_missing_right",
                         "splits_on_missing")}
    return {"trees": trees, "scores": scores, "resolved": resolved,
            "leaves": booster.predict(X, pred_leaf=True),
            "want": reference_trees(codes, y, params, STEPS),
            "counted": counted, "y": y}


def code_threshold(spec, threshold_real):
    """The highest code at or under a tree's real threshold."""
    return int(np.searchsorted(tablegen.code_values(spec), threshold_real,
                               side="right")) - 1


CASES = [(p, m) for p in PATHS for m in (0.0, 0.2, 0.8)]


@pytest.mark.parametrize("path,missing", CASES)
def test_splits_are_the_plain_references(path, missing):
    """Every split of the first three trees: the reference's feature, its
    threshold, and its side for the missing wherever the split leaf held a
    missing value of that column (where it held none the side is a tie,
    which both keep left)."""
    found = grown(path, missing)
    specs = tablegen.column_specs(columns(missing))
    if path == "chip":
        assert found["resolved"] == {
            "packed": True, "kernel": "pallas", "partition": "sort",
            "async": True, "scan_directions": 2 if missing else 1}
        assert all(t.first_split_dense for t in found["trees"])
    else:
        assert found["resolved"]["kernel"] == "scatter"
        assert found["resolved"]["scan_directions"] == (2 if missing else 1)
    for tree, want in zip(found["trees"], found["want"]):
        assert tree.num_leaves == PATHS[path][2] == len(want["splits"]) + 1
        for i, (f, t, goes_left, held_missing) in enumerate(want["splits"]):
            got = (int(tree.split_feature[i]),
                   code_threshold(specs[f], tree.threshold_real[i]))
            assert got == (f, t), (i, got, (f, t))
            if held_missing or not missing:
                assert bool(tree.default_left[i]) == goes_left, i


@pytest.mark.parametrize("path,missing", CASES)
def test_leaves_are_the_plain_references(path, missing):
    """Leaf by leaf: the rows the program's own ``predict(pred_leaf=True)``
    sends each leaf are the reference's, so are their count and the leaf's
    value, and the training scores after each step (what the benchmark's
    ``correct`` reads) follow."""
    found = grown(path, missing)
    start = ref.init_score(found["y"])
    for k, (tree, want) in enumerate(zip(found["trees"], found["want"])):
        n = tree.num_leaves
        np.testing.assert_array_equal(found["leaves"][:, k], want["leaf_id"])
        np.testing.assert_array_equal(np.asarray(tree.leaf_count[:n]),
                                      want["counts"][:n])
        # the first tree carries the start score (boost_from_average)
        values = np.asarray(tree.leaf_value[:n], np.float64) - \
            (start if k == 0 else 0.0)
        np.testing.assert_allclose(values, want["values"][:n], atol=1e-4)
        np.testing.assert_allclose(found["scores"][k], want["score"],
                                   atol=2e-4)


@pytest.mark.parametrize("path", list(PATHS))
def test_column_without_missing_values_in_a_table_that_has_them(path):
    """B1's shape: the first tree splits on columns that hold no missing
    value (the grid columns) while other columns do, and every leaf's value
    is -learning_rate x G / H of the rows the program itself sends it; no
    leaf passes what a hessian floor of 5 allows."""
    found = grown(path, 0.2)
    tree, y = found["trees"][0], found["y"]
    n = tree.num_leaves
    on_dense = [i for i in range(n - 1) if int(tree.split_feature[i]) >= 13]
    on_count = [i for i in range(n - 1) if int(tree.split_feature[i]) < 13]
    assert on_dense and on_count
    start = ref.init_score(y)
    p = 1.0 / (1.0 + np.exp(-start))
    leaf = found["leaves"][:, 0]
    G = np.bincount(leaf, weights=p - y, minlength=n)
    H = np.bincount(leaf, weights=np.full(len(y), p * (1.0 - p)),
                    minlength=n)
    assert H.min() >= 5.0 - 1e-3
    values = np.asarray(tree.leaf_value[:n], np.float64) - start
    np.testing.assert_allclose(values, -0.1 * G / H, atol=1e-4)
    assert np.abs(values).max() <= 0.1 * len(y) / 5.0


@pytest.mark.parametrize("path,missing", [(p, m) for p in PATHS
                                          for m in (0.2, 0.8)])
def test_missing_go_both_ways_and_are_counted(path, missing):
    """Among the splits whose leaf held missing values some send them
    right (the forward scan's winners) and some left, as the reference
    does; the tracing's counters read the trees: ``splits_missing_right``
    the splits whose ``default_left`` is false, ``splits_on_missing`` those
    on a column with a bin for the missing."""
    found = grown(path, missing)
    sides = [goes_left for want in found["want"]
             for _, _, goes_left, held in want["splits"] if held]
    assert 2 <= sides.count(False) <= len(sides) - 2
    trees = found["trees"]
    right = sum(int(np.count_nonzero(~np.asarray(
        t.default_left[:t.num_leaves - 1], bool))) for t in trees)
    on_missing = sum(int(np.count_nonzero(np.asarray(
        t.split_feature[:t.num_leaves - 1]) < 13)) for t in trees)
    assert found["counted"] == {
        "trees": STEPS, "splits": STEPS * (PATHS[path][2] - 1),
        "splits_missing_right": right, "splits_on_missing": on_missing}
    assert right >= sides.count(False)


def test_a_table_without_missing_values_counts_none():
    assert grown("cpu", 0.0)["counted"] == {
        "trees": STEPS, "splits": STEPS * 62, "splits_missing_right": 0,
        "splits_on_missing": 0}


# ---- one column, by hand: the side the missing belong on --------------------

def one_column(rows, missing_like):
    """A column of values 0..9 and a third NaN; the label is ``value >=
    5``, and the NaN rows carry the label of the side ``missing_like``."""
    rng = np.random.default_rng(3)
    x = rng.integers(0, 10, size=rows).astype(np.float32)
    gone = rng.random(rows) < 1 / 3
    y = np.where(gone, missing_like == "high", x >= 5).astype(np.float32)
    x[gone] = np.nan
    noise = rng.normal(size=(rows, 2)).astype(np.float32)
    return np.column_stack([x, noise]), y, gone


@pytest.mark.parametrize("path,rows", [("cpu", 3000), ("chip", 3000)])
@pytest.mark.parametrize("missing_like,default_left", [("high", False),
                                                       ("low", True)])
def test_the_root_sends_the_missing_where_they_belong(path, rows,
                                                      missing_like,
                                                      default_left):
    """The root splits the NaN-bin column at 4 | 5 and sends the missing to
    the side whose label they carry: right (``default_left`` false, the
    forward scan) or left (the reverse scan). On the chip's path this is
    the first split in place: ``make_part(dense=True)`` compares the
    column where it lies and ``hist_first`` histograms the smaller child in
    one masked pass, both routing the NaN bin by ``default_left``. Training
    scores and ``predict`` agree on where a NaN row goes."""
    X, y, gone = one_column(rows, missing_like)
    extra = {**PATHS[path][0], "tpu_min_bucket": 32} if path == "chip" \
        else {}
    booster = lgb.Booster({**PARAMS, "num_leaves": 4,
                           "min_sum_hessian_in_leaf": 1.0, **extra},
                          lgb.Dataset(X, label=y))
    booster.update()
    engine = booster._engine
    train_score = np.asarray(engine.score, np.float64).reshape(-1)
    tree = engine.models[0]
    assert int(tree.split_feature[0]) == 0
    assert 4.0 < tree.threshold_real[0] < 5.0
    assert bool(tree.default_left[0]) == default_left
    assert bool(tree.first_split_dense) == (path == "chip")
    leaf = booster.predict(X, pred_leaf=True)[:, 0]
    # the root's left child keeps leaf 0's side of the tree: every NaN row
    # sits with the rows of the side it belongs on
    left_of_root = _left_leaves(tree)
    goes_left = np.isin(leaf, left_of_root)
    assert np.all(goes_left[gone] == default_left)
    assert np.all(goes_left[~gone] == (X[~gone, 0] <= 4))
    np.testing.assert_allclose(
        booster.predict(X, raw_score=True), train_score, atol=1e-5)


def _left_leaves(tree):
    """The leaves under the root's left child."""
    out, todo = [], [int(tree.left_child[0])]
    while todo:
        node = todo.pop()
        if node < 0:
            out.append(~node)
        else:
            todo += [int(tree.left_child[node]), int(tree.right_child[node])]
    return out


# ---- the compile script's reading of the scan's layouts ---------------------

def test_compile_script_finds_a_channel_minor_copy_of_the_scans_sums():
    """``scripts/tpu_compile_grower.py`` counts the arrays shaped like the
    scan's cumulative sums that the compiler laid out with the three
    channels minor: PR 33's ``%rev.43`` is one, the layouts the module has
    at 1,000,000 x 968 x 251 with both directions in it are none."""
    script = _bench_module("tpu_compile_grower",
                           "../scripts/tpu_compile_grower.py")
    text = "\n".join([
        "%region_1.2 (p: f32[3]) -> f32[3] {",
        "  %rev.43 = f32[2,3,2000,251]{1,3,2,0} reverse(%x), dimensions={3}",
        "  %cp.1 = f32[2,3,2000,251]{3,2,0,1:T(8,128)} copy(%rev.43)",
        "  %pfx.2 = f32[3,2000,251]{0,2,1:T(8,128)} fusion(%cp.1), kind=kLoop",
        "  %sfx.3 = f32[3,2000,251]{2,1,0:T(8,128)S(1)} fusion(%cp.1), "
        "kind=kLoop",
        "  %t.4 = (f32[3,2000,251]{0,2,1}, s32[2]{0}) tuple(%pfx.2, %n)",
        "}"])
    found = script.channel_minor_scan_arrays(text, 2000, 251)
    assert [name for _, name, _ in found] == ["%rev.43", "%pfx.2"]
    assert all(comp == "%region_1.2" for comp, _, _ in found)
    assert script.channel_minor_scan_arrays(text, 968, 251) == []
