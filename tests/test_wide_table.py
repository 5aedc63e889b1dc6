"""The wide dense table (``epsilon``: 2,000 columns, 500 packed words a row).

The program against the benchmark's plain reference
(``benchmark/references/gbdt_leafwise.py``, which owes it nothing) on seeded
tables of 150 and 500 packed words, with ``min_data_in_leaf=1`` and a
``min_sum_hessian_in_leaf`` that binds: the first three trees, the five
readings ``epsilon.train`` compares, under that cell's limits. Each width
runs as the CPU resolves it (scatter on bytes) and as the chip does (packed
words, the Pallas kernel, interpreted here), where both widths hold the
table twice (``core/plan.rows_held_twice``). Then the grower alone with the
table held once and twice, and the tracing's counters.
"""
import dataclasses
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from conftest import pack_words
from lightgbm_tpu.core import grower as grower_mod
from lightgbm_tpu.core import plan as plan_mod
from lightgbm_tpu.utils import timer
from test_compact_grower import (_grow_with_order, _odd_columns, _same_tree,
                                 _tables)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
CHIP = {"tpu_packed_bins": "true", "tpu_hist_kernel": "pallas"}
# (columns, rows, leaves): 150 and 500 packed words a row
SHAPES = {"150words": (600, 4000, 31), "500words": (2000, 2000, 15)}


def _bench(name):
    """A file of ``benchmark/`` by its path: the directory goes last on
    ``sys.path``, for what the file itself imports by name."""
    if BENCH not in sys.path:
        sys.path.append(BENCH)
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace("/", "_")[:-3], os.path.join(BENCH, name))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _json(name):
    with open(os.path.join(BENCH, name), encoding="utf-8") as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def bench():
    return {"tablegen": _bench("tablegen.py"),
            "driver": _bench("drivers/train.py"),
            "reference": _bench("references/gbdt_leafwise.py"),
            "cfg": _json("configs/epsilon.json"),
            "limits": _json("workloads/epsilon.train.json")["limits"]}


@pytest.fixture(scope="module")
def tables(bench):
    """{shape: (X, y, params, the reference's scores)}, each made once."""
    made = {}

    def table(shape):
        if shape not in made:
            cols, rows, leaves = SHAPES[shape]
            gen, cfg = bench["tablegen"], bench["cfg"]
            columns = [{"kind": "grid", "n": cols, "levels": 32}]
            label = dict(cfg["label"],
                         columns=list(range(0, cols, cols // 50)))
            codes = gen.codes(columns, 33, rows)
            y = gen.labels(columns, label, 33, rows)
            X = gen.values_table(columns, np.asarray(codes))
            params = {**cfg["params"], "num_leaves": leaves,
                      "min_sum_hessian_in_leaf": 20}
            want, _ = bench["reference"].train(codes, y, params, 3)
            made[shape] = (X, y, params,
                           [np.asarray(s, np.float64) for s in want])
        return made[shape]
    return table


@pytest.mark.parametrize("path", ["auto", "chip"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_program_agrees_with_the_plain_reference(bench, tables, shape, path):
    X, y, params, want = tables(shape)
    assert 0.4 < y.mean() < 0.6
    booster = lgb.Booster({**params, **(CHIP if path == "chip" else {})},
                          lgb.Dataset(X, label=y, params={"max_bin": 255}))
    eng = booster._engine
    words = -(-X.shape[1] // 4)
    if path == "chip":
        assert eng.grower_cfg.hist_rm_backend == "pallas"
        assert eng._packed_cols == X.shape[1]
        assert plan_mod.rows_held_twice(words)
    program = []
    for _ in range(3):
        booster.update()
        program.append(np.asarray(eng.score, np.float64).reshape(-1))
    trees = eng.models
    assert all(8 <= t.num_leaves <= params["num_leaves"] for t in trees)
    # the hessian floor binds: with at most 0.25 a row no leaf may hold
    # fewer than 80 rows, where ``min_data_in_leaf=1`` would allow one;
    # the smallest leaf is not far above it
    floor = params["min_sum_hessian_in_leaf"] / 0.25
    smallest = min(int(t.leaf_count[:t.num_leaves].min()) for t in trees)
    assert floor <= smallest < 2 * floor, smallest
    found = bench["driver"].readings(y, program, want)
    for name, limit in bench["limits"].items():
        assert found[name] <= limit, (name, found[name])


@pytest.mark.parametrize("pool", ["full", "none"])
def test_same_trees_with_the_table_held_once_or_twice(rng, pool,
                                                      monkeypatch):
    """The grower on the words kernel with the row gather reading the
    table as it is handed in, or a copy of its own behind a barrier
    (``rows_held_twice``, here forced at 2 words): ``order`` and
    ``leaf_id`` equal element for element, the tree too with the full
    pool. Without a pool both children are histogrammed, the larger in
    the bucket of every row, which a table held twice reads in place: the
    same rows summed in another order, values to float32's tolerance."""
    compact, meta, bundle, _, rm, gh, _ = _tables(
        rng, _odd_columns, L=8, hist_rm_backend="pallas", hist_pool=pool)
    compact = dataclasses.replace(compact, packed_cols=rm.shape[1])
    words = jnp.asarray(pack_words(rm))
    grown = {}
    for twice in (False, True):
        monkeypatch.setattr(grower_mod, "rows_held_twice",
                            lambda num_words, twice=twice: twice)
        grown[twice] = _grow_with_order(compact, meta, bundle, words, gh)
    once, twice = grown[False], grown[True]
    assert once[0].num_leaves == 8
    np.testing.assert_array_equal(once[2], twice[2])
    np.testing.assert_array_equal(once[1], twice[1])
    _same_tree(once[0], twice[0], exact=pool == "full")


def test_rule_holds_a_table_twice_from_where_the_compiler_re_lays_it():
    assert [plan_mod.rows_held_twice(w) for w in (7, 17, 35, 40)] == \
        [False] * 4
    assert [plan_mod.rows_held_twice(w) for w in (41, 64, 127, 128, 150,
                                                  175, 500)] == [True] * 7


def test_counters_after_three_trees(bench, tables):
    """``splits`` where a tree becomes a host tree; ``pool_bytes`` and
    ``table_words``, sizes that each set-up writes anew and a second booster
    in the process does not add to; the table prints them under the
    sections."""
    X, y, params, _ = tables("150words")
    counters = timer.global_timer.counters
    before = {k: counters[k] for k in ("trees", "splits")}
    leaves, cols = 31, X.shape[1]
    for _ in range(2):
        booster = lgb.Booster(
            {**params, "tpu_packed_bins": "true"},
            lgb.Dataset(X, label=y, params={"max_bin": 255}))
        eng = booster._engine
        assert counters["pool_bytes"] == \
            leaves * cols * eng.grower_cfg.num_bin * 12
        assert counters["table_words"] == X.shape[0] * 150
    for _ in range(3):
        booster.update()
    splits = sum(t.num_leaves - 1 for t in eng.models)
    assert splits == 3 * (leaves - 1)
    assert counters["trees"] - before["trees"] == 3
    assert counters["splits"] - before["splits"] == splits
    printed = {ln.split()[0]: ln.split()[1]
               for ln in timer.global_timer.table().splitlines()}
    for name in ("trees", "splits", "pool_bytes", "table_words"):
        assert printed[name] == str(counters[name])
