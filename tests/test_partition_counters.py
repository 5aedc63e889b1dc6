"""The partition's counters, ``part_rows_live`` / ``part_rows_read`` /
``part_rows_ordered`` / ``part_calls_mid_rung`` (``TreeArrays.part_rows``,
recorded in ``core/grower.py`` where the split loop picks the partition's
branch, counted in ``models/gbdt.py`` ``_finalize_tree``), against
derivations that do not go through the record: the live rows are the
tree's own ``internal_count`` summed over its splits; the rows the column
fetch read are every row at the first split, which partitions the table in
place, and at every other split the smallest rung of the fetch's ladder
(the table's rows, then every power of two and every three halves of one
under them down to ``tpu_min_bucket``) that holds its ``internal_count``;
the rows the order read are the same with the bucket ladder (the table's
rows, then the powers of two under them down to the first at or over
``tpu_min_bucket``); the mid-rung calls are the splits whose two rungs
differ. On the CPU's path and on the chip's (the Pallas kernel on packed
words, interpreted here), on a narrow dense table, on
``test_missing_values``'s table with four cells in five missing, whose
trees are lopsided, and with a smaller ``tpu_min_bucket``. The growers that
partition no segment leave the counters absent, and the record sits at the
split loop's level of the compiled grower, in no branch, adding no
``conditional``."""
import importlib.util
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.core import grower as grower_mod
from lightgbm_tpu.core.grower import GrowerConfig, make_tree_grower
from lightgbm_tpu.models import gbdt as gbdt_mod
from lightgbm_tpu.ops.split import FeatureMeta, SplitHyperParams
from lightgbm_tpu.utils import timer
from test_missing_values import CHIP
from test_missing_values import table as missing_table

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
PARAMS = {"objective": "binary", "learning_rate": 0.1, "min_data_in_leaf": 5,
          "min_sum_hessian_in_leaf": 1.0, "verbose": -1}
# path -> (its parameters, rows, leaves): the interpreter pays by the row
PATHS = {"cpu": ({}, 6_000, 31), "chip": (CHIP, 4_000, 15)}
STEPS = 2


def dense_table(rows):
    rng = np.random.default_rng(11)
    X = rng.normal(size=(rows, 10)).astype(np.float32)
    y = X[:, 0] * 2 + X[:, 1] ** 2 + rng.normal(size=rows) > 1
    return X, y.astype(np.float32)


def missing_values_table(rows):
    _, X, y = missing_table(0.8, rows)
    return X, y


# table -> (its rows and labels, extra parameters)
TABLES = {"dense": (dense_table, {}),
          "missing": (missing_values_table, {}),
          "min_bucket": (dense_table, {"tpu_min_bucket": 256})}


KEYS = ("part_rows_live", "part_rows_read", "part_rows_ordered",
        "part_calls_mid_rung")


def ladder_bucket(rows, R, min_bucket):
    """The fetch's rung of a leaf of ``rows``: R, and every 2^k and
    3 * 2^(k-1) from ``min_bucket`` up to under R."""
    rungs = [R] + [v for k in range(R.bit_length())
                   for v in (2 ** k, 3 * 2 ** k // 2)
                   if min_bucket <= v < R]
    return min(S for S in rungs if S >= rows)


def pow2_bucket(rows, R, min_bucket):
    """The order's bucket of a leaf of ``rows``: R, and every power of
    two under it down to the first at or over ``min_bucket``."""
    rungs = [R] + [1 << k for k in range(R.bit_length())
                   if min_bucket <= 1 << k < R]
    return min(S for S in rungs if S >= rows)


@pytest.mark.parametrize("path", list(PATHS))
@pytest.mark.parametrize("name", list(TABLES))
def test_partition_rows_follow_the_trees_and_the_ladder(path, name):
    extra, rows, leaves = PATHS[path]
    make, asked = TABLES[name]
    X, y = make(rows)
    counters = timer.global_timer.counters
    before = {k: counters.get(k, 0) for k in KEYS}
    booster = lgb.Booster({**PARAMS, "num_leaves": leaves, **extra, **asked},
                          lgb.Dataset(X, label=y))
    engine = booster._engine
    assert engine.grower_cfg.row_sched == "compact"
    assert engine.grower_cfg.hist_rm_backend == (
        "pallas" if path == "chip" else "scatter")
    min_bucket = engine.grower_cfg.min_bucket
    assert min_bucket == asked.get("tpu_min_bucket", 2048)
    for _ in range(STEPS):
        booster.update()
    trees = engine.models
    assert len(trees) == STEPS
    lopsided = 0
    for tree in trees:
        n = tree.num_leaves - 1
        assert n == leaves - 1
        count = [int(c) for c in tree.internal_count[:n]]
        assert count[0] == rows
        read = [ladder_bucket(c, rows, min_bucket) for c in count[1:]]
        ordered = [pow2_bucket(c, rows, min_bucket) for c in count[1:]]
        want = (sum(count), rows + sum(read), rows + sum(ordered),
                sum(q < p for q, p in zip(read, ordered)))
        assert tree.part_rows == want
        assert 0 < want[0] < want[1] <= want[2]
        sides = [[int(tree.internal_count[c]) if c >= 0 else
                  int(tree.leaf_count[~c]) for c in (left, right)]
                 for left, right in zip(tree.left_child[:n],
                                        tree.right_child[:n])]
        lopsided += sum(min(s) * 10 < sum(s) for s in sides)
    if name == "missing":
        # splits that send under a tenth of their leaf one way
        assert lopsided
    added = tuple(counters[k] - before[k] for k in KEYS)
    assert added == tuple(sum(t.part_rows[k] for t in trees)
                          for k in range(len(KEYS)))


def test_the_finer_ladder_pads_the_lopsided_table_less(monkeypatch):
    """On ``test_missing_values``'s table, whose splits send most of a
    leaf one way, the share of the fetched indices that are padding, ``1 -
    part_rows_live / part_rows_read``, is below what the power-of-two
    ladder leaves, ``1 - part_rows_live / part_rows_ordered``, which is
    what the fetch read on that ladder: the rows read with the fetch put
    back on the buckets."""
    extra, rows, leaves = PATHS["cpu"]
    X, y = missing_values_table(rows)

    def counted():
        fresh = timer.Timer()
        with mock.patch.object(gbdt_mod, "global_timer", fresh):
            booster = lgb.Booster({**PARAMS, "num_leaves": leaves, **extra},
                                  lgb.Dataset(X, label=y))
            for _ in range(STEPS):
                booster.update()
        return [fresh.counters[k] for k in KEYS]

    live, read, ordered, mid = counted()
    monkeypatch.setattr(grower_mod, "_fetch_sizes", grower_mod._bucket_sizes)
    assert counted() == [live, ordered, ordered, 0]
    assert 1 - live / read < 1 - live / ordered and mid > 0


@pytest.mark.parametrize("params", [
    {"tpu_row_scheduling": "full"},
    {"tpu_row_scheduling": "level", "max_depth": 4},
], ids=["masked", "level"])
def test_growers_that_partition_no_segment_count_nothing(params):
    """The masked grower and the level grower keep no ``order``: their
    trees carry no ``part_rows`` and add no zero to the counters, which
    stay absent where only they grew trees."""
    X, y = dense_table(3_000)
    fresh = timer.Timer()
    with mock.patch.object(gbdt_mod, "global_timer", fresh):
        booster = lgb.Booster({**PARAMS, "num_leaves": 15, **params},
                              lgb.Dataset(X, label=y))
        booster.update()
        trees = booster._engine.models
    assert fresh.counters["trees"] == 1 and trees[0].num_leaves > 1
    assert trees[0].part_rows is None
    assert not set(KEYS) & set(fresh.counters)


def test_a_hybrid_handoff_counts_the_compact_loops_splits():
    """The hybrid's level phase sorts the rows by leaf once, which is not a
    partition here: its tail's splits alone are counted."""
    X, y = dense_table(3_000)
    booster = lgb.Booster({**PARAMS, "num_leaves": 31,
                           "tpu_row_scheduling": "level"},
                          lgb.Dataset(X, label=y))
    booster.update()
    tree = booster._engine.models[0]
    live, read, ordered, _ = tree.part_rows
    assert 0 < live < int(tree.internal_count[:tree.num_leaves - 1].sum())
    assert live < read <= ordered


def _compile_script():
    spec = importlib.util.spec_from_file_location(
        "tpu_compile_grower_for_tests",
        os.path.join(ROOT, "scripts", "tpu_compile_grower.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("backend", ["scatter", "pallas"])
def test_the_record_adds_no_conditional_and_sits_in_no_branch(backend,
                                                              monkeypatch):
    """The compiled grower (words kernel interpreted, or the CPU's scatter)
    with the record and with it taken out: the same ``conditional``s, and
    the record's one ``dynamic-update-slice`` of ``s32[L-1, 2]`` in no
    computation that a ``conditional``'s branches reach."""
    script = _compile_script()
    F, B, R, L = 6, 32, 3000, 12
    meta = FeatureMeta(num_bin=jnp.full((F,), B, jnp.int32),
                       missing_type=jnp.zeros((F,), jnp.int32),
                       default_bin=jnp.zeros((F,), jnp.int32),
                       is_categorical=jnp.zeros((F,), bool))
    cfg = GrowerConfig(num_leaves=L, num_bin=B, row_sched="compact",
                       hist_rm_backend=backend, partition_mode="scatter",
                       min_bucket=256, packed_cols=F, block_rows=512,
                       hist_dtype="float32",
                       hparams=SplitHyperParams(min_data_in_leaf=5))
    bins = jax.ShapeDtypeStruct((R, 2), jnp.uint32)
    gh = jax.ShapeDtypeStruct((R, 3), jnp.float32)

    def compiled():
        text = jax.jit(make_tree_grower(cfg, meta)).lower(
            bins, gh).compile().as_text()
        records = [line for line in text.splitlines()
                   if " dynamic-update-slice(" in line and
                   f"= s32[{L - 1},2]" in line]
        branched, conditionals = script.in_branches(
            text, f"s32[{L - 1},2]", "dynamic-update-slice")
        return len(records), branched, conditionals

    records, branched, conditionals = compiled()
    monkeypatch.setattr(grower_mod, "_record_partition",
                        lambda calls, *_: calls)
    assert compiled() == (0, [], conditionals)
    assert records == 1 and branched == []
    assert conditionals >= 1


def test_compile_script_finds_what_sits_in_a_branch():
    """``in_branches`` follows a ``conditional``'s branches down to the
    computations they call, and no further than they reach."""
    script = _compile_script()
    text = "\n".join([
        "%fused (a: s32[7,2]) -> s32[7,2] {",
        "  %d.1 = s32[7,2]{1,0} dynamic-update-slice(%a, %u, %i, %z)",
        "}",
        "%branch_0 (p: s32[7,2]) -> s32[7,2] {",
        "  ROOT %f.2 = s32[7,2]{1,0} fusion(%p), kind=kLoop, calls=%fused",
        "}",
        "%branch_1 (p: s32[7,2]) -> s32[7,2] {",
        "  ROOT %c.3 = s32[7,2]{1,0} copy(%p)",
        "}",
        "%body (s: s32[7,2]) -> s32[7,2] {",
        "  %d.4 = s32[7,2]{1,0} dynamic-update-slice(%s, %u, %i, %z)",
        "  ROOT %k.5 = s32[7,2]{1,0} conditional(%b, %d.4, %d.4), "
        "branch_computations={%branch_0, %branch_1}",
        "}"])
    assert script.in_branches(text, "s32[7,2]", "dynamic-update-slice") \
        == ([("%fused", "%d.1")], 1)
    assert script.in_branches(text, "s32[7,3]", "dynamic-update-slice") \
        == ([], 1)
