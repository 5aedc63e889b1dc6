"""Compact (O(rows_in_leaf)) row scheduling vs the full masked-pass grower.

The compact scheduler (grower.py row_sched="compact") must reproduce the
full grower split-for-split: same features/thresholds/partitions — the same
triangle the reference closes between its indexed histogram construction and
a naive full scan (ref: src/treelearner/serial_tree_learner.cpp:368-386
smaller-child scheduling, src/io/data_partition.hpp DataPartition).
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import pack_words
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.bundling import find_bundles, pack_bins
from lightgbm_tpu.io.dataset_core import BinnedDataset
from lightgbm_tpu.ops.split import FeatureMeta, SplitHyperParams
from lightgbm_tpu.core import grower as grower_mod
from lightgbm_tpu.core.grower import GrowerConfig, make_tree_grower
from lightgbm_tpu.core.tree import HostTree


def _make_data(rng, n=3000, f=6):
    X = rng.normal(size=(n, f))
    X[:, 1] = rng.integers(0, 12, size=n)
    X[:, 2] = np.where(rng.random(n) < 0.7, 0.0, X[:, 2])
    X[rng.random(n) < 0.15, 3] = np.nan
    y = (X[:, 0] * 1.5 + np.sin(X[:, 1]) + np.nan_to_num(X[:, 2]) ** 2 * 0.3
         + rng.normal(scale=0.1, size=n))
    return X, y


def _grow(ds, gh, num_leaves, hp, row_sched, partition_mode="scatter",
          min_bucket=256, forced=None, monotone=None):
    mappers = ds.used_bin_mappers()
    meta = FeatureMeta.from_mappers(mappers, monotone)
    B = int(max(m.num_bin for m in mappers))
    gcfg = GrowerConfig(num_leaves=num_leaves, num_bin=B, hparams=hp,
                        hist_backend="scatter", block_rows=512,
                        row_sched=row_sched, hist_dtype="float32", hist_rm_backend="scatter",
                        partition_mode=partition_mode, min_bucket=min_bucket)
    grow = jax.jit(make_tree_grower(gcfg, meta, forced=forced))
    bins = ds.bins if row_sched == "full" else \
        np.ascontiguousarray(ds.bins.T)
    tree, leaf_id = grow(jnp.asarray(bins), jnp.asarray(gh))
    return (HostTree(jax.tree.map(np.asarray, tree), ds.used_feature_map),
            np.asarray(leaf_id))


def _assert_same_tree(a, b, num_leaves):
    ha, la = a
    hb, lb = b
    assert ha.num_leaves == hb.num_leaves
    np.testing.assert_array_equal(ha.split_feature_inner,
                                  hb.split_feature_inner)
    np.testing.assert_array_equal(ha.threshold_bin, hb.threshold_bin)
    np.testing.assert_array_equal(ha.default_left, hb.default_left)
    np.testing.assert_array_equal(la, lb)
    np.testing.assert_allclose(ha.leaf_value[:num_leaves],
                               hb.leaf_value[:num_leaves], rtol=1e-5)


@pytest.mark.parametrize("partition_mode", ["scatter", "sort"])
def test_compact_matches_full(rng, partition_mode):
    X, y = _make_data(rng)
    cfg = Config({"num_leaves": 16, "min_data_in_leaf": 5})
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    hp = SplitHyperParams(min_data_in_leaf=5)
    grad = -(y.astype(np.float32))
    gh = np.stack([grad, np.ones_like(grad), np.ones_like(grad)], axis=1)
    full = _grow(ds, gh, 16, hp, "full")
    comp = _grow(ds, gh, 16, hp, "compact", partition_mode)
    _assert_same_tree(full, comp, 16)


def test_compact_with_bagging_mask(rng):
    """Bagged-out rows ride along in segments with zero gh; masked counts
    drive splits while raw counts drive scheduling."""
    X, y = _make_data(rng, n=4000)
    cfg = Config({"num_leaves": 12, "min_data_in_leaf": 5})
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    hp = SplitHyperParams(min_data_in_leaf=5)
    grad = -(y.astype(np.float32))
    m = (rng.random(len(y)) < 0.7).astype(np.float32)
    gh = np.stack([grad * m, m, m], axis=1)
    full = _grow(ds, gh, 12, hp, "full")
    comp = _grow(ds, gh, 12, hp, "compact")
    _assert_same_tree(full, comp, 12)


def test_compact_min_bucket_bigger_than_rows(rng):
    """Tiny dataset: single bucket covering all rows."""
    X, y = _make_data(rng, n=300)
    cfg = Config({"num_leaves": 8, "min_data_in_leaf": 3})
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    hp = SplitHyperParams(min_data_in_leaf=3)
    grad = -(y.astype(np.float32))
    gh = np.stack([grad, np.ones_like(grad), np.ones_like(grad)], axis=1)
    full = _grow(ds, gh, 8, hp, "full")
    comp = _grow(ds, gh, 8, hp, "compact", min_bucket=4096)
    _assert_same_tree(full, comp, 8)


def test_compact_forced_splits(rng):
    X, y = _make_data(rng)
    cfg = Config({"num_leaves": 8, "min_data_in_leaf": 5})
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    hp = SplitHyperParams(min_data_in_leaf=5)
    grad = -(y.astype(np.float32))
    gh = np.stack([grad, np.ones_like(grad), np.ones_like(grad)], axis=1)
    L = 8
    active = np.zeros(L - 1, bool)
    slot = np.zeros(L - 1, np.int32)
    feat = np.zeros(L - 1, np.int32)
    thr = np.zeros(L - 1, np.int32)
    active[0], slot[0], feat[0], thr[0] = True, 0, 1, 3
    active[1], slot[1], feat[1], thr[1] = True, 1, 0, 10
    forced = (active, slot, feat, thr)
    full = _grow(ds, gh, L, hp, "full", forced=forced)
    comp = _grow(ds, gh, L, hp, "compact", forced=forced)
    _assert_same_tree(full, comp, L)
    assert full[0].split_feature_inner[0] == 1


def test_compact_monotone(rng):
    X, y = _make_data(rng)
    cfg = Config({"num_leaves": 12, "min_data_in_leaf": 5})
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    hp = SplitHyperParams(min_data_in_leaf=5)
    grad = -(y.astype(np.float32))
    gh = np.stack([grad, np.ones_like(grad), np.ones_like(grad)], axis=1)
    mono = np.zeros(ds.num_used_features, np.int32)
    mono[0] = 1
    full = _grow(ds, gh, 12, hp, "full", monotone=mono)
    comp = _grow(ds, gh, 12, hp, "compact", monotone=mono)
    _assert_same_tree(full, comp, 12)


# ---- the partition's column fetch: packed, unpacked and full agree --------

def _grow_with_order(gcfg, meta, bundle, bins, gh):
    """(tree, leaf_id, order): ``order`` is the split loop's state, which
    ``grow`` does not return; it is read off the loop's outputs in the
    jaxpr as the one int32 [R] that is a permutation of the rows."""
    closed, shapes = jax.make_jaxpr(
        make_tree_grower(gcfg, meta, bundle=bundle), return_shape=True)(
            bins, gh)
    loop = [e for e in closed.jaxpr.eqns
            if e.primitive.name in ("scan", "while")][-1]
    R = gh.shape[0]
    extra = [v for v in loop.outvars
             if v.aval.shape == (R,) and v.aval.dtype == jnp.int32]
    both = closed.jaxpr.replace(
        outvars=list(closed.jaxpr.outvars) + extra)
    n_out = len(closed.jaxpr.outvars)
    out = jax.jit(lambda b, g: jax.core.eval_jaxpr(
        both, closed.consts, b, g))(bins, gh)
    tree, leaf_id = jax.tree.unflatten(jax.tree.structure(shapes),
                                       out[:n_out])
    perms = [np.asarray(o) for o in out[n_out:]
             if np.array_equal(np.sort(np.asarray(o)), np.arange(R))]
    assert len(perms) == 1
    return jax.tree.map(np.asarray, tree), np.asarray(leaf_id), perms[0]


def _odd_columns(rng):
    # 6 columns -> 2 words, the second part-filled; the signal sits in
    # its last column
    X = rng.normal(size=(3000, 6))
    y = 2.0 * X[:, 5] + np.sin(3 * X[:, 4]) + 0.2 * X[:, 0]
    return X, y, {}


def _efb(rng):
    # six mutually exclusive one-hots bundle into one physical column
    n = 3000
    cat = rng.integers(0, 6, size=n)
    X = np.zeros((n, 11))
    X[np.arange(n), cat] = 1.0
    X[:, 6:] = rng.normal(size=(n, 5))
    y = (cat % 2) * 2.0 + (cat == 3) + 0.3 * X[:, 7]
    return X, y, {"bundle": True}


def _clipped_start(rng):
    # 2500 rows, buckets [2500, 2048]: a right child in the 2048 bucket
    # starts past R - P, so start_c is clipped and delta > 0
    X = rng.normal(size=(2500, 7))
    y = X[:, 6] + 0.5 * X[:, 1] * X[:, 2]
    return X, y, {"min_bucket": 2048}


def _tables(rng, case, L=16, **grower):
    """A case's table as the compact grower takes it: (config for
    unpacked uint8 rows, meta, bundle, physical bins [Fp, R], the same
    row-major uint8 [R, Fp], gh, the raw matrix X)."""
    X, y, opt = case(rng)
    ds = BinnedDataset.from_matrix(
        X, Config({"num_leaves": L, "min_data_in_leaf": 5,
                   **opt.get("params", {})}), label=y,
        categorical_features=opt.get("categorical", ()))
    mappers = ds.used_bin_mappers()
    meta = FeatureMeta.from_mappers(mappers)
    B = int(max(m.num_bin for m in mappers))
    phys, bundle = ds.bins, None                      # [F, R]
    if opt.get("bundle"):
        info = find_bundles(ds.bins, np.asarray(
            [m.num_bin for m in mappers], np.int64), max_conflict_rate=0.0)
        B = int(max(B, info.group_num_bin.max()))
        info.build_gather_map(B)
        phys = pack_bins(ds.bins, info)               # [G, R]
        bundle = dict(gather_map=info.gather_map, group=info.group,
                      offset=info.offset, default_bin=info.default_bin,
                      num_bin=info.num_bin, num_groups=info.num_groups)
        assert phys.shape[0] < ds.bins.shape[0]
    grad = -(y.astype(np.float32))
    gh = jnp.asarray(np.stack([grad, np.ones_like(grad),
                               np.ones_like(grad)], axis=1))
    rm = np.ascontiguousarray(phys.T).astype(np.uint8)
    assert rm.shape[1] % 4 != 0                # a part-filled last word
    base = GrowerConfig(
        num_leaves=L, num_bin=B, hparams=SplitHyperParams(min_data_in_leaf=5),
        hist_backend="scatter", block_rows=512, hist_dtype="float32",
        hist_rm_backend="scatter", partition_mode="scatter",
        min_bucket=opt.get("min_bucket", 256), row_sched="compact")
    return (dataclasses.replace(base, **grower), meta, bundle, phys, rm, gh,
            X)


def _assert_case_exercised(case, tree, bundle, X):
    """The tree touches what the case is named for."""
    feats = tree.split_feature[:int(tree.num_leaves) - 1]
    if case is _odd_columns:
        # a split on the last word's columns (4, 5 of 6)
        assert (feats >= 4).any()
    if case is _efb:
        # a split on a feature that shares its physical column
        group = np.asarray(bundle["group"])
        shared = np.bincount(group)[group] > 1
        assert shared[feats].any()
    if case is _clipped_start:
        # the root's right child is split again, in the 2048 bucket, and
        # starts where the left child ends: past R - P
        lc, rc = int(tree.left_child[0]), int(tree.right_child[0])
        n_left = (tree.internal_count[lc] if lc >= 0
                  else tree.leaf_count[~lc])
        assert rc >= 0 and n_left > X.shape[0] - 2048


def _assert_same_growth(a, b):
    """(tree, leaf_id, order) equal element for element."""
    np.testing.assert_array_equal(a[2], b[2])
    np.testing.assert_array_equal(a[1], b[1])
    for x, y in zip(jax.tree.leaves(a[0]), jax.tree.leaves(b[0])):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("partition_mode", ["scatter", "sort"])
@pytest.mark.parametrize("case", [_odd_columns, _efb, _clipped_start])
def test_partition_fetch_packed_unpacked_full_agree(rng, case,
                                                    partition_mode):
    """One column out of the table, then the leaf's rows out of the
    column: ``order``, ``leaf_id`` and the tree are the same, element for
    element, on packed words, on plain uint8 bins and (tree and
    ``leaf_id``) on the full-row grower that keeps no ``order``."""
    compact, meta, bundle, phys, rm, gh, X = _tables(
        rng, case, partition_mode=partition_mode)
    unpacked = _grow_with_order(compact, meta, bundle, jnp.asarray(rm), gh)
    t_p, l_p, o_p = packed = _grow_with_order(
        dataclasses.replace(compact, packed_cols=rm.shape[1]), meta, bundle,
        jnp.asarray(pack_words(rm)), gh)
    _assert_same_growth(packed, unpacked)
    t_f, l_f = jax.jit(make_tree_grower(
        dataclasses.replace(compact, row_sched="full"), meta,
        bundle=bundle))(jnp.asarray(phys), gh)
    np.testing.assert_array_equal(l_p, np.asarray(l_f))
    for name in ("split_feature", "threshold_bin", "default_left",
                 "left_child", "right_child", "num_leaves"):
        np.testing.assert_array_equal(getattr(t_p, name),
                                      np.asarray(getattr(t_f, name)))
    np.testing.assert_allclose(t_p.leaf_value, np.asarray(t_f.leaf_value),
                               rtol=1e-5)
    _assert_case_exercised(case, t_p, bundle, X)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("case", [_odd_columns, _efb, _clipped_start])
def test_words_kernel_packed_unpacked_agree(rng, case, quantized,
                                            monkeypatch):
    """With the Pallas kernel (interpreted here) packed words go to it as
    they are gathered and it takes each byte out itself; unpacked uint8
    rows go through ``hist_pallas_rm``. Same row blocks, same sums: the
    tree, ``leaf_id`` and ``order`` are equal element for element, with a
    part-filled last word, with EFB bundles, with a clipped segment, on
    float and on int8 gradients."""
    compact, meta, bundle, _, rm, gh, X = _tables(
        rng, case, L=8, hist_rm_backend="pallas", quantized=quantized,
        stochastic_rounding=False)
    unpacked = _grow_with_order(compact, meta, bundle, jnp.asarray(rm), gh)
    # this is about the two kernels on the same row blocks: the first
    # split's masked pass over the table (packed words only, other row
    # blocks: tested below) stays out of it
    monkeypatch.setattr(grower_mod, "first_split_dense_rows",
                        lambda rows, words, cols: rows)
    packed = _grow_with_order(
        dataclasses.replace(compact, packed_cols=rm.shape[1]), meta, bundle,
        jnp.asarray(pack_words(rm)), gh)
    _assert_same_growth(packed, unpacked)
    assert packed[0].num_leaves == 8
    _assert_case_exercised(case, packed[0], bundle, X)


# ---- the first split runs dense --------------------------------------------

def _categorical(rng):
    # a 9-valued categorical column whose odd values carry the signal
    n = 3000
    X = rng.normal(size=(n, 6))
    X[:, 0] = rng.integers(0, 9, size=n)
    y = 3.0 * (X[:, 0] % 2) + 0.2 * X[:, 3]
    return X, y, {"categorical": [0]}


def _default_bin(rng):
    # zeros are missing values (missing_type "zero"): they sit in the
    # column's default bin, carry the low labels, and the root sends them
    # left by ``default_left``
    n = 3000
    X = rng.normal(size=(n, 6))
    X[:, 5] = np.abs(X[:, 5]) + 0.5
    X[rng.random(n) < 0.4, 5] = 0.0
    y = np.where(X[:, 5] == 0.0, -4.0, X[:, 5]) + 0.1 * X[:, 1]
    return X, y, {"params": {"zero_as_missing": True}}


def _lopsided(rng):
    # the root split sends 0.4 % of the rows one way: under the 32-row
    # bucket, which the rule's line (34 rows at 6 columns since PR 36's
    # price of the kernel) keeps gathered
    X = rng.normal(size=(3000, 6))
    X[:, 2] = rng.random(3000) < 0.004
    y = 50.0 * X[:, 2] + 0.2 * X[:, 0]
    return X, y, {"min_bucket": 32}


@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("case", [_odd_columns, _categorical, _efb,
                                  _default_bin])
def test_first_split_partition_in_place(rng, case, packed):
    """A tree's first split reads its column in place (``order`` is the
    identity there): ``order`` after it is the stable partition of the rows
    by the leaf the full-row grower, which gathers nothing and keeps no
    ``order``, gives each, and ``nL`` its left count: the same integers as
    the gathered partition wrote, on a numerical, a categorical and a
    bundled column and with ``default_left`` deciding the default bin."""
    compact, meta, bundle, phys, rm, gh, X = _tables(rng, case, L=2)
    if packed:
        compact = dataclasses.replace(compact, packed_cols=rm.shape[1])
        rm = pack_words(rm)
    tree, leaf_id, order = _grow_with_order(compact, meta, bundle,
                                            jnp.asarray(rm), gh)
    t_f, l_f = jax.jit(make_tree_grower(
        dataclasses.replace(compact, row_sched="full", packed_cols=0), meta,
        bundle=bundle))(jnp.asarray(phys), gh)
    l_f = np.asarray(l_f)
    assert tree.num_leaves == 2
    np.testing.assert_array_equal(leaf_id, l_f)
    np.testing.assert_array_equal(order, np.argsort(l_f, kind="stable"))
    assert tree.leaf_count[0] == (l_f == 0).sum()          # nL
    assert 0 < tree.leaf_count[0] < X.shape[0]
    f = int(tree.split_feature[0])
    if case is _categorical:
        assert tree.cat_count[0] > 0
    if case is _efb:
        group = np.asarray(bundle["group"])
        assert np.bincount(group)[group[f]] > 1
    if case is _default_bin:
        assert f == 5 and tree.default_left[0]
        assert int(np.asarray(meta.missing_type)[f]) == 1
        assert ((X[:, 5] == 0.0) == (l_f == 0)).all()


def _same_tree(a, b, exact):
    """Structure, thresholds and counts equal; values to float32's
    tolerance, or every array bit for bit."""
    for name in a._fields:
        x, y = getattr(a, name), getattr(b, name)
        if x is None or name in ("first_split_dense", "hist_rows"):
            continue                # what the grower did, not the tree
        if exact or x.dtype.kind in "ib":
            np.testing.assert_array_equal(x, y, err_msg=name)
        elif name.endswith("_count"):
            np.testing.assert_array_equal(x, y, err_msg=name)
        else:
            np.testing.assert_allclose(x, y, rtol=2e-5, atol=1e-6,
                                       err_msg=name)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("case", [_odd_columns, _efb, _clipped_start])
def test_first_split_dense_trees_match_gathered(rng, case, quantized,
                                                monkeypatch):
    """Whole trees on the words kernel (interpreted), 3000 and 2500 rows:
    the first split's smaller child as one masked pass over the table in
    place against the same child gathered. The same rows are summed in
    another order: structure, thresholds, counts, ``leaf_id`` and ``order``
    equal, values to float32's tolerance, and on int8 gradients every
    array bit for bit."""
    compact, meta, bundle, _, rm, gh, X = _tables(
        rng, case, L=8, hist_rm_backend="pallas", quantized=quantized,
        stochastic_rounding=False)
    compact = dataclasses.replace(compact, packed_cols=rm.shape[1])
    words = jnp.asarray(pack_words(rm))
    dense = _grow_with_order(compact, meta, bundle, words, gh)
    monkeypatch.setattr(grower_mod, "first_split_dense_rows",
                        lambda rows, words, cols: rows)
    gathered = _grow_with_order(compact, meta, bundle, words, gh)
    assert dense[0].first_split_dense == 1
    assert gathered[0].first_split_dense == 0
    np.testing.assert_array_equal(dense[2], gathered[2])
    np.testing.assert_array_equal(dense[1], gathered[1])
    _same_tree(dense[0], gathered[0], exact=quantized)
    assert dense[0].num_leaves == 8
    _assert_case_exercised(case, dense[0], bundle, X)


@pytest.mark.parametrize("case,backend,resume,dense", [
    (_odd_columns, "pallas", False, 1),      # balanced: the masked pass
    (_lopsided, "pallas", False, 0),         # 0.4 % / 99.6 %: gathered
    (_odd_columns, "scatter", False, 0),     # no kernel that reads in place
    (_odd_columns, "scatter", True, 0),      # hybrid handoff past step 0
], ids=["balanced", "lopsided", "scatter", "hybrid_resume"])
def test_first_split_rule(rng, case, backend, resume, dense):
    """What the first split takes, from what the code sees: the masked
    pass where the kernel reads the table in place and the smaller
    child's bucket is over the rule's line; the gathered call for a
    lopsided split and for a backend that pays per row (its partition is
    in place all the same); neither once a hybrid handoff resumes past
    step 0, where ``order`` is an argsort. The tree is the full-row
    grower's in every case."""
    compact, meta, bundle, phys, rm, gh, X = _tables(
        rng, case, L=8, hist_rm_backend=backend)
    R = rm.shape[0]
    assert R & (R - 1)                        # not a power of two
    t_f, l_f = jax.jit(make_tree_grower(
        dataclasses.replace(compact, row_sched="full"), meta,
        bundle=bundle))(jnp.asarray(phys), gh)
    if resume:
        from lightgbm_tpu.core.hybrid_grower import make_hybrid_grower
        tree, leaf_id = jax.tree.map(np.asarray, jax.jit(make_hybrid_grower(
            compact, meta, bundle=bundle, handoff_depth=2))(
                jnp.asarray(rm), gh))
    else:
        tree, leaf_id, _ = _grow_with_order(
            dataclasses.replace(compact, packed_cols=rm.shape[1]), meta,
            bundle, jnp.asarray(pack_words(rm)), gh)
    np.testing.assert_array_equal(leaf_id, np.asarray(l_f))
    assert tree.first_split_dense == dense
    assert tree.num_leaves == 8
    if case is _lopsided:
        # the smaller child's bucket is under the rule's line of 34 rows
        sides = [tree.internal_count[c] if c >= 0 else tree.leaf_count[~c]
                 for c in (int(tree.left_child[0]),
                           int(tree.right_child[0]))]
        assert int(tree.split_feature[0]) == 2 and min(sides) <= 32
        assert grower_mod.first_split_dense_rows(R, 2, 6) >= 32
    _same_tree(tree, jax.tree.map(np.asarray, t_f), exact=False)


# ---- the kernel is told the segment's rows inside its bucket ---------------

def _no_live_range(monkeypatch):
    """The kernel's entries as they were: every row block of the bucket."""
    from lightgbm_tpu.ops import hist_pallas
    for name in ("hist_pallas_words", "hist_pallas_rm"):
        entry = getattr(hist_pallas, name)
        monkeypatch.setattr(
            hist_pallas, name,
            lambda *a, live=None, entry=entry, **kw: entry(*a, **kw))


LIVE_CASES = {
    # (case, packed, table held twice, histogram pool)
    "words": (_odd_columns, True, False, "full"),
    "words_efb": (_efb, True, False, "full"),
    "words_clipped_at_the_end": (_clipped_start, True, False, "full"),
    "words_held_twice": (_odd_columns, True, True, "full"),
    "words_held_twice_no_pool": (_odd_columns, True, True, "none"),
    "unpacked": (_odd_columns, False, False, "full"),
    "unpacked_clipped_at_the_end": (_clipped_start, False, False, "full"),
}


def _grow_live_case(rng, name, monkeypatch, **grower):
    case, packed, twice, pool = LIVE_CASES[name]
    compact, meta, bundle, _, rm, gh, X = _tables(
        rng, case, L=8, **{"hist_rm_backend": "pallas", "hist_pool": pool,
                           "block_rows": 128, **grower})
    monkeypatch.setattr(grower_mod, "rows_held_twice", lambda words: twice)
    if packed:
        compact = dataclasses.replace(compact, packed_cols=rm.shape[1])
        rm = pack_words(rm)
    grow = lambda: _grow_with_order(compact, meta, bundle,  # noqa: E731
                                    jnp.asarray(rm), gh)
    return grow, compact, case, bundle, X


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("name", list(LIVE_CASES))
def test_live_range_grows_the_same_tree(rng, name, quantized, monkeypatch):
    """``hb`` hands the (interpreted) kernel its segment's rows inside the
    bucket and the kernel skips the row blocks outside them: the tree,
    ``leaf_id`` and ``order`` are what the kernel over every block grows,
    bit for bit, on packed words and unpacked bytes, with a segment clipped
    at the end of the table (``delta`` > 0), and with a table held twice,
    whose 2048 bucket is gathered as two blocks of 1024 rows, each handed
    the range cut to it."""
    grow, compact, case, bundle, X = _grow_live_case(
        rng, name, monkeypatch, quantized=quantized,
        stochastic_rounding=False)
    ranged = grow()
    _no_live_range(monkeypatch)
    every = grow()
    _assert_same_growth(ranged, every)
    assert ranged[0].num_leaves == 8
    assert 0 < ranged[0].hist_rows[1] < ranged[0].hist_rows[2]
    _assert_case_exercised(case, ranged[0], bundle, X)


def _gathered_calls(tree, R, sizes, both):
    """(start, rows, bucket) of a tree's gathered histogram calls, from
    its own child counts: node ``i`` splits the segment of the leaf its
    chain of left children ends in, the left child keeps the segment's
    start, and the smaller child (``both``: each child) is histogrammed in
    the smallest bucket that holds it."""
    def count(c):
        return int(tree.internal_count[c] if c >= 0 else tree.leaf_count[~c])

    seg, calls = {0: (0, R)}, []
    for i in range(int(tree.num_leaves) - 1):
        leaf = int(tree.left_child[i])
        while leaf >= 0:
            leaf = int(tree.left_child[leaf])
        start, rows = seg[~leaf]
        nl = count(int(tree.left_child[i]))
        assert nl + count(int(tree.right_child[i])) == rows
        seg[~leaf], seg[i + 1] = (start, nl), (start + nl, rows - nl)
        kids = [seg[~leaf], seg[i + 1]]
        if not both:
            kids = kids[:1] if nl <= rows - nl else kids[1:]
        calls.append([(s, n, min(S for S in sizes if S >= n))
                      for s, n in kids])
    return calls


@pytest.mark.parametrize("name,first_dense", [
    ("words", True), ("words", False), ("words_clipped_at_the_end", False),
    ("words_held_twice", False), ("words_held_twice_no_pool", False),
    ("unpacked", False), ("scatter", False), ("words_blocks_of_512", False),
    ("words_held_twice_blocks_of_512", False)])
def test_hist_rows_counts_the_gathered_calls(rng, name, first_dense,
                                             monkeypatch):
    """``TreeArrays.hist_rows`` against the tree's own child counts:
    ``live`` the rows of the children that were histogrammed, ``bucket``
    their buckets', ``read`` the rows of the bucket in the row blocks that
    overlap the segment, so ``live <= read <= bucket``. A row block is what
    the configuration asks (128 rows here, 512 in the last two cases) and a
    quarter of the bucket where that is less: of buckets of 3000, 2048,
    1024, 512 and 256 rows the last three take 256, 128 and 128. The calls
    that read the table in place are not gathered and not counted: a first
    split's masked pass, a table held twice in its bucket of every row. A
    backend with no kernel reads every row of its bucket."""
    asked = 128
    if name == "scatter":
        grow, compact, *_ = _grow_live_case(rng, "words", monkeypatch,
                                            hist_rm_backend="scatter")
    elif name.endswith("_blocks_of_512"):
        asked, name = 512, name[:-len("_blocks_of_512")]
        grow, compact, *_ = _grow_live_case(rng, name, monkeypatch,
                                            block_rows=512)
    else:
        grow, compact, *_ = _grow_live_case(rng, name, monkeypatch)
    if not first_dense:
        monkeypatch.setattr(grower_mod, "first_split_dense_rows",
                            lambda rows, words, cols: rows)
    tree, leaf_id, _ = grow()
    R = leaf_id.shape[0]
    assert tree.first_split_dense == first_dense
    sizes = grower_mod._bucket_sizes(R, compact.min_bucket)
    twice, pool = LIVE_CASES.get(name, LIVE_CASES["words"])[2:]
    calls = _gathered_calls(tree, R, sizes, both=pool == "none")
    want = np.zeros(3, np.int64)
    for start, rows, S in sum(calls[1 if first_dense else 0:], []):
        if twice and S == R:
            continue
        delta = start - min(max(start, 0), R - S)
        block = min(asked, max(128, S // 4))
        blocks = -(-(delta + rows) // block) - delta // block
        read = S if name == "scatter" else min(block * blocks, S)
        want += (rows, read, S)
    live, read, bucket = (int(x) for x in tree.hist_rows)
    assert (live, read, bucket) == tuple(want)
    assert 0 < live <= read <= bucket
    if name != "scatter":
        assert read < bucket and live < read
    host = HostTree(tree, np.arange(tree.split_feature.shape[0] + 8))
    assert host.hist_rows == (live, read, bucket)
