"""Reduce-scatter histogram collectives (ISSUE 12).

The contract under test: ``tpu_hist_reduce=reduce_scatter`` leaves each
device one contiguous feature slice of the summed histogram
(``lax.psum_scatter``), the split scan runs on the window with
globally-correct feature ids, and the per-device winners merge through
the tiny packed-record combine (≡ Network::ReduceScatter +
SyncUpGlobalBestSplit, network.h:90-276 / parallel_tree_learner.h:210)
— and the trees must be BIT-identical to both the allreduce mode and
the serial scan (exact int32 psum_scatter under quantized gradients;
dyadic f32 gradients make f32 sums association-free so the f32 legs of
the matrix are exact too; ties resolve by global feature index).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.core.grower import GrowerConfig, make_tree_grower
from lightgbm_tpu.ops.split import FeatureMeta, SplitHyperParams
from lightgbm_tpu.parallel import (build_mesh, make_data_parallel_grower,
                                   make_voting_parallel_grower,
                                   row_sharding)
from lightgbm_tpu.parallel.data_parallel import make_distributed_train_step
from lightgbm_tpu.parallel.mesh import feature_tile

N_DEV = 8


def _meta(F, B):
    return FeatureMeta(
        num_bin=jnp.full(F, B, jnp.int32),
        missing_type=jnp.zeros(F, jnp.int32),
        default_bin=jnp.zeros(F, jnp.int32),
        is_categorical=jnp.zeros(F, bool))


def _dyadic_gh(rng, n, weights=False):
    """Dyadic gradients (+ optional small-integer weights): every
    partial sum is exact in f32, so f32 histogram reductions are
    association-free and serial-vs-sharded bit-identity is meaningful
    for the f32 legs of the matrix, not just the quantized ones."""
    grad = (rng.integers(-8, 8, size=n) * 0.25).astype(np.float32)
    w = (rng.integers(1, 4, size=n).astype(np.float32) if weights
         else np.ones(n, np.float32))
    return np.stack([grad * w, w, w], axis=1)


def _toy(rng, n, F, B, weights=False):
    bins = rng.integers(0, B, size=(F, n)).astype(np.uint8)
    return bins, _dyadic_gh(rng, n, weights)


def _cfg(B, sched="compact", quant=False, leaves=15):
    return GrowerConfig(
        num_leaves=leaves, num_bin=B,
        hparams=SplitHyperParams(min_data_in_leaf=5),
        block_rows=512, row_sched=sched, hist_rm_backend="scatter",
        hist_backend="scatter" if sched == "full" else "xla",
        quantized=quant, stochastic_rounding=False)


def _tree_bytes(tree):
    """Bit-level tree identity: -0.0 vs 0.0 and every ulp count."""
    n = int(tree.num_leaves)
    return (n,
            np.asarray(tree.split_feature[:n - 1]).tobytes(),
            np.asarray(tree.threshold_bin[:n - 1]).tobytes(),
            np.asarray(tree.split_gain[:n - 1]).tobytes(),
            np.asarray(tree.leaf_value[:n]).tobytes(),
            np.asarray(tree.leaf_weight[:n]).tobytes(),
            np.asarray(tree.leaf_count[:n]).tobytes())


def _grow_all(cfg, meta, bins, gh, modes=("allreduce", "reduce_scatter"),
              voting_k=None):
    """(serial_tree, serial_leaf, {mode: (tree, leaf)}) on the test
    mesh; bins enter in the scheduling's layout."""
    bins_in = bins.T.copy() if cfg.row_sched == "compact" else bins
    serial = jax.jit(make_tree_grower(cfg, meta))
    tree_s, leaf_s = serial(jnp.asarray(bins_in), jnp.asarray(gh), None)
    mesh = build_mesh(N_DEV)
    rowdim = 0 if cfg.row_sched == "compact" else 1
    b = jax.device_put(bins_in, row_sharding(mesh, rowdim, 2))
    g = jax.device_put(gh, row_sharding(mesh, 0, 2))
    out = {}
    for mode in modes:
        if voting_k is not None:
            grow = make_voting_parallel_grower(cfg, meta, mesh,
                                               top_k=voting_k,
                                               hist_reduce=mode)
        else:
            grow = make_data_parallel_grower(cfg, meta, mesh,
                                             hist_reduce=mode)
        out[mode] = jax.jit(grow)(b, g, None)
    return tree_s, leaf_s, out


# ---------------------------------------------------------------------------
# the bit-identity matrix (acceptance): serial vs data-parallel under
# BOTH reduce modes x {f32 dyadic, quantized int, weighted rows,
# ragged Fp (pad slice), 255 leaves}
# ---------------------------------------------------------------------------

# even tiles and a pad slice; one fast representative (dyadic F=16),
# the other three cells behind -m slow (comms_smoke.py gates parity on
# both dtypes every check.sh run)
@pytest.mark.parametrize("F", [16, pytest.param(11, marks=pytest.mark.slow)])
@pytest.mark.parametrize("quant", [
    False, pytest.param(True, marks=pytest.mark.slow)])
def test_matrix_serial_vs_data_both_modes(rng, F, quant):
    bins, gh = _toy(rng, 2048, F, 32)
    tree_s, leaf_s, out = _grow_all(_cfg(32, quant=quant), _meta(F, 32),
                                    bins, gh)
    for mode, (tree_d, leaf_d) in out.items():
        assert _tree_bytes(tree_s) == _tree_bytes(tree_d), (F, quant, mode)
        np.testing.assert_array_equal(np.asarray(leaf_s),
                                      np.asarray(leaf_d))


@pytest.mark.slow
def test_matrix_full_sched_and_weighted(rng):
    """full (masked-pass) scheduling + weighted rows legs."""
    bins, gh = _toy(rng, 2048, 16, 32, weights=True)
    tree_s, leaf_s, out = _grow_all(
        _cfg(32, sched="full", quant=True), _meta(16, 32), bins, gh)
    for mode, (tree_d, leaf_d) in out.items():
        assert _tree_bytes(tree_s) == _tree_bytes(tree_d), mode
        np.testing.assert_array_equal(np.asarray(leaf_s),
                                      np.asarray(leaf_d))


@pytest.mark.slow
def test_matrix_255_leaves(rng):
    bins, gh = _toy(rng, 8192, 12, 64)
    cfg = _cfg(64, quant=True, leaves=255)
    tree_s, leaf_s, out = _grow_all(cfg, _meta(12, 64), bins, gh,
                                    modes=("reduce_scatter",))
    tree_d, leaf_d = out["reduce_scatter"]
    assert int(tree_s.num_leaves) > 100   # the deep config actually grew
    assert _tree_bytes(tree_s) == _tree_bytes(tree_d)
    np.testing.assert_array_equal(np.asarray(leaf_s), np.asarray(leaf_d))


@pytest.mark.slow
def test_matrix_poolless(rng):
    """hist_pool='none' (the wide-table downgrade: both children
    histogrammed per split, no pool) composes with reduce_scatter —
    both child reductions window the same way."""
    bins, gh = _toy(rng, 2048, 11, 32)
    cfg = GrowerConfig(
        num_leaves=15, num_bin=32,
        hparams=SplitHyperParams(min_data_in_leaf=5), block_rows=512,
        row_sched="compact", hist_rm_backend="scatter",
        hist_pool="none", quantized=True, stochastic_rounding=False)
    tree_s, leaf_s, out = _grow_all(cfg, _meta(11, 32), bins, gh)
    for mode, (tree_d, leaf_d) in out.items():
        assert _tree_bytes(tree_s) == _tree_bytes(tree_d), mode
        np.testing.assert_array_equal(np.asarray(leaf_s),
                                      np.asarray(leaf_d))


@pytest.mark.slow
@pytest.mark.parametrize("quant", [False, True])
def test_voting_modes_match(rng, quant):
    """Voting composes: with full coverage (2*top_k >= F) both reduce
    modes equal serial; the selected top-2k hists reduce-scatter the
    same way the data-parallel full set does."""
    bins, gh = _toy(rng, 2048, 11, 32)
    tree_s, leaf_s, out = _grow_all(_cfg(32, quant=quant), _meta(11, 32),
                                    bins, gh, voting_k=11)
    for mode, (tree_v, leaf_v) in out.items():
        assert _tree_bytes(tree_s) == _tree_bytes(tree_v), mode
        np.testing.assert_array_equal(np.asarray(leaf_s),
                                      np.asarray(leaf_v))


@pytest.mark.slow
def test_voting_small_k_modes_match(rng):
    """Partial coverage (the lossy-vote regime): the two reduce modes
    must still agree with EACH OTHER bit-for-bit (same vote, same
    candidate set, different histogram layout only)."""
    bins, gh = _toy(rng, 4096, 16, 32)
    cfg = _cfg(32, quant=True)
    _, _, out = _grow_all(cfg, _meta(16, 32), bins, gh, voting_k=3)
    tree_a, leaf_a = out["allreduce"]
    tree_r, leaf_r = out["reduce_scatter"]
    assert _tree_bytes(tree_a) == _tree_bytes(tree_r)
    np.testing.assert_array_equal(np.asarray(leaf_a), np.asarray(leaf_r))


# ---------------------------------------------------------------------------
# sharded-argmax tie-break (acceptance): byte-equal gains on different
# shards must pick the lower global feature id
# ---------------------------------------------------------------------------

def test_tiebreak_across_shards_picks_lower_feature_id(rng):
    """Feature 9 is a byte-exact copy of feature 2 — identical
    histograms, identical gains — living in a DIFFERENT device window
    (8 devices x 2-feature tiles: feature 2 on device 1, feature 9 on
    device 4). The serial scan's first-seen argmax picks 2; the sharded
    window scan + combine must too, at every split of the tree."""
    F, B, n = 16, 32, 2048
    assert feature_tile(F, N_DEV) == 2
    bins, gh = _toy(rng, n, F, B)
    bins[9] = bins[2]
    tree_s, leaf_s, out = _grow_all(_cfg(B, quant=True), _meta(F, B),
                                    bins, gh)
    tree_d, leaf_d = out["reduce_scatter"]
    feats = np.asarray(tree_d.split_feature[:int(tree_d.num_leaves) - 1])
    assert 2 in feats          # the duplicated signal is actually used
    assert 9 not in feats      # ties resolved to the LOWER global id
    assert _tree_bytes(tree_s) == _tree_bytes(tree_d)
    np.testing.assert_array_equal(np.asarray(leaf_s), np.asarray(leaf_d))


def test_no_valid_split_replicates_invalid_record(rng):
    """Degenerate case: min_data_in_leaf beyond the row count means NO
    device finds a valid split — every per-device record is invalid and
    the combine must still produce one replicated (single-leaf) tree."""
    bins, gh = _toy(rng, 256, 8, 16)
    cfg = GrowerConfig(num_leaves=7, num_bin=16,
                       hparams=SplitHyperParams(min_data_in_leaf=10_000),
                       block_rows=256, row_sched="compact",
                       hist_rm_backend="scatter")
    tree_s, _, out = _grow_all(cfg, _meta(8, 16), bins, gh,
                               modes=("reduce_scatter",))
    tree_d, _ = out["reduce_scatter"]
    assert int(tree_s.num_leaves) == 1
    assert int(tree_d.num_leaves) == 1


# ---------------------------------------------------------------------------
# collective bytes (acceptance): the reduce_scatter program must ship
# measurably fewer bytes per level, with NO full-histogram broadcast
# ---------------------------------------------------------------------------

def test_hlo_collective_bytes_drop(rng):
    from lightgbm_tpu.analysis.hlo import collective_wire_bytes
    F, B, n = 16, 32, 2048
    bins, gh = _toy(rng, n, F, B)
    cfg = _cfg(B, quant=True)
    meta = _meta(F, B)
    mesh = build_mesh(N_DEV)
    bins_in = bins.T.copy()
    b = jax.device_put(bins_in, row_sharding(mesh, 0, 2))
    g = jax.device_put(gh, row_sharding(mesh, 0, 2))
    texts = {}
    for mode in ("allreduce", "reduce_scatter"):
        grow = jax.jit(make_data_parallel_grower(cfg, meta, mesh,
                                                 hist_reduce=mode))
        texts[mode] = grow.lower(b, g, None).compile().as_text()
    hist_bytes = F * B * 3 * 4          # one int32 [F, B, 3] histogram
    ar = collective_wire_bytes(texts["allreduce"], N_DEV)
    rs = collective_wire_bytes(texts["reduce_scatter"], N_DEV)
    assert "reduce-scatter" in texts["reduce_scatter"]
    # the full-histogram broadcast is ABSENT from the steady-state
    # program: no all-reduce at (or above) the histogram size remains
    assert rs["max_allreduce_result"] < hist_bytes, rs
    assert ar["max_allreduce_result"] >= hist_bytes, ar
    # and the per-program wire total drops (2(N-1)/N|H| -> (N-1)/N|H|
    # on the histogram reductions; the combine adds only tiny records)
    assert rs["total"] < ar["total"], (rs, ar)


# ---------------------------------------------------------------------------
# make_distributed_train_step: the "serial" silent-remap fix (satellite)
# ---------------------------------------------------------------------------

def test_train_step_serial_remap_logs_and_trains(rng):
    from lightgbm_tpu.utils import log as lgb_log
    F, B, n = 8, 32, 2048
    bins, gh = _toy(rng, n, F, B)
    cfg = GrowerConfig(num_leaves=15, num_bin=B,
                       hparams=SplitHyperParams(min_data_in_leaf=5),
                       block_rows=512, row_sched="full",
                       hist_backend="scatter")
    meta = _meta(F, B)
    mesh = build_mesh(N_DEV)
    y = (gh[:, 0] > 0).astype(np.float32)
    grad_fn = lambda s, lbl: (s - lbl, jnp.ones_like(s))
    lgb_log.logged_once.clear()
    # capture through the log layer itself: earlier suite tests train
    # with verbose=-1, which lowers the GLOBAL log level below INFO —
    # stderr capture would see nothing through no fault of the remap
    msgs = []
    old_level = lgb_log._level
    lgb_log.register_logger(msgs.append)
    lgb_log.set_verbosity(lgb_log.INFO)
    try:
        step = make_distributed_train_step(cfg, meta, mesh, grad_fn,
                                           0.1, tree_learner="serial")
        # and again: the remap notice fires ONCE per process
        make_distributed_train_step(cfg, meta, mesh, grad_fn, 0.1,
                                    tree_learner="serial")
    finally:
        lgb_log.register_logger(None)
        lgb_log.set_verbosity(old_level)
    hits = [m for m in msgs if "DATA-parallel grower" in m]
    assert len(hits) == 1, msgs
    assert "tree_learner='serial'" in hits[0]
    b = jax.device_put(bins, row_sharding(mesh, 1, 2))
    yv = jax.device_put(y, row_sharding(mesh, 0, 1))
    score = jax.device_put(np.zeros(n, np.float32),
                           row_sharding(mesh, 0, 1))
    mask = jax.device_put(np.ones(n, np.float32),
                          row_sharding(mesh, 0, 1))
    new_score, tree, _ = jax.jit(step)(b, yv, score, mask)
    assert int(tree.num_leaves) > 1
    assert not np.array_equal(np.asarray(new_score), np.zeros(n))


@pytest.mark.slow
def test_train_step_reduce_scatter_mode(rng):
    """hist_reduce threads through the step builder for both learners."""
    F, B, n = 8, 32, 2048
    bins, gh = _toy(rng, n, F, B)
    cfg = GrowerConfig(num_leaves=15, num_bin=B,
                       hparams=SplitHyperParams(min_data_in_leaf=5),
                       block_rows=512, row_sched="full",
                       hist_backend="scatter")
    meta = _meta(F, B)
    mesh = build_mesh(N_DEV)
    y = (gh[:, 0] > 0).astype(np.float32)
    grad_fn = lambda s, lbl: (s - lbl, jnp.ones_like(s))
    b = jax.device_put(bins, row_sharding(mesh, 1, 2))
    args = (b, jax.device_put(y, row_sharding(mesh, 0, 1)),
            jax.device_put(np.zeros(n, np.float32),
                           row_sharding(mesh, 0, 1)),
            jax.device_put(np.ones(n, np.float32),
                           row_sharding(mesh, 0, 1)))
    outs = {}
    for tl in ("data", "voting"):
        for mode in ("allreduce", "reduce_scatter"):
            step = make_distributed_train_step(
                cfg, meta, mesh, grad_fn, 0.1, tree_learner=tl,
                top_k=F, hist_reduce=mode)
            _, tree, _ = jax.jit(step)(*args)
            outs[(tl, mode)] = _tree_bytes(tree)
    assert outs[("data", "allreduce")] == outs[("data", "reduce_scatter")]
    assert outs[("voting", "allreduce")] == \
        outs[("voting", "reduce_scatter")]


# ---------------------------------------------------------------------------
# engine wiring: resolution, eligibility ladder, attribution
# ---------------------------------------------------------------------------

def _engine_data(rng, n=1500, f=10):
    X = rng.normal(size=(n, f))
    y = ((X[:, 0] * 2 - X[:, 1] + 0.5 * X[:, 2] * X[:, 3]) > 0
         ).astype(np.float64)
    return X, y


def _trees_only(booster):
    s = booster.model_to_string()
    return s.split("parameters:")[0].split("feature_importances")[0]


@pytest.mark.slow
def test_engine_quantized_bit_parity_and_attribution(rng):
    import lightgbm_tpu as lgb
    X, y = _engine_data(rng)
    base = {"objective": "binary", "verbose": -1, "num_leaves": 15,
            "min_data_in_leaf": 5, "seed": 7, "deterministic": True,
            "use_quantized_grad": True, "stochastic_rounding": False}
    serial = lgb.train(base, lgb.Dataset(X, label=y), num_boost_round=3)
    rs = lgb.train(
        dict(base, tree_learner="data", tpu_hist_reduce="reduce_scatter"),
        lgb.Dataset(X, label=y), num_boost_round=3)
    assert rs._engine._hist_reduce == "reduce_scatter"
    assert serial._engine._hist_reduce == "n/a"
    assert _trees_only(serial) == _trees_only(rs)


@pytest.mark.slow
def test_engine_fallback_attribution(rng):
    """Ineligible configs resolve to allreduce with the reason recorded
    (the PR6 level_backend contract: bench numbers must be attributable
    to the comm config that actually ran)."""
    import lightgbm_tpu as lgb
    X, y = _engine_data(rng, n=800)
    base = {"objective": "binary", "verbose": -1, "num_leaves": 7,
            "min_data_in_leaf": 5, "tree_learner": "data",
            "tpu_hist_reduce": "reduce_scatter"}
    cat = lgb.train(base, lgb.Dataset(X, label=y, categorical_feature=[0]),
                    num_boost_round=1)
    assert cat._engine._hist_reduce == "allreduce(fallback:categorical)"
    mono = lgb.train(dict(base, monotone_constraints=[1] + [0] * 9),
                     lgb.Dataset(X, label=y), num_boost_round=1)
    assert mono._engine._hist_reduce == "allreduce(fallback:monotone)"
    # the fallback mode trains fine (and identically to plain allreduce)
    ar = lgb.train(dict(base, tpu_hist_reduce="allreduce",
                        monotone_constraints=[1] + [0] * 9),
                   lgb.Dataset(X, label=y), num_boost_round=1)
    assert _trees_only(mono) == _trees_only(ar)


def test_resolve_hist_reduce_unit():
    from lightgbm_tpu.core.plan import make_plan

    def reduce_of(requested, num_data, platform):
        return make_plan(platform=platform, num_data=num_data,
                         num_bin_max=255, quantized=False,
                         hist_dtype="float32", tree_learner="data",
                         storage="dense", row_sched="compact",
                         hist_reduce=requested).hist_reduce
    # explicit values pass through (the learner's eligibility fallback
    # is the engine's, test_engine_fallback_attribution)
    assert reduce_of("reduce_scatter", 10, "cpu") == "reduce_scatter"
    assert reduce_of("allreduce", 10 ** 7, "tpu") == "allreduce"
    # auto is allreduce on both platforms, on both sides of the row gate
    for platform in ("cpu", "tpu"):
        for num_data in (100, 10 ** 7):
            assert reduce_of("auto", num_data, platform) == "allreduce"


def test_config_validates_hist_reduce_choice():
    import lightgbm_tpu as lgb
    with pytest.raises(ValueError, match="reduce_scater.*is not one of"):
        lgb.Dataset(np.zeros((50, 2)), label=np.zeros(50),
                    params={"tpu_hist_reduce": "reduce_scater"}
                    ).construct()


def test_grower_rejects_ineligible_window_configs():
    """Direct grower users get loud raises, not silent wrong trees."""
    meta = _meta(4, 8)
    cfg = GrowerConfig(num_leaves=3, num_bin=8)
    dummy = lambda *a: None
    with pytest.raises(ValueError, match="select_best"):
        make_tree_grower(cfg, meta, scan_window=dummy)
    mono = meta._replace(monotone=jnp.zeros(4, jnp.int32).at[0].set(1))
    with pytest.raises(ValueError, match="monotone"):
        make_tree_grower(cfg, mono, scan_window=dummy, select_best=dummy)
