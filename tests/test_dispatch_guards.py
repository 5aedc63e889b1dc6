"""Runtime dispatch guards (lightgbm_tpu.analysis.guards).

The compile-count regression test is the runtime half of the jaxlint
contract: a warmed-up training loop must NOT recompile per iteration.
It guards the level-grower steady-state win from the round-5 A/B session
(one compile per level width, cached across trees) and the leaf-wise
default alike — a regression that reintroduces per-iteration retraces
fails the budget instead of silently running 100x slow on TPU.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.analysis import guards


def _data(seed=5, n=2000, f=8):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] +
         0.1 * rng.normal(size=n) > 0).astype(np.float32)
    return X, y


@pytest.mark.parametrize("sched,max_depth", [
    ("full", -1),
    ("level", 6),     # pure level mode
    ("level", -1),    # HYBRID level+tail (the round-7 default-config
                      # path: level phase + traced-start fori tail —
                      # the traced k0 cut must not retrace per tree)
])
def test_train_one_iter_steady_state_compile_budget(compile_budget, sched,
                                                    max_depth):
    """5 post-warmup iterations of GBDT.train_one_iter stay within a
    2-compile budget (steady state is 0; the slack absorbs one-off eager
    primitives from host-side bookkeeping, never a per-iteration jit)."""
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "max_depth": max_depth, "tpu_row_scheduling": sched}
    booster = lgb.Booster(params, lgb.Dataset(X, label=y))
    for _ in range(3):  # warmup: trace + compile the training programs
        booster.update()
    with compile_budget(2, f"train_one_iter x5 [{sched}/{max_depth}]"):
        for _ in range(5):
            booster.update()


def test_hybrid_pallas_level_steady_state_compile_budget(compile_budget):
    """The sorted-segment Pallas level kernel (ISSUE 6) under the
    HYBRID grower: 5 post-warmup iterations stay within the same
    2-compile budget — per-depth pallas_call shapes are static inside
    the one jitted grow program, so a retrace per tree/depth (the
    failure mode the segment-aligned padding bound exists to prevent:
    a data-dependent block count would respecialize every call) blows
    the budget here."""
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "max_depth": -1, "max_bin": 63,
              "tpu_row_scheduling": "level",
              "tpu_hist_kernel": "pallas_level"}
    booster = lgb.Booster(params, lgb.Dataset(X, label=y))
    from lightgbm_tpu.core.level_grower import effective_level_backend
    assert effective_level_backend(
        booster._engine.grower_cfg) == "pallas_level"
    for _ in range(3):  # warmup: trace + compile the training programs
        booster.update()
    with compile_budget(2, "train_one_iter x5 [level/-1/pallas_level]"):
        for _ in range(5):
            booster.update()


def test_reduce_scatter_steady_state_compile_budget(compile_budget):
    """The reduce-scatter histogram collective (ISSUE 12) under the
    data-parallel learner: 5 post-warmup iterations stay within the
    same 2-compile budget — the feature-window slice indices and the
    psum_scatter padding are static inside the one jitted grow program,
    so neither the per-device window math nor the packed-record combine
    may respecialize per tree."""
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "tree_learner": "data", "tpu_num_devices": 2,
              "tpu_hist_reduce": "reduce_scatter",
              "use_quantized_grad": True}
    booster = lgb.Booster(params, lgb.Dataset(X, label=y))
    assert booster._engine._hist_reduce == "reduce_scatter"
    for _ in range(3):  # warmup: trace + compile the training programs
        booster.update()
    with compile_budget(2, "train_one_iter x5 [data/reduce_scatter]"):
        for _ in range(5):
            booster.update()


def _grower_compiled_text(make, cfg_kw):
    """Compile a grower at a tiny CPU geometry; return optimized HLO."""
    import re
    from lightgbm_tpu.core.grower import GrowerConfig
    from lightgbm_tpu.ops.split import FeatureMeta, SplitHyperParams
    F, B, R = 8, 64, 2048
    meta = FeatureMeta(
        num_bin=jnp.full((F,), B, jnp.int32),
        missing_type=jnp.zeros((F,), jnp.int32),
        default_bin=jnp.zeros((F,), jnp.int32),
        is_categorical=jnp.zeros((F,), bool),
        monotone=None)
    cfg = GrowerConfig(num_bin=B,
                       hparams=SplitHyperParams(min_data_in_leaf=20),
                       hist_rm_backend="scatter",
                       partition_mode="scatter", **cfg_kw)
    bins = jnp.zeros((R, F), jnp.uint8)
    gh = jnp.zeros((R, 3), jnp.float32)
    txt = jax.jit(make(cfg, meta)).lower(bins, gh).compile().as_text()
    n = sum(1 for ln in txt.splitlines()
            if re.match(r"\s+(%|ROOT )", ln))
    return n


def test_level_phase_dispatch_count_is_o_levels():
    """The level program's compiled instruction count — the dispatch
    proxy (docs/TPU_RUNBOOK.md cost model: every top-level kernel is a
    device launch; there is no sequential while loop here) — must
    scale with DEPTH, not with num_leaves. 63 -> 255 leaves is 4.1x
    the splits but only 6 -> 8 levels; a split-loop-shaped program
    would blow the 2x bound (measured ratio ~1.3)."""
    from lightgbm_tpu.core.level_grower import make_level_grower
    small = _grower_compiled_text(
        make_level_grower, dict(num_leaves=63, max_depth=6,
                                row_sched="level"))
    big = _grower_compiled_text(
        make_level_grower, dict(num_leaves=255, max_depth=8,
                                row_sched="level"))
    assert big < small * 2.0, (
        f"level program instrs scaled like splits, not levels: "
        f"{small} -> {big}")


def test_hybrid_program_shape():
    """The hybrid program = one straight-line level phase + ONE
    sequential tail loop. Its instruction count stays within a small
    constant of the pure level program at the same geometry — i.e. the
    handoff/assembly does not smuggle an O(splits) unrolled stage back
    in."""
    from lightgbm_tpu.core.hybrid_grower import make_hybrid_grower
    from lightgbm_tpu.core.level_grower import make_level_grower
    pure = _grower_compiled_text(
        make_level_grower, dict(num_leaves=63, max_depth=6,
                                row_sched="level"))
    hybrid = _grower_compiled_text(
        make_hybrid_grower, dict(num_leaves=63, max_depth=-1,
                                 row_sched="level"))
    # level phase to D0=7 (auto for 63 leaves) + tail body + handoff:
    # comfortably under 3x the pure-D6 program, nowhere near the ~62
    # unrolled splits a sequential-shaped program would add
    assert hybrid < pure * 3.0, (pure, hybrid)


def test_compile_budget_fails_a_deliberately_recompiling_loop(
        compile_budget):
    """A loop that retraces every pass (fresh shape each iteration) must
    blow the budget — this is the CI tripwire the fixture exists for."""
    f = jax.jit(lambda x: x * 2.0)
    f(jnp.ones(4)).block_until_ready()  # warmup
    with pytest.raises(guards.CompileBudgetExceeded) as exc:
        with compile_budget(1, "shape sweep"):
            for n in range(5, 10):  # 5 distinct shapes -> 5 retraces
                f(jnp.ones(n)).block_until_ready()
    assert "compile budget exceeded" in str(exc.value)
    assert "shape sweep" in str(exc.value)


def test_compile_counter_warm_cache_counts_zero():
    f = jax.jit(lambda x: x + 1.0)
    x = jnp.ones(7)
    f(x).block_until_ready()
    with guards.CompileCounter() as counter:
        f(x).block_until_ready()
    assert counter.count == 0, counter.names


def test_compile_counter_restores_logger_state():
    import logging
    lg = logging.getLogger("jax._src.dispatch")
    level, prop, n_handlers = lg.level, lg.propagate, len(lg.handlers)
    with guards.CompileCounter():
        pass
    assert (lg.level, lg.propagate, len(lg.handlers)) == \
        (level, prop, n_handlers)


def test_no_implicit_transfers_allows_explicit_fetch():
    """Explicit materialization (jax.device_get) stays allowed under the
    guard — the deliberate fetch points in models/gbdt.py go through
    device_get and must keep working. np.asarray on a device array is
    NOT safe under strict mode (jax counts __array__ as implicit); here
    it only touches the numpy array device_get returned. (The
    implicit-transfer RAISE only manifests on a real accelerator
    backend; on the CPU backend arrays are already host-resident, so
    this is a smoke test there.)"""
    a = jnp.arange(4, dtype=jnp.float32)
    with guards.no_implicit_transfers():
        host = np.asarray(jax.device_get(a))
    np.testing.assert_array_equal(host, np.arange(4, dtype=np.float32))


def test_guard_mode_env_parsing():
    # LIGHTGBM_TPU_GUARDS aliases the toggle under the package's
    # established env prefix; the short name wins when both are set
    assert guards.guard_mode({"LIGHTGBM_TPU_GUARDS": "strict"}) == \
        "disallow"
    assert guards.guard_mode({"LGBM_TPU_GUARDS": "log",
                              "LIGHTGBM_TPU_GUARDS": "strict"}) == "log"
    assert guards.guard_mode({"LGBM_TPU_GUARDS": "1"}) == "log"
    assert guards.guard_mode({"LGBM_TPU_GUARDS": "log"}) == "log"
    assert guards.guard_mode({"LGBM_TPU_GUARDS": "strict"}) == "disallow"
    assert guards.guard_mode({"LGBM_TPU_GUARDS": "2"}) == "disallow"
    assert guards.guard_mode({"LGBM_TPU_GUARDS": "0"}) is None
    assert guards.guard_mode({"LGBM_TPU_GUARDS": "off"}) is None
    assert guards.guard_mode({}) is None
