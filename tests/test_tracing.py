"""The program's tracing (``lightgbm_tpu/utils/timer.py``): device stages
and the stage map, host sections as profiler annotations and records."""
import collections
import contextlib
import dataclasses
import glob
import os
from unittest import mock

import jax
import jax.monitoring
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.utils import timer

PARAMS = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "tpu_row_scheduling": "compact", "tpu_packed_bins": "true",
          "tpu_async_boosting": "true"}
# what does no work of its own: values the compiler materialises, and views
NO_WORK = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
           "broadcast", "iota"}
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def table(seed=0, rows=3000, cols=10):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, cols)).astype(np.float32)
    y = (X[:, 0] * 2 + X[:, 1] ** 2 + rng.normal(size=rows) > 1)
    return X, y.astype(np.float32)


@contextlib.contextmanager
def fresh_compiles():
    """jax leaves metadata out of its persistent cache's key, so a cache
    filled before the scopes existed (or under ``test_null_stages``) would
    hand back an executable with other scopes than the program traced."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def grown(steps=3, **params):
    X, y = table()
    with fresh_compiles():
        booster = lgb.Booster({**PARAMS, **params}, lgb.Dataset(X, label=y))
        for _ in range(steps):
            booster.update()
    return booster


@pytest.fixture(scope="module")
def booster():
    """A compact-grower booster on packed bins and the asynchronous path,
    kept alive so that its programs stay in the stage map."""
    booster = grown()
    g = booster._engine.grower_cfg
    assert g.row_sched == "compact" and g.packed_cols == 10
    assert booster._engine._async_on()
    return booster


@pytest.fixture(scope="module")
def compile_events():
    seen = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **_: seen.append(event))
    return seen


@pytest.mark.parametrize("quantized", [False, True])
def test_stage_map_holds_every_stage_of_the_path(booster, quantized):
    """Every stage is in the map; ``gh_quantize`` only while a program
    that quantizes its gradients (``use_quantized_grad``) lives, and
    ``dart_rescore`` belongs to DART alone (``tests/test_dart.py``)."""
    import gc
    path = grown(use_quantized_grad=True, stochastic_rounding=False) \
        if quantized else booster
    assert path._engine._async_on()
    found = set(timer.stage_map().values())
    assert set(timer.STAGES) - {"gh_quantize", "dart_rescore"} <= found
    assert ("gh_quantize" in found) == quantized
    if quantized:
        del path
        gc.collect()
        assert "gh_quantize" not in set(timer.stage_map().values())


def test_stage_map_leaves_few_working_instructions_unnamed(booster):
    grow = booster._engine._grow
    working = [i for i in timer.instructions(grow.compiled_text())
               if i[1] not in NO_WORK]
    unnamed = [i for i in working if i[2] is None]
    assert len(working) > 500
    assert len(unnamed) < 0.10 * len(working), collections.Counter(
        i[1] for i in unnamed).most_common(8)


def test_stage_map_compiles_nothing(booster, compile_events):
    before = compile_events.count(COMPILE_EVENT)
    assert timer.stage_map()
    assert compile_events.count(COMPILE_EVENT) == before


def test_stage_map_keeps_no_buffer_alive(booster):
    held = jax.tree.leaves(booster._engine._grow.last)
    assert held and not any(isinstance(x, jax.Array) for x in held)


def test_stage_map_forgets_a_dead_engine():
    import gc
    import weakref
    throwaway = grown(steps=1)
    grow = weakref.ref(throwaway._engine._grow)
    assert grow() in timer._programs
    del throwaway
    gc.collect()
    assert grow() is None and None not in set(timer._programs)


def equations(jaxpr, scope=""):
    """Every equation of a jaxpr and of the jaxprs inside it, each with the
    scopes it sits under, the enclosing equations' included."""
    for eqn in jaxpr.eqns:
        here = f"{scope}/{eqn.source_info.name_stack}"
        yield eqn, here
        for param in eqn.params.values():
            for inner in param if isinstance(param, (tuple, list)) \
                    else (param,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from equations(inner, here)


@pytest.mark.parametrize("packed", [True, False])
def test_partition_fetch_indexes_a_column_never_the_table(booster, packed):
    """The partition takes the split's column out of the table as a slice
    and the leaf's rows out of that column: a gather whose operand is the
    table pays HBM's price an index for one word (PERF.md, PR 26)."""
    from lightgbm_tpu.core.grower import make_tree_grower
    engine = booster._engine
    R, F = 3000, engine.grower_cfg.packed_cols
    cfg = engine.grower_cfg if packed else dataclasses.replace(
        engine.grower_cfg, packed_cols=0)
    bins = jax.ShapeDtypeStruct((R, (F + 3) // 4), np.uint32) if packed \
        else jax.ShapeDtypeStruct((R, F), np.uint8)
    closed = jax.make_jaxpr(make_tree_grower(cfg, engine.feature_meta))(
        bins, jax.ShapeDtypeStruct((R, 3), np.float32))
    fetches = 0
    for eqn, scope in equations(closed.jaxpr):
        name = eqn.primitive.name
        if name == "gather" and "lgbm.partition_fetch" in scope:
            fetches += 1
            assert eqn.invars[0].aval.size <= R, (scope, eqn.invars[0].aval)
        if name == "reshape":
            flat = eqn.outvars[0].aval
            assert not (flat.ndim == 1 and flat.size == bins.size and
                        flat.dtype == bins.dtype), scope
    assert fetches


def packed_grower_jaxpr(engine, rows, **changed):
    """The jaxpr of the booster's grower on packed words, and its config."""
    from lightgbm_tpu.core.grower import make_tree_grower
    cfg = dataclasses.replace(engine.grower_cfg, **changed)
    closed = jax.make_jaxpr(make_tree_grower(cfg, engine.feature_meta))(
        jax.ShapeDtypeStruct((rows, (cfg.packed_cols + 3) // 4), np.uint32),
        jax.ShapeDtypeStruct((rows, 3), np.float32))
    return closed.jaxpr, cfg


def test_packed_pallas_grower_hands_the_kernel_words_not_unpacked_rows(
        booster):
    """On packed bins the Pallas histogram kernel reads the words the table
    stores: between the gather and the kernel there is no int32 copy of a
    bucket's rows at the table's column count, in either orientation, and
    the kernel's operand is 32-bit words of the table's word count
    (PERF.md, PR 30)."""
    from lightgbm_tpu.core.grower import _bucket_sizes
    jaxpr, cfg = packed_grower_jaxpr(booster._engine, 3000,
                                     hist_rm_backend="pallas")
    F = cfg.packed_cols
    W = (F + 3) // 4
    buckets = set(_bucket_sizes(3000, cfg.min_bucket))
    assert len(buckets) > 1
    kernels = collections.Counter()
    for eqn, scope in equations(jaxpr):
        if "lgbm.hist_gather" in scope or "lgbm.hist_kernel" in scope:
            for var in eqn.outvars:
                aval = var.aval
                unpacked = (aval.ndim == 2 and aval.dtype == np.int32 and
                            F in aval.shape and buckets & set(aval.shape))
                assert not unpacked, (scope, eqn.primitive.name, aval)
        if eqn.primitive.name == "pallas_call":
            assert "lgbm.hist_kernel" in scope, scope
            # behind the two prefetched scalars that name its live blocks
            live, words = (v.aval for v in eqn.invars[:2])
            assert live.shape == (2,) and live.dtype == np.int32, live
            assert words.dtype in (np.uint32, np.int32), words
            assert words.shape[0] in (W, -(-W // 8) * 8), words
            assert words.shape[1] in buckets, words
            kernels[words.shape[1]] += 1
    # the root's and one for every bucket a child can fall into
    assert set(kernels) == buckets, kernels


def test_histogram_pool_is_read_and_written_as_slices(booster):
    """A split touches two slots of the [L, F, B, 3] pool. As a gather and a
    scatter the compiler took the whole pool for read, and on the chip it
    moved all of it into VMEM and back once a split (PERF.md, PR 30)."""
    jaxpr, cfg = packed_grower_jaxpr(booster._engine, 3000)

    def is_pool(aval):
        return aval.ndim == 4 and aval.shape[0] == cfg.num_leaves

    touched = collections.Counter()
    for eqn, scope in equations(jaxpr):
        # (the root's slot is set once a tree, outside the stage)
        if "lgbm.hist_subtract" in scope and any(
                is_pool(v.aval) for v in eqn.invars if hasattr(v, "aval")):
            touched[eqn.primitive.name] += 1
    assert touched["dynamic_slice"] and touched["dynamic_update_slice"]
    assert not {"gather", "scatter", "scatter-add"} & set(touched), touched


class Text:
    """A planted program for the stage map."""

    def __init__(self, text):
        self.text = text

    def compiled_text(self):
        return self.text


def line(name, shape, opcode, operands, scope=None):
    meta = f', metadata={{op_name="jit(f)/{scope}/{opcode}"}}' if scope \
        else ""
    return f"  {name} = {shape} {opcode}({operands}){meta}"


def test_shared_name_is_ambiguous_and_the_shape_settles_it():
    one = Text(line("%fusion.1", "u32[64]{0:T(128)}", "fusion", "%p",
                    "lgbm.partition_fetch/while/body/lgbm.hist_gather"))
    two = Text(line("%fusion.1", "f32[64,3]{1,0}", "fusion", "%p",
                    "lgbm.gradients"))
    with mock.patch.object(timer, "_programs", [one, two]):
        found = timer.stage_map()
    assert found["%fusion.1"] == timer.AMBIGUOUS
    assert found["%fusion.1 = u32[64]{0:T(128)}"] == "hist_gather"
    assert found["%fusion.1 = f32[64,3]{1,0}"] == "gradients"


def test_name_outside_every_stage_in_one_program_is_ambiguous():
    one = Text(line("%copy.2", "f32[8]{0}", "copy", "%p", "lgbm.tree_update"))
    two = Text(line("%copy.2", "f32[8]{0}", "copy", "%p"))
    with mock.patch.object(timer, "_programs", [one, two]):
        assert timer.stage_map() == {"%copy.2": timer.AMBIGUOUS}


def test_unstaged_instruction_takes_its_operands_stage():
    text = "\n".join([
        line("%p", "s32[8]{0}", "parameter", "0"),
        line("%sort.1", "(s32[8]{0}, s32[8]{0})", "sort", "%p, %p",
             "lgbm.partition_order"),
        line("%copy.5", "s32[8]{0}", "copy", "%sort.1"),
        line("%while.1", "(s32[], s32[8]{0})", "while", "%p")])
    assert [(i[0], i[2]) for i in timer.instructions(text)] == [
        ("%p", None), ("%sort.1", "partition_order"),
        ("%copy.5", "partition_order"), ("%while.1", None)]


def test_unknown_stage_raises():
    with pytest.raises(ValueError, match="unknown stage"):
        timer.stage("histogram")


def test_null_stages_grow_the_same_model(booster):
    """The scopes are metadata and nothing else."""
    with mock.patch.object(timer, "stage",
                           lambda name: contextlib.nullcontext()):
        plain = grown()
        assert not any(i[2] for i in timer.instructions(
            plain._engine._grow.compiled_text()))
    assert plain.model_to_string() == booster.model_to_string()


def test_capture_holds_program_spans_inside_the_callers(tmp_path):
    from jax.profiler import ProfileData
    X, y = table(seed=1)
    b = lgb.Booster(dict(PARAMS), lgb.Dataset(X, label=y))
    b.update()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("caller.window"):
            for _ in range(3):
                b.update()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for ln in plane.lines for e in ln.events]
    (_, lo, hi), = [e for e in events if e[0] == "caller.window"]
    train = [e for e in events if e[0] == "lgbm.TreeLearner::Train"]
    assert len(train) == 3
    assert all(lo <= a and b_ <= hi for _, a, b_ in train)
    assert {"lgbm.GBDT::Boosting", "lgbm.GBDT::UpdateScore"} <= {
        e[0] for e in events}


def test_records_carry_parent_and_iteration():
    t = timer.Timer()
    with t.section("outer", iteration=7):
        with t.section("inner"):
            pass
    with t.section("alone"):
        pass
    inner, outer, alone = t.records
    assert (inner.name, inner.parent, inner.iteration) == ("inner", "outer", 7)
    assert (outer.name, outer.parent, outer.iteration) == ("outer", None, 7)
    assert (alone.parent, alone.iteration) == (None, None)
    assert outer.start <= inner.start <= inner.end <= outer.end <= alone.start


def test_training_records_every_iteration(booster):
    done = [r for r in timer.global_timer.records
            if r.name == "TreeLearner::Train"]
    assert len(done) >= 3
    assert all(r.parent is None and r.iteration is not None for r in done)


def test_records_stay_at_their_bound_and_totals_go_on():
    t = timer.Timer()
    for _ in range(timer.MAX_RECORDS + 10):
        with t.section("tick"):
            pass
    assert len(t.records) == timer.MAX_RECORDS
    assert t._count["tick"] == timer.MAX_RECORDS + 10
    assert "tick" in t.table()


def test_table_fills_with_timetag_off():
    t = timer.Timer()
    t.enabled = False
    with t.section("GBDT::Boosting"):
        pass
    header, row = t.table().splitlines()
    assert header.split() == ["section", "total(s)", "count", "mean(ms)"]
    assert row.split()[0] == "GBDT::Boosting" and row.split()[2] == "1"


@pytest.mark.parametrize("enabled", [False, True])
def test_sync_blocks_only_when_enabled(enabled):
    t = timer.Timer()
    t.enabled = enabled
    asked = []
    with t.section("step", sync=lambda: asked.append(1) or ()):
        pass
    assert bool(asked) == enabled


# ---- the counters: how often the first split ran dense ---------------------

def lopsided_table(rows=3000, cols=10):
    """One column sends 0.4 % of the rows one way and decides the label:
    under the 32-row bucket, which the first split's rule (its line at 55
    rows here) keeps gathered."""
    rng = np.random.default_rng(5)
    X = rng.normal(size=(rows, cols)).astype(np.float32)
    X[:, 3] = rng.random(rows) < 0.004
    return X, X[:, 3].copy()


@pytest.mark.parametrize("make,steps,dense", [(table, 3, 3),
                                              (lopsided_table, 1, 0)],
                         ids=["balanced", "lopsided"])
def test_first_split_dense_is_counted_beside_the_trees(make, steps, dense):
    """``first_split_dense`` / ``trees``: on a balanced table every tree's
    first split histograms its smaller child in one masked pass over the
    table in place (the words kernel, interpreted here), on a lopsided
    one none does; the table prints both. The flag comes to the host in
    the fetch that brings the tree, ``Tree::ToHost``: the asynchronous
    path fetches what it fetched, as often as it did."""
    X, y = make()
    counters = timer.global_timer.counters
    rows = ("hist_rows_live", "hist_rows_read", "hist_rows_bucket")
    before = {k: counters[k] for k in ("trees", "first_split_dense") + rows}
    booster = lgb.Booster({**PARAMS, "tpu_hist_kernel": "pallas",
                           "min_data_in_leaf": 5, "tpu_min_bucket": 32},
                          lgb.Dataset(X, label=y))
    engine = booster._engine
    assert engine._async_on() and engine.grower_cfg.packed_cols
    fetched = []
    with mock.patch.object(jax, "device_get", side_effect=lambda x: (
            fetched.append(x), jax.tree.map(np.asarray, x))[1]):
        for _ in range(steps):
            booster.update()
        assert counters["trees"] == before["trees"]    # nothing came yet
        trees = engine.models                          # the flush
    assert len(trees) == steps and all(t.num_leaves > 1 for t in trees)
    assert counters["trees"] - before["trees"] == steps
    assert counters["first_split_dense"] - before["first_split_dense"] \
        == dense
    assert [t.first_split_dense for t in trees] == [bool(dense)] * steps
    # the gathered histogram calls' rows, added up over the trees: the
    # segments', those in the row blocks the kernel read, the buckets'
    added = [counters[k] - before[k] for k in rows]
    assert added == [sum(t.hist_rows[k] for t in trees) for k in range(3)]
    assert 0 < added[0] <= added[1] <= added[2]
    # one fetch of the leaf counts (the stop check) and one of the trees
    kinds = [type(x).__name__ for x in fetched]
    assert kinds.count("TreeArrays") == 1 and len(fetched) == 2, kinds
    lines = timer.global_timer.table().splitlines()
    at = lines.index(next(ln for ln in lines if ln.split() ==
                          ["counter", "count"]))
    assert {ln.split()[0]: int(ln.split()[1]) for ln in lines[at + 1:]} \
        == dict(counters)


# (parameters, the note): the float32 triple's channels, seven since the
# grower's count is 0 or 1, over the eight high parts of 255 bins (and the
# one sublane tile 63 bins' two still take), the three channels of bfloat16
# and of quantized int8 gradients, and a backend that is not the Pallas kernel
EXPANDED_ROWS = [
    ({"tpu_hist_kernel": "pallas"}, 7 * 8),
    ({"tpu_hist_kernel": "pallas", "max_bin": 63}, 7 * 8),
    ({"tpu_hist_kernel": "pallas", "tpu_hist_dtype": "bfloat16"}, 3 * 8),
    ({"tpu_hist_kernel": "pallas", "use_quantized_grad": True}, 3 * 8),
    ({"tpu_hist_kernel": "einsum"}, 0),
    ({}, 0),
]


@pytest.mark.parametrize("params,rows", EXPANDED_ROWS, ids=[
    "f32", "f32-63bins", "bf16", "int8", "einsum", "cpu-auto"])
def test_hist_expanded_rows_says_which_kernel_a_run_had(params, rows):
    """Set at set-up beside ``scan_directions``: the rows of the operand a
    column's contraction holds still in the Pallas kernel (the one-hot it
    had until PR 36 would read 256), 0 where the kernel does not run; a
    second booster sets it anew."""
    X, y = table(rows=600)
    timer.global_timer.note("hist_expanded_rows", -1)
    max_bin = params.get("max_bin", 255)
    lgb.Booster({**PARAMS, **params},
                lgb.Dataset(X, label=y, params={"max_bin": max_bin}))
    assert timer.global_timer.counters["hist_expanded_rows"] == rows
    assert rows < 256


# (parameters, the note): the lanes a column has in the kernel's output,
# 64 for the float32 triple's seven channels over eight high parts (at 63
# bins too), 32 for three channels of bfloat16 or int8 gradients, 0 where
# another backend builds the histograms
OUTPUT_LANES = [(params, lanes) for (params, _), lanes in
                zip(EXPANDED_ROWS, [64, 64, 32, 32, 0, 0])]


@pytest.mark.parametrize("params,lanes", OUTPUT_LANES, ids=[
    "f32", "f32-63bins", "bf16", "int8", "einsum", "cpu-auto"])
def test_hist_output_lanes_says_what_the_kernel_wrote(params, lanes):
    """Set beside ``hist_expanded_rows``: the lanes a column has in the
    Pallas kernel's output (a whole lane tile of 128 when every column had
    its own), 0 where the kernel does not run; a second booster sets it
    anew."""
    X, y = table(rows=600)
    timer.global_timer.note("hist_output_lanes", -1)
    max_bin = params.get("max_bin", 255)
    lgb.Booster({**PARAMS, **params},
                lgb.Dataset(X, label=y, params={"max_bin": max_bin}))
    assert timer.global_timer.counters["hist_output_lanes"] == lanes
    assert lanes in (0, 32, 64)


def test_table_prints_counters_without_sections():
    t = timer.Timer()
    assert t.table() == "(no timing sections recorded)"
    t.count("trees", 3)
    t.count("first_split_dense")
    assert [ln.split() for ln in t.table().splitlines()] == [
        ["counter", "count"], ["trees", "3"], ["first_split_dense", "1"]]
    t.reset()
    assert t.table() == "(no timing sections recorded)"
