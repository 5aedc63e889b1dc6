"""What the grower runs, as one table: ``core/plan.make_plan`` from what the
code can observe to the values ``GrowerConfig`` and ``_setup_train`` consume.

The rows were written down from the parent's behaviour (``resolve_hist_kernel``,
``resolve_level_hist_kernel``, ``resolve_hist_reduce``, the ``part_mode`` block
and the ``want_pack`` expression of ``models/gbdt.py`` with the shipped
tuned-defaults cache) before the code moved. The platform is an argument, so the
TPU's answers are held here on the CPU.
"""
import pytest

from lightgbm_tpu.core import plan as plan_mod
from lightgbm_tpu.core.plan import make_plan

M2 = 2_000_000
GATE = plan_mod.MEASURED_FROM_ROWS

# (platform, num_data, dtype, quantized, learner, storage, sched,
#  num_bin_max, requests) -> (hist_rm_backend, level_hist_backend,
#                             partition_mode, pack, hist_reduce)
CASES = [
    # the cell's own parameters (criteo-share.train): every tpu_* auto
    ("tpu", M2, "float32", False, "serial", "dense", "compact", 255, {},
     ("pallas", "einsum", "auto", True, "allreduce")),
    # the same on the CPU: scatter everywhere; the row gate of packing
    # never asked for a platform
    ("cpu", M2, "float32", False, "serial", "dense", "compact", 255, {},
     ("scatter", "scatter", "scatter", True, "allreduce")),
    ("cpu", 20_000, "float32", False, "serial", "dense", "compact", 255, {},
     ("scatter", "scatter", "scatter", False, "allreduce")),
    # the 65,536-row gate, both sides
    ("tpu", GATE - 1, "float32", False, "serial", "dense", "compact", 255,
     {}, ("einsum", "einsum", "auto", False, "allreduce")),
    ("tpu", GATE, "float32", False, "serial", "dense", "compact", 255, {},
     ("pallas", "einsum", "auto", True, "allreduce")),
    # bf16 and int8 histograms take the kernel at every size; packing
    # still waits for the gate
    ("tpu", GATE - 1, "bfloat16", False, "serial", "dense", "compact", 255,
     {}, ("pallas", "einsum", "auto", False, "allreduce")),
    ("tpu", GATE - 1, "bf16", False, "serial", "dense", "compact", 255, {},
     ("pallas", "einsum", "auto", False, "allreduce")),
    ("tpu", M2, "bf16", False, "serial", "dense", "compact", 255, {},
     ("pallas", "einsum", "auto", True, "allreduce")),
    ("tpu", GATE - 1, "float32", True, "serial", "dense", "compact", 255,
     {}, ("pallas", "einsum", "auto", False, "allreduce")),
    ("cpu", M2, "bfloat16", True, "serial", "dense", "compact", 255, {},
     ("scatter", "scatter", "scatter", True, "allreduce")),
    # every explicit tpu_hist_kernel passes through on both platforms
    ("tpu", M2, "float32", False, "serial", "dense", "compact", 255,
     {"hist_kernel": "einsum"},
     ("einsum", "einsum", "auto", True, "allreduce")),
    ("tpu", GATE - 1, "float32", False, "serial", "dense", "compact", 255,
     {"hist_kernel": "pallas"},
     ("pallas", "pallas", "auto", False, "allreduce")),
    ("tpu", M2, "float32", False, "serial", "dense", "compact", 255,
     {"hist_kernel": "scatter"},
     ("scatter", "scatter", "auto", True, "allreduce")),
    ("cpu", 20_000, "float32", False, "serial", "dense", "compact", 255,
     {"hist_kernel": "einsum"},
     ("einsum", "einsum", "scatter", False, "allreduce")),
    ("cpu", 20_000, "float32", False, "serial", "dense", "compact", 255,
     {"hist_kernel": "pallas"},
     ("pallas", "pallas", "scatter", False, "allreduce")),
    # pallas_level names the level phase's kernel; the row-major path
    # resolves as auto
    ("tpu", M2, "float32", False, "serial", "dense", "level", 255,
     {"hist_kernel": "pallas_level"},
     ("pallas", "pallas_level", "auto", False, "allreduce")),
    ("tpu", GATE - 1, "float32", False, "serial", "dense", "level", 255,
     {"hist_kernel": "pallas_level"},
     ("einsum", "pallas_level", "auto", False, "allreduce")),
    ("cpu", 20_000, "float32", False, "serial", "dense", "level", 255,
     {"hist_kernel": "pallas_level"},
     ("scatter", "pallas_level", "scatter", False, "allreduce")),
    # tpu_packed_bins: asked for, refused, and the uint8 limit
    ("cpu", 20_000, "float32", False, "serial", "dense", "compact", 255,
     {"packed_bins": "true"},
     ("scatter", "scatter", "scatter", True, "allreduce")),
    ("tpu", GATE - 1, "float32", False, "serial", "dense", "compact", 255,
     {"packed_bins": "on"},
     ("einsum", "einsum", "auto", True, "allreduce")),
    ("tpu", M2, "float32", False, "serial", "dense", "compact", 255,
     {"packed_bins": "false"},
     ("pallas", "einsum", "auto", False, "allreduce")),
    ("tpu", M2, "float32", False, "serial", "dense", "compact", 255,
     {"packed_bins": "0"},
     ("pallas", "einsum", "auto", False, "allreduce")),
    ("tpu", M2, "float32", False, "serial", "dense", "compact", 256, {},
     ("pallas", "einsum", "auto", False, "allreduce")),
    ("tpu", M2, "float32", False, "serial", "dense", "compact", 256,
     {"packed_bins": "true"},
     ("pallas", "einsum", "auto", False, "allreduce")),
    # EFB's physical columns pack like logical ones
    ("tpu", M2, "float32", False, "serial", "bundled", "compact", 255, {},
     ("pallas", "einsum", "auto", True, "allreduce")),
    # who never packs: the distributed learners (they shard a copy of
    # their own), multi-value storage, the level and full schedulers
    ("tpu", M2, "float32", False, "data", "dense", "compact", 255, {},
     ("pallas", "einsum", "auto", False, "allreduce")),
    ("tpu", M2, "float32", False, "data", "dense", "compact", 255,
     {"packed_bins": "true"},
     ("pallas", "einsum", "auto", False, "allreduce")),
    ("tpu", M2, "float32", False, "voting", "dense", "compact", 255, {},
     ("pallas", "einsum", "auto", False, "allreduce")),
    ("tpu", M2, "float32", False, "serial", "multival", "compact", 255,
     {"packed_bins": "true"},
     ("pallas", "einsum", "auto", False, "allreduce")),
    ("tpu", M2, "float32", False, "serial", "dense", "level", 255,
     {"packed_bins": "true"},
     ("pallas", "einsum", "auto", False, "allreduce")),
    ("tpu", M2, "float32", False, "serial", "dense", "full", 255, {},
     ("pallas", "einsum", "auto", False, "allreduce")),
    # tpu_partition_mode: auto is the CPU's scatter and the grower's own
    # choice per bucket on a chip; explicit values pass through
    ("cpu", 20_000, "float32", False, "serial", "dense", "compact", 255,
     {"partition_mode": "sort"},
     ("scatter", "scatter", "sort", False, "allreduce")),
    ("tpu", M2, "float32", False, "serial", "dense", "compact", 255,
     {"partition_mode": "scatter"},
     ("pallas", "einsum", "scatter", True, "allreduce")),
    # tpu_hist_reduce: auto is allreduce; an explicit value passes through
    # (the learner's eligibility fallback is the engine's)
    ("tpu", M2, "float32", False, "data", "dense", "compact", 255,
     {"hist_reduce": "reduce_scatter"},
     ("pallas", "einsum", "auto", False, "reduce_scatter")),
    ("cpu", 20_000, "float32", False, "data", "dense", "compact", 255,
     {"hist_reduce": "reduce_scatter"},
     ("scatter", "scatter", "scatter", False, "reduce_scatter")),
    ("tpu", M2, "float32", False, "voting", "dense", "compact", 255,
     {"hist_reduce": "allreduce"},
     ("pallas", "einsum", "auto", False, "allreduce")),
]


def _plan(case):
    platform, num_data, dtype, quantized, learner, storage, sched, \
        num_bin_max, requests, _ = case
    return make_plan(platform=platform, num_data=num_data,
                     num_bin_max=num_bin_max, quantized=quantized,
                     hist_dtype=dtype, tree_learner=learner,
                     storage=storage, row_sched=sched, **requests)


def _case_id(case):
    platform, num_data, dtype, quantized, learner, storage, sched, \
        num_bin_max, requests, _ = case
    asked = ",".join(f"{k}={v}" for k, v in requests.items()) or "auto"
    return (f"{platform}-{num_data}-{dtype}{'-q' if quantized else ''}-"
            f"{learner}-{storage}-{sched}-{num_bin_max}-{asked}")


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_plan_table(case):
    p = _plan(case)
    assert (p.hist_rm_backend, p.level_hist_backend, p.partition_mode,
            p.pack, p.hist_reduce) == case[-1]


def test_plan_notes_say_what_was_remapped():
    """The two requests the plan does not grant as asked each leave one
    line for the engine to log; a granted plan leaves none."""
    assert _plan(CASES[0]).notes == ()
    level = make_plan(platform="tpu", num_data=M2, num_bin_max=255,
                      quantized=False, hist_dtype="float32",
                      tree_learner="serial", storage="dense",
                      row_sched="level", hist_kernel="pallas_level")
    assert [lvl for lvl, _ in level.notes] == ["info"]
    assert "level-phase" in level.notes[0][1]
    wide = make_plan(platform="tpu", num_data=M2, num_bin_max=256,
                     quantized=False, hist_dtype="float32",
                     tree_learner="serial", storage="dense",
                     row_sched="compact")
    assert [lvl for lvl, _ in wide.notes] == ["warning"]
    assert "num_bin_max=256" in wide.notes[0][1]
    # a learner that never packs is not warned about widths
    assert make_plan(platform="tpu", num_data=M2, num_bin_max=256,
                     quantized=False, hist_dtype="float32",
                     tree_learner="data", storage="dense",
                     row_sched="compact").notes == ()


def test_engine_runs_the_plan():
    """The engine's GrowerConfig carries what the plan says for its own
    inputs (here: the CPU, a small table, packing asked for)."""
    import jax
    import numpy as np
    import lightgbm_tpu as lgb

    rng = np.random.default_rng(0)
    X = rng.normal(size=(600, 5)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1, "min_data_in_leaf": 5,
                     "tpu_packed_bins": "true"},
                    lgb.Dataset(X, label=y), num_boost_round=1)
    eng = bst._engine
    want = make_plan(platform=jax.default_backend(), num_data=600,
                     num_bin_max=eng.num_bin_max, quantized=False,
                     hist_dtype="float32", tree_learner="serial",
                     storage="dense", row_sched="compact",
                     packed_bins="true")
    g = eng.grower_cfg
    assert (g.hist_rm_backend, g.level_hist_backend, g.partition_mode,
            eng._packed_cols > 0) == \
        (want.hist_rm_backend, want.level_hist_backend,
         want.partition_mode, want.pack)
    assert eng._plan == want


# ---- the first split's rule ------------------------------------------------

# (packed words, columns) -> the rows of the table that one of the rule's
# lines lies at: a bucket over it takes the masked pass over the table
# (the kernel's price since PR 36, 0.050 ns a column a row where 0.174 was:
# every line lies lower, the gather being no cheaper)
FIRST_SPLIT_LINES = [
    (7, 28, 21.54),     # chip_smoke.py's table: about R/21
    (17, 67, 11.22),    # criteo-share: about R/11
    (35, 137, 7.45),    # MS LTR's width (ledger, PR 27): about R/7
    (40, 160, 6.86),    # the widest table held once (my TPU compiles, PR 33)
    # from 41 words the compiler re-lays a table held once at every split:
    # it is held twice and whole rows are gathered out of the row-major
    # copy for a tenth of the price, a price read at 500 words alone
    (41, 164, 1.68),    # the narrowest table held twice: no reading behind it
    (175, 700, 1.28),   # Expo's width: no reading behind it either
    (242, 968, 1.24),   # Bosch's (1,000,000 rows: the second width run, PR 35)
    (500, 2000, 1.20),  # Epsilon's (my chip run, PR 33, priced it)
]


@pytest.mark.parametrize("words,cols,share", FIRST_SPLIT_LINES,
                         ids=[f"{w}words" for w, _, _ in FIRST_SPLIT_LINES])
def test_first_split_rule_line(words, cols, share):
    from lightgbm_tpu.core.plan import first_split_dense_rows
    line = first_split_dense_rows(M2, words, cols)
    assert M2 / line == pytest.approx(share, abs=0.01)
    # in the cell's ladder of buckets: all three buckets a smaller child of
    # more than 131,072 rows can fall into are over the line at 67
    # columns and under; the upper two from 137 columns on; none of a
    # table held twice
    over = [b for b in (1048576, 524288, 262144) if b > line]
    assert over == ([1048576, 524288, 262144] if cols <= 67 else
                    [1048576, 524288]
                    if words < plan_mod.HELD_TWICE_FROM_WORDS else [])
    # the line scales with the rows and no bucket of a tiny child passes
    assert first_split_dense_rows(M2 // 2, words, cols) == \
        pytest.approx(line / 2, abs=1)


def test_first_split_rule_rises_with_the_width():
    """A wider row makes the kernel's pass over the whole table dearer
    faster than it makes the gather dearer: the line moves up, towards
    half the rows, and each of its constants names the ledger line it
    stands on."""
    import inspect
    from lightgbm_tpu.core import plan
    lines = [plan.first_split_dense_rows(M2, -(-c // 4), c)
             for c in range(4, 2049, 4)]
    assert all(a < b for a, b in zip(lines, lines[1:]))
    assert 0 < lines[0] and lines[-1] < M2
    source = inspect.getsource(plan)
    above = source[:source.index("def first_split_dense_rows")]
    for constant in ("ROW_GATHER_NS_INDEX", "ROW_GATHER_NS_WORD",
                     "GH_GATHER_NS_INDEX", "KERNEL_NS_COLUMN_ROW",
                     "HELD_TWICE_FROM_WORDS", "ROWS_GATHER_NS_WORD"):
        assert f"\n{constant} = " in above
    block = above[above.index("# The first split's smaller child"):]
    assert "ledger, PR 31" in block and "ledger, PR 27" in block
    assert "my chip run, PR 33" in block


def test_bosch_takes_the_cells_path_and_gathers_its_first_split():
    """1,000,000 x 968 with a NaN bin in every column (251 bins) on a chip,
    every ``tpu_*`` auto: the Pallas kernel on packed words, 242 of them a
    row, between the two widths the held-twice path had been run at (17 held
    once, 500 held twice). The table is held twice; a first split's smaller
    child has at most 500,000 rows, the 524,288 bucket, and the rule's line
    lies at 0.80 R: gathered, two blocks of 262,144 rows."""
    got = make_plan(platform="tpu", num_data=1_000_000, num_bin_max=251,
                    quantized=False, hist_dtype="float32",
                    tree_learner="serial", storage="dense",
                    row_sched="compact")
    assert (got.hist_rm_backend, got.level_hist_backend, got.partition_mode,
            got.pack, got.hist_reduce, got.notes) == \
        ("pallas", "einsum", "auto", True, "allreduce", ())
    assert -(-968 // 4) == 242 and plan_mod.rows_held_twice(242)
    line = plan_mod.first_split_dense_rows(1_000_000, 242, 968)
    assert 524_288 < line < 1_000_000
    assert line / 1_000_000 == pytest.approx(0.8040, abs=1e-3)


def test_epsilon_takes_the_cells_path_and_gathers_its_first_split():
    """400,000 x 2,000 on a chip resolves as the cell we have (every
    ``tpu_*`` auto): the Pallas kernel on packed words. At 500 words the
    table is held twice, and no smaller child of a first split (at most
    200,000 rows, the 262,144 bucket) is over the rule's line."""
    got = make_plan(platform="tpu", num_data=400_000, num_bin_max=251,
                    quantized=False, hist_dtype="float32",
                    tree_learner="serial", storage="dense",
                    row_sched="compact")
    assert (got.hist_rm_backend, got.level_hist_backend, got.partition_mode,
            got.pack, got.hist_reduce, got.notes) == \
        ("pallas", "einsum", "auto", True, "allreduce", ())
    assert plan_mod.rows_held_twice(500)
    assert not plan_mod.rows_held_twice(17)
    assert plan_mod.first_split_dense_rows(400_000, 500, 2000) > 262_144
    # the word-major gather's fit, had it priced this width: the line at
    # 100,025 rows, a 40 ms masked pass where the 131,072 bucket's gathered
    # call costs 16
    assert 65_536 < 400_000 * 100 / (100 + 20.6 + 0.55 * 500 + 4.3) \
        < 131_072
