"""Quantized-gradient training, program against the benchmark's plain
reference.

The plain reference (``benchmark/references/gbdt_quantized.py``: jax.numpy,
nothing of the program) rounds every row's gradient and hessian to the
nearest of ``num_grad_quant_bins`` levels by per-tree scales, sums the
integers and dequantizes each sum by one multiplication before the gain and
the leaf value. Here the program's first three trees under
``use_quantized_grad`` (4 levels, ``stochastic_rounding`` off) are laid
against the reference's split by split (feature, threshold) and score by
score, through the CPU's path (scatter histograms on unpacked bins) and
through the chip's (the Pallas kernel's int8 entry on packed words,
interpreted here, the int32 pool, async boosting). The same comparison
refuses the reference with quantization off: the program that ignores
``use_quantized_grad``.
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
ref = _bench_module("bench_gbdt_quantized", "references/gbdt_quantized.py")

STEPS = 3
ROWS = 4096
PARAMS = {"objective": "binary", "learning_rate": 0.1, "max_bin": 255,
          "num_leaves": 31, "min_data_in_leaf": 1,
          "min_sum_hessian_in_leaf": 5.0, "use_quantized_grad": True,
          "num_grad_quant_bins": 4, "stochastic_rounding": False,
          "tpu_hist_dtype": "float32", "verbose": -1}
# what ``auto`` resolves to on a chip (core/plan.py), asked for by name
CHIP = {"tpu_hist_kernel": "pallas", "tpu_packed_bins": "true",
        "tpu_partition_mode": "sort", "tpu_row_scheduling": "compact",
        "tpu_async_boosting": "true"}
PATHS = {"cpu": {}, "chip": CHIP}
COLUMNS = [{"kind": "grid", "n": 64, "levels": 32}]
LABEL = {"columns": [0, 3, 7, 20, 40, 63],
         "coefficients": [0.9, -0.7, 0.5, 0.8, -0.6, 0.4, 0.5, -0.3],
         "noise": 1.0, "noise_seed": 7}
TABLE_SEED = 20261005
# a score's gap, a share of the score and of the largest change the
# reference made to a score by then: float32's rounding of sums taken in
# another order, a few units in the last place
SCORE_GAP = 1e-6


@functools.lru_cache(maxsize=None)
def table():
    codes = np.asarray(tablegen.codes(COLUMNS, TABLE_SEED, ROWS))
    y = tablegen.labels(COLUMNS, LABEL, TABLE_SEED, ROWS)
    return codes, tablegen.values_table(COLUMNS, codes), y


@functools.lru_cache(maxsize=None)
def reference(quantized):
    """The reference's splits a step (feature, code threshold, in the
    order it made them) and its scores after each step, with the start."""
    codes, _, y = table()
    params = {**PARAMS, "use_quantized_grad": quantized}
    prog = ref._programs(*codes.shape)
    grads, apply_split, made = prog["grads"], prog["apply_split"], []

    def step(*args):                    # called once a tree, first
        made.append([])
        return grads(*args)

    def noted(leaf_id, codes_t, f, t, *rest):
        made[-1].append((int(f), int(t)))
        return apply_split(leaf_id, codes_t, f, t, *rest)

    prog.update(grads=step, apply_split=noted)
    try:
        scores, _ = ref.train(codes, y, params, STEPS)
    finally:
        prog.update(grads=grads, apply_split=apply_split)
    return made, [np.asarray(s, np.float64) for s in scores]


@functools.lru_cache(maxsize=None)
def grown(path):
    """The program's trees, its scores after each step and what it
    resolved to, for one path."""
    _, X, y = table()
    booster = lgb.Booster({**PARAMS, **PATHS[path]}, lgb.Dataset(X, label=y))
    engine = booster._engine
    resolved = {"packed": bool(engine._packed_cols),
                "kernel": engine.grower_cfg.hist_rm_backend,
                "quantized": engine.grower_cfg.quantized,
                "async": bool(engine._async_on()),
                "hist_expanded_rows": global_timer.counters[
                    "hist_expanded_rows"]}
    scores = []
    for _ in range(STEPS):
        booster.update()
        scores.append(np.asarray(engine.score, np.float64).reshape(-1))
    return list(engine.models), scores, resolved


def program_splits(trees):
    spec = tablegen.column_specs(COLUMNS)[0]
    values = tablegen.code_values(spec)
    return [[(int(t.split_feature[i]),
              int(np.searchsorted(values, t.threshold_real[i],
                                  side="right")) - 1)
             for i in range(t.num_leaves - 1)] for t in trees]


def score_gaps(program, want):
    """Each step's largest score gap in units of ``SCORE_GAP`` x (the
    score + the largest change the reference made to a score by then)."""
    start = want[0]
    return [float(np.max(np.abs(p - w) / (
        SCORE_GAP * (np.abs(w) + np.abs(w - start).max()))))
        for p, w in zip(program, want[1:])]


@pytest.mark.parametrize("path", list(PATHS))
def test_splits_are_the_plain_references(path):
    trees, _, resolved = grown(path)
    if path == "chip":
        # the int8 entry of the kernel: 24 rows of its operand, not 56
        assert resolved == {"packed": True, "kernel": "pallas",
                            "quantized": True, "async": True,
                            "hist_expanded_rows": 24}
    else:
        assert resolved["kernel"] == "scatter" and resolved["quantized"]
    want, _ = reference(True)
    assert len(want) == STEPS
    for got, made in zip(program_splits(trees), want):
        assert len(got) == PARAMS["num_leaves"] - 1
        assert got == made


@pytest.mark.parametrize("path", list(PATHS))
def test_scores_are_the_plain_references(path):
    _, scores, _ = grown(path)
    gaps = score_gaps(scores, reference(True)[1])
    assert max(gaps) <= 1.0, gaps


@pytest.mark.parametrize("path", list(PATHS))
def test_the_reference_without_quantization_is_refused(path):
    """The program that ignores ``use_quantized_grad`` grows other trees:
    float32 gradients split elsewhere, and the scores are far apart."""
    trees, scores, _ = grown(path)
    want, got = reference(False)
    assert program_splits(trees) != want
    assert min(score_gaps(scores, got)) > 1000
