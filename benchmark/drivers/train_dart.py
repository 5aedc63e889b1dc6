"""Driver of the DART training cells: ``drivers/train.py``'s set-up, a
window of a fixed number of iterations, and a comparison of its own.

The window runs the cell's ``window_steps`` iterations (``trace_steps``
when traced) whatever ``--seconds`` says: a DART iteration re-scores the
trees it drops, and their number grows with the trees there are, so a
window timed to fill would hand a faster program more work. Around each
iteration it reads the program's counters of the trees and rows the
re-scoring walked (``dart_trees_rescored``, ``dart_rows_rescored``;
``None`` for a program without them).

``compare`` lays the program's scores after each of its first
``reference_steps`` iterations against the plain DART reference's
(``references/gbdt_dart.py``; the trees it grows on the same codes) under
``drivers/train.py``'s readings, and the program's training scores after
the window, each as the largest gap of a row over the median size of a
score, against the reference's float64 walk of every tree of the
program's model text with its leaf values (``forest_score_gap``) and
against DART's arithmetic replayed in float64 on those trees' shapes
(``dart_score_gap``: every leaf valued from the gradients at the scores
the draw left). The replay is what holds the program to DART over 80
iterations: the program's trees and the reference's own part at a
near-tie split within a few steps on this table. Only ``lgb.Booster``'s
public calls make the model.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import tablegen
from drivers import train
from drivers.train import held, ordered_codes, ordered_labels, readings


def setup(run) -> dict:
    """``drivers/train.py``'s set-up, after a probe. The cell measures DART
    on the program's asynchronous device path; a program that runs this
    configuration on its synchronous path (each dropped tree brought to
    the host, uploaded and walked twice) cannot run the cell, and stops
    here, on a booster of 64 rows before the table is made, with an error
    and a non-zero exit. The CPU's rehearsal runs the synchronous path and
    is not probed."""
    if not run.rehearse:
        import lightgbm_tpu as lgb
        X = np.arange(128, dtype=np.float64).reshape(64, 2)
        probe = lgb.Booster(dict(run.cfg["params"]),
                            lgb.Dataset(X, label=np.arange(64) % 2))
        if not probe._engine._async_on():
            raise RuntimeError(
                "benchmark: this program runs boosting=dart on its "
                "synchronous path; the cell measures the asynchronous one")
        del probe
    return train.setup(run)


def counters():
    try:
        from lightgbm_tpu.utils.timer import global_timer
    except ImportError:
        return {}
    return getattr(global_timer, "counters", {})


def window(run, state: dict) -> dict:
    import jax
    booster = state["booster"]
    n = int(run.workload["trace_steps" if run.trace else "window_steps"])
    count = counters()
    walked = ("dart_trees_rescored", "dart_rows_rescored")
    per_step = []
    t0 = time.perf_counter()
    for _ in range(n):
        before = [count.get(k, 0) for k in walked]
        with run.spans("update"):
            booster.update()
        per_step.append([count.get(k, 0) - b for k, b in zip(walked, before)])
    with run.spans("drain"):
        jax.block_until_ready(booster._engine.score)
    seconds = time.perf_counter() - t0
    counted = all(k in count for k in walked)
    return {"work": n, "seconds": seconds, "attempted": n, "failed": 0,
            "steps_before": int(run.workload["first_steps"]),
            "dart_trees": [t for t, _ in per_step] if counted else None,
            "dart_rows": sum(r for _, r in per_step) if counted else None}


def work(run, state: dict, result: dict) -> dict:
    """``drivers/train.py``'s work of the grown trees, and the re-scoring's
    (``work_dart``) from the trees each window iteration drops by the
    configuration's draw, the same for every program and every seed."""
    import work_dart
    out = train.work(run, state, result)
    ref = run.load_module(run.cfg["reference"])
    first = result["steps_before"]
    drops = ref.drop_sets(run.cfg["params"], first + result["work"])[first:]
    part = work_dart.rescore_part([len(d) for d in drops],
                                  int(run.cfg["num_data"]))
    out["parts"]["rescore"] = part
    out["bytes"] += part["bytes"]
    out["ops"] += part["ops"]
    return out


def code_values(cfg: dict) -> list:
    return [tablegen.code_values(s)
            for s in tablegen.column_specs(cfg["columns"])]


def gap(score: np.ndarray, want: np.ndarray) -> float:
    """The largest gap of a row over the median size of a score."""
    return float(np.max(np.abs(score - want)) / np.median(np.abs(want)))


def forest_readings(ref, trees: list, codes, y, params: dict,
                    score: np.ndarray, **walk) -> dict:
    """``forest_score_gap``: ``score`` against the float64 walk of
    ``trees`` with their leaf values; ``dart_score_gap``: against DART's
    arithmetic replayed in float64 on the trees' shapes."""
    leaf = ref.leaves(trees, codes, **walk)
    walked = sum((np.asarray(t["leaf_value"], np.float64)[i]
                  for t, i in zip(trees, leaf)), np.zeros(len(score)))
    _, replayed = ref.replay(trees, leaf, y, params)
    return {"forest_score_gap": gap(score, walked),
            "dart_score_gap": gap(score, replayed)}


def compare(run, state: dict, result: dict) -> dict:
    cfg = run.cfg
    booster = state["booster"]
    model = booster.model_to_string()
    score = np.asarray(booster._engine.score, np.float64).reshape(-1)
    y, first, order = state["labels"], state["first_scores"], state["order"]
    del booster
    state.clear()                       # frees the booster and its table
    gc.collect()
    ref = run.load_module(cfg["reference"])
    t0 = time.perf_counter()
    codes = ordered_codes(run, order)
    steps = int(run.workload["reference_steps"])
    scores, _ = ref.train(codes, y, cfg["params"], steps)
    found = readings(y, [f.astype(np.float64) for f in first[:steps]],
                     scores)
    t1 = time.perf_counter()
    trees = ref.trees_from_text(model, code_values(cfg))
    found.update(forest_readings(ref, trees, codes, y, cfg["params"], score))
    run.say(f"reference: {steps} steps in {t1 - t0:.1f}s, the walk and the "
            f"replay of {len(trees)} trees in {time.perf_counter() - t1:.1f}s")
    return held(run, found)


def controls(run, program: list = None, faults: bool = True) -> dict:
    """The plain reference in the program's place: in bfloat16, with the
    normalisation skipped, with every missing value sent left in the walk,
    and with every step leaving the state as it was. The first steps'
    readings as ``compare`` takes them; the forest's and DART's from the
    reference's own trees and scores after the iterations up to the first
    that drops a tree. With ``program`` (``setup``'s ``first_scores``) its
    first steps' readings come first; its forest and DART readings are
    the cell's runs' alone."""
    cfg = run.cfg
    ref = run.load_module(cfg["reference"])
    params = cfg["params"]
    y, order = ordered_labels(run)
    codes = ordered_codes(run, order)
    steps = int(run.workload["reference_steps"])
    through = 1 + next(i for i, d in enumerate(ref.drop_sets(params, 200))
                       if d)
    t0 = time.perf_counter()
    want, trees = ref.train(codes, y, params, through)
    run.say(f"reference: {through} steps in {time.perf_counter() - t0:.1f}s")
    out = {}
    if program is not None:
        out["program"] = readings(
            y, [p.astype(np.float64) for p in program[:steps]],
            want[:steps + 1])

    def read(got, got_trees, **walk):
        return dict(readings(y, got[1:steps + 1], want[:steps + 1]),
                    **forest_readings(ref, got_trees, codes, y, params,
                                      got[-1], **walk))
    if faults:
        for name, kwargs in (("reference in bfloat16",
                              {"precision": "bfloat16"}),
                             ("normalisation skipped",
                              {"normalize": False})):
            out[name] = read(*ref.train(codes, y, params, through,
                                        **kwargs))
        out["missing values sent left in the walk"] = read(
            want, trees, missing_left=True)
        out["a step that leaves the state unchanged"] = read(
            [want[0]] * (through + 1), trees)
    return out
