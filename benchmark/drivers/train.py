"""Driver of the training cells: ``Booster.update`` on a binned table.

Set-up loads the configuration's binned table from the checkout's cache
(the first run there bins it and saves it), puts its rows and labels in this
seed's order, builds the booster and drives it through its first iterations, each
drained; their scores are what ``compare`` lays against the plain
reference. The same booster is then handed to the window.
"""
from __future__ import annotations

import gc
import json
import os
import time
import zlib

import numpy as np

import tablegen
from drivers import common


def table_path(run) -> str:
    """Keyed by all that the features depend on: rows, seed and columns."""
    cfg = run.cfg
    columns = zlib.crc32(json.dumps(cfg["columns"], sort_keys=True).encode())
    return os.path.join(
        run.data_dir, f"{run.cell['config']}-{cfg['num_data']}-"
        f"{cfg['table_seed']}-{columns:08x}.bin")


def build_table(run, path: str) -> None:
    """Bin the float table once, in its own order and with its labels, and
    save it; later runs of any seed load."""
    import lightgbm_tpu as lgb
    cfg = run.cfg
    t0 = time.perf_counter()
    y = tablegen.labels(cfg["columns"], cfg["label"], cfg["table_seed"],
                        cfg["num_data"])
    codes = np.asarray(tablegen.codes(cfg["columns"], cfg["table_seed"],
                                      cfg["num_data"]))
    X = tablegen.values_table(cfg["columns"], codes)
    del codes
    ds = lgb.Dataset(X, label=y, params={"max_bin": cfg["params"]["max_bin"]})
    ds.construct()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".partial"
    ds.save_binary(tmp)
    os.replace(tmp, path)
    run.say(f"binned and saved {path} in {time.perf_counter() - t0:.1f}s")


def load_table(path: str, y: np.ndarray, order: np.ndarray):
    """The cached binned table with its rows in ``order``; ``y`` is in that
    order already."""
    import lightgbm_tpu as lgb
    ds = lgb.Dataset(path).subset(order)
    ds.used_indices = order      # subset() sorts what it is given
    ds.set_label(y)
    return ds.construct()


def ordered_labels(run):
    """(the table's labels in this seed's row order, the order)."""
    cfg = run.cfg
    order = tablegen.row_order(run.seed, cfg["num_data"])
    y = tablegen.labels(cfg["columns"], cfg["label"], cfg["table_seed"],
                        cfg["num_data"])
    return y[order], order


def ordered_codes(run, order: np.ndarray):
    """The table's codes [F, rows] on the device, rows in ``order``."""
    import jax.numpy as jnp
    cfg = run.cfg
    codes = tablegen.codes(cfg["columns"], cfg["table_seed"],
                           cfg["num_data"])
    return jnp.take(codes, jnp.asarray(order), axis=1)


def setup(run) -> dict:
    import jax
    import lightgbm_tpu as lgb
    common.capture_program_log(run)
    cfg = run.cfg
    y, order = ordered_labels(run)
    path = table_path(run)
    if not os.path.exists(path):
        build_table(run, path)
    t0 = time.perf_counter()
    ds = load_table(path, y, order)
    booster = lgb.Booster(dict(cfg["params"]), ds)
    eng = booster._engine
    run.say(f"table loaded and booster built in "
            f"{time.perf_counter() - t0:.1f}s; resolved "
            f"{common.resolved(eng)}")
    # the first steps, each drained: as many as the program's own checks
    # take to come round once (its stop check fires every 16th iteration
    # and compiles a program of its own), so that the window compiles
    # nothing; the scores after the first few go to the comparison
    first_scores, step_s = [], []
    for k in range(int(run.workload["first_steps"])):
        t0 = time.perf_counter()
        booster.update()
        jax.block_until_ready(eng.score)
        step_s.append(time.perf_counter() - t0)
        if k < int(run.workload["reference_steps"]):
            first_scores.append(np.asarray(eng.score, np.float32).reshape(-1))
    run.say(f"first steps took {[round(s, 3) for s in step_s]} s")
    infeasible = [m for m in run.log if "infeasible" in m]
    if infeasible:
        raise RuntimeError("benchmark: the histogram kernel's tiles do not "
                           f"fit, the fallback ran: {infeasible}")
    return {"booster": booster, "labels": y, "order": order,
            "first_scores": first_scores, "steady_s": step_s[-1]}


def window(run, state: dict) -> dict:
    import jax
    booster = state["booster"]
    eng = booster._engine
    n = run.steps(state["steady_s"])
    t0 = time.perf_counter()
    for _ in range(n):
        with run.spans("update"):
            booster.update()
    with run.spans("drain"):
        jax.block_until_ready(eng.score)
    seconds = time.perf_counter() - t0
    return {"work": n, "seconds": seconds, "attempted": n, "failed": 0,
            "steps_before": int(run.workload["first_steps"])}


def work(run, state: dict, result: dict) -> dict:
    """Essential work of the window's iterations, from the grown trees."""
    import work as work_fns
    eng = state["booster"]._engine
    trees = eng.models[result["steps_before"]:
                       result["steps_before"] + result["work"]]
    cfg = run.cfg
    return work_fns.train_iterations(
        [{"left_child": np.asarray(t.left_child),
          "right_child": np.asarray(t.right_child),
          "internal_count": np.asarray(t.internal_count),
          "leaf_count": np.asarray(t.leaf_count),
          "num_leaves": int(t.num_leaves)} for t in trees],
        rows=cfg["num_data"], features=cfg["num_features"])


def compare(run, state: dict, result: dict) -> dict:
    """The program's scores after each of its first steps against the
    plain reference's, grown from the same codes and labels."""
    cfg = run.cfg
    y, first, order = state["labels"], state["first_scores"], state["order"]
    state.clear()                       # frees the booster and its table
    gc.collect()
    ref = run.load_module(cfg["reference"])
    t0 = time.perf_counter()
    codes = ordered_codes(run, order)
    steps = int(run.workload["reference_steps"])
    scores, losses = ref.train(codes, y, cfg["params"], steps)
    scores = [np.asarray(s, np.float64) for s in scores]
    del codes
    run.say(f"reference: {steps} steps in {time.perf_counter() - t0:.1f}s; "
            f"its first loss, summed in float32 on the device {losses[0]!r},"
            f" in float64 on the host {logloss(scores[1], y)!r}")
    return held(run, readings(
        y, [f.astype(np.float64) for f in first[:steps]], scores))


def held(run, found: dict) -> dict:
    """The readings that the cell's file holds to a limit; the others are
    said and not compared (``PERF.md`` says why each went out)."""
    limits = run.workload["limits"]
    rest = {k: v for k, v in found.items() if k not in limits}
    if rest:
        run.say("read, not compared: " + json.dumps(rest))
    return {k: v for k, v in found.items() if k in limits}


def logloss(score: np.ndarray, y: np.ndarray) -> float:
    return float(np.mean(np.logaddexp(0.0, score) - y * score))


def readings(y, program: list, reference: list) -> dict:
    """``program``: scores after steps 1..k; ``reference``: the start score
    and the scores after steps 1..k. Every gap is a share of the
    reference's own number. Both sides' losses are summed here, in
    float64, from their float32 scores."""
    start, out = reference[0], {}
    for k, (p, r) in enumerate(zip(program, reference[1:]), 1):
        ref_loss = logloss(r, y)
        out[f"loss_gap_step{k}"] = abs(logloss(p, y) - ref_loss) / ref_loss
    # the first tree as the scores get it, and the change after all steps:
    # gap between the norms, not norm of the gap
    for name, k in (("step1_norm_gap", 1), ("change_norm_gap", len(program))):
        ref_norm = np.linalg.norm(reference[k] - start)
        out[name] = abs(np.linalg.norm(program[k - 1] - start) - ref_norm) \
            / ref_norm
    # row by row after the last step: the 90th percentile leaves out the few
    # rows that a near-tie between two splits sends to another leaf
    diff = np.abs(program[-1] - reference[len(program)])
    scale = np.median(np.abs(reference[len(program)] - start))
    out["score_p90_gap"] = float(np.percentile(diff, 90) / scale)
    out["score_mean_gap"] = float(np.mean(diff) / scale)
    return {k: float(v) for k, v in out.items()}


def controls(run, program: list = None, faults: bool = True) -> dict:
    """The reference in the program's place: in bfloat16, with half of the
    batch left out, and with every step leaving the state as it was.
    Readings as ``compare`` takes them; with ``program`` (its scores after
    the first steps, ``setup``'s ``first_scores``) the program's own
    readings come first, from the same run of the reference."""
    cfg = run.cfg
    ref = run.load_module(cfg["reference"])
    y, order = ordered_labels(run)
    codes = ordered_codes(run, order)
    steps = int(run.workload["reference_steps"])
    t0 = time.perf_counter()
    want, _ = ref.train(codes, y, cfg["params"], steps)
    want = [np.asarray(s, np.float64) for s in want]
    run.say(f"reference: {steps} steps in {time.perf_counter() - t0:.1f}s")
    out = {}
    if program is not None:
        out["program"] = readings(
            y, [p.astype(np.float64) for p in program[:steps]], want)
    if faults:
        for name, kwargs in (
                ("reference in bfloat16", {"precision": "bfloat16"}),
                ("half of the batch left out", {"row_share": 0.5})):
            got, _ = ref.train(codes, y, cfg["params"], steps, **kwargs)
            out[name] = readings(
                y, [np.asarray(s, np.float64) for s in got[1:]], want)
        out["a step that leaves the state unchanged"] = readings(
            y, [want[0]] * steps, want)
    return out
