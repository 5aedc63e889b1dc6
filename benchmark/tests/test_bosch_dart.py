"""The ``bosch-dart`` configuration: its table is ``bosch``'s; the cell's
traced iterations drop trees under ``drop_seed`` 4; a ``--rehearse`` run of
its cell, traced and not, ends in a well-formed line that is ``correct``;
the plain DART reference without drops is ``gbdt_leafwise``, and its walk
gives back its scores; both planted faults are refused by ``run.judge``;
the manifest's new entries, looked up by name."""
import argparse
import json
import os

import numpy as np
import pytest

import run as harness
from conftest import BENCH, ROOT

CELL, CONFIG = "bosch-dart.train", "bosch-dart"
NEW = ("train.stage.dart_rescore_ms", "train.dart_rows_m",
       "train.dart_rescore_roofline")
DART = {"boosting": "dart", "drop_rate": 0.1, "max_drop": 50,
        "skip_drop": 0.5, "uniform_drop": False, "xgboost_dart_mode": False,
        "drop_seed": 4}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        return json.load(fh)


def config(manifest, name):
    entry = next(c for c in manifest["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"]), encoding="utf-8") as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def cfg(manifest):
    return config(manifest, CONFIG)


@pytest.fixture(scope="module")
def workload():
    return harness.load_json(os.path.join(BENCH, "workloads",
                                          CELL + ".json"))


def test_table_and_settings_are_boschs(manifest, cfg):
    bosch = config(manifest, "bosch")
    for key in ("num_data", "num_features", "table_seed", "columns",
                "label", "published"):
        assert cfg[key] == bosch[key], key
    params = dict(cfg["params"])
    assert {k: params.pop(k) for k in DART} == DART
    assert params == bosch["params"]
    rehearse = dict(cfg["rehearse"])
    r_params = dict(rehearse.pop("params"))
    assert {k: r_params.pop(k) for k in DART} == DART
    assert r_params == bosch["rehearse"]["params"]
    assert rehearse == {k: v for k, v in bosch["rehearse"].items()
                        if k != "params"}


def test_manifest_entries(manifest, cfg):
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_trees"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "train",
                    "chips": 1, "why": cell["why"]}
    listed = {m["name"] for m in manifest["end_to_end"] +
              manifest["per_layer"] if CELL in m.get("workloads", [CELL])}
    bosch = {m["name"] for m in manifest["end_to_end"] +
             manifest["per_layer"] if "bosch.train" in
             m.get("workloads", ["bosch.train"])}
    assert listed == bosch | set(NEW)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_iters_per_s"


def test_traced_iterations_drop_trees(cfg, workload):
    ref = harness.load_module(cfg["reference"])
    first, traced = workload["first_steps"], workload["trace_steps"]
    window = workload["window_steps"]
    drops = ref.drop_sets(cfg["params"], first + window)
    assert any(drops[first:first + traced])
    assert sum(len(d) for d in drops[first:]) == 106


def last_line(capsys, trace: int, seed: int):
    rc = harness.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       "1", "--trace", str(trace), "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace,seed", [(0, 3_000_000_041), (1, 41)])
def test_rehearsal(capsys, trace, seed, workload):
    line = last_line(capsys, trace, seed)
    assert line["correct"] is True and line["failed"] == 0
    assert line["compiles_in_window"] == 0
    assert line["attempted"] == workload["trace_steps" if trace
                                         else "window_steps"]
    assert list(line)[-1] == "compared" and len(line["compared"]) == 7
    if trace:
        # rows x dropped trees: the traced iterations drop 2, 0, 4 and 4
        rows = line["metrics"]["train.dart_rows_m"]["value"]
        assert rows == pytest.approx(10 * 4000 / 4 / 1e6)
        # no device plane on the CPU: the stage's readers find nothing
        assert "train.stage.dart_rescore_ms" not in line["metrics"]
        assert "train.dart_rescore_roofline" not in line["metrics"]
    else:
        assert {"setup_s", "train_iters_per_s"} <= set(line["metrics"])


@pytest.fixture(scope="module")
def rehearsal(cfg, workload):
    cfg = {**cfg, **cfg["rehearse"]}
    args = argparse.Namespace(seed=414141, seconds=0.0, trace=0,
                              rehearse=True)
    run = harness.Run(args, {"name": CELL, "config": CONFIG}, cfg, workload)
    driver = harness.load_module(cfg["driver"])
    ref = harness.load_module(cfg["reference"])
    y, order = driver.ordered_labels(run)
    return run, driver, ref, y, driver.ordered_codes(run, order)


def test_without_drops_the_reference_is_gbdt_leafwise(rehearsal, cfg):
    run, driver, ref, y, codes = rehearsal
    leafwise = harness.load_module("references/gbdt_leafwise.py")
    params = {**cfg["rehearse"]["params"], "skip_drop": 1.0}
    got, trees = ref.train(codes, y, params, 4)
    want, _ = leafwise.train(codes, y, params, 4)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, np.asarray(w, np.float64), rtol=0,
                                   atol=2e-6)
    np.testing.assert_allclose(ref.walk(trees, codes), got[-1], rtol=0,
                               atol=1e-9)


def test_both_planted_faults_are_refused(rehearsal, workload):
    run, driver, *_ = rehearsal
    out = driver.controls(run)
    limits = workload["limits"]
    for name in ("normalisation skipped",
                 "missing values sent left in the walk"):
        ok, compared = harness.judge(driver.held(run, out[name]), limits)
        assert not ok, name
    def over(name):
        return {k for k, c in harness.judge(
            driver.held(run, out[name]), limits)[1].items()
            if c["value"] > c["limit"]}
    assert over("normalisation skipped") == {"dart_score_gap"}
    assert "forest_score_gap" in over("missing values sent left in the walk")
    assert over("reference in bfloat16") >= {"loss_gap_step1",
                                              "score_p90_gap"}
