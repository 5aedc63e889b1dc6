"""The ``bosch`` configuration: the manifest's entries and the published
shape; a ``--rehearse`` run of its cell, traced and not, ends in a
well-formed line that is ``correct``; the plain reference made to send every
missing value left is refused by the cell's limits; and the two-way scan's
share of its roofline from a made-up trace, with and without the program's
counters."""
import argparse
import json
import os

import numpy as np
import pytest

import run as harness
import stages
from conftest import BENCH, ROOT

CELL, CONFIG = "bosch.train", "bosch"


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def cfg(manifest):
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    with open(os.path.join(ROOT, entry["file"]), encoding="utf-8") as fh:
        return json.load(fh)


def last_line(capsys, trace: int, seed: int):
    rc = harness.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       "1", "--trace", str(trace), "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace,seed", [(0, 3_000_000_035), (1, 35)])
def test_rehearsal(capsys, trace, seed):
    line = last_line(capsys, trace, seed)
    assert line["correct"] is True and line["failed"] == 0
    assert line["compiles_in_window"] == 0
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "compared" and len(line["compared"]) == 5
    if trace:
        assert all(n.startswith("train.") for n in line["metrics"])
        # no device plane on the CPU: the new reader finds nothing to read
        # and leaves its metric out
        assert "train.scan_two_way_roofline" not in line["metrics"]
        assert "train.scan_roofline" not in line["metrics"]
    else:
        assert {"setup_s", "train_iters_per_s"} <= set(line["metrics"])


def test_manifest_entries(manifest, cfg):
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_trees"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert manifest["configs"][-1] is entry
    cell = manifest["workloads"][-1]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "train",
                    "chips": 1, "why": cell["why"]}
    listed = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
              if CELL in m.get("workloads", [CELL])]
    # every cell's three, the twelve per-layer numbers of both other cells,
    # the pool's stage and the one new share; not the one-way scan's
    assert len(listed) == 3 + 14
    assert "train.scan_two_way_roofline" in listed
    assert "train.stage.hist_subtract_ms" in listed
    assert "train.scan_roofline" not in listed
    new = manifest["per_layer"][-1]
    assert new == {"name": "train.scan_two_way_roofline", "unit": "%",
                   "better": "higher", "source": "device_trace",
                   "layer": "grower: split scan",
                   "moves": "train_iters_per_s", "workloads": [CELL]}


def test_configuration_is_the_published_one(cfg):
    # no row and no column cut
    assert cfg["num_data"] == cfg["published"]["num_data"] == 1_000_000
    assert cfg["num_features"] == cfg["published"]["num_features"] == 968
    assert cfg["columns"] == [{"kind": "count", "n": 968, "scale": 100.0,
                               "cap": 249, "missing": 0.8}]
    assert {k: cfg["params"][k] for k in (
        "num_leaves", "learning_rate", "max_bin", "min_data_in_leaf",
        "min_sum_hessian_in_leaf")} == {
            "num_leaves": 255, "learning_rate": 0.1, "max_bin": 255,
            "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 100}
    assert not any(k.startswith("tpu_") and k != "tpu_hist_dtype"
                   for k in cfg["params"])
    label = cfg["label"]
    assert label["columns"] == list(range(0, 950, 19))
    assert len(label["coefficients"]) == len(label["columns"]) + 2
    assert 20 < sum(c > 0 for c in label["coefficients"]) < 32
    # a missing value counts as a code near the mean of the present ones
    present = 100.0 * (1.0 - np.exp(-2.49)) - 0.5
    assert abs(label["missing_as"] - present) < 30
    assert os.path.exists(os.path.join(BENCH, cfg["reference"]))
    for key in ("columns", "missing", "label", "max_bin", "source"):
        assert key in cfg["assumed"]
    assert cfg["rehearse"]["columns"][0]["missing"] == 0.8


@pytest.fixture(scope="module")
def controls(cfg):
    """The reference in the program's place at rehearsal size: itself, and
    made to send every missing value left (run on codes in which the
    missing are code 0 and every value one code higher: a column without
    missing values whose lowest value they are)."""
    import jax.numpy as jnp
    cfg = {**cfg, **cfg["rehearse"]}
    workload = harness.load_json(os.path.join(BENCH, "workloads",
                                              CELL + ".json"))
    args = argparse.Namespace(seed=353535, seconds=0.0, trace=0,
                              rehearse=True)
    run = harness.Run(args, {"name": CELL, "config": CONFIG}, cfg, workload)
    driver = harness.load_module(cfg["driver"])
    ref = harness.load_module(cfg["reference"])
    y, order = driver.ordered_labels(run)
    codes = driver.ordered_codes(run, order)
    steps = workload["reference_steps"]
    want, _ = ref.train(codes, y, cfg["params"], steps)
    want = [np.asarray(s, np.float64) for s in want]
    left = jnp.where(codes == ref.MISSING, jnp.uint8(0),
                     codes + jnp.uint8(1))
    assert int(jnp.max(left)) < ref.MISSING
    got, _ = ref.train(left, y, cfg["params"], steps)
    got = [np.asarray(s, np.float64) for s in got]
    return ({"itself": driver.held(run, driver.readings(y, want[1:], want)),
             "left": driver.held(run, driver.readings(y, got[1:], want))},
            workload["limits"])


def test_every_missing_value_sent_left_is_not_correct(controls):
    readings, limits = controls
    assert harness.judge(readings["itself"], limits)[0]
    ok, compared = harness.judge(readings["left"], limits)
    assert not ok
    over = [k for k, c in compared.items() if c["value"] > c["limit"]]
    assert "score_p90_gap" in over


def test_two_way_scan_roofline_reads_the_programs_counters(monkeypatch):
    from lightgbm_tpu.utils.timer import global_timer
    module = harness.load_module("metrics/train.scan_two_way_roofline.py")
    assert module.table_bins([{"kind": "count", "n": 3, "scale": 100.0,
                               "cap": 249, "missing": 0.8}]) == 251
    assert module.table_bins([{"kind": "count", "n": 3, "scale": 6.0,
                               "cap": 40},
                              {"kind": "grid", "n": 2, "levels": 32}]) == 41
    ctx = {"trace": {"op_s": {"%a = f32[1]{0} fusion(": 2.0}},
           "peaks": {"hbm_bytes_per_s": 120.0, "bf16_flops_per_s": 1e9},
           "result": {"work": 1},
           "cfg": {"num_features": 10, "params": {"max_bin": 255},
                   "columns": [{"kind": "count", "n": 10, "scale": 2.0,
                                "cap": 5, "missing": 0.5}]}}
    monkeypatch.setattr(stages, "program_stage_map",
                        lambda: {"%a": "split_scan"})
    monkeypatch.setattr(stages, "_memo", [None, None])
    # 3 trees of 2 splits each, 6 values and the NaN bin: one tree's window
    # scans 2 x 2 x 10 x 7 = 280 cells, 28 s at the least, in 2 s of the
    # stage... a share over 100 % only here
    monkeypatch.setattr(global_timer, "counters", {"splits": 6, "trees": 3})
    assert module.read(ctx) == pytest.approx(100.0 * 28.0 / 2.0)
    # a program without the counters: nothing to read
    monkeypatch.setattr(global_timer, "counters", {"trees": 3})
    assert module.read(ctx) is None
    monkeypatch.setattr(stages, "_memo", [None, None])
    assert module.read(dict(ctx, trace=None)) is None
