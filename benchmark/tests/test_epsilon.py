"""The ``epsilon`` configuration: a ``--rehearse`` run of its cell, traced
and not, ends in a well-formed line that is ``correct``; the split scan's
essential work against a hand count on a three-leaf tree; and the scan's
share of its roofline from a made-up trace, with and without the program's
counters."""
import json
import os

import pytest

import run as harness
import stages
import work
import work_scan
from conftest import BENCH, ROOT

CELL = "epsilon.train"


def last_line(capsys, trace: int, seed: int):
    rc = harness.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       "1", "--trace", str(trace), "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("trace,seed", [(0, 3_000_000_033), (1, 33)])
def test_rehearsal(capsys, trace, seed):
    line = last_line(capsys, trace, seed)
    assert line["correct"] is True and line["failed"] == 0
    assert line["compiles_in_window"] == 0
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "compared" and len(line["compared"]) == 5
    if trace:
        assert all(n.startswith("train.") for n in line["metrics"])
        # no device plane on the CPU: the two new readers find nothing to
        # read and leave their metrics out
        assert "train.scan_roofline" not in line["metrics"]
        assert "train.stage.hist_subtract_ms" not in line["metrics"]
    else:
        assert {"setup_s", "train_iters_per_s"} <= set(line["metrics"])


def test_configuration_is_the_published_one():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        entry = next(c for c in json.load(fh)["configs"]
                     if c["name"] == "epsilon")
    with open(os.path.join(ROOT, entry["file"]), encoding="utf-8") as fh:
        cfg = json.load(fh)
    assert entry["reduced"] == ["num_trees"]
    assert entry["source"] == cfg["source"]
    # no row and no column cut
    assert cfg["num_data"] == cfg["published"]["num_data"] == 400_000
    assert cfg["num_features"] == cfg["published"]["num_features"] == 2_000
    assert sum(g["n"] for g in cfg["columns"]) == cfg["num_features"]
    assert {k: cfg["params"][k] for k in (
        "num_leaves", "learning_rate", "max_bin", "min_data_in_leaf",
        "min_sum_hessian_in_leaf")} == {
            "num_leaves": 255, "learning_rate": 0.1, "max_bin": 255,
            "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 100}
    label = cfg["label"]
    assert label["columns"] == list(range(0, 2000, 40))
    assert len(label["coefficients"]) == len(label["columns"]) + 2
    assert sum(c * c for c in label["coefficients"]) == pytest.approx(3, abs=.1)
    assert 20 < sum(c > 0 for c in label["coefficients"]) < 32
    assert os.path.exists(os.path.join(BENCH, cfg["reference"]))


def test_scan_cells_by_hand():
    # a three-leaf tree has two splits; each makes two children, and each
    # child's scan reads every column's every bin: 2 x 2 x 10 x 7
    assert work_scan.scan_cells(2, columns=10, bins=7) == 280
    part = work_scan.scan_part(2, columns=10, bins=7)
    assert part == {"bytes": 280 * 12, "ops": 280}
    peaks = {"hbm_bytes_per_s": 120.0, "bf16_flops_per_s": 1e9}
    assert work.least_seconds(part, peaks) == 28.0


def test_scan_roofline_reads_the_programs_counters(monkeypatch):
    from lightgbm_tpu.utils.timer import global_timer
    read = harness.load_module("metrics/train.scan_roofline.py").read
    ctx = {"trace": {"op_s": {"%a = f32[1]{0} fusion(": 2.0}},
           "peaks": {"hbm_bytes_per_s": 120.0, "bf16_flops_per_s": 1e9},
           "result": {"work": 1},
           "cfg": {"num_features": 10, "params": {"max_bin": 255},
                   "columns": [{"kind": "grid", "n": 10, "levels": 6}]}}
    monkeypatch.setattr(stages, "program_stage_map",
                        lambda: {"%a": "split_scan"})
    monkeypatch.setattr(stages, "_memo", [None, None])
    # 3 trees of 2 splits each, 6 levels and the bin of zero: one tree's
    # window scans 280 cells, 28 s at the least, in 2 s of the stage... a
    # share over 100 % only here
    monkeypatch.setattr(global_timer, "counters", {"splits": 6, "trees": 3})
    assert read(ctx) == pytest.approx(100.0 * 28.0 / 2.0)
    # the parent of the PR that brought the counters: nothing to read
    monkeypatch.setattr(global_timer, "counters", {"trees": 3})
    assert read(ctx) is None
    monkeypatch.setattr(stages, "_memo", [None, None])
    assert read(dict(ctx, trace=None)) is None
