"""A ``--rehearse`` run of each cell of ``BENCHMARK.json``, traced and not,
ends in a well-formed last line; and with the timed path broken
underneath, ``correct`` comes out false (a step that leaves its state
unchanged, half of the batch left out)."""
import json

import numpy as np
import pytest

import run as harness

TRAIN = "criteo-share.train"


def last_line(capsys, cell: str, trace: int = 0, seed: int = 3_000_000_019):
    rc = harness.main(["--workload", cell, "--seed", str(seed), "--seconds",
                       "1", "--trace", str(trace), "--rehearse"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def well_formed(line: dict, metric_names: set) -> None:
    assert list(line)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(line["device"])
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) <= metric_names
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    for c in line["compared"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("cell,rate", [(TRAIN, "train_iters_per_s")])
def test_untraced_run(capsys, cell, rate):
    line = last_line(capsys, cell)
    well_formed(line, {"setup_s", rate, "peak_hbm_gib"})
    assert {"setup_s", rate} <= set(line["metrics"])
    assert line["correct"] is True and line["failed"] == 0
    assert line["compiles_in_window"] == 0


@pytest.mark.parametrize("cell,prefix", [(TRAIN, "train.")])
def test_traced_run(capsys, cell, prefix):
    line = last_line(capsys, cell, trace=1, seed=11)
    assert all(n.startswith(prefix) for n in line["metrics"])
    # the whole step's share needs no device plane, so it is there even here
    assert prefix + "step_mfu" not in line["metrics"]   # cpu: no peaks
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert line["correct"] is True


def test_step_that_leaves_state_unchanged_is_not_correct(capsys, monkeypatch):
    import lightgbm_tpu as lgb
    monkeypatch.setattr(lgb.Booster, "update", lambda self, *a, **k: False)
    line = last_line(capsys, TRAIN)
    assert line["correct"] is False
    # the scores never left the program's start (zero, before the first
    # step adds the label mean's log-odds and a tree)
    gap = line["compared"]["change_norm_gap"]
    assert gap["value"] > 100 * gap["limit"]


def test_half_of_the_batch_left_out_is_not_correct(capsys, monkeypatch):
    driver = harness.load_module("drivers/train.py")
    whole = driver.load_table

    def half(path, y, order):
        w = (np.arange(len(y)) < len(y) // 2).astype(np.float32)
        return whole(path, y, order).set_weight(w)

    monkeypatch.setattr(driver, "load_table", half)
    line = last_line(capsys, TRAIN)
    assert line["correct"] is False
