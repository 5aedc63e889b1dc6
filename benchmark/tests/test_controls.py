"""The controls: the plain reference put in the program's place, computed
in the nearest precision below the one the configuration states (bfloat16
for float32), with half of the batch left out, or with every step leaving
the state as it was, has to come out as not correct: judged by
``run.judge``, the comparison of a benchmark run, under the cell's own
limits. Here at a size a test run can hold; ``controls.py`` reads them on
the chip at the cell's own size."""
import argparse
import json
import os

import numpy as np
import pytest

import run as harness
from conftest import BENCH

CELL, CONFIG = "criteo-share.train", "criteo-share"


@pytest.fixture(scope="module")
def found():
    """``driver.controls`` at rehearsal size, the reference standing in
    for the program too."""
    with open(os.path.join(BENCH, "configs", CONFIG + ".json"),
              encoding="utf-8") as fh:
        cfg = json.load(fh)
    cfg = {**cfg, **cfg["rehearse"]}
    workload = harness.load_json(os.path.join(BENCH, "workloads",
                                              CELL + ".json"))
    args = argparse.Namespace(seed=424242, seconds=0.0, trace=0,
                              rehearse=True)
    run = harness.Run(args, {"name": CELL, "config": CONFIG}, cfg, workload)
    driver = harness.load_module(cfg["driver"])
    ref = harness.load_module(cfg["reference"])
    y, order = driver.ordered_labels(run)
    codes = driver.ordered_codes(run, order)
    again, _ = ref.train(codes, y, cfg["params"],
                         workload["reference_steps"])
    program = [np.asarray(s, np.float32) for s in again[1:]]
    return ({name: driver.held(run, readings) for name, readings
             in driver.controls(run, program).items()}, workload["limits"])


def test_the_reference_against_itself_is_correct(found):
    readings, limits = found
    ok, compared = harness.judge(readings["program"], limits)
    assert ok and all(c["value"] == 0.0 for c in compared.values())


@pytest.mark.parametrize("control", [
    "reference in bfloat16", "half of the batch left out",
    "a step that leaves the state unchanged"])
def test_control_is_not_correct(found, control):
    readings, limits = found
    ok, compared = harness.judge(readings[control], limits)
    assert not ok
    assert any(c["value"] > c["limit"] for c in compared.values())


def test_judge_wants_every_limit_read_and_every_reading_a_number():
    limits = {"a": 1.0, "b": 2.0}
    assert harness.judge({"a": 0.5, "b": 2.0}, limits)[0]
    assert not harness.judge({"a": 0.5}, limits)[0]
    assert not harness.judge({"a": 0.5, "b": float("nan")}, limits)[0]
    assert not harness.judge({"a": 0.5, "b": None}, limits)[0]
    assert not harness.judge({"a": 0.5, "b": 2.5}, limits)[0]
