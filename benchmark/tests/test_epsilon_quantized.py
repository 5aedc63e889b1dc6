"""The ``epsilon-quantized`` configuration: a ``--rehearse`` run of its
cell, traced and not, ends in a well-formed line that is ``correct``; the
plain quantized reference with quantization off is ``gbdt_leafwise``; each
of the four controls of ``drivers/train_quantized.py`` is refused by the
cell's limits; the new stage metric reads nothing without a trace and a
number from a recorded one; the manifest's new entries, looked up by
name."""
import argparse
import json
import os

import numpy as np
import pytest

import reduce_trace
import run as harness
import stages
from conftest import BENCH, HERE, ROOT

CELL, CONFIG = "epsilon-quantized.train", "epsilon-quantized"
NEW = "train.stage.gh_quantize_ms"
RECORDED = os.path.join(HERE, "data", "tiny.xplane.pb")


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


@pytest.mark.parametrize("trace,seed", [(0, 3_000_000_039), (1, 39)])
def test_rehearsal(capsys, trace, seed):
    line = last_line(capsys, trace, seed)
    assert line["correct"] is True and line["failed"] == 0
    assert line["compiles_in_window"] == 0
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "compared" and len(line["compared"]) == 5
    if trace:
        assert all(n.startswith("train.") for n in line["metrics"])
        # no device plane on the CPU: the new readers find nothing to read
        # and leave their metrics out
        assert NEW not in line["metrics"]
    else:
        assert {"setup_s", "train_iters_per_s"} <= set(line["metrics"])


def test_manifest_entries(manifest, cfg):
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_trees"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": "train",
                    "chips": 1, "why": cell["why"]}
    listed = {m["name"] for m in manifest["end_to_end"] +
              manifest["per_layer"] if CELL in m.get("workloads", [CELL])}
    # the table has no missing value: the forward scan is not compiled in
    assert "train.scan_two_way_roofline" not in listed
    assert {"setup_s", "peak_hbm_gib", "train_iters_per_s",
            "train.stage.hist_kernel_ms", "train.scan_roofline",
            "train.hist_roofline", "train.step_mfu",
            "train.partition_rows_m", NEW} <= listed
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert by_name[NEW] == {
        "name": NEW, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "grower: gradient quantization",
        "moves": "train_iters_per_s", "workloads": [CELL]}
    assert os.path.exists(os.path.join(BENCH, "metrics", NEW + ".py"))


def test_configuration_is_epsilons_with_quantized_gradients(manifest, cfg):
    entry = next(c for c in manifest["configs"] if c["name"] == "epsilon")
    with open(os.path.join(ROOT, entry["file"]), encoding="utf-8") as fh:
        epsilon = json.load(fh)
    # the same table: every seed's rows, labels and bins are epsilon's
    for key in ("num_data", "num_features", "table_seed", "columns",
                "label", "published"):
        assert cfg[key] == epsilon[key], key
    quantized = {"use_quantized_grad": True, "num_grad_quant_bins": 4,
                 "stochastic_rounding": False,
                 "quant_train_renew_leaf": False}
    assert cfg["params"] == {**epsilon["params"], **quantized}
    assert cfg["rehearse"]["params"] == {**epsilon["rehearse"]["params"],
                                         **quantized}
    for key in ("columns", "label", "max_bin", "source",
                "stochastic_rounding"):
        assert key in cfg["assumed"]
    assert "guarantees" in cfg
    assert os.path.exists(os.path.join(BENCH, cfg["reference"]))
    assert os.path.exists(os.path.join(BENCH, cfg["driver"]))


def small_table(rows=2048, cols=16):
    import tablegen
    columns = [{"kind": "grid", "n": cols, "levels": 32}]
    label = {"columns": [0, 3, 7, cols - 1],
             "coefficients": [0.9, -0.7, 0.5, 0.8, -0.6, 0.4],
             "noise": 1.0, "noise_seed": 7}
    return (tablegen.codes(columns, 20261005, rows),
            tablegen.labels(columns, label, 20261005, rows))


def test_without_quantization_the_reference_is_gbdt_leafwise(cfg):
    quantized = harness.load_module("references/gbdt_quantized.py")
    leafwise = harness.load_module("references/gbdt_leafwise.py")
    codes, y = small_table()
    params = {**cfg["rehearse"]["params"], "num_leaves": 15,
              "min_sum_hessian_in_leaf": 5.0}
    got, _ = quantized.train(codes, y, {**params,
                                        "use_quantized_grad": False}, 2)
    want, _ = leafwise.train(codes, y, params, 2)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-6)
    # and with it, other trees
    on, _ = quantized.train(codes, y, params, 2)
    assert np.abs(np.asarray(on[1]) - np.asarray(want[1])).max() > 1e-3


def test_stochastic_rounding_is_refused():
    quantized = harness.load_module("references/gbdt_quantized.py")
    with pytest.raises(ValueError, match="stochastic_rounding"):
        quantized.quant_bins({"use_quantized_grad": True})
    assert quantized.quant_bins({"use_quantized_grad": False}) == 0


@pytest.fixture(scope="module")
def controls(cfg):
    """The four controls of ``drivers/train_quantized.py`` at rehearsal
    size, and what judges them."""
    cfg = {**cfg, **cfg["rehearse"]}
    workload = harness.load_json(os.path.join(BENCH, "workloads",
                                              CELL + ".json"))
    args = argparse.Namespace(seed=393939, seconds=0.0, trace=0,
                              rehearse=True)
    run = harness.Run(args, {"name": CELL, "config": CONFIG}, cfg, workload)
    driver = harness.load_module(cfg["driver"])
    return (driver.controls(run), {"run": run, "driver": driver,
                                   "limits": workload["limits"]})


def test_each_control_is_refused(controls):
    readings, ctx = controls
    assert list(readings) == [
        "reference with quantization off",
        "reference with histograms in bfloat16",
        "half of the batch left out", "a step that leaves the state unchanged"]
    for name, found in readings.items():
        ok, _ = harness.judge(ctx["driver"].held(ctx["run"], found),
                              ctx["limits"])
        assert not ok, name


def test_new_metric_is_silent_without_a_trace():
    metric = harness.load_module(f"metrics/{NEW}.py")
    assert metric.read({"trace": None, "peaks": {}, "result":
                        {"work": 2}}) is None


def test_new_metric_reads_a_recorded_trace(monkeypatch):
    summary = reduce_trace.reduce(RECORDED)
    (line, seconds), = [(n, s) for n, s in summary["op_s"].items()
                        if n.startswith("%fusion.11 ")]
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    ctx = {"trace": summary, "peaks": peaks, "result": {"work": 3}}
    gh_quantize = harness.load_module(f"metrics/{NEW}.py")
    monkeypatch.setattr(stages, "_memo", [None, None])
    # a program whose map has no such stage (the parent of the PR that
    # named it): nothing
    monkeypatch.setattr(stages, "program_stage_map",
                        lambda: {"%fusion.11": "gradients"})
    assert gh_quantize.read(ctx) is None
    monkeypatch.setattr(stages, "_memo", [None, None])
    monkeypatch.setattr(stages, "program_stage_map",
                        lambda: {"%fusion.11": "gh_quantize"})
    assert gh_quantize.read(ctx) == pytest.approx(1e3 * seconds / 3)
