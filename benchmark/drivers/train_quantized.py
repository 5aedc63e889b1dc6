"""Driver of the quantized-gradient training cells: ``drivers/train.py``'s
set-up, window, work and comparison, and controls of their own.

The controls put the plain reference (``references/gbdt_quantized.py``) in
the program's place as a program that does not keep the configuration's
guarantees would: with quantization off (float32 gradients, the program
that ignores ``use_quantized_grad``), with its histograms held in bfloat16
(a lower precision than the exact integer sums the configuration states),
with half of the batch left out, and with every step leaving the state as
it was.
"""
from __future__ import annotations

import time

import numpy as np

from drivers import train
from drivers.train import compare, held, readings, window, work  # noqa: F401


def setup(run) -> dict:
    """``train.setup``, and a line saying what the gradients went in as:
    24 expanded rows are the histogram kernel's int8 (or bfloat16) entry,
    56 its float32 one."""
    state = train.setup(run)
    from lightgbm_tpu.utils.timer import global_timer
    g = state["booster"]._engine.grower_cfg
    run.say(f"gradients: quantized {g.quantized}, levels {g.quant_bins}, "
            f"stochastic_rounding {g.stochastic_rounding}, "
            f"hist_expanded_rows "
            f"{global_timer.counters.get('hist_expanded_rows')}")
    return state


def controls(run, program: list = None, faults: bool = True) -> dict:
    """Readings as ``compare`` takes them, of the reference in the
    program's place with each fault; with ``program`` (``setup``'s
    ``first_scores``) the program's own readings come first, from the same
    run of the reference."""
    cfg = run.cfg
    ref = run.load_module(cfg["reference"])
    params = cfg["params"]
    y, order = train.ordered_labels(run)
    codes = train.ordered_codes(run, order)
    steps = int(run.workload["reference_steps"])
    t0 = time.perf_counter()
    want, _ = ref.train(codes, y, params, steps)
    want = [np.asarray(s, np.float64) for s in want]
    run.say(f"reference: {steps} steps in {time.perf_counter() - t0:.1f}s")
    out = {}
    if program is not None:
        out["program"] = readings(
            y, [p.astype(np.float64) for p in program[:steps]], want)
    if faults:
        for name, changed, kwargs in (
                ("reference with quantization off",
                 {"use_quantized_grad": False}, {}),
                ("reference with histograms in bfloat16", {},
                 {"precision": "bfloat16"}),
                ("half of the batch left out", {}, {"row_share": 0.5})):
            got, _ = ref.train(codes, y, {**params, **changed}, steps,
                               **kwargs)
            out[name] = readings(
                y, [np.asarray(s, np.float64) for s in got[1:]], want)
        out["a step that leaves the state unchanged"] = readings(
            y, [want[0]] * steps, want)
    return out
