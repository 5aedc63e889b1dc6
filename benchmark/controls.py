"""Reads the controls and the planted faults at a cell's own size.

    python benchmark/controls.py --workload <cell> --seeds 1,2,3 \\
        [--program-seeds 4,5,6]

For every seed of ``--seeds`` the program goes through the cell's first
steps (no window is measured), the plain reference is run once, and then
the reference is put in the program's place: computed in the nearest
precision below the configuration's, and with each fault the cell's driver
can plant (``driver.controls``). For every seed of ``--program-seeds`` only
the program is read. Each set of readings is judged by ``run.judge``, the
comparison of a benchmark run, and printed beside what refused it. Not part
of a benchmark run: this is where the lower and upper readings in
``PERF.md`` come from.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import run as harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.trace, args.seconds = 0, 0.0
    opened = harness.open_cell(args)
    if isinstance(opened, int):
        return opened
    _, cell, cfg, workload, _ = opened
    driver = harness.load_module(cfg["driver"])
    # the comparison needs the first steps only, not the whole warm-up
    workload = dict(workload, first_steps=workload["reference_steps"])
    seeds = [(int(s), True) for s in args.seeds.split(",") if s] + \
        [(int(s), False) for s in args.program_seeds.split(",") if s]
    for seed, faults in seeds:
        args.seed = seed
        run = harness.Run(args, cell, cfg, workload)
        state = driver.setup(run)
        program = state["first_scores"]
        state.clear()
        gc.collect()
        for name, readings in driver.controls(run, program, faults).items():
            ok, compared = harness.judge(driver.held(run, readings),
                                         workload["limits"])
            over = [k for k, c in compared.items()
                    if not c["value"] <= c["limit"]]
            print("[control] " + json.dumps(
                {"seed": seed, "control": name, "correct": ok,
                 "refused_by": over, "readings": readings}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
