"""What the drivers share: the program's log, and its resolved choices."""
from __future__ import annotations

import sys


def capture_program_log(run) -> None:
    """Route the program's log lines into ``run.log`` (and on to standard
    error), where a driver looks for the fallbacks that the program only
    warns about."""
    from lightgbm_tpu.utils import log as program_log

    def sink(msg: str) -> None:
        run.log.append(msg)
        print(msg, file=sys.stderr, flush=True)

    program_log.register_logger(sink)


def resolved(eng) -> dict:
    """What ``auto`` resolved to (after ``chip_smoke._resolved``)."""
    g = eng.grower_cfg
    return {"row_sched": g.row_sched, "hist_rm_backend": g.hist_rm_backend,
            "async": bool(eng._async_on()),
            "packed_cols": int(eng._packed_cols),
            "partition_mode": g.partition_mode,
            "tree_learner": eng._tree_learner}
