"""The essential work of DART's re-scoring, counted from the trees each
iteration drops: what any implementation has to move, never what the
program's walk executed.

An iteration that drops trees reads and writes the training score once
(float32 in and out: 8 bytes a row), and reads for each dropped tree the
least in which a row's leaf can be known, one byte a row at up to 256
leaves (a walk reads more; a stored leaf id reads that). One add a row a
tree. An iteration that drops nothing re-scores nothing.
"""
from __future__ import annotations

SCORE_BYTES = 8       # the score read and written, float32
LEAF_BYTES = 1        # a row's leaf in one of up to 256


def rescore_part(drops: list, rows: int) -> dict:
    """{"bytes", "ops"} as ``work.least_seconds`` takes a part, for
    iterations that drop ``drops[i]`` trees each."""
    return {"bytes": sum(rows * (SCORE_BYTES + LEAF_BYTES * d)
                         for d in drops if d),
            "ops": sum(rows * d for d in drops)}
