"""The essential work of the split scan, counted from shapes and from the
program's count of splits: what the scan has to read, never what it
executed.

A split makes two children, and each child's best split is found from its
histogram: every used column, every bin, three float32 sums (gradient,
hessian, count). The root's scan, once a tree, is left out: the trace's
``split_scan`` stage holds it, so the share reads that much lower (one
part in 2 x splits).
"""
from __future__ import annotations

CELL_BYTES = 12       # gradient, hessian and count sums, float32


def scan_cells(splits: float, columns: int, bins: int) -> float:
    """Histogram cells the scans of ``splits`` splits had to read."""
    return splits * 2 * columns * bins


def scan_part(splits: float, columns: int, bins: int) -> dict:
    """{"bytes", "ops"} as ``work.least_seconds`` takes a part: every cell
    read once, one add (the running sum) and the gain's handful of
    operations left uncounted, the scan being bound by bytes."""
    cells = scan_cells(splits, columns, bins)
    return {"bytes": cells * CELL_BYTES, "ops": cells}
