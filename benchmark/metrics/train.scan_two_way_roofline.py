"""The split scan's share of its roofline on a table with missing values,
where the scan runs in both directions: least time for the histogram cells
the scan had to read in the traced window (``work_scan``: 2 children a
split, every column, every bin, 12 bytes a cell, read once whatever the
program does with them, at the chip's memory bandwidth) over the device
seconds of the stage ``split_scan``, which holds the reverse scan, the
forward scan and their merge.

The bins are the table's, not ``max_bin``: a count column's ``cap`` + 1
values, each in a bin of its own, and the NaN bin where the column has
missing values (251 of 255 on ``bosch``, the width the program scans); a
grid column's ``levels`` and the bin of zero, as ``train.scan_roofline``
counts them.

The splits are the program's own count (``global_timer.counters``:
``splits`` over ``trees``, the mean of every tree it made into a host
tree, times the window's trees). A program without those counters gives
``None``."""
import stages
import work
import work_scan


def table_bins(columns: list) -> int:
    """The most bins a column of the table fills."""
    return max(int(g["cap"]) + 1 + (g.get("missing", 0.0) > 0.0)
               if g["kind"] == "count" else int(g["levels"]) + 1
               for g in columns)


def read(ctx):
    by_stage = stages.seconds_by_stage(ctx)
    if by_stage is None or not ctx["peaks"]:
        return None
    try:
        from lightgbm_tpu.utils.timer import global_timer
    except ImportError:
        return None
    counters = getattr(global_timer, "counters", {})
    splits, trees = counters.get("splits"), counters.get("trees")
    scan_s = by_stage.get("split_scan", 0.0)
    if not splits or not trees or scan_s <= 0:
        return None
    cfg = ctx["cfg"]
    part = work_scan.scan_part(splits / trees * ctx["result"]["work"],
                               int(cfg["num_features"]),
                               table_bins(cfg["columns"]))
    return 100.0 * work.least_seconds(part, ctx["peaks"]) / scan_s
