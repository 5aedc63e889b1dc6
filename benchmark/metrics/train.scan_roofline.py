"""The split scan's share of its roofline: least time for the histogram
cells the scan had to read in the traced window (``work_scan``: 2 children
a split, every column, every bin, 12 bytes a cell, at the chip's memory
bandwidth) over the device seconds of the stage ``split_scan``.

The bins are the table's, not ``max_bin``: a grid column's ``levels``, each
in a bin of its own, and the bin of zero, which the grid steps over and the
quantiser keeps (251 of 255 on ``epsilon``, the width the program scans).

The splits are the program's own count (``global_timer.counters``:
``splits`` over ``trees``, the mean of every tree it made into a host
tree, times the window's trees). A program without those counters gives
``None``."""
import stages
import work
import work_scan


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
    bins = max(int(group["levels"]) for group in cfg["columns"]) + 1
    part = work_scan.scan_part(splits / trees * ctx["result"]["work"],
                               int(cfg["num_features"]), bins)
    return 100.0 * work.least_seconds(part, ctx["peaks"]) / scan_s
