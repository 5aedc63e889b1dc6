"""Device milliseconds an iteration spends in the program's ``split_scan``
stage (``stages.py``)."""
import stages


def read(ctx):
    return stages.stage_ms(ctx, "split_scan")
