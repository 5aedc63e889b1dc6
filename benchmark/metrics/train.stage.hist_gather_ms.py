"""Device milliseconds an iteration spends in the program's ``hist_gather``
stage (``stages.py``)."""
import stages


def read(ctx):
    return stages.stage_ms(ctx, "hist_gather")
