"""Device milliseconds an iteration spends in the program's ``partition_fetch``
stage (``stages.py``)."""
import stages


def read(ctx):
    return stages.stage_ms(ctx, "partition_fetch")
