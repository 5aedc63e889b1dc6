"""Device milliseconds an iteration spends in the program's small stages:
gradients, histogram subtraction, tree and score updates (``stages.py``)."""
import stages


def read(ctx):
    return stages.stage_ms(ctx, *stages.REST)
