"""Device milliseconds an iteration spends in the program's ``gh_quantize``
stage (``stages.py``): the per-tree scales, the rounding and the int8 cast
of quantized-gradient training. A program that keeps these operations in
another stage (the parent of the PR that named the stage) gives ``None``,
never 0."""
import stages


def read(ctx):
    by_stage = stages.seconds_by_stage(ctx)
    if not by_stage or "gh_quantize" not in by_stage:
        return None
    return stages.stage_ms(ctx, "gh_quantize")
