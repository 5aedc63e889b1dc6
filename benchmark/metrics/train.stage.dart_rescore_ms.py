"""Device milliseconds an iteration spends in the program's ``dart_rescore``
stage (``stages.py``): DART's re-scoring of the dropped trees over the
training rows and the score updates it feeds. A program that keeps these
operations in no such stage (the parent of the PR that named it) gives
``None``, never 0."""
import stages


def read(ctx):
    by_stage = stages.seconds_by_stage(ctx)
    if not by_stage or "dart_rescore" not in by_stage:
        return None
    return stages.stage_ms(ctx, "dart_rescore")
