"""DART's re-scoring against its roofline: the least time for the window's
essential re-scoring bytes (``work_dart``: the score read and written once
and one byte a row for each dropped tree, an iteration that drops trees,
at the chip's memory bandwidth) over the device seconds of the stage
``dart_rescore``. A program without that stage gives ``None``."""
import stages
import work


def read(ctx):
    by_stage = stages.seconds_by_stage(ctx)
    part = ((ctx.get("work") or {}).get("parts") or {}).get("rescore")
    if not by_stage or not ctx["peaks"] or not part:
        return None
    seconds = by_stage.get("dart_rescore", 0.0)
    if seconds <= 0:
        return None
    return 100.0 * work.least_seconds(part, ctx["peaks"]) / seconds
