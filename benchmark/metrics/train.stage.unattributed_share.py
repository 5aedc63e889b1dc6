"""Share of the device's busy seconds spent in operations that the program
puts in no stage (the compiler's own copies between memories, a loop's own
time), or whose name two programs share (``stages.py``)."""
import stages


def read(ctx):
    by_stage = stages.seconds_by_stage(ctx)
    if by_stage is None or not ctx["trace"]["busy_s"]:
        return None
    return 100.0 * by_stage.get(stages.UNATTRIBUTED, 0.0) \
        / ctx["trace"]["busy_s"]
