"""Device seconds by the program's stages.

The traced run knows how long each compiled operation ran
(``reduce_trace``: ``op_s``, self seconds by the operation's whole HLO
line); the program knows which stage each of its compiled operations
belongs to (``lightgbm_tpu.utils.timer.stage_map``: the ``lgbm.<stage>``
scopes, read back out of the programs jit holds). This joins the two by the
name the line begins with. Operation names are XLA's and change with every
change to the program; the stages' names do not.

A program without a stage map (the parent of the PR that brought it) gives
``None``, and so does a trace without device operations (the CPU rehearsal).
"""
from __future__ import annotations

UNATTRIBUTED = "(unattributed)"
AMBIGUOUS = "(ambiguous)"
# what train.stage.rest_ms sums: the stages too small for a metric each
REST = ("gradients", "hist_subtract", "tree_update", "score_update")

_memo: list = [None, None]             # (the trace summary, its result)


def split_line(op: str):
    """An operation's HLO line -> (its name, its name and result shape):
    ``%fusion.3 = f32[8]{0} fusion(...)`` -> (``%fusion.3``,
    ``%fusion.3 = f32[8]{0}``)."""
    name, _, rest = op.partition(" = ")
    depth = 0
    for i, ch in enumerate(rest):           # the shape may be a tuple
        depth += ch == "("
        depth -= ch == ")"
        if ch == " " and depth == 0:
            return name, f"{name} = {rest[:i]}"
    return name, op


def join(op_s: dict, stage_map: dict) -> dict:
    """{stage: seconds}; what is in no stage, or in a name that two
    programs share and the shape does not settle, is ``(unattributed)``."""
    out: dict = {}
    for op, seconds in op_s.items():
        name, with_shape = split_line(op)
        stage = stage_map.get(name)
        if stage == AMBIGUOUS:
            stage = stage_map.get(with_shape)
        if stage is None:
            stage = UNATTRIBUTED
        out[stage] = out.get(stage, 0.0) + seconds
    return out


def program_stage_map():
    """The program's map, or None where the program has none."""
    try:
        from lightgbm_tpu.utils import timer
    except ImportError:
        return None
    stage_map = getattr(timer, "stage_map", None)
    return stage_map() if stage_map else None


def seconds_by_stage(ctx: dict):
    trace = ctx.get("trace")
    if not trace or not trace.get("op_s"):
        return None
    if _memo[0] is not trace:
        stage_map = program_stage_map()
        _memo[:] = [trace, None if not stage_map
                    else join(trace["op_s"], stage_map)]
    return _memo[1]


def stage_ms(ctx: dict, *stages: str):
    """Milliseconds an iteration of the window spent in ``stages``."""
    by_stage = seconds_by_stage(ctx)
    if by_stage is None or not ctx["result"]["work"]:
        return None
    return 1e3 * sum(by_stage.get(s, 0.0) for s in stages) \
        / ctx["result"]["work"]
