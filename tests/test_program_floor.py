"""Regression gate for the grower's fixed program cost.

The split-loop while-body op count is the CPU-measurable proxy for the
per-split dispatch floor on device (docs/TPU_RUNBOOK.md cost model:
microseconds per instruction). Round 4 brought it 305 -> 128; this
test pins the ceiling so a refactor cannot silently regress the floor.
Lower the constant as the body shrinks — never raise it without a
device-measured justification.

Reference behavior being chased: the serial learner's split loop has no
per-split kernel-dispatch floor at all (ref:
src/treelearner/serial_tree_learner.cpp:183-249 — plain C++ loop).
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))

from body_opcount import analyze, dispatch_ops  # noqa: E402

# round-4 landed 128; round-5's paired (parent, new-leaf) scatters
# (_set_rows2) brought it to 105; the iteration-space suffix scan (no
# shift concats — and no tot-minus-prefix cancellation), the cumsum
# winner fetch, inline row packing, meta scalar constants and the
# paired node write brought it to 78. Lower as the body shrinks —
# never raise without a device-measured justification.
#
# Round 7: the gate counts DISPATCH-relevant body ops (body_opcount.
# dispatch_ops — tuple plumbing and literals never launch a kernel),
# because this image's XLA renames the fori body to a "wide.*region"
# clone whose raw line count includes ~30 get-tuple-element/constant
# lines the old metadata-matched body did not carry. The ceiling is
# RE-BASELINED to the new metric (61 measured + 4 slack for XLA
# fusion-boundary jitter) — carrying the old 78 over would hand a
# future regression ~17 free kernels per split.
BODY_INSTR_CEILING = 65


def test_while_body_op_floor():
    # small R keeps the compile fast; the body op count is R-stable
    # (verified: same 128 at R=16384 and R=4096)
    total, body_n, ops, _ = analyze(L=255, R=4096)
    assert body_n is not None, "grower while body not found in HLO"
    n_dispatch = dispatch_ops(ops)
    assert n_dispatch <= BODY_INSTR_CEILING, (
        f"while-body grew to {n_dispatch} dispatch ops "
        f"(> {BODY_INSTR_CEILING}); opcode histogram: "
        f"{sorted(ops.items(), key=lambda kv: -kv[1])}")
