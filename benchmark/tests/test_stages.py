"""``stages.py`` joins per-operation device seconds with the program's
stage map, and the metrics that read it keep silent where there is nothing
to read."""
import json
import os

import pytest

import run
import stages
from conftest import BENCH, ROOT

NEW = ["train.stage.hist_gather_ms", "train.stage.hist_kernel_ms",
       "train.stage.split_scan_ms", "train.stage.partition_fetch_ms",
       "train.stage.partition_order_ms", "train.stage.rest_ms",
       "train.stage.unattributed_share", "train.host_dispatch_ms"]

# the trace names an operation by its whole HLO line
OP_S = {
    "%fusion.22 = u32[4096]{0:T(1024)} fusion(u32[34000000]{0} %p.1, "
    "s32[4096]{0} %p.2), kind=kLoop, calls=%fused_computation.22": 0.30,
    "%fusion.2 = u32[4096,17]{1,0:T(8,128)} fusion(u32[2000000,17]{1,0} "
    "%p.3), kind=kLoop, calls=%fused_computation.2": 0.20,
    "%_hist_pallas_impl.21 = f32[16,18432]{1,0} custom-call(s32[72,4096]"
    "{1,0} %p.4), custom_call_target=\"tpu_custom_call\"": 0.15,
    # one name in two programs: the grower's scan and the gradients
    "%fusion.1 = f32[2,67,250]{2,1,0} fusion(f32[2,67,250,3]{3,2,1,0} "
    "%p.5), kind=kLoop, calls=%fused_computation.1": 0.10,
    "%fusion.1 = f32[2000000]{0} fusion(f32[1,2000000]{1,0} %p.6), "
    "kind=kLoop, calls=%fused_computation.1": 0.02,
    # and once more with a shape that no program's map holds
    "%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p.7), kind=kLoop, "
    "calls=%fused_computation.1": 0.01,
    "%sort.3 = (s32[4096]{0}, s32[4096]{0}) sort(s32[4096]{0} %p.8, "
    "s32[4096]{0} %p.9), dimensions={0}, is_stable=true": 0.05,
    "%copy.7 = f32[255,12]{1,0} copy(f32[255,12]{1,0} %p.10)": 0.03,
    # a loop's own time carries no scope
    "%while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %tuple.1), "
    "condition=%cond.1, body=%body.1": 0.04,
}
STAGE_MAP = {
    "%fusion.22": "partition_fetch", "%fusion.2": "hist_gather",
    "%_hist_pallas_impl.21": "hist_kernel", "%sort.3": "partition_order",
    "%copy.7": "tree_update",
    "%fusion.1": stages.AMBIGUOUS,
    "%fusion.1 = f32[2,67,250]{2,1,0}": "split_scan",
    "%fusion.1 = f32[2000000]{0}": "gradients",
    "%fusion.never_ran": "score_update",
}


def test_join_by_the_name_the_line_begins_with():
    got = stages.join(OP_S, STAGE_MAP)
    assert got == pytest.approx({
        "partition_fetch": 0.30, "hist_gather": 0.20, "hist_kernel": 0.15,
        "split_scan": 0.10, "gradients": 0.02, "partition_order": 0.05,
        "tree_update": 0.03, stages.UNATTRIBUTED: 0.05})


def test_the_parts_add_up_to_the_whole():
    assert sum(stages.join(OP_S, STAGE_MAP).values()) == pytest.approx(
        sum(OP_S.values()))


def test_shared_name_without_a_settling_shape_is_unattributed():
    got = stages.join({"%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p)": 1.0},
                      {"%fusion.1": stages.AMBIGUOUS})
    assert got == {stages.UNATTRIBUTED: 1.0}


def test_a_loops_own_time_is_unattributed():
    got = stages.join({k: v for k, v in OP_S.items()
                       if k.startswith("%while")}, STAGE_MAP)
    assert got == {stages.UNATTRIBUTED: pytest.approx(0.04)}


@pytest.mark.parametrize("line, name, with_shape", [
    ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
     "%fusion.3", "%fusion.3 = f32[8]{0}"),
    ("%while = (s32[]{:T(128)}, f32[512,512]{1,0:T(8,128)S(1)}) "
     "while((s32[]{:T(128)}, f32[512,512]{1,0:T(8,128)S(1)}) %tuple.13), "
     "condition=%c, body=%b",
     "%while", "%while = (s32[]{:T(128)}, f32[512,512]{1,0:T(8,128)S(1)})"),
    ("not an HLO line", "not an HLO line", "not an HLO line")])
def test_split_line(line, name, with_shape):
    assert stages.split_line(line) == (name, with_shape)


def test_split_line_agrees_with_the_programs_parser():
    """The map's ``name = shape`` keys and the trace's lines meet."""
    from lightgbm_tpu.utils import timer
    text = ('  ROOT %fusion.9 = (f32[8]{0:T(128)S(1)}, s32[]{:T(128)}) '
            'fusion(%a, %b), kind=kLoop, calls=%fc.9, '
            'metadata={op_name="jit(f)/lgbm.split_scan/mul"}')
    (name, _, stage, shape), = timer.instructions(text)
    trace_line = ("%fusion.9 = (f32[8]{0:T(128)S(1)}, s32[]{:T(128)}) "
                  "fusion(f32[8]{0} %a, s32[] %b), kind=kLoop, calls=%fc.9")
    assert stages.split_line(trace_line) == (name, f"{name} = {shape}")
    assert stage == "split_scan"


def ctx(trace):
    spans = run.Spans(tracing=False)
    with spans("update"):
        pass
    return {"trace": trace, "spans": spans,
            "result": {"work": 2, "seconds": 1.8}}


NOTHING_ON_THE_DEVICE = {"window_s": 0.2, "busy_s": None, "op_s": {},
                         "module_s": {}, "span_s": {}, "idle_gaps": []}


@pytest.mark.parametrize("name", NEW[:-1])
@pytest.mark.parametrize("trace", [None, NOTHING_ON_THE_DEVICE])
def test_stage_metrics_are_silent_without_device_operations(name, trace):
    metric = run.load_module(f"metrics/{name}.py")
    assert metric.read(ctx(trace)) is None


def test_stage_metrics_are_silent_where_the_program_has_no_stage_map(
        monkeypatch):
    """The parent of the PR that brought the map has none."""
    monkeypatch.setattr(stages, "program_stage_map", lambda: None)
    trace = dict(NOTHING_ON_THE_DEVICE, busy_s=1.0, op_s=dict(OP_S))
    for name in NEW[:-1]:
        assert run.load_module(f"metrics/{name}.py").read(ctx(trace)) is None


def test_stage_metrics_read_a_planted_trace(monkeypatch):
    monkeypatch.setattr(stages, "program_stage_map", lambda: STAGE_MAP)
    trace = dict(NOTHING_ON_THE_DEVICE, busy_s=sum(OP_S.values()),
                 op_s=dict(OP_S))
    c = ctx(trace)
    got = {n: run.load_module(f"metrics/{n}.py").read(c) for n in NEW[:-1]}
    assert got == pytest.approx({
        "train.stage.hist_gather_ms": 100.0,
        "train.stage.hist_kernel_ms": 75.0,
        "train.stage.split_scan_ms": 50.0,
        "train.stage.partition_fetch_ms": 150.0,
        "train.stage.partition_order_ms": 25.0,
        "train.stage.rest_ms": 25.0,                # gradients + tree_update
        "train.stage.unattributed_share": 100.0 * 0.05 / 0.90})
    # the six stage readings and the unattributed seconds make busy_s
    ms = sum(v for n, v in got.items() if n.endswith("_ms"))
    assert ms / 1e3 * 2 + 0.05 == pytest.approx(trace["busy_s"])


def test_host_dispatch_reads_the_programs_records_inside_the_window():
    from lightgbm_tpu.utils.timer import Record, global_timer
    c = ctx(None)
    (_, lo, hi), = c["spans"].records
    mid = 0.5 * (lo + hi)
    kept = list(global_timer.records)
    global_timer.records.clear()
    try:
        global_timer.records.extend([
            Record("TreeLearner::Train", lo - 1.0, lo - 0.5, None, 15),
            Record("GBDT::Boosting", mid, mid + 0.004, None, 16),
            Record("TreeLearner::Train", mid, mid + 0.010, None, 16),
            Record("nested", mid, mid + 0.009, "TreeLearner::Train", 16)])
        metric = run.load_module("metrics/train.host_dispatch_ms.py")
        assert metric.read(c) == pytest.approx(7.0)       # 14 ms / 2
    finally:
        global_timer.records.clear()
        global_timer.records.extend(kept)


def test_the_eight_entries_sit_at_the_end_with_the_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        per_layer = json.load(fh)["per_layer"]
    assert [m["name"] for m in per_layer[-8:]] == NEW
    for m in per_layer[-8:]:
        assert m["workloads"] == ["criteo-share.train"]
        assert m["moves"] == "train_iters_per_s"
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
