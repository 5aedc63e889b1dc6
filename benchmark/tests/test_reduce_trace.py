"""The trace reducer: interval arithmetic by hand, and a small trace that
was recorded on the chip."""
import os

import pytest

import reduce_trace as rt
from conftest import HERE

RECORDED = os.path.join(HERE, "data", "tiny.xplane.pb")


def test_union_and_clip():
    assert rt.union([[3, 4], [0, 1], [0.5, 2]]) == [[0, 2], [3, 4]]
    assert rt.clip([[0, 2], [3, 4]], 1, 3.5) == [[1, 2], [3, 3.5]]


def test_self_time_leaves_out_nested_events():
    events = [(0.0, 10.0, "while"), (1.0, 3.0, "fusion"),
              (4.0, 5.0, "fusion"), (4.2, 4.4, "inner"), (12.0, 13.0, "copy")]
    out = rt.self_times(events)
    assert out["while"] == pytest.approx(7.0)
    assert out["fusion"] == pytest.approx(2.8)
    assert out["inner"] == pytest.approx(0.2)
    assert out["copy"] == pytest.approx(1.0)


def test_gaps_go_to_the_innermost_open_span():
    busy = [[1.0, 2.0], [5.0, 6.0]]
    spans = [(0.0, 4.5, "update"), (2.5, 3.0, "inner"), (4.5, 10.0, "drain")]
    gaps = rt.gaps_by_span(busy, spans, 0.0, 8.0)
    # [0,1) mid .5 -> update; [2,5) mid 3.5 -> update; [6,8) mid 7 -> drain
    assert gaps == {"update": pytest.approx(4.0), "drain": pytest.approx(2.0)}


def test_summary_by_hand():
    spans = [(0.0, 10.0, "window"), (0.0, 6.0, "update")]
    ops = [(1.0, 3.0, "a"), (2.0, 4.0, "b"), (7.0, 8.0, "a")]
    modules = [(1.0, 4.0, "jit_f"), (7.0, 8.0, "jit_f")]
    s = rt.summarise(spans, [(ops, modules)])
    assert s["window_s"] == 10.0 and s["busy_s"] == pytest.approx(4.0)
    assert s["module_s"] == {"jit_f": pytest.approx(4.0)}
    assert s["span_s"]["update"] == 6.0
    # idle [0,1) and [4,7) fall in "update", [8,10) in no span
    assert dict(s["idle_gaps"]) == {"update": pytest.approx(4.0),
                                    "(none)": pytest.approx(2.0)}
    top = rt.breakdown(s)
    assert top["device_ops"][0][0] in ("a", "b")


def test_short_name():
    op = ("%while = (s32[]{:T(128)}, f32[512,512]{1,0:T(8,128)S(1)}) "
          "while((s32[]{:T(128)}) %tuple.13), condition=%c, body=%b")
    assert rt.short_name(op) == "%while while"
    assert rt.short_name("%copy.11 = f32[8]{0} copy(f32[8]{0} %x)") == \
        "%copy.11 copy"
    assert rt.short_name("no hlo here") == "no hlo here"


def test_nothing_on_the_device_gives_no_busy_time():
    s = rt.summarise([(0.0, 1.0, "window")], [])
    assert s["busy_s"] is None and s["op_s"] == {}


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace():
    s = rt.reduce(RECORDED)
    assert s["chips"] == 1
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["op_s"] and s["module_s"]
    assert {"window", "step", "pause"} <= set(s["span_s"])
    assert list(s["module_s"]) == ["jit_step(10060351457490764335)"]
    # the three pauses are idle time, and are laid to the pause span
    assert dict(s["idle_gaps"])["pause"] > 0.9 * s["span_s"]["pause"]
    names = [n for n, _ in rt.breakdown(s)["device_ops"]]
    assert all(rt.short_name(n) == n for n in names)
    assert any(n.endswith(" fusion") or n.endswith(" while") for n in names)
    assert sum(t for _, t in s["idle_gaps"]) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-6)
