"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

Uses ``jax.profiler.ProfileData`` and nothing else. A TPU trace holds one
plane per chip (``/device:TPU:n``) with a line of compiled programs
(``XLA Modules``) and a line of their operations (``XLA Ops``), and one
host plane (``/host:CPU``) with a line per thread; the benchmark's own
spans (``run.Spans``) are ``TraceAnnotation`` events there, named
``bench.<span>``, on the same clock.

``reduce`` returns a plain dict:

* ``window_s``   length of the ``bench.window`` span (or of all device work);
* ``busy_s``     seconds in which an operation ran on a chip inside the
  window (union of the op intervals), averaged over the chips;
* ``op_s``       self seconds by operation name (an op that contains others,
  such as a ``while``, counts only the time none of its children cover);
* ``module_s``   seconds by compiled program;
* ``span_s``     seconds by benchmark span;
* ``idle_gaps``  idle seconds of the first chip by the innermost benchmark
  span open in the middle of each gap, longest first.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW = "window"            # the span that bounds the measured window


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: list) -> list:
    """Merge [start, end) pairs into disjoint sorted intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if min(b, hi) > max(a, lo)]


def self_times(events: list) -> dict:
    """Seconds by name, each event less what its nested events cover.
    ``events``: (start, end, name), any order, nested or disjoint."""
    out: dict = {}
    stack: list = []                         # [end, name, self]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _, name, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        close(start)
        if stack:
            stack[-1][2] -= min(end, stack[-1][0]) - start
        stack.append([end, name, end - start])
    close(float("inf"))
    return out


def gaps_by_span(busy: list, spans: list, lo: float, hi: float) -> dict:
    """Idle seconds inside [lo, hi) by the innermost span (start, end,
    name) open at each gap's middle; ``(none)`` where no span is open."""
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    out: dict = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = 0.5 * (a + b)
        open_ = [s for s in spans if s[0] <= mid < s[1]]
        name = min(open_, key=lambda s: s[1] - s[0])[2] if open_ \
            else "(none)"
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def _events(line) -> list:
    return [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
            for e in line.events]


def reduce(xplane_path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    spans, chips, seen = [], [], {}
    for plane in data.planes:
        seen[plane.name] = [ln.name for ln in plane.lines][:12]
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [(a, b, n[len(SPAN_PREFIX):])
                          for a, b, n in _events(line)
                          if n.startswith(SPAN_PREFIX)]
        elif DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            chips.append((
                _events(lines[OPS_LINE]) if OPS_LINE in lines else [],
                _events(lines[MODULES_LINE]) if MODULES_LINE in lines
                else []))
    out = summarise(spans, chips)
    out["planes"] = seen
    return out


def summarise(spans: list, chips: list) -> dict:
    """``spans``: (start, end, name) in seconds; ``chips``: one (ops,
    modules) pair of such lists per chip."""
    window = [s for s in spans if s[2] == WINDOW]
    all_ops = [e for ops, _ in chips for e in ops]
    if window:
        lo, hi = window[0][0], window[0][1]
    elif all_ops:
        lo, hi = min(e[0] for e in all_ops), max(e[1] for e in all_ops)
    else:
        lo = hi = 0.0
    span_s: dict = {}
    for a, b, n in spans:
        span_s[n] = span_s.get(n, 0.0) + (b - a)
    out = {"window_s": hi - lo, "chips": len(chips), "span_s": span_s,
           "busy_s": None, "op_s": {}, "module_s": {}, "idle_gaps": []}
    if not chips or not all_ops:
        return out
    busy_each = []
    for ops, modules in chips:
        inside = [e for e in ops if e[1] > lo and e[0] < hi]
        busy = clip(union([[a, b] for a, b, _ in inside]), lo, hi)
        busy_each.append(busy)
        for n, s in self_times(inside).items():
            out["op_s"][n] = out["op_s"].get(n, 0.0) + s / len(chips)
        for a, b, n in modules:
            if b > lo and a < hi:
                out["module_s"][n] = out["module_s"].get(n, 0.0) + \
                    (min(b, hi) - max(a, lo)) / len(chips)
    out["busy_s"] = sum(sum(b - a for a, b in busy)
                        for busy in busy_each) / len(chips)
    inner = [s for s in spans if s[2] != WINDOW]
    gaps = gaps_by_span(busy_each[0], inner, lo, hi)
    out["idle_gaps"] = sorted(gaps.items(), key=lambda kv: -kv[1])
    return out


_HLO = re.compile(r"^(%[^ ]+) = .*? ([A-Za-z][\w\-]*)\(")


def short_name(op: str) -> str:
    """An operation is named by its whole HLO line in the trace; keep its
    result name and its kind: ``%fusion.12 fusion``."""
    m = _HLO.match(op)
    return f"{m.group(1)} {m.group(2)}" if m else op[:80]


def breakdown(summary: dict, top: int = 10) -> dict:
    ops = sorted(summary["op_s"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[short_name(n), s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in summary["idle_gaps"][:top]]}
