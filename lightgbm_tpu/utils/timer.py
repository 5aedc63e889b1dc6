"""The program's one tracing system: host sections, device stages, and the
map from a compiled operation to its stage.

**Host sections** (``global_timer.section(name)``; ≡ the reference's
USE_TIMETAG table, include/LightGBM/utils/common.h:980 Common::Timer,
:1044 FunctionTimer). Every section is always (a) a
``jax.profiler.TraceAnnotation`` named ``lgbm.<name>`` — a no-op without a
profiler session; inside one (``tpu_profile_dir``, the benchmark's traced
run) the span lies in the xplane's host plane, on the device trace's
clock — and (b) a record ``(name, start, end, parent, iteration)`` on
``time.perf_counter`` in a bounded deque, beside totals and counts that
``table()`` prints. Under the sections ``table()`` prints the **counters**
(``global_timer.count(name)``): what the device decided, read off what comes
to the host anyway. Eight are counted where a grown tree becomes a
``HostTree`` (``models/gbdt.py``, ``_finalize_tree``): ``trees``;
``first_split_dense``, those whose first split histogrammed its smaller child
in one masked pass over the table in place (``TreeArrays.first_split_dense``,
``core/grower.py``); ``splits``, the trees' real splits;
``splits_missing_right``, the numerical splits among them whose
``default_left`` is false (the forward scan's winners: the missing go right),
and ``splits_on_missing``, those on a column whose ``missing_type`` is not
none; and the rows of the
compact grower's gathered histogram calls (``TreeArrays.hist_rows``):
``hist_rows_live``, the leaves' segments; ``hist_rows_read``, the rows in the
row blocks the kernel read for them; ``hist_rows_bucket``, the buckets the
segments were padded to. ``1 - read / bucket`` is the share of the buckets
the kernel skipped, ``1 - live / bucket`` the share of every gathered index
that is padding. Four are notes,
set and not added (``global_timer.note``) at each set-up (``_setup_train``):
``pool_bytes``, the histogram pool as the budget left it,
``table_words``, the 32-bit words of the packed table,
``scan_directions``, 2 where a column has a bin for the missing and the
split scan's forward half is compiled in (``ops/split.py``), else 1, and
``hist_expanded_rows``, the rows of the operand a column's contraction
holds still in the Pallas histogram kernel (``ops/hist_pallas.py``,
``expanded_rows``; 0 where another backend builds the histograms).
``LIGHTGBM_TPU_TIMETAG`` (or ``global_timer.enabled = True``) turns on only
the ``sync=`` barrier and the table printed at the end of training.

What a section measures: on the synchronous path with ``enabled``, the
section blocks on its ``sync=`` value, so it holds the device's seconds. On
the asynchronous path (``tpu_async_boosting``, the default on a TPU) and
whenever ``enabled`` is off, a section is a DISPATCH span: the host seconds
it took to enqueue the work. The device's seconds are not in it; they come
by stage, below.

**Device stages** (``stage(name)`` = ``jax.named_scope("lgbm.<name>")``
with ``name`` out of ``STAGES``). Scopes change the compiled program's
metadata and nothing else. xprof groups a ``tpu_profile_dir`` capture by
them; ``stage_map()`` reads them back out of the compiled programs, so that
whoever holds per-operation device seconds (the benchmark's trace) can sum
them by stage. jax leaves metadata out of its persistent compilation
cache's key: an executable that the cache kept from before a scope was
there is handed back without it, and the map then finds nothing in it
until the cache entry goes.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import os
import re
import threading
import time
import weakref
from collections import defaultdict

import jax

from . import log

SCOPE = "lgbm."
# every operation of the training step that does work sits in one of these
STAGES = ("gradients", "hist_gather", "hist_kernel", "hist_subtract",
          "split_scan", "partition_fetch", "partition_order", "tree_update",
          "score_update")
AMBIGUOUS = "(ambiguous)"
MAX_RECORDS = 4096

Record = collections.namedtuple(
    "Record", ("name", "start", "end", "parent", "iteration"))


def stage(name: str):
    """Name the device operations traced inside the ``with`` block."""
    # jaxlint: disable=JL002 — name is a Python string, checked at trace time
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; the stages are {STAGES}")
    return jax.named_scope(SCOPE + name)


def in_stage(name: str, fn):
    """``fn``, traced inside the stage ``name``. Where stages nest, an
    operation belongs to the innermost."""
    @functools.wraps(fn)
    def staged(*args, **kwargs):
        with stage(name):
            return fn(*args, **kwargs)
    return staged


class _OpenSections(threading.local):
    """Per thread: the (name, iteration) of every section that is open."""

    def __init__(self):
        self.stack = []


class Timer:
    """Section spans and their aggregate table (ref: Common::Timer,
    utils/common.h:980)."""

    def __init__(self):
        self.enabled = bool(os.environ.get("LIGHTGBM_TPU_TIMETAG"))
        self._total = defaultdict(float)
        self._count = defaultdict(int)
        self.counters = defaultdict(int)
        self.records = collections.deque(maxlen=MAX_RECORDS)
        self._open = _OpenSections()

    @contextlib.contextmanager
    def section(self, name: str, sync=None, iteration=None):
        """``iteration`` defaults to the enclosing section's."""
        stack = self._open.stack
        parent, inherited = stack[-1] if stack else (None, None)
        if iteration is None:
            iteration = inherited
        stack.append((name, iteration))
        said = {} if iteration is None else {"iter": int(iteration)}
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(SCOPE + name, **said):
                yield
        finally:
            if sync is not None and self.enabled:
                try:
                    jax.block_until_ready(sync() if callable(sync) else sync)
                except Exception:
                    pass  # never mask the body's exception from the sync hook
            t1 = time.perf_counter()
            stack.pop()
            self._total[name] += t1 - t0
            self._count[name] += 1
            self.records.append(Record(name, t0, t1, parent, iteration))

    def count(self, name: str, n: int = 1) -> None:
        """Add ``n`` to the counter ``name``."""
        self.counters[name] += int(n)

    def note(self, name: str, value: int) -> None:
        """Set the counter ``name`` to ``value``: a size, which a second
        booster in the process replaces and does not add to."""
        self.counters[name] = int(value)

    def reset(self) -> None:
        self._total.clear()
        self._count.clear()
        self.counters.clear()
        self.records.clear()

    def table(self) -> str:
        """Render the aggregate table (ref: Timer::Print, common.h:1013),
        then the counters in the order they were first counted."""
        if not self._total and not self.counters:
            return "(no timing sections recorded)"
        width = max(len(k) for k in (*self._total, *self.counters))
        lines = []
        if self._total:
            lines.append(
                f"{'section'.ljust(width)}   total(s)      count    mean(ms)")
        for name in sorted(self._total, key=self._total.get, reverse=True):
            t, c = self._total[name], self._count[name]
            lines.append(f"{name.ljust(width)} {t:10.3f} {c:10d} "
                         f"{1e3 * t / max(c, 1):11.3f}")
        if self.counters:
            lines.append(f"{'counter'.ljust(width)} {'count':>10}")
        for name, c in self.counters.items():
            lines.append(f"{name.ljust(width)} {c:10d}")
        return "\n".join(lines)

    def print(self) -> None:
        if self.enabled and self._total:
            log.info("time table:\n" + self.table())


global_timer = Timer()


# ---- the stage map ------------------------------------------------------

_programs = weakref.WeakSet()      # the live engines' staged programs


def _abstract(x):
    """A device array as its shape, dtype and placement; anything else as it
    is. Enough to lower the call again, and it keeps no buffer alive."""
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=x.weak_type,
            sharding=x.sharding if x.committed else None)
    return x


class Program:
    """A jitted function of a training engine that remembers how it was last
    called, so that ``stage_map`` can ask jit for the executable it already
    holds. The engine owns it; the registry holds it weakly."""

    def __init__(self, jitted):
        self.jitted = jitted
        self.last = None
        _programs.add(self)

    def __call__(self, *args):
        self.last = jax.tree.map(_abstract, args)
        return self.jitted(*args)

    def compiled_text(self):
        """The optimised module's text, the names the device trace uses;
        None before the first call."""
        if self.last is None:
            return None
        return self.jitted.lower(*self.last).compile().as_text()


def jit(fn, **jit_kwargs) -> Program:
    """``jax.jit`` for a training engine's program: what it compiles is in
    ``stage_map`` while the engine lives."""
    return Program(jax.jit(fn, **jit_kwargs))


# "  ROOT %fusion.3 = f32[8]{0} fusion(%p, %copy.2), kind=kLoop, calls=%fused.3,
#    metadata={op_name="jit(grow)/while/body/lgbm.split_scan/mul" ...}"
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?(%[^\s=]+) = (.*?) ([A-Za-z][\w\-]*)\((.*?)\)(?:,|$)")
_OPERAND = re.compile(r"%[^\s,()]+")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_STAGE = re.compile(re.escape(SCOPE) + r"([A-Za-z_]+)")


def instructions(hlo_text: str) -> list:
    """(name, opcode, stage or None, result shape) of every instruction of
    one compiled module. The stage is the innermost ``lgbm.<stage>`` of the
    instruction's ``op_name``. An instruction that carries none (the
    compiler's own copies, pads and the pieces it expands a scan into have
    no metadata) belongs to the stage that made its first staged operand."""
    out, known = [], {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, shape, opcode, operands = m.groups()
        op = _OP_NAME.search(line)
        found = _STAGE.findall(op.group(1)) if op else []
        staged = found[-1] if found else next(
            (known[o] for o in _OPERAND.findall(operands)
             if known.get(o)), None)
        known[name] = staged
        out.append((name, opcode, staged, shape))
    return out


def stage_map() -> dict:
    """{HLO instruction name: stage} over the compiled programs of every
    live training engine; a name outside every stage is left out. Compiles
    nothing and changes nothing: each program is lowered with the arguments
    of its last call, which jit answers from its cache.

    Instruction names are only unique inside one program. A name that the
    programs do not all put in one stage maps to ``(ambiguous)``, and the
    map then also holds ``"<name> = <result shape>"`` (the instruction's
    line up to its opcode, as the device trace prints it) for each shape
    that does settle the stage."""
    seen: dict = {}                       # name -> shape -> {stage or None}
    for program in list(_programs):
        for name, _, found, shape in instructions(
                program.compiled_text() or ""):
            seen.setdefault(name, {}).setdefault(shape, set()).add(found)
    out = {}
    for name, shapes in seen.items():
        found = set().union(*shapes.values())
        if found == {None}:
            continue
        if len(found) == 1:
            out[name] = next(iter(found))
            continue
        out[name] = AMBIGUOUS
        for shape, here in shapes.items():
            if len(here) == 1 and None not in here:
                out[f"{name} = {shape}"] = next(iter(here))
    return out
