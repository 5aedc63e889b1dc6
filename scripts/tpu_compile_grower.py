#!/usr/bin/env python
"""Compile the compact grower for a v5e that is described, not attached, and
print what the compiler did with the table: minutes in the sandbox, no chip.

    JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \\
        python scripts/tpu_compile_grower.py --rows 200000 [--cols 67] \\
        [--leaves 255] [--bins 255] [--missing 0] [--unpacked] \\
        [--quantized 4] [--text grow.hlo.txt]

It prints the compile seconds, the compiler's memory analysis (the
temporaries are the block the chip reports as ``peak_bytes_reserved``) and,
for one stage (``--stage``, default ``partition_fetch``), every gather with
its operand's shape, layout and memory space: ``S(1)`` in a layout is VMEM,
none is HBM (``stage_gathers <n> stage_gathers_from_vmem <m>`` counts
them: the fetch's gathers are one a rung of its ladder, in the branches of
a switch inside each bucket's). It also lists every ``copy`` and
``bitcast`` of an array of the table's size, with the computation it sits
in: a copy inside a ``branch_*`` computation is paid once a split. The
Pallas kernels are compiled by Mosaic as on the chip
(``pallas_custom_calls``). Nothing runs, so it gives no time. The last
line, ``table_sized_copies_in_loop <n>``,
counts the copies and transposing fusions of an array of the table's size
in the computations the split loop's body reaches: each is paid once a
split. Layouts change with the shape (at 200,000 x 67 the row gather reads a
row-major copy, at 2 M x 67 the word-major parameter; at 400,000 x 2,000 the
parent of PR 33 copied the table in every branch of the histogram's switch):
what a PR claims of a cell it checks at that cell's shape, ``--rows 2000000``
(seven minutes) for `criteo-share.train` and ``--rows 400000 --cols 2000``
(under a minute) for `epsilon.train`, ``--rows 1000000 --cols 968 --bins 251
--missing 1`` for `bosch.train`. ``--missing <share>`` gives that share of
the columns (the first ones) a NaN bin, their last: with any such column the
split scan's forward direction is in the module, as it is for a table with
missing values, and ``scan_directions`` says which was compiled. The line
``channel_minor_scan_copies`` counts the arrays shaped like the scan's prefix
or suffix sums, ``[.., 3, cols, bins]``, that the compiler laid out with the
three channels minor (128 lanes for 3), anywhere in the module: PR 33 found
one, written and reversed once a split. ``scan_lane_reverses`` counts the
``reverse`` instructions inside ``lgbm.split_scan`` (0 since PR 38: the
suffix sums are one contraction, the reverse scan's ties a max over the
masked bin indices), listed above it with the stage's ``copy``,
``transpose`` and ``gather`` instructions. Each of the scan's contractions
is listed with its operands, its precision and the bf16 passes the
compiler's cycle estimate holds; ``scan_contraction_min_passes`` is the
least of them, 3 where the f32 sums are kept to float32 against the 0/1
matrix, 1 where they were cut to bf16. ``conditionals`` counts the
module's ``conditional``s, and ``part_record_in_branches`` the writes of the
partition's counter that sit in one of their branches (0: it is written at
the split loop's level). ``hist_output_bytes`` is the largest histogram
kernel output in the split loop, and ``hist_output_copies_in_loop`` counts
the instructions there that re-lay such an output whole, or the histogram
switch's ``[cols, bins, 3]`` result (listed above it): a copy the pool's
layout asks of the switch is paid once a split.
"""
import argparse
import math
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from lightgbm_tpu.core.grower import GrowerConfig, make_tree_grower
from lightgbm_tpu.ops import hist_pallas
from lightgbm_tpu.ops.split import MISSING_ENUM, FeatureMeta
from lightgbm_tpu.utils import timer

_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[^\s(]+) \(.*\{\s*$")
_DIMS = re.compile(r"\[([\d,]+)\]")
# what an instruction calls: body=%b, calls=%f, branch_computations={%a, %b}
_CALLED = re.compile(
    r"(?:body|condition|calls|to_apply|true_computation|false_computation)"
    r"=(%[^\s,)}]+)|branch_computations=\{([^}]*)\}")


def _elements(shape):
    dims = _DIMS.search(shape)
    return math.prod(int(d) for d in dims.group(1).split(",")) if dims else 0


def _instructions(text):
    """Every instruction of the module: (computation, name, result shape,
    opcode, the computations it calls, those of them that are a
    ``conditional``'s branches, its operands)."""
    comp = None
    for line in text.splitlines():
        started = _COMPUTATION.match(line)
        if started:
            comp = started.group(1)
            continue
        m = timer._INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name, shape, opcode, operands = m.groups()
        called, branches = set(), set()
        for one, many in _CALLED.findall(line):
            if one:
                called.add(one)
            else:
                branches.update(timer._OPERAND.findall(many))
        yield (comp, name, shape, opcode, called | branches, branches,
               timer._OPERAND.findall(operands))


def _reached(calls, roots):
    """The computations ``roots`` reach through ``calls``, roots included."""
    reached, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c not in reached and c in calls:
            reached.add(c)
            todo.extend(calls[c])
    return reached


def copies_in_loop(text, table_elements):
    """The instructions that re-lay an array of the table's size once a
    split: in every computation that a ``while`` body reaches (the branches
    of its conditionals among them), each ``copy`` of ``table_elements``
    elements and each fusion of that size whose computation holds such a
    ``copy`` or ``transpose``. Returns [(computation, name, shape)]."""
    body_of, calls, relays, bodies = {}, {}, set(), []
    for comp, name, shape, opcode, called, _, _ in _instructions(text):
        calls.setdefault(comp, set()).update(called)
        sized = not shape.startswith("(") and \
            _elements(shape) == table_elements
        if sized and opcode in ("copy", "transpose"):
            relays.add(comp)
        body_of.setdefault(comp, []).append((name, shape, opcode, called,
                                             sized))
        if opcode == "while":
            bodies.extend(called)       # its condition copies no table
    return [(c, name, shape) for c in sorted(_reached(calls, bodies))
            for name, shape, opcode, called, sized in body_of[c]
            if sized and (opcode == "copy" or
                          (opcode == "fusion" and called & relays))]


_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "s8": 1, "u8": 1,
          "pred": 1}


def hist_outputs_in_loop(text, cols, bins):
    """What the split loop does with the histogram kernel's output: the
    bytes of the largest ``_hist_pallas*`` custom call's result in the
    computations a ``while`` body reaches, and the ``copy`` instructions
    there whose operand (through bitcasts and tuple elements) is such a
    result or a ``conditional``'s ``[cols, bins, 3]`` one, the histogram's
    switch, and the ``reshape`` and ``transpose`` ones, which re-lay it as
    a copy does. Returns (bytes, [(computation, name, shape)])."""
    calls, made, bodies, copies = {}, {}, [], []
    for comp, name, shape, opcode, called, _, operands in \
            _instructions(text):
        calls.setdefault(comp, set()).update(called)
        if opcode == "while":
            bodies.extend(called)
        made[name] = (comp, shape, opcode, operands[:1])
    loop = _reached(calls, bodies)

    def origin(name):
        while made[name][2] in ("bitcast", "get-tuple-element") and \
                made[name][3]:
            name = made[name][3][0]
        return name

    def kernel(name):
        return made[name][2] == "custom-call" and \
            name.startswith("%_hist_pallas")

    largest = max((_elements(shape) * _BYTES[shape.split("[")[0]]
                   for name, (comp, shape, _, _) in made.items()
                   if comp in loop and kernel(name)), default=0)
    for name, (comp, shape, opcode, operand) in made.items():
        if comp not in loop or not operand or \
                opcode not in ("copy", "reshape", "transpose"):
            continue
        src = origin(operand[0])
        if kernel(src) or made[src][2] == "conditional" and \
                shape.split("{")[0].endswith(f"[{cols},{bins},3]"):
            copies.append((comp, name, shape))
    return largest, sorted(copies)


def in_branches(text, shape, opcode):
    """The ``opcode`` instructions whose result is ``shape`` (layout
    aside) in the computations that a ``conditional``'s branches reach,
    and how many ``conditional``s the module holds: ([(computation,
    name)], conditionals). What sits in a branch is paid where the branch
    runs, and a ``conditional`` in the split loop once a split."""
    calls, found, roots, conditionals = {}, [], [], 0
    for comp, name, result, op, called, branches, _ in \
            _instructions(text):
        calls.setdefault(comp, set()).update(called)
        roots.extend(branches)
        conditionals += op == "conditional"
        if op == opcode and result.split("{")[0] == shape:
            found.append((comp, name))
    reached = _reached(calls, roots)
    return sorted(f for f in found if f[0] in reached), conditionals


_LAYOUT = re.compile(r"\[([\d,]+)\]\{([\d,]+)")


def channel_minor_scan_arrays(text, cols, bins):
    """The instructions whose result is shaped like the scan's cumulative
    sums, ``[.., 3, cols, bins]``, and laid out with the three channels
    minor: [(computation, name, shape)]. Such an array fills 128 lanes for
    3, and the scan that made one wrote and reversed it once a split."""
    found, comp = [], None
    for line in text.splitlines():
        started = _COMPUTATION.match(line)
        if started:
            comp = started.group(1)
            continue
        m = timer._INSTRUCTION.match(line)
        lay = m and not m.group(2).startswith("(") and \
            _LAYOUT.search(m.group(2))
        if not lay:
            continue
        dims = [int(d) for d in lay.group(1).split(",")]
        if dims[-3:] == [3, cols, bins] and \
                int(lay.group(2).split(",")[0]) == len(dims) - 3:
            found.append((comp, m.group(1), m.group(2)))
    return found


def scan_moves(text):
    """The instructions of stage ``split_scan`` that move data and do no
    arithmetic: [(name, opcode, result shape)] of its ``reverse``,
    ``copy``, ``transpose`` and ``gather`` instructions, inside fusions
    too (the stage is the timer's: an instruction's innermost
    ``lgbm.<stage>``, or its first staged operand's)."""
    return [(name, opcode, shape)
            for name, opcode, stage, shape in timer.instructions(text)
            if stage == "split_scan" and
            opcode in ("reverse", "copy", "transpose", "gather")]


_PRECISION = re.compile(r"operand_precision=\{([^}]*)\}")
_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')
# a v5e TensorCore's matrix units: 4 of 128 x 128 multiply-adds a cycle
_MXU_MACS = 4 * 128 * 128


def scan_contractions(text):
    """The matrix-unit contractions of stage ``split_scan`` (the scan's
    cumulative sums): [(name, operand shapes, operand_precision, passes)].
    ``passes`` is the compiler's estimated cycles for the fusion that holds
    the contraction over the cycles of one bf16 pass of its multiply-adds
    (both lane dims padded to 128), or None where it gives no estimate. An
    f32 operand against an exact 0/1 (``pred``) one is held to float32 in
    three bf16 passes; one pass would keep 8 bits of each bin."""
    cycles, shape_of, found, comp = {}, {}, [], None
    for line in text.splitlines():
        started = _COMPUTATION.match(line)
        if started:
            comp = started.group(1)
        m = timer._INSTRUCTION.match(line)
        if not m:
            continue
        name, shape, opcode, operands = m.groups()
        shape_of[name] = shape.split("{")[0]
        est = _CYCLES.search(line)
        if est:
            for called, _ in _CALLED.findall(line):
                cycles[called] = int(est.group(1))
        if opcode in ("convolution", "dot") and "lgbm.split_scan" in line:
            found.append((comp, name, shape.split("{")[0],
                          re.findall(r"%[^\s,()]+", operands),
                          _PRECISION.search(line)))
    out = []
    for comp, name, shape, ops, prec in found:
        lhs, rhs = (shape_of.get(o, "?") for o in ops[:2])
        rows, cols = _elements(shape), int(_DIMS.search(shape).group(1)
                                          .split(",")[-1])
        depth = int(_DIMS.search(rhs).group(1).split(",")[0]) \
            if _DIMS.search(rhs) else 0
        one_pass = rows // cols * (-(-depth // 128) * 128) * \
            (-(-cols // 128) * 128) / _MXU_MACS
        passes = cycles[comp] / one_pass if comp in cycles and one_pass \
            else None
        out.append((name, f"{lhs} x {rhs}",
                    prec.group(1) if prec else "default", passes))
    return out


def report(text, table_elements, stage):
    """The lines of the compiled module that say where the stage's gathers
    read from and what was copied. A gather is the stage's where the stage
    is the innermost ``lgbm.`` scope of its name, in a switch's branch too
    (the partition's fetch switches over its rungs). Returns the count of
    the stage's gathers and of those whose operand is in VMEM."""
    shape_of, comp, gathers, in_vmem = {}, None, 0, 0
    for line in text.splitlines():
        started = _COMPUTATION.match(line)
        if started:
            comp = started.group(1)
        m = timer._INSTRUCTION.match(line)
        if not m:
            continue
        name, shape, opcode, operands = m.groups()
        shape_of[name] = shape
        op = timer._OP_NAME.search(line)
        inner = op.group(1).rsplit("/lgbm.", 1)[-1] if op else ""
        if (opcode == "fusion" and "kind=kCustom" in line and
                inner.startswith(f"{stage}/") and inner.endswith("/gather")):
            operand = timer._OPERAND.findall(operands)[0]
            gathers += 1
            in_vmem += "S(1)" in (shape_of.get(operand) or "")
            print(f"gather {name} = {shape}  in {comp}\n"
                  f"    operand {operand} = {shape_of.get(operand)}")
        elif (opcode in ("copy", "bitcast") and not shape.startswith("(")
              and _elements(shape) == table_elements):
            print(f"{opcode} {name} = {shape}  in {comp}")
    return gathers, in_vmem


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=200000)
    ap.add_argument("--cols", type=int, default=67,
                    help="columns of the table: 67 is `criteo-share`'s, "
                         "2000 `epsilon`'s")
    ap.add_argument("--leaves", type=int, default=255)
    ap.add_argument("--bins", type=int, default=255,
                    help="bins of every column, a NaN bin included")
    ap.add_argument("--missing", type=float, default=0.0,
                    help="share of the columns with a NaN bin (missing_type "
                         "nan): any puts the forward scan in the module")
    ap.add_argument("--unpacked", action="store_true")
    ap.add_argument("--quantized", type=int, default=0,
                    help="levels of quantized gradients (use_quantized_grad, "
                         "rounded to the nearest): 4 is "
                         "`epsilon-quantized`'s, 0 float32 gradients")
    ap.add_argument("--stage", default="partition_fetch")
    ap.add_argument("--text", help="write the compiled module's text here")
    args = ap.parse_args()
    # a compile for a described chip is written to the persistent cache
    # and can never be read back from it
    jax.config.update("jax_enable_compilation_cache", False)
    # jax still sees the CPU here, where the program interprets its Pallas
    # kernels: compile them as the chip would (until PR 30 this script
    # compiled the interpreter's loops in the custom calls' place)
    hist_pallas.default_interpret = lambda: False
    F, B = args.cols, args.bins
    with_nan = np.arange(F) < round(args.missing * F)
    meta = FeatureMeta(num_bin=jnp.full((F,), B, jnp.int32),
                       missing_type=jnp.asarray(
                           np.where(with_nan, MISSING_ENUM["nan"],
                                    MISSING_ENUM["none"]), jnp.int32),
                       default_bin=jnp.zeros((F,), jnp.int32),
                       is_categorical=jnp.zeros((F,), bool))
    quantized = dict(quantized=True, quant_bins=args.quantized,
                     stochastic_rounding=False) if args.quantized else {}
    # what `criteo-share.train` and `epsilon.train` resolve their `auto`
    # parameters to on a v5e
    cfg = GrowerConfig(num_leaves=args.leaves, num_bin=B,
                       row_sched="compact", hist_rm_backend="pallas",
                       partition_mode="auto", min_bucket=2048,
                       packed_cols=0 if args.unpacked else F,
                       **quantized)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    width, dtype = (F, jnp.uint8) if args.unpacked else \
        ((F + 3) // 4, jnp.uint32)
    bins = jax.ShapeDtypeStruct((args.rows, width), dtype, sharding=chip)
    gh = jax.ShapeDtypeStruct((args.rows, 3), jnp.float32, sharding=chip)
    t0 = time.time()
    compiled = jax.jit(make_tree_grower(cfg, meta)).lower(bins, gh).compile()
    # jaxlint: disable=JL005 — times the compile, which returns when done;
    # nothing is dispatched
    print(f"compile_s {time.time() - t0:.1f}")
    mem = compiled.memory_analysis()
    for field in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes"):
        print(f"{field} {getattr(mem, field)} "
              f"({getattr(mem, field) / 2 ** 20:.1f} MiB)")
    text = compiled.as_text()
    if args.text:
        with open(args.text, "w") as f:
            f.write(text)
    print(f"pallas_custom_calls {text.count('tpu_custom_call')}")
    gathers, in_vmem = report(text, args.rows * width, args.stage)
    print(f"stage_gathers {gathers} stage_gathers_from_vmem {in_vmem}")
    in_loop = copies_in_loop(text, args.rows * width)
    for comp, name, shape in in_loop:
        print(f"in the split loop: {name} = {shape}  in {comp}")
    minor = channel_minor_scan_arrays(text, F, B)
    for comp, name, shape in minor:
        print(f"channels minor: {name} = {shape}  in {comp}")
    # the partition's counter (``GrowState.part_calls``) is written at the
    # loop body's level: none of it in a branch, no conditional more
    record, conditionals = in_branches(
        text, f"s32[{args.leaves - 1},2]", "dynamic-update-slice")
    moves = scan_moves(text)
    for name, opcode, shape in moves:
        print(f"in split_scan: {opcode} {name} = {shape}")
    dots = scan_contractions(text)
    for name, operands, precision, passes in dots:
        print(f"scan contraction: {name} {operands} precision={precision} "
              f"passes={'?' if passes is None else f'{passes:.2f}'}")
    print(f"conditionals {conditionals}")
    print(f"part_record_in_branches {len(record)}")
    print(f"scan_directions {2 if with_nan.any() else 1}")
    print(f"scan_lane_reverses "
          f"{sum(opcode == 'reverse' for _, opcode, _ in moves)}")
    print(f"channel_minor_scan_copies {len(minor)}")
    print(f"scan_contraction_min_passes "
          f"{min((p for *_, p in dots if p is not None), default=0):.2f}")
    out_bytes, out_copies = hist_outputs_in_loop(text, F, B)
    for comp, name, shape in out_copies:
        print(f"histogram output copied: {name} = {shape}  in {comp}")
    print(f"hist_output_bytes {out_bytes}")
    print(f"hist_output_copies_in_loop {len(out_copies)}")
    print(f"table_sized_copies_in_loop {len(in_loop)}")


if __name__ == "__main__":
    main()
