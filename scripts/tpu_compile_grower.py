#!/usr/bin/env python
"""Compile the compact grower for a v5e that is described, not attached, and
print what the compiler did with the table: minutes in the sandbox, no chip.

    JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \\
        python scripts/tpu_compile_grower.py --rows 200000 [--cols 67] \\
        [--leaves 255] [--bins 255] [--missing 0] [--unpacked] \\
        [--text /root/scratch/grow.hlo.txt]

It prints the compile seconds, the compiler's memory analysis (the
temporaries are the block the chip reports as ``peak_bytes_reserved``) and,
for one stage (``--stage``, default ``partition_fetch``), every gather with
its operand's shape, layout and memory space: ``S(1)`` in a layout is VMEM,
none is HBM. It also lists every ``copy`` and ``bitcast`` of an array of the
table's size, with the computation it sits in: a copy inside a
``branch_*`` computation is paid once a split. The Pallas kernels are
compiled by Mosaic as on the chip (``pallas_custom_calls``). Nothing runs,
so it gives no time. The last line, ``table_sized_copies_in_loop <n>``,
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
one, written and reversed once a split.
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


def copies_in_loop(text, table_elements):
    """The instructions that re-lay an array of the table's size once a
    split: in every computation that a ``while`` body reaches (the branches
    of its conditionals among them), each ``copy`` of ``table_elements``
    elements and each fusion of that size whose computation holds such a
    ``copy`` or ``transpose``. Returns [(computation, name, shape)]."""
    body_of, calls, relays, bodies, comp = {}, {}, set(), [], None
    for line in text.splitlines():
        started = _COMPUTATION.match(line)
        if started:
            comp = started.group(1)
            body_of[comp], calls[comp] = [], set()
            continue
        m = timer._INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name, shape, opcode, _ = m.groups()
        called = set()
        for one, many in _CALLED.findall(line):
            called.update([one] if one else timer._OPERAND.findall(many))
        calls[comp] |= called
        sized = not shape.startswith("(") and \
            _elements(shape) == table_elements
        if sized and opcode in ("copy", "transpose"):
            relays.add(comp)
        body_of[comp].append((name, shape, opcode, called, sized))
        if opcode == "while":
            bodies.extend(called)       # its condition copies no table
    reached, todo = set(), list(bodies)
    while todo:
        c = todo.pop()
        if c not in reached and c in calls:
            reached.add(c)
            todo.extend(calls[c])
    return [(c, name, shape) for c in sorted(reached)
            for name, shape, opcode, called, sized in body_of[c]
            if sized and (opcode == "copy" or
                          (opcode == "fusion" and called & relays))]


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


def report(text, table_elements, stage):
    """The lines of the compiled module that say where the stage's gathers
    read from and what was copied."""
    shape_of, comp = {}, None
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
        if (opcode == "fusion" and "kind=kCustom" in line and op and
                op.group(1).endswith(f"lgbm.{stage}/gather")):
            operand = timer._OPERAND.findall(operands)[0]
            print(f"gather {name} = {shape}  in {comp}\n"
                  f"    operand {operand} = {shape_of.get(operand)}")
        elif (opcode in ("copy", "bitcast") and not shape.startswith("(")
              and _elements(shape) == table_elements):
            print(f"{opcode} {name} = {shape}  in {comp}")


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
    # what `criteo-share.train` and `epsilon.train` resolve their `auto`
    # parameters to on a v5e
    cfg = GrowerConfig(num_leaves=args.leaves, num_bin=B,
                       row_sched="compact", hist_rm_backend="pallas",
                       partition_mode="auto", min_bucket=2048,
                       packed_cols=0 if args.unpacked else F)
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
    report(text, args.rows * width, args.stage)
    in_loop = copies_in_loop(text, args.rows * width)
    for comp, name, shape in in_loop:
        print(f"in the split loop: {name} = {shape}  in {comp}")
    minor = channel_minor_scan_arrays(text, F, B)
    for comp, name, shape in minor:
        print(f"channels minor: {name} = {shape}  in {comp}")
    print(f"scan_directions {2 if with_nan.any() else 1}")
    print(f"channel_minor_scan_copies {len(minor)}")
    print(f"table_sized_copies_in_loop {len(in_loop)}")


if __name__ == "__main__":
    main()
