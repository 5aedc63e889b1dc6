"""What the grower runs, decided in one place from what the code can see.

``make_plan`` maps the platform, the table (rows, widest bin count,
storage), the learner, the scheduler and the user's explicit requests to
the values ``models/gbdt.GBDT._setup_train`` hands to ``GrowerConfig``:
the histogram kernel of the compact path and of the level phase, the
partition primitive, whether the row-major bins are packed four to a
32-bit word, and the histogram collective. It is pure: the platform is an
argument (so the chip's answers are held by ``tests/test_plan.py`` on the
CPU), and it reads no file and no environment. An explicit request passes
through; ``auto`` takes the constants below, each with the measurement it
stands on. Nothing else in the package writes these defaults.

Choices this module does not make, because they are made per bucket inside
the traced program from static shapes (``core/grower.py``, ``grow``):
``words_kernel`` (packed words go to ``hist_pallas_words`` as the table
stores them when the backend is ``pallas``; every other backend gets
``unpack_rows``), and ``partition_mode="auto"``'s ``lax.sort`` for buckets
of 32,768 rows and up, cumsum-scatter below. One choice is made per split,
at run time, from what the device sees: a tree's first split, where
``order`` is still the identity, reads its column in place, and histograms
its smaller child in one masked pass over the table in place when the
child's bucket holds more rows than ``first_split_dense_rows`` below says
(the rule is here, with its constants; the grower applies it to the
child's row count). What needs the engine's state stays with the engine:
the scheduler's eligibility (``_level_ineligibility``),
the collective's (``_resolve_hist_reduce_mode``), async boosting
(``_async_on``), the histogram pool's budget.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

# The row count from which a chip's `auto` takes the measured combination.
# At and above it: every accepted line of `criteo-share.train` (2,000,000 x
# 67; ledger, PR 24, 26, 30: 1.1521 -> 1.4205 -> 1.5361 iter/s) ran the
# Pallas kernel on packed words; chip_smoke.py holds the same at 1,000,000
# x 28. Below it: the builders' v5e reading of 2026-08-01, 84.1 it/s on
# einsum and unpacked bytes against 57.0 with both flips at 16,384 x 28.
# No cell stands on the small side (ROADMAP.md C14).
MEASURED_FROM_ROWS = 65536

# float32 histograms on a chip, compact path (ledger, PR 24/26/30, as above)
F32_KERNEL_LARGE = "pallas"
F32_KERNEL_SMALL = "einsum"
# bfloat16 and int8 histograms on a chip, any size: 5.99 / 5.56 ms a call
# against einsum's 16.5 / 16.3 at 1 M rows (builders' v5e reading of the
# feature-major kernel, docs/Features.md; not re-measured since)
NARROW_KERNEL = "pallas"
# level-phase histograms on a chip: the level growers never grew a tree
# there (ROADMAP.md C1), so this is the conservative seed, not a reading
LEVEL_KERNEL = "einsum"
# the CPU backend, at every size: a one-hot einsum is about 100 times
# slower there than a scatter-add, and the cumsum scatter beats lax.sort
CPU_KERNEL = "scatter"
CPU_PARTITION = "scatter"
# row-sharded learners: allreduce is the incumbent; reduce_scatter was
# never measured across chips (ROADMAP.md B4)
HIST_REDUCE = "allreduce"
# four uint8 bins to a word: a wider bin does not fit
PACK_MAX_BIN = 255

# The first split's smaller child: gathered, or one masked pass over the
# table in place. Prices in ns on a v5e, from `criteo-share.train` at 17
# packed words and 67 columns (ledger, PR 31: `train.stage.hist_gather_ms`
# 275.40, `train.stage.hist_kernel_ms` 115.62; PERF.md section 5 has the
# parts) and `msltr.train` at 35 words and 137 columns (ledger, PR 27:
# `train.stage.hist_gather_ms` 477.58, `train.stage.hist_kernel_ms` 252.28).
# The packed-row gather out of HBM pays per index, and a little per word:
# 239.5 ms over 7.9 M padded indices = 30.3 ns at 17 words, and the PR 27
# line at 35 words puts the two parts at 20.6 ns an index + 0.55 ns a word.
ROW_GATHER_NS_INDEX = 20.6
ROW_GATHER_NS_WORD = 0.55
# the gh rows f32[S,3] out of VMEM: 34.3 ms over the same 7.9 M indices
GH_GATHER_NS_INDEX = 4.3
# the Pallas kernel, a row it reads, a column: read from the root's call,
# which reads the table in place, on the three cells (my chip runs, PR 36,
# PERF.md section 5): 39.40 ms for 400,000 rows of 2,000 columns, 0.0493;
# 47.77 ms for 1,000,000 of 968, 0.0494; 7.055 ms for 2,000,000 of 67,
# 0.0527 (a row costs about 0.2 ns beside its columns). `chip_smoke.py`
# prints the same quotient for each kernel entry, at 28 columns and with the
# call's wrapper in it (0.0897 for `hist_pallas_words`). One price for
# every call, the small buckets' too (PERF.md section 6, PR 34). A call that
# reads the table in place pays it for every row; a gathered call for the
# row blocks that overlap its leaf's segment, not for the bucket's padding
# (since PR 34: `ops/hist_pallas.py`, `_hist_call`'s live range)
KERNEL_NS_COLUMN_ROW = 0.050

# The packed words of a row from which the compact grower holds the table
# twice: the narrowest row at which the compiler was seen to re-lay a table
# held once inside the split loop (my TPU compiles, PR 33,
# `scripts/tpu_compile_grower.py`, PERF.md section 6). Held once, the
# word-major table is copied row-major at the head of every branch of the
# histogram's bucket switch, once a split, at 41, 42, 43, 44, 47, 48, 56, 64,
# 100, 127, 128, 175, 250 and 500 words a row at 400,000 rows and at 64 at
# 1 M, and read as it is, a word at a time, at 35 and 40 (400,000 and 1 M
# rows), at 41 at 1 M and at 17 at 2 M: the line moves with the rows, and
# what the compiler weighs is not known. Held twice the module has no such
# copy at any of 35, 41, 64, 100, 127, 128, 175, 250 and 500 words, and
# smaller temporaries at every one; where the compiler keeps the word-major
# table (35 words; 41 at 1 M rows) the second value is the same buffer and
# no copy is made at all. Tables that fit VMEM row-major (200,000 rows: 17,
# 35 and 64 words) are re-laid there once a split at any width, and are left
# as they were.
HELD_TWICE_FROM_WORDS = 41
# Such a table's rows come whole out of its row-major copy and the bucket is
# re-laid for the kernel: `epsilon.train` at 500 words and 2,000 columns
# (my chip run, PR 33; PERF.md section 6: `train.stage.hist_gather_ms` 32.55,
# the `gh` rows and the transposes in it, over about 1.66 M padded rows a
# tree) pays 19.6 ns a row all told: the `gh` rows' 4.3 and 0.031 ns a word,
# a tenth of what the word-major gather's fit would say at that width (300).
# One width priced it: under 128 words a row-major row is padded to a lane
# tile, and what the gather pays there was not read.
ROWS_GATHER_NS_WORD = 0.031

_TRUTHY = ("true", "1", "yes", "on")


def first_split_dense_rows(num_rows: int, num_words: int,
                           num_cols: int) -> int:
    """The most rows a bucket may hold and keep the gathered call at a
    tree's first split. The dense pass costs ``num_rows`` rows of the
    kernel; the gathered call costs the bucket's rows of the gather and of
    the kernel: dense when ``bucket x (gather + kernel) > num_rows x
    kernel``. A wider table moves the line up (about R/21 at 28 columns,
    R/11 at 67, R/7 at 137 and at 160); a table held
    twice (``rows_held_twice``: from 41 words) gathers whole rows for a
    tenth of that, and the line lies at 0.60 R and over (0.80 R at 968
    columns, 0.83 R at 2,000): a smaller child's bucket passes it only
    when the bucket is most of the table, so nearly every first split is
    gathered. Rough: three widths priced it (17 and 35 words the word-major
    gather, 500 the row-major one; between 41 and 499 the row-major price
    is drawn through that one point).

    The rule still prices the gathered call's kernel by its bucket. Since
    PR 34 that call pays for its live row blocks alone, so the rule
    over-prices it by the bucket's padding and leans towards the dense
    pass; neither measured shape turns on it (PERF.md section 6, PR 34),
    and the gather's part, which is the larger, does pay for the bucket."""
    kernel = KERNEL_NS_COLUMN_ROW * num_cols
    if rows_held_twice(num_words):
        gather = ROWS_GATHER_NS_WORD * num_words + GH_GATHER_NS_INDEX
    else:
        gather = (ROW_GATHER_NS_INDEX + ROW_GATHER_NS_WORD * num_words +
                  GH_GATHER_NS_INDEX)
    return math.floor(num_rows * kernel / (gather + kernel))


def rows_held_twice(num_words: int) -> bool:
    """Whether the compact grower keeps a row-major copy of the packed table
    beside the word-major one the column fetch and the in-place kernel
    read."""
    return num_words >= HELD_TWICE_FROM_WORDS


@dataclasses.dataclass(frozen=True)
class Plan:
    hist_rm_backend: str        # GrowerConfig.hist_rm_backend
    level_hist_backend: str     # GrowerConfig.level_hist_backend
    partition_mode: str         # GrowerConfig.partition_mode
    pack: bool                  # store the serial learner's rows as words
    hist_reduce: str            # before the learner's eligibility check
    # (level, line) for the engine to log: requests not granted as asked
    notes: Tuple[Tuple[str, str], ...] = ()


def make_plan(*, platform: str, num_data: int, num_bin_max: int,
              quantized: bool, hist_dtype: str, tree_learner: str,
              storage: str, row_sched: str, hist_kernel: str = "auto",
              packed_bins: str = "auto", partition_mode: str = "auto",
              hist_reduce: str = "auto") -> Plan:
    """The plan for one training set-up.

    ``platform`` is ``jax.default_backend()``; ``storage`` one of
    ``dense`` / ``bundled`` / ``multival``; ``row_sched`` the scheduler
    after its eligibility fallback; the last four are the ``tpu_*``
    parameters of the same names as the user set them.
    """
    notes = []
    cpu = platform == "cpu"
    large = num_data >= MEASURED_FROM_ROWS

    if hist_kernel == "pallas_level":
        # silent remaps make A/B numbers unattributable: say so
        notes.append(("info",
                      "tpu_hist_kernel=pallas_level applies to level-phase "
                      "histograms only; the compact/tail row-major path "
                      "resolves as auto"))
    if hist_kernel not in ("auto", "pallas_level"):
        rm_backend = hist_kernel
    elif cpu:
        rm_backend = CPU_KERNEL
    elif quantized or hist_dtype in ("bfloat16", "bf16"):
        rm_backend = NARROW_KERNEL
    else:
        rm_backend = F32_KERNEL_LARGE if large else F32_KERNEL_SMALL

    if hist_kernel != "auto":
        level_backend = hist_kernel
    else:
        level_backend = CPU_KERNEL if cpu else LEVEL_KERNEL

    if partition_mode == "auto" and cpu:
        partition_mode = CPU_PARTITION

    if hist_reduce == "auto":
        hist_reduce = HIST_REDUCE

    # only the serial learner's row-major copy is packed (the distributed
    # learners shard their own, multi-value storage has none), and only
    # for the compact scheduler: the level grower reads plain uint8 rows
    asked = str(packed_bins).lower()
    pack = (tree_learner == "serial" and storage != "multival" and
            row_sched == "compact" and
            (asked in _TRUTHY or (asked == "auto" and large)))
    if pack and num_bin_max > PACK_MAX_BIN:
        notes.append(("warning",
                      "tpu_packed_bins: bins exceed uint8 "
                      f"(num_bin_max={num_bin_max}); storing unpacked"))
        pack = False

    return Plan(rm_backend, level_backend, partition_mode, pack,
                hist_reduce, tuple(notes))
