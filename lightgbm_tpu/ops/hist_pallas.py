"""Pallas TPU histogram kernel — the hottest op, on the MXU.

TPU-native counterpart of the reference's histogram kernels
(ref: src/treelearner/cuda/cuda_histogram_constructor.cu:21-71 shared-mem
atomicAdd kernel; src/io/dense_bin.hpp Bin::ConstructHistogram). TPUs have
no fast scatter-add, so the scatter is reformulated as a one-hot matmul
(SURVEY.md §7 kernels (a)) — the sums `hist_xla` expresses, but with
explicit VMEM residency and without comparing a bin with every bin:

- grid = (feature tiles, row blocks); the row-block axis is innermost and
  maps to the SAME output block, so the [lo, FT*Np] accumulator stays
  pinned in VMEM across the whole row loop — zero HBM traffic for partial
  histograms (XLA's scan materializes the [F, B, C] carry each step).
- per step and feature: a bin is ``_LO * hi + lo`` and ``[bin == b] =
  [hi_r == hi] * [lo_r == lo]``, so the kernel builds the one-hot of the
  low part alone, ``[lo, RB]``, and ``A [channels * num_hi, RB]``, each
  ``gh`` channel over the high parts, kept where the row's high part is
  the sublane's and zero elsewhere, and contracts the two over the rows on
  the MXU with f32/int32 accumulation: ``H[c, hi, lo] = sum_r A[c, hi, r]
  * onehot_lo[lo, r]``. The terms of every sum are the terms a [Bp, RB]
  one-hot gives (`ops/hist_level_pallas.py` builds that one: 256 rows a
  column through the MXU where this kernel pushes 32 + 56, and a v5e prices
  a column by those rows, PERF.md section 6, PR 36).

TPU tiling rules (measured on v5e: blocks whose last two dims are not
multiples of (sublane, lane) = (8, 128) for 32-bit types fail to lower):
- the channel axis (grad, hess, count; nine with the bf16 triple) is
  padded to 16 sublanes (bf16) / 32 (int8) in ``gh``'s block; the kernel
  reads the live ones;
- the bins tile is feature-major [FT, RB] with FT a multiple of 8 and
  the row block a multiple of 128. Row-major uint8/int32 [S, F] inputs
  (``hist_pallas_rm``: the compact scheduler's gathered leaf on unpacked
  bins) are transposed, padded and widened to int32 on entry: 4 bytes a
  bin. Bit-packed rows (``hist_pallas_words``: the compact scheduler on
  packed bins) arrive as the uint32 words the table stores, word-major
  [W, S], and are read in place, unpadded; the tile is [8, RB] words,
  32 features, and the kernel takes a feature's byte out of its word in
  VMEM with a shift and a mask — 1 byte a bin, no unpacked copy of the
  rows, and at the root no copy of the table at all. Both entries run one
  kernel body (``_hist_kernel``), which differs only in how it fetches a
  feature's row from the tile.

Gradients/hessians enter pre-masked by leaf (gh rows of other leaves are
zero), so a leaf histogram is one pass over the row blocks; the sibling
subtraction trick (FeatureHistogram::Subtract) halves the passes upstream.

``int8`` gh inputs take the quantized-gradient path: both operands stay
int8 and the contraction accumulates EXACTLY in int32 on the MXU
(ref: bin.h:49-82 integer histogram reducers).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import timer
from ..utils.log import info_once as _log_once


def default_interpret() -> bool:
    """Run the Pallas interpreter only where Mosaic cannot run at all —
    the CPU backend (tier-1 tests). Every other platform compiles the
    kernel or raises: an accelerator that silently interpreted would
    publish interpreter timings as device numbers."""
    return jax.default_backend() == "cpu"


def _row_of_bins(block, f):
    """Feature ``f`` of a feature-major bins tile int32 [FT, RB]."""
    return lax.slice_in_dim(block, f, f + 1, axis=0)


def _row_of_words(block, f):
    """Feature ``f`` of a word-major tile of packed words uint32 [WT, RB]:
    byte ``f % 4`` of word ``f // 4``, as int32. The bitcast is free in
    registers (outside the kernel it is a copy of the operand); after it
    the shift is arithmetic and drags the sign down from the top byte,
    and the mask leaves the byte either way."""
    word = lax.bitcast_convert_type(
        lax.slice_in_dim(block, f // 4, f // 4 + 1, axis=0), jnp.int32)
    return (word >> (8 * (f % 4))) & 0xFF


# A bin is ``_LO * hi + lo``: the kernel contracts a one-hot of ``lo`` against
# ``gh`` masked by ``hi`` (``_hist_kernel``). A byte's low five bits and high
# three: on a v5e a column costs by the rows of the two operands, the masked
# one's first (PERF.md section 6, PR 36: 1,048,576 x 2,000, ns a column a
# row: the [256, RB] one-hot 0.173; 16 x 16 with nine channels, 144 rows,
# 0.104; 32 x 8, 72 rows, 0.059, and 0.049 with seven channels, 56 rows)
_LO = 32


def _hist_kernel(live_ref, bins_ref, gh_ref, out_ref, *, feature_tile: int,
                 num_hi: int, channels: int, lo: int, fetch, tiles: int,
                 live_in_last: int, int8_mode: bool = False,
                 interpret: bool = False):
    """One (feature-tile, row-block) grid step.

    live_ref: int32 [2] in SMEM (scalar prefetch): the first live row block
              and how many follow it. Row step ``j`` holds block
              ``live_ref[0] + j`` (``_hist_call``'s index maps) and adds
              nothing from ``live_ref[1]`` on
    bins_ref: int32 [FT, RB] feature-major bins, or uint32 [FT/4, RB]
              word-major packed words; ``fetch(block, f)`` takes feature
              ``f``'s int32 [1, RB] row out of either
    gh_ref:   bf16/int8 [Cp, RB] — transposed, channel-padded, leaf-masked;
              the first ``channels`` rows are read
    out_ref:  f32/int32 [lo, FT*Np] — accumulator, pinned across row
              blocks; feature ``f``'s ``Np`` lanes hold, at lane
              ``c * num_hi + hi`` of sublane ``l``, channel ``c`` of bin
              ``lo * hi + l``

    ``live_in_last``: how many features of the last of the ``tiles``
    feature tiles exist. That tile runs those alone: the others are
    skipped, not histogrammed and sliced off.

    No byte is compared with every bin. ``[bin == b]`` is ``[hi_r == hi] *
    [lo_r == lo]``, so for feature f the kernel builds a one-hot of the low
    part alone, ``[lo, RB]``, and ``A [channels * num_hi, RB]``: each ``gh``
    channel over ``num_hi`` sublanes, kept where the row's high part is the
    sublane's and zero elsewhere (a select: every value is ``gh``'s own or
    zero, so every sum adds the terms a ``[Bp, RB]`` one-hot would). Their
    contraction over the row axis on the MXU is the feature's histogram,
    ``[lo, channels * num_hi]``, stored to a static lane slice of the
    accumulator. Every op is Mosaic-friendly by construction: static row
    slices broadcast against 2D iotas, no gather, no transpose, no reshape.
    """
    # both read here: the interpreter resolves a program id at the kernel's
    # top level, not inside a branch of a branch
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    # one guard a kernel, around everything a step does with its blocks: a
    # step past the live ones names the block the step before it held, so
    # nothing was fetched for it, and it builds nothing
    @pl.when(j < live_ref[1])
    def _():
        bins = bins_ref[:]                          # [FT, RB] / [FT/4, RB]
        rb = bins.shape[1]
        lanes = out_ref.shape[1] // feature_tile    # Np
        iota_lo = lax.broadcasted_iota(jnp.int32, (lo, rb), 0)
        iota_hi = lax.broadcasted_iota(jnp.int32, (num_hi, rb), 0)

        if int8_mode:
            wide, narrow, acc_dtype = jnp.int32, jnp.int8, jnp.int32
        else:
            # f32 inputs arrive pre-decomposed into bf16 channel triples
            # (see _hist_call) — the kernel always contracts at native bf16
            # MXU rate with f32 accumulation, and selects in f32, where the
            # bf16 values are exact. The interpreter backend (CPU tests)
            # lacks bf16 dots; f32 compute there is numerically identical.
            wide, narrow, acc_dtype = jnp.float32, jnp.bfloat16, jnp.float32
            if interpret:
                narrow = jnp.float32
        # each channel over num_hi sublanes: once a row block, not a column
        gh = gh_ref[:].astype(wide)                 # [Cp, RB]
        rep = [jnp.broadcast_to(gh[c:c + 1, :], (num_hi, rb))
               for c in range(channels)]
        zero = jnp.zeros((num_hi, rb), wide)
        dead = lanes - channels * num_hi
        tail = [jnp.zeros((dead, rb), wide)] if dead else []

        def add(f):
            row = fetch(bins, f)                                 # [1, RB]
            oh_lo = ((row & (lo - 1)) == iota_lo).astype(narrow)  # [lo, RB]
            keep = (row >> (lo.bit_length() - 1)) == iota_hi  # [num_hi, RB]
            a = jnp.concatenate(
                [jnp.where(keep, r, zero) for r in rep] + tail,
                axis=0).astype(narrow)                           # [Np, RB]
            # contract over rows: [lo, RB] x [Np, RB] -> [lo, Np]
            hist_f = lax.dot_general(
                oh_lo, a, (((1,), (1,)), ((), ())),
                preferred_element_type=acc_dtype)
            out_ref[:, f * lanes:(f + 1) * lanes] += hist_f

        def features(n):
            for f in range(n):
                add(f)

        if tiles == 1 or live_in_last == feature_tile:
            features(live_in_last)
        else:
            # two straight-line branches, not a guard around each feature
            # that the last tile lacks: 29 guards a kernel, 12 kernels, took
            # 2 s more to trace and lower, at every start of a training
            # process
            lax.cond(i < tiles - 1,
                     functools.partial(features, feature_tile),
                     functools.partial(features, live_in_last))


def _pad_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def bf16_triple(gh: jnp.ndarray) -> jnp.ndarray:
    """f32 [R, C] -> bf16 [R, 3C] = (hi | mid | lo) with
    hi + mid + lo == gh to ~24 mantissa bits (the one-hot operand is
    0/1, exact in bf16, so three bf16 contractions re-summed in f32 give
    an f32-accurate histogram).

    The rounding is ``lax.reduce_precision``, NOT
    ``astype(bf16).astype(f32)``: XLA:TPU elides a convert round trip it
    considers excess precision, which zeroes ``mid`` and ``lo`` and
    leaves a plain-bf16 histogram (seen on v5e: error 0.23 vs 6e-5 per
    bin at 1M rows)."""
    def rnd(x):
        return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    hi = rnd(gh)
    r1 = gh - hi
    mid = rnd(r1)
    lo = r1 - mid
    return jnp.concatenate([hi, mid, lo], axis=1).astype(jnp.bfloat16)


def live_row_blocks(live, num_rows: int, block_rows: int) -> tuple:
    """(first, count) of the ``block_rows`` row blocks that overlap rows
    ``live = (lo, hi)``, cut to an operand of ``num_rows`` rows."""
    lo = jnp.clip(live[0], 0, num_rows)
    hi = jnp.clip(live[1], lo, num_rows)
    first = lo // block_rows
    return first, (hi + block_rows - 1) // block_rows - first


def _hist_call(bins_op, bins_block, gh, num_bin, num_features,
               feature_tile, block_rows, fetch, interpret, live=None,
               count_in_bf16=False):
    """The ``pallas_call`` and what both entries do around it: the bf16
    triple split, ``gh`` padded and transposed, the re-sum.

    bins_op: int32 bins or uint32 words, rows on the lane axis, at least
    ``gh``'s; where it stops short of a whole block the kernel reads what
    lies behind it, and ``gh`` is zero there. ``bins_block`` is its
    block's sublane extent (``feature_tile`` bins rows, or the words that
    hold them).

    live: ``(lo, hi)``, traced int32 scalars: ``gh`` is zero outside rows
    ``[lo, hi)`` (a leaf's segment inside its bucket), and only the row
    blocks that overlap them are fetched and histogrammed. The grid stays
    the static ``(tiles, row blocks)``: the first live block and their
    count reach the index maps and the kernel as prefetched scalars, row
    step ``j`` holds live block ``j``, and a step past the last names that
    block again, which the pipeline does not fetch twice. ``None`` is every
    row: the same kernel, told that every block is live.

    count_in_bf16: float32 ``gh`` whose last column bfloat16 holds exactly
    (the grower's count: 0 or 1). Its mid and lo parts of the triple are
    zero and stay out of the contraction, seven channels for nine: every
    sum is the sum it was, and ``A`` has two ninths fewer rows.
    """
    R, C = gh.shape
    int8_mode = gh.dtype == jnp.int8
    f32_mode = gh.dtype == jnp.float32
    acc_dtype = jnp.int32 if int8_mode else jnp.float32
    if f32_mode:
        # Full f32 accuracy at native bf16 MXU rate: contract all 3C
        # bf16 component channels in ONE matmul and re-sum the component
        # histograms in f32 below.
        gh = bf16_triple(gh)                                # [R, 3C]
        # the columns whose mid and lo parts are contracted: the last one's
        # are zero with ``count_in_bf16``, and left out
        n = C - 1 if count_in_bf16 else C
        if n < C:
            # slices, which fuse into the pad and the transpose below (an
            # indexed pick is a gather, and held [R, 3C - 2] beside them)
            gh = jnp.concatenate([gh[:, :C], gh[:, C:C + n],
                                  gh[:, 2 * C:2 * C + n]], axis=1)
    Cin = gh.shape[1]
    # sublane-align the channel axis per dtype tile: (16,128) bf16,
    # (32,128) int8
    Cp = 32 if int8_mode else _pad_to(max(Cin, 16), 16)
    num_hi, Np = _operand_shape(num_bin, Cin)
    Rp = _pad_to(R, block_rows)
    tiles = pl.cdiv(num_features, feature_tile)
    Fp = tiles * feature_tile

    with timer.stage("hist_gather"):
        # padded rows carry gh = 0 so they accumulate nothing
        gh_t = jnp.pad(gh, ((0, Rp - R), (0, Cp - Cin))).T    # [Cp, Rp]

    blocks = Rp // block_rows
    live_blocks = jnp.stack(live_row_blocks(
        (0, R) if live is None else live, R, block_rows)).astype(jnp.int32)

    def row_block(j, live_ref):
        # a dead step stays on the last live block; an empty range on one
        # inside the operand
        last = jnp.maximum(live_ref[1], 1) - 1
        return jnp.minimum(live_ref[0] + jnp.minimum(j, last), blocks - 1)

    kernel = functools.partial(
        _hist_kernel, feature_tile=feature_tile, num_hi=num_hi,
        channels=Cin, lo=_LO, fetch=fetch, tiles=tiles,
        live_in_last=num_features - (tiles - 1) * feature_tile,
        int8_mode=int8_mode, interpret=interpret)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles, blocks),
            in_specs=[
                pl.BlockSpec((bins_block, block_rows),
                             lambda i, j, lv: (i, row_block(j, lv)),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((Cp, block_rows),
                             lambda i, j, lv: (0, row_block(j, lv)),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((_LO, feature_tile * Np),
                                   lambda i, j, lv: (0, i),
                                   memory_space=pltpu.VMEM),
        ),
        out_shape=jax.ShapeDtypeStruct((_LO, Fp * Np), acc_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(live_blocks, bins_op, gh_t)

    # [lo, Fp*Np] -> [Fp, hi, lo, Cin] -> [F, num_bin, Cin]
    hist = out.reshape(_LO, Fp, Np)[:, :, :Cin * num_hi]
    hist = hist.reshape(_LO, Fp, Cin, num_hi).transpose(1, 3, 0, 2)
    hist = hist.reshape(Fp, num_hi * _LO, Cin)[:num_features, :num_bin, :]
    if f32_mode:
        # re-sum the bf16 hi/mid/lo component histograms in f32; a column
        # without mid and lo parts is its hi part
        sums = (hist[:, :, 0:n] + hist[:, :, C:C + n] +
                hist[:, :, C + n:C + 2 * n])
        return sums if n == C else jnp.concatenate(
            [sums, hist[:, :, n:C]], axis=2)
    return hist[:, :, :C]


# The benchmark finds the kernel's events in a device trace by the name of
# the jit that encloses the ``pallas_call``: both start ``_hist_pallas``.
@functools.partial(jax.jit, static_argnames=("num_bin", "block_rows",
                                             "feature_tile", "interpret",
                                             "count_in_bf16"))
def _hist_pallas_impl(bins_fm: jnp.ndarray, gh: jnp.ndarray, num_bin: int,
                      block_rows: int, feature_tile: int,
                      interpret: bool, live=None,
                      count_in_bf16: bool = False) -> jnp.ndarray:
    F, R = bins_fm.shape
    feature_tile = max(8, _pad_to(feature_tile, 8))
    block_rows = _pad_to(block_rows, 128)
    Fp = _pad_to(F, feature_tile)
    Rp = _pad_to(R, block_rows)
    with timer.stage("hist_gather"):
        if Fp != F or Rp != R:
            # dead feature rows produce columns sliced off below
            bins_fm = jnp.pad(bins_fm, ((0, Fp - F), (0, Rp - R)))
        bins_fm = bins_fm.astype(jnp.int32)
    return _hist_call(bins_fm, feature_tile, gh, num_bin, Fp, feature_tile,
                      block_rows, _row_of_bins, interpret, live,
                      count_in_bf16)[:F]


_WORD_TILE = 8      # words a tile: one sublane tile of 32-bit elements


@functools.partial(jax.jit, static_argnames=("num_bin", "num_cols",
                                             "block_rows", "interpret",
                                             "count_in_bf16"))
def _hist_pallas_words(words_cm: jnp.ndarray, gh: jnp.ndarray, num_bin: int,
                       num_cols: int, block_rows: int,
                       interpret: bool, live=None,
                       count_in_bf16: bool = False) -> jnp.ndarray:
    block_rows = _pad_to(block_rows, 128)
    # the (8, block_rows) tile of 32-bit elements hist_pallas_rm reads, now
    # 32 features. Where the word axis ends inside a tile (17 words: the
    # third; fewer than 8: the only one) the block reaches past the
    # operand, and the kernel fetches only the words its live columns
    # lie in
    return _hist_call(words_cm, _WORD_TILE, gh, num_bin, num_cols,
                      4 * _WORD_TILE, block_rows, _row_of_words, interpret,
                      live, count_in_bf16)


# the kernel's VMEM residents stay within ~4 MB of 32-bit elements, which
# leaves room for double buffering in the ~16 MB/core VMEM
_VMEM_BUDGET_ELEMS = (4 << 20) // 4
# the f32 triple's: the most channels a call contracts
_MAX_CHANNELS = 9


def _operand_shape(num_bin: int, channels: int) -> tuple:
    """(num_hi, lanes) of a column's contraction: the sublanes a bin's high
    part runs over, whole tiles of 32-bit elements, and the lanes a column
    has in the output, ``channels * num_hi`` of them in use."""
    num_hi = _pad_to(pl.cdiv(num_bin, _LO), 8)
    return num_hi, _pad_to(channels * num_hi, 128)


def expanded_rows(num_bin: int, float32_gh: bool,
                  count_in_bf16: bool = False) -> int:
    """Rows of ``A``, the operand a column's contraction holds still: the
    call's channels (from float32 ``gh`` the triple's nine, seven with
    ``count_in_bf16``; else three) over the high parts of ``num_bin`` bins.
    The ``[Bp, RB]`` one-hot had 256."""
    channels = 3 if not float32_gh else _MAX_CHANNELS - 2 * count_in_bf16
    return channels * _operand_shape(num_bin, channels)[0]


def _resident(feature_tile: int, block_rows: int, num_bin: int) -> int:
    num_hi, lanes = _operand_shape(num_bin, _MAX_CHANNELS)
    return (feature_tile * block_rows           # bins tile
            + _LO * feature_tile * lanes        # accumulator
            # gh over the high parts, and a column's A: selected in 32
            # bits, then in the contraction's dtype
            + (_MAX_CHANNELS * num_hi + 2 * lanes) * block_rows)


def fit_tiles(feature_tile: int, num_bin: int, block_rows: int,
              resident=_resident) -> tuple:
    """Shrink (feature_tile, block_rows) so the kernel's VMEM residents
    (``resident``; this module's: bins tile + pinned accumulator + ``gh``
    over the high parts and one column's ``A`` at a time) stay within the
    budget. feature_tile stays a multiple of 8 (sublane rule), block_rows
    a multiple of 128 (lane rule); feature_tile shrinks first, then
    block_rows — the operands' terms are feature-tile-independent, so a
    large tpu_rows_per_block must clamp rows, not just features."""
    feature_tile = max(8, _pad_to(feature_tile, 8))
    block_rows = max(128, _pad_to(block_rows, 128))

    while feature_tile > 8 and \
            resident(feature_tile, block_rows, num_bin) > _VMEM_BUDGET_ELEMS:
        feature_tile //= 2
    while block_rows > 128 and \
            resident(feature_tile, block_rows, num_bin) > _VMEM_BUDGET_ELEMS:
        block_rows //= 2
    feature_tile, block_rows = max(feature_tile, 8), max(block_rows, 128)
    # feasible=False when even the (8, 128) floor exceeds the budget
    # (huge num_bin: the operands' lanes grow with the bins' high parts,
    # 4096 bins are 256 of them) — callers must fall back to a non-Pallas
    # backend rather than launch an over-budget kernel
    return feature_tile, block_rows, \
        resident(feature_tile, block_rows, num_bin) <= _VMEM_BUDGET_ELEMS


def words_block_rows(block_rows: int, num_bin: int) -> int:
    """The row block ``hist_pallas_words`` runs when asked for
    ``block_rows``. Its feature tile is the word tile's 32 columns whatever
    the budget says, so only the rows give way; a byte's 256 bins fit at
    128 rows."""
    block_rows = max(128, _pad_to(block_rows, 128))
    while block_rows > 128 and _resident(
            _WORD_TILE * 4, block_rows, num_bin) > _VMEM_BUDGET_ELEMS:
        block_rows //= 2
    return block_rows


def hist_pallas(bins_t: jnp.ndarray, gh: jnp.ndarray, num_bin: int,
                block_rows: int = 1024, feature_tile: int = 8,
                interpret: bool | None = None, live=None) -> jnp.ndarray:
    """Histogram [F, num_bin, C] over feature-major [F, R] bins.

    Same contract as hist_xla (ops/histogram.py). `interpret=None` picks
    the Pallas interpreter on the CPU backend only (``default_interpret``;
    the kernel itself is identical) and compiled mode everywhere else.
    ``live``: the rows outside which ``gh`` is zero (``_hist_call``); the
    kernel alone makes use of it.
    """
    if interpret is None:
        interpret = default_interpret()
    feature_tile, block_rows, ok = fit_tiles(feature_tile, num_bin,
                                             block_rows)
    if not ok:
        from .histogram import hist_xla
        _log_once(f"hist_pallas: tiles infeasible for bins {bins_t.shape} "
                  f"at num_bin={num_bin} (VMEM budget); using hist_xla")
        return hist_xla(bins_t, gh, num_bin, block_rows)
    # jaxlint: disable=JL001 — interpret is a static Python flag
    return _hist_pallas_impl(bins_t, gh, num_bin, block_rows, feature_tile,
                             bool(interpret), live)


def hist_pallas_rm(bins_rm: jnp.ndarray, gh: jnp.ndarray, num_bin: int,
                   block_rows: int = 512, feature_tile: int = 8,
                   interpret: bool | None = None, live=None,
                   count_in_bf16: bool = False) -> jnp.ndarray:
    """Row-major histogram [F, num_bin, C] over a gathered [S, F] block —
    the compact scheduler's layout (same contract as hist_rowmajor;
    ``live`` as ``hist_pallas`` takes it, ``count_in_bf16`` as
    ``_hist_call``).

    The tile-legal kernel wants lane-aligned rows, so the block is
    transposed to feature-major first; XLA fuses the u8 transpose into
    the gather that produced the block when both live in one program.
    """
    if interpret is None:
        interpret = default_interpret()
    feature_tile, block_rows, ok = fit_tiles(feature_tile, num_bin,
                                             block_rows)
    if not ok:
        from .histogram import hist_rowmajor
        _log_once(f"hist_pallas_rm: tiles infeasible for bins "
                  f"{bins_rm.shape} at num_bin={num_bin} (VMEM budget); "
                  "using the einsum row-major kernel")
        return hist_rowmajor(bins_rm, gh, num_bin,
                             block_rows=block_rows, backend="einsum")
    with timer.stage("hist_gather"):
        bins_fm = bins_rm.T
    # jaxlint: disable=JL001 — interpret is a static Python flag
    return _hist_pallas_impl(bins_fm, gh, num_bin, block_rows,
                             feature_tile, bool(interpret), live,
                             count_in_bf16)


def hist_pallas_words(words_cm: jnp.ndarray, gh: jnp.ndarray, num_bin: int,
                      num_cols: int, block_rows: int = 512,
                      dtype: str = "float32",
                      interpret: bool | None = None,
                      live=None, count_in_bf16: bool = False) -> jnp.ndarray:
    """Histogram [num_cols, num_bin, C] over bit-packed rows as the table
    stores them: ``words_cm`` uint32 [ceil(num_cols / 4), S] word-major
    (rows on the lane axis), byte ``k`` of word ``w`` = column ``4w + k``.
    ``dtype`` as ``hist_rowmajor`` takes it: "bfloat16" rounds a float
    ``gh`` to bf16 first. ``live`` as ``hist_pallas`` takes it: a leaf's
    segment inside its gathered bucket; ``count_in_bf16`` as ``_hist_call``.

    Equal bit for bit to ``hist_rowmajor(backend="pallas")`` on the
    unpacked rows wherever the two read the same row blocks (any
    ``block_rows`` up to 2,688: same order of accumulation), without the
    int32 [S, num_cols] copy: the kernel reads an (8, block_rows) tile of
    words, 32 columns, where that one reads 8 columns, and takes each byte
    out in VMEM. The operand is read in place, unpadded: the table's own
    word-major view at the root.
    """
    if interpret is None:
        interpret = default_interpret()
    if words_cm.shape != ((num_cols + 3) // 4, gh.shape[0]):
        raise ValueError(f"hist_pallas_words: words {words_cm.shape} are "
                         f"not {num_cols} columns of {gh.shape[0]} rows, "
                         "word-major")
    Bp = _pad_to(num_bin, 128)
    if Bp > 256:
        raise ValueError(f"hist_pallas_words: num_bin={num_bin}, and a "
                         "packed bin is a byte")
    if dtype in ("bfloat16", "bf16") and gh.dtype != jnp.int8:
        gh = gh.astype(jnp.bfloat16)
    # jaxlint: disable=JL001 — interpret is a static Python flag
    return _hist_pallas_words(words_cm, gh, num_bin, num_cols,
                              words_block_rows(block_rows, num_bin),
                              bool(interpret), live, count_in_bf16)
