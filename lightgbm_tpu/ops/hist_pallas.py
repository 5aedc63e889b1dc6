"""Pallas TPU histogram kernel — the hottest op, on the MXU.

TPU-native counterpart of the reference's histogram kernels
(ref: src/treelearner/cuda/cuda_histogram_constructor.cu:21-71 shared-mem
atomicAdd kernel; src/io/dense_bin.hpp Bin::ConstructHistogram). TPUs have
no fast scatter-add, so the scatter is reformulated as a one-hot matmul
(SURVEY.md §7 kernels (a)) — the same contraction `hist_xla` expresses, but
with explicit VMEM residency:

- grid = (feature tiles, row blocks); the row-block axis is innermost and
  maps to the SAME output block, so the [Cp, FT*Bp] accumulator stays
  pinned in VMEM across the whole row loop — zero HBM traffic for partial
  histograms (XLA's scan materializes the [F, B, C] carry each step).
- per step: build the one-hot expansion of the bin tile in VMEM and
  contract gh_t [Cp, RB] @ onehot [RB, FT*Bp] on the MXU with f32/int32
  accumulation.

TPU tiling rules (measured on v5e: blocks whose last two dims are not
multiples of (sublane, lane) = (8, 128) for 32-bit types fail to lower):
- the channel axis C=3 (grad, hess, count) is padded to 8 sublanes
  (f32) / 32 (int8) — the dead rows multiply zeros and are sliced off;
- the bins tile is feature-major [FT, RB] with FT a multiple of 8 and
  the row block a multiple of 128. Row-major [S, F] inputs (the compact
  scheduler's gathered-leaf layout) are transposed on entry — one cheap
  XLA u8 transpose (~2 bytes/row/feature of HBM traffic) buys a
  tile-legal lane-aligned row axis.

Gradients/hessians enter pre-masked by leaf (gh rows of other leaves are
zero), so a leaf histogram is one pass over the row blocks; the sibling
subtraction trick (FeatureHistogram::Subtract) halves the passes upstream.

``int8`` gh inputs take the quantized-gradient path: the one-hot stays
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


def _hist_kernel(bins_ref, gh_ref, out_ref, *, feature_tile: int,
                 num_bin_padded: int, int8_mode: bool = False,
                 interpret: bool = False):
    """One (feature-tile, row-block) grid step.

    bins_ref: int32 [FT, RB] feature-major
    gh_ref:   f32/int8 [Cp, RB] — transposed, channel-padded, leaf-masked
    out_ref:  f32/int32 [Cp, FT*Bp] — accumulator, pinned across row blocks

    Every op here is Mosaic-friendly by construction: the one-hot for
    feature f is built in [Bp, RB] orientation (a static row slice of the
    bins tile broadcast against a 2D iota — no gather, no transpose, no
    reshape), contracted against gh over the row axis on the MXU, and
    stored to a static lane slice of the accumulator. Peak extra VMEM is
    one [Bp, RB] one-hot (~0.5 MB at Bp=256, RB=512) instead of the full
    [RB, FT*Bp] expansion.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    bins = bins_ref[:]                              # [FT, RB] int32
    gh = gh_ref[:]                                  # [Cp, RB]
    rb = bins.shape[1]
    # iota_b[b, r] = b; onehot_f[b, r] = (bins[f, r] == b)
    iota_b = lax.broadcasted_iota(jnp.int32, (num_bin_padded, rb), 0)

    if int8_mode:
        onehot_dtype, acc_dtype = jnp.int8, jnp.int32
    else:
        # f32 inputs arrive pre-decomposed into bf16 channel triples (see
        # _hist_pallas_impl) — the kernel always contracts at native bf16
        # MXU rate with f32 accumulation. The interpreter backend (CPU
        # tests) lacks bf16 dots; f32 compute there is numerically
        # identical (bf16 values are exact in f32).
        onehot_dtype, acc_dtype = jnp.bfloat16, jnp.float32
        if interpret:
            onehot_dtype = jnp.float32
            gh = gh.astype(jnp.float32)
    for f in range(feature_tile):
        row = lax.slice_in_dim(bins, f, f + 1, axis=0)       # [1, RB]
        onehot_f = (row == iota_b).astype(onehot_dtype)      # [Bp, RB]
        # contract over rows: [Cp, RB] x [Bp, RB] -> [Cp, Bp]
        hist_f = lax.dot_general(
            gh, onehot_f, (((1,), (1,)), ((), ())),
            preferred_element_type=acc_dtype)
        sl = slice(f * num_bin_padded, (f + 1) * num_bin_padded)
        out_ref[:, sl] += hist_f


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


@functools.partial(jax.jit, static_argnames=("num_bin", "block_rows",
                                             "feature_tile", "interpret"))
def _hist_pallas_impl(bins_fm: jnp.ndarray, gh: jnp.ndarray, num_bin: int,
                      block_rows: int, feature_tile: int,
                      interpret: bool) -> jnp.ndarray:
    F, R = bins_fm.shape
    C = gh.shape[1]
    int8_mode = gh.dtype == jnp.int8
    f32_mode = gh.dtype == jnp.float32
    acc_dtype = jnp.int32 if int8_mode else jnp.float32
    if f32_mode:
        # Full f32 accuracy at native bf16 MXU rate: contract all 3C
        # bf16 component channels in ONE matmul — 9 channels still fit
        # the 16-sublane bf16 tile the plain-bf16 path pays for, so the
        # extra accuracy is free — and re-sum the component histograms
        # in f32 below.
        gh = bf16_triple(gh)                                # [R, 3C]
    Cin = gh.shape[1]
    # sublane-align the channel axis per dtype tile: (16,128) bf16,
    # (32,128) int8
    Cp = 32 if int8_mode else _pad_to(max(Cin, 16), 16)
    Bp = _pad_to(num_bin, 128)            # lane-align the bin axis
    feature_tile = max(8, _pad_to(feature_tile, 8))
    block_rows = _pad_to(block_rows, 128)
    Fp = _pad_to(F, feature_tile)
    Rp = _pad_to(R, block_rows)

    with timer.stage("hist_gather"):
        if Fp != F or Rp != R:
            # dead feature rows produce columns sliced off below; padded
            # rows carry gh = 0 so they accumulate nothing
            bins_fm = jnp.pad(bins_fm, ((0, Fp - F), (0, Rp - R)))
        bins_fm = bins_fm.astype(jnp.int32)
        gh_t = jnp.pad(gh, ((0, Rp - R), (0, Cp - Cin))).T    # [Cp, Rp]

    grid = (Fp // feature_tile, Rp // block_rows)
    kernel = functools.partial(_hist_kernel, feature_tile=feature_tile,
                               num_bin_padded=Bp, int8_mode=int8_mode,
                               interpret=interpret)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((feature_tile, block_rows), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((Cp, block_rows), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((Cp, feature_tile * Bp), lambda i, j: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((Cp, Fp * Bp), acc_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(bins_fm, gh_t)

    # [Cp, Fp*Bp] -> [Fp, Bp, Cp] -> [F, num_bin, C]
    hist = out.reshape(Cp, Fp, Bp).transpose(1, 2, 0)
    hist = hist[:F, :num_bin, :]
    if f32_mode:
        # re-sum the bf16 hi/mid/lo component histograms in f32
        return (hist[:, :, 0:C] + hist[:, :, C:2 * C] +
                hist[:, :, 2 * C:3 * C])
    return hist[:, :, :C]


def fit_tiles(feature_tile: int, num_bin: int,
              block_rows: int) -> tuple:
    """Shrink (feature_tile, block_rows) so the kernel's VMEM residents
    (bins tile + pinned accumulator + one [Bp, RB] one-hot at a time)
    stay within ~4 MB, leaving room for double buffering in the
    ~16 MB/core VMEM. feature_tile stays a multiple of 8 (sublane rule),
    block_rows a multiple of 128 (lane rule); feature_tile shrinks
    first, then block_rows — the one-hot term Bp*block_rows is
    feature-tile-independent, so a large tpu_rows_per_block must clamp
    rows, not just features."""
    budget_elems = (4 << 20) // 4
    Bp = _pad_to(num_bin, 128)
    feature_tile = max(8, _pad_to(feature_tile, 8))
    block_rows = max(128, _pad_to(block_rows, 128))

    def resident(ft, br):
        return (ft * br                 # bins tile
                + 32 * ft * Bp          # accumulator (Cp<=32)
                + Bp * br)              # one-hot
    while feature_tile > 8 and \
            resident(feature_tile, block_rows) > budget_elems:
        feature_tile //= 2
    while block_rows > 128 and \
            resident(feature_tile, block_rows) > budget_elems:
        block_rows //= 2
    feature_tile, block_rows = max(feature_tile, 8), max(block_rows, 128)
    # feasible=False when even the (8, 128) floor exceeds the budget
    # (huge num_bin: the pinned 32*8*Bp accumulator alone overflows once
    # Bp >= 4096) — callers must fall back to a non-Pallas backend
    # rather than launch an over-budget kernel
    return feature_tile, block_rows, \
        resident(feature_tile, block_rows) <= budget_elems


def hist_pallas(bins_t: jnp.ndarray, gh: jnp.ndarray, num_bin: int,
                block_rows: int = 1024, feature_tile: int = 8,
                interpret: bool | None = None) -> jnp.ndarray:
    """Histogram [F, num_bin, C] over feature-major [F, R] bins.

    Same contract as hist_xla (ops/histogram.py). `interpret=None` picks
    the Pallas interpreter on the CPU backend only (``default_interpret``;
    the kernel itself is identical) and compiled mode everywhere else.
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
                             bool(interpret))


def hist_pallas_rm(bins_rm: jnp.ndarray, gh: jnp.ndarray, num_bin: int,
                   block_rows: int = 512, feature_tile: int = 8,
                   interpret: bool | None = None) -> jnp.ndarray:
    """Row-major histogram [F, num_bin, C] over a gathered [S, F] block —
    the compact scheduler's layout (same contract as hist_rowmajor).

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
                             feature_tile, bool(interpret))
