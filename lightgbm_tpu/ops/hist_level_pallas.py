"""Sorted-segment Pallas histogram kernel for level-mode growth.

ONE kernel launch produces the full per-level histogram tensor
``[n_nodes, F, B, 3]`` — the TPU-native analogue of the reference's
per-node CUDA histogram kernel over node-contiguous rows
(ref: src/treelearner/cuda/cuda_histogram_constructor.cu:21-71, which
walks DataPartition-sorted rows with shared-memory accumulators). It
replaces the blocks composition in ``core/level_grower.hist_blocks``
(per-block interior histograms via a vmapped row-major kernel + an
owner scatter + TWO masked edge-window passes per node ≈ 4 large
batched kernels per level) with a single grid.

Layout trick — segment-ALIGNED rows, one owner per block:

- the level phase's stable sort on owner-node keys makes each node's
  rows contiguous; this module additionally pads every segment up to a
  multiple of ``block_rows`` (one gather builds the padded layout
  straight from the ORIGINAL row-major bins, so the sorted copy is
  never materialized). Every row block therefore belongs to exactly
  ONE node — no straddling blocks, hence no edge windows and no
  in-kernel segment boundary handling at all.
- grid = (feature tiles, row blocks); the per-block owner node ids ride
  in as a scalar-prefetch operand, and the OUTPUT BlockSpec's index map
  reads them: step (i, j) accumulates into the VMEM bank of node
  ``owner[j]``. Owners are non-decreasing over j (sorted rows), so each
  node's accumulator stays pinned in VMEM across its whole row range
  and is written back exactly once — the revisit-free accumulation
  contract Pallas TPU requires.
- the kernel body is a one-hot MXU contraction, every byte compared with
  every bin (``ops/hist_pallas.py`` factorises the bin instead and is 3.5
  times cheaper a column; bf16 hi/mid/lo triple decomposition for f32
  inputs — exact ~24-bit accumulation at native bf16 rate; int8 one-hot
  with EXACT int32 accumulation for quantized gradients), zero-inited
  via ``pl.when`` on the first block of each owner.

Padding cost: ≤ ``(n_nodes + 1) * block_rows`` dead rows (gh = 0, so
they accumulate nothing). ``level_tiles`` caps ``block_rows`` so the
pad stays ~25% of R at the deepest levels and the VMEM residents
(bins tile + pinned accumulator + one [Bp, RB] one-hot) fit
``fit_tiles``' ~4 MB budget; infeasible shapes (huge num_bin) report
``ok=False`` and callers fall back to the blocks composition.

Transients are O(R): one padded u8 gather [Rp, F], its i32 feature-major
copy for the kernel operand (4 B/row/feature, fused with the gather),
and ~20 B/row of int32 slot bookkeeping — within the level phase's
documented per-level memory budget (core/level_grower.py).

Exactness: each node accumulates only its own rows, in sorted-row
block order — bit-identical to ``hist_blocks`` for dyadic gradients
and for the quantized int32 path (no f32 reassociation channel at
all there), ordinary f32 reassociation noise otherwise, same caveat
as every other formulation in this repo.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .hist_pallas import (_pad_to, bf16_triple, default_interpret,
                          fit_tiles)


def level_tiles(feature_tile: int, num_bin: int, block_rows: int,
                n_nodes: int, num_rows: int) -> tuple:
    """Fit (feature_tile, block_rows) for the level kernel.

    The ~4 MB budget of ``hist_pallas.fit_tiles`` over this kernel's own
    residents (``_resident``); additionally caps ``block_rows`` so the
    segment-alignment padding — at most ``(n_nodes + 1) * block_rows``
    dead rows — stays around a quarter of the real row count at deep
    levels (1024 nodes at 1M rows: 256-row blocks, ≤ ~26% pad).
    Returns ``(feature_tile, block_rows, ok)``; ``ok=False`` means even
    the (8, 128) floor busts VMEM (num_bin >= ~4096) and the caller
    must use the blocks composition instead.
    """
    pad_cap = max(128, (num_rows // max(4 * n_nodes, 1)) // 128 * 128)
    return fit_tiles(feature_tile, num_bin, min(block_rows, pad_cap),
                     resident=_resident)


def _resident(feature_tile: int, block_rows: int, num_bin: int) -> int:
    """This kernel's residents, in 32-bit elements: its ``[Bp, RB]``
    one-hot, where ``hist_pallas._resident`` counts that kernel's two
    operands."""
    Bp = _pad_to(num_bin, 128)
    return (feature_tile * block_rows       # bins tile
            + 32 * feature_tile * Bp        # accumulator (Cp<=32)
            + Bp * block_rows)              # one-hot


def _hist_level_kernel(owner_ref, bins_ref, gh_ref, out_ref, *,
                       feature_tile: int, num_bin_padded: int,
                       int8_mode: bool = False, interpret: bool = False):
    """One (feature-tile i, row-block j) grid step.

    owner_ref: int32 [G] scalar-prefetch — owner node of each row block
    bins_ref:  int32 [FT, RB] feature-major
    gh_ref:    f32/int8 [Cp, RB] — transposed, channel-padded, pad-masked
    out_ref:   f32/int32 [1, Cp, FT*Bp] — the owner node's accumulator,
               pinned in VMEM across the node's whole block range

    The accumulator is zero-initialized on the FIRST block of each
    owner (j == 0 or an owner change); because owners are
    non-decreasing in j, a node's bank is never revisited after
    write-back. The contraction is ``gh [Cp, RB]`` against a ``[Bp, RB]``
    one-hot.
    """
    j = pl.program_id(1)
    prev = owner_ref[jnp.maximum(j - 1, 0)]

    @pl.when((j == 0) | (owner_ref[j] != prev))
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    bins = bins_ref[:]                              # [FT, RB]
    gh = gh_ref[:]                                  # [Cp, RB]
    rb = bins.shape[1]
    iota_b = lax.broadcasted_iota(jnp.int32, (num_bin_padded, rb), 0)

    if int8_mode:
        onehot_dtype, acc_dtype = jnp.int8, jnp.int32
    else:
        # f32 inputs arrive pre-decomposed into bf16 hi/mid/lo channel
        # triples (see _hist_level_impl); the interpreter backend lacks
        # bf16 dots, and f32 compute there is numerically identical
        onehot_dtype, acc_dtype = jnp.bfloat16, jnp.float32
        if interpret:
            onehot_dtype = jnp.float32
            gh = gh.astype(jnp.float32)
    for f in range(feature_tile):
        row = lax.slice_in_dim(bins, f, f + 1, axis=0)       # [1, RB]
        onehot_f = (row == iota_b).astype(onehot_dtype)      # [Bp, RB]
        hist_f = lax.dot_general(
            gh, onehot_f, (((1,), (1,)), ((), ())),
            preferred_element_type=acc_dtype)                # [Cp, Bp]
        sl = slice(f * num_bin_padded, (f + 1) * num_bin_padded)
        out_ref[0, :, sl] += hist_f


@functools.partial(jax.jit, static_argnames=("n_nodes", "num_bin",
                                             "block_rows", "feature_tile",
                                             "interpret"))
def _hist_level_impl(bins_fm: jnp.ndarray, gh: jnp.ndarray,
                     owner: jnp.ndarray, n_nodes: int, num_bin: int,
                     block_rows: int, feature_tile: int,
                     interpret: bool) -> jnp.ndarray:
    """[n_nodes + 1, F, num_bin, C] from segment-aligned operands.

    bins_fm: int32 [F, Rp] feature-major, Rp = G * block_rows
    gh:      f32/int8 [Rp, C], pad rows zeroed
    owner:   int32 [G] non-decreasing block owners in [0, n_nodes]
             (slot ``n_nodes`` collects dump/pad blocks)
    """
    F, Rp = bins_fm.shape
    C = gh.shape[1]
    int8_mode = gh.dtype == jnp.int8
    f32_mode = gh.dtype == jnp.float32
    acc_dtype = jnp.int32 if int8_mode else jnp.float32
    if f32_mode:
        # f32-accurate accumulation at native bf16 MXU rate
        gh = bf16_triple(gh)                                 # [Rp, 3C]
    Cin = gh.shape[1]
    Cp = 32 if int8_mode else _pad_to(max(Cin, 16), 16)
    Bp = _pad_to(num_bin, 128)
    feature_tile = max(8, _pad_to(feature_tile, 8))
    Fp = _pad_to(F, feature_tile)
    G = Rp // block_rows

    if Fp != F:
        # dead feature rows: their histogram columns are sliced off
        bins_fm = jnp.pad(bins_fm, ((0, Fp - F), (0, 0)))
    gh_t = jnp.pad(gh, ((0, 0), (0, Cp - Cin))).T            # [Cp, Rp]

    kernel = functools.partial(_hist_level_kernel,
                               feature_tile=feature_tile,
                               num_bin_padded=Bp, int8_mode=int8_mode,
                               interpret=interpret)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Fp // feature_tile, G),
        in_specs=[
            pl.BlockSpec((feature_tile, block_rows),
                         lambda i, j, own: (i, j)),
            pl.BlockSpec((Cp, block_rows), lambda i, j, own: (0, j)),
        ],
        # the owner-keyed VMEM bank: block (owner[j], :, i). Owners are
        # non-decreasing, so the same out block is mapped by CONSECUTIVE
        # j steps only — the Pallas accumulation contract
        out_specs=pl.BlockSpec((1, Cp, feature_tile * Bp),
                               lambda i, j, own: (own[j], 0, i)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_nodes + 1, Cp, Fp * Bp),
                                       acc_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(owner, bins_fm, gh_t)

    # [N+1, Cp, Fp*Bp] -> [N+1, Fp, Bp, Cp] -> [N+1, F, num_bin, C]
    hist = out.reshape(n_nodes + 1, Cp, Fp, Bp).transpose(0, 2, 3, 1)
    hist = hist[:, :F, :num_bin, :]
    if f32_mode:
        return (hist[..., 0:C] + hist[..., C:2 * C] +
                hist[..., 2 * C:3 * C])
    return hist[..., :C]


def hist_level(bins_rm: jnp.ndarray, gh: jnp.ndarray, local: jnp.ndarray,
               in_lvl: jnp.ndarray, n_nodes: int, num_bin: int,
               block_rows: int = 512, feature_tile: int = 8,
               interpret: bool | None = None) -> jnp.ndarray:
    """Per-node level histograms ``[n_nodes, F, num_bin, C]`` in ONE
    kernel launch over node-sorted rows.

    Same contract as ``core/level_grower.hist_level_blocks``: row-major
    uint8/16 ``bins_rm`` [R, F] (EFB physical-group columns pass through
    untouched), per-row values ``gh`` [R, C] (f32 triples or int8
    quantized), ``local`` the per-row level-local node id with
    ``in_lvl`` masking rows that already left the level (they land in a
    dump slot that is sliced off). Ragged segments — empty nodes,
    single-row nodes, everything-in-one-node — are served by
    construction: empty nodes own zero blocks (their never-written
    banks are masked to zero below), tiny nodes own one padded block.

    ``interpret=None`` picks the Pallas interpreter on the CPU backend
    only (the CPU parity tests run the interpreter on the SAME kernel)
    and compiled mode everywhere else. Infeasible tile shapes must be
    rejected by the caller via ``level_tiles`` BEFORE calling (the level
    phase falls back to the blocks composition there).
    """
    if interpret is None:
        interpret = default_interpret()
    R, F = bins_rm.shape
    feature_tile, block_rows, ok = level_tiles(feature_tile, num_bin,
                                               block_rows, n_nodes, R)
    if not ok:
        raise ValueError(
            f"hist_level tiles infeasible at num_bin={num_bin} "
            "(VMEM budget); gate with level_tiles and fall back")

    key = jnp.where(in_lvl, local, n_nodes).astype(jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    cnt = jnp.zeros(n_nodes + 1, jnp.int32).at[key].add(1)
    starts = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(cnt)])          # [N+2]
    # segment-ALIGNED layout: node v's rows start at a block multiple
    blocks_v = (cnt + block_rows - 1) // block_rows
    astarts = jnp.concatenate(
        [jnp.zeros(1, jnp.int32),
         jnp.cumsum(blocks_v * block_rows)])                 # [N+2]
    # static block-count bound: sum(ceil(cnt_v/RB)) <= R//RB + N + 1
    G = R // block_rows + n_nodes + 1
    Rp = G * block_rows

    q = jnp.arange(Rp, dtype=jnp.int32)
    v = jnp.clip(jnp.searchsorted(astarts, q, side="right")
                 .astype(jnp.int32) - 1, 0, n_nodes)
    sortpos = q - astarts[v] + starts[v]
    valid = sortpos < starts[v] + cnt[v]
    src = order[jnp.clip(sortpos, 0, R - 1)]
    # ONE gather straight from the original row-major arrays (the
    # sorted copy is never materialized); pad/overhang rows carry
    # gh = 0 so they accumulate nothing
    pb = jnp.take(bins_rm, src, axis=0)                      # [Rp, F]
    pgh = jnp.take(gh, src, axis=0) * valid[:, None].astype(gh.dtype)
    owner = v.reshape(G, block_rows)[:, 0]                   # [G]

    # jaxlint: disable=JL001 — interpret is a static Python flag
    hist = _hist_level_impl(pb.T.astype(jnp.int32), pgh, owner,
                            n_nodes, num_bin, block_rows, feature_tile,
                            bool(interpret))
    # empty nodes own zero blocks, so their banks were never written
    # (undefined memory): force them to exact zeros
    nonempty = (cnt[:n_nodes] > 0)[:, None, None, None]
    return jnp.where(nonempty, hist[:n_nodes], jnp.zeros_like(
        hist[:n_nodes]))
