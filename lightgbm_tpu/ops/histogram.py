"""Histogram construction: the hottest op in GBDT training.

TPU-native equivalent of Bin::ConstructHistogram /
MultiValBinWrapper::ConstructHistograms (ref: include/LightGBM/bin.h:351-422,
src/io/dense_bin.hpp, src/treelearner/cuda/cuda_histogram_constructor.cu:21).

The reference scatter-adds (grad, hess) into per-feature bin arrays. TPUs have
no fast generic scatter, so the kernel is reformulated as a matmul against an
in-register one-hot expansion of the bin indices — the MXU-friendly shape
(SURVEY.md §7 kernels (a)):

    hist[c, f*B + b] = sum_r gh[c, r] * onehot(bins[r, f] == b)

i.e. a [C, R_blk] @ [R_blk, F*B] matmul per row block, accumulated in f32.
Leaf membership enters as a mask multiplied into gh — histogram of a leaf is a
full pass with rows of other leaves zeroed (LightGBM's O(rows_in_leaf) via
index partitioning is recovered later through block-skip scheduling; the
sibling subtraction trick halves the passes either way, see grower.py).

Two implementations:
- ``hist_xla``: lax.scan over row blocks of an einsum — portable baseline.
- ``hist_pallas`` (ops/hist_pallas.py): the Pallas TPU kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def hist_xla(bins_t: jnp.ndarray, gh: jnp.ndarray, num_bin: int,
             block_rows: int = 4096) -> jnp.ndarray:
    """Histogram via blocked one-hot einsum.

    Parameters
    ----------
    bins_t : uint8/uint16/int32 [F, R] feature-major bin indices.
    gh : f32 [R, C] per-row values to accumulate (pre-masked: typically
        (grad*m, hess*m, m) so channel 2 yields exact in-leaf counts).
    num_bin : static B (max bins over features).
    block_rows : rows per scan step; R must be divisible (pad upstream).

    Returns f32 [F, num_bin, C].
    """
    F, R = bins_t.shape
    C = gh.shape[1]
    iota = jnp.arange(num_bin, dtype=jnp.int32)
    int8_mode = gh.dtype == jnp.int8
    acc_dtype = jnp.int32 if int8_mode else jnp.float32

    def block_hist(bb, gb):
        if int8_mode:
            # quantized path: EXACT int32 accumulation on the int8 MXU
            # (ref: bin.h:49-82 Int32HistogramSumReducer et al.)
            onehot = (bb[:, :, None] == iota).astype(jnp.int8)
            return jnp.einsum("frb,rc->fbc", onehot, gb,
                              preferred_element_type=jnp.int32)
        onehot = (bb[:, :, None] == iota).astype(jnp.float32)  # [F, rb, B]
        # HIGHEST keeps true-f32 accumulation on the MXU (the one-hot side is
        # exact in bf16 but gradients are not)
        return jnp.einsum("frb,rc->fbc", onehot, gb,
                          precision=lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)

    nb = R // block_rows
    main = nb * block_rows
    acc = jnp.zeros((F, num_bin, C), acc_dtype)
    if nb > 0:
        bins_blk = bins_t[:, :main].reshape(F, nb, block_rows).transpose(1, 0, 2)
        gh_blk = gh[:main].reshape(nb, block_rows, C)

        def body(a, inp):
            bb, gb = inp                              # [F, rb], [rb, C]
            return a + block_hist(bb, gb), None

        acc, _ = lax.scan(body, acc, (bins_blk, gh_blk))
    if main < R:  # ragged tail block
        acc = acc + block_hist(bins_t[:, main:], gh[main:])
    return acc


def hist_rowmajor(bins_rm: jnp.ndarray, gh: jnp.ndarray, num_bin: int,
                  block_rows: int = 4096, dtype: str = "float32",
                  backend: str = "einsum", live=None,
                  count_in_bf16: bool = False) -> jnp.ndarray:
    """Histogram over a ROW-MAJOR [S, F] bin block (the gathered-leaf layout
    of the compact scheduler — rows of one leaf gathered contiguously, so a
    leaf histogram costs O(rows_in_leaf) like the reference's
    DataPartition-indexed construction, serial_tree_learner.cpp:368-386).

    dtype: "float32" keeps exact f32 MXU accumulation (HIGHEST);
    "bfloat16" rounds gh to bf16 (one-hot side is exact either way) with
    f32 accumulation — the single-precision-style fast path, mirroring the
    reference GPU backend's float histograms (doc: GPU-Performance.rst).
    backend: "einsum" (one-hot matmul, the TPU path) or "scatter"
    (true scatter-add, the natural CPU kernel).
    live: ``(lo, hi)``, the rows outside which the caller zeroed ``gh``;
    the Pallas kernel skips the row blocks outside them
    (``hist_pallas._hist_call``), the other backends read every row.
    count_in_bf16: ``gh``'s last column is 0 or 1 (any value bfloat16 holds
    exactly); the Pallas kernel then leaves its mid and lo parts out of the
    float32 triple (``hist_pallas._hist_call``), the other backends do
    nothing with it.
    Returns f32 [F, num_bin, C].
    """
    S, F = bins_rm.shape
    C = gh.shape[1]
    iota = jnp.arange(num_bin, dtype=jnp.int32)
    bf16 = dtype in ("bfloat16", "bf16")
    int8_mode = gh.dtype == jnp.int8
    acc_dtype = jnp.int32 if int8_mode else jnp.float32

    def block_hist(bb, gb):
        if int8_mode:
            onehot = (bb[:, :, None] == iota).astype(jnp.int8)
            return jnp.einsum("rfb,rc->fbc", onehot, gb,
                              preferred_element_type=jnp.int32)
        if bf16:
            onehot = (bb[:, :, None] == iota).astype(jnp.bfloat16)
            gb = gb.astype(jnp.bfloat16)
            return jnp.einsum("rfb,rc->fbc", onehot, gb,
                              preferred_element_type=jnp.float32)
        onehot = (bb[:, :, None] == iota).astype(jnp.float32)
        return jnp.einsum("rfb,rc->fbc", onehot, gb,
                          precision=lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)

    if backend == "scatter":
        # CPU-friendly path (tests); XLA fuses the transpose into the gather
        return hist_scatter(bins_rm.T, gh, num_bin)
    if backend == "pallas":
        # VMEM-resident one-hot kernel (no HBM traffic for the expansion)
        from .hist_pallas import hist_pallas_rm
        if bf16 and not int8_mode:
            # native bf16 kernel path: gh rounded to bf16, one-hot exact,
            # f32 accumulation (f32 inputs take the exact bf16-triple
            # decomposition inside the kernel instead)
            gh = gh.astype(jnp.bfloat16)
        return hist_pallas_rm(bins_rm, gh, num_bin, block_rows=block_rows,
                              live=live, count_in_bf16=count_in_bf16)
    if backend != "einsum":
        raise ValueError(f"unknown hist_rowmajor backend {backend!r}; "
                         "expected einsum | scatter | pallas")

    nb = S // block_rows
    main = nb * block_rows
    acc = jnp.zeros((F, num_bin, C), acc_dtype)
    if nb > 0:
        bins_blk = bins_rm[:main].reshape(nb, block_rows, F)
        gh_blk = gh[:main].reshape(nb, block_rows, C)

        def body(a, inp):
            bb, gb = inp
            return a + block_hist(bb, gb), None

        acc, _ = lax.scan(body, acc, (bins_blk, gh_blk))
    if main < S:
        acc = acc + block_hist(bins_rm[main:], gh[main:])
    return acc


def hist_scatter(bins_t: jnp.ndarray, gh: jnp.ndarray,
                 num_bin: int) -> jnp.ndarray:
    """Histogram via scatter-add. Fastest on CPU backend (tests), slow on TPU."""
    F, R = bins_t.shape
    C = gh.shape[1]
    acc_dtype = jnp.int32 if gh.dtype == jnp.int8 else jnp.float32
    out = jnp.zeros((F, num_bin, C), acc_dtype)
    f_idx = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32)[:, None], (F, R))
    b_idx = bins_t.astype(jnp.int32)
    gh = gh.astype(acc_dtype)
    vals = jnp.broadcast_to(gh.T[None, :, :], (F, C, R)).transpose(0, 2, 1)
    return out.at[f_idx.reshape(-1), b_idx.reshape(-1)].add(
        vals.reshape(F * R, C))


def make_hist_fn(backend: str, num_bin: int, block_rows: int = 4096):
    """Select histogram implementation by backend name."""
    if backend == "scatter":
        return functools.partial(hist_scatter, num_bin=num_bin)
    if backend == "xla":
        return functools.partial(hist_xla, num_bin=num_bin,
                                 block_rows=block_rows)
    if backend == "multival":
        from .hist_multival import hist_multival
        return functools.partial(hist_multival, num_bin=num_bin)
    raise ValueError(f"unknown histogram backend {backend}")
