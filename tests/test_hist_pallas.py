"""Pallas histogram kernel parity vs the XLA one-hot path
(ref: the reference's CPU-vs-GPU histogram parity gates, tests/cpp_tests/
test_dual.py — same triangle, here XLA-vs-Pallas on identical inputs).

On the CPU test mesh the kernel runs under the Pallas interpreter; the
kernel body (and therefore the arithmetic) is identical to compiled TPU
mode.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops.hist_pallas import hist_pallas
from lightgbm_tpu.ops.histogram import hist_scatter, hist_xla


@pytest.mark.parametrize("F,R,B", [(8, 4096, 64), (11, 3000, 63),
                                   (3, 500, 256)])
def test_hist_pallas_matches_xla(rng, F, R, B):
    bins = rng.integers(0, B, size=(F, R)).astype(
        np.uint8 if B <= 256 else np.uint16)
    gh = rng.normal(size=(R, 3)).astype(np.float32)
    ref = np.asarray(hist_xla(jnp.asarray(bins), jnp.asarray(gh), B))
    out = np.asarray(hist_pallas(jnp.asarray(bins), jnp.asarray(gh), B,
                                 block_rows=512, feature_tile=4))
    assert out.shape == (F, B, 3)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)


def test_hist_pallas_masked_rows_invisible(rng):
    """Rows with gh == 0 (leaf mask / padding) contribute nothing."""
    F, R, B = 4, 1024, 32
    bins = rng.integers(0, B, size=(F, R)).astype(np.uint8)
    gh = rng.normal(size=(R, 3)).astype(np.float32)
    mask = (rng.uniform(size=R) < 0.5).astype(np.float32)
    gh_masked = gh * mask[:, None]
    out = np.asarray(hist_pallas(jnp.asarray(bins), jnp.asarray(gh_masked),
                                 B, block_rows=256, feature_tile=4))
    ref = np.asarray(hist_scatter(jnp.asarray(bins[:, mask > 0]),
                                  jnp.asarray(gh[mask > 0]), B))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)


def test_hist_pallas_rm_matches_rowmajor(rng):
    """Row-major kernel (compact scheduler layout) vs the einsum path."""
    from lightgbm_tpu.ops.hist_pallas import hist_pallas_rm
    from lightgbm_tpu.ops.histogram import hist_rowmajor

    S, F, B = 1000, 11, 64           # ragged row/feature tiles
    bins = rng.integers(0, B, size=(S, F)).astype(np.uint8)
    gh = rng.normal(size=(S, 3)).astype(np.float32)
    ref = np.asarray(hist_rowmajor(jnp.asarray(bins), jnp.asarray(gh),
                                   num_bin=B, backend="scatter"))
    out = np.asarray(hist_pallas_rm(jnp.asarray(bins), jnp.asarray(gh), B,
                                    block_rows=256, feature_tile=4))
    assert out.shape == (F, B, 3)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)


def test_hist_rowmajor_pallas_backend(rng):
    """hist_rowmajor(backend='pallas') dispatch path."""
    from lightgbm_tpu.ops.histogram import hist_rowmajor

    S, F, B = 512, 6, 32
    bins = rng.integers(0, B, size=(S, F)).astype(np.uint8)
    gh = rng.normal(size=(S, 3)).astype(np.float32)
    ref = np.asarray(hist_rowmajor(jnp.asarray(bins), jnp.asarray(gh),
                                   num_bin=B, backend="scatter"))
    out = np.asarray(hist_rowmajor(jnp.asarray(bins), jnp.asarray(gh),
                                   num_bin=B, backend="pallas"))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)


def test_hist_pallas_rm_int8_exact(rng):
    """Quantized path: int8 contraction accumulates exactly in int32."""
    from lightgbm_tpu.ops.histogram import hist_rowmajor

    S, F, B = 700, 5, 64
    bins = rng.integers(0, B, size=(S, F)).astype(np.uint8)
    ghq = rng.integers(-8, 8, size=(S, 3)).astype(np.int8)
    ref = np.asarray(hist_rowmajor(jnp.asarray(bins), jnp.asarray(ghq),
                                   num_bin=B, backend="einsum"))
    out = np.asarray(hist_rowmajor(jnp.asarray(bins), jnp.asarray(ghq),
                                   num_bin=B, backend="pallas"))
    assert out.dtype == np.int32 and ref.dtype == np.int32
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("backend,interpret", [("cpu", True),
                                               ("tpu", False),
                                               ("gpu", False)])
def test_interpret_only_on_cpu(monkeypatch, backend, interpret):
    """The interpreter is chosen on the cpu backend alone; any other
    platform compiles the kernel (or raises) — an accelerator must never
    run interpreted."""
    import jax

    from lightgbm_tpu.ops import hist_pallas as hp
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert hp.default_interpret() is interpret


def test_bf16_triple_reconstructs_f32(rng):
    """hi + mid + lo recovers the f32 input (the rounding that must
    survive XLA's excess-precision folding on TPU)."""
    from lightgbm_tpu.ops.hist_pallas import bf16_triple
    g = (rng.normal(size=(513, 3)) * 10.0 ** rng.integers(
        -6, 3, size=(513, 3))).astype(np.float32)
    t = np.asarray(bf16_triple(jnp.asarray(g)).astype(jnp.float32),
                   np.float64)
    assert np.abs(t[:, 3:6]).max() > 0 and np.abs(t[:, 6:9]).max() > 0
    np.testing.assert_allclose(t[:, 0:3] + t[:, 3:6] + t[:, 6:9], g,
                               rtol=2.0 ** -22, atol=0)


def test_infeasible_tiles_fall_back_loudly(rng):
    """A tile that cannot fit VMEM (huge num_bin) hands the histogram to
    the einsum kernel — once, with the shape, never silently."""
    from lightgbm_tpu.ops.hist_pallas import fit_tiles, hist_pallas_rm
    from lightgbm_tpu.ops.histogram import hist_rowmajor
    from lightgbm_tpu.utils import log

    S, F, B = 96, 2, 8192
    assert not fit_tiles(8, B, 512)[2]
    bins = rng.integers(0, B, size=(S, F)).astype(np.uint16)
    gh = rng.normal(size=(S, 3)).astype(np.float32)
    seen = []
    log.logged_once.clear()
    level = log._level          # earlier tests train with verbose=-1
    log.set_verbosity(log.INFO)
    log.register_logger(seen.append)
    try:
        out = hist_pallas_rm(jnp.asarray(bins), jnp.asarray(gh), B)
        hist_pallas_rm(jnp.asarray(bins), jnp.asarray(gh), B)
    finally:
        log.register_logger(None)
        log.set_verbosity(level)
    notes = [m for m in seen if "tiles infeasible" in m]
    assert len(notes) == 1, seen
    assert f"({S}, {F})" in notes[0] and f"num_bin={B}" in notes[0]
    ref = hist_rowmajor(jnp.asarray(bins), jnp.asarray(gh), B,
                        block_rows=512, backend="einsum")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
