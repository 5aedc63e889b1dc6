"""Pallas histogram kernel parity vs the XLA one-hot path
(ref: the reference's CPU-vs-GPU histogram parity gates, tests/cpp_tests/
test_dual.py — same triangle, here XLA-vs-Pallas on identical inputs).

On the CPU test mesh the kernel runs under the Pallas interpreter; the
kernel body (and therefore the arithmetic) is identical to compiled TPU
mode.
"""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import pack_words
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


# (columns, rows, num_bin, block_rows): 67 columns are 17 words, the last
# part-filled, in three tiles of 8 words; 64 fill 16 words, two whole
# tiles; 5 and 12 are one tile of fewer than 8 words; 3000 and 700 rows
# end inside a row block, 256 rows are less than one
WORD_CASES = [(67, 3000, 255, 512), (64, 1024, 255, 512),
              (5, 700, 64, 256), (12, 2048, 64, 1024), (40, 256, 255, 512)]


@pytest.mark.parametrize("gh_dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("F,S,B,block_rows", WORD_CASES)
def test_hist_pallas_words_equals_unpacked_bit_for_bit(rng, F, S, B,
                                                       block_rows, gh_dtype):
    """The packed entry reads the words the table stores and takes each
    byte out in the kernel: same row blocks, same order of accumulation,
    so the same bits as ``hist_pallas_rm`` on the unpacked rows."""
    from lightgbm_tpu.ops.hist_pallas import hist_pallas_rm, hist_pallas_words

    rm = rng.integers(0, B, size=(S, F)).astype(np.uint8)
    # every fourth column is a word's top byte: bins of 255 there set the
    # word's sign bit, which the arithmetic shift drags down
    rm[::3, 3::4] = B - 1
    rm[::5, F - 1] = B - 1
    if gh_dtype == "int8":
        gh = jnp.asarray(rng.integers(-8, 8, size=(S, 3)).astype(np.int8))
    else:
        gh = jnp.asarray(rng.normal(size=(S, 3)).astype(np.float32)
                         ).astype(gh_dtype)
    ref = hist_pallas_rm(jnp.asarray(rm.astype(np.int32)), gh, B,
                         block_rows=block_rows)
    out = hist_pallas_words(jnp.asarray(pack_words(rm).T), gh, B, F,
                            block_rows=block_rows)
    assert out.shape == (F, B, 3) and out.dtype == ref.dtype
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert np.asarray(out)[F - 1, B - 1].any()


def test_hist_pallas_words_rows_give_way_to_the_vmem_budget(monkeypatch):
    """The word tile's 32 columns are fixed, so a row block too large for
    the budget beside them is halved until it fits: 4096 rows run as
    2048, and what ``tpu_rows_per_block`` gives by default stays."""
    from lightgbm_tpu.ops import hist_pallas
    assert hist_pallas._resident(32, 4096, 256) > \
        hist_pallas._VMEM_BUDGET_ELEMS >= hist_pallas._resident(32, 2048, 256)
    ran = []
    monkeypatch.setattr(hist_pallas, "_hist_pallas_words",
                        lambda w, gh, B, F, block_rows, interp, live, c7:
                        ran.append(block_rows))
    words = jnp.zeros((3, 300), jnp.uint32)
    gh = jnp.zeros((300, 3), jnp.float32)
    for asked in (4096, 3072, 2048, 1024, 100):
        hist_pallas.hist_pallas_words(words, gh, 255, 9, block_rows=asked)
    assert ran == [2048, 1536, 2048, 1024, 128]


def test_hist_pallas_words_dtype_matches_rowmajor(rng):
    """``hist_pallas_words`` is ``hist_rowmajor(backend="pallas")`` on the
    same rows, for the dtypes the grower hands it."""
    from lightgbm_tpu.ops.hist_pallas import hist_pallas_words
    from lightgbm_tpu.ops.histogram import hist_rowmajor
    F, S, B = 10, 900, 64
    rm = rng.integers(0, B, size=(S, F)).astype(np.uint8)
    gh = jnp.asarray(rng.normal(size=(S, 3)).astype(np.float32))
    words = jnp.asarray(pack_words(rm).T)
    for dtype in ("float32", "bfloat16"):
        np.testing.assert_array_equal(
            np.asarray(hist_pallas_words(words, gh, B, F, block_rows=512,
                                         dtype=dtype)),
            np.asarray(hist_rowmajor(jnp.asarray(rm.astype(np.int32)), gh,
                                     B, block_rows=512, dtype=dtype,
                                     backend="pallas")))


# ---- a live row range: a leaf's segment inside its bucket ------------------

# 3000 rows in blocks of 512: five whole blocks and one of 440 rows
LIVE_RANGES = {
    "inside_one_block": (600, 900),
    "whole_blocks": (512, 2048),
    "starts_and_ends_inside_blocks": (700, 2300),
    "into_the_short_last_block": (2500, 3000),
    "first_row_only": (0, 1),
    "empty": (1024, 1024),
    "empty_inside_a_block": (1000, 1000),
    "empty_at_the_end": (3000, 3000),
    "whole": (0, 3000),
}


def _live_case(rng, gh_dtype, lo, hi, S=3000, F=37, B=255):
    rm = rng.integers(0, B, size=(S, F)).astype(np.uint8)
    inside = (np.arange(S) >= lo) & (np.arange(S) < hi)
    if gh_dtype == "int8":
        gh = rng.integers(-8, 8, size=(S, 3)).astype(np.int8)
    else:
        gh = rng.normal(size=(S, 3)).astype(np.float32)
    gh = jnp.asarray(gh * inside[:, None].astype(gh.dtype)).astype(gh_dtype)
    return rm, gh, inside


@pytest.mark.parametrize("gh_dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("name", list(LIVE_RANGES))
def test_hist_pallas_words_live_range_bit_for_bit(rng, name, gh_dtype):
    """Told which rows carry weight, the kernel leaves out the row blocks
    outside them, which would have added exact zeros: the same bits as the
    call over every block, from both entries the grower uses."""
    from lightgbm_tpu.ops.hist_pallas import hist_pallas_rm, hist_pallas_words

    lo, hi = LIVE_RANGES[name]
    rm, gh, inside = _live_case(rng, gh_dtype, lo, hi)
    F, B = rm.shape[1], 255
    words = jnp.asarray(pack_words(rm).T)
    live = (jnp.int32(lo), jnp.int32(hi))
    every = hist_pallas_words(words, gh, B, F, block_rows=512)
    out = hist_pallas_words(words, gh, B, F, block_rows=512, live=live)
    assert out.dtype == every.dtype
    np.testing.assert_array_equal(np.asarray(out), np.asarray(every))
    np.testing.assert_array_equal(
        np.asarray(hist_pallas_rm(jnp.asarray(rm.astype(np.int32)), gh, B,
                                  block_rows=512, live=live)),
        np.asarray(every))
    assert np.asarray(out).any() == bool(inside.any())


@pytest.mark.parametrize("name", list(LIVE_RANGES))
def test_hist_pallas_words_dead_blocks_are_not_read(rng, name):
    """The proof that a dead block is skipped and not added as zeros: NaN
    in ``gh`` everywhere outside the live blocks leaves the histogram
    finite and equal."""
    from lightgbm_tpu.ops.hist_pallas import hist_pallas_words, live_row_blocks

    lo, hi = LIVE_RANGES[name]
    rm, gh, _ = _live_case(rng, "float32", lo, hi)
    F, B, S = rm.shape[1], 255, rm.shape[0]
    first, n = (int(x) for x in live_row_blocks((lo, hi), S, 512))
    assert n == -(-hi // 512) - lo // 512
    block = np.arange(S) // 512
    dead = (block < first) | (block >= first + n)
    assert dead.any() or name == "whole"
    words = jnp.asarray(pack_words(rm).T)
    want = hist_pallas_words(words, gh, B, F, block_rows=512)
    poisoned = jnp.where(dead[:, None], jnp.nan, gh)
    out = np.asarray(hist_pallas_words(
        words, poisoned, B, F, block_rows=512,
        live=(jnp.int32(lo), jnp.int32(hi))))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, np.asarray(want))
    if dead.any():
        assert np.isnan(np.asarray(hist_pallas_words(
            words, poisoned, B, F, block_rows=512))).any()


# ---- the factorised body against exact host sums ---------------------------
# A bin is 32 * hi + lo (``hist_pallas._LO``): the kernel contracts a one-hot
# of ``lo`` against ``gh`` masked by ``hi``. ``gh`` here is made of small
# dyadic values, so
# every partial sum is exact in f32 and the order of accumulation cannot
# show: the kernel must equal the host's f64 sums to the last bit.

def _exact_gh(rng, S, gh_dtype, count01=False):
    if gh_dtype == "int8":
        gh = rng.integers(-8, 8, size=(S, 3)).astype(np.int8)
    else:
        # k / 4, |k| <= 32: exact in bfloat16, and so are their f32 sums
        gh = (rng.integers(-32, 33, size=(S, 3)) / 4.0).astype(np.float32)
    if count01:
        gh[:, 2] = rng.integers(0, 2, size=S)
    return gh


def _host_sums(rm, gh, B):
    out = np.zeros((rm.shape[1], B, gh.shape[1]))
    for f in range(rm.shape[1]):
        for c in range(gh.shape[1]):
            out[f, :, c] = np.bincount(rm[:, f], weights=gh[:, c].astype(
                np.float64), minlength=B)[:B]
    return out


def _words_hist(rm, gh, B, gh_dtype, **kw):
    from lightgbm_tpu.ops.hist_pallas import hist_pallas_words
    return np.asarray(hist_pallas_words(
        jnp.asarray(pack_words(rm).T), jnp.asarray(gh).astype(gh_dtype), B,
        rm.shape[1], block_rows=512, **kw))


@pytest.mark.parametrize("gh_dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("B", [255, 63])
def test_factorised_body_equals_exact_host_sums(rng, B, gh_dtype):
    """Both entries, 255 bins (8 high parts) and 63 (8 sublanes of high
    parts, two in use), a last row block of 476 rows, a last tile of 5
    columns."""
    from lightgbm_tpu.ops.hist_pallas import hist_pallas_rm
    S, F = 1500, 37
    rm = rng.integers(0, B, size=(S, F)).astype(np.uint8)
    gh = _exact_gh(rng, S, gh_dtype)
    want = _host_sums(rm, gh, B)
    out = _words_hist(rm, gh, B, gh_dtype)
    assert out.shape == (F, B, 3)
    assert out.dtype == (np.int32 if gh_dtype == "int8" else np.float32)
    np.testing.assert_array_equal(out.astype(np.float64), want)
    np.testing.assert_array_equal(np.asarray(hist_pallas_rm(
        jnp.asarray(rm.astype(np.int32)), jnp.asarray(gh).astype(gh_dtype),
        B, block_rows=512)).astype(np.float64), want)


# the bins either side of a change of the high part, whether the byte is cut
# 4 + 4 or 5 + 3 (``_LO`` 16 or 32), the first and the last
NIBBLE_EDGES = [0, 15, 16, 17, 31, 32, 33, 127, 128, 223, 224, 239, 240, 254,
                255]


@pytest.mark.parametrize("gh_dtype", ["float32", "bfloat16", "int8"])
def test_factorised_body_on_the_nibbles_edges(rng, gh_dtype):
    """Every row's byte is one of the bins where the low part wraps and
    the high part steps; 256 bins, so that byte 255 is a bin. No other bin
    gets anything."""
    S, F, B = 1100, 9, 256
    rm = rng.choice(np.asarray(NIBBLE_EDGES, np.uint8), size=(S, F))
    gh = _exact_gh(rng, S, gh_dtype)
    out = _words_hist(rm, gh, B, gh_dtype).astype(np.float64)
    np.testing.assert_array_equal(out, _host_sums(rm, gh, B))
    others = np.setdiff1d(np.arange(B), NIBBLE_EDGES)
    assert not out[:, others].any() and out[:, NIBBLE_EDGES].any()


@pytest.mark.parametrize("gh_dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("B", [251, 255, 256, 63])
def test_factorised_body_every_byte_the_last_bin(rng, B, gh_dtype):
    """`bosch.train`'s commonest block: every cell the NaN bin, the table's
    last (250 of 251 there). The whole of ``gh`` lands in one bin of every
    column."""
    S, F = 1024, 12
    rm = np.full((S, F), B - 1, np.uint8)
    rm[:, 5] = rng.integers(0, B, size=S)       # one column that is not
    gh = _exact_gh(rng, S, gh_dtype)
    out = _words_hist(rm, gh, B, gh_dtype).astype(np.float64)
    np.testing.assert_array_equal(out, _host_sums(rm, gh, B))
    total = gh.astype(np.float64).sum(axis=0)
    for f in (0, 4, 6, 11):
        np.testing.assert_array_equal(out[f, B - 1], total)
        assert not out[f, :B - 1].any()


@pytest.mark.parametrize("entry", ["words", "rm"])
@pytest.mark.parametrize("B", [255, 63])
def test_count_in_bf16_leaves_out_two_channels_bit_for_bit(rng, B, entry):
    """The grower's third column is 0/1, so its mid and lo parts in the
    bf16 triple are zero: told so, the kernel contracts seven channels and
    not nine, and every sum is the sum it was. Normal ``g`` and ``h``, whose
    three parts are all in use."""
    from lightgbm_tpu.ops.hist_pallas import hist_pallas_rm, hist_pallas_words
    S, F = 1500, 37
    rm = rng.integers(0, B, size=(S, F)).astype(np.uint8)
    gh = rng.normal(size=(S, 3)).astype(np.float32)
    gh[:, 2] = rng.integers(0, 2, size=S)
    gh[:, :2] *= gh[:, 2:]                    # as the grower masks a leaf
    if entry == "words":
        fn = functools.partial(hist_pallas_words, jnp.asarray(
            pack_words(rm).T), jnp.asarray(gh), B, F, block_rows=512)
    else:
        fn = functools.partial(hist_pallas_rm, jnp.asarray(
            rm.astype(np.int32)), jnp.asarray(gh), B, block_rows=512)
    nine, seven = np.asarray(fn()), np.asarray(fn(count_in_bf16=True))
    assert seven.shape == nine.shape == (F, B, 3) and nine.any()
    np.testing.assert_array_equal(seven, nine)
    np.testing.assert_array_equal(
        seven[:, :, 2].astype(np.float64),
        _host_sums(rm, gh[:, 2:].astype(np.float64), B)[:, :, 0])
