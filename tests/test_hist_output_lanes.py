"""The histogram kernel's output gives each column the lanes its channels
use (``ops/hist_pallas._operand_shape``): 64 for float32 ``gh`` with the
grower's 0/1 count (seven channels over eight high parts), 32 for three
channels of bfloat16 or int8 ``gh``, 128 for the whole triple's nine. Two or
four columns share a lane tile, and a ragged last tile leaves its empty
slots zero.

Every entry against the host's sums, on the Pallas interpreter: int8 to the
last integer, float32 and bfloat16 within ``tests/test_hist_pallas.py``'s
bounds against float64 sums of what the kernel was given.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import pack_words
from lightgbm_tpu.ops import hist_pallas
from lightgbm_tpu.ops.hist_pallas import (hist_pallas_rm, hist_pallas_words,
                                          output_lanes)

# (gh kind, the lanes a column has at 255 bins)
GH = {"float32": 128, "float32-count": 64, "bfloat16": 32, "int8": 32}

# (columns, rows, num_bin, live rows): a last tile of 3 columns of 32 (67, a
# word tile) or 5 of 8 (37), `bosch`'s 968, `epsilon`'s 2,000 cut in rows;
# a live range that is empty, inside one block, or mid-bucket over blocks
SHAPES = {
    "67-255-every": (67, 1300, 255, None),
    "37-63-one_block": (37, 1500, 63, (600, 900)),
    "37-16-mid_bucket": (37, 1500, 16, (300, 1400)),
    "968-255-empty": (968, 300, 255, (128, 128)),
    "2000-255-mid_bucket": (2000, 260, 255, (5, 250)),
}


def _gh(rng, S, kind):
    if kind == "int8":
        return rng.integers(-8, 8, size=(S, 3)).astype(np.int8)
    gh = rng.normal(size=(S, 3)).astype(np.float32)
    if kind == "float32-count":
        gh[:, 2] = rng.integers(0, 2, size=S)
    return gh


def _inputs(rng, F, S, B, live):
    rm = rng.integers(0, B, size=(S, F)).astype(np.uint8)
    rm[::7, F - 1] = B - 1                  # the last column, the last bin
    inside = np.ones(S, bool) if live is None else \
        (np.arange(S) >= live[0]) & (np.arange(S) < live[1])
    return rm, inside


def _host_sums(rm, gh, B):
    """float64 (int64 for int8) [F, B, C]: ``gh``'s rows summed by bin."""
    out = np.zeros((rm.shape[1], B, gh.shape[1]))
    for f in range(rm.shape[1]):
        for c in range(gh.shape[1]):
            out[f, :, c] = np.bincount(rm[:, f], weights=gh[:, c], minlength=B)
    return out.astype(np.int64) if gh.dtype == np.int8 else out


def _call(entry, rm, gh, B, kind, live):
    """The entry's histogram and the shape of its ``pallas_call``'s
    output."""
    F = rm.shape[1]
    gh = jnp.asarray(gh).astype(jnp.bfloat16 if kind == "bfloat16" else
                                jnp.asarray(gh).dtype)
    kw = dict(live=None if live is None else
              (jnp.int32(live[0]), jnp.int32(live[1])))
    if kind == "float32-count":
        kw["count_in_bf16"] = True
    if entry == "words":
        fn = lambda g: hist_pallas_words(jnp.asarray(pack_words(rm).T), g, B,
                                         F, block_rows=256, **kw)
    elif entry == "rm":
        fn = lambda g: hist_pallas_rm(jnp.asarray(rm), g, B, block_rows=256,
                                      **kw)
    else:
        # feature-major, as the level growers call it (no count_in_bf16)
        kw.pop("count_in_bf16", None)
        fn = lambda g: hist_pallas.hist_pallas(jnp.asarray(rm.T), g, B,
                                               block_rows=256, **kw)
    return np.asarray(fn(gh)), _pallas_out(jax.make_jaxpr(fn)(gh).jaxpr)


def _pallas_out(jaxpr):
    """The output shape of the one ``pallas_call`` in ``jaxpr``."""
    found = []

    def walk(j):
        for e in j.eqns:
            if e.primitive.name == "pallas_call":
                found.append(e.outvars[0].aval.shape)
            for p in e.params.values():
                for inner in p if isinstance(p, tuple) else (p,):
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        walk(inner)
    walk(jaxpr)
    assert len(found) == 1, found
    return found[0]


# every gh kind of each entry on the narrow shapes; the wide ones through
# the packed words alone, the entry the grower runs on them (the unpacked
# entries read 8 columns a tile, and the level growers' feature-major one
# takes no count_in_bf16)
CASES = [(e, k, s) for s in SHAPES for e in ("words", "rm", "fm")
         for k in GH if (e == "words" or SHAPES[s][0] < 100) and
         (e != "fm" or k != "float32-count")]


@pytest.mark.parametrize("entry,kind,shape", CASES,
                         ids=["-".join(c) for c in CASES])
def test_each_entry_equals_the_host_sums(rng, entry, kind, shape):
    F, S, B, live = SHAPES[shape]
    rm, inside = _inputs(rng, F, S, B, live)
    gh = _gh(rng, S, kind) * inside[:, None].astype(np.int8)
    got, out_shape = _call(entry, rm, gh, B, kind, live)
    want = _host_sums(rm, gh.astype(np.float32).astype(
        jnp.bfloat16).astype(np.float64) if kind == "bfloat16" else gh, B)
    assert got.shape == (F, B, 3)
    if kind == "int8":
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    # the output holds each column's lanes, not 128 a column
    lanes = output_lanes(B, kind.startswith("float32"),
                         kind == "float32-count")
    tile = 32 if entry == "words" else 8
    assert np.prod(out_shape) == 32 * -(-F // tile) * tile * lanes
    assert got.any() == bool(inside.any())


@pytest.mark.parametrize("kind,lanes", list(GH.items()))
@pytest.mark.parametrize("B", [255, 63, 16])
def test_output_lanes_follow_the_channels(kind, lanes, B):
    """64 for seven channels, 32 for three, 128 for nine, at every bin
    count a byte holds (the high parts are one sublane tile from 1 to 256
    bins); the accumulator's sizing (``_resident``) stays nine channels'
    128 lanes, so every call keeps its feature tile and row block."""
    assert output_lanes(B, kind.startswith("float32"),
                        kind == "float32-count") == lanes
    assert hist_pallas._operand_shape(B, hist_pallas._MAX_CHANNELS) == (8, 128)


@pytest.mark.parametrize("channels,lanes", [(1, 16), (2, 16), (3, 32),
                                            (4, 32), (7, 64), (8, 64),
                                            (9, 128), (16, 128), (17, 256)])
def test_lanes_are_a_power_of_two_under_a_lane_tile(channels, lanes):
    """A column's lanes divide 128 where its channels take 64 or fewer, so
    whole columns share a lane tile, and are at least 16, so that 8
    columns, the least feature tile, fill whole ones; above 64 whole lane
    tiles."""
    assert hist_pallas._operand_shape(255, channels) == (8, lanes)


@pytest.mark.parametrize("kind", ["float32-count", "int8"])
def test_a_ragged_last_tile_leaves_its_empty_slots_zero(rng, monkeypatch,
                                                        kind):
    """67 columns are three word tiles of 32, the last holding 3 columns:
    the kernel's raw output is zero in every slot past the last column,
    and every column before it has something."""
    F, S, B = 67, 600, 255
    rm = rng.integers(0, B, size=(S, F)).astype(np.uint8)
    gh = jnp.asarray(_gh(rng, S, kind))
    # the raw output, [lane tiles, lo, 128]: ``_unpack`` left out (its
    # caller's slice keeps every lane tile and every sublane here), traced
    # afresh and the jit caches dropped after, so nothing else sees it
    monkeypatch.setattr(hist_pallas, "_unpack", lambda out, *a: out)
    jax.clear_caches()
    try:
        out = np.asarray(hist_pallas_words(
            jnp.asarray(pack_words(rm).T), gh, B, F, block_rows=256,
            count_in_bf16=kind != "int8"))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    lanes = output_lanes(B, kind != "int8", True)
    P = 128 // lanes
    # lane tile t of word tile i holds column 32 i + 32 s / P + t in slot s
    cols = out.reshape(3, 32 // P, 32, P, lanes).transpose(0, 3, 1, 4, 2)
    cols = cols.reshape(96, lanes, 32)
    assert cols[:F].any(axis=(1, 2)).all()
    assert not cols[F:].any()


# ---- compiled for a v5e that is described, not attached ---------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("gh_dtype,result", [("float32", "f32[1008,32,128]"),
                                             ("int8", "s32[504,32,128]")])
def test_compiled_the_output_is_half_or_a_quarter_and_never_sliced_whole(
        one_chip, monkeypatch, gh_dtype, result):
    """At `epsilon`'s 2,000 columns the kernel writes 1,008 or 504 lane
    tiles, not 2,016 columns of 128 lanes, and the module holds no
    ``[32, 2016, 56]`` (or ``24``) slice of it: Mosaic compiles the kernel
    as on the chip."""
    monkeypatch.setattr(hist_pallas, "default_interpret", lambda: False)
    cached = jax.config.jax_enable_compilation_cache
    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        words = jax.ShapeDtypeStruct((500, 2048), jnp.uint32,
                                     sharding=one_chip)
        gh = jax.ShapeDtypeStruct((2048, 3), jnp.dtype(gh_dtype),
                                  sharding=one_chip)
        text = jax.jit(lambda w, g: hist_pallas_words(
            w, g, 255, 2000, block_rows=512,
            count_in_bf16=gh_dtype == "float32")).lower(
                words, gh).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln and
             " = " in ln]
    assert len(calls) == 1 and f"= {result}" in calls[0], calls
    assert "[32,2016,56]" not in text and "[32,2016,24]" not in text
