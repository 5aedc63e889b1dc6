"""tpu_packed_bins: bit-packed (4 uint8/uint32) compact-scheduler bins
must reproduce the unpacked path's models exactly — the packing only
changes how the per-leaf row gather reads memory (grower.py: unpack_rows
for the einsum/scatter kernels, the words themselves for the Pallas one).
"""
import pytest
import numpy as np

import lightgbm_tpu as lgb


def _data(n=3000, f=10, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] +
         0.1 * rng.normal(size=n) > 0).astype(np.float32)
    return X, y


def _efb_data():
    rng = np.random.default_rng(3)
    n = 2000
    cat = rng.integers(0, 6, size=n)
    X = np.zeros((n, 12), np.float32)
    X[np.arange(n), cat] = 1.0           # 6 mutually-exclusive one-hots
    X[:, 6:] = rng.normal(size=(n, 6)).astype(np.float32)
    return X, (cat % 2 == 0).astype(np.float32)


def _trees_only(model_str: str) -> str:
    """Model text from the first Tree= up to the trailing parameters
    echo (which legitimately differs by tpu_packed_bins itself)."""
    s = model_str[model_str.index("Tree=0"):]
    cut = s.find("\nparameters:")
    return s if cut < 0 else s[:cut]


def _models(params, n_round=15):
    X, y = _data()
    out = {}
    for mode in ("false", "true"):
        b = lgb.train(dict(params, tpu_packed_bins=mode, verbose=-1),
                      lgb.Dataset(X, label=y), num_boost_round=n_round)
        out[mode] = b
    return X, out


def test_packed_matches_unpacked_plain():
    X, out = _models(dict(objective="binary", num_leaves=15))
    assert (_trees_only(out["true"].model_to_string()) ==
            _trees_only(out["false"].model_to_string()))


@pytest.mark.slow
def test_packed_matches_unpacked_odd_features():
    # 10 features -> W=3 words with 2 dead pad bytes exercised
    X, out = _models(dict(objective="binary", num_leaves=7,
                          min_data_in_leaf=5))
    np.testing.assert_array_equal(out["true"].predict(X),
                                  out["false"].predict(X))


@pytest.mark.slow
def test_packed_with_efb_bundling():
    X, y = _efb_data()
    out = {}
    for mode in ("false", "true"):
        b = lgb.train(dict(objective="binary", num_leaves=7, verbose=-1,
                           enable_bundle=True, tpu_packed_bins=mode),
                      lgb.Dataset(X, label=y), num_boost_round=8)
        out[mode] = _trees_only(b.model_to_string())
    assert out["true"] == out["false"]


@pytest.mark.slow
def test_packed_quantized():
    X, out = _models(dict(objective="binary", num_leaves=15,
                          use_quantized_grad=True,
                          stochastic_rounding=False))
    assert (_trees_only(out["true"].model_to_string()) ==
            _trees_only(out["false"].model_to_string()))


@pytest.mark.parametrize("extra", [{}, {"enable_bundle": True},
                                   {"use_quantized_grad": True,
                                    "stochastic_rounding": False}],
                         ids=["plain", "efb", "int8"])
def test_packed_matches_unpacked_pallas_kernel(extra):
    """tpu_hist_kernel=pallas (the chip's kernel, interpreted here): packed
    words reach the kernel as the table stores them, and the models are the
    unpacked path's to the last digit — 10 columns are 3 words, the last
    part-filled; with EFB the words hold physical columns."""
    X, y = _efb_data() if "enable_bundle" in extra else _data()
    out = {}
    for mode in ("false", "true"):
        b = lgb.train(dict(objective="binary", num_leaves=7, verbose=-1,
                           min_data_in_leaf=5, tpu_hist_kernel="pallas",
                           tpu_row_scheduling="compact",
                           tpu_packed_bins=mode, **extra),
                      lgb.Dataset(X, label=y), num_boost_round=3)
        cfg = b._engine.grower_cfg
        assert cfg.hist_rm_backend == "pallas" and cfg.row_sched == "compact"
        assert (cfg.packed_cols > 0) == (mode == "true")
        out[mode] = _trees_only(b.model_to_string())
    assert out["true"] == out["false"]
