"""Serving-throughput benchmark: native C predict vs the Python paths.

The reference serves predictions through an OMP row-parallel C++ loop
(ref: src/application/predictor.hpp:31); our serving surface is
native/c_api.cpp's interpreter-free model parser + ParallelRows thread
pool, plus the packed-forest device route (ops/forest.py). This script
times the paths on the same model/data and writes
bench_logs/SERVING.json under scripts/_bench_io.py's status grammar
("measured" / "device_unreachable" / "no_result" — the session driver
keys on it):

- native C ABI  (LGBM_BoosterPredictForMat via ctypes, f32 rows)
- Python API    (Booster.predict host walk — the API default)
- device route  (Booster.predict(device=True) -> packed-forest engine)

An already-set JAX_PLATFORMS is honored (ISSUE 8 satellite): inside a
TPU session the device route measures the real accelerator; only an
unset environment pins CPU so a bare local run stays deterministic.

Shapes follow the reference's serving sweet spot: a 100-tree, 31-leaf
binary model over [N, 28] dense f32. Run with N=1000000 for the
headline number (verdict item: single-digit-% gap or better at 1M).

Usage: python scripts/bench_serving.py [nrows] [ntrees]
"""
from __future__ import annotations

import ctypes
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
OUT = os.path.join(REPO, "bench_logs", "SERVING.json")


def run(n: int, n_trees: int) -> dict:
    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu.native import get_lib

    rng = np.random.default_rng(0)
    Xtr = rng.normal(size=(100_000, 28)).astype(np.float32)
    ytr = (Xtr[:, 0] + 0.5 * Xtr[:, 1] ** 2 > 0.5).astype(np.float32)
    t0 = time.perf_counter()
    bst = lgb.train({"objective": "binary", "num_leaves": 31,
                     "verbosity": -1}, lgb.Dataset(Xtr, label=ytr),
                    num_boost_round=n_trees)
    model_file = os.path.join(REPO, "bench_logs", "serving_model.txt")
    bst.save_model(model_file)
    print(f"[serve] trained {n_trees} trees "
          f"({time.perf_counter() - t0:.1f}s)", flush=True)

    X = rng.normal(size=(n, 28)).astype(np.float32)

    # ---- native C path (interpreter-free parser + ParallelRows) ----
    lib = get_lib()
    assert lib is not None, "native library unavailable"
    handle = ctypes.c_void_p()
    n_iters = ctypes.c_int()
    rc = lib.LGBM_BoosterCreateFromModelfile(
        model_file.encode(), ctypes.byref(n_iters), ctypes.byref(handle))
    assert rc == 0
    out = np.empty(n, np.float64)
    out_len = ctypes.c_int64()

    def run_native() -> float:
        t = time.perf_counter()
        r = lib.LGBM_BoosterPredictForMat(
            handle, X.ctypes.data_as(ctypes.c_void_p), 0,
            ctypes.c_int32(n), ctypes.c_int32(28), 1, 0, 0, -1, b"",
            ctypes.byref(out_len), out.ctypes.data_as(
                ctypes.POINTER(ctypes.c_double)))
        assert r == 0
        return time.perf_counter() - t

    run_native()                       # warm (page-in)
    native_dt = min(run_native() for _ in range(3))
    native_rps = n / native_dt

    # ---- python path (host walk, the API default) ----
    # jaxlint: disable=JL005 — both predict routes return a
    # host-materialized np.ndarray (predict_device ends in np.asarray),
    # a real barrier: the timing measures execution, not dispatch
    t = time.perf_counter()
    py_pred = bst.predict(X)
    py_dt = time.perf_counter() - t
    py_rps = n / py_dt

    # ---- device route (packed-forest engine; real accelerator when
    # JAX_PLATFORMS points at one). Warm at the FULL request shape:
    # N rows land in a different bucket_rows shape than a small
    # warm-up batch, and the large-batch compile must not sit inside
    # the timed region the native route measures min-of-3 against ----
    bst.predict(X, device=True)                  # compile + pack warm-up
    t = time.perf_counter()
    dev_pred = bst.predict(X, device=True)
    dev_dt = time.perf_counter() - t
    dev_rps = n / dev_dt

    # agreement guard: all paths must produce the same scores
    np.testing.assert_allclose(out, py_pred, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(out, dev_pred, rtol=1e-5, atol=1e-6)

    nthreads = os.cpu_count()
    return {
        "rows": n, "trees": n_trees, "host_threads": nthreads,
        "backend": jax.default_backend(),
        "native_rows_per_sec": round(native_rps),
        "native_sec": round(native_dt, 3),
        "python_rows_per_sec": round(py_rps),
        "python_sec": round(py_dt, 3),
        "device_rows_per_sec": round(dev_rps),
        "device_sec": round(dev_dt, 3),
        # ref CPU-16 Higgs predict is not directly comparable from this
        # 1-core host; record the per-thread figure for scaling math
        "native_rows_per_sec_per_thread": round(native_rps / nthreads),
        # this writer has no ModelServer (direct predict routes only),
        # so it can never end on the host fallback; the field exists so
        # every SERVING*.json carries the same ISSUE 9 status schema
        "degraded": False,
        "status": "measured",
    }


def main() -> int:
    from _bench_io import classify_status, write_record
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    n_trees = int(sys.argv[2]) if len(sys.argv) > 2 else 100
    base = {"rows": n, "trees": n_trees}
    try:
        write_record(OUT, run(n, n_trees))
        return 0
    except Exception as e:  # noqa: BLE001 — classified into the grammar
        write_record(OUT, dict(base, status=classify_status(e),
                               note=repr(e)))
        return 1


if __name__ == "__main__":
    sys.exit(main())
