"""In-memory serving head-to-head: the reference's lib_lightgbm.so vs
our native/c_api.cpp, both via ctypes LGBM_BoosterPredictForMat on the
SAME model file and the SAME [N, 28] f32 matrix, single thread
(ref: src/application/predictor.hpp:31 — the reference serves via an
OMP row-parallel loop; ours via native/c_api.cpp ParallelRows).

Writes bench_logs/SERVING_AB.json under scripts/_bench_io.py's status grammar
("measured" / "no_result" — the session driver keys on it; ISSUE 8
satellite). A run that cannot measure (reference build absent on this
host) keeps the last measured record under "previous" instead of
silently discarding it.

Measured 2026-08-01 on this host (1 core): ours 124k rows/s vs
reference 103k rows/s (+21%), max |pred diff| = 0.0
(bench_logs/SERVING_AB.json).

Building the reference library here (vendored submodules are absent in
the read-only mount, cmake is older than its minimum; nothing is
written into /root/reference):

  1. shim headers in /tmp/lgb_shim: fast_double_parser.h (strtod),
     fmt/format.h (snprintf for the three format strings common.h
     uses), Eigen/Dense (MatrixXd + Gauss-Jordan fullPivLu().inverse(),
     linear-tree solve only), nanoarrow/nanoarrow.hpp (schema-view +
     Unique wrappers; Arrow paths are never exercised).
  2. g++ -O2 -std=c++17 -fopenmp -pthread -shared -fPIC
       -I/root/reference/include -I/tmp/lgb_shim
       -DUSE_SOCKET -DMM_PREFETCH -DMM_MALLOC
       /root/reference/src/{application,boosting,io,metric,network,
       objective,treelearner,utils}/*.cpp /root/reference/src/c_api.cpp
       -o /tmp/lgb_bin/lib_lightgbm.so
"""
import ctypes
import os
import sys
import time

import numpy as np

N = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
MODEL = os.path.join(REPO, "bench_logs", "serving_model.txt")
OUT = os.path.join(REPO, "bench_logs", "SERVING_AB.json")
REF_LIB = "/tmp/lgb_bin/lib_lightgbm.so"
OUR_LIB = os.path.join(REPO, "lightgbm_tpu", "native", "_build",
                       "lgbm_native.so")

C_API_DTYPE_FLOAT32 = 0
C_API_PREDICT_NORMAL = 0


def bench(libpath, label, extra_param, X):
    lib = ctypes.CDLL(libpath)
    h = ctypes.c_void_p()
    out_iter = ctypes.c_int(0)
    rc = lib.LGBM_BoosterCreateFromModelfile(
        MODEL.encode(), ctypes.byref(out_iter), ctypes.byref(h))
    assert rc == 0, f"{label}: load failed"
    out_len = ctypes.c_int64(0)
    preds = np.zeros(N, dtype=np.float64)
    args = (h, X.ctypes.data_as(ctypes.c_void_p),
            ctypes.c_int(C_API_DTYPE_FLOAT32),
            ctypes.c_int32(N), ctypes.c_int32(28), ctypes.c_int(1),
            ctypes.c_int(C_API_PREDICT_NORMAL), ctypes.c_int(0),
            ctypes.c_int(-1), extra_param.encode(),
            ctypes.byref(out_len),
            preds.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    lib.LGBM_BoosterPredictForMat(*args)          # warmup
    t0 = time.perf_counter()
    rc = lib.LGBM_BoosterPredictForMat(*args)
    dt = time.perf_counter() - t0
    assert rc == 0 and out_len.value == N, f"{label}: predict failed"
    print(f"{label}: {dt:.3f}s  {N / dt / 1e3:.0f}k rows/s "
          f"(pred[0]={preds[0]:.6f} mean={preds.mean():.6f})")
    return preds, dt


def main() -> int:
    from _bench_io import read_previous_measured, write_record
    missing = [p for p in (REF_LIB, OUR_LIB, MODEL)
               if not os.path.exists(p)]
    if missing:
        rec = {"status": "no_result",
               "note": f"cannot measure: missing {missing} (build recipe "
                       "in the script docstring)"}
        # keep the last real measurement through ANY number of
        # consecutive failure runs
        previous = read_previous_measured(OUT)
        if previous is not None:
            rec["previous"] = previous
        write_record(OUT, rec)
        return 1
    try:
        rng = np.random.default_rng(0)
        X = np.ascontiguousarray(
            rng.normal(size=(N, 28)).astype(np.float32))
        p_ref, ref_dt = bench(REF_LIB, "reference (1 thread)",
                              "num_threads=1", X)
        p_ours, our_dt = bench(OUR_LIB, "ours (1 thread)",
                               "num_threads=1", X)
        err = float(np.max(np.abs(p_ref - p_ours)))
    except Exception as e:  # noqa: BLE001 — a mid-measure failure must
        # not leave the previous run's "measured" record in place for
        # the driver to read as a fresh success
        rec = {"status": "no_result", "note": repr(e)}
        previous = read_previous_measured(OUT)
        if previous is not None:
            rec["previous"] = previous
        write_record(OUT, rec)
        return 1
    print(f"max |pred diff| = {err:.3e}")
    write_record(OUT, {
        "benchmark": "in-memory LGBM_BoosterPredictForMat head-to-head, "
                     f"same model ({os.path.relpath(MODEL, REPO)}), same "
                     f"[{N}, 28] f32 matrix, num_threads=1",
        "reference_rows_per_sec": round(N / ref_dt),
        "reference_sec": round(ref_dt, 3),
        "ours_rows_per_sec": round(N / our_dt),
        "ours_sec": round(our_dt, 3),
        "speedup": round(ref_dt / our_dt, 2),
        "max_abs_pred_diff": err,
        # pure-ctypes head-to-head — no ModelServer, so no host
        # fallback; field present for the shared SERVING*.json schema
        "degraded": False,
        "status": "measured",
    })
    return 0


if __name__ == "__main__":
    sys.exit(main())
