"""On-demand build + ctypes bindings for the native runtime kernels.

The parsing hot path (CSV/TSV/LibSVM byte scanning) runs as C++
(parser.cpp) compiled once per source state into
``_build/lgbm_native.<hash of the sources>.so``, with
``_build/lgbm_native.so`` kept as a link to the current one for C
programs that link against it; every entry point has a pure-numpy
fallback so the package works without a compiler
(``LIGHTGBM_TPU_NO_NATIVE=1`` forces the fallback).
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

from ..utils import log

_HERE = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_HERE, "_build")
_SO_PATH = os.path.join(_BUILD_DIR, "lgbm_native.so")
_SRCS = [os.path.join(_HERE, "parser.cpp"),
         os.path.join(_HERE, "c_api.cpp"),
         os.path.join(_HERE, "c_api_train.cpp"),
         os.path.join(_HERE, "shap.cpp"),
         os.path.join(_HERE, "arrow_ingest.cpp")]

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def artifact_path() -> str:
    """The built library for the sources as they are NOW, named by a
    hash of their contents. An mtime comparison is not enough: a copied
    checkout (archive, container snapshot) can reset mtimes, and a stale
    ``.so`` would then shadow edited C++."""
    h = hashlib.sha256()
    for src in _SRCS:
        with open(src, "rb") as f:
            h.update(f.read())
        h.update(b"\0")        # file boundary is part of the key
    return os.path.join(_BUILD_DIR,
                        f"lgbm_native.{h.hexdigest()[:16]}.so")


def _build() -> Optional[str]:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    so = artifact_path()
    tmp = f"{so}.{os.getpid()}.tmp"     # concurrent builders never share
    try:
        if not os.path.exists(so):
            cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                   "-pthread", *_SRCS, "-ldl", "-o", tmp]
            subprocess.run(cmd, check=True, capture_output=True,
                           timeout=120)
            os.replace(tmp, so)
            for stale in glob.glob(os.path.join(_BUILD_DIR,
                                                "lgbm_native.*.so")):
                if stale != so:
                    os.unlink(stale)
        if not (os.path.islink(_SO_PATH) and
                os.readlink(_SO_PATH) == os.path.basename(so)):
            os.symlink(os.path.basename(so), tmp)
            os.replace(tmp, _SO_PATH)
        return so
    except (OSError, subprocess.SubprocessError) as e:
        log.debug(f"native build failed ({e}); using numpy fallbacks")
        return None


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, or None (fallback mode)."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("LIGHTGBM_TPU_NO_NATIVE"):
            return None
        # conlint: disable=CL002 — deliberate: double-checked one-time
        # build; holding _lock across the g++ run is the point (every
        # other thread needs the built .so before it can do anything)
        so = _build()
        if so is None:
            return None
        lib = ctypes.CDLL(so)
        lib.lgbm_count_cols.restype = ctypes.c_int64
        lib.lgbm_count_cols.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_char]
        lib.lgbm_parse_dense.restype = ctypes.c_int64
        lib.lgbm_parse_dense.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_char, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_double), ctypes.c_int64]
        lib.lgbm_parse_libsvm.restype = ctypes.c_int64
        lib.lgbm_parse_libsvm.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_double), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_double), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int32)]
        lib.lgbm_tree_shap_batch.restype = ctypes.c_int
        lib.lgbm_tree_shap_batch.argtypes = [
            ctypes.POINTER(ctypes.c_int32),   # split_feature
            ctypes.POINTER(ctypes.c_double),  # threshold_real
            ctypes.POINTER(ctypes.c_int32),   # decision_type
            ctypes.POINTER(ctypes.c_int32),   # left_child
            ctypes.POINTER(ctypes.c_int32),   # right_child
            ctypes.POINTER(ctypes.c_double),  # leaf_value
            ctypes.POINTER(ctypes.c_double),  # leaf_count
            ctypes.POINTER(ctypes.c_double),  # internal_count
            ctypes.c_int32,                   # n_int
            ctypes.POINTER(ctypes.c_int32),   # cat_boundaries
            ctypes.POINTER(ctypes.c_uint32),  # cat_threshold
            ctypes.c_int32,                   # num_cat
            ctypes.c_int32,                   # n_cat_words
            ctypes.POINTER(ctypes.c_double),  # X
            ctypes.c_int64,                   # nrow
            ctypes.c_int32,                   # ncol
            ctypes.POINTER(ctypes.c_double),  # out
            ctypes.c_int64,                   # out_stride
            ctypes.c_int32]                   # nthreads
        _lib = lib
        return _lib


def _count_rows(chunk: bytes) -> int:
    return sum(1 for ln in chunk.split(b"\n") if ln.strip())


def parse_dense_chunk(chunk: bytes, sep: str, n_cols: int) -> np.ndarray:
    """Parse a newline-aligned CSV/TSV byte chunk -> float64 [rows, n_cols]."""
    lib = get_lib()
    if lib is not None:
        max_rows = chunk.count(b"\n") + 1
        out = np.empty((max_rows, n_cols), np.float64)
        buf = chunk + b"\0"
        n = lib.lgbm_parse_dense(
            buf, len(chunk), sep.encode()[0], n_cols,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), max_rows)
        return out[:n]
    # numpy fallback
    rows = [ln for ln in chunk.decode("utf-8", "replace").split("\n")
            if ln.strip()]
    out = np.full((len(rows), n_cols), np.nan)
    for i, ln in enumerate(rows):
        for j, tok in enumerate(ln.split(sep)[:n_cols]):
            tok = tok.strip()
            if tok == "" or tok.lower() in ("na", "nan", "null", "?"):
                continue
            try:
                out[i, j] = float(tok)
            except ValueError:
                pass
    return out


def parse_libsvm_chunk(chunk: bytes) -> Tuple[np.ndarray, np.ndarray,
                                              np.ndarray, np.ndarray, int]:
    """Parse a LibSVM byte chunk -> (labels, rows, cols, vals, max_col)."""
    lib = get_lib()
    if lib is not None:
        max_rows = chunk.count(b"\n") + 1
        max_nnz = max(chunk.count(b":"), 1)
        labels = np.empty(max_rows, np.float64)
        rows = np.empty(max_nnz, np.int32)
        cols = np.empty(max_nnz, np.int32)
        vals = np.empty(max_nnz, np.float64)
        nnz = ctypes.c_int64()
        max_col = ctypes.c_int32()
        buf = chunk + b"\0"
        n = lib.lgbm_parse_libsvm(
            buf, len(chunk),
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), max_rows,
            rows.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            cols.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            vals.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), max_nnz,
            ctypes.byref(nnz), ctypes.byref(max_col))
        k = nnz.value
        return labels[:n], rows[:k], cols[:k], vals[:k], int(max_col.value)
    # numpy fallback
    lines = [ln for ln in chunk.decode("utf-8", "replace").split("\n")
             if ln.strip()]
    labels = np.zeros(len(lines))
    r_l, c_l, v_l = [], [], []
    max_col = -1
    for i, ln in enumerate(lines):
        toks = ln.split()
        if toks:
            try:
                labels[i] = float(toks[0])
            except ValueError:
                labels[i] = np.nan
        for t in toks[1:]:
            if ":" not in t:
                continue
            k, _, v = t.partition(":")
            try:
                idx = int(k)
                val = float(v)
            except ValueError:
                continue
            r_l.append(i)
            c_l.append(idx)
            v_l.append(val)
            max_col = max(max_col, idx)
    return (labels, np.asarray(r_l, np.int32), np.asarray(c_l, np.int32),
            np.asarray(v_l, np.float64), max_col)


def iter_file_chunks(path: str, skip_lines: int = 0,
                     chunk_bytes: int = 32 << 20):
    """Yield newline-aligned byte chunks of a text file."""
    with open(path, "rb") as f:
        for _ in range(skip_lines):
            f.readline()
        carry = b""
        while True:
            block = f.read(chunk_bytes)
            if not block:
                if carry.strip():
                    yield carry
                return
            block = carry + block
            cut = block.rfind(b"\n")
            if cut < 0:
                carry = block
                continue
            yield block[:cut + 1]
            carry = block[cut + 1:]
