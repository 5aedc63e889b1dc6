"""Standing proof that the main path starts and runs on the chip.

One process drives train -> kernels -> scan sums -> predict -> explain ->
serve -> DART (async against sync and the plain reference's walk) at the
full width of the model the repo benches (synthetic Higgs shape: 28
features, max_bin=255, num_leaves=255, binary) through the entry points a
user calls (``lgb.train`` / ``Booster.update`` / ``Booster.predict`` /
``Booster.serve``), checks every result against the repo's own host
references, and prints a ``[smoke] summary: {...}`` line (every phase's
report, ending ``"claim": null``) and then, as the last line of its
standard output, exactly:

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

with the device as JAX reports it. It is a smoke, not a benchmark: the
seconds it prints say the program started and finished, nothing about
speed (``"claim": null``).

No accelerator -> non-zero exit before any work, and no result line.
``--rehearse-cpu`` is the explicit tiny-size CPU rehearsal (Pallas in
interpret mode) for debugging the script itself before spending chip time;
it prints ``platform=cpu`` and can never print the pass line.

Every phase prints its seconds; a phase that fails raises and the process
exits non-zero. Nothing here downgrades a failure to a note.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import importlib.metadata
import json
import sys
import threading
import time
import traceback

import numpy as np

N_FEATURES = 28
PARAMS = {
    "objective": "binary",
    "num_leaves": 255,
    "learning_rate": 0.1,
    "max_bin": 255,
    "min_data_in_leaf": 20,
    "verbose": -1,
}
STEADY_ITERS = 5
DART_ITERS = 24
SERVE_CLIENTS = 4
SERVE_REQUEST_ROWS = (1, 32, 4096)
EXPLAIN_ROWS = 32
# a Mosaic kernel lowers to this custom-call target; the interpreter does not
MOSAIC_CALL = "tpu_custom_call"


@dataclasses.dataclass(frozen=True)
class Sizes:
    rows: int            # training rows (per chip in the data-parallel leg)
    level_nodes: int     # nodes of the level-kernel shape (depth 10 = 1024)
    predict_rows: int
    parity_rows: int
    contrib_rows: int
    quant_rows: int      # rows of the quantized serial-vs-data identity check
    scan_cols: int       # columns of the split scan's sums check


# width is never cut; rows stay >= core/plan.MEASURED_FROM_ROWS so the chip
# run resolves the kernels a large table takes
CHIP = Sizes(rows=1_000_000, level_nodes=1024, predict_rows=100_000,
             parity_rows=4096, contrib_rows=1024, quant_rows=262_144,
             scan_cols=2000)
REHEARSAL = Sizes(rows=32768, level_nodes=16, predict_rows=2048,
                  parity_rows=512, contrib_rows=64, quant_rows=4096,
                  scan_cols=16)

_PHASES: dict = {}
_FAILED: list = []


@contextlib.contextmanager
def phase(name: str):
    """Time one phase. A failure is printed in full and the run goes on —
    the later phases say more about the chip in the same call — but the
    phase is recorded as failed: ``main`` then exits non-zero and prints
    no result line."""
    print(f"[smoke] {name} ...", flush=True)
    t0 = time.perf_counter()
    try:
        yield
    except Exception:  # noqa: BLE001 — reported, and settled in main()
        traceback.print_exc()
        _FAILED.append(name)
        print(f"[smoke] {name} FAILED {time.perf_counter() - t0:.2f}s",
              flush=True)
    else:
        dt = time.perf_counter() - t0
        _PHASES[name] = round(dt, 2)
        print(f"[smoke] {name} ok {dt:.2f}s", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke: {what}")


def _version(dist: str) -> str:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def synth_higgs(n, f, seed=0):
    """A Higgs-shaped table: ``f`` standard-normal columns, a label cut at
    the median of a logit of the first four."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    logits = (X[:, 0] - 0.5 * X[:, 1] * X[:, 2] + 0.25 * X[:, 3] ** 2
              + 0.1 * rng.normal(size=n))
    y = (logits > np.median(logits)).astype(np.float32)
    return X, y


def _logloss(bst) -> float:
    (_, name, value, _), = [r for r in bst.eval_train()
                            if r[1] == "binary_logloss"]
    return float(value)


def _peak_bytes(devs) -> list:
    out = []
    for d in devs:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def _resolved(eng) -> dict:
    g = eng.grower_cfg
    return {"row_sched": g.row_sched, "hist_rm_backend": g.hist_rm_backend,
            "async": bool(eng._async_on()),
            "packed_cols": int(eng._packed_cols),
            "partition_mode": g.partition_mode,
            "tree_learner": eng._tree_learner}


def _train(X, y, extra: dict):
    """First iteration through ``lgb.train`` (compile + step), then
    STEADY_ITERS ``Booster.update`` calls ended by block_until_ready on
    the score. Returns (booster, report)."""
    import jax
    import jax.numpy as jnp

    import lightgbm_tpu as lgb
    params = dict(PARAMS, **extra)
    t0 = time.perf_counter()
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    bin_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    bst = lgb.train(params, ds, num_boost_round=1,
                    keep_training_booster=True)
    eng = bst._engine
    jax.block_until_ready(eng.score)
    first_s = time.perf_counter() - t0
    ll_first = _logloss(bst)
    float(jnp.sum(eng.score))      # compile the fetch used as a probe below

    t0 = time.perf_counter()
    for _ in range(STEADY_ITERS):
        bst.update()
    t_dispatch = time.perf_counter()
    jax.block_until_ready(eng.score)
    t_block = time.perf_counter()
    # were block_until_ready a no-op, this scalar fetch would absorb the
    # device time of the whole loop
    float(jnp.sum(eng.score))
    t_fetch = time.perf_counter()
    steady_s = t_block - t0
    ll_last = _logloss(bst)

    prior = float(np.mean(y))
    ll_prior = float(-(prior * np.log(prior) +
                       (1 - prior) * np.log(1 - prior)))
    report = {
        "rows": int(X.shape[0]), "bin_s": round(bin_s, 2),
        "first_iter_compile_plus_step_s": round(first_s, 2),
        "steady_s_per_iter": round(steady_s / STEADY_ITERS, 4),
        "dispatch_s": round(t_dispatch - t0, 3),
        "block_s": round(t_block - t_dispatch, 3),
        "fetch_after_block_s": round(t_fetch - t_block, 4),
        "logloss_prior": round(ll_prior, 5),
        "logloss_iter1": round(ll_first, 5),
        f"logloss_iter{1 + STEADY_ITERS}": round(ll_last, 5),
        "resolved": _resolved(eng),
    }
    print(f"[smoke]   {json.dumps(report)}", flush=True)
    check(np.isfinite([ll_first, ll_last]).all(), "training logloss not finite")
    check(ll_last < ll_first < ll_prior,
          f"training logloss did not fall: prior {ll_prior:.5f} -> "
          f"{ll_first:.5f} -> {ll_last:.5f}")
    check(bst.current_iteration() == 1 + STEADY_ITERS,
          f"trained {bst.current_iteration()} iterations, expected "
          f"{1 + STEADY_ITERS}")
    check(t_fetch - t_block < 0.2 * steady_s + 0.05,
          "jax.block_until_ready returned before the device finished "
          f"(a scalar fetch after it still took {t_fetch - t_block:.3f}s "
          f"of a {steady_s:.3f}s loop)")
    return bst, report


def _host_hist(keys: np.ndarray, vals: np.ndarray, size: int) -> np.ndarray:
    """Exact reference: f64 sums of ``vals`` [R, C] by integer key per
    column of ``keys`` [R, F] -> [F, size, C] (numpy, on the host)."""
    vals = np.asarray(vals, np.float64)
    out = np.empty((keys.shape[1], size, vals.shape[1]))
    for f in range(keys.shape[1]):
        for c in range(vals.shape[1]):
            out[f, :, c] = np.bincount(keys[:, f], weights=vals[:, c],
                                       minlength=size)
    return out


def _hist_err(got, ref, ref_abs) -> dict:
    """Error of a histogram against the exact host sums, in units of the
    f32 accumulation bound: summing n terms in f32 errs by a small
    multiple of eps * sum|x| (``ref_abs``). Integer histograms have
    ``ref_abs`` None and must be exact."""
    got = np.asarray(got, np.float64)
    if got.shape != ref.shape or not np.isfinite(got).all():
        return {"shape": list(got.shape), "finite": False}
    err = np.abs(got - ref)
    if ref_abs is None:
        return {"max_err": float(err.max()), "exact": bool((err == 0).all())}
    return {"max_err": float(err.max()),
            "err_over_eps_sum_abs": float((err / (ref_abs + 1e-3)).max()
                                          / np.finfo(np.float32).eps)}


# accepted kernel error in eps * sum|x| units. Measured at 1M x 28 on a
# v5e against the exact sums: the bf16-triple kernels 0.8-1.0, a kernel
# that silently kept only bf16 (the astype round trip XLA elides) ~1,000.
# The scatter formulations are run beside the kernels and reported, not
# judged: XLA:TPU's f32 scatter-add measured 240 here (a serial sum's
# worst case is rows_per_bin / 2 ~ 2,000), so it cannot referee them.
F32_ACC_BOUND = 50.0


def _kernels(eng, sizes: Sizes, on_chip: bool) -> dict:
    """hist_pallas_rm (f32 bf16-triple, bf16, int8) and hist_pallas_words
    (f32, int8: the packed words the training leg's grower hands it, 7
    words of 28 columns, so the word axis ends inside the kernel's 8-word
    tile; f32 again with a live row range, the dead row blocks poisoned)
    at the smoke's shape and hist_level (f32, int8) at the depth-10
    level shape: lowered for
    this backend, shown to hold a Mosaic call, run, and compared with
    exact host sums (the scatter formulations run beside them for the
    record). Every variant is reported before a failure is raised."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.core.level_grower import hist_level_scatter
    from lightgbm_tpu.ops.hist_level_pallas import hist_level
    from lightgbm_tpu.ops.hist_pallas import hist_pallas_rm, hist_pallas_words
    from lightgbm_tpu.ops.histogram import hist_scatter

    B = int(eng.grower_cfg.num_bin)
    block_rows = int(eng.grower_cfg.block_rows)
    bins_host = np.ascontiguousarray(eng.train_set.bins.T)   # [R, F] u8
    bins_rm = jnp.asarray(bins_host)
    bins_fm = jnp.asarray(eng.train_set.bins)                # [F, R] u8
    R, F = bins_host.shape
    grad, hess = eng._gh_fn(eng.score)
    gh = jnp.stack([grad.reshape(-1), hess.reshape(-1),
                    jnp.ones(R, jnp.float32)], axis=1)       # [R, 3] f32
    gh_bf16 = gh.astype(jnp.bfloat16)
    gh_i8 = jnp.concatenate(
        [jnp.clip(jnp.round(gh[:, :2] * 63.0), -127, 127),
         jnp.ones((R, 1), jnp.float32)], axis=1).astype(jnp.int8)
    host = {"f32": np.asarray(gh), "int8": np.asarray(gh_i8),
            "bf16": np.asarray(gh_bf16.astype(jnp.float32))}
    report, failed = {}, []

    def run(name, fn, args, ref, ref_abs, kernel=True, **extra):
        lowered = jax.jit(fn).lower(*args)
        if kernel and on_chip and MOSAIC_CALL not in lowered.as_text():
            failed.append(f"{name}: no {MOSAIC_CALL} in the lowered "
                          "program, the kernel was not handed to Mosaic")
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t1 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        t2 = time.perf_counter()
        # the price core/plan.py holds (KERNEL_NS_COLUMN_ROW) is this, read
        # on a second call of the loaded program: seconds over rows x columns
        jax.block_until_ready(compiled(*args))
        again = time.perf_counter() - t2
        rec = dict(_hist_err(out, ref, ref_abs), **extra,
                   compile_s=round(t1 - t0, 2), run_s=round(t2 - t1, 4),
                   ns_col_row=round(again / (R * F) * 1e9, 4))
        report[name] = rec
        print(f"[smoke]   {name}: {json.dumps(rec)}", flush=True)
        ok = rec.get("exact", False) if ref_abs is None else \
            rec.get("err_over_eps_sum_abs", np.inf) <= F32_ACC_BOUND
        if kernel and not ok:
            failed.append(name)

    refs = {k: _host_hist(bins_host, v, B) for k, v in host.items()}
    ref_abs = _host_hist(bins_host, np.abs(host["f32"]), B)
    rm = functools.partial(hist_pallas_rm, num_bin=B, block_rows=block_rows)
    scatter = functools.partial(hist_scatter, num_bin=B)
    run("hist_scatter/f32", scatter, (bins_fm, gh), refs["f32"], ref_abs,
        kernel=False)
    run("hist_pallas_rm/f32", rm, (bins_rm, gh), refs["f32"], ref_abs)
    run("hist_pallas_rm/bf16", rm, (bins_rm, gh_bf16), refs["bf16"], ref_abs)
    run("hist_pallas_rm/int8", rm, (bins_rm, gh_i8), refs["int8"], None)
    padded = np.zeros((R, -(-F // 4) * 4), np.uint8)
    padded[:, :F] = bins_host           # byte k of word w = column 4w + k
    words_cm = jnp.asarray(np.ascontiguousarray(padded.view(np.uint32).T))
    words = functools.partial(hist_pallas_words, num_bin=B, num_cols=F,
                              block_rows=block_rows)
    run("hist_pallas_words/f32", words, (words_cm, gh), refs["f32"], ref_abs)
    run("hist_pallas_words/int8", words, (words_cm, gh_i8), refs["int8"],
        None)
    # a leaf's segment inside its bucket: the range starts and ends inside
    # row blocks, ``gh`` is zero outside it as the grower hands it, and NaN
    # in the blocks the kernel must not read
    lo, hi = R // 3 + 77, R // 2 + 13
    at = np.arange(R)
    dead = (at // block_rows < lo // block_rows) | \
        (at // block_rows > (hi - 1) // block_rows)
    gh_seg = host["f32"] * ((at >= lo) & (at < hi))[:, None]
    run("hist_pallas_words/f32/live",
        lambda w, g, a, b: words(w, g, live=(a, b)),
        (words_cm, jnp.asarray(np.where(dead[:, None], np.nan, gh_seg)),
         jnp.int32(lo), jnp.int32(hi)),
        _host_hist(bins_host, gh_seg, B),
        _host_hist(bins_host, np.abs(gh_seg), B), live_rows=hi - lo)

    n_nodes = sizes.level_nodes
    rows = np.arange(R, dtype=np.uint32)
    local_h = ((rows * np.uint32(2654435761)) >> np.uint32(7)).astype(
        np.int64) % n_nodes
    in_lvl_h = (rows % 53) != 0         # some rows already left the level
    keys = local_h[:, None] * B + bins_host

    def level_ref(vals):
        h = _host_hist(keys, np.asarray(vals, np.float64) *
                       in_lvl_h[:, None], n_nodes * B)
        return h.reshape(-1, n_nodes, B, 3).transpose(1, 0, 2, 3)

    local = jnp.asarray(local_h.astype(np.int32))
    in_lvl = jnp.asarray(in_lvl_h)
    lsafe = jnp.where(in_lvl, local, 0)
    lvl_abs = level_ref(np.abs(host["f32"]))
    lvl = functools.partial(hist_level, n_nodes=n_nodes, num_bin=B)
    run("hist_level_scatter/f32", functools.partial(
        hist_level_scatter, n_d=n_nodes, num_bin=B, acc_dtype=jnp.float32),
        (bins_fm, gh, lsafe, in_lvl), level_ref(host["f32"]), lvl_abs,
        kernel=False, nodes=n_nodes)
    run("hist_level/f32", lvl, (bins_rm, gh, local, in_lvl),
        level_ref(host["f32"]), lvl_abs, nodes=n_nodes)
    run("hist_level/int8", lvl, (bins_rm, gh_i8, local, in_lvl),
        level_ref(host["int8"]), None, nodes=n_nodes)
    check(not failed, f"histogram kernels failed: {failed}")
    return report


def _scan_sums(sizes: Sizes) -> dict:
    """The split scan's suffix and prefix sums (``ops/split._cumsum_bins``,
    one contraction with a 0/1 matrix) on real-valued histograms of both
    children at the widest bench shape, 2,000 columns of 251 live bins,
    against float64 on the host, in the units of ``_hist_err``: a
    contraction that kept only bf16 of each bin reads in the thousands."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops.split import _cumsum_bins

    rng = np.random.default_rng(38)
    shape = (2, 3, sizes.scan_cols, 256)
    x = rng.normal(size=shape).astype(np.float32)
    x[:, 1] = np.abs(x[:, 1])                       # hessians
    x[:, 2] = rng.integers(0, 40, size=shape[0:1] + shape[2:])   # counts
    x[..., 251:] = 0
    report, failed = {}, []
    sums = jax.jit(_cumsum_bins, static_argnums=1)
    for reverse in (True, False):
        name = "suffix" if reverse else "prefix"
        got = sums(jnp.asarray(x), reverse)

        def acc(v):
            v = np.asarray(v, np.float64)
            return (np.cumsum(v[..., ::-1], axis=-1)[..., ::-1] if reverse
                    else np.cumsum(v, axis=-1))
        rec = _hist_err(got, acc(x), acc(np.abs(x)))
        report[name] = rec
        print(f"[smoke]   scan {name}: {json.dumps(rec)}", flush=True)
        if not rec.get("err_over_eps_sum_abs", np.inf) <= F32_ACC_BOUND:
            failed.append(name)
    check(not failed, f"split-scan sums not float32: {failed}")
    return report


def _predict(bst, X, sizes: Sizes) -> dict:
    eng = bst._engine
    t0 = time.perf_counter()
    dev = bst.predict(X[:sizes.predict_rows], device=True, raw_score=True)
    first_s = time.perf_counter() - t0
    # Booster.predict answers from the HOST walk (with a warning only) when
    # the serving engine refuses a shape; that must not pass as the device
    srv = eng._serving
    check(srv is not None and srv.pack.count == len(eng.models),
          "device predict did not serve (host fallback engaged)")
    check(dev.shape == (sizes.predict_rows,) and np.isfinite(dev).all(),
          f"device predict: bad output {dev.shape}")
    Xp = X[:sizes.parity_rows]
    host = bst.predict(Xp, raw_score=True)
    check(np.allclose(host, dev[:sizes.parity_rows], rtol=1e-5, atol=1e-6),
          "device/host predict parity broke: max |d| = "
          f"{np.abs(host - dev[:sizes.parity_rows]).max():.3e}")

    Xc = X[:sizes.contrib_rows]
    t0 = time.perf_counter()
    dev_c = bst.predict(Xc, pred_contrib=True, device=True)
    contrib_s = time.perf_counter() - t0
    srv = eng._serving
    check(srv is not None and srv.shap_pack is not None and
          srv.shap_pack.count == len(eng.models),
          "device explain did not serve (host fallback engaged)")
    host_c = bst.predict(Xc, pred_contrib=True)
    check(dev_c.shape == (sizes.contrib_rows, N_FEATURES + 1) and
          np.isfinite(dev_c).all(), f"device explain: bad output {dev_c.shape}")
    check(np.allclose(dev_c, host_c, rtol=1e-4, atol=1e-5),
          "device/host explain parity broke: max |d| = "
          f"{np.abs(dev_c - host_c).max():.3e}")
    check(np.allclose(dev_c.sum(axis=1), host[:sizes.contrib_rows],
                      rtol=1e-5, atol=1e-5),
          "explain additivity broke (contributions do not sum to the score)")
    report = {"predict_rows": sizes.predict_rows,
              "predict_first_call_s": round(first_s, 2),
              "contrib_rows": sizes.contrib_rows,
              "contrib_first_call_s": round(contrib_s, 2)}
    print(f"[smoke]   {json.dumps(report)}", flush=True)
    return report


def _serve(bst, X, n_devices: int) -> dict:
    """Four client threads (1 / 32 / 4,096-row requests and one explain
    each) against ``Booster.serve()`` while one more tree is trained and
    hot-swapped in; every response is compared with the host walk of the
    generation that served it."""
    import lightgbm_tpu as lgb
    refs = {}           # generation version -> frozen host reference

    def freeze(version: int) -> None:
        refs[version] = lgb.Booster(model_str=bst.model_to_string())

    failures: list = []
    answered: list = []
    swapped = threading.Event()

    def client(cid: int, srv) -> None:
        try:
            rng = np.random.default_rng(100 + cid)
            first, last = True, False
            # traffic stays up until the hot-swap landed; the round that
            # STARTS after it is the last, so the new generation answers
            while not last:
                last = swapped.is_set()
                for n in SERVE_REQUEST_ROWS:
                    off = int(rng.integers(0, X.shape[0] - n))
                    fut = srv.submit(X[off:off + n])
                    answered.append(("score", off, n, fut.result(120.0),
                                     fut.generation.version))
                if first:
                    off = int(rng.integers(0, X.shape[0] - EXPLAIN_ROWS))
                    fut = srv.submit(X[off:off + EXPLAIN_ROWS],
                                     kind="contrib")
                    answered.append(("contrib", off, EXPLAIN_ROWS,
                                     fut.result(300.0),
                                     fut.generation.version))
                    first = False
        except BaseException as e:  # noqa: BLE001 — re-raised by the caller
            failures.append(e)

    with bst.serve() as srv:
        freeze(srv.generation.version)
        threads = [threading.Thread(target=client, args=(i, srv),
                                    name=f"smoke-client-{i}")
                   for i in range(SERVE_CLIENTS)]
        for t in threads:
            t.start()
        bst.update()                         # train under traffic ...
        gen = srv.publish()                  # ... and hot-swap it in
        freeze(gen.version)
        swapped.set()
        for t in threads:
            t.join(600.0)
        check(not any(t.is_alive() for t in threads),
              "serving clients did not finish")
        stats = srv.stats()
    if failures:
        raise failures[0]

    gens = sorted({a[4] for a in answered})
    for kind, off, n, got, version in answered:
        ref = refs[version]
        Xq = X[off:off + n]
        if kind == "score":
            want = ref.predict(Xq)
            ok = got.shape == (n,) and np.allclose(got, want, rtol=1e-5,
                                                   atol=1e-6)
        else:
            want = ref.predict(Xq, pred_contrib=True)
            ok = got.shape == want.shape and np.allclose(
                got, want, rtol=1e-4, atol=1e-5)
        check(ok, f"served {kind} response ({n} rows, generation "
              f"{version}) disagrees with the host walk")
    report = {k: stats[k] for k in (
        "requests", "rows", "batches", "errors", "degraded",
        "degrade_events", "degraded_batches", "dispatch_failures",
        "dispatch_retries", "explain_degraded", "mesh_devices",
        "generation", "num_trees")}
    report["explain_requests"] = stats["explain"]["requests"]
    report["generations_answering"] = gens
    print(f"[smoke]   {json.dumps(report)}", flush=True)
    check(stats["degraded"] is False, "server ended degraded")
    for k in ("degrade_events", "degraded_batches", "dispatch_failures",
              "errors", "explain_degraded"):
        check(stats[k] == 0, f"serving counter {k} = {stats[k]} (the host "
              "answered for the device)")
    check(stats["mesh_devices"] == n_devices,
          f"serving mesh spans {stats['mesh_devices']} of {n_devices} chips")
    check(stats["explain"]["requests"] == SERVE_CLIENTS,
          "not every explain request was served")
    check(len(gens) == 2, f"only generation(s) {gens} answered — the "
          "hot-swapped model never served")
    return report


def _dart(X, y) -> dict:
    """``boosting=dart`` (drop settings raised so that most iterations
    drop) for DART_ITERS iterations with a fifth of four columns missing,
    on the asynchronous device path and on the synchronous one: the two
    paths' training scores agree, and both agree with the plain
    reference's float64 walk of the model (``benchmark/references/
    gbdt_dart.py``, on codes made from the model's own thresholds)."""
    import importlib.util
    import os

    import jax

    import lightgbm_tpu as lgb
    X = X.copy()
    X[:, :4][np.random.default_rng(1).random((len(X), 4)) < 0.2] = np.nan
    params = dict(PARAMS, boosting="dart", drop_rate=0.3, skip_drop=0.25)
    got = {}
    for mode in ("true", "false"):
        t0 = time.perf_counter()
        bst = lgb.Booster(dict(params, tpu_async_boosting=mode),
                          lgb.Dataset(X, label=y))
        dropped = 0
        for _ in range(DART_ITERS):
            bst.update()
            dropped += len(bst._engine.drop_index)
        jax.block_until_ready(bst._engine.score)
        got[mode] = {"async": bool(bst._engine._async_on()),
                     "seconds": round(time.perf_counter() - t0, 2),
                     "dropped": dropped,
                     "score": np.asarray(bst._engine.score[0], np.float64),
                     "model": bst.model_to_string()}
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmark", "references", "gbdt_dart.py")
    spec = importlib.util.spec_from_file_location("smoke_gbdt_dart", path)
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    # a column's codes: how many of the models' thresholds lie under a
    # value (the thresholds are each column's bin bounds, so a code decides
    # every split as the value does)
    cuts = [set() for _ in range(X.shape[1])]
    for g in got.values():
        feats = []
        for line in g["model"].splitlines():
            key, _, value = line.partition("=")
            if key == "split_feature":
                feats = [int(f) for f in value.split()]
            elif key == "threshold":
                for f, r in zip(feats, value.split()):
                    cuts[f].add(float(r))
    cuts = [np.asarray(sorted(c), np.float64) for c in cuts]
    check(max(len(c) for c in cuts) < ref.MISSING,
          "a column has too many thresholds for byte codes")
    codes = np.stack([np.searchsorted(c, X[:, f], side="left")
                      for f, c in enumerate(cuts)]).astype(np.uint8)
    codes[np.isnan(X.T)] = ref.MISSING
    walked = {m: ref.walk(ref.trees_from_text(got[m]["model"], cuts), codes)
              for m in got}
    report = {m: {k: v for k, v in g.items() if k in ("async", "seconds",
                                                       "dropped")}
              for m, g in got.items()}
    report["async_sync_max_gap"] = float(np.max(np.abs(
        got["true"]["score"] - got["false"]["score"])))
    report["walk_max_gap"] = max(float(np.max(np.abs(
        got[m]["score"] - walked[m]))) for m in got)
    print(f"[smoke]   {json.dumps(report)}", flush=True)
    check(got["true"]["async"] and not got["false"]["async"],
          f"DART resolved async {report}")
    check(got["true"]["dropped"] > DART_ITERS,
          f"DART dropped {got['true']['dropped']} trees in {DART_ITERS} "
          "iterations")
    check(report["async_sync_max_gap"] <= 1e-5,
          f"DART's async and sync scores part by "
          f"{report['async_sync_max_gap']}")
    check(report["walk_max_gap"] <= 1e-4,
          f"DART's scores part from the reference's walk of its model by "
          f"{report['walk_max_gap']}")
    return report


def _data_parallel(sizes: Sizes, devs) -> dict:
    """More than one chip: the train leg with tree_learner=data over all
    of them (rows per chip as in the one-chip leg), placement checked
    shard by shard; then quantized serial == quantized data-parallel."""
    import lightgbm_tpu as lgb
    n = len(devs)
    X, y = synth_higgs(sizes.rows * n, N_FEATURES, seed=1)
    bst, report = _train(X, y, {"tree_learner": "data"})
    eng = bst._engine
    check(eng._tree_learner == "data",
          f"tree_learner=data resolved to {eng._tree_learner!r}")
    shards = eng.bins_sharded.addressable_shards
    placed = sorted(s.device.id for s in shards)
    check(placed == sorted(d.id for d in devs),
          f"row shards sit on devices {placed}, expected one on each of "
          f"{sorted(d.id for d in devs)}")
    check(len({s.data.shape for s in shards}) == 1 and
          shards[0].data.shape[0] * n == eng.bins_sharded.shape[0],
          "row shards are not an even split of the table")
    report["shard_devices"] = placed
    report["hist_reduce"] = eng._hist_reduce

    # exact int32 histogram sums are order-independent, so with
    # deterministic rounding the sharded trees must equal the serial
    # ones bit for bit (stochastic rounding draws per-device noise)
    Xq, yq = X[:sizes.quant_rows], y[:sizes.quant_rows]
    quant = dict(PARAMS, use_quantized_grad=True, stochastic_rounding=False,
                 deterministic=True, seed=7)
    trees = {}
    for learner in ("serial", "data"):
        b = lgb.train(dict(quant, tree_learner=learner),
                      lgb.Dataset(Xq, label=yq), num_boost_round=3)
        check(b._engine._tree_learner == learner,
              f"tree_learner={learner} resolved to "
              f"{b._engine._tree_learner!r}")
        trees[learner] = b.model_to_string().split("parameters:")[0] \
            .split("feature_importances")[0]
    check(trees["serial"] == trees["data"],
          "use_quantized_grad: the data-parallel trees differ from the "
          "serial ones")
    report["quantized_data_equals_serial"] = True
    print(f"[smoke]   {json.dumps(report)}", flush=True)
    return report


def result_line(device: dict) -> str:
    """The driver's contract for the last line of standard output: a JSON
    object with exactly the keys ``ok`` and ``device``, the latter with
    exactly ``platform``, ``kind`` (text) and ``count`` (a whole number).
    Everything else the smoke learned goes on the summary line before."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny-size CPU rehearsal of this script; never "
                         "prints the pass line")
    args = ap.parse_args(argv)

    import jax
    import jaxlib
    devs = jax.devices()
    plat = devs[0].platform
    device = {"platform": plat, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"[smoke] platform={plat} device_kind={devs[0].device_kind} "
          f"count={len(devs)} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={_version('libtpu')}",
          flush=True)
    if args.rehearse_cpu:
        if plat != "cpu":
            print(f"chip_smoke: --rehearse-cpu is the CPU rehearsal; found "
                  f"platform={plat} (run it under JAX_PLATFORMS=cpu)",
                  file=sys.stderr)
            return 2
    elif plat != "tpu":
        print(f"chip_smoke: needs a TPU, found platform={plat} "
              f"({devs[0].device_kind} x{len(devs)}); no result",
              file=sys.stderr)
        return 2
    on_chip = plat == "tpu"
    sizes = CHIP if on_chip else REHEARSAL

    from lightgbm_tpu.core.plan import make_plan
    from lightgbm_tpu.utils.jit_cache import enable_persistent_cache
    cache_dir = enable_persistent_cache()
    print(f"[smoke] compile cache: {cache_dir}", flush=True)

    t_all = time.perf_counter()
    reports: dict = {}
    with phase("data"):
        X, y = synth_higgs(sizes.rows, N_FEATURES)
    with phase("train"):
        bst, reports["train"] = _train(X, y, {})
        eng = bst._engine
        cfg = eng.config
        want = make_plan(
            platform=plat, num_data=eng.num_data,
            num_bin_max=eng.num_bin_max,
            quantized=bool(cfg.use_quantized_grad),
            hist_dtype=cfg.tpu_hist_dtype, tree_learner="serial",
            storage="dense", row_sched="compact",
            hist_kernel=cfg.tpu_hist_kernel).hist_rm_backend
        res = reports["train"]["resolved"]
        check(res["hist_rm_backend"] == want,
              f"compact-path kernel is {res['hist_rm_backend']!r}, "
              f"make_plan names {want!r} for {plat}")
        if on_chip:
            check(res["row_sched"] == "compact" and
                  res["hist_rm_backend"] == "pallas" and res["async"] and
                  res["packed_cols"] == N_FEATURES,
                  f"TPU defaults resolved to {res}, expected the compact "
                  "grower on the pallas kernel with async boosting and "
                  "packed bins")
    if _FAILED:
        print(f"chip_smoke: FAILED {_FAILED}; nothing trained to go on "
              "with, no result", file=sys.stderr)
        return 1
    with phase("kernels"):
        reports["kernels"] = _kernels(eng, sizes, on_chip)
    with phase("scan"):
        reports["scan"] = _scan_sums(sizes)
    with phase("predict"):
        reports["predict"] = _predict(bst, X, sizes)
    with phase("serve"):
        reports["serve"] = _serve(bst, X, len(devs))
    with phase("dart"):
        reports["dart"] = _dart(X, y)
    if len(devs) > 1:
        with phase("data_parallel"):
            reports["data_parallel"] = _data_parallel(sizes, devs)
    else:
        print("[smoke] data_parallel skipped: one device visible",
              flush=True)
    peak = _peak_bytes(devs)
    print(f"[smoke] peak_bytes_in_use per device: {peak}", flush=True)
    if _FAILED:
        print(f"chip_smoke: FAILED phases {_FAILED}; no result",
              file=sys.stderr)
        return 1

    summary = {
        "device": device, "rows": sizes.rows, "features": N_FEATURES,
        "phases_s": _PHASES,
        "total_s": round(time.perf_counter() - t_all, 2), **reports,
        "peak_bytes_in_use": peak, "compile_cache": cache_dir,
        "claim": None,
    }
    print(f"[smoke] summary: {json.dumps(summary)}", flush=True)
    # the rehearsal proves this script, not the system on a chip: it
    # ends without the result line
    if on_chip:
        print(result_line(device), flush=True)
    else:
        print("[smoke] rehearsal ok (platform=cpu; no result line)",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
