"""Benchmark harness: Higgs-style boosting throughput on the current backend.

Mirrors the reference's headline benchmark (docs/Experiments.rst:82-134 —
Higgs 10.5M rows x 28 features, num_leaves=255, lr=0.1, 500 iters, 130.1 s on
a 16-thread CPU => 3.84 iters/sec). Rows are synthetic with the same shape
and a learnable binary signal; data prep/binning is excluded from the timed
region, matching the reference's convention of reporting training time.

`vs_baseline` scales the reference CPU throughput linearly to the benched row
count (per-iteration cost in histogram GBDT is ~linear in rows at fixed
leaves/bins): ref_ips(N) = 3.843 * (10.5e6 / N).

Robustness (ISSUE 4 — heartbeat-aware supervision): every child writes
phase-tagged heartbeats (compiling / warmup / measuring, robustness/
heartbeat.py) and the parent replaces blind wall-clock slots with
phase-aware liveness deadlines: a child advancing is never parked, a
child silent past its phase's stall budget is classified hung
(DeviceStallError, transient) and RETRIED — with the persistent compile
cache (utils/jit_cache.py) shared across attempts so the retry skips
the multi-minute compile that used to eat the watchdog. Measurement
children additionally BANK partial throughput (a crash-safe JSON
rewrite) so a stage that parks or stalls late still salvages its last
banked number instead of reporting an unconditional 0.0.
Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "iters/sec", "vs_baseline": N}
"""
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

from lightgbm_tpu.robustness import heartbeat
from lightgbm_tpu.robustness.supervisor import (DeviceStallError,
                                                StillAlive, watch_child)
from lightgbm_tpu.utils.jit_cache import (ENV_COMPILE_CACHE,
                                          resolve_cache_dir)

# Watchdog: if the device wedges (or compile stalls pathologically),
# emit an honest zero-result line instead of hanging the driver forever.
# Sized UNDER the driver's kill budget (round-2 postmortem: a 3000 s default
# outlived the driver and turned a wedged device into a silent rc=124).
BENCH_WATCHDOG_SEC = int(os.environ.get("BENCH_WATCHDOG_SEC", 1800))
# Pre-flight device probe: a tiny jit must complete before we attempt the
# full-size program. Generous (a recovering device runtime can take minutes
# to hand out the chip) but bounded well under the watchdog.
BENCH_PROBE_SEC = int(os.environ.get("BENCH_PROBE_SEC", 420))

N_ROWS = int(os.environ.get("BENCH_ROWS", 1_000_000))
N_FEATURES = 28
NUM_LEAVES = int(os.environ.get("BENCH_LEAVES", 255))
MAX_BIN = 255
WARMUP_ITERS = 3
TIMED_ITERS = int(os.environ.get("BENCH_ITERS", 20))
# extra params merged into the training config (JSON), e.g.
# BENCH_EXTRA='{"tpu_hist_dtype":"bfloat16"}' or '{"use_quantized_grad":true}'
BENCH_EXTRA = json.loads(os.environ.get("BENCH_EXTRA", "{}"))
REF_HIGGS_IPS = 500.0 / 130.094     # docs/Experiments.rst:113
REF_HIGGS_ROWS = 10_500_000

# scheduling modes to attempt, in order. The default is the ONE program
# the product selects: a default mode that fails to compile must end the
# run non-zero, not be followed by a different program and exit 0. More
# than one mode only when BENCH_SCHEDS asks for it.
SCHED_MODES = os.environ.get("BENCH_SCHEDS", "compact").split(",")

# how many times a STALL-classified (heartbeat-silent) measurement child
# is relaunched before salvaging; with the compile cache warm a retry
# costs a cache read, not a recompile
BENCH_MEASURE_ATTEMPTS = int(os.environ.get("BENCH_MEASURE_ATTEMPTS", 2))
# partial-result banking cadence inside the timed loop (seconds between
# banks; each bank costs one device sync, so the default is sized to
# never fire during a healthy fast run — 0 banks after every iteration,
# for tests)
ENV_PARTIAL = "LGBM_TPU_PARTIAL"
PARTIAL_EVERY_SEC = float(os.environ.get("LGBM_TPU_PARTIAL_EVERY_SEC",
                                         45.0))

# inference axis (ISSUE 5): after the training measurement the same child
# times the packed-forest serving engine (models/gbdt.py predict_device)
# over the trained model — binned route (device searchsorted binning) and
# raw route (model round-tripped through text, served without mappers via
# tree_leaf_raw). Emits a second JSON line, unit rows/sec, same status
# grammar; banked partials salvage it when the child dies mid-measure.
ENV_PARTIAL_PREDICT = "LGBM_TPU_PARTIAL_PREDICT"
BENCH_PREDICT = os.environ.get("BENCH_PREDICT", "1") == "1"
PREDICT_BATCH = int(os.environ.get("BENCH_PREDICT_BATCH", 100_000))
PREDICT_ROWS = int(os.environ.get("BENCH_PREDICT_ROWS", 1_000_000))
# SHAP contribution serving (ISSUE 20): each row emits (F+1)*K values
# through the packed path tensors, so the explain leg drives fewer rows
# than the score legs at the same wall budget
CONTRIB_ROWS = int(os.environ.get("BENCH_CONTRIB_ROWS", 200_000))

# ingestion axis (ISSUE 7): replicated-vs-sharded ingest A/B at the
# reference Higgs shape. A launch_local gang of BENCH_INGEST_WORLD
# processes (virtual CPU devices — the gang NEVER touches the TPU
# claim) constructs the synthetic table twice: replicated (every rank
# materializes + bins the GLOBAL table — the pre-round-7 behavior) and
# sharded (pre_partition: each rank generates + bins only its shard;
# distributed bin finding syncs the mappers). Per-rank ingest seconds
# and peak RSS go into a third JSON line, same status grammar. Runs on
# the full-success path AND the reaped-children failure paths (skipped
# only when a parked/unkillable child still owns the box), inside the
# remaining watchdog budget.
BENCH_INGEST = os.environ.get("BENCH_INGEST", "1") == "1"
INGEST_ROWS = int(os.environ.get("BENCH_INGEST_ROWS", 10_500_000))
INGEST_WORLD = int(os.environ.get("BENCH_INGEST_WORLD", 2))
# minimum watchdog seconds left to even start the ingest stage (two
# gang launches binning INGEST_ROWS rows; generous on server hosts)
INGEST_MIN_BUDGET = float(os.environ.get("BENCH_INGEST_MIN_BUDGET", 420))


# non-default configs (leaves ladder, dtype modes) are labeled so their
# numbers can't masquerade as the headline metric
_SUFFIX = ""
if NUM_LEAVES != 255:
    _SUFFIX += f"_L{NUM_LEAVES}"
if BENCH_EXTRA:
    _SUFFIX += "_" + "_".join(
        f"{k}={v}" for k, v in sorted(BENCH_EXTRA.items()))


# exit codes (BENCH_*.json consumers key on "status"; the rc mirrors it):
# 0 = result emitted; 3 = bench ran but produced no result ("slow code" /
# child failure); 4 = device unreachable — every probe attempt failed, the
# 0.0 value says nothing about the code under test ("hung device").
RC_NO_RESULT = 3
RC_DEVICE_UNREACHABLE = 4


# resolved level-histogram kernel attribution (ISSUE 6): set by
# run_child once the engine exists; "n/a" = non-level scheduling,
# "unknown" = parent-side failure lines emitted before/without a child
# resolution (salvaged lines inherit the child's banked value). r05's
# A/B confusion came from device numbers that could not be attributed
# to a kernel config — every record now carries the resolution.
_LEVEL_BACKEND = "unknown"

# resolved histogram-collective attribution (ISSUE 12, same contract):
# "n/a" = no row-sharded learner ran, else the engine's resolved mode
# with fallback attribution (e.g. "allreduce(fallback:efb)"); banked
# partials and salvage carry the child's value like level_backend.
_HIST_REDUCE = "unknown"

# comms A/B (ISSUE 12): allreduce-vs-reduce_scatter data-parallel arms
# on virtual CPU devices — mechanics for the queued device stage
# (tpu_session_auto ab_hist_reduce_*). Opt-in: two full trainings.
BENCH_COMMS = os.environ.get("BENCH_COMMS", "0") == "1"
COMMS_ROWS = int(os.environ.get("BENCH_COMMS_ROWS", 1_000_000))
COMMS_ITERS = int(os.environ.get("BENCH_COMMS_ITERS", 6))
COMMS_DEPTH = int(os.environ.get("BENCH_COMMS_DEPTH", 10))
COMMS_DEVICES = int(os.environ.get("BENCH_COMMS_DEVICES", 2))
COMMS_MIN_BUDGET = float(os.environ.get("BENCH_COMMS_MIN_BUDGET", 300))
# write the winner into TUNED.json's hist_reduce (3% margin, allreduce
# incumbent) — the same key + margin the session's DEVICE arms
# (ab_hist_reduce_*) re-learn. Default OFF: these arms run on virtual
# CPU devices, and resolve_hist_reduce consults the cache only on
# device precisely because shared-memory collective timings don't
# predict ICI behavior — a CPU win must not steer device defaults
# (review finding). Opt in to exercise the write mechanics.
COMMS_TUNED_WRITE = os.environ.get("BENCH_COMMS_TUNED_WRITE", "0") == "1"


# the device a measurement child ran on (jax.devices()[0].platform /
# .device_kind / count), set once the child has a backend. Every record
# carries it, so a number can never be read as another device's; the
# parent never touches jax, so its failure lines say "unknown".
_DEVICE = {"platform": "unknown", "device_kind": "unknown", "devices": 0}


def _note_device() -> None:
    import jax
    devs = jax.devices()
    _DEVICE.update(platform=devs[0].platform,
                   device_kind=devs[0].device_kind, devices=len(devs))


def _result_record(ips: float, **extra) -> dict:
    """The ONE place the benchmark record shape lives (metric name,
    reference-scaled vs_baseline, device + level-kernel attribution):
    shared by the headline result, the banked partials and the failure
    lines so they can never desynchronize."""
    ref_ips_at_n = REF_HIGGS_IPS * (REF_HIGGS_ROWS / N_ROWS)
    return {
        "metric": f"higgs_synth_{N_ROWS}x{N_FEATURES}"
                  f"_iters_per_sec{_SUFFIX}",
        "value": round(ips, 4),
        "unit": "iters/sec",
        "vs_baseline": round(ips / ref_ips_at_n, 4) if ips else 0.0,
        **_DEVICE,
        "level_backend": _LEVEL_BACKEND,
        "hist_reduce": _HIST_REDUCE,
        **extra,
    }


def _fail_line(note: str, status: str = "no_result") -> str:
    return json.dumps(_result_record(0.0, status=status, note=note))


def _predict_record(rows_per_sec: float, **extra) -> dict:
    """The ONE shape of the inference metric (same status grammar as the
    training record; `value` is the BINNED-route throughput, the raw
    route rides along as a field)."""
    return {
        "metric": f"higgs_synth_{N_ROWS}x{N_FEATURES}"
                  f"_predict_rows_per_sec{_SUFFIX}",
        "value": round(rows_per_sec, 1),
        "unit": "rows/sec",
        **_DEVICE,
        **extra,
    }


def _predict_fail_line(note: str, status: str = "no_result") -> str:
    return json.dumps(_predict_record(0.0, status=status, note=note))


def _lat_fields(lats, prefix: str = "") -> dict:
    """p50/p99 per-chunk latency fields riding the predict record
    (ISSUE 8) — nearest-rank over the timed chunks, in ms. Banked
    partials carry the same fields so a salvaged line reports the tail
    the child actually sustained, not just the mean rate."""
    if not lats:
        return {}
    from lightgbm_tpu.serving.metrics import percentile
    return {f"{prefix}p50_ms": round(percentile(lats, 50) * 1e3, 3),
            f"{prefix}p99_ms": round(percentile(lats, 99) * 1e3, 3)}


def _force_sync(arr) -> None:
    """Barrier ending a timed region: wait until the device has produced
    ``arr`` (chip_smoke.py checks on the chip that block_until_ready
    really blocks there)."""
    import jax
    jax.block_until_ready(arr)


def synth_higgs(n, f, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    logits = (X[:, 0] - 0.5 * X[:, 1] * X[:, 2] + 0.25 * X[:, 3] ** 2
              + 0.1 * rng.normal(size=n))
    y = (logits > np.median(logits)).astype(np.float32)
    return X, y


def _bank_record(path: str, rec: dict) -> None:
    """Crash-safe rewrite of a partial-result file (tmp + replace):
    whatever the parent finds here after a park/stall is the last
    throughput the device PROVABLY sustained (each bank follows a full
    device sync)."""
    if not path:
        return
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        with open(tmp, "w", encoding="utf-8") as f:
            f.write(json.dumps(rec))
        os.replace(tmp, path)
    except OSError as e:
        print(f"[bench] partial bank failed: {e!r}", file=sys.stderr)


def _bank_partial(path: str, sched: str, iters_done: int,
                  elapsed: float) -> None:
    if not path or iters_done <= 0 or elapsed <= 0:
        return
    _bank_record(path, _result_record(iters_done / elapsed, sched=sched,
                                      partial=True, iters_done=iters_done))


def run_child(sched: str) -> None:
    """Measure one scheduling mode and print the JSON result line."""
    heartbeat.install_from_env()
    heartbeat.beat(heartbeat.PHASE_COMPILING, 0)
    from lightgbm_tpu.utils.jit_cache import enable_persistent_cache
    enable_persistent_cache()
    import lightgbm_tpu as lgb
    _note_device()

    partial_path = os.environ.get(ENV_PARTIAL, "")
    X, y = synth_higgs(N_ROWS, N_FEATURES)
    params = {
        "objective": "binary",
        "num_leaves": NUM_LEAVES,
        "learning_rate": 0.1,
        "max_bin": MAX_BIN,
        "min_data_in_leaf": 20,
        "verbose": -1,
        "tpu_row_scheduling": sched,
        **BENCH_EXTRA,
    }
    ds = lgb.Dataset(X, label=y)
    if os.environ.get("BENCH_PROBE_COMPILE", "1") == "1":
        # staged compile: a num_leaves-reduced program at the full data
        # shape first, so a compiler that chokes on the 255-leaf program
        # fails fast (and cheap) instead of wedging the full compile
        # (round-1/2 postmortem: oversized compiles stalled)
        t0 = time.perf_counter()
        probe_b = lgb.Booster(dict(params, num_leaves=31), ds)
        probe_b.update()
        _force_sync(probe_b._engine.score)
        print(f"[bench] 31-leaf probe compile+step ok "
              f"({time.perf_counter() - t0:.1f}s)", file=sys.stderr)
        del probe_b
    heartbeat.beat(heartbeat.PHASE_COMPILING, 1)
    booster = lgb.Booster(params, ds)
    global _LEVEL_BACKEND, _HIST_REDUCE
    try:
        gcfg = booster._engine.grower_cfg
        if gcfg.row_sched == "level":
            from lightgbm_tpu.core.level_grower import \
                effective_level_backend
            _LEVEL_BACKEND = effective_level_backend(gcfg)
        else:                      # incl. an eligibility fallback:
            _LEVEL_BACKEND = "n/a"  # the record's sched field + this
            # say "no level kernel ran", attributably
    except Exception as e:
        print(f"[bench] level-backend attribution failed: {e!r}",
              file=sys.stderr)
    try:
        # ISSUE 12: the resolved histogram collective (with fallback
        # attribution) — "n/a" when no row-sharded learner ran
        _HIST_REDUCE = getattr(booster._engine, "_hist_reduce", "n/a")
    except Exception as e:
        print(f"[bench] hist-reduce attribution failed: {e!r}",
              file=sys.stderr)
    for w in range(WARMUP_ITERS):      # compile + cache warm
        heartbeat.beat(heartbeat.PHASE_WARMUP, w)
        booster.update()

    _force_sync(booster._engine.score)
    from lightgbm_tpu.utils.timer import global_timer
    global_timer.reset()  # drop warmup/compile time from the table
    heartbeat.beat(heartbeat.PHASE_MEASURING, 0)
    t0 = time.perf_counter()
    next_bank = (t0 + PARTIAL_EVERY_SEC) if partial_path else None
    for i in range(TIMED_ITERS):
        booster.update()
        heartbeat.beat(heartbeat.PHASE_MEASURING, i + 1)
        if next_bank is not None and i + 1 < TIMED_ITERS and \
                time.perf_counter() >= next_bank:
            # salvage point: sync so the banked rate covers COMPLETED
            # work, then re-arm the cadence (healthy fast runs never
            # reach the first bank — zero cost on the headline)
            _force_sync(booster._engine.score)
            _bank_partial(partial_path, sched, i + 1,
                          time.perf_counter() - t0)
            next_bank = time.perf_counter() + PARTIAL_EVERY_SEC
    _force_sync(booster._engine.score)
    dt = time.perf_counter() - t0

    ips = TIMED_ITERS / dt
    if partial_path:
        _bank_partial(partial_path, sched, TIMED_ITERS, dt)
    if global_timer.enabled:
        print(global_timer.table(), file=sys.stderr)
    # quality line (stderr): lets dtype/kernel modes prove they didn't
    # trade accuracy for speed — same data, same iteration count
    try:
        pred = booster._engine.score[0]
        import jax.numpy as jnp
        p = 1.0 / (1.0 + jnp.exp(-pred))
        eps = 1e-7
        ll = -jnp.mean(y * jnp.log(p + eps) +
                       (1 - y) * jnp.log(1 - p + eps))
        order = jnp.argsort(pred)
        ranks = jnp.zeros_like(pred).at[order].set(
            jnp.arange(1, pred.shape[0] + 1, dtype=pred.dtype))
        n_pos = float(y.sum())
        n_neg = float(len(y) - n_pos)
        auc = (float(jnp.sum(ranks * y)) -
               n_pos * (n_pos + 1) / 2) / (n_pos * n_neg)
        print(f"[bench] quality after {booster.current_iteration()} iters: "
              f"train_logloss={float(ll):.5f} train_auc={auc:.5f}",
              file=sys.stderr)
        # tree-depth stats: evidence for the level-synchronous grower's
        # D0 cap (docs/TPU_RUNBOOK.md round-6 design) — how deep do
        # best-first trees actually go at this shape, and what fraction
        # of splits sit at depth < 10?
        try:
            import numpy as _np
            depths = []
            shallow = total = 0
            for t in booster._engine.models[-5:]:
                nn = int(t.num_leaves) - 1
                if nn <= 0:
                    depths.append(0)
                    continue
                lc, rc = (_np.asarray(t.left_child),
                          _np.asarray(t.right_child))
                dep = _np.zeros(nn, _np.int32)
                for i in range(nn):     # parents precede children
                    for c in (int(lc[i]), int(rc[i])):
                        if 0 <= c < nn:
                            dep[c] = dep[i] + 1
                depths.append(int(dep.max()) + 1)
                shallow += int((dep < 9).sum())
                total += nn
            if total:
                print(f"[bench] tree depth (last {len(depths)} trees): "
                      f"max={max(depths)} "
                      f"splits_below_depth9={shallow}/{total} "
                      f"({100.0 * shallow / total:.0f}%)",
                      file=sys.stderr)
        except Exception as e:
            print(f"[bench] depth stats failed: {e!r}", file=sys.stderr)
    except Exception as e:          # quality line must never kill the bench
        print(f"[bench] quality line failed: {e!r}", file=sys.stderr)
    print(json.dumps(_result_record(ips, sched=sched)), flush=True)

    if BENCH_PREDICT:
        # inference axis (ISSUE 5): serve the just-trained model through
        # the packed-forest engine. Failures must never retro-poison the
        # training line already printed above.
        try:
            _measure_predict(lgb, booster, X, sched)
        except Exception as e:
            print(f"[bench] predict measurement failed: {e!r}",
                  file=sys.stderr)
            print(_predict_fail_line(f"sched={sched}: {e!r}"), flush=True)


def _timed_predict(predict_fn, X, tag: str, sched: str,
                   bank_path: str, extra: dict):
    """Drive predict_fn over PREDICT_ROWS rows in PREDICT_BATCH chunks;
    returns (rows/sec, per-chunk latencies). Each chunk result is
    host-materialized (a real barrier), beats the heartbeat, and banks
    a crash-safe partial so a late park/stall still salvages a
    provably-sustained rate + latency tail."""
    n = X.shape[0]
    rows_target = extra.pop("_rows_target", PREDICT_ROWS)
    rows_done = 0
    lats = []
    t0 = time.perf_counter()
    next_bank = t0 + PARTIAL_EVERY_SEC if bank_path else None
    chunk_i = 0
    while rows_done < rows_target:
        off = (chunk_i * PREDICT_BATCH) % n
        chunk = X[off:off + PREDICT_BATCH]
        t_chunk = time.perf_counter()
        predict_fn(chunk)
        lats.append(time.perf_counter() - t_chunk)
        rows_done += len(chunk)
        chunk_i += 1
        heartbeat.beat(heartbeat.PHASE_MEASURING, 10_000 + chunk_i)
        now = time.perf_counter()
        if next_bank is not None and rows_done < rows_target and \
                now >= next_bank:
            _bank_record(bank_path, _predict_record(
                rows_done / (now - t0), partial=True, path=tag,
                sched=sched, rows_done=rows_done, **_lat_fields(lats),
                **extra))
            next_bank = time.perf_counter() + PARTIAL_EVERY_SEC
    return rows_done / (time.perf_counter() - t0), lats


def _measure_predict(lgb, booster, X, sched: str) -> None:
    """Binned + raw serving throughput over the trained model; prints the
    predict JSON line."""
    bank_path = os.environ.get(ENV_PARTIAL_PREDICT, "")
    Xq = np.asarray(X[:PREDICT_BATCH], np.float64)
    n_trees = booster.current_iteration()
    extra = {"trees": n_trees, "leaves": NUM_LEAVES,
             "batch": PREDICT_BATCH}

    def binned(chunk):
        return booster.predict(chunk, device=True, raw_score=True)

    t0 = time.perf_counter()
    binned(Xq[:PREDICT_BATCH])           # compile + pack, untimed
    print(f"[bench] predict binned warmup {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)
    # Booster.predict falls back to the HOST walk (with only a stderr
    # warning) when the serving engine refuses a shape — a number
    # measured there must never masquerade as device throughput
    srv = getattr(booster._engine, "_serving", None)
    if srv is None or srv.pack.count != len(booster._engine.models):
        raise RuntimeError("binned device route did not serve (host "
                           "fallback engaged) — refusing to publish host "
                           "throughput as the packed-forest metric")
    binned_rps, binned_lats = _timed_predict(binned, X, "binned", sched,
                                             bank_path, extra)

    # raw route: round-trip through model text — a loaded model has no
    # bin mappers, so predict_device serves via tree_leaf_raw
    loaded = lgb.Booster(model_str=booster.model_to_string())

    def raw(chunk):
        return loaded.predict(chunk, device=True, raw_score=True)

    t0 = time.perf_counter()
    raw(Xq[:PREDICT_BATCH])
    print(f"[bench] predict raw warmup {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)
    srv = getattr(loaded._engine, "_serving", None)
    if srv is None or srv.raw_pack.count != len(loaded._engine.models):
        raise RuntimeError("raw device route did not serve (host "
                           "fallback engaged) — refusing to publish host "
                           "throughput as the packed-forest metric")
    raw_rps, raw_lats = _timed_predict(raw, X, "raw", sched, bank_path,
                                       extra)

    # SHAP contribution serving (ISSUE 20): the packed-path-tensor
    # explain route over the same model — same heartbeat / partial
    # banking / salvage grammar, fewer rows (CONTRIB_ROWS) because each
    # row emits (F+1)*K values instead of K
    def contrib(chunk):
        return booster.predict(chunk, device=True, pred_contrib=True)

    t0 = time.perf_counter()
    contrib(Xq[:PREDICT_BATCH])          # compile + SHAP pack, untimed
    print(f"[bench] predict contrib warmup {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)
    # the same host-fallback guard as the score legs: Booster.predict
    # answers the host predict_contrib walk (loudly once) when the SHAP
    # pack refuses the model — that number must never publish as the
    # device explain metric
    srv = getattr(booster._engine, "_serving", None)
    if srv is None or srv.shap_pack is None or \
            srv.shap_pack.count != len(booster._engine.models):
        raise RuntimeError("contrib device route did not serve (host "
                           "fallback engaged) — refusing to publish host "
                           "throughput as the packed-path metric")
    contrib_rps, contrib_lats = _timed_predict(
        contrib, X, "contrib", sched, bank_path,
        dict(extra, _rows_target=CONTRIB_ROWS))

    # parity guard: a serving engine that quietly diverged must not
    # publish a throughput number
    host = booster.predict(Xq[:4096], raw_score=True)
    dev = binned(Xq[:4096])
    if not np.allclose(host, dev, rtol=1e-5, atol=1e-6):
        raise RuntimeError("device/host prediction parity broke: "
                           f"max|d|={np.abs(host - dev).max():.3e}")
    rec = _predict_record(binned_rps, sched=sched,
                          binned_rows_per_sec=round(binned_rps, 1),
                          raw_rows_per_sec=round(raw_rps, 1),
                          contrib_rows_per_sec=round(contrib_rps, 1),
                          **_lat_fields(binned_lats),
                          **_lat_fields(raw_lats, "raw_"),
                          **_lat_fields(contrib_lats, "contrib_"),
                          **extra)
    if bank_path:
        _bank_record(bank_path, dict(rec, partial=True,
                                     rows_done=PREDICT_ROWS))
    print(json.dumps(rec), flush=True)


def _ingest_record(value: float, **extra) -> dict:
    """The ONE shape of the ingest metric line (status grammar shared
    with the training/predict lines): ``value`` is the slowest rank's
    SHARDED ingest seconds, the replicated arm and the RSS A/B ride
    along as fields."""
    return {
        "metric": f"ingest_synth_{INGEST_ROWS}x{N_FEATURES}"
                  f"_w{INGEST_WORLD}_sec",
        "value": round(value, 2),
        "unit": "sec",
        **extra,
    }


def run_ingest_child(mode: str) -> None:
    """One rank of the ingest gang: generate THIS rank's data (sharded)
    or the global table (replicated), construct the Dataset, report
    ingest seconds + peak RSS as one JSON line on stdout."""
    # init_from_env BEFORE other jax use (virtual CPU devices + gloo)
    from lightgbm_tpu.distributed import init_from_env
    rank = init_from_env()
    import resource

    from lightgbm_tpu.robustness import heartbeat as hb
    hb_base = os.environ.get(hb.ENV_HEARTBEAT, "")
    if hb_base:
        hb.install(hb.rank_path(hb_base, rank))
    hb.beat(hb.PHASE_COMPILING, 0)
    import jax

    import lightgbm_tpu as lgb
    world = jax.process_count()
    if mode == "sharded":
        from lightgbm_tpu.distributed import row_slice
        lo, hi = row_slice(INGEST_ROWS, rank, world)
        n_local, seed = hi - lo, 1000 + rank
    else:
        n_local, seed = INGEST_ROWS, 1000
    t_gen = time.perf_counter()
    X, y = synth_higgs(n_local, N_FEATURES, seed=seed)
    gen_sec = time.perf_counter() - t_gen
    hb.beat(hb.PHASE_MEASURING, 0)
    params = {"verbose": -1}
    if mode == "sharded":
        params["pre_partition"] = True
        params["tree_learner"] = "data"
    # jaxlint: disable=JL005 — the timed region is host-side binning +
    # allgather collectives (process_allgather returns host numpy, a
    # real barrier); there is no async device dispatch to sync
    t0 = time.perf_counter()
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    ingest_sec = time.perf_counter() - t0
    hb.beat(hb.PHASE_MEASURING, 1)
    binned = ds._binned
    local_rows = binned.bins.shape[1] if binned.bins is not None else 0
    if mode == "sharded":
        assert binned.shard is not None, "sharded ingest did not engage"
        assert local_rows == n_local
    # ru_maxrss: KB on linux — the per-process peak over generation +
    # binning, i.e. exactly the "does a host ever hold the global
    # table" number the stage exists to measure
    peak_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    print(json.dumps({
        "rank": rank, "mode": mode, "world": world,
        "rows_local": int(n_local), "ingest_sec": round(ingest_sec, 2),
        "gen_sec": round(gen_sec, 2),
        "peak_rss_mb": round(peak_kb / 1024.0, 1)}), flush=True)


def _run_ingest_gang(mode: str, deadline: float) -> list:
    """Launch + supervise one ingest gang; returns the per-rank record
    dicts. Raises on rank failure/timeout (caller maps to status).

    Supervision is the ISSUE 10 gang supervisor over the children's
    per-rank heartbeats: a rank death SIGTERMs the survivors instead of
    leaving them wedged in the binning allgathers until the blunt
    timeout, and the raised GangError carries a per-rank last-phase
    diagnosis for the no_result record."""
    import dataclasses as _dc
    import tempfile as _tf

    from lightgbm_tpu.distributed import spawn_local
    from lightgbm_tpu.robustness.gang import GangSupervisor
    from lightgbm_tpu.robustness.heartbeat import StallPolicy, rank_path
    fd, hb_base = _tf.mkstemp(prefix=f"bench_ingest_{mode}_",
                              suffix=".hb")
    os.close(fd)
    budget = max(deadline - time.time(), 30.0)
    # a construct() at bench scale is a legitimately LONG quiet phase
    # (the replicated leg beats once then bins for minutes; 100M-row
    # targets far exceed the default 300 s measuring budget), so widen
    # every per-phase stall budget to the gang budget — death and
    # file-silence detection (the keepalive thread keeps touching
    # through construct) still fire fast, which is the supervisor's
    # whole advantage over the old blunt kill
    pol = StallPolicy.from_env()
    pol = _dc.replace(
        pol,
        stall_sec={p: max(v, budget) for p, v in pol.stall_sec.items()},
        default_stall=max(pol.default_stall, budget))
    try:
        procs = spawn_local(
            [sys.executable, os.path.abspath(__file__)],
            num_processes=INGEST_WORLD, cpu_devices_per_process=1,
            env_extra={"_LGBM_BENCH_INGEST_CHILD": mode,
                       heartbeat.ENV_HEARTBEAT: hb_base,
                       ENV_COMPILE_CACHE: _cache_dir()})
        sup = GangSupervisor(
            procs, hb_base,
            hb_paths=[rank_path(hb_base, r)
                      for r in range(INGEST_WORLD)],
            policy=pol, label=f"ingest {mode} gang",
            escalate_kill=True)      # virtual-CPU gang, no device claim
        results = sup.watch(timeout=budget)
    finally:
        for r in range(INGEST_WORLD):
            for p in (hb_base, rank_path(hb_base, r)):
                try:
                    os.unlink(p)
                except OSError:
                    pass
    recs = []
    for r, (rc, out) in enumerate(results):
        rec = None
        for ln in out.splitlines():
            ln = ln.strip()
            if ln.startswith("{") and '"ingest_sec"' in ln:
                rec = json.loads(ln)
        if rc != 0 or rec is None:
            raise RuntimeError(
                f"ingest {mode} rank {r} rc={rc}: {out[-400:]!r}")
        recs.append(rec)
    return recs


def maybe_run_ingest(deadline: float) -> None:
    """Replicated-vs-sharded ingest A/B line. The gang runs on virtual
    CPU devices and never touches the device claim, so it runs on BOTH
    the full-success path and the reaped-children failure paths
    (device_unreachable / salvage / no_result — on those its line is
    printed BEFORE the final training fail/salvage line, which stays
    LAST for downstream consumers). It is skipped only when a child is
    still alive on the box (parked / unkillable probe: the A/B timings
    would race a live claim-holder for the cores). Its own failure must
    never poison the training/predict lines already printed. Skips
    silently when disabled or the watchdog is nearly spent."""
    if not BENCH_INGEST:
        return
    remaining = deadline - time.time()
    if remaining < INGEST_MIN_BUDGET:
        print(f"[bench] ingest stage skipped: {remaining:.0f}s of "
              f"watchdog left (< {INGEST_MIN_BUDGET:.0f}s floor)",
              file=sys.stderr)
        return
    try:
        sharded = _run_ingest_gang("sharded", deadline)
        replicated = _run_ingest_gang("replicated", deadline)
        sh_sec = max(r["ingest_sec"] for r in sharded)
        re_sec = max(r["ingest_sec"] for r in replicated)
        sh_rss = max(r["peak_rss_mb"] for r in sharded)
        re_rss = max(r["peak_rss_mb"] for r in replicated)
        print(json.dumps(_ingest_record(
            sh_sec, replicated_sec=re_sec,
            sharded_peak_rss_mb=sh_rss, replicated_peak_rss_mb=re_rss,
            rss_ratio=round(sh_rss / max(re_rss, 1e-9), 3),
            sharded=sharded, replicated=replicated)), flush=True)
    except Exception as e:  # noqa: BLE001 — never poison earlier lines
        print(f"[bench] ingest stage failed: {e!r}", file=sys.stderr)
        print(json.dumps(_ingest_record(
            0.0, status="no_result", note=f"ingest stage: {e}")),
            flush=True)


def _comms_record(value: float, **extra) -> dict:
    """The ONE shape of the comms A/B line (status grammar shared with
    the training/ingest lines): ``value`` is the reduce_scatter arm's
    iters/sec, the allreduce arm rides along as a field."""
    return {
        "metric": f"comms_ab_{COMMS_ROWS}x{N_FEATURES}_d{COMMS_DEPTH}"
                  f"_w{COMMS_DEVICES}_iters_per_sec",
        "value": round(value, 4),
        "unit": "iters/sec",
        **extra,
    }


def run_comms_child(mode: str) -> None:
    """One arm of the hist-reduce A/B: train the depth-capped shape
    with tree_learner=data over COMMS_DEVICES virtual CPU devices under
    ``tpu_hist_reduce=mode``; print one JSON line with the rate AND the
    engine's resolved attribution (the parent refuses to compare arms
    that silently resolved to the same collective)."""
    heartbeat.install_from_env()
    heartbeat.beat(heartbeat.PHASE_COMPILING, 0)
    from lightgbm_tpu.utils.jit_cache import enable_persistent_cache
    enable_persistent_cache()
    import jax

    import lightgbm_tpu as lgb
    ndev = len(jax.devices())
    if ndev < COMMS_DEVICES:
        raise RuntimeError(
            f"comms child needs {COMMS_DEVICES} devices, got {ndev} "
            "(parent must export xla_force_host_platform_device_count)")
    X, y = synth_higgs(COMMS_ROWS, N_FEATURES, seed=5)
    params = {
        "objective": "binary",
        "num_leaves": NUM_LEAVES,
        "learning_rate": 0.1,
        "max_bin": MAX_BIN,
        "min_data_in_leaf": 20,
        "max_depth": COMMS_DEPTH,
        "verbose": -1,
        "tree_learner": "data",
        "tpu_num_devices": COMMS_DEVICES,
        "tpu_hist_reduce": mode,
        **BENCH_EXTRA,
    }
    booster = lgb.Booster(params, lgb.Dataset(X, label=y))
    resolved = getattr(booster._engine, "_hist_reduce", "unknown")
    for w in range(2):
        heartbeat.beat(heartbeat.PHASE_WARMUP, w)
        booster.update()
    _force_sync(booster._engine.score)
    heartbeat.beat(heartbeat.PHASE_MEASURING, 0)
    t0 = time.perf_counter()
    for i in range(COMMS_ITERS):
        booster.update()
        heartbeat.beat(heartbeat.PHASE_MEASURING, i + 1)
    _force_sync(booster._engine.score)
    dt = time.perf_counter() - t0
    print(json.dumps({
        "comms_mode": mode, "hist_reduce": resolved,
        "ips": round(COMMS_ITERS / dt, 4),
        "rows": COMMS_ROWS, "devices": COMMS_DEVICES}), flush=True)


def maybe_run_comms_ab(deadline: float) -> None:
    """allreduce-vs-reduce_scatter A/B on virtual CPU devices
    (ISSUE 12): CPU mechanics for the queued device stage — the arms,
    the record grammar and the TUNED.json ``hist_reduce`` re-learn
    (3% margin, allreduce incumbent; the write requires BOTH arms to
    have attributed to their requested collective, so an eligibility
    fallback can never tune on two identical programs). Same contract
    as the ingest stage: its own failure never poisons earlier lines.
    """
    if not BENCH_COMMS:
        return
    remaining = deadline - time.time()
    if remaining < COMMS_MIN_BUDGET:
        print(f"[bench] comms A/B skipped: {remaining:.0f}s of watchdog "
              f"left (< {COMMS_MIN_BUDGET:.0f}s floor)", file=sys.stderr)
        return
    try:
        arms = {}
        for mode in ("allreduce", "reduce_scatter"):
            env = dict(os.environ,
                       _LGBM_BENCH_COMMS_CHILD=mode,
                       JAX_PLATFORMS="cpu")
            for k in ("_LGBM_BENCH_CHILD", "_LGBM_BENCH_PROBE",
                      "_LGBM_BENCH_INGEST_CHILD"):
                env.pop(k, None)
            xf = env.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in xf:
                env["XLA_FLAGS"] = (
                    xf + " --xla_force_host_platform_device_count="
                    f"{COMMS_DEVICES}").strip()
            env[ENV_COMPILE_CACHE] = _cache_dir()
            budget = max(deadline - time.time(), 60.0)
            p = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                env=env, capture_output=True, text=True, timeout=budget)
            rec = None
            for ln in p.stdout.splitlines():
                ln = ln.strip()
                if ln.startswith("{") and '"comms_mode"' in ln:
                    rec = json.loads(ln)
            if p.returncode != 0 or rec is None:
                raise RuntimeError(
                    f"comms arm {mode} rc={p.returncode}: "
                    f"{p.stderr[-400:]!r}")
            arms[mode] = rec
        ar, rs = arms["allreduce"], arms["reduce_scatter"]
        attributed = (ar["hist_reduce"] == "allreduce" and
                      rs["hist_reduce"] == "reduce_scatter")
        win = (attributed and ar["ips"] > 0 and
               rs["ips"] > ar["ips"] * 1.03)
        tuned_written = False
        if win and COMMS_TUNED_WRITE:
            from lightgbm_tpu import tuned
            path = tuned.write({"hist_reduce": "reduce_scatter"})
            tuned_written = True
            print(f"[bench] hist_reduce=reduce_scatter written to "
                  f"{path} ({rs['ips']:.3f} vs {ar['ips']:.3f} it/s)",
                  file=sys.stderr)
        print(json.dumps(_comms_record(
            rs["ips"], allreduce_ips=ar["ips"],
            hist_reduce=rs["hist_reduce"],
            allreduce_attr=ar["hist_reduce"], attributed=attributed,
            winner=("reduce_scatter" if win else "allreduce"),
            tuned_written=tuned_written)), flush=True)
    except Exception as e:  # noqa: BLE001 — never poison earlier lines
        print(f"[bench] comms A/B failed: {e!r}", file=sys.stderr)
        print(json.dumps(_comms_record(
            0.0, status="no_result", note=f"comms A/B: {e}")),
            flush=True)


def run_probe() -> None:
    """Tiny end-to-end sanity: device claim + a small jitted train step."""
    heartbeat.install_from_env()
    heartbeat.beat(heartbeat.PHASE_COMPILING, 0)
    # fault harness hook: LGBM_TPU_FAULTS=probe_timeout (inherited via
    # env) makes this child fail with the UNAVAILABLE signature, so the
    # parent's shared retry policy is testable without a flaky device
    from lightgbm_tpu.robustness import faults
    faults.maybe_fail("probe_timeout")
    from lightgbm_tpu.utils.jit_cache import enable_persistent_cache
    enable_persistent_cache()
    import jax
    devs = jax.devices()
    import lightgbm_tpu as lgb
    X, y = synth_higgs(4096, N_FEATURES)
    ds = lgb.Dataset(X, label=y)
    booster = lgb.Booster({"objective": "binary", "num_leaves": 7,
                           "max_bin": 63, "verbose": -1}, ds)
    booster.update()
    _force_sync(booster._engine.score)
    print(json.dumps({"probe_ok": True, "devices": [str(d) for d in devs]}),
          flush=True)


class _ParkedChild(Exception):
    """A measurement child was left RUNNING (parked): either it was
    alive AND ADVANCING at the hard watchdog deadline, or it was
    classified hung but ignored SIGTERM. Its bench tree may hold the
    device claim mid-compile, and a SIGKILL there is the documented
    machine-wide wedge trigger that zeroed three driver bench rounds
    running. The parent salvages the last
    banked partial (if any) and skips remaining stages."""


class _ChildSpawn:
    """One supervised child: file-redirected streams (an abandoned
    child can never block on a pipe) + its own heartbeat and
    partial-result files, compile cache shared across attempts."""

    def __init__(self, env_extra: dict, tag: str,
                 partial: bool = False):
        self.out_f = tempfile.NamedTemporaryFile(
            mode="w+", prefix=f"bench_{tag}_", suffix=".out",
            delete=False)
        self.err_f = tempfile.NamedTemporaryFile(
            mode="w+", prefix=f"bench_{tag}_", suffix=".err",
            delete=False)
        # mkstemp (not the race-prone mktemp): the file exists from
        # birth with 0600 perms; an empty heartbeat/partial file reads
        # as "no record yet", which is exactly right
        fd, self.hb_path = tempfile.mkstemp(prefix=f"bench_{tag}_",
                                            suffix=".hb")
        os.close(fd)
        self.partial_path = ""
        self.predict_partial_path = ""
        if partial:
            fd, self.partial_path = tempfile.mkstemp(
                prefix=f"bench_{tag}_", suffix=".partial")
            os.close(fd)
            fd, self.predict_partial_path = tempfile.mkstemp(
                prefix=f"bench_{tag}_", suffix=".ppartial")
            os.close(fd)
        env = dict(os.environ, **env_extra)
        env[heartbeat.ENV_HEARTBEAT] = self.hb_path
        env[ENV_COMPILE_CACHE] = _cache_dir()
        env.pop(ENV_PARTIAL, None)
        env.pop(ENV_PARTIAL_PREDICT, None)
        if self.partial_path:
            env[ENV_PARTIAL] = self.partial_path
        if self.predict_partial_path:
            env[ENV_PARTIAL_PREDICT] = self.predict_partial_path
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)],
            env=env, stdout=self.out_f, stderr=self.err_f, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)))

    def fail_cleanup(self, tail: int = 2000) -> bool:
        """Failure-path epilogue shared by every probe/measurement
        except-branch: dump the stderr tail, clean up, and report
        whether the child is actually DEAD (False = it survived
        SIGTERM and was left running — the caller must treat it as
        stuck/parked, never retry on top of it)."""
        _, err = self.read_streams()
        sys.stderr.write(err[-tail:])
        dead = self.proc.poll() is not None
        self.cleanup()
        return dead

    def read_streams(self):
        self.out_f.flush()
        self.err_f.flush()
        with open(self.out_f.name, "r", encoding="utf-8",
                  errors="replace") as f:
            out = f.read()
        with open(self.err_f.name, "r", encoding="utf-8",
                  errors="replace") as f:
            err = f.read()
        return out, err

    def cleanup(self):
        # every dead-child exit removes the temp pair (sessions spawn
        # many children; parked children keep theirs — the child still
        # writes there and the operator may want the tail)
        if self.proc.poll() is None:
            sys.stderr.write(
                f"[bench] parked child output stays in "
                f"{self.out_f.name} / {self.err_f.name}\n")
            return
        for f in (self.out_f, self.err_f):
            try:
                f.close()
                os.unlink(f.name)
            except OSError:
                pass
        # the child's atomic-write tmp (hb_path.<pid>.tmp) can be
        # orphaned when the interpreter exits mid-keepalive — sweep it
        for p in (self.hb_path,
                  f"{self.hb_path}.{self.proc.pid}.tmp"):
            try:
                os.unlink(p)
            except OSError:
                pass


def _cache_dir() -> str:
    """Compile cache shared by every child of this bench run (and, via
    the directory the session supervisor exports, across
    retried/relaunched stages): a retried attempt reads the first
    attempt's compile from disk instead of repaying the minutes that
    used to eat the watchdog."""
    d = resolve_cache_dir()
    os.makedirs(d, exist_ok=True)
    return d


def _read_partial(path: str):
    """Last banked partial result, or None (missing/torn tolerated)."""
    if not path:
        return None
    try:
        with open(path, "r", encoding="utf-8") as f:
            d = json.loads(f.read())
        return d if float(d.get("value", 0.0)) > 0 else None
    except (OSError, ValueError):
        return None


def _run_instrumented(fn, *args) -> int:
    """Child entry shell: a stall classified by the child's OWN
    watchdog (raised at an iteration boundary, or delivered as the
    watchdog's interrupt) must exit with EXIT_STALLED so the parent
    maps it to DeviceStallError and RETRIES — a generic rc would read
    as a code failure and kill the retry the stall deserves."""
    try:
        fn(*args)
        return 0
    except DeviceStallError as e:
        print(f"[bench] self-watchdogged stall: {e}", file=sys.stderr)
        return heartbeat.EXIT_STALLED
    except KeyboardInterrupt:
        if heartbeat.stall_pending():
            print("[bench] stall watchdog interrupt", file=sys.stderr)
            return heartbeat.EXIT_STALLED
        raise


def main() -> int:
    if os.environ.get("_LGBM_BENCH_PROBE"):
        return _run_instrumented(run_probe)
    if os.environ.get("_LGBM_BENCH_CHILD"):
        return _run_instrumented(run_child,
                                 os.environ["_LGBM_BENCH_CHILD"])
    if os.environ.get("_LGBM_BENCH_INGEST_CHILD"):
        return _run_instrumented(
            run_ingest_child, os.environ["_LGBM_BENCH_INGEST_CHILD"])
    if os.environ.get("_LGBM_BENCH_COMMS_CHILD"):
        return _run_instrumented(
            run_comms_child, os.environ["_LGBM_BENCH_COMMS_CHILD"])
    if os.environ.get("BENCH_INGEST_ONLY"):
        # standalone ingest A/B (PARITY.md numbers, smoke): no device
        # probe, no training — the gang runs on virtual CPU devices
        maybe_run_ingest(time.time() + BENCH_WATCHDOG_SEC)
        return 0
    if os.environ.get("BENCH_COMMS_ONLY"):
        # standalone hist-reduce A/B (ISSUE 12): no device probe — the
        # arms run on virtual CPU devices (device arms live in the
        # session's ab_hist_reduce_* stage)
        globals()["BENCH_COMMS"] = True
        maybe_run_comms_ab(time.time() + BENCH_WATCHDOG_SEC)
        return 0

    deadline = time.time() + BENCH_WATCHDOG_SEC
    # liveness plumbing (ISSUE 4): this parent's own heartbeat (present
    # when a session supervisor exported LGBM_TPU_HEARTBEAT — child
    # spawns override the env with their own files) relays every
    # observed child advance upward; the stall policy governs how long
    # a child phase may sit silent before it is hung, replacing the
    # blind wall-clock slots that parked healthy compiling children in
    # rounds 3-5
    hb_self = heartbeat.install_from_env()
    stall_policy = heartbeat.StallPolicy.from_env()
    watch_poll = float(os.environ.get("BENCH_WATCH_POLL", 1.0))

    # Stage 0: establish the device is reachable — retrying ACROSS the bench
    # window instead of dying on the first failed probe (round-3 postmortem:
    # one 420 s probe attempt turned a recovering device into a 0.0 bench).
    # The retry loop itself is the SHARED policy from
    # lightgbm_tpu/robustness/retry.py (bounded attempts, decorrelated
    # jitter, deadline): rc=4 device_unreachable is only ever reported
    # after that policy's budget is exhausted, the same contract
    # init_distributed and the injected collectives run under.
    #
    # The documented recovery signature (docs/TPU_RUNBOOK.md) is a probe that
    # errors with "UNAVAILABLE: TPU backend setup/compile error" — that means
    # the backend is cycling and a LATER claim may succeed, so it is
    # classified transient and retried. Killing a claim-WAITER at its slot
    # deadline is benign (the machine-wide wedge comes from killing a client
    # that HOLDS the grant mid-compile; probing first is what avoids that).
    # We reserve ~35% of the watchdog for the measurement itself: a probe
    # succeeding with less than that leaves no room to compile+run anyway.
    from lightgbm_tpu.robustness.retry import (RetryError, RetryPolicy,
                                               retry_call)

    reserve = min(max(BENCH_WATCHDOG_SEC * 0.35, 120.0),
                  BENCH_WATCHDOG_SEC * 0.5)
    class _ProbeCodeFailure(Exception):
        """Probe child failed in a non-device way (import error, OOM,
        …) — NOT transient: retrying won't help and the 0.0 must not
        masquerade as "hung device" (status/rc contract above)."""

    class _ProbeStuck(Exception):
        """A stalled probe ignored SIGTERM and is still running: a
        fresh probe must NOT stack on it (one patient single-client
        probe, never stacked) — terminal, reported as the device
        symptom it is."""

    from lightgbm_tpu.robustness.retry import is_transient_error

    def _probe_classifier(exc: BaseException) -> bool:
        # a code failure is terminal even if the embedded stderr tail
        # happens to contain a substring the generic classifier would
        # match ("timed out" in some unrelated traceback)
        if isinstance(exc, (_ProbeCodeFailure, _ProbeStuck)):
            return False
        return is_transient_error(exc)

    policy = RetryPolicy(
        max_attempts=int(os.environ.get("BENCH_PROBE_ATTEMPTS", "6")),
        base_delay=5.0, max_delay=30.0,
        deadline=max(BENCH_WATCHDOG_SEC - reserve, 1.0),
        classifier=_probe_classifier)

    state = {"attempts": 0}

    def probe_attempt(slot_budget=None) -> None:
        # ``slot_budget`` is injected by retry_call (budget_kw): the
        # POLICY's remaining deadline, so an attempt slot can never
        # exceed the window that actually remains (ISSUE 4 satellite —
        # the r05 log showed attempt 2 granted 750 s inside an already
        # half-spent window)
        state["attempts"] += 1
        if state["attempts"] == 1:
            # fast-fail slot: a healthy device answers in seconds
            slot = min(BENCH_PROBE_SEC, slot_budget
                       if slot_budget is not None else BENCH_PROBE_SEC)
        else:
            # patient slot: the documented recovery signature is a claim
            # that waits ~1500 s then errors UNAVAILABLE — only a probe
            # allowed to wait that long can ever surface it, so retries
            # get the whole remaining pre-reserve window (one patient
            # single-client probe, never stacked)
            slot = slot_budget if slot_budget is not None \
                else BENCH_PROBE_SEC
        slot = max(slot, 30.0)
        child = _ChildSpawn({"_LGBM_BENCH_PROBE": "1"},
                            tag=f"probe{state['attempts']}")
        try:
            rc = watch_child(
                child.proc, child.hb_path, policy=stall_policy,
                hard_deadline=time.monotonic() + slot,
                poll=watch_poll, relay=hb_self,
                label=f"probe attempt {state['attempts']}")
        except StillAlive:
            # a probe is a claim-WAITER: stopping it at slot expiry is
            # benign (the wedge comes from killing claim HOLDERS);
            # SIGTERM + grace, never SIGKILL
            from lightgbm_tpu.robustness.supervisor import \
                terminate_gently
            terminate_gently(child.proc, 10.0,
                             f"probe attempt {state['attempts']}")
            if not child.fail_cleanup():
                # it survived SIGTERM: a retry would stack a second
                # probe on the one still in the claim queue
                raise _ProbeStuck(
                    f"slot-expired probe pid={child.proc.pid} ignored "
                    "SIGTERM; left running — further probes would "
                    "stack claims") from None
            raise TimeoutError(
                f"probe attempt {state['attempts']} timed out "
                f"({slot:.0f}s)") from None
        except DeviceStallError:
            # heartbeat-silent probe: already classified (and SIGTERMed)
            # by the supervisor WITHIN stall/silent_sec — not after the
            # full slot; transient, the policy retries
            if not child.fail_cleanup():
                raise _ProbeStuck(
                    f"stalled probe pid={child.proc.pid} ignored "
                    "SIGTERM; left running — further probes would "
                    "stack claims") from None
            raise
        out, err = child.read_streams()
        child.cleanup()
        if '"probe_ok"' in out:
            sys.stderr.write(
                f"[bench] probe ok (attempt {state['attempts']}): "
                f"{out.strip()[:200]}\n")
            return
        sys.stderr.write(err[-2000:])
        tail = err[-300:]
        if "UNAVAILABLE" in err:
            # known recovery signature — transient, policy will retry
            raise RuntimeError(
                f"UNAVAILABLE: probe attempt {state['attempts']} "
                f"rc={rc}: {tail!r}")
        raise _ProbeCodeFailure(
            f"probe attempt {state['attempts']} "
            f"rc={rc}: {tail!r}")

    try:
        retry_call(probe_attempt, policy=policy,
                   what="bench device probe", budget_kw="slot_budget")
    except RetryError as e:
        # transient failures exhausted the shared policy → honest
        # device symptom (rc=4), reported only after the deadline.
        # Every probe child was reaped, so the CPU-only ingest A/B can
        # still bank its line (the pre-reserve ~35% window is > its
        # 420 s floor); it prints FIRST so the device fail line stays
        # the last training-axis line.
        maybe_run_ingest(deadline)
        note = (f"probe failed after {e.attempts} attempt(s) across "
                f"{BENCH_WATCHDOG_SEC}s window: {e.last!r}")
        print(_fail_line(note, status="device_unreachable"), flush=True)
        if BENCH_PREDICT:
            print(_predict_fail_line(note, status="device_unreachable"),
                  flush=True)
        return RC_DEVICE_UNREACHABLE
    except _ProbeStuck as e:
        # NO ingest here: the unkillable probe is still alive on the
        # box — same skip rule as parked children
        note = f"probe stalled and unkillable: {e}"
        print(_fail_line(note, status="device_unreachable"), flush=True)
        if BENCH_PREDICT:
            print(_predict_fail_line(note, status="device_unreachable"),
                  flush=True)
        return RC_DEVICE_UNREACHABLE
    except _ProbeCodeFailure as e:
        maybe_run_ingest(deadline)
        print(_fail_line(
            f"probe failed (code failure, not retried): {e}",
            status="no_result"), flush=True)
        if BENCH_PREDICT:
            print(_predict_fail_line(
                f"probe failed (code failure, not retried): {e}"),
                flush=True)
        return RC_NO_RESULT

    # ---- measurement stages: phase-aware liveness instead of fixed
    # slots. Each sched's children get the FULL remaining watchdog as
    # their hard deadline: an ADVANCING child (compiling with live
    # keepalives, iterating) deserves the window — the old 70% slot
    # split existed only because blind slots could not tell advancing
    # from wedged. A STALLED child is classified within its phase's
    # stall budget (not the full watchdog), SIGTERMed, and retried
    # under the shared RetryPolicy — with the compile cache warm the
    # retry skips the recompile. Partial results banked by any attempt
    # are SALVAGED if every attempt ultimately fails.
    class _ChildNoResult(Exception):
        """Child exited without a result line — a code failure, not a
        device symptom: never retried."""

    def _measure_classifier(exc: BaseException) -> bool:
        # the embedded stderr tail may contain strings the generic
        # classifier would match ("timed out" in an unrelated child
        # traceback) — a no-result exit is terminal no matter what
        if isinstance(exc, (_ChildNoResult, _ParkedChild)):
            return False
        return is_transient_error(exc)

    salvage_files: list = []   # (sched, partial_path), attempt order
    predict_salvage_files: list = []   # (sched, predict_partial_path)
    parked_pid = {"pid": None}

    def _best_banked(files, progress_key):
        """Best banked partial across attempts, by measured progress —
        the ONE selection rule for both metric lines."""
        best = None
        for _, p in files:
            rec = _read_partial(p)
            if rec is None:
                continue
            if best is None or int(rec.get(progress_key, 0)) >= \
                    int(best.get(progress_key, 0)):
                best = rec
        return best

    def _salvage_decorate(rec: dict, note: str) -> dict:
        """The ONE salvage-record shape (status/note/parked fields) both
        metric lines share — tpu_session_auto keys on these fields."""
        rec = dict(rec)
        rec.pop("partial", None)
        rec["status"] = "salvaged"
        rec["note"] = note
        if parked_pid["pid"] is not None:
            rec["parked"] = True
            rec["parked_pid"] = parked_pid["pid"]
        return rec

    def best_salvage():
        return _best_banked(salvage_files, "iters_done")

    def emit_predict_line(line, failed_stage: str, reason: str) -> None:
        """Second metric line (inference axis): the child's own line when
        it produced one (run_child prints its own 0.0 fail line when the
        predict stage dies after a successful training print), else the
        best banked predict partial with status=salvaged. A failed run
        that never reached the predict stage emits NOTHING here — the
        training salvage/fail line stays the LAST line, which downstream
        consumers (test_heartbeat, session logs) key on."""
        if not BENCH_PREDICT:
            return
        if line is not None:
            print(line, flush=True)
            return
        best = _best_banked(predict_salvage_files, "rows_done")
        if best is not None:
            print(json.dumps(_salvage_decorate(
                best,
                f"salvaged: last banked predict partial "
                f"({best.get('rows_done')} rows, path="
                f"{best.get('path', 'final')}); "
                f"{failed_stage}: {reason}")), flush=True)

    def emit_salvaged(failed_stage: str, reason: str) -> bool:
        """Print the last banked stage metric (with a "salvaged" note
        naming the failed stage) instead of an unconditional 0.0. Only
        when NOTHING ever banked does the caller fall through to the
        0.0 line."""
        rec = best_salvage()
        if rec is None:
            return False
        # parked/parked_pid are load-bearing for tpu_session_auto.py: a
        # parked child may still hold the device claim — no further
        # session claims (attached by _salvage_decorate)
        print(json.dumps(_salvage_decorate(
            rec,
            f"salvaged: last banked partial "
            f"({rec.get('iters_done')} iters, "
            f"sched={rec.get('sched')}); failed stage "
            f"{failed_stage}: {reason}")), flush=True)
        return True

    # a fresh measurement child needs at least this much window to be
    # supervisable at all (startup + first beats); launching into a
    # near-exhausted watchdog would make a seconds-old WAITING child hit
    # the hard deadline instantly and be mis-parked, stopping the whole
    # session for nothing
    measure_min_slot = min(60.0, BENCH_WATCHDOG_SEC * 0.3)

    def measure_attempt(sched: str) -> tuple:
        """One supervised measurement child; returns (training result
        line, predict result line or None)."""
        remaining = deadline - time.time()
        if remaining < measure_min_slot:
            raise _ChildNoResult(
                f"sched={sched}: only {remaining:.0f}s of watchdog "
                f"remain (< {measure_min_slot:.0f}s floor) — not "
                "launching a fresh measurement child")
        child = _ChildSpawn({"_LGBM_BENCH_CHILD": sched},
                            tag=f"child_{sched}", partial=True)
        salvage_files.append((sched, child.partial_path))
        predict_salvage_files.append(
            (sched, getattr(child, "predict_partial_path", "")))
        try:
            rc = watch_child(
                child.proc, child.hb_path, policy=stall_policy,
                hard_deadline=time.monotonic() + (deadline - time.time()),
                poll=watch_poll, relay=hb_self,
                label=f"measurement sched={sched}")
        except StillAlive as e:
            # alive AND advancing at the watchdog: park (never kill a
            # claim holder), skip every remaining stage
            child.fail_cleanup()
            parked_pid["pid"] = e.pid
            raise _ParkedChild(
                f"measurement child pid={e.pid} still advancing at the "
                "watchdog deadline; left alive (parked) to avoid the "
                "mid-compile claim-holder kill wedge") from None
        except DeviceStallError:
            if not child.fail_cleanup():
                # hung AND unkillable (ignored SIGTERM): treat as
                # parked — a fresh claim must not stack on it
                parked_pid["pid"] = child.proc.pid
                raise _ParkedChild(
                    f"stalled measurement child pid={child.proc.pid} "
                    "ignored SIGTERM; left running (parked)") from None
            raise       # transient: the retry policy relaunches
        out, err = child.read_streams()
        child.cleanup()
        sys.stderr.write(err[-4000:])
        train_line = predict_line = None
        for ln in out.splitlines():
            ln = ln.strip()
            if not ln.startswith("{"):
                continue
            if '"iters/sec"' in ln and train_line is None:
                train_line = ln
            elif '"rows/sec"' in ln and predict_line is None:
                predict_line = ln
        if train_line is not None:
            return train_line, predict_line
        raise _ChildNoResult(
            f"sched={sched} exited rc={rc} without a result: "
            f"{err[-300:]!r}")

    try:
        last_note = "no scheduling mode completed"
        for sched in [s.strip() for s in SCHED_MODES]:
            budget = deadline - time.time()
            if budget <= 5:
                last_note = f"watchdog exhausted before trying sched={sched}"
                break
            measure_policy = RetryPolicy(
                max_attempts=BENCH_MEASURE_ATTEMPTS, base_delay=2.0,
                max_delay=15.0, deadline=max(budget, 1.0),
                classifier=_measure_classifier)
            try:
                line, predict_line = retry_call(
                    measure_attempt, sched, policy=measure_policy,
                    what=f"bench measurement sched={sched}")
                print(line, flush=True)
                emit_predict_line(predict_line, f"sched={sched}",
                                  "child exited without a predict line")
                maybe_run_ingest(deadline)
                maybe_run_comms_ab(deadline)
                return 0
            except _ParkedChild as e:
                # status "parked" (or a salvaged line with parked=true) is
                # load-bearing: tpu_session_auto.py keys on it to skip ALL
                # remaining session stages — a parked grandchild still
                # holds the device claim, and any fresh claim stacked on
                # it is the documented wedge trigger
                if emit_salvaged(f"sched={sched}", str(e)):
                    emit_predict_line(None, f"sched={sched}", str(e))
                    return 0
                print(_fail_line(
                    f"sched={sched}: {e} — remaining stages skipped",
                    status="parked"), flush=True)
                emit_predict_line(None, f"sched={sched}",
                                  f"parked: {e}")
                return RC_NO_RESULT
            except RetryError as e:
                # every relaunch stalled: salvage whatever a timed loop
                # banked before the device went quiet. Children were
                # reaped (not parked), so the CPU-only ingest A/B still
                # banks its line — before the salvage lines, which stay
                # last.
                if best_salvage() is not None:
                    maybe_run_ingest(deadline)
                if emit_salvaged(f"sched={sched}", str(e)):
                    emit_predict_line(None, f"sched={sched}", str(e))
                    return 0
                last_note = (f"sched={sched} stalled through "
                             f"{e.attempts} attempt(s): {e.last!r}")
                continue
            except _ChildNoResult as e:
                last_note = str(e)
                continue
        # exiting without a training result; children were reaped (the
        # parked path returned above), so the CPU-only ingest/comms
        # lines can still bank
        maybe_run_ingest(deadline)
        maybe_run_comms_ab(deadline)
        if emit_salvaged("all scheduling modes", last_note):
            emit_predict_line(None, "all scheduling modes", last_note)
            return 0
        print(_fail_line(last_note), flush=True)
        emit_predict_line(None, "all scheduling modes", last_note)
        return RC_NO_RESULT
    finally:
        # banked partials were read by emit_salvaged above;
        # drop them unless a parked child still writes there
        if parked_pid["pid"] is None:
            for _, pth in salvage_files + predict_salvage_files:
                try:
                    os.unlink(pth)
                except OSError:
                    pass


if __name__ == "__main__":
    sys.exit(main())
