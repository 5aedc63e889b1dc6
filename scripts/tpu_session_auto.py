"""Unattended TPU measurement session (round 5).

Runs the full measurement ladder without a human in the loop:
headline benches, kernel/packing A/Bs, an automatic
flip of the staged defaults into the tuned cache
(``lightgbm_tpu/TUNED.json``) when the A/Bs hold, tuned re-runs, the
10.5M Higgs-shape number, and the leaves ladder. Artifacts land in
``bench_logs/`` (MEASURED_r05.json is rewritten after every stage so a
mid-session wedge still leaves evidence) and everything is committed to
git at the end.

Run by hand on a machine that holds the chip. All stages run
sequentially — one process on the chip at a time.

Round-6 hardening (VERDICT weak #1): the DRIVER-SHAPED 1M stage runs
FIRST so the official number banks before anything can close the
window, and a stage that outlives its deadline is PARKED — left
running to finish its compile and release the claim cleanly — with
every remaining stage skipped. No SIGKILL ever reaches a process that
may hold the device claim.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGDIR = os.path.join(REPO, "bench_logs")
MEASURED = os.path.join(LOGDIR, "MEASURED_r05.json")
T0 = time.time()

sys.path.insert(0, REPO)
from lightgbm_tpu.robustness import heartbeat  # noqa: E402
from lightgbm_tpu.utils.jit_cache import (ENV_COMPILE_CACHE,  # noqa: E402
                                          resolve_cache_dir)

# ISSUE 4: one persistent compile cache for EVERY stage of the session
# (and every bench child under them) — a stage relaunched after a park/
# stall, or simply the next stage at the same shape, reads the previous
# compile from disk instead of repaying the multi-minute remote compile
# that used to eat stage deadlines.
SESSION_CACHE = os.environ.get(ENV_COMPILE_CACHE) or resolve_cache_dir()

# heartbeat-aware stage extension: a stage past its deadline whose bench
# tree is still ADVANCING (bench.py relays grandchild beats onto its own
# heartbeat file) gets up to this much extra wall-clock before parking;
# a stage gone heartbeat-silent parks at the deadline, classified as a
# stall rather than as slow.
STALL_EXTEND_SEC = int(os.environ.get("SESSION_STALL_EXTEND_SEC", 1500))

# consecutive stages that come back "device unreachable" before we
# conclude the window closed and hand control back to the watcher
MAX_CONSEC_FAILS = 2

RESULTS: list[dict] = []
STATE: dict = {"started_unix": time.time(), "stages": [], "flips": {}}


def say(msg: str) -> None:
    print(f"[session +{time.time() - T0:7.1f}s] {msg}", flush=True)


# a stage that outlived its deadline and was left running: its bench
# tree may hold the device claim mid-compile, and SIGKILLing that is
# the documented machine-wide wedge trigger (VERDICT weak #1 — it
# zeroed BENCH_r0{3,4,5}.json three rounds running). The session skips
# every remaining stage instead.
PARKED: dict = {"proc": None, "stage": None}


class SessionParked(Exception):
    """Raised when a stage is parked: no further device claims may be
    made by this session (a parked claim-holder plus a fresh claim =
    stacked claims = the wedge)."""


def _run_stage(cmd: list, env: dict, timeout: float, logpath: str):
    """Run *cmd* in its own process group with output to FILES (so an
    abandoned child can never block on a pipe). NEVER kills on
    timeout: the child is parked — left running to finish its compile
    and release the claim cleanly — and (stdout_text, timed_out=True)
    is returned with whatever output it produced so far.

    ISSUE 4: the deadline is heartbeat-aware. The bench parent beats at
    ``<logpath>.hb`` (relaying its grandchildren's phase/progress), and
    a stage past ``timeout`` whose heartbeat still ADVANCES is granted
    up to STALL_EXTEND_SEC more — a healthy long compile is not a
    wedge. A stage whose heartbeat went silent parks at the deadline
    with a "stalled" classification in the log (still no kill: the
    grandchild may hold the device claim)."""
    hb_path = logpath + ".hb"
    policy = heartbeat.StallPolicy.from_env()
    with open(logpath + ".stdout", "w", encoding="utf-8") as out_f, \
            open(logpath, "a", encoding="utf-8") as err_f:
        proc = subprocess.Popen(
            cmd, env=dict(env, LGBM_TPU_HEARTBEAT=hb_path), cwd=REPO,
            text=True, start_new_session=True,
            stdout=out_f, stderr=err_f)
        timed_out = False
        verdict = "alive"
        started = time.monotonic()
        base_deadline = started + timeout
        hard_deadline = base_deadline + STALL_EXTEND_SEC
        extending = False
        while True:
            try:
                proc.wait(timeout=5.0)
                break
            except subprocess.TimeoutExpired:
                pass
            now = time.monotonic()
            if now < base_deadline:
                continue
            rec = heartbeat.read(hb_path)
            verdict = policy.classify(rec, now, started)
            if verdict == heartbeat.ALIVE and now < hard_deadline:
                if not extending:
                    extending = True
                    say(f"stage deadline reached but the bench tree is "
                        f"ALIVE (phase {rec.phase!r} progress "
                        f"{rec.progress}); extending up to "
                        f"{STALL_EXTEND_SEC}s instead of parking")
                continue
            timed_out = True
            PARKED["proc"] = proc
            with open(logpath, "a", encoding="utf-8") as f2:
                f2.write(f"stage liveness verdict at park: {verdict} "
                         f"(hb={rec!r})\n")
            break
    with open(logpath + ".stdout", "r", encoding="utf-8",
              errors="replace") as f:
        stdout = f.read()
    return stdout, timed_out


def dump_state() -> None:
    os.makedirs(LOGDIR, exist_ok=True)
    STATE["results"] = RESULTS
    STATE["elapsed_sec"] = round(time.time() - T0, 1)
    with open(MEASURED, "w", encoding="utf-8") as f:
        json.dump(STATE, f, indent=1)
        f.write("\n")


def run_bench(stage: str, rows: int, iters: int, extra: dict | None = None,
              leaves: int | None = None, watchdog: int = 1700,
              scheds: str | None = None,
              env_extra: dict | None = None) -> dict | None:
    """One bench.py invocation; returns the parsed JSON result or None."""
    env = dict(os.environ,
               BENCH_ROWS=str(rows), BENCH_ITERS=str(iters),
               BENCH_WATCHDOG_SEC=str(watchdog))
    env[ENV_COMPILE_CACHE] = SESSION_CACHE
    # the replicated-vs-sharded ingest A/B runs ONCE as its own stage
    # (run_ingest_stage), not inside every training stage's window
    env.setdefault("BENCH_INGEST", "0")
    if scheds is not None:
        env["BENCH_SCHEDS"] = scheds
    if env_extra:
        env.update(env_extra)
    if extra:
        env["BENCH_EXTRA"] = json.dumps(extra)
    if leaves is not None:
        env["BENCH_LEAVES"] = str(leaves)
    if PARKED["proc"] is not None and PARKED["proc"].poll() is None:
        # a previous stage is parked and still alive — no new claims
        raise SessionParked(
            f"stage {stage} skipped: stage {PARKED['stage']!r} is "
            f"parked (pid={PARKED['proc'].pid} still running)")
    say(f"stage {stage}: rows={rows} iters={iters} extra={extra} "
        f"leaves={leaves}")
    logpath = os.path.join(LOGDIR, f"r05_{stage}.log")
    # bench.py's internal watchdog is the normal exit path; this outer
    # deadline only fires if bench.py itself wedges. On expiry the
    # bench tree is PARKED, never killed: its grandchild may hold the
    # device claim mid-compile, and a SIGKILL there is the documented
    # machine-wide wedge trigger (VERDICT weak #1 — three rounds of
    # zeroed BENCH json). Remaining stages are skipped via
    # SessionParked so no fresh claim can stack on the parked one.
    stdout, timed_out = _run_stage(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, timeout=watchdog + 300, logpath=logpath)
    if timed_out:
        PARKED["stage"] = stage
        with open(logpath, "a", encoding="utf-8") as f:
            f.write(f"PARKED after {watchdog + 300}s (left running; "
                    "session skips remaining stages)\n")
        say(f"stage {stage}: deadline expired — child PARKED (pid="
            f"{PARKED['proc'].pid}), skipping all remaining stages")
        raise SessionParked(f"stage {stage} parked at its deadline")
    proc_stdout = stdout
    result = None
    for ln in proc_stdout.splitlines():
        ln = ln.strip()
        if ln.startswith("{") and '"iters/sec"' in ln:
            try:
                result = json.loads(ln)
            except ValueError:
                pass
    if result is not None:
        result["stage"] = stage
        RESULTS.append(result)
        if result.get("status") == "parked" or result.get("parked"):
            # bench.py exited but left a claim-holding grandchild
            # RUNNING (its internal watchdog preempts ours, so the
            # PARKED proc-handle guard above never sees it) — no
            # further claims from this session. A "salvaged" result
            # with parked=true still BANKED its partial metric above
            # before the park stops the session.
            dump_state()
            raise SessionParked(
                f"stage {stage}: bench parked a claim-holding child"
                + (f" (salvaged {result.get('value')} it/s first)"
                   if result.get("status") == "salvaged" else ""))
        say(f"stage {stage}: {result.get('value')} it/s "
            f"(vs_baseline {result.get('vs_baseline')})"
            + (" [salvaged]" if result.get("status") == "salvaged"
               else ""))
    else:
        say(f"stage {stage}: no result line")
    STATE["stages"].append({"stage": stage,
                            "ok": bool(result and result.get("value", 0) > 0)})
    dump_state()
    return result


def value(res: dict | None) -> float:
    return float(res.get("value", 0.0)) if res else 0.0


def pick_flips(base: float, pallas: float, packed: float,
               both: float) -> dict:
    """Tuned-default selection from the exactness-preserving A/Bs.

    Returns the MEASURED-best configuration — never a composition that
    was not itself measured to win (the two flips can interact
    negatively). The 3% margin guards run-to-run noise; ties keep the
    current defaults.
    """
    if base <= 0:
        return {}
    cands = [
        (both, {"f32_hist_kernel": "pallas", "packed_bins": True}),
        (pallas, {"f32_hist_kernel": "pallas"}),
        (packed, {"packed_bins": True}),
    ]
    best_v, best_f = max(cands, key=lambda c: c[0])
    return best_f if best_v > base * 1.03 else {}


def unreachable(res: dict | None) -> bool:
    if res is None:
        return True
    if "status" in res:  # bench.py structured status (rc=4 companion)
        return res["status"] == "device_unreachable"
    # pre-status payloads (BENCH_r05.json and earlier): note text only
    return (res.get("value", 1) == 0 and
            "unreachable" in str(res.get("note", "")))


TUNED_PATH = os.path.join(REPO, "lightgbm_tpu", "TUNED.json")
TUNED_STASH = os.path.join(LOGDIR, "TUNED.stash.json")


def stash_tuned() -> None:
    """Move the tuned cache aside so base/A-B stages measure BUILT-IN
    defaults (a rerun with flips active compares flipped baselines
    against themselves and un-learns real winners — observed
    2026-08-01). The stash lives ON DISK so a killed session can't
    lose it; a leftover stash from a crash is restored first."""
    if os.path.exists(TUNED_STASH) and not os.path.exists(TUNED_PATH):
        os.replace(TUNED_STASH, TUNED_PATH)
        say("recovered tuned cache from a previous session's stash")
    if os.path.exists(TUNED_PATH):
        os.replace(TUNED_PATH, TUNED_STASH)
        say("tuned cache stashed for unbiased A/Bs")


def restore_tuned() -> None:
    """Put the stashed cache back (no fresh flips were written)."""
    if os.path.exists(TUNED_STASH) and not os.path.exists(TUNED_PATH):
        os.replace(TUNED_STASH, TUNED_PATH)
        say("tuned cache restored (session ended before new flips)")


def git_commit(msg: str) -> None:
    try:
        # every commit is an exit-path act: put the stashed tuned cache
        # back first (no-op when fresh flips already merged it) so no
        # commit can ever stage a deleted TUNED.json or the stash file
        restore_tuned()
        # separate adds: a missing TUNED.json (no flips written) must
        # not fail the pathspec atomically and leave the logs unstaged
        subprocess.run(["git", "add", "bench_logs"],
                       cwd=REPO, check=False, capture_output=True)
        subprocess.run(["git", "add", "lightgbm_tpu/TUNED.json"],
                       cwd=REPO, check=False, capture_output=True)
        subprocess.run(["git", "commit", "-m", msg],
                       cwd=REPO, check=False, capture_output=True)
    except Exception as e:  # noqa: BLE001
        say(f"git commit failed: {e}")


def main() -> int:
    os.makedirs(LOGDIR, exist_ok=True)
    stash_tuned()
    try:
        return _stages()
    except SessionParked as e:
        # a stage deadline expired with a live (possibly claim-holding)
        # bench tree: it was left running and every later stage is
        # skipped — never SIGKILL a claim holder, never stack claims
        say(f"session parked: {e}")
        STATE["parked"] = str(e)
        dump_state()
        git_commit("bench_logs: session parked at a stage deadline "
                   "(claim holder left running, no kill)")
        return 3
    finally:
        # any exit path that did not merge fresh flips (exception,
        # guard bail, watcher kill that still lets finally run)
        # restores the previous measured winners
        restore_tuned()


def _stages() -> int:
    fails = 0

    def guard(res: dict | None) -> bool:
        """Track consecutive dead stages; True means bail out."""
        nonlocal fails
        fails = fails + 1 if unreachable(res) else 0
        return fails >= MAX_CONSEC_FAILS

    # ---- stage 0: the DRIVER-SHAPED 1M headline FIRST (VERDICT weak
    # #1: three rounds running, the official BENCH_r0X.json stayed 0.0
    # because this exact shape only ran after earlier stages had
    # wedged the device — bank the official number before anything
    # else can park or close the window)
    h1m = run_bench("headline_1m", 1_000_000, 20)
    if guard(h1m):
        say("window closed during headline_1m — bailing")
        git_commit("bench_logs: r6 session aborted at the 1M headline")
        return 3

    # ---- stage 0.5: hybrid level scheduling at the SAME driver shape
    # (round-7 tentpole: 255 leaves / max_depth=-1 is level-eligible
    # now — headline_1m above is its compact baseline pair; ≥1.5x here
    # makes level the default scheduler for the headline)
    h1m_lvl = run_bench("headline_1m_level", 1_000_000, 20,
                        scheds="level")
    if guard(h1m_lvl):
        git_commit("bench_logs: r6 partial session (compact 1M only)")
        return 3

    # ---- stage 0.8: replicated-vs-sharded ingest A/B at the 10.5M
    # reference shape (ISSUE 7). The gang runs on VIRTUAL CPU devices
    # and never touches the device claim — zero wedge risk — so it can
    # run right after the headlines bank; only wall time is spent.
    # Never gates the session: a failure logs and moves on.
    try:
        ingest_env = dict(os.environ, BENCH_INGEST_ONLY="1",
                          BENCH_WATCHDOG_SEC="1500")
        ingest_env[ENV_COMPILE_CACHE] = SESSION_CACHE
        say("stage ingest_ab: replicated-vs-sharded ingest at 10.5M")
        ing_out, ing_timeout = _run_stage(
            [sys.executable, os.path.join(REPO, "bench.py")],
            env=ingest_env, timeout=1600,
            logpath=os.path.join(LOGDIR, "r05_ingest_ab.log"))
        ing_res = None
        if ing_timeout:
            # unlike training stages, this gang runs on virtual CPU
            # devices — it holds NO device claim, so parking semantics
            # do not apply: stop it and clear the park so the session
            # continues
            import signal as _signal
            p = PARKED.get("proc")
            if p is not None and p.poll() is None:
                try:
                    os.killpg(os.getpgid(p.pid), _signal.SIGTERM)
                except OSError:
                    pass
            PARKED["proc"] = None
            say("stage ingest_ab: timed out (CPU-only gang stopped; "
                "session continues)")
        else:
            for ln in ing_out.splitlines():
                ln = ln.strip()
                if ln.startswith("{") and '"ingest_synth' in ln:
                    ing_res = json.loads(ln)
        if ing_res is not None:
            ing_res["stage"] = "ingest_ab"
            RESULTS.append(ing_res)
            say(f"stage ingest_ab: sharded {ing_res.get('value')}s vs "
                f"replicated {ing_res.get('replicated_sec')}s, rss "
                f"ratio {ing_res.get('rss_ratio')}")
        else:
            say("stage ingest_ab: no result line (continuing)")
        STATE["stages"].append({"stage": "ingest_ab",
                                "ok": bool(ing_res and
                                           ing_res.get("value", 0) > 0)})
        dump_state()
    except Exception as e:  # noqa: BLE001 — informational stage only
        say(f"stage ingest_ab failed: {e!r} (continuing)")

    # ---- stage 00: micro number (16k rows, 31 leaves, seconds of
    # compile); the _L31 suffix keeps it from masquerading as the
    # headline metric
    micro = run_bench("micro_16k", 16_384, 10, leaves=31, watchdog=900)
    if guard(micro):
        say("window closed during micro_16k — bailing")
        git_commit("bench_logs: r6 partial session (1M headlines landed)")
        return 3

    # ---- stage 1: the 100k headline (compile-cache warm by now)
    h100 = run_bench("headline_100k", 100_000, 30, watchdog=1500)
    if guard(h100):
        say("window closed during headline_100k — bailing")
        git_commit("bench_logs: r6 session aborted (device window closed; "
                   "1M + micro numbers landed)")
        return 3

    # ---- stage 2: A/Bs at 100k (compile-dominated, fast turnaround).
    # Exactness-preserving candidates first (they can become defaults),
    # then the opt-in dtype/quantized modes for the runbook tables.
    ab_pallas = run_bench("ab_pallas", 100_000, 30,
                          {"tpu_hist_kernel": "pallas"}, watchdog=1500)
    if guard(ab_pallas):
        git_commit("bench_logs: r5 partial session (headlines only)")
        return 3
    ab_packed = run_bench("ab_packed", 100_000, 30,
                          {"tpu_packed_bins": "true"}, watchdog=1500)
    if guard(ab_packed):
        git_commit("bench_logs: r5 partial session (headlines + 1 A/B)")
        return 3
    ab_both = run_bench("ab_pallas_packed", 100_000, 30,
                        {"tpu_hist_kernel": "pallas",
                         "tpu_packed_bins": "true"}, watchdog=1500)
    if guard(ab_both):
        git_commit("bench_logs: r5 partial session (headlines + partial A/B)")
        return 3
    # informational dtype/quantized modes (runbook tables; not flip
    # candidates — they trade exactness). Run BEFORE the flip write so
    # their numbers are pure deltas against base_100k, not conflated
    # with a just-flipped default.
    ab_bf16 = run_bench("ab_bf16", 100_000, 30,
                        {"tpu_hist_dtype": "bfloat16"}, watchdog=1500)
    bf16_dead = guard(ab_bf16)
    ab_quant = None
    if not bf16_dead:
        ab_quant = run_bench("ab_quant", 100_000, 30,
                             {"use_quantized_grad": True}, watchdog=1500)

    # ---- stage 3: flip tuned defaults the measurements justify (see
    # pick_flips; both candidates are exactness-preserving — the
    # bf16-triple Pallas kernel is f32-exact by construction and
    # CPU-parity-tested; packed bins change gather layout only)
    base = value(h100)
    flips = pick_flips(base, value(ab_pallas), value(ab_packed),
                       value(ab_both))
    if flips:
        sys.path.insert(0, REPO)
        from lightgbm_tpu import tuned
        # restore the stashed keys FIRST so write() merges the new
        # flips on top — previously measured keys the flip candidates
        # don't produce (e.g. flip_min_rows) must survive the session
        restore_tuned()
        tuned.reload()
        path = tuned.write(flips)
        say(f"tuned flips written to {path}: {flips}")
    else:
        say("no tuned flips justified by the A/Bs")
    STATE["flips"] = flips
    STATE["ab_summary"] = {
        "base_100k": base, "pallas": value(ab_pallas),
        "packed": value(ab_packed), "both": value(ab_both),
        "bf16": value(ab_bf16), "quant": value(ab_quant)}
    dump_state()
    if bf16_dead or guard(ab_quant):
        git_commit(f"bench_logs: r5 partial session (flips {flips or 'none'})")
        return 3

    # ---- stage 4: tuned re-runs (defaults now include the flips) + the
    # Higgs-scale number the verdict demands
    final_1m = run_bench("final_1m", 1_000_000, 20)
    if guard(final_1m):
        git_commit("bench_logs: r5 session (A/Bs done, window closed "
                   "before final runs)")
        return 3
    # ---- stage 4.5: one TIMETAG diagnostic run at 1M — the section
    # table (stderr -> r05_diag_1m.log) localizes where the ~320 ms/tree
    # goes (gather / hist / partition / split-scan / pool writes); its
    # throughput number is informational (host-side sync per section
    # serializes the async pipeline)
    run_bench("diag_1m", 1_000_000, 12,
              env_extra={"LIGHTGBM_TPU_TIMETAG": "1"})

    # ---- stage 4.6: level-vs-compact A/B at a depth-capped config
    # (the level grower's first device measurement — informational, the
    # metric suffix carries the non-headline config). BOTH arms pin the
    # einsum kernel so the pair differs ONLY in scheduling (the tuned
    # flip would otherwise put pallas under the compact arm), and the
    # level arm selects its scheduler through BENCH_SCHEDS so bench.py
    # labels the result correctly and has no phantom fallback rerun.
    lvl_kw = {"max_depth": 10, "tpu_hist_kernel": "einsum"}
    run_bench("ab_depth10_compact", 1_000_000, 15, lvl_kw,
              scheds="compact")
    run_bench("ab_depth10_level", 1_000_000, 15, lvl_kw,
              scheds="level")

    # ---- stage 4.7 (ISSUE 6): level-histogram kernel A/B + the
    # TUNED.json re-learn. One raw-kernel table from the microbench
    # (depth 4/7/10 x F x quantized — goes to the runbook), then three
    # end-to-end arms at the depth-10 level shape differing ONLY in
    # tpu_hist_kernel; every BENCH record carries the resolved backend
    # (bench.py level_backend), so these numbers are attributable. The
    # winner is written to TUNED.json's level_hist_backend (consulted
    # by resolve_level_hist_kernel under tpu_hist_kernel=auto) with the
    # same 3% noise margin as pick_flips; einsum (the blocks
    # composition) is the incumbent default.
    mb_log = os.path.join(LOGDIR, "r06_microbench_hist_level.log")
    _run_stage([sys.executable, os.path.join(REPO, "microbench.py"),
                "hist_level"],
               env=dict(os.environ, **{ENV_COMPILE_CACHE: SESSION_CACHE}),
               timeout=1500, logpath=mb_log)
    lvl_arms = {}
    lvl_window_closed = False
    for kern in ("scatter", "pallas_level"):
        res = run_bench(f"ab_level_kernel_{kern}", 1_000_000, 15,
                        {"max_depth": 10, "tpu_hist_kernel": kern},
                        scheds="level")
        lvl_arms[kern] = value(res)
        if guard(res):
            lvl_window_closed = True
            break
    # incumbent = the einsum-blocks arm already measured as
    # ab_depth10_level above
    lvl_base = 0.0
    for r in RESULTS:
        if r.get("stage") == "ab_depth10_level":
            lvl_base = value(r)
    best_kern, best_v = max(lvl_arms.items(), key=lambda kv: kv[1],
                            default=("einsum", 0.0))
    if lvl_base > 0 and best_v > lvl_base * 1.03:
        sys.path.insert(0, REPO)
        from lightgbm_tpu import tuned
        restore_tuned()
        tuned.reload()
        path = tuned.write({"level_hist_backend": best_kern})
        say(f"level_hist_backend={best_kern} written to {path} "
            f"({best_v:.3f} vs einsum-blocks {lvl_base:.3f} it/s)")
    else:
        say(f"level_hist_backend stays einsum (arms {lvl_arms}, "
            f"base {lvl_base})")
    STATE["level_kernel_ab"] = dict(lvl_arms, einsum=lvl_base)
    dump_state()
    if lvl_window_closed:
        # same discipline as every other guard site: do NOT point a
        # fresh claim (the ladder / 10.5M stages) at a dead or wedged
        # device — bail with whatever landed
        say("window closed during the level-kernel A/B — bailing")
        git_commit("bench_logs: r6 partial session (level-kernel A/B "
                   "cut short; headlines landed)")
        return 3

    # ---- stage 4.8 (ISSUE 12): histogram-collective A/B + the
    # TUNED.json hist_reduce re-learn. Two end-to-end data-parallel
    # arms at the 1M depth-10 shape differing ONLY in tpu_hist_reduce;
    # every BENCH record carries the engine's resolved collective
    # (bench.py hist_reduce field), and the write REQUIRES both arms to
    # have attributed to their requested mode — a 1-core window remaps
    # tree_learner=data to serial (hist_reduce "n/a") and two identical
    # programs must never tune the cache. Same 3% noise margin as
    # pick_flips; allreduce is the incumbent.
    hr_arms = {}
    hr_attr = {}
    hr_window_closed = False
    for hr in ("allreduce", "reduce_scatter"):
        res = run_bench(f"ab_hist_reduce_{hr}", 1_000_000, 15,
                        {"max_depth": 10, "tree_learner": "data",
                         "tpu_hist_reduce": hr},
                        scheds="compact")
        hr_arms[hr] = value(res)
        hr_attr[hr] = (res or {}).get("hist_reduce", "unknown")
        if guard(res):
            hr_window_closed = True
            break
    hr_attributed = (hr_attr.get("allreduce") == "allreduce" and
                     hr_attr.get("reduce_scatter") == "reduce_scatter")
    if (hr_attributed and hr_arms.get("allreduce", 0) > 0 and
            hr_arms.get("reduce_scatter", 0) >
            hr_arms["allreduce"] * 1.03):
        sys.path.insert(0, REPO)
        from lightgbm_tpu import tuned
        restore_tuned()
        tuned.reload()
        path = tuned.write({"hist_reduce": "reduce_scatter"})
        say(f"hist_reduce=reduce_scatter written to {path} "
            f"({hr_arms['reduce_scatter']:.3f} vs allreduce "
            f"{hr_arms['allreduce']:.3f} it/s)")
    else:
        say(f"hist_reduce stays allreduce (arms {hr_arms}, "
            f"attribution {hr_attr})")
    STATE["hist_reduce_ab"] = dict(hr_arms, attribution=hr_attr)
    dump_state()
    if hr_window_closed:
        say("window closed during the hist-reduce A/B — bailing")
        git_commit("bench_logs: partial session (hist-reduce A/B cut "
                   "short; headlines landed)")
        return 3

    # ---- stage 5: leaves ladder at 1M (fixed-cost curve for the
    # runbook) runs BEFORE the 10.5M stage: the big shape's compiles
    # have been pathological (a 31-leaf probe alone once took 254 s),
    # and a watchdog kill there is a
    # mid-compile claim-holder kill — the documented machine-wide wedge
    # trigger, which then zeroes everything after it.
    window_closed = False
    for lv in (31, 63, 127):
        res = run_bench(f"ladder_L{lv}", 1_000_000, 15, leaves=lv)
        if guard(res):
            window_closed = True
            break

    best_1m = max(value(final_1m), value(h1m))
    if window_closed:
        # do NOT point a 3700 s claim at a dead/wedged device — that is
        # the mid-compile claim-holder kill scenario all over again
        say("window closed during the ladder — skipping the 10.5M stage")
        git_commit(
            f"bench_logs: r5 partial session — 1M {best_1m:.2f} it/s, "
            f"flips {flips or 'none'} (window closed before 10.5M)")
        return 3

    # ---- stage 6: the Higgs-scale number, LAST (wedge risk): one
    # scheduler only and a watchdog sized so compile + 10 iters fit
    # without the kill path firing
    run_bench("final_10m", 10_500_000, 10, watchdog=3400,
              scheds="compact")

    STATE["done"] = True
    dump_state()
    git_commit(
        f"bench_logs: r5 measured session — 1M {best_1m:.2f} it/s, "
        f"flips {flips or 'none'}")
    say("session complete")
    return 0


if __name__ == "__main__":
    sys.exit(main())
