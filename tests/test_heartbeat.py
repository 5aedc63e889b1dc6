"""Heartbeat-aware execution supervision (ISSUE 4).

Covers the acceptance criteria on CPU:

- heartbeat file round-trip and torn-write tolerance;
- supervisor phase-deadline decisions under a fake clock: a
  compile-long child with live keepalives survives, an iter-advancing
  child is never parked before the hard deadline, a silent child is
  classified hung WITHIN the stall budget (not the full watchdog);
- an injected ``hang`` recovered by the shared RetryPolicy (stalled
  attempt classified + terminated, relaunch succeeds);
- the persistent compile cache honored by the engine
  (``tpu_compile_cache_dir`` / ``LGBM_TPU_COMPILE_CACHE``) and a warm
  relaunch skipping recompilation, asserted via the dispatch-guard
  compile counter's persistent-cache-hit channel;
- retry.py window accounting: attempt slots clipped to the policy's
  remaining deadline, backoff sleeps that would exhaust the deadline
  skipped.
"""
import json
import os
import subprocess
import sys
import time

import pytest

from lightgbm_tpu.robustness import faults, heartbeat
from lightgbm_tpu.robustness.heartbeat import (ALIVE, SILENT, STALLED,
                                               WAITING, DeviceStallError,
                                               Heartbeat, StallPolicy,
                                               TrainingWatchdog, read)
from lightgbm_tpu.robustness.retry import (RetryError, RetryPolicy,
                                           is_transient_error, retry_call)
from lightgbm_tpu.robustness.supervisor import (EXIT_STALLED, StillAlive,
                                                watch_child)

REPO = os.path.join(os.path.dirname(__file__), "..")


# ---------------------------------------------------------------------------
# heartbeat file round-trip + torn-write tolerance
# ---------------------------------------------------------------------------

def test_heartbeat_roundtrip(tmp_path):
    path = str(tmp_path / "x.hb")
    hb = Heartbeat(path)
    hb.beat(heartbeat.PHASE_COMPILING, 0)
    rec = read(path)
    assert rec is not None
    assert rec.phase == "compiling"
    assert rec.progress == 0
    assert rec.pid == os.getpid()
    assert rec.seq == 1
    hb.beat(heartbeat.PHASE_ITER, 7)
    rec2 = read(path)
    assert (rec2.phase, rec2.progress, rec2.seq) == ("iter", 7, 2)
    assert rec2.t >= rec.t
    assert rec2.advanced_over(rec)
    assert not rec2.advanced_over(rec2)


def test_heartbeat_touch_refreshes_keepalive_only(tmp_path):
    path = str(tmp_path / "x.hb")
    clock = {"t": 100.0}
    hb = Heartbeat(path, clock=lambda: clock["t"])
    hb.beat("measuring", 3)
    clock["t"] = 150.0
    hb.touch()
    rec = read(path)
    assert rec.t == 100.0          # substantive beat unchanged
    assert rec.ka == 150.0         # keepalive advanced
    assert rec.progress == 3


def test_heartbeat_read_tolerates_torn_and_garbage(tmp_path):
    p = tmp_path / "torn.hb"
    assert read(str(p)) is None                      # missing
    p.write_text("")
    assert read(str(p)) is None                      # empty
    p.write_text('{"phase": "iter", "progr')         # truncated JSON
    assert read(str(p)) is None
    p.write_bytes(b"\x00\xffgarbage\x01")            # binary garbage
    assert read(str(p)) is None
    p.write_text('{"phase": "iter"}')                # missing fields
    assert read(str(p)) is None
    # a valid record after garbage reads fine (single-line rewrite)
    Heartbeat(str(p)).beat("iter", 1)
    assert read(str(p)).progress == 1


# ---------------------------------------------------------------------------
# StallPolicy classification
# ---------------------------------------------------------------------------

def _rec(phase, progress, t, ka, seq=1):
    return heartbeat.HeartbeatRecord(phase=phase, progress=progress,
                                     t=t, ka=ka, pid=1, seq=seq,
                                     wall=0.0)


def test_policy_classify_phases():
    pol = StallPolicy(stall_sec={"compiling": 100.0, "iter": 10.0},
                      default_stall=10.0, silent_sec=5.0,
                      startup_grace=20.0)
    # no record: grace, then silent
    assert pol.classify(None, now=10.0, started_at=0.0) == WAITING
    assert pol.classify(None, now=25.0, started_at=0.0) == SILENT
    # long compile with fresh keepalive: alive (phase budget generous)
    assert pol.classify(_rec("compiling", 0, t=0.0, ka=79.0),
                        now=80.0, started_at=0.0) == ALIVE
    # same age in the iter phase: stalled
    assert pol.classify(_rec("iter", 5, t=0.0, ka=79.0),
                        now=80.0, started_at=0.0) == STALLED
    # keepalive gone quiet beats every phase budget
    assert pol.classify(_rec("compiling", 0, t=0.0, ka=0.0),
                        now=6.0, started_at=0.0) == SILENT
    # fresh substantive beat: alive
    assert pol.classify(_rec("iter", 6, t=78.0, ka=79.0),
                        now=80.0, started_at=0.0) == ALIVE


def test_policy_from_env_overrides():
    env = {"LGBM_TPU_STALL_SEC": "50",
           "LGBM_TPU_STALL_SEC_COMPILING": "900",
           "LGBM_TPU_STALL_SEC_SILENT": "7"}
    pol = StallPolicy.from_env(env)
    assert pol.stall_for("compiling") == 900.0
    assert pol.stall_for("iter") == 50.0
    assert pol.stall_for("unknown-phase") == 50.0
    assert pol.silent_sec == 7.0


# ---------------------------------------------------------------------------
# supervisor decisions (fake clock + fake process; no subprocesses)
# ---------------------------------------------------------------------------

class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class _FakeProc:
    def __init__(self):
        self.pid = 4242
        self.rc = None
        self.terminated = False

    def poll(self):
        return self.rc

    def terminate(self):
        self.terminated = True
        self.rc = -15

    def wait(self, timeout=None):
        if self.rc is None:
            raise subprocess.TimeoutExpired("fake", timeout)
        return self.rc


def _write_rec(path, phase, progress, t, ka, seq=1):
    with open(path, "w", encoding="utf-8") as f:
        f.write(json.dumps({"phase": phase, "progress": progress,
                            "t": t, "ka": ka, "pid": 4242, "seq": seq,
                            "wall": 0.0}))


_POL = StallPolicy(stall_sec={"compiling": 100.0, "iter": 10.0,
                              "measuring": 10.0},
                   default_stall=10.0, silent_sec=5.0,
                   startup_grace=10.0)


def test_supervisor_compile_long_child_survives(tmp_path):
    """A child compiling for 60s (way past every blind slot this test
    grants) with live keepalives is never classified hung; its exit
    code comes back normally."""
    hb = str(tmp_path / "c.hb")
    clock = _FakeClock()
    proc = _FakeProc()

    def sleep(s):
        clock.sleep(s)
        # keepalive thread alive the whole time; exits at t=60
        _write_rec(hb, "compiling", 0, t=0.0, ka=clock.t)
        if clock.t >= 60.0:
            proc.rc = 0

    _write_rec(hb, "compiling", 0, t=0.0, ka=0.0)
    rc = watch_child(proc, hb, policy=_POL, hard_deadline=500.0,
                     poll=1.0, clock=clock, sleep=sleep)
    assert rc == 0
    assert not proc.terminated
    assert clock.t >= 60.0


def test_supervisor_iterating_child_never_parked_early(tmp_path):
    """A child advancing iterations hits the HARD deadline as
    StillAlive (park), never as a stall — even though each individual
    beat is young only because progress keeps moving."""
    hb = str(tmp_path / "i.hb")
    clock = _FakeClock()
    proc = _FakeProc()

    def sleep(s):
        clock.sleep(s)
        _write_rec(hb, "iter", int(clock.t), t=clock.t, ka=clock.t,
                   seq=int(clock.t) + 1)

    _write_rec(hb, "iter", 0, t=0.0, ka=0.0)
    with pytest.raises(StillAlive):
        watch_child(proc, hb, policy=_POL, hard_deadline=50.0,
                    poll=1.0, clock=clock, sleep=sleep)
    assert not proc.terminated
    assert clock.t >= 50.0


def test_supervisor_silent_child_hung_within_budget(tmp_path):
    """A silent child is classified hung within silent_sec (+ poll
    hysteresis), nowhere near the 1000s watchdog, and is SIGTERMed."""
    hb = str(tmp_path / "s.hb")
    clock = _FakeClock()
    proc = _FakeProc()
    _write_rec(hb, "measuring", 8, t=0.0, ka=0.0)   # then silence
    with pytest.raises(DeviceStallError) as ei:
        watch_child(proc, hb, policy=_POL, hard_deadline=1000.0,
                    poll=1.0, clock=clock, sleep=clock.sleep)
    assert clock.t < 15.0          # silent_sec=5 + hysteresis, not 1000
    assert proc.terminated
    assert "DEADLINE_EXCEEDED" in str(ei.value)
    assert is_transient_error(ei.value)   # retryable by the policy


def test_supervisor_phase_stall_with_live_keepalive(tmp_path):
    """Keepalives flowing but the measuring phase sitting still past
    its budget: hung (the wedge signature — process alive, loop dead)."""
    hb = str(tmp_path / "p.hb")
    clock = _FakeClock()
    proc = _FakeProc()

    def sleep(s):
        clock.sleep(s)
        _write_rec(hb, "measuring", 8, t=0.0, ka=clock.t)

    _write_rec(hb, "measuring", 8, t=0.0, ka=0.0)
    with pytest.raises(DeviceStallError):
        watch_child(proc, hb, policy=_POL, hard_deadline=1000.0,
                    poll=1.0, clock=clock, sleep=sleep)
    assert 10.0 <= clock.t < 20.0  # the measuring budget, not watchdog


def test_supervisor_maps_exit_stalled_rc(tmp_path):
    proc = _FakeProc()
    proc.rc = EXIT_STALLED
    with pytest.raises(DeviceStallError):
        watch_child(proc, str(tmp_path / "none.hb"), policy=_POL)


# ---------------------------------------------------------------------------
# injected hang: in-process latch + subprocess recovery via retry
# ---------------------------------------------------------------------------

def test_hang_fault_silences_writes_not_calls(tmp_path):
    path = str(tmp_path / "h.hb")
    hb = Heartbeat(path)
    with faults.inject("hang:after=2"):
        hb.beat("measuring", 1)
        hb.beat("measuring", 2)
        rec = read(path)
        assert rec.progress == 2
        hb.beat("measuring", 3)       # hang fires: write suppressed
        hb.beat("measuring", 4)       # and stays suppressed
        hb.touch()
        assert read(path).progress == 2   # file frozen
        # in-memory attempt bookkeeping still advances (the in-child
        # watchdog must NOT fire under an injected supervisor-path hang)
        assert hb.last_attempt >= hb.last_beat


_CHILD_SRC = r"""
import os, sys, time
sys.path.insert(0, {repo!r})
from lightgbm_tpu.robustness import heartbeat
heartbeat.install_from_env()
for i in range(int(os.environ.get("SMOKE_ITERS", "40"))):
    heartbeat.beat("measuring", i)
    time.sleep(0.1)
"""


@pytest.mark.slow
def test_injected_hang_recovered_by_retry(tmp_path):
    """Attempt 1 runs under LGBM_TPU_FAULTS=hang → goes silent, is
    classified + terminated; attempt 2 (fault clear) completes. The
    shared RetryPolicy drives the relaunch because DeviceStallError is
    transient."""
    pol = StallPolicy(stall_sec={"measuring": 2.0}, default_stall=2.0,
                      silent_sec=1.0, startup_grace=20.0)
    attempts = []

    def attempt():
        n = len(attempts) + 1
        attempts.append(n)
        hb = str(tmp_path / f"a{n}.hb")
        env = dict(os.environ, LGBM_TPU_HEARTBEAT=hb,
                   LGBM_TPU_HEARTBEAT_KA="0.2", SMOKE_ITERS="40")
        env.pop("LGBM_TPU_FAULTS", None)
        if n == 1:
            env["LGBM_TPU_FAULTS"] = "hang:after=3"
            env["SMOKE_ITERS"] = "200"   # would run 20s if not stopped
        else:
            env["SMOKE_ITERS"] = "5"
        proc = subprocess.Popen(
            [sys.executable, "-c", _CHILD_SRC.format(repo=REPO)],
            env=env)
        rc = watch_child(proc, hb, policy=pol, poll=0.25,
                         term_grace=5.0, label=f"hang attempt {n}")
        assert rc == 0
        return n

    t0 = time.monotonic()
    done = retry_call(
        attempt,
        policy=RetryPolicy(max_attempts=3, base_delay=0.01,
                           max_delay=0.05, deadline=60.0),
        what="hang recovery")
    assert done == 2 and attempts == [1, 2]
    assert time.monotonic() - t0 < 40.0


def test_training_watchdog_arms_and_raises(tmp_path):
    """A wedged 'training loop' (no beats while armed) is interrupted
    and surfaces as DeviceStallError at the next check — instead of
    hanging forever."""
    hb = Heartbeat(str(tmp_path / "w.hb"))
    pol = StallPolicy(stall_sec={p: 0.15 for p in
                                 ("compiling", "warmup", "measuring",
                                  "iter")},
                      default_stall=0.15, silent_sec=10.0)
    wd = TrainingWatchdog(hb, policy=pol, poll=0.05,
                          exit_on_stall=False)
    wd.start()
    hb.beat("iter", 1)
    wd.begin()
    try:
        try:
            time.sleep(1.0)        # "wedged": no beats while armed
        except KeyboardInterrupt:
            pass                   # the watchdog's interrupt_main
        with pytest.raises(DeviceStallError):
            wd.check()
    finally:
        wd.end()
        wd.stop()


def test_training_watchdog_quiet_when_disarmed(tmp_path):
    """No iteration in flight (idle trained model) → never a stall,
    regardless of beat age."""
    hb = Heartbeat(str(tmp_path / "q.hb"))
    pol = StallPolicy(default_stall=0.05, stall_sec={}, silent_sec=10.0)
    wd = TrainingWatchdog(hb, policy=pol, poll=0.02,
                          exit_on_stall=False)
    wd.start()
    time.sleep(0.3)
    wd.check()                     # nothing armed
    wd.stop()


# ---------------------------------------------------------------------------
# compile cache honored by the engine; warm relaunch skips recompilation
# ---------------------------------------------------------------------------

def _tiny_train(extra_params, rounds=3):
    import numpy as np

    import lightgbm_tpu as lgb
    rng = np.random.default_rng(0)
    X = rng.normal(size=(400, 5)).astype("float32")
    y = (X[:, 0] > 0).astype("float32")
    params = dict(objective="binary", num_leaves=7, verbose=-1,
                  **extra_params)
    return lgb.train(params, lgb.Dataset(X, label=y),
                     num_boost_round=rounds)


def test_cache_dir_default_is_the_checkout_and_stable():
    """Nothing set: <checkout>/.jax_cache, the same path every call —
    never a temporary name."""
    from lightgbm_tpu.utils.jit_cache import resolve_cache_dir
    want = os.path.join(os.path.abspath(REPO), ".jax_cache")
    assert resolve_cache_dir(env={}) == want
    assert resolve_cache_dir(env={}) == want
    # the jax variable beats the param and the in-repo variable, verbatim
    assert resolve_cache_dir("param_dir", env={
        "JAX_COMPILATION_CACHE_DIR": "placed/../placed",
        "LGBM_TPU_COMPILE_CACHE": "/x"}) == "placed/../placed"
    assert resolve_cache_dir(env={"LGBM_TPU_JIT_CACHE": "/legacy"}) == want


def test_placed_cache_is_never_overridden(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the program uses it and sets no
    other directory in code — not from the param, not from the in-repo
    variable — before or after lgb.train."""
    placed = str(tmp_path / "placed")
    code = (
        "import jax, numpy as np\n"
        "import lightgbm_tpu as lgb\n"
        "from lightgbm_tpu.utils.jit_cache import enable_persistent_cache\n"
        "print('ENABLED', enable_persistent_cache('%s'))\n"
        "X = np.random.default_rng(0).normal(size=(300, 4))\n"
        "lgb.train({'objective': 'regression', 'num_leaves': 4,\n"
        "           'verbose': -1, 'tpu_compile_cache_dir': '%s'},\n"
        "          lgb.Dataset(X, label=X[:, 0]), num_boost_round=1)\n"
        "print('CONFIG', jax.config.jax_compilation_cache_dir)\n"
        % (tmp_path / "arg", tmp_path / "param"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=placed,
               LGBM_TPU_COMPILE_CACHE=str(tmp_path / "inrepo"))
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert f"ENABLED {placed}" in out.stdout
    assert f"CONFIG {placed}" in out.stdout
    assert sorted(os.listdir(tmp_path)) in ([], ["placed"])


def test_engine_honors_compile_cache_param(tmp_path, monkeypatch):
    import jax
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    cache = str(tmp_path / "cc")
    try:
        booster = _tiny_train({"tpu_compile_cache_dir": cache})
        assert booster.current_iteration() == 3
        assert jax.config.jax_compilation_cache_dir == cache
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_engine_honors_compile_cache_env(tmp_path, monkeypatch):
    import jax
    prev = jax.config.jax_compilation_cache_dir
    cache = str(tmp_path / "env_cc")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("LGBM_TPU_COMPILE_CACHE", cache)
    try:
        _tiny_train({})
        assert jax.config.jax_compilation_cache_dir == cache
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_warm_cache_relaunch_skips_recompile(tmp_path, monkeypatch):
    """The ISSUE-4 compile-cache contract at mechanism level: the same
    program, 'relaunched' against a warm persistent cache (in-process
    jit caches cleared — what a fresh child process starts with), is
    served from the on-disk cache. Asserted via the dispatch-guard
    compile counter's persistent-cache-hit channel."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.analysis.guards import CompileCounter
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    cache = str(tmp_path / "warm")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        from lightgbm_tpu.utils.jit_cache import enable_persistent_cache
        enable_persistent_cache(cache)
        # tiny programs compile in <0.5s; drop the persistence floor so
        # the test's program is cached at all
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0)

        def f(x):
            return (x * 2.0 + 1.0).sum()

        jax.jit(f, donate_argnums=())(jnp.arange(64, dtype=jnp.float32))
        assert os.listdir(cache)           # entry persisted
        jax.clear_caches()                 # "relaunch": cold process caches
        with CompileCounter() as counter:
            jax.jit(f, donate_argnums=())(
                jnp.arange(64, dtype=jnp.float32))
        assert counter.cache_hits, (
            "warm relaunch should be served from the persistent cache; "
            f"events: {counter.names}")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_min)


# ---------------------------------------------------------------------------
# gbdt instrumentation: beats written during training
# ---------------------------------------------------------------------------

def test_gbdt_writes_phase_tagged_beats(tmp_path):
    hb_path = str(tmp_path / "train.hb")
    try:
        booster = _tiny_train({"tpu_heartbeat_file": hb_path}, rounds=4)
        assert booster.current_iteration() == 4
        rec = read(hb_path)
        assert rec is not None
        assert rec.phase == "iter"    # past the compiling phase
        assert rec.progress >= 3
        assert rec.seq >= 4
    finally:
        # the heartbeat is process-global: drop it so later tests'
        # boosters train unsupervised again
        heartbeat.uninstall()


# ---------------------------------------------------------------------------
# retry.py window accounting (ISSUE 4 satellite)
# ---------------------------------------------------------------------------

class _Unavail(Exception):
    pass


def test_retry_budget_kw_clips_attempt_slots():
    clock = _FakeClock()
    budgets = []

    def attempt(slot_budget=None):
        budgets.append(slot_budget)
        clock.t += 40.0            # each attempt burns 40s
        raise _Unavail("UNAVAILABLE: nope")

    with pytest.raises(RetryError):
        retry_call(attempt,
                   policy=RetryPolicy(max_attempts=5, base_delay=10.0,
                                      max_delay=10.0, deadline=100.0),
                   clock=clock, sleep=clock.sleep,
                   budget_kw="slot_budget", what="slots")
    assert budgets[0] == pytest.approx(100.0)
    # every later attempt was granted ONLY what remained of the window
    for prev, cur in zip(budgets, budgets[1:]):
        assert cur < prev
    assert all(b >= 0.0 for b in budgets)
    # and no attempt started after the deadline passed
    assert len(budgets) <= 3      # 40s + sleep per attempt in a 100s window


def test_retry_skips_sleep_that_would_exhaust_deadline():
    clock = _FakeClock()
    sleeps = []

    def sleep(s):
        sleeps.append(s)
        clock.sleep(s)

    calls = []

    def attempt():
        calls.append(clock.t)
        clock.t += 1.0
        if len(calls) < 3:
            raise _Unavail("UNAVAILABLE: nope")
        return "ok"

    out = retry_call(attempt,
                     policy=RetryPolicy(max_attempts=3, base_delay=8.0,
                                        max_delay=8.0, deadline=12.0),
                     clock=clock, sleep=sleep, what="skip-sleep")
    assert out == "ok"
    assert len(calls) == 3
    # attempt 2 slept the full 8s backoff (fits); attempt 3's backoff
    # would have crossed the 12s deadline and was skipped, so the final
    # attempt ran INSIDE the window instead of sleeping it away
    assert calls[-1] < 12.0
    assert all(s > 0.0 for s in sleeps)
    assert len(sleeps) == 1


def test_retry_no_attempt_starts_past_deadline():
    clock = _FakeClock()
    calls = []

    def attempt():
        calls.append(clock.t)
        clock.t += 30.0            # attempt itself outlives the window
        raise _Unavail("UNAVAILABLE: nope")

    with pytest.raises(RetryError) as ei:
        retry_call(attempt,
                   policy=RetryPolicy(max_attempts=10, base_delay=0.1,
                                      max_delay=0.1, deadline=25.0),
                   clock=clock, sleep=clock.sleep, what="past-deadline")
    assert len(calls) == 1         # nothing launched at t=30 > 25
    assert ei.value.attempts == 1


# ---------------------------------------------------------------------------
# fault grammar extensions
# ---------------------------------------------------------------------------

def test_fault_grammar_hang_and_slow_compile():
    plan = faults.FaultPlan.parse("hang:after=4,slow_compile:sec=2.5")
    assert set(plan.faults) == {"hang", "slow_compile"}
    assert plan.faults["slow_compile"].sec == 2.5
    assert plan.faults["hang"].after == 4
    with pytest.raises(ValueError):
        faults.FaultPlan.parse("hang:bogus=1")


def test_maybe_delay_sleeps_injected_duration():
    slept = []
    with faults.inject("slow_compile:sec=3.5"):
        got = faults.maybe_delay("slow_compile", sleep=slept.append)
        assert got == 3.5 and slept == [3.5]
        # bare spec: p=1 -> n defaults to 1, disarms after one firing
        assert faults.maybe_delay("slow_compile",
                                  sleep=slept.append) == 0.0
    assert faults.maybe_delay("slow_compile", sleep=slept.append) == 0.0


def test_check_is_deterministic_and_counted():
    with faults.inject("hang:p=0.5:seed=3:n=100"):
        seq1 = [faults.check("hang") for _ in range(20)]
    with faults.inject("hang:p=0.5:seed=3:n=100"):
        seq2 = [faults.check("hang") for _ in range(20)]
    assert seq1 == seq2
    assert any(seq1) and not all(seq1)
