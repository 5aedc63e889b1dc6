"""Fault-tolerant training runtime (lightgbm_tpu/robustness/).

Covers the ISSUE 2 acceptance criteria on CPU via the fault-injection
harness:

- retry policy unit behavior (classification, bounded attempts,
  deadline, jitter bounds);
- atomic checkpoint writes: CRC validation, mid-write kill leaving the
  previous checkpoint set intact, corrupt-newest fallback;
- resume-equivalence: training killed mid-checkpoint-write at iteration
  k, resumed from the newest valid checkpoint, produces a
  split-structure-identical ensemble (and bit-equal predictions) vs an
  uninterrupted run;
- injected transient collective failures (p=0.2) still converge to the
  bit-exact 2-worker model of test_injected_collectives.py within the
  retry budget;
- tpu_fallback_to_cpu completes training when the device probe never
  succeeds.
"""
import os
import threading

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.robustness import checkpoint as ckpt
from lightgbm_tpu.robustness import faults
from lightgbm_tpu.robustness import integrity as _integrity
from lightgbm_tpu.robustness.retry import (RetryError, RetryPolicy,
                                           is_transient_error,
                                           retry_call)


# ---------------------------------------------------------------------------
# retry.py
# ---------------------------------------------------------------------------

class _Unavailable(Exception):
    pass


def test_classifier_transient_and_not():
    assert is_transient_error(RuntimeError(
        "UNAVAILABLE: TPU backend setup/compile error"))
    assert is_transient_error(RuntimeError("DEADLINE_EXCEEDED: rpc"))
    assert is_transient_error(TimeoutError("claim timed out"))
    assert is_transient_error(ConnectionResetError())
    assert not is_transient_error(TypeError("bad argument"))
    assert not is_transient_error(ValueError("num_leaves must be > 1"))


def test_retry_succeeds_after_transient_failures():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise _Unavailable("UNAVAILABLE: injected")
        return "ok"

    slept = []
    out = retry_call(flaky, policy=RetryPolicy(max_attempts=5,
                                               base_delay=0.01,
                                               max_delay=0.05),
                     sleep=slept.append)
    assert out == "ok"
    assert len(calls) == 3
    assert len(slept) == 2
    assert all(0.0 <= s <= 0.05 for s in slept)


def test_retry_bounded_attempts_then_retryerror():
    calls = []

    def always_down():
        calls.append(1)
        raise _Unavailable("UNAVAILABLE: still down")

    with pytest.raises(RetryError) as ei:
        retry_call(always_down,
                   policy=RetryPolicy(max_attempts=4, base_delay=0.001,
                                      max_delay=0.002),
                   sleep=lambda s: None)
    assert len(calls) == 4
    assert ei.value.attempts == 4
    assert isinstance(ei.value.last, _Unavailable)


def test_retry_nontransient_propagates_immediately():
    calls = []

    def buggy():
        calls.append(1)
        raise TypeError("code bug")

    with pytest.raises(TypeError):
        retry_call(buggy, policy=RetryPolicy(max_attempts=5),
                   sleep=lambda s: None)
    assert len(calls) == 1


def test_retry_deadline_respected():
    """No attempt starts after the deadline; sleeps are clipped to it."""
    t = [0.0]

    def clock():
        return t[0]

    def sleep(s):
        t[0] += s

    calls = []

    def always_down():
        calls.append(1)
        t[0] += 3.0     # each attempt costs 3s of fake time
        raise _Unavailable("UNAVAILABLE")

    with pytest.raises(RetryError):
        retry_call(always_down,
                   policy=RetryPolicy(max_attempts=100, base_delay=0.5,
                                      max_delay=2.0, deadline=10.0),
                   sleep=sleep, clock=clock)
    # 10s deadline / ~3.5s per attempt -> far fewer than max_attempts
    assert 2 <= len(calls) <= 4
    assert t[0] <= 16.0     # never ran away past the budget


def test_decorrelated_jitter_bounds():
    import random
    p = RetryPolicy(base_delay=0.5, max_delay=30.0)
    rng = random.Random(0)
    d = p.base_delay
    for _ in range(100):
        d = p.next_delay(d, rng)
        assert 0.5 <= d <= 30.0


# ---------------------------------------------------------------------------
# faults.py grammar
# ---------------------------------------------------------------------------

def test_fault_grammar_parse():
    plan = faults.FaultPlan.parse(
        "collective:p=0.2:seed=7,probe_timeout,write_kill:n=1:after=3")
    assert set(plan.faults) == {"collective", "probe_timeout",
                                "write_kill"}
    assert plan.faults["collective"].p == 0.2
    assert plan.faults["write_kill"].after == 3
    # bare always-on faults disarm after one shot
    assert plan.faults["probe_timeout"].n == 1
    with pytest.raises(ValueError):
        faults.FaultPlan.parse("bogus_class")
    with pytest.raises(ValueError):
        faults.FaultPlan.parse("collective:p")
    with pytest.raises(ValueError):
        faults.FaultPlan.parse("collective,collective")


def test_fault_determinism_and_counts():
    with faults.inject("collective:p=0.5:seed=3:n=100"):
        fired1 = [False] * 50
        for i in range(50):
            try:
                faults.maybe_fail("collective")
            except faults.FaultInjected:
                fired1[i] = True
    with faults.inject("collective:p=0.5:seed=3:n=100"):
        fired2 = [False] * 50
        for i in range(50):
            try:
                faults.maybe_fail("collective")
            except faults.FaultInjected:
                fired2[i] = True
    assert fired1 == fired2          # same seed -> same schedule
    assert any(fired1) and not all(fired1)
    # no plan installed -> never fires
    faults.maybe_fail("collective")


def test_fault_after_and_n():
    with faults.inject("write_kill:after=2:n=1"):
        faults.maybe_fail("write_kill")
        faults.maybe_fail("write_kill")
        with pytest.raises(faults.WriteKilled):
            faults.maybe_fail("write_kill")
        faults.maybe_fail("write_kill")   # disarmed after n=1


def test_serving_fault_sites_parse_and_fire():
    """ISSUE 9: the serving sites speak the existing grammar
    (p/n/after/seed/sec opts) and raise transient-classified faults."""
    plan = faults.FaultPlan.parse(
        "dispatch_error:p=0.5:seed=3,slow_dispatch:sec=0.01,"
        "publish_fail:n=2:after=1")
    assert set(plan.faults) == {"dispatch_error", "slow_dispatch",
                                "publish_fail"}
    assert plan.faults["slow_dispatch"].sec == 0.01
    assert plan.faults["publish_fail"].n == 2
    with faults.inject("dispatch_error"):
        with pytest.raises(faults.FaultInjected) as ei:
            faults.maybe_fail("dispatch_error")
        assert is_transient_error(ei.value)   # retried, not crashed on
    slept = []
    with faults.inject("slow_dispatch:sec=1.5"):
        assert faults.maybe_delay("slow_dispatch",
                                  sleep=slept.append) == 1.5
    assert slept == [1.5]


def test_faults_docstring_lists_every_known_site():
    """The module docstring's site list drifts from KNOWN_SITES unless
    gated (ISSUE 9 satellite): every site must be documented as a
    ``site`` bullet."""
    for site in faults.KNOWN_SITES:
        assert f"``{site}``" in faults.__doc__, \
            f"fault site {site!r} missing from faults.py docstring"


# ---------------------------------------------------------------------------
# error classification (ISSUE 17): the RESOURCE_EXHAUSTED class
# ---------------------------------------------------------------------------

def test_error_classifier_table():
    """classify_error files every exception into exactly one of the
    documented classes; OOM is recognized by type AND by message, and
    beats a transient-looking message (retrying the same allocation is
    futile)."""
    from lightgbm_tpu.robustness.retry import (ERROR_CLASSES,
                                               classify_error,
                                               is_oom_error)
    cases = {
        "TRANSIENT": [_Unavailable("UNAVAILABLE: socket closed"),
                      RuntimeError("ABORTED: chip reset"),
                      ConnectionResetError("peer")],
        "DEADLINE": [TimeoutError("slot wait"),
                     RuntimeError("DEADLINE_EXCEEDED: 5s")],
        "RESOURCE_EXHAUSTED": [
            MemoryError("malloc"),
            RuntimeError("RESOURCE_EXHAUSTED: out of memory"),
            RuntimeError("failed to allocate 2.5G hbm"),
            # OOM text inside a transient-looking envelope: still OOM
            RuntimeError("UNAVAILABLE: failed to allocate 1G"),
        ],
        "FATAL": [ValueError("a code bug"), KeyError("t0")],
        "DATA_CORRUPTION": [
            RuntimeError("DATA_CORRUPTION: non-finite gradient sum"),
            # the integrity exceptions carry the marker in-message
            _integrity.IntegrityError("host pack CRC mismatch"),
            _integrity.NumericHealthError("NaN leaf at iteration 3"),
            _integrity.CanaryMismatch("route t0 parity"),
            _integrity.GangDivergence("rank 1 digest"),
        ],
    }
    from lightgbm_tpu.robustness.retry import is_corruption_error
    for expected, excs in cases.items():
        for e in excs:
            assert classify_error(e) == expected, (e, classify_error(e))
            assert is_oom_error(e) == (expected == "RESOURCE_EXHAUSTED")
            assert is_corruption_error(e) == \
                (expected == "DATA_CORRUPTION")
            # DEADLINE is retried like TRANSIENT (fresh sub-slot); OOM
            # and FATAL are not
            assert is_transient_error(e) == \
                (expected in ("TRANSIENT", "DEADLINE"))
    assert set(cases) == set(ERROR_CLASSES)


def test_error_classes_documented():
    """Every recognized class is documented in retry.py's classifier
    table (the same drift contract the faults docstring carries)."""
    from lightgbm_tpu.robustness import retry
    for cls in retry.ERROR_CLASSES:
        assert cls in retry.__doc__, \
            f"error class {cls!r} missing from retry.py docstring"


def test_oom_site_known_and_nontransient():
    """The ``oom`` site speaks the grammar, raises the RESOURCE_EXHAUSTED
    class and is NEVER retried: retry_call propagates it unwrapped on
    the first attempt (adaptation is the caller's job)."""
    from lightgbm_tpu.robustness.retry import is_oom_error
    assert "oom" in faults.KNOWN_SITES
    with faults.inject("oom"):
        with pytest.raises(faults.OOMInjected) as ei:
            faults.maybe_fail("oom")
    assert is_oom_error(ei.value)
    assert not is_transient_error(ei.value)
    calls = []

    def allocate():
        calls.append(1)
        raise faults.OOMInjected("RESOURCE_EXHAUSTED: injected")

    with pytest.raises(faults.OOMInjected):
        retry_call(allocate, policy=RetryPolicy(max_attempts=5,
                                                base_delay=0.001))
    assert len(calls) == 1   # the retry budget was never burned


# ---------------------------------------------------------------------------
# checkpoint.py: atomicity + CRC
# ---------------------------------------------------------------------------

def test_atomic_write_and_crc_roundtrip(tmp_path):
    state = {"iteration": 7, "model": "tree\nstuff\n", "rng": {"a": 1},
             "best_iteration": -1, "best_score": {},
             "eval_history": {"v": {"l2": [1.0, 0.5]}}}
    path = ckpt.write_checkpoint(str(tmp_path), state)
    assert os.path.basename(path) == "ckpt_000000007.lgbmckpt"
    back = ckpt.read_checkpoint(path)
    assert back["iteration"] == 7
    assert back["model"] == "tree\nstuff\n"
    assert back["eval_history"] == {"v": {"l2": [1.0, 0.5]}}
    # no tmp litter after a clean write
    assert [n for n in os.listdir(tmp_path) if ".tmp." in n] == []


def test_write_kill_leaves_previous_checkpoints_intact(tmp_path):
    s = {"iteration": 1, "model": "m1", "rng": {}}
    ckpt.write_checkpoint(str(tmp_path), s)
    with faults.inject("write_kill"):
        with pytest.raises(faults.WriteKilled):
            ckpt.write_checkpoint(str(tmp_path),
                                  dict(s, iteration=2, model="m2"))
    # final file for iteration 2 never appeared; iteration 1 survives
    names = sorted(os.listdir(tmp_path))
    assert "ckpt_000000001.lgbmckpt" in names
    assert "ckpt_000000002.lgbmckpt" not in names
    got = ckpt.latest_valid_checkpoint(str(tmp_path))
    assert got is not None and got[1]["iteration"] == 1
    # the partial tmp litter is ignored by listing and pruned away
    assert any(".tmp." in n for n in names)
    ckpt.prune_checkpoints(str(tmp_path), keep_last=5)
    assert not any(".tmp." in n
                   for n in os.listdir(tmp_path))


def test_corrupt_newest_falls_back_to_previous(tmp_path):
    for it in (1, 2, 3):
        ckpt.write_checkpoint(str(tmp_path),
                              {"iteration": it, "model": f"m{it}",
                               "rng": {}})
    newest = os.path.join(tmp_path, "ckpt_000000003.lgbmckpt")
    blob = bytearray(open(newest, "rb").read())
    blob[len(blob) // 2] ^= 0xFF          # flip a payload byte
    with open(newest, "wb") as f:
        f.write(blob)
    with pytest.raises(ckpt.CheckpointError):
        ckpt.read_checkpoint(newest)
    path, state = ckpt.latest_valid_checkpoint(str(tmp_path))
    assert state["iteration"] == 2 and state["model"] == "m2"
    # truncation (lost footer) is also detected
    trunc = os.path.join(tmp_path, "ckpt_000000002.lgbmckpt")
    blob = open(trunc, "rb").read()
    with open(trunc, "wb") as f:
        f.write(blob[:len(blob) - 10])
    path, state = ckpt.latest_valid_checkpoint(str(tmp_path))
    assert state["iteration"] == 1


def test_prune_keep_last(tmp_path):
    for it in range(1, 8):
        ckpt.write_checkpoint(str(tmp_path),
                              {"iteration": it, "model": "m", "rng": {}})
    ckpt.prune_checkpoints(str(tmp_path), keep_last=3)
    its = [i for i, _ in ckpt.list_checkpoints(str(tmp_path))]
    assert its == [7, 6, 5]


# ---------------------------------------------------------------------------
# resume-equivalence (acceptance criterion)
# ---------------------------------------------------------------------------

def _train_data(rng, n=1200, f=8):
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] +
         0.1 * rng.normal(size=n) > 0).astype(np.float32)
    return X, y


RESUME_PARAMS = dict(objective="binary", num_leaves=15,
                     learning_rate=0.1, verbose=-1, seed=3,
                     bagging_fraction=0.8, bagging_freq=1,
                     feature_fraction=0.9)


def _structure(model):
    return [(t.num_leaves, t.split_feature.tolist(),
             t.leaf_count.tolist())
            for t in model._engine.models]


@pytest.mark.slow
def test_resume_equivalence_after_write_kill(tmp_path, rng):
    """Kill training mid-checkpoint-write at iteration 6; resume from
    the newest valid checkpoint (iteration 5); the final ensemble must
    be split-structure-identical (and prediction-bit-identical) to an
    uninterrupted run."""
    X, y = _train_data(rng)
    N = 12
    full = lgb.train(dict(RESUME_PARAMS), lgb.Dataset(X, label=y),
                     num_boost_round=N)

    ckdir = str(tmp_path / "ck")
    cb = lgb.checkpoint_callback(ckdir, every_n=1, keep_last=3)
    with faults.inject("write_kill:after=5:n=1"):
        with pytest.raises(faults.WriteKilled):
            lgb.train(dict(RESUME_PARAMS), lgb.Dataset(X, label=y),
                      num_boost_round=N, callbacks=[cb])
    got = ckpt.latest_valid_checkpoint(ckdir)
    assert got is not None
    assert got[1]["iteration"] == 5   # write #6 was killed mid-write

    cb2 = lgb.checkpoint_callback(ckdir, every_n=1, keep_last=3)
    resumed = lgb.train(dict(RESUME_PARAMS), lgb.Dataset(X, label=y),
                        num_boost_round=N, callbacks=[cb2],
                        resume_from=ckdir)
    assert resumed.current_iteration() == N
    assert _structure(resumed) == _structure(full)
    np.testing.assert_array_equal(resumed.predict(X), full.predict(X))
    # the resumed run kept checkpointing from where it left off
    assert ckpt.latest_valid_checkpoint(ckdir)[1]["iteration"] == N


def test_resume_skips_corrupt_newest(tmp_path, rng):
    """A CRC-corrupted newest checkpoint is skipped in favor of the
    previous valid one, and the resumed run still matches the
    uninterrupted one."""
    X, y = _train_data(rng, n=800)
    N = 8
    full = lgb.train(dict(RESUME_PARAMS), lgb.Dataset(X, label=y),
                     num_boost_round=N)
    ckdir = str(tmp_path / "ck")
    cb = lgb.checkpoint_callback(ckdir, every_n=1, keep_last=4)
    with faults.inject("write_kill:after=5:n=1"):
        with pytest.raises(faults.WriteKilled):
            lgb.train(dict(RESUME_PARAMS), lgb.Dataset(X, label=y),
                      num_boost_round=N, callbacks=[cb])
    # corrupt the newest surviving checkpoint (iteration 5): resume
    # must fall back to iteration 4
    path5 = ckpt.latest_valid_checkpoint(ckdir)[0]
    blob = bytearray(open(path5, "rb").read())
    blob[len(blob) // 3] ^= 0x55
    with open(path5, "wb") as f:
        f.write(blob)
    assert ckpt.latest_valid_checkpoint(ckdir)[1]["iteration"] == 4

    resumed = lgb.train(dict(RESUME_PARAMS), lgb.Dataset(X, label=y),
                        num_boost_round=N, resume_from=ckdir)
    assert resumed.current_iteration() == N
    assert _structure(resumed) == _structure(full)
    np.testing.assert_array_equal(resumed.predict(X), full.predict(X))


def test_resume_from_empty_dir_starts_fresh(tmp_path, rng):
    X, y = _train_data(rng, n=400)
    b = lgb.train(dict(RESUME_PARAMS), lgb.Dataset(X, label=y),
                  num_boost_round=4,
                  resume_from=str(tmp_path / "nothing_here"))
    assert b.current_iteration() == 4


def test_resume_already_complete_returns_immediately(tmp_path, rng):
    X, y = _train_data(rng, n=400)
    ckdir = str(tmp_path / "ck")
    lgb.train(dict(RESUME_PARAMS), lgb.Dataset(X, label=y),
              num_boost_round=5,
              callbacks=[lgb.checkpoint_callback(ckdir, every_n=1)])
    b = lgb.train(dict(RESUME_PARAMS), lgb.Dataset(X, label=y),
                  num_boost_round=5, resume_from=ckdir)
    assert b.current_iteration() == 5


@pytest.mark.slow
def test_checkpoint_eval_history_persists(tmp_path, rng):
    """Eval history accumulated before the kill is carried into
    checkpoints written after resume."""
    X, y = _train_data(rng, n=600)
    Xv, yv = _train_data(np.random.default_rng(9), n=300)
    ckdir = str(tmp_path / "ck")

    def run(resume):
        ds = lgb.Dataset(X, label=y)
        cb = lgb.checkpoint_callback(ckdir, every_n=1, keep_last=2)
        kw = dict(resume_from=ckdir) if resume else {}
        return lgb.train(dict(RESUME_PARAMS), ds, num_boost_round=6,
                         valid_sets=[lgb.Dataset(Xv, label=yv,
                                                 reference=ds)],
                         valid_names=["v"], callbacks=[cb], **kw)

    with faults.inject("write_kill:after=3:n=1"):
        with pytest.raises(faults.WriteKilled):
            run(resume=False)
    run(resume=True)
    hist = ckpt.latest_valid_checkpoint(ckdir)[1]["eval_history"]
    assert len(hist["v"]["binary_logloss"]) == 6


# ---------------------------------------------------------------------------
# CLI snapshot_freq: atomic writes + keep_last pruning
# ---------------------------------------------------------------------------

def test_cli_snapshots_atomic_and_pruned(tmp_path, rng):
    from lightgbm_tpu.cli import run as cli_run
    X = rng.normal(size=(300, 6))
    y = (X[:, 0] + X[:, 1] ** 2 > 0.5).astype(np.float64)
    train_csv = str(tmp_path / "train.csv")
    np.savetxt(train_csv, np.column_stack([y, X]), delimiter=",",
               fmt="%.8g")
    model_path = str(tmp_path / "model.txt")
    assert cli_run(["task=train", "objective=binary",
                    f"data={train_csv}", "num_iterations=8",
                    "num_leaves=7", "min_data_in_leaf=5",
                    "verbosity=-1", "snapshot_freq=2",
                    "snapshot_keep_last=2",
                    f"output_model={model_path}"]) == 0
    snaps = sorted(n for n in os.listdir(tmp_path)
                   if ".snapshot_iter_" in n)
    # iters 2,4,6,8 were snapshotted; only the newest 2 survive pruning
    assert snaps == ["model.txt.snapshot_iter_6",
                     "model.txt.snapshot_iter_8"]
    # snapshots are loadable models (atomic write = never torn)
    b = lgb.Booster(model_file=str(tmp_path / snaps[0]))
    assert b.num_trees() == 6
    # a kill mid-snapshot-write leaves no torn file, only tmp litter
    with faults.inject("write_kill"):
        rc = None
        try:
            cli_run(["task=train", "objective=binary",
                     f"data={train_csv}", "num_iterations=4",
                     "num_leaves=7", "min_data_in_leaf=5",
                     "verbosity=-1", "snapshot_freq=2",
                     f"output_model={model_path}"])
        except faults.WriteKilled:
            rc = "killed"
    assert rc == "killed"
    for n in os.listdir(tmp_path):
        if ".snapshot_iter_" in n and ".tmp." not in n:
            lgb.Booster(model_file=str(tmp_path / n))  # still loadable


# ---------------------------------------------------------------------------
# injected transient collective failures (acceptance criterion)
# ---------------------------------------------------------------------------

class ThreadAllreduce:
    """Deterministic allreduce over threads (same contract as
    test_injected_collectives.py)."""

    def __init__(self, world):
        self.world = world
        self.barrier = threading.Barrier(world)
        self.bufs = [None] * world
        self.calls = 0

    def _exchange(self, rank, arr, op):
        self.bufs[rank] = np.asarray(arr).copy()
        self.barrier.wait()
        out = self.bufs[0].astype(np.float64) if op == "sum" \
            else self.bufs[0]
        for b in self.bufs[1:]:
            out = out + b if op == "sum" else np.maximum(out, b)
        self.calls += 1
        self.barrier.wait()
        return out.astype(arr.dtype)

    def make(self, rank):
        return (lambda a: self._exchange(rank, a, "sum"),
                lambda a: self._exchange(rank, a, "max"))


@pytest.mark.slow
def test_collective_faults_converge_bit_exact(rng, monkeypatch):
    """20% injected transient collective failures: the 2-worker
    injected-collectives training retries through the shared policy and
    still matches centralized training bit-for-bit (int32 quantized
    histogram algebra), with attempts bounded by the policy."""
    from lightgbm_tpu.distributed import (clear_collectives,
                                          inject_collectives)
    # fast, generous retry budget: P[8 consecutive 20% failures] ~ 3e-6
    monkeypatch.setenv("LGBM_TPU_RETRY_ATTEMPTS", "8")
    monkeypatch.setenv("LGBM_TPU_RETRY_BASE_DELAY", "0.001")
    monkeypatch.setenv("LGBM_TPU_RETRY_MAX_DELAY", "0.01")

    params = {
        "objective": "regression", "num_leaves": 15,
        "learning_rate": 0.2, "min_data_in_leaf": 5,
        "use_quantized_grad": True, "stochastic_rounding": False,
        "verbosity": -1,
    }
    rounds = 6
    n, f = 600, 6
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] * 2 - X[:, 1] * X[:, 2] +
         0.05 * rng.normal(size=n)).astype(np.float32)

    clear_collectives()
    full = lgb.Dataset(X, label=y)
    bst_c = lgb.train(dict(params), full, num_boost_round=rounds)
    pred_c = bst_c.predict(X)

    allred = ThreadAllreduce(2)
    halves = [(X[: n // 2], y[: n // 2]), (X[n // 2:], y[n // 2:])]
    boosters = [None, None]
    for rank in range(2):
        rsum, rmax = allred.make(rank)
        inject_collectives(rsum, reduce_max=rmax, rank=rank,
                           num_machines=2)
        ds = lgb.Dataset(halves[rank][0], label=halves[rank][1],
                         reference=full)
        boosters[rank] = lgb.Booster(dict(params), ds)
    clear_collectives()

    errs = []

    def run(rank):
        try:
            for _ in range(rounds):
                boosters[rank].update()
        except Exception as e:          # pragma: no cover
            errs.append((rank, e))
            try:
                allred.barrier.abort()
            except Exception:
                pass

    with faults.inject("collective:p=0.2:seed=11:n=100000") as plan:
        threads = [threading.Thread(target=run, args=(r,))
                   for r in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        fired = plan.faults["collective"].fired
    assert not errs, errs
    assert fired > 0, "no faults were injected — p=0.2 test is vacuous"
    assert allred.calls > 0

    m0 = boosters[0].model_to_string()
    m1 = boosters[1].model_to_string()
    assert m0 == m1
    pred_0 = boosters[0].predict(X)
    np.testing.assert_allclose(pred_0, pred_c, rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# device probe fallback (acceptance criterion)
# ---------------------------------------------------------------------------

def test_fallback_to_cpu_when_probe_never_succeeds(rng, monkeypatch):
    """tpu_fallback_to_cpu=true: the probe retries under the policy,
    then training completes on CPU instead of aborting."""
    monkeypatch.setenv("LGBM_TPU_RETRY_ATTEMPTS", "3")
    monkeypatch.setenv("LGBM_TPU_RETRY_BASE_DELAY", "0.001")
    monkeypatch.setenv("LGBM_TPU_RETRY_MAX_DELAY", "0.01")
    monkeypatch.setenv("LGBM_TPU_RETRY_DEADLINE", "5")
    X, y = _train_data(rng, n=400)
    with faults.inject("probe_timeout:p=1:n=1000000"):
        b = lgb.train(dict(RESUME_PARAMS, tpu_fallback_to_cpu=True),
                      lgb.Dataset(X, label=y), num_boost_round=3)
    assert b.current_iteration() == 3


def test_probe_retries_then_succeeds(monkeypatch):
    """A probe that fails twice then recovers: retry_call drives
    probe_device through the transient failures."""
    from lightgbm_tpu.robustness.retry import probe_device
    with faults.inject("probe_timeout:n=2"):
        out = retry_call(probe_device,
                         policy=RetryPolicy(max_attempts=5,
                                            base_delay=0.001,
                                            max_delay=0.01))
    assert out >= 1


def test_probe_nonfallback_raises(rng, monkeypatch):
    """Without tpu_fallback_to_cpu the exhausted policy surfaces as
    RetryError (no silent degradation)."""
    monkeypatch.setenv("LGBM_TPU_RETRY_ATTEMPTS", "2")
    monkeypatch.setenv("LGBM_TPU_RETRY_BASE_DELAY", "0.001")
    monkeypatch.setenv("LGBM_TPU_RETRY_MAX_DELAY", "0.01")
    from lightgbm_tpu.robustness.retry import ensure_device_or_fallback
    with faults.inject("probe_timeout:p=1:n=1000000"):
        with pytest.raises(RetryError):
            ensure_device_or_fallback(fallback=False)
