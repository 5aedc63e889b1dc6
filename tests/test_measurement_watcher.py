"""Unit coverage for the unattended measurement session's decision
logic (scripts/tpu_session_auto.py) and the tuned-defaults cache.

The session itself needs a healthy device; these tests pin the pure
logic — flip selection must choose the MEASURED-best configuration
(never an unmeasured composition), unreachable detection must match
bench.py's fail-line contract, and the tuned cache must round-trip and
fail soft.
"""
import importlib.util
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")


def _load_session_mod():
    path = os.path.join(REPO, "scripts", "tpu_session_auto.py")
    spec = importlib.util.spec_from_file_location("tpu_session_auto", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def sess():
    return _load_session_mod()


def test_unreachable_matches_bench_fail_contract(sess):
    assert sess.unreachable(None)
    # structured status (bench.py rc=4 companion) wins over note text —
    # rewording the note must not break detection
    assert sess.unreachable({"value": 0.0, "status": "device_unreachable",
                             "note": "device gave up"})
    assert not sess.unreachable({"value": 0.0, "status": "no_result",
                                 "note": "device unreachable-sounding"})
    # pre-status payloads (early bench rounds): note fallback
    assert sess.unreachable({"value": 0.0, "note": "device unreachable "
                             "after 2 probe attempt(s)"})
    # a 0.0 from a non-device failure is a failure but not window-closed
    assert not sess.unreachable({"value": 0.0, "note": "sched=compact "
                                 "exited rc=1"})
    assert not sess.unreachable({"value": 2.5, "vs_baseline": 0.06})


def test_bench_fail_line_carries_status_and_distinct_rcs():
    """bench.py's JSON fail line must let consumers tell "hung device"
    (status=device_unreachable, rc=4) from "slow code / child failure"
    (status=no_result, rc=3) — the ISSUE-1 satellite contract."""
    bench = _load_bench_mod()
    assert bench.RC_DEVICE_UNREACHABLE == 4
    assert bench.RC_NO_RESULT == 3
    assert bench.RC_DEVICE_UNREACHABLE != bench.RC_NO_RESULT
    unreach = json.loads(bench._fail_line("probe died",
                                          status="device_unreachable"))
    assert unreach["status"] == "device_unreachable"
    assert unreach["value"] == 0.0
    default = json.loads(bench._fail_line("child rc=1"))
    assert default["status"] == "no_result"


def _load_bench_mod():
    spec = importlib.util.spec_from_file_location(
        "bench_probe_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_probe_failure_classification(monkeypatch, capsys):
    """Only device symptoms (probe timeouts / UNAVAILABLE cycling) may
    report status=device_unreachable rc=4; a probe child that dies of a
    code failure (import error, OOM) is status=no_result rc=3 so the
    session watcher doesn't count a code bug toward window closure."""
    bench = _load_bench_mod()
    bench.BENCH_WATCHDOG_SEC = 1  # reserve=0.5s -> tiny retry window

    class _FakeProc:
        pid = 1
        _rc = None

        def poll(self):
            return self._rc

        def terminate(self):
            self._rc = -15

        def wait(self, timeout=None):
            return self._rc

    class _FakeChild:
        """Post-ISSUE-4 spawn surface (_ChildSpawn + watch_child)."""

        stderr_text = ""

        def __init__(self, env_extra, tag, partial=False):
            self.hb_path = "/nonexistent.hb"
            self.partial_path = ""
            self.proc = _FakeProc()

        def read_streams(self):
            return "", type(self).stderr_text

        def cleanup(self):
            pass

        def fail_cleanup(self, tail=2000):
            return self.proc.poll() is not None

    from lightgbm_tpu.robustness.supervisor import StillAlive
    monkeypatch.setattr(bench, "_ChildSpawn", _FakeChild)

    def timing_out(proc, hb, **kw):
        # consume the whole retry window so exactly one attempt runs
        # (a real timed-out probe has eaten its slot by definition)
        time.sleep(0.6)
        raise StillAlive("probe at slot", pid=1)
    monkeypatch.setattr(bench, "watch_child", timing_out)
    rc = bench.main()
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == bench.RC_DEVICE_UNREACHABLE == 4
    assert res["status"] == "device_unreachable"

    _FakeChild.stderr_text = "ImportError: cannot import name 'grower'"
    monkeypatch.setattr(bench, "watch_child", lambda *a, **k: 1)
    rc = bench.main()
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == bench.RC_NO_RESULT == 3
    assert res["status"] == "no_result"


def test_flip_never_ships_a_measured_losing_composition(sess):
    # negative interaction: both individually win, composition loses —
    # the default must become the best SINGLE flip, not the pair
    flips = sess.pick_flips(base=100.0, pallas=110.0, packed=108.0,
                            both=90.0)
    assert flips == {"f32_hist_kernel": "pallas"}


def test_flip_requires_margin(sess):
    assert sess.pick_flips(100.0, 102.0, 101.0, 102.5) == {}
    assert sess.pick_flips(0.0, 110.0, 108.0, 125.0) == {}


def test_flip_prefers_winning_composition(sess):
    flips = sess.pick_flips(100.0, 110.0, 108.0, 125.0)
    assert flips == {"f32_hist_kernel": "pallas", "packed_bins": True}


def test_tuned_cache_fail_soft(tmp_path, monkeypatch):
    monkeypatch.setenv("LIGHTGBM_TPU_TUNED", str(tmp_path / "TUNED.json"))
    sys.path.insert(0, REPO)
    from lightgbm_tpu import tuned
    tuned.reload()
    assert tuned.get("f32_hist_kernel", "einsum") == "einsum"
    # malformed file degrades to fallbacks, never raises
    (tmp_path / "TUNED.json").write_text("{not json")
    tuned.reload()
    assert tuned.get("packed_bins", False) is False
    tuned.write({"packed_bins": True})
    assert tuned.get("packed_bins") is True
    tuned.reload()
    assert tuned.get("packed_bins") is True
    monkeypatch.delenv("LIGHTGBM_TPU_TUNED")
    tuned.reload()


def test_gbdt_sanitizes_unknown_tuned_kernel(tmp_path, monkeypatch):
    """A wrong-typed tuned value must fall back, not crash training."""
    cache = tmp_path / "TUNED.json"
    cache.write_text(json.dumps({"f32_hist_kernel": True,
                                 "packed_bins": "yes-ish"}))
    code = (
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np, lightgbm_tpu as lgb\n"
        "rng = np.random.default_rng(0)\n"
        "X = rng.normal(size=(500, 4)); y = (X[:, 0] > 0).astype('f4')\n"
        "b = lgb.train({'objective': 'binary', 'num_leaves': 7,\n"
        "               'verbosity': -1}, lgb.Dataset(X, label=y),\n"
        "              num_boost_round=2)\n"
        "print('OK', len(b.predict(X)))\n")
    env = dict(os.environ, LIGHTGBM_TPU_TUNED=str(cache))
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK 500" in out.stdout


def test_chip_smoke_refuses_to_run_without_a_chip():
    """On the CPU the chip smoke exits non-zero before any work and
    prints no result line (the pass line needs platform=tpu)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], env=env,
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "platform=cpu" in out.stdout
    assert '"ok"' not in out.stdout and "{" not in out.stdout
    assert "needs a TPU" in out.stderr


def test_chip_smoke_result_line_has_exactly_the_contract_keys():
    """The driver reads the LAST stdout line as a JSON object with exactly
    ``ok`` and ``device`` {platform, kind, count}; the phase reports go on
    the summary line before it, never into this one."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod     # its dataclass looks itself up
    try:
        spec.loader.exec_module(mod)
    finally:
        del sys.modules["chip_smoke"]
    line = mod.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert "\n" not in line
    got = json.loads(line)
    assert got == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert type(got["device"]["count"]) is int
