"""Continual-learning service (ISSUE 14): stream follower, resident
trainer resume, publish pump, and the HTTP front door — wire-deadline
propagation into the PR9 drop-before-coalescing path, malformed/oversize
rejection without poisoning coalesced peers, bit-identity of HTTP-served
scores vs in-process ``predict_device``, and the staleness plumbing."""
import io
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.io.stream_loader import StreamFollower
from lightgbm_tpu.robustness import faults
from lightgbm_tpu.service import (ContinualService, FrontDoor,
                                  ServerGateway, TrainerSpec,
                                  run_resident_trainer)

PARAMS = dict(objective="binary", num_leaves=15, learning_rate=0.1,
              verbose=-1, seed=7)


def _rows(n, f=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(np.float32)
    return np.column_stack([y, X])


def _append(path, block):
    with open(path, "a") as f:
        f.write("\n".join(",".join(repr(float(v)) for v in r)
                          for r in block) + "\n")


def _post(url, body, headers, timeout=60):
    req = urllib.request.Request(url, data=body, headers=headers)
    return urllib.request.urlopen(req, timeout=timeout)


def _post_npy(url, X, extra_headers=(), timeout=60):
    buf = io.BytesIO()
    np.save(buf, np.asarray(X, np.float64), allow_pickle=False)
    r = _post(url, buf.getvalue(),
              dict({"Content-Type": "application/x-npy"}, **dict(
                  extra_headers)), timeout)
    out = np.load(io.BytesIO(r.read()), allow_pickle=False)
    return out, r


# ---------------------------------------------------------------------------
# stream follower
# ---------------------------------------------------------------------------

def test_stream_follower_tail_and_torn_lines(tmp_path):
    p = str(tmp_path / "s.csv")
    block = _rows(10)
    _append(p, block[:4])
    f = StreamFollower(p)
    got = f.poll()
    assert got.shape == (4, 7) and f.rows_seen == 4
    np.testing.assert_allclose(got, block[:4], rtol=0, atol=0)
    # a torn trailing line (producer mid-write) is NOT consumed ...
    with open(p, "a") as fh:
        fh.write("0.5,0.1")                     # no newline, incomplete
    assert f.poll() is None
    off = f.offset
    with open(p, "a") as fh:
        fh.write(",1,2,3,4,5\n")
    got = f.poll()                              # ... until completed
    assert got.shape == (1, 7) and f.offset > off
    assert f.poll() is None                     # idempotent at EOF


def test_stream_follower_quarantines_unparseable_rows(tmp_path):
    """A poison row (right shape, no numbers) is quarantined to the
    deadletter sidecar — not fatal (ISSUE 17: one corrupt producer
    write must not become a trainer crash loop)."""
    p = str(tmp_path / "s.csv")
    _append(p, _rows(3))
    f = StreamFollower(p)
    assert f.poll().shape == (3, 7)
    with open(p, "a") as fh:
        fh.write("not,numbers,at,all,x,y,z\n")
    assert f.poll() is None                     # nothing good to train
    assert f.rows_skipped == 1
    with open(f.deadletter_path, "rb") as fh:
        assert fh.read() == b"not,numbers,at,all,x,y,z\n"
    # the stream keeps flowing: later good rows still train
    block = _rows(2, seed=5)
    _append(p, block)
    got = f.poll()
    np.testing.assert_allclose(got, block, rtol=0, atol=0)


def test_stream_follower_quarantines_ragged_lines(tmp_path):
    """A short line (non-atomic producer write) is quarantined; the
    good lines around it in the SAME poll still parse, in order."""
    p = str(tmp_path / "s.csv")
    block = _rows(4)
    _append(p, block[:1])
    f = StreamFollower(p)
    assert f.poll().shape == (1, 7)
    with open(p, "a") as fh:
        fh.write("0.5,0.25\n")                  # ragged: 2 of 7 cols
    _append(p, block[1:])
    got = f.poll()
    np.testing.assert_allclose(got, block[1:], rtol=0, atol=0)
    assert f.rows_skipped == 1 and f.rows_seen == 4
    with open(f.deadletter_path, "rb") as fh:
        assert fh.read() == b"0.5,0.25\n"


def test_stream_follower_skip_budget_is_fatal(tmp_path):
    """Past ``max_skips`` the follower raises: a stream that is MOSTLY
    garbage is a config error, not a few torn writes."""
    p = str(tmp_path / "s.csv")
    _append(p, _rows(1))
    f = StreamFollower(p, max_skips=2)
    f.poll()
    with open(p, "a") as fh:
        fh.write("a\nb\nc\n")
    with pytest.raises(ValueError, match="skip budget"):
        f.poll()
    assert f.rows_skipped == 3


# ---------------------------------------------------------------------------
# resident trainer: checkpoint resume continues the SAME model
# ---------------------------------------------------------------------------

def test_trainer_resume_continues_iteration(tmp_path):
    from lightgbm_tpu.robustness.checkpoint import latest_valid_checkpoint
    stream = str(tmp_path / "s.csv")
    ck = str(tmp_path / "ck")
    _append(stream, _rows(600))
    spec = TrainerSpec(params=dict(PARAMS), stream_path=stream,
                       ckpt_dir=ck, window_rows=600, min_rows=256,
                       iters_per_cycle=2, publish_every_iters=2,
                       target_iterations=4, poll_sec=0.05)
    assert run_resident_trainer(spec) == 0
    _p, st4 = latest_valid_checkpoint(ck)
    assert st4["iteration"] == 4
    svc = st4["service"]
    assert svc["watermark_rows"] == 600 and svc["watermark_ts"] > 0
    # second run with a higher target RESUMES (4 -> 8), extending the
    # committed model rather than restarting
    spec.target_iterations = 8
    assert run_resident_trainer(spec) == 0
    _p, st8 = latest_valid_checkpoint(ck)
    assert st8["iteration"] == 8
    b4 = lgb.Booster(model_str=st4["model"])
    b8 = lgb.Booster(model_str=st8["model"])
    assert b8.num_trees() == 8 and b4.num_trees() == 4
    # prefix trees bit-identical: the resume continued, not retrained
    for t4, t8 in zip(b4._engine.models, b8._engine.models):
        np.testing.assert_array_equal(np.asarray(t4.leaf_value),
                                      np.asarray(t8.leaf_value))


def test_trainer_window_autoshrink_on_oom(tmp_path):
    """An OOM'd re-bin cycle halves the rolling window down to the
    floor and the trainer KEEPS publishing (ISSUE 17): a freshness
    regression, never a crash loop."""
    from lightgbm_tpu.robustness import faults
    from lightgbm_tpu.robustness.checkpoint import latest_valid_checkpoint
    stream = str(tmp_path / "s.csv")
    ck = str(tmp_path / "ck")
    _append(stream, _rows(600))
    spec = TrainerSpec(params=dict(PARAMS), stream_path=stream,
                       ckpt_dir=ck, window_rows=600,
                       window_floor_rows=128, min_rows=256,
                       iters_per_cycle=2, publish_every_iters=2,
                       target_iterations=4, poll_sec=0.05)
    with faults.inject("oom:n=2"):      # first TWO cycles OOM
        assert run_resident_trainer(spec) == 0
    _p, st = latest_valid_checkpoint(ck)
    assert st["iteration"] == 4         # still reached the target
    svc = st["service"]
    assert svc["window_rows_target"] == 150      # 600 -> 300 -> 150
    assert svc["window_rows"] <= 150
    assert svc["skipped_rows"] == 0


def test_trainer_window_grows_back_when_pressure_clears(tmp_path):
    """After sustained clean cycles the shrunken window recovers to the
    spec size — the shrink is adaptive, not a ratchet."""
    from lightgbm_tpu.robustness import faults
    from lightgbm_tpu.robustness.checkpoint import latest_valid_checkpoint
    stream = str(tmp_path / "s.csv")
    ck = str(tmp_path / "ck")
    _append(stream, _rows(600))
    spec = TrainerSpec(params=dict(PARAMS), stream_path=stream,
                       ckpt_dir=ck, window_rows=600,
                       window_floor_rows=128, min_rows=256,
                       iters_per_cycle=2, publish_every_iters=2,
                       target_iterations=10, poll_sec=0.05)
    with faults.inject("oom:n=1"):      # one OOM'd cycle, then clear
        assert run_resident_trainer(spec) == 0
    _p, st = latest_valid_checkpoint(ck)
    assert st["iteration"] == 10
    assert st["service"]["window_rows_target"] == 600   # grew back


def test_trainer_oom_at_floor_is_fatal(tmp_path):
    """Persistent OOM that survives shrinking to the floor re-raises:
    genuine exhaustion must surface, not spin forever on a floor-sized
    window that still doesn't fit."""
    from lightgbm_tpu.robustness import faults
    stream = str(tmp_path / "s.csv")
    _append(stream, _rows(400))
    spec = TrainerSpec(params=dict(PARAMS), stream_path=stream,
                       ckpt_dir=str(tmp_path / "ck"), window_rows=400,
                       window_floor_rows=400, min_rows=256,
                       iters_per_cycle=2, publish_every_iters=2,
                       target_iterations=4, poll_sec=0.05)
    with faults.inject("oom:p=1:n=100000"):
        with pytest.raises(faults.OOMInjected):
            run_resident_trainer(spec)


# ---------------------------------------------------------------------------
# front door over a plain ModelServer (no trainer: fast, deterministic)
# ---------------------------------------------------------------------------

@pytest.fixture
def served_booster():
    block = _rows(500, seed=3)
    X, y = block[:, 1:], block[:, 0]
    bst = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y),
                    num_boost_round=4, keep_training_booster=True)
    srv = bst.serve(linger_ms=1.0, raw_score=True)
    gw = ServerGateway(srv)
    door = FrontDoor(gw, chunk_rows=64, max_body_mb=1.0)
    yield bst, srv, gw, door
    door.close()
    srv.close(timeout=60)


def test_http_scores_bit_identical_to_predict_device(served_booster):
    bst, _srv, _gw, door = served_booster
    probe = _rows(48, seed=5)[:, 1:].astype(np.float64)
    want = bst.predict(probe, device=True, raw_score=True)
    out, r = _post_npy(door.address + "/v1/predict", probe)
    np.testing.assert_array_equal(out, want)     # bit-identical
    assert r.headers["X-Model-Generation"] == "1"
    # JSON route: repr round-trip is exact too
    rj = _post(door.address + "/v1/predict",
               json.dumps({"rows": probe.tolist()}).encode(),
               {"Content-Type": "application/json"})
    got = np.asarray(json.loads(rj.read())["scores"])
    np.testing.assert_array_equal(got, want)


def test_http_chunked_streaming_large_response(served_booster):
    bst, _srv, _gw, door = served_booster
    probe = _rows(200, seed=6)[:, 1:].astype(np.float64)  # > chunk_rows=64
    want = bst.predict(probe, device=True, raw_score=True)
    out, r = _post_npy(door.address + "/v1/predict", probe)
    assert r.headers.get("Transfer-Encoding") == "chunked"
    np.testing.assert_array_equal(out, want)
    rj = _post(door.address + "/v1/predict",
               json.dumps({"rows": probe.tolist()}).encode(),
               {"Content-Type": "application/json"})
    assert rj.headers.get("Transfer-Encoding") == "chunked"
    got = np.asarray(json.loads(rj.read())["scores"])
    np.testing.assert_array_equal(got, want)


def test_wire_deadline_expires_before_coalescing(served_booster):
    """X-Deadline-Ms -> submit(deadline_ms=) -> the dispatcher drops the
    expired request BEFORE coalescing (PR9) -> HTTP 504; the wedged
    batch is still answered and the peer's bits are unaffected."""
    bst, srv, _gw, door = served_booster
    probe = _rows(32, seed=7)[:, 1:].astype(np.float64)
    want = bst.predict(probe, device=True, raw_score=True)
    codes = {}

    def slow_req():
        out, r = _post_npy(door.address + "/v1/predict", probe,
                           timeout=90)
        codes["slow"] = (r.status, out)

    with faults.inject("slow_dispatch:sec=0.6:n=1"):
        t = threading.Thread(target=slow_req)
        t.start()
        t_end = time.monotonic() + 5
        while srv.stats()["queued_rows"] and time.monotonic() < t_end:
            time.sleep(0.01)
        time.sleep(0.05)          # outlive the linger (pop != dispatched)
        try:
            _post_npy(door.address + "/v1/predict", probe,
                      extra_headers=[("X-Deadline-Ms", "40")],
                      timeout=90)
            raise AssertionError("expired wire deadline was served")
        except urllib.error.HTTPError as e:
            assert e.code == 504
            assert "DEADLINE_EXCEEDED" in json.loads(e.read())["error"]
        t.join(90)
    st, out = codes["slow"]
    assert st == 200
    np.testing.assert_array_equal(out, want)
    assert srv.counters.get("expired") == 1


def test_malformed_and_oversize_rejected_without_poisoning(
        served_booster):
    bst, srv, _gw, door = served_booster
    url = door.address + "/v1/predict"
    probe = _rows(16, seed=8)[:, 1:].astype(np.float64)
    want = bst.predict(probe, device=True, raw_score=True)
    n0 = srv.stats()["requests"]

    def expect(code, body, headers):
        try:
            _post(url, body, headers)
            raise AssertionError(f"expected HTTP {code}")
        except urllib.error.HTTPError as e:
            assert e.code == code, (e.code, e.read())

    expect(400, b"{not json", {"Content-Type": "application/json"})
    expect(400, json.dumps({"rows": [["a", "b"]]}).encode(),
           {"Content-Type": "application/json"})
    # wrong feature width fails ITS submitter at submit() validation
    expect(400, json.dumps({"rows": [[1.0, 2.0]]}).encode(),
           {"Content-Type": "application/json"})
    expect(400, b"whatever", {"Content-Type": "text/plain"})
    big = b"x" * (door.max_body_bytes + 1)
    expect(413, big, {"Content-Type": "application/x-npy",
                      "Content-Length": str(len(big))})
    # none of the rejects reached the dispatcher...
    assert srv.stats()["requests"] == n0
    # ...and a well-formed peer is served bit-identically afterwards
    out, _r = _post_npy(url, probe)
    np.testing.assert_array_equal(out, want)


def test_malformed_reject_404_route(served_booster):
    _bst, _srv, _gw, door = served_booster
    try:
        _post(door.address + "/v1/nope", b"{}",
              {"Content-Type": "application/json"})
        raise AssertionError("expected 404")
    except urllib.error.HTTPError as e:
        assert e.code == 404


def test_staleness_headers_and_stats(served_booster):
    _bst, _srv, gw, door = served_booster
    mark_ts = time.time() - 1.5
    gw.set_watermark(1, rows=1234, ts=mark_ts, iteration=4)
    probe = _rows(8, seed=9)[:, 1:].astype(np.float64)
    _out, r = _post_npy(door.address + "/v1/predict", probe)
    assert r.headers["X-Watermark-Rows"] == "1234"
    stale = float(r.headers["X-Staleness-Ms"])
    assert 1000.0 <= stale < 120_000.0
    st = json.loads(urllib.request.urlopen(
        door.address + "/v1/stats", timeout=30).read())
    assert st["staleness_p50_ms"] >= 1000.0
    h = json.loads(urllib.request.urlopen(
        door.address + "/healthz", timeout=30).read())
    assert h["status"] == "ok"


def test_overload_maps_to_429(served_booster):
    _bst, srv, _gw, door = served_booster
    probe = _rows(8, seed=10)[:, 1:].astype(np.float64)
    # wedge the dispatcher, fill the queue past the row bound, submit
    orig = srv._batcher.max_queue_rows
    srv._batcher.max_queue_rows = 8
    try:
        with faults.inject("slow_dispatch:sec=0.5:n=1"):
            slow = srv.submit(probe)             # wedges the dispatcher
            t_end = time.monotonic() + 5
            while srv.stats()["queued_rows"] and \
                    time.monotonic() < t_end:
                time.sleep(0.01)
            time.sleep(0.05)
            backlog = srv.submit(probe)          # backlog: 8 rows queued
            try:
                _post_npy(door.address + "/v1/predict", probe)
                raise AssertionError("expected 429")
            except urllib.error.HTTPError as e:
                assert e.code == 429
                assert e.headers.get("Retry-After") is not None
            slow.result(60)
            backlog.result(60)
    finally:
        srv._batcher.max_queue_rows = orig


def test_frontdoor_fleet_tenant_route():
    """The front door serves a FleetServer too: /v1/tenants/<t>/predict
    routes to the named tenant with per-tenant bit-identity; an unknown
    tenant is 404."""
    boosters = {}
    for i, leaves in enumerate((15, 31)):
        block = _rows(400, seed=20 + i)
        boosters[f"t{i}"] = lgb.train(
            dict(PARAMS, num_leaves=leaves),
            lgb.Dataset(block[:, 1:], label=block[:, 0]),
            num_boost_round=3, keep_training_booster=True)
    fleet = lgb.serve_fleet(boosters, raw_score=True, linger_ms=1.0)
    gw = ServerGateway(None, fleet=fleet)
    door = FrontDoor(gw)
    try:
        probe = _rows(16, seed=22)[:, 1:].astype(np.float64)
        for name, bst in boosters.items():
            want = bst.predict(probe, device=True, raw_score=True)
            out, _r = _post_npy(
                door.address + f"/v1/tenants/{name}/predict", probe)
            np.testing.assert_array_equal(out, want)
        try:
            _post_npy(door.address + "/v1/tenants/nope/predict", probe)
            raise AssertionError("unknown tenant served")
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        door.close()
        fleet.close()


# ---------------------------------------------------------------------------
# end-to-end continual service (thread trainer — in-budget for tier-1)
# ---------------------------------------------------------------------------

def test_continual_service_publishes_and_serves(tmp_path):
    from lightgbm_tpu.robustness.checkpoint import (list_checkpoints,
                                                    read_checkpoint)
    stream = str(tmp_path / "s.csv")
    ck = str(tmp_path / "ck")
    _append(stream, _rows(600, seed=11))
    svc = ContinualService(
        dict(PARAMS), stream, ck, trainer_mode="thread",
        window_rows=800, min_rows=256, iters_per_cycle=2,
        publish_every_iters=2, target_iterations=6, raw_score=True,
        boot_timeout_s=300, poll_sec=0.05)
    try:
        probe = _rows(24, seed=12)[:, 1:].astype(np.float64)
        url = svc.frontdoor.address
        seen = []
        t_end = time.time() + 120
        while time.time() < t_end:
            _append(stream, _rows(40, seed=int(time.time() * 997) % 9973))
            out, r = _post_npy(url + "/v1/predict", probe)
            seen.append((int(r.headers["X-Model-Generation"]), out,
                         float(r.headers["X-Staleness-Ms"])))
            if svc.stats()["service"]["served_iteration"] >= 6:
                break
            time.sleep(0.1)
        versions = [v for v, _o, _s in seen]
        assert versions == sorted(versions), "generations moved backwards"
        assert svc.generation.version >= 3, seen
        # every response bit-matches ITS generation's checkpointed model
        by_iter = {}
        for it, path in list_checkpoints(ck):
            by_iter[it] = read_checkpoint(path)["model"]
        for v, out, stale in seen:
            assert stale >= 0.0
            mark = svc.freshness(v)
            assert mark is not None
            model = by_iter.get(mark["iteration"])
            if model is None:
                continue                          # pruned checkpoint
            ref = lgb.Booster(model_str=model)
            np.testing.assert_array_equal(
                out, ref.predict(probe, device=True, raw_score=True))
        # incremental the whole way: never a destructive repack
        assert svc.generation.model_gen == 0
        st = svc.stats()
        assert st["service"]["publishes"] >= 3
        assert st["staleness_n"] == len(seen)
    finally:
        svc.close()
    # closed service reports closed
    assert svc.closed


# ---------------------------------------------------------------------------
# integrity defense satellites (ISSUE 19)
# ---------------------------------------------------------------------------

def test_readyz_vs_healthz_liveness():
    """``/readyz`` is the load-balancer signal: 503 the moment the tier
    is degraded, while ``/healthz`` stays 200 — restarting a live
    process never fixes degradation, so liveness must not flap with
    readiness."""
    block = _rows(400, seed=11)
    X, y = block[:, 1:], block[:, 0]
    bst = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y),
                    num_boost_round=2)
    # probe_interval_s=0: forced degradation is sticky (no recovery
    # probe to un-degrade mid-assert)
    srv = bst.serve(linger_ms=1.0, raw_score=True, probe_interval_s=0.0)
    gw = ServerGateway(srv)
    door = FrontDoor(gw)
    try:
        r = urllib.request.urlopen(door.address + "/readyz", timeout=30)
        assert r.status == 200
        assert json.loads(r.read()) == {"ready": True, "status": "ok"}

        srv.degrade("readiness drill")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(door.address + "/readyz", timeout=30)
        assert ei.value.code == 503
        body = json.loads(ei.value.read())
        assert body["ready"] is False and body["status"] == "degraded"
        # liveness unchanged: degraded-but-alive is 200 on /healthz
        r = urllib.request.urlopen(door.address + "/healthz", timeout=30)
        assert r.status == 200
        assert json.loads(r.read())["status"] == "degraded"
        # and the degraded tier still answers correctly (host walk)
        probe = _rows(16, seed=13)[:, 1:].astype(np.float64)
        out, _r = _post_npy(door.address + "/v1/predict", probe)
        np.testing.assert_allclose(
            out, bst.predict(probe, raw_score=True),
            rtol=1e-5, atol=1e-6)
    finally:
        door.close()
        srv.close(timeout=60)
    # a CLOSED server is neither live nor ready
    door2 = FrontDoor(ServerGateway(srv))
    try:
        for route in ("/readyz", "/healthz"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(door2.address + route, timeout=30)
            assert ei.value.code == 503, route
        assert json.loads(ei.value.read())["status"] == "closed"
    finally:
        door2.close()


def test_deadletter_survives_supervised_relaunch(tmp_path):
    """Poison rows quarantined to the ``.deadletter`` sidecar — and the
    ``skipped_rows`` count in the checkpointed watermark — survive a
    supervised trainer crash + relaunch: the relaunched child must not
    report a clean stream while the sidecar holds quarantined lines."""
    from lightgbm_tpu.robustness.checkpoint import latest_valid_checkpoint
    from lightgbm_tpu.service.trainer import TrainerSupervisor
    stream = str(tmp_path / "s.csv")
    ck = str(tmp_path / "ck")
    block = _rows(600)
    _append(stream, block[:300])
    with open(stream, "a") as f:
        f.write("not,a,number,row,at,all,zzz\n")   # unparseable
        f.write("1.0,2.0\n")                        # ragged
    _append(stream, block[300:])
    spec = TrainerSpec(params=dict(PARAMS), stream_path=stream,
                       ckpt_dir=ck, window_rows=600, min_rows=256,
                       iters_per_cycle=2, publish_every_iters=2,
                       target_iterations=6, poll_sec=0.05)
    # attempt 0 is murdered at the iteration boundary AFTER its first
    # commit; attempt 1 runs fault-free to the target
    sup = TrainerSupervisor(
        spec, max_relaunches=2,
        attempt_env=lambda i: (
            {"LGBM_TPU_FAULTS": "rank_kill:rank=0:after=2"}
            if i == 0 else {"LGBM_TPU_FAULTS": ""}))
    t_end = time.time() + 570
    try:
        while time.time() < t_end and sup.alive:
            time.sleep(0.25)
        assert not sup.alive, sup.describe()
        assert sup.last_rc == 0, sup.describe()
        assert sup.relaunches == 1, sup.describe()
    finally:
        sup.stop()
    found = latest_valid_checkpoint(ck)
    assert found is not None
    st = found[1]
    assert int(st["iteration"]) == 6
    # BOTH halves of the deadletter contract survived the relaunch
    assert int(st["service"]["skipped_rows"]) >= 2, st["service"]
    with open(stream + ".deadletter", "rb") as f:
        dead = f.read()
    assert b"not,a,number" in dead and b"1.0,2.0" in dead


def test_trainer_child_platform_is_explicit(monkeypatch):
    """A caller-set JAX_PLATFORMS is inherited; unset on the CPU nothing
    is added; unset while THIS process holds an accelerator the child is
    put on the CPU (one process per chip) — loudly, never silently."""
    import jax

    from lightgbm_tpu.service.trainer import TrainerSupervisor
    from lightgbm_tpu.utils import log
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert TrainerSupervisor._child_platform_env() == {}
    monkeypatch.delenv("JAX_PLATFORMS")
    assert TrainerSupervisor._child_platform_env() == {}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seen = []
    level = log._level          # earlier tests train with verbose=-1
    log.set_verbosity(log.WARNING)
    log.register_logger(seen.append)
    try:
        assert TrainerSupervisor._child_platform_env() == {
            "JAX_PLATFORMS": "cpu"}
    finally:
        log.register_logger(None)
        log.set_verbosity(level)
    assert any("platform=cpu" in m and "thread" in m for m in seen), seen
