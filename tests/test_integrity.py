"""Integrity defense (ISSUE 19): silent-corruption canaries for the
serving tier, the numeric-health guard for training, gang digest
agreement, and the disk-full survival path of the publish channel.

The drills here are the CPU-fast halves of the acceptance criteria:

- primitives: canary batches, CRC fingerprints, ``corrupt_pack`` rot,
  digest-moment agreement algebra, the numeric-health guard's refusal
  table, ``where=``-filtered fault budgets;
- solo server: in-residency device rot -> canary mismatch -> quarantine
  to the host walk -> repair republish -> un-quarantine, with exact
  counter accounting;
- fleet: device rot caught BEFORE install (0 wrong responses), only the
  afflicted tenant quarantined, host-rot diagnosed by the mega-pack CRC,
  a corrupt publish refused by the host-walk anchor;
- ``/readyz`` flips 503 while any tenant route is quarantined;
- checkpoint writes survive ENOSPC by pruning beyond ``keep_last`` and
  retrying once.

The full chaos proof (fleet traffic + injected rot + trainer poisoning
under load) is ``scripts/serving_load.py --integrity-chaos``; the gang
divergence drill over injected collectives rides the slow-marked
harness in test_injected_collectives.py's world (see
scripts/integrity_smoke.py for the <30 s version).
"""
import errno
import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.robustness import checkpoint as ckpt
from lightgbm_tpu.robustness import faults
from lightgbm_tpu.robustness import integrity

PARAMS = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
          "verbose": -1, "deterministic": True, "seed": 7}


def _data(n=500, f=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    return X, y


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def test_canary_batch_deterministic_and_f32_exact():
    a = integrity.canary_batch(7, rows=16, seed=0)
    b = integrity.canary_batch(7, rows=16, seed=0)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (16, 7) and a.dtype == np.float64
    # f32-representable: the device cast must be lossless so host-walk
    # and device routes score THE SAME canary bits
    np.testing.assert_array_equal(a, a.astype(np.float32).astype(np.float64))
    assert not np.array_equal(a, integrity.canary_batch(7, seed=1))
    assert not np.array_equal(a[:, :6], integrity.canary_batch(6))


def test_numeric_guard_refusal_table():
    g = integrity.NumericHealthGuard(window=4, spike_factor=10.0)
    g.check_gradients(1.5, 2.5, 0)                  # finite: fine
    with pytest.raises(integrity.NumericHealthError):
        g.check_gradients(float("nan"), 1.0, 1)
    with pytest.raises(integrity.NumericHealthError):
        g.check_gradients(1.0, float("inf"), 1)
    with pytest.raises(integrity.NumericHealthError):
        g.check_leaves(np.array([0.1, np.nan]), 2)
    g.check_leaves(np.array([0.1, -0.2]), 2)
    # loss spike: 10x over the rolling-window median trips the guard
    for i in range(4):
        g.observe_loss(1.0 + 0.01 * i, i)
    with pytest.raises(integrity.NumericHealthError):
        g.observe_loss(1000.0, 5)
    # the spike cleared the history: recovery does not re-trip
    for i in range(6, 10):
        g.observe_loss(1.0, i)
    # every refusal is DATA_CORRUPTION-classified (rollback, not retry)
    from lightgbm_tpu.robustness.retry import is_corruption_error
    try:
        g.check_gradients(float("nan"), 1.0, 1)
    except integrity.NumericHealthError as e:
        assert is_corruption_error(e)


def test_loss_spike_fault_site_trips_guard():
    g = integrity.NumericHealthGuard(window=4, spike_factor=10.0)
    for i in range(4):
        g.observe_loss(1.0, i)
    with faults.inject("loss_spike:p=1"):
        with pytest.raises(integrity.NumericHealthError):
            g.observe_loss(1.0, 4)


def test_digest_reduction_agreement_algebra():
    """world * sum(d^2) == (sum d)^2 per 16-bit half iff every rank
    holds the SAME digest — exact in f64, transported over nothing but
    reduce_sum (the only collective the injection API guarantees)."""
    digest = integrity.iteration_digest([])  # empty is a digest too
    X, y = _data(200, 4, seed=2)
    bst = lgb.train(dict(PARAMS, num_leaves=7),
                    lgb.Dataset(X, label=y), num_boost_round=2)
    digest = integrity.iteration_digest(bst._engine.models)
    assert digest == integrity.iteration_digest(bst._engine.models)
    for world in (2, 4):
        total = world * integrity.digest_reduction(digest)
        integrity.check_digest_reduction(total, world, digest, 3)
    # one lying rank: every OTHER rank's verification fails too
    world = 3
    bad = digest ^ 0x1
    total = (2 * integrity.digest_reduction(digest) +
             integrity.digest_reduction(bad))
    for d in (digest, bad):
        with pytest.raises(integrity.GangDivergence):
            integrity.check_digest_reduction(total, world, d, 3, rank=0)


def test_crc_fingerprint_catches_pack_rot():
    import jax
    X, y = _data(300, 5, seed=4)
    bst = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y),
                    num_boost_round=3)
    srv = bst.serve(linger_ms=1.0, raw_score=True, probe_interval_s=0.0)
    try:
        # the placed serving window, pulled back to host — the same
        # pytree server.py rots for the where=dev drill
        win = jax.tree.map(np.asarray, srv._active[0].win)
    finally:
        srv.close(timeout=60)
    before = integrity.crc32_fingerprint(win)
    assert before == integrity.crc32_fingerprint(win)   # deterministic
    rotten = integrity.corrupt_pack(win)
    assert integrity.crc32_fingerprint(rotten) != before
    assert integrity.crc32_fingerprint(win) == before   # copy, not mutate
    # the rot is real: slot-0 leaf outputs sign-flipped
    a = np.asarray(getattr(win, "tree", win).leaf_value)
    b = np.asarray(getattr(rotten, "tree", rotten).leaf_value)
    assert np.all(b[0] == -a[0]) and np.any(b != a)


def test_where_filter_preserves_fault_budget():
    """A ``where=dev`` plan must NOT be burned by consults at other
    sites: the ckpt consult leaves the single-fire plan armed for the
    device consult."""
    with faults.inject("bitflip:p=1:where=dev"):
        assert not faults.check("bitflip", where="ckpt")
        assert not faults.check("bitflip", where="host")
        assert faults.check("bitflip", where="dev")
        assert not faults.check("bitflip", where="dev")  # fired once


# ---------------------------------------------------------------------------
# checkpoint disk-full survival
# ---------------------------------------------------------------------------

def _state(i):
    return {"iteration": i, "model": f"model-{i}\n" * 50}


def test_checkpoint_enospc_prunes_and_retries(tmp_path):
    d = str(tmp_path)
    for i in range(1, 6):
        ckpt.write_checkpoint(d, _state(i))
    assert len(ckpt.list_checkpoints(d)) == 5
    with faults.inject("disk_full:p=1"):
        path = ckpt.write_checkpoint(d, _state(6), keep_last=2)
    # the single-fire ENOSPC was survived: pruned to keep_last=2 THEN
    # committed the new generation on the retry
    its = sorted(i for i, _p in ckpt.list_checkpoints(d))
    assert its == [4, 5, 6], its
    _p, st = ckpt.latest_valid_checkpoint(d)
    assert st["iteration"] == 6 and st["model"] == _state(6)["model"]
    assert path.endswith(ckpt.checkpoint_name(6))
    # no tmp litter left behind by the failed attempt
    litter = [p for p in tmp_path.iterdir() if ".tmp." in p.name]
    assert not litter, litter


def test_checkpoint_enospc_without_retention_is_loud(tmp_path):
    d = str(tmp_path)
    ckpt.write_checkpoint(d, _state(1))
    with faults.inject("disk_full:p=1"):
        with pytest.raises(OSError) as ei:
            ckpt.write_checkpoint(d, _state(2))        # keep_last=None
    assert ei.value.errno == errno.ENOSPC
    # the committed set is untouched by the failure
    _p, st = ckpt.latest_valid_checkpoint(d)
    assert st["iteration"] == 1


def test_checkpoint_enospc_twice_is_fatal(tmp_path):
    d = str(tmp_path)
    ckpt.write_checkpoint(d, _state(1))
    with faults.inject("disk_full:p=1:n=2"):
        with pytest.raises(OSError) as ei:
            ckpt.write_checkpoint(d, _state(2), keep_last=2)
    assert ei.value.errno == errno.ENOSPC
    _p, st = ckpt.latest_valid_checkpoint(d)
    assert st["iteration"] == 1


# ---------------------------------------------------------------------------
# solo server canary round-trip
# ---------------------------------------------------------------------------

def test_solo_canary_quarantine_repair_roundtrip():
    X, y = _data(seed=5)
    params = dict(PARAMS, tpu_integrity_probe_interval_s=0.05)
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=5,
                    keep_training_booster=True)
    srv = bst.serve(linger_ms=1.0, raw_score=True, probe_interval_s=0.05)
    try:
        y0 = srv.predict(X[:64])
        np.testing.assert_allclose(y0, bst.predict(X[:64], raw_score=True),
                                   rtol=1e-5, atol=1e-6)
        st = srv.stats()
        assert st["integrity_probe_interval_s"] == 0.05

        # in-residency rot: republish with the device-rot plan armed —
        # the golden records from the CLEAN snapshot, then the resident
        # pack's bits flip under it
        with faults.inject("bitflip:p=1:where=dev"):
            srv.publish()
        deadline = time.time() + 20
        while time.time() < deadline:
            if srv.counters.snapshot().get("repairs", 0) >= 1 and \
                    not srv.stats().get("degraded"):
                break
            time.sleep(0.05)
        snap = srv.counters.snapshot()
        assert snap["integrity_probes"] >= 1, snap
        assert snap["integrity_mismatches"] == 1, snap
        assert snap["quarantines"] == 1, snap
        assert snap["repairs"] == 1, snap
        assert not srv.stats().get("degraded")
        # repaired device route: bit-identical to the pre-rot answers
        np.testing.assert_array_equal(srv.predict(X[:64]), y0)
    finally:
        srv.close(timeout=60)


# ---------------------------------------------------------------------------
# fleet canary: rot diagnosis, blast radius, repair
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fleet_pair():
    X, y = _data(seed=0)
    params = dict(PARAMS, tpu_integrity_probe_interval_s=0.15)
    b1 = lgb.train(dict(params), lgb.Dataset(X, label=y),
                   num_boost_round=6, keep_training_booster=True)
    b2 = lgb.train(dict(params, seed=11), lgb.Dataset(X, label=y),
                   num_boost_round=6)
    return X, b1, b2


def test_fleet_device_rot_quarantines_only_afflicted_tenant(fleet_pair):
    X, b1, b2 = fleet_pair
    # a LONG probe interval, and the probe's cycle run by hand below: on a
    # loaded machine the fixture's 0.15 s background probe came round
    # inside the armed fault (a second quarantine) or repaired before the
    # quarantine was read (ROADMAP C10)
    cfg = b1.config.copy()
    cfg.set("tpu_integrity_probe_interval_s", 600.0)
    fleet = lgb.serve_fleet({"a": b1, "b": b2}, config=cfg)
    try:
        assert fleet.stats()["n_buckets"] == 1   # shared mega-pack
        ya0, yb0 = fleet.predict("a", X), fleet.predict("b", X)

        # rot the REBUILT upload: evict a's pack, arm the device plan —
        # the canary verify catches the corrupt pack BEFORE install, so
        # no wrong bits are ever served
        assert fleet.evict("a")
        with faults.inject("bitflip:p=1:where=dev"):
            ya1 = fleet.predict("a", X)
            yb1 = fleet.predict("b", X)
        # tenant a answered by the host walk (f64 — allclose, not
        # bit-equal); tenant b's clean rebuild serves device bits
        np.testing.assert_allclose(ya1, ya0, rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(yb1, yb0)
        snap = fleet.counters.snapshot()
        assert snap["integrity_mismatches"] == 1, snap
        assert snap["quarantines"] == 1, snap
        assert fleet.tenant_stats("a")["quarantined"] is True
        assert fleet.tenant_stats("b")["quarantined"] is False
        assert fleet.stats()["quarantined"] == ["a"]
        # quarantined answers stay deterministic (host walk, same bits)
        np.testing.assert_array_equal(fleet.predict("a", X), ya1)

        # one probe cycle repairs (clean re-upload) and un-quarantines
        fleet._integrity_check()
        snap = fleet.counters.snapshot()
        assert snap["integrity_probes"] == 1, snap
        assert snap["repairs"] == 1, snap
        assert snap["integrity_mismatches"] == 1, snap   # no recount
        assert "quarantined" not in fleet.stats()
        np.testing.assert_array_equal(fleet.predict("a", X), ya0)
        np.testing.assert_array_equal(fleet.predict("b", X), yb0)
        # per-tenant accounting: a carries the incident, b is clean
        ts = fleet.tenant_stats("a")
        assert ts["integrity_mismatches"] == 1 \
            and ts["quarantines"] == 1 and ts["repairs"] == 1, ts
        tb = fleet.tenant_stats("b")
        assert tb.get("integrity_mismatches", 0) == 0, tb
    finally:
        fleet.close()


def test_fleet_host_rot_diagnosed_by_crc_and_rebuilt(fleet_pair):
    X, b1, b2 = fleet_pair
    # a LONG probe interval: the rebuild's verify finds the rot, once; on
    # a loaded machine the 0.15 s background probe found it too, first,
    # and the count below read 2 (ROADMAP C10)
    cfg = b1.config.copy()
    cfg.set("tpu_integrity_probe_interval_s", 600.0)
    fleet = lgb.serve_fleet({"a": b1, "b": b2}, config=cfg)
    try:
        ya0, yb0 = fleet.predict("a", X), fleet.predict("b", X)
        # rot the RETAINED host mega-pack in place: the recorded CRC
        # distinguishes host-side rot (rebuild from engine windows)
        # from device-side rot (re-upload of clean host bits)
        b = list(fleet._state.buckets.values())[0]
        carrier = getattr(b.host, "tree", b.host)
        carrier.leaf_value[0] = -carrier.leaf_value[0]
        assert fleet.evict("a")
        ya1, yb1 = fleet.predict("a", X), fleet.predict("b", X)
        # the rebuild-from-windows path produced CLEAN device bits:
        # nobody was quarantined, nobody got wrong answers
        np.testing.assert_array_equal(ya1, ya0)
        np.testing.assert_array_equal(yb1, yb0)
        snap = fleet.counters.snapshot()
        assert snap["integrity_mismatches"] == 1, snap
        assert snap["quarantines"] == 0, snap
        assert not fleet.tenant_stats("a")["quarantined"]
    finally:
        fleet.close()


def test_fleet_publish_anchor_refuses_corrupt_pack(fleet_pair):
    X, b1, _b2 = fleet_pair
    fleet = lgb.serve_fleet({"a": b1})
    try:
        ya0 = fleet.predict("a", X)
        gen0 = fleet._state.routes["a"].generation.version
        b1.update()
        try:
            with faults.inject("bitflip:p=1:where=host"):
                fleet.publish("a")
            raise AssertionError("corrupt publish was not refused")
        except integrity.CanaryMismatch:
            pass
        # still serving the OLD generation, untorn
        assert fleet._state.routes["a"].generation.version == gen0
        np.testing.assert_array_equal(fleet.predict("a", X), ya0)
        fleet.publish("a")                    # clean publish succeeds
        assert fleet._state.routes["a"].generation.version == gen0 + 1
    finally:
        fleet.close()
        b1.rollback_one_iter()


def test_readyz_flips_503_while_tenant_quarantined(fleet_pair):
    from lightgbm_tpu.service import FrontDoor, ServerGateway
    X, b1, b2 = fleet_pair
    # a LONG probe interval: detection comes from the rebuild verify,
    # and no background repair races the readiness asserts
    cfg = b1.config.copy()
    cfg.set("tpu_integrity_probe_interval_s", 600.0)
    fleet = lgb.serve_fleet({"a": b1, "b": b2}, config=cfg)
    door = FrontDoor(ServerGateway(None, fleet=fleet))
    try:
        r = urllib.request.urlopen(door.address + "/readyz", timeout=30)
        assert json.loads(r.read()) == {"ready": True, "status": "ok"}
        assert fleet.evict("a")
        with faults.inject("bitflip:p=1:where=dev"):
            fleet.predict("a", X[:32])
        assert fleet.tenant_stats("a")["quarantined"]
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(door.address + "/readyz", timeout=30)
        assert ei.value.code == 503
        body = json.loads(ei.value.read())
        assert body["status"] == "quarantined"
        assert body["quarantined"] == ["a"]
        # liveness unaffected: the fleet still answers, /healthz is 200
        r = urllib.request.urlopen(door.address + "/healthz", timeout=30)
        assert r.status == 200
    finally:
        door.close()
        fleet.close()


def test_gang_digest_check_stubbed_transport():
    """``_gang_digest_check`` end to end on ONE thread: a stubbed
    ``reduce_sum`` transport plays the gang (the real threaded
    injected-collectives harness needs parallelism this box lacks).
    Agreement verifies; a diverged peer — or this rank lying via the
    ``where=digest`` bitflip drill — raises GangDivergence; world=1
    never consults the transport."""
    rng = np.random.default_rng(0)
    X = rng.standard_normal((300, 5))
    y = (X[:, 0] > 0).astype(np.float64)
    bst = lgb.train(dict(PARAMS, tpu_integrity_digest_every=1),
                    lgb.Dataset(X, label=y), num_boost_round=2)
    eng = bst._engine
    K = eng.num_tree_per_iteration
    honest = integrity.digest_reduction(
        integrity.iteration_digest(eng.models[-K:]))

    # clean agreement: every rank committed the same trees
    eng._inj = {"reduce_sum": lambda v: np.asarray(v) * 2,
                "num_machines": 2, "rank": 0}
    eng._gang_digest_check()

    # a peer synced a digest for DIFFERENT trees: refuse loudly
    peer = integrity.digest_reduction(0xDEADBEEF)
    eng._inj = {"reduce_sum": lambda v: np.asarray(v) + peer,
                "num_machines": 2, "rank": 1}
    with pytest.raises(integrity.GangDivergence):
        eng._gang_digest_check()

    # the where=digest drill: THIS rank lies, the honest peer does not
    eng._inj = {"reduce_sum": lambda v: np.asarray(v) + honest,
                "num_machines": 2, "rank": 0}
    with faults.inject("bitflip:p=1:where=digest"):
        with pytest.raises(integrity.GangDivergence):
            eng._gang_digest_check()

    # world=1: the transport must never be consulted
    def boom(_v):
        raise AssertionError("reduce_sum consulted for world=1")
    eng._inj = {"reduce_sum": boom, "num_machines": 1, "rank": 0}
    eng._gang_digest_check()
