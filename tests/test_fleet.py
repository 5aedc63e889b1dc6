"""Multi-tenant fleet serving (ISSUE 13): capacity bucketing units,
cross-tenant coalescing bit-parity vs each tenant's own predict_device,
per-tenant isolation (malformed / expired / publish_fail never touch
coalesced peers), exact per-tenant counter accounting (the PR9 contract
extended to 3 tenants), the flat-in-fleet-size trace budget, placement
modes, and the one-live-server-per-booster regression."""
import threading
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.analysis import guards
from lightgbm_tpu.ops import forest
from lightgbm_tpu.robustness import faults
from lightgbm_tpu.serving import (DeadlineExceeded, FleetServer, Overloaded,
                                  ServingCounters, TenantHandle, serve_fleet)


def _make_booster(seed, n_features=6, leaves=15, trees=5, rows=700,
                  objective="regression", scale=1.0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, n_features)).astype(np.float32) \
        .astype(np.float64)
    if objective == "multiclass":
        y = (np.abs(X[:, 0] * scale) * 1.5).astype(int) % 3
        params = {"objective": "multiclass", "num_class": 3}
    elif objective == "binary":
        y = (X[:, 0] * scale + 0.3 * X[:, 1] ** 2 > 0.2).astype(float)
        params = {"objective": "binary"}
    else:
        y = X[:, 0] * scale + 0.3 * X[:, 1] ** 2
        params = {"objective": "regression"}
    params.update({"num_leaves": leaves, "verbose": -1,
                   "min_data_in_leaf": 5})
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=trees,
                    keep_training_booster=True)
    return bst, X


@pytest.fixture(scope="module")
def trio():
    """Three same-shape tenants (they share one bucket) + request
    pools."""
    return {f"t{i}": _make_booster(seed=10 + i, scale=1.0 + i)
            for i in range(3)}


# ---------------------------------------------------------------------------
# capacity bucketing units (no server needed)
# ---------------------------------------------------------------------------

def test_pow2_cap():
    assert forest.pow2_cap(1) == 1
    assert forest.pow2_cap(2) == 2
    assert forest.pow2_cap(3) == 4
    assert forest.pow2_cap(5, lo=4) == 8
    assert forest.pow2_cap(2, lo=4) == 4
    assert forest.pow2_cap(0) == 1


def test_tenant_shape_buckets_not_global_max():
    """Mixed-shape tenants land in SEPARATE buckets sized to their own
    pow2 caps — a small model never pads to a big neighbor's shape."""
    small, _ = _make_booster(1, leaves=7, trees=3)
    big, _ = _make_booster(2, leaves=31, trees=20)
    ss = forest.tenant_shape(small._engine.models, 1, 6, "binned")
    bs = forest.tenant_shape(big._engine.models, 1, 6, "binned")
    assert ss != bs
    assert ss.leaf_cap <= 8 and bs.leaf_cap == 32
    assert ss.win_slots == 4 and bs.win_slots >= 32
    # same-shape tenants collapse onto ONE key (the trace-budget rule)
    small2, _ = _make_booster(3, leaves=7, trees=3)
    assert forest.tenant_shape(small2._engine.models, 1, 6,
                               "binned") == ss


def test_pad_window_refuses_overflow():
    win = forest.pack_window_raw(
        _make_booster(4, leaves=7, trees=3)[0]._engine.models,
        forest.tenant_shape(
            _make_booster(4, leaves=7, trees=3)[0]._engine.models, 1, 6,
            "raw"))
    with pytest.raises(ValueError, match="exceeds its capacity"):
        forest.pad_window(win, 2)


# ---------------------------------------------------------------------------
# per-tenant counters (no jax)
# ---------------------------------------------------------------------------

def test_counters_tenant_dimension():
    c = ServingCounters()
    c.inc("shed", tenant="a")
    c.inc("shed")                       # global only
    c.inc_tenant("a", "requests")
    c.inc_tenant("b", "rows", 32)
    assert c.get("shed") == 2
    t = c.tenant_snapshot()
    assert t["a"]["shed"] == 1 and t["a"]["requests"] == 1
    assert t["b"]["rows"] == 32 and t["b"]["shed"] == 0
    assert c.get_tenant("a", "expired") == 0
    with pytest.raises(KeyError):
        c.inc_tenant("a", "not_a_counter")
    with pytest.raises(KeyError):
        c.inc("not_a_counter")


# ---------------------------------------------------------------------------
# cross-tenant coalescing: bit-parity + trace budget
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fleet_mixed_shapes_bit_parity():
    """Tenants with mixed (leaves, trees, F) shapes — multiple buckets —
    all bit-identical to their own predict_device through one fleet."""
    tenants = {
        "small": _make_booster(20, n_features=5, leaves=7, trees=3),
        "mid": _make_booster(21, n_features=9, leaves=15, trees=8),
        "deep": _make_booster(22, n_features=5, leaves=63, trees=12),
        # identical training -> identical shape key: must SHARE a bucket
        "twin": _make_booster(20, n_features=5, leaves=7, trees=3),
    }
    with serve_fleet({k: b for k, (b, _x) in tenants.items()},
                     raw_score=True, linger_ms=30.0) as fleet:
        assert fleet.stats()["n_tenants"] == 4
        # small+twin share a bucket; mid and deep get their own
        assert fleet.stats()["n_buckets"] == 3
        futs = {k: fleet.submit(k, x[:40]) for k, (_b, x) in
                tenants.items()}
        for k, fut in futs.items():
            b, x = tenants[k]
            assert np.array_equal(
                fut.result(120),
                b.predict(x[:40], device=True, raw_score=True)), k
        # the whole burst coalesced into fewer dispatch pops
        assert fleet.stats()["batches"] < len(tenants)


def test_fleet_objective_conversion_and_multiclass():
    """Non-raw responses ride each tenant's OWN objective conversion —
    a binary and a 3-class tenant in one fleet both match their
    boosters' converted outputs."""
    bin_b, bin_x = _make_booster(30, objective="binary")
    mc_b, mc_x = _make_booster(31, objective="multiclass")
    with serve_fleet({"bin": bin_b, "mc": mc_b}, linger_ms=20.0) as fleet:
        got_bin = fleet.predict("bin", bin_x[:32], timeout=120)
        got_mc = fleet.predict("mc", mc_x[:32], timeout=120)
    ref_bin = bin_b.predict(bin_x[:32], device=True)
    ref_mc = mc_b.predict(mc_x[:32], device=True)
    assert np.array_equal(got_bin, ref_bin)
    assert got_mc.shape == (32, 3)
    assert np.array_equal(got_mc, ref_mc)


def test_fleet_categorical_tenant_shares_bucket_with_numeric():
    """A tenant with categorical splits coalesces with an all-numeric
    same-shape tenant: the bucket-level cat-width normalization
    (_widen_window_np) grows empty cat fields on the numeric window and
    both stay bit-identical — incl. NaN routing through the cat
    tenant's own mappers."""
    rng = np.random.default_rng(90)
    Xc = rng.normal(size=(700, 6)).astype(np.float32).astype(np.float64)
    Xc[:, 5] = rng.integers(0, 8, size=700)
    Xc[rng.uniform(size=Xc.shape) < 0.05] = np.nan
    Xc[:, 5] = np.abs(np.nan_to_num(Xc[:, 5]))
    yc = np.nan_to_num(Xc[:, 0]) + (Xc[:, 5] % 3)
    cat_b = lgb.train({"objective": "regression", "num_leaves": 15,
                       "verbose": -1, "min_data_in_leaf": 5},
                      lgb.Dataset(Xc, label=yc, categorical_feature=[5]),
                      num_boost_round=5, keep_training_booster=True)
    num_b, Xn = _make_booster(91, n_features=6, leaves=15, trees=5)
    with serve_fleet({"cat": cat_b, "num": num_b}, raw_score=True,
                     linger_ms=30.0) as fleet:
        # one shared bucket: the numeric window really was cat-widened
        assert fleet.stats()["n_buckets"] == 1
        fc = fleet.submit("cat", Xc[:48])
        fn = fleet.submit("num", Xn[:48])
        assert np.array_equal(
            fc.result(120),
            cat_b.predict(Xc[:48], device=True, raw_score=True))
        assert np.array_equal(
            fn.result(120),
            num_b.predict(Xn[:48], device=True, raw_score=True))


def test_fleet_raw_route_loaded_models():
    """Mapperless (loaded) tenants serve over the fleet raw route,
    bit-identical to their loaded engines' device predict."""
    b1, x1 = _make_booster(40, leaves=15, trees=4)
    b2, x2 = _make_booster(41, leaves=15, trees=4)
    l1 = lgb.Booster(model_str=b1.model_to_string())
    l2 = lgb.Booster(model_str=b2.model_to_string())
    with serve_fleet({"a": l1, "b": l2}, raw_score=True,
                     linger_ms=20.0) as fleet:
        fa = fleet.submit("a", np.asarray(x1[:40], np.float32)
                          .astype(np.float64))
        fb = fleet.submit("b", np.asarray(x2[:40], np.float32)
                          .astype(np.float64))
        assert np.array_equal(
            fa.result(120),
            l1.predict(x1[:40], device=True, raw_score=True))
        assert np.array_equal(
            fb.result(120),
            l2.predict(x2[:40], device=True, raw_score=True))
        # f64-only values are refused at submit (the raw contract)
        bad = np.asarray(x1[:4], np.float64).copy()
        bad[0, 0] = 1.0 + 1e-12
        with pytest.raises(ValueError, match="float32-representable"):
            fleet.submit("a", bad)


def test_fleet_trace_budget_flat(trio):
    """After warming each (shape bucket, row bucket), mixed cross-tenant
    traffic — including a hot-swap — compiles NOTHING new: the
    steady-state trace count is flat in fleet size."""
    with serve_fleet({k: b for k, (b, _x) in trio.items()},
                     raw_score=True, linger_ms=10.0) as fleet:
        assert fleet.stats()["n_buckets"] == 1
        x = trio["t0"][1]
        for warm in (200, 500):          # the 256 and 512 row buckets
            for k in trio:
                fleet.predict(k, trio[k][1][:warm], timeout=120)
        with guards.CompileCounter() as counter:
            for rep in range(4):
                futs = [fleet.submit(k, trio[k][1][:10 + 31 * j])
                        for j, k in enumerate(trio)]
                for f in futs:
                    f.result(120)
            fleet.predict("t1", x[:300], timeout=120)
        assert counter.count == 0, counter.names
        # a publish within capacity keeps every program shape: the NEXT
        # dispatch after a hot-swap reuses the warmed programs too
        b0 = trio["t0"][0]
        b0.update()
        fleet.publish("t0")
        with guards.CompileCounter() as counter:
            got = fleet.predict("t0", x[:64], timeout=120)
        assert counter.count == 0, counter.names
        assert np.array_equal(
            got, b0.predict(x[:64], device=True, raw_score=True))


# ---------------------------------------------------------------------------
# isolation: one tenant's failure never touches coalesced peers
# ---------------------------------------------------------------------------

def test_fleet_malformed_request_fails_its_submitter_only(trio):
    with serve_fleet({k: b for k, (b, _x) in trio.items()},
                     raw_score=True, linger_ms=20.0) as fleet:
        with pytest.raises(ValueError, match="rows, 6"):
            fleet.submit("t0", trio["t0"][1][:8, :4])    # wrong width
        with pytest.raises(KeyError):
            fleet.submit("nope", trio["t0"][1][:8])
        # peers submitted around the malformed one are served bit-exact
        f1 = fleet.submit("t1", trio["t1"][1][:24])
        assert np.array_equal(
            f1.result(120),
            trio["t1"][0].predict(trio["t1"][1][:24], device=True,
                                  raw_score=True))


def test_fleet_expired_tenant_never_poisons_peers(trio):
    """Tenant A's expired-deadline request is dropped at pop time;
    tenant B's rows it would have coalesced with stay bit-identical."""
    with serve_fleet({k: b for k, (b, _x) in trio.items()},
                     raw_score=True, linger_ms=1.0) as fleet:
        with faults.inject("slow_dispatch:sec=0.4:n=1"):
            slow = fleet.submit("t2", trio["t2"][1][:48])  # wedge
            end = time.monotonic() + 5
            while fleet.stats()["queued_rows"] and time.monotonic() < end:
                time.sleep(0.005)
            time.sleep(0.05)             # outlive the linger window
            dead = fleet.submit("t0", trio["t0"][1][:32], deadline_ms=40.0)
            good = fleet.submit("t1", trio["t1"][1][64:128])
            got_slow = slow.result(60)
            got_good = good.result(60)
        with pytest.raises(DeadlineExceeded):
            dead.result(60)
        assert np.array_equal(
            got_slow, trio["t2"][0].predict(trio["t2"][1][:48],
                                            device=True, raw_score=True))
        assert np.array_equal(
            got_good, trio["t1"][0].predict(trio["t1"][1][64:128],
                                            device=True, raw_score=True))
        t = fleet.counters.tenant_snapshot()
        assert t["t0"]["expired"] == 1
        assert t["t1"]["expired"] == 0 and t["t2"]["expired"] == 0


def test_fleet_publish_fail_isolated_per_tenant(trio):
    """An injected publish_fail rolls ONE tenant back; its old
    generation keeps serving and the other tenants' routes, versions
    and responses are untouched."""
    with serve_fleet({k: b for k, (b, _x) in trio.items()},
                     raw_score=True, linger_ms=5.0) as fleet:
        x0, x1 = trio["t0"][1], trio["t1"][1]
        before0 = fleet.predict("t0", x0[:40], timeout=120)
        before1 = fleet.predict("t1", x1[:40], timeout=120)
        v1 = fleet._state.routes["t1"].generation.version
        trio["t0"][0].update()
        with faults.inject("publish_fail:n=1"):
            with pytest.raises(faults.FaultInjected):
                fleet.publish("t0")
        # rollback: t0 still serves its OLD generation bit-exactly
        assert np.array_equal(fleet.predict("t0", x0[:40], timeout=120),
                              before0)
        assert fleet.counters.tenant_snapshot()["t0"][
            "publish_failures"] == 1
        # t1: untouched version, untouched responses, no failure counts
        assert fleet._state.routes["t1"].generation.version == v1
        assert np.array_equal(fleet.predict("t1", x1[:40], timeout=120),
                              before1)
        assert fleet.counters.tenant_snapshot()["t1"][
            "publish_failures"] == 0
        # the retried publish succeeds gaplessly and serves new trees
        info = fleet.publish("t0")
        assert info.version == 2
        assert np.array_equal(
            fleet.predict("t0", x0[:40], timeout=120),
            trio["t0"][0].predict(x0[:40], device=True, raw_score=True))


def test_fleet_hot_swap_under_cross_tenant_load():
    """Continuous publishes of one tenant under another tenant's
    traffic: zero failed or torn responses on BOTH, generations move
    forward only."""
    b0, x0 = _make_booster(50, trees=3)
    b1, x1 = _make_booster(51, trees=3)
    with serve_fleet({"pub": b0, "steady": b1}, raw_score=True,
                     linger_ms=2.0) as fleet:
        expected_pub = {1: b0.predict(x0[:32], device=True,
                                      raw_score=True)}
        steady_ref = b1.predict(x1[:32], device=True, raw_score=True)
        stop = threading.Event()
        seen, errors = [], []

        def client(name, x, sink):
            while not stop.is_set():
                try:
                    fut = fleet.submit(name, x[:32])
                    sink.append((fut.result(120), fut.generation))
                except Exception as e:  # noqa: BLE001
                    errors.append(repr(e))
                    return

        pub_seen, steady_seen = [], []
        threads = [threading.Thread(target=client,
                                    args=("pub", x0, pub_seen),
                                    daemon=True),
                   threading.Thread(target=client,
                                    args=("steady", x1, steady_seen),
                                    daemon=True)]
        for t in threads:
            t.start()
        for _ in range(3):
            time.sleep(0.05)
            b0.update()
            info = fleet.publish("pub")
            expected_pub[info.version] = b0.predict(
                x0[:32], device=True, raw_score=True)
        time.sleep(0.1)
        stop.set()
        for t in threads:
            t.join(60)
        assert not errors and pub_seen and steady_seen, errors[:1]
        versions = [g.version for _o, g in pub_seen]
        assert versions == sorted(versions)
        for out, gen in pub_seen:
            assert np.array_equal(out, expected_pub[gen.version])
        for out, gen in steady_seen:
            assert gen.version == 1      # never republished
            assert np.array_equal(out, steady_ref)


def test_fleet_degrade_host_walk_parity_and_recovery(trio):
    """Forced degradation serves every tenant via ITS host walk
    (bit-identical to Booster.predict raw), counts per-tenant degraded
    batches, and the background probe un-degrades."""
    with serve_fleet({k: b for k, (b, _x) in trio.items()},
                     raw_score=True, linger_ms=10.0,
                     probe_interval_s=0.05) as fleet:
        fleet.degrade("test drill")
        futs = {k: fleet.submit(k, trio[k][1][:24]) for k in trio}
        for k, fut in futs.items():
            assert np.array_equal(
                fut.result(120),
                trio[k][0].predict(trio[k][1][:24], raw_score=True)), k
        t = fleet.counters.tenant_snapshot()
        assert all(t[k]["degraded_batches"] >= 1 for k in trio)
        end = time.monotonic() + 10
        while fleet.stats()["degraded"] and time.monotonic() < end:
            time.sleep(0.01)
        assert not fleet.stats()["degraded"]
        assert fleet.counters.get("recoveries") == 1


# ---------------------------------------------------------------------------
# per-tenant admission quota + exact 3-tenant accounting (PR9 extended)
# ---------------------------------------------------------------------------

def test_fleet_tenant_quota_sheds_one_tenant_only(trio):
    """Tenant t0's row quota sheds ITS backlog while t1/t2 submits are
    admitted unaffected — and the ledger blames only t0."""
    with serve_fleet({k: b for k, (b, _x) in trio.items()},
                     raw_score=True, linger_ms=1.0) as fleet:
        fleet._tenants["t0"].quota_rows = 64
        with faults.inject("slow_dispatch:sec=0.4:n=1"):
            wedge = fleet.submit("t1", trio["t1"][1][:16])
            end = time.monotonic() + 5
            while fleet.stats()["queued_rows"] and time.monotonic() < end:
                time.sleep(0.005)
            q0 = fleet.submit("t0", trio["t0"][1][:64])   # fills quota
            with pytest.raises(Overloaded, match="tenant 't0'"):
                fleet.submit("t0", trio["t0"][1][:8])
            q1 = fleet.submit("t1", trio["t1"][1][:64])   # unaffected
            q2 = fleet.submit("t2", trio["t2"][1][:64])
            for f in (wedge, q0, q1, q2):
                assert f.result(60) is not None
        t = fleet.counters.tenant_snapshot()
        assert t["t0"]["shed"] == 1
        assert t["t1"]["shed"] == 0 and t["t2"]["shed"] == 0


def test_fleet_exact_three_tenant_accounting(trio):
    """The PR9 exact client-vs-server contract, per tenant: every
    request lands in exactly one per-tenant ledger entry and the
    ledgers reconcile EXACTLY with what each client observed."""
    with serve_fleet({k: b for k, (b, _x) in trio.items()},
                     raw_score=True, linger_ms=2.0) as fleet:
        fleet._tenants["t2"].quota_rows = 48
        observed = {k: {"requests": 0, "rows": 0, "shed": 0,
                        "expired": 0} for k in trio}
        with faults.inject("slow_dispatch:sec=0.3:n=1"):
            wedge = fleet.submit("t0", trio["t0"][1][:16])
            observed["t0"]["requests"] += 1
            observed["t0"]["rows"] += 16
            end = time.monotonic() + 5
            while fleet.stats()["queued_rows"] and time.monotonic() < end:
                time.sleep(0.005)
            time.sleep(0.05)
            pend = []
            # t0: two good requests; t1: one good + one that expires;
            # t2: one good + one shed on its quota
            for k, n, dl in (("t0", 16, None), ("t0", 8, None),
                             ("t1", 24, None), ("t1", 8, 30.0),
                             ("t2", 40, None)):
                pend.append((k, n, dl,
                             fleet.submit(k, trio[k][1][:n],
                                          deadline_ms=dl)))
            try:
                fleet.submit("t2", trio["t2"][1][:16])
                observed["t2"]["requests"] += 1
                observed["t2"]["rows"] += 16
            except Overloaded:
                observed["t2"]["shed"] += 1
            wedge.result(60)
            for k, n, dl, fut in pend:
                try:
                    fut.result(60)
                    observed[k]["requests"] += 1
                    observed[k]["rows"] += n
                except DeadlineExceeded:
                    observed[k]["expired"] += 1
        ledger = fleet.counters.tenant_snapshot()
        for k in trio:
            for name in ("requests", "rows", "shed", "expired"):
                assert ledger[k][name] == observed[k][name], \
                    (k, name, ledger[k], observed[k])
        # the expired request really expired (the test is not vacuous)
        assert sum(o["expired"] for o in observed.values()) == 1
        assert sum(o["shed"] for o in observed.values()) == 1


# ---------------------------------------------------------------------------
# placement modes
# ---------------------------------------------------------------------------

def test_fleet_auto_shard_by_pack_budget(trio):
    """auto placement replicates under the budget and model-shards past
    it (when >1 device); parity holds either way."""
    import jax
    boosters = {k: b for k, (b, _x) in trio.items()}
    with serve_fleet(boosters, raw_score=True,
                     pack_budget_mb=1024.0) as fleet:
        assert fleet.stats()["fleet_shard"] == "replicate"
    with serve_fleet(boosters, raw_score=True,
                     pack_budget_mb=1e-6) as fleet:
        expect = "model" if len(jax.devices()) > 1 else "replicate"
        assert fleet.stats()["fleet_shard"] == expect
        for k in boosters:
            assert np.array_equal(
                fleet.predict(k, trio[k][1][:24], timeout=120),
                boosters[k].predict(trio[k][1][:24], device=True,
                                    raw_score=True))
    with pytest.raises(ValueError, match="auto|replicate|model"):
        FleetServer(fleet_shard="sideways")


def test_fleet_shard_flip_distributes_buckets():
    """A replicate->model placement flip must spread the buckets over
    the mesh via one balanced assignment — never pile the whole fleet
    onto device 0 (the incremental owner picker reads the PRE-flip
    state where nothing has an owner)."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs >1 device")
    tenants = {"a": _make_booster(95, leaves=7, trees=3)[0],
               "b": _make_booster(96, leaves=31, trees=8)[0],
               "c": _make_booster(97, leaves=63, trees=12)[0]}
    with serve_fleet(tenants, raw_score=True,
                     pack_budget_mb=1024.0) as fleet:
        assert fleet.stats()["fleet_shard"] == "replicate"
        assert fleet.stats()["n_buckets"] >= 2
        fleet._pack_budget = 0.0          # next publish crosses budget
        fleet.publish("a")
        st = fleet._state
        assert st.shard == "model"
        owners = {b.device for b in st.buckets.values()}
        assert None not in owners
        assert len(owners) >= 2, \
            f"flip piled every bucket onto one device: {owners}"


def test_serve_fleet_autoname_survives_removal(trio):
    """The default tenant name must probe for a free slot: len()-based
    naming collides after any removal."""
    with serve_fleet({"t0": trio["t0"][0]}, raw_score=True) as fleet:
        h1 = _make_booster(98)[0].serve(fleet=fleet)      # tenant1
        h2 = _make_booster(99)[0].serve(fleet=fleet)      # tenant2
        h1.close()                                        # free a slot
        h3 = _make_booster(100)[0].serve(fleet=fleet)     # must not raise
        assert h3.name in fleet.tenants and h3.name != h2.name


def test_served_booster_still_pickles():
    """serve() stores the live server on the booster; pickling/deepcopy
    must still work (the server is process state, not model state)."""
    import copy
    import pickle
    b, x = _make_booster(101)
    srv = b.serve(linger_ms=1.0, raw_score=True)
    try:
        blob = pickle.dumps(b)
        clone = pickle.loads(blob)
        assert np.allclose(clone.predict(x[:8]), b.predict(x[:8]))
        assert getattr(clone, "_live_server", None) is None
        copy.deepcopy(b)
    finally:
        srv.close()


def test_fleet_publish_grows_window_bucket_move(trio):
    """A tenant that outgrows its window capacity moves to a bigger
    bucket on publish; parity holds and its neighbors stay put."""
    b, x = _make_booster(60, trees=4)    # win_slots 4
    with serve_fleet({"grow": b, "stay": trio["t0"][0]},
                     raw_score=True, linger_ms=5.0) as fleet:
        key0 = fleet._state.routes["grow"].key
        for _ in range(5):               # 9 trees > 4 slots
            b.update()
        fleet.publish("grow")
        key1 = fleet._state.routes["grow"].key
        assert key1.win_slots > key0.win_slots
        assert np.array_equal(
            fleet.predict("grow", x[:32], timeout=120),
            b.predict(x[:32], device=True, raw_score=True))
        assert np.array_equal(
            fleet.predict("stay", trio["t0"][1][:32], timeout=120),
            trio["t0"][0].predict(trio["t0"][1][:32], device=True,
                                  raw_score=True))


def test_fleet_level_knobs_reach_tenants(trio):
    """A fleet-level deadline reaches tenants whose boosters never set
    one (Config exposes every param with a default — the fallback must
    key on EXPLICITLY-set params); an explicit booster param still
    wins."""
    with serve_fleet({"t0": trio["t0"][0]}, raw_score=True,
                     deadline_ms=500.0) as fleet:
        assert fleet._tenants["t0"].deadline_ms == 500.0
    explicit, _x = _make_booster(110)
    explicit.params["tpu_serving_deadline_ms"] = 250.0
    from lightgbm_tpu.config import Config
    explicit.config = Config(explicit.params)
    with serve_fleet({"t0": trio["t0"][0]}, raw_score=True,
                     deadline_ms=500.0) as fleet:
        h = explicit.serve(fleet=fleet, tenant="exp")
        assert fleet._tenants["exp"].deadline_ms == 250.0
        assert h.stats()["deadline_ms"] == 250.0


def test_fleet_remove_tenant(trio):
    boosters = {k: b for k, (b, _x) in trio.items()}
    fleet = serve_fleet(boosters, raw_score=True, linger_ms=5.0)
    try:
        h = TenantHandle(fleet, "t1")
        h.close()
        assert "t1" not in fleet.tenants
        with pytest.raises(KeyError):
            fleet.submit("t1", trio["t1"][1][:8])
        assert np.array_equal(
            fleet.predict("t0", trio["t0"][1][:24], timeout=120),
            trio["t0"][0].predict(trio["t0"][1][:24], device=True,
                                  raw_score=True))
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# Booster.serve integration + the one-live-server regression
# ---------------------------------------------------------------------------

def test_serve_fleet_kwarg_returns_tenant_handle(trio):
    b_new, x_new = _make_booster(70)
    with serve_fleet({"t0": trio["t0"][0]}, raw_score=True) as fleet:
        h = b_new.serve(fleet=fleet, tenant="newbie", raw_score=True)
        assert isinstance(h, TenantHandle)
        assert "newbie" in fleet.tenants
        assert np.array_equal(
            h.predict(x_new[:16], timeout=120),
            b_new.predict(x_new[:16], device=True, raw_score=True))
        assert h.stats()["generation"] == 1
        with pytest.raises(ValueError, match="already served"):
            b_new.serve(fleet=fleet, tenant="newbie")
        # auto-named tenant
        h2 = _make_booster(71)[0].serve(fleet=fleet)
        assert h2.name in fleet.tenants


def test_second_serve_returns_live_server_no_second_dispatcher():
    """ISSUE 13 satellite: serve() on a booster with a live server must
    return THE live server (or refuse loudly with kwargs) — never spawn
    a second dispatcher thread over the same pack."""
    b, x = _make_booster(80)

    def dispatchers():
        return [t for t in threading.enumerate()
                if t.name == "lgbm-serving-batcher" and t.is_alive()]

    base = len(dispatchers())
    srv = b.serve(linger_ms=1.0, raw_score=True)
    try:
        # one server owns two batchers: scores and explanations (PR 20)
        assert len(dispatchers()) == base + 2
        again = b.serve()
        assert again is srv
        assert len(dispatchers()) == base + 2, \
            "second serve() spawned a second dispatcher"
        with pytest.raises(lgb.LightGBMError, match="live ModelServer"):
            b.serve(linger_ms=9.0)
        assert len(dispatchers()) == base + 2
    finally:
        srv.close()
    # a CLOSED server is replaced, not resurrected
    srv2 = b.serve(linger_ms=1.0, raw_score=True)
    try:
        assert srv2 is not srv
        assert np.array_equal(
            srv2.predict(x[:16], timeout=120),
            b.predict(x[:16], device=True, raw_score=True))
    finally:
        srv2.close()


# ---------------------------------------------------------------------------
# HBM budget + cold-tenant eviction (ISSUE 17)
# ---------------------------------------------------------------------------

def test_fleet_evicted_then_rebuilt_bucket_bit_identical():
    """Under a budget too small for every pack, cold buckets are
    LRU-evicted (device pack dropped, host pack kept) and lazily
    rebuilt on next touch — every tenant's response stays bit-identical
    to its own direct device predict, generations preserved."""
    tenants = {f"t{i}": _make_booster(60 + i, leaves=7 + 8 * i,
                                      trees=3 + i) for i in range(3)}
    with serve_fleet({k: b for k, (b, _x) in tenants.items()},
                     raw_score=True, linger_ms=10.0,
                     mem_budget_mb=1e-4) as fleet:
        st = fleet.stats()
        assert st["n_buckets"] == 3
        assert st["evicted_buckets"] >= 1, st
        assert st["resident_pack_bytes"] <= st["pack_bytes"]
        gens = {}
        for name, (b, x) in tenants.items():
            got = fleet.predict(name, x[:64], timeout=120)
            assert np.array_equal(
                got, b.predict(x[:64], device=True, raw_score=True)), name
            gens[name] = fleet.tenant_stats(name)["generation"]
        # touching every bucket under the budget churned: something was
        # evicted AND rebuilt, and nothing re-published (gen still 1)
        st = fleet.stats()
        assert st["evictions"] >= 1 and st["rebuilds"] >= 1, st
        assert all(g == 1 for g in gens.values()), gens
        # second pass: rebuilds keep serving exact bits
        for name, (b, x) in tenants.items():
            assert np.array_equal(
                fleet.predict(name, x[:64], timeout=120),
                b.predict(x[:64], device=True, raw_score=True)), name


def test_fleet_hot_swap_of_evicted_tenant_lands(trio):
    """publish() of a tenant whose bucket is currently evicted builds
    and serves the NEW generation correctly (the publish path uploads a
    fresh pack; the stale evicted one is simply dropped)."""
    tenants = {f"e{i}": _make_booster(70 + i, leaves=7 + 8 * i,
                                      trees=3 + i) for i in range(3)}
    with serve_fleet({k: b for k, (b, _x) in tenants.items()},
                     raw_score=True, linger_ms=10.0,
                     mem_budget_mb=1e-4) as fleet:
        assert fleet.stats()["evicted_buckets"] >= 1
        # find an evicted tenant
        state = fleet._state
        name = next(n for n, r in state.routes.items()
                    if state.buckets[r.key].dev is None)
        b, x = tenants[name]
        b.update()
        info = fleet.publish(name)
        assert info.version == 2
        got = fleet.predict(name, x[:48], timeout=120)
        assert np.array_equal(
            got, b.predict(x[:48], device=True, raw_score=True))
        assert fleet.tenant_stats(name)["generation"] == 2


def test_fleet_eviction_never_strands_inflight_batch(trio):
    """A dispatch wedged on the device keeps the OLD state's pack
    reference; a concurrent publish that evicts that bucket in the NEW
    state cannot strand it — the wedged batch still answers exactly."""
    tenants = {f"s{i}": _make_booster(85 + i, leaves=7 + 8 * i,
                                      trees=3 + i) for i in range(2)}
    (b0, x0), (b1, x1) = tenants["s0"], tenants["s1"]
    with serve_fleet({k: b for k, (b, _x) in tenants.items()},
                     raw_score=True, linger_ms=1.0,
                     mem_budget_mb=1e-4) as fleet:
        with faults.inject("slow_dispatch:sec=0.5:n=1"):
            slow = fleet.submit("s0", x0[:48])     # wedges in dispatch
            time.sleep(0.1)
            # publish s1 while s0's batch is in flight: the budget pass
            # may evict s0's bucket in the NEW state
            b1.update()
            fleet.publish("s1")
            got = slow.result(120)
        assert np.array_equal(
            got, b0.predict(x0[:48], device=True, raw_score=True))
        # and the possibly-evicted bucket still rebuilds exactly
        assert np.array_equal(
            fleet.predict("s0", x0[:48], timeout=120),
            b0.predict(x0[:48], device=True, raw_score=True))


def test_fleet_oom_floor_host_walks_one_request_peers_on_device(trio):
    """oom:n=2 fails the 2-request group and its left 1-request half:
    that request is host-walked ALONE; its coalesced peer retries clean
    and stays on the device. No degrade, per-request blast radius."""
    (b0, x0) = trio["t0"]
    (b1, x1) = trio["t1"]
    with serve_fleet({"t0": b0, "t1": b1}, raw_score=True,
                     linger_ms=60.0) as fleet:
        fleet.predict("t0", x0[:32], timeout=120)          # warm
        with faults.inject("oom:p=1:n=2"):
            f0 = fleet.submit("t0", x0[:32])
            f1 = fleet.submit("t1", x1[:32])
            r0 = f0.result(120)
            r1 = f1.result(120)
        st = fleet.stats()
        assert st["oom_bisects"] == 1
        assert not st["degraded"]
    np.testing.assert_allclose(
        r0, b0.predict(x0[:32], device=False, raw_score=True),
        rtol=1e-12, atol=1e-12)
    assert np.array_equal(
        r1, b1.predict(x1[:32], device=True, raw_score=True))


def test_fleet_publish_forced_eviction_instead_of_failing(trio):
    """A pack upload that OOMs during publish evicts the coldest
    resident pack and retries — the new generation lands instead of
    the publish failing."""
    tenants = {f"p{i}": _make_booster(95 + i, leaves=7 + 8 * i,
                                      trees=3 + i) for i in range(2)}
    (b0, x0), (b1, x1) = tenants["p0"], tenants["p1"]
    with serve_fleet({k: b for k, (b, _x) in tenants.items()},
                     raw_score=True, linger_ms=10.0) as fleet:
        b0.update()
        with faults.inject("oom:n=1"):     # fails the publish upload
            info = fleet.publish("p0")
        assert info.version == 2
        st = fleet.stats()
        assert st["evictions"] >= 1, st
        assert fleet.counters.get("publish_failures") == 0
        assert np.array_equal(
            fleet.predict("p0", x0[:48], timeout=120),
            b0.predict(x0[:48], device=True, raw_score=True))
        # the force-evicted peer rebuilds on next touch, still exact
        assert np.array_equal(
            fleet.predict("p1", x1[:48], timeout=120),
            b1.predict(x1[:48], device=True, raw_score=True))
