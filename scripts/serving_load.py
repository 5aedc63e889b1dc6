"""Concurrent-serving load generator (ISSUE 8/9): sustained QPS + tail
latency for the serving tier, device and native-C-ABI routes side by
side — plus the chaos gate over the failure path.

Drives N concurrent clients against ``Booster.serve()`` (the dynamic
micro-batcher + mesh-replicated packed forest) and, when the native
library is available, against the C ABI's OMP row-parallel predictor
(the analogue of the reference's src/application/predictor.hpp:31 route)
— and reports, per route:

- sustained QPS and rows/sec over the measurement window
- p50 / p99 / p999 request latency (client-observed; open-loop mode
  measures from the INTENDED Poisson arrival time, so queueing delay
  from a saturated server is charged to the request — no coordinated
  omission)
- the single-stream baseline (one client, direct device predict at the
  same request size) and the concurrent speedup over it

Traffic modes: ``closed`` (each client submits, waits, repeats —
throughput-coupled) and ``open`` (Poisson arrivals at --rate req/s
total, the honest latency-under-load model).

Chaos gate (``--chaos``, ISSUE 9): open-loop Poisson traffic from
``--clients`` threads while 5% of device dispatches fail transiently
(``dispatch_error:p=0.05``), exactly one hot-swap publish dies
(``publish_fail:n=1``), and a mid-run degradation to the host-walk
route is forced at half-duration. The gate FAILS (status no_result)
unless: zero torn or wrong responses (every response bit-matches its
generation's device or host route), per-client generations move forward
only, every shed/expired/degraded/publish event is accounted in the
ServingCounters exactly as clients observed it, the forced degradation
recovers via the background probe, and p999 stays under
``--chaos-p999-ms``.

Results land in bench_logs/SERVING_LOAD.json under scripts/_bench_io.py's
status grammar (measured / degraded / device_unreachable / no_result — a
"degraded" record means the tier ended on the host fallback) so the
session driver can key on them.

Fleet mode (``--fleet N``, ISSUE 13): N tenants with mixed (leaves,
trees, F) shapes served by ONE FleetServer — open-loop Poisson traffic
picks a tenant per arrival with mixed request sizes, banking QPS +
p50/p99/p999, the measured steady-state trace count (the flat-in-fleet-
size budget, via guards.CompileCounter over the warmed measurement
window) and a CHAOS LEG (one tenant's publish_fail + a forced mid-run
degrade; verified: 0 torn responses per tenant against that tenant's
device or host bits, exact per-tenant counter accounting) to
``bench_logs/SERVING_FLEET.json`` in the shared _bench_io grammar.

Live mode (``--live``, ISSUE 14 — the freshness chaos gate): boots the
FULL continual-learning service (resident trainer in a SUPERVISED child
process, publish pump, HTTP front door) on a synthetic stream that keeps
producing rows, then drives open-loop Poisson HTTP traffic while the
trainer publishes continuously AND one injected trainer crash
(``rank_kill`` on launch 1 only; the gang supervisor relaunches and the
trainer resumes from its newest committed checkpoint). The gate FAILS
(status no_result) unless: 0 torn responses (every response bit-matches
its generation's checkpointed model — device or host bits), per-client
generations move forward only with the published set gapless, >= 2
generations land AFTER the crash (the relaunch proved itself), and the
wire carried staleness on every response. Banks QPS + latency
percentiles + measured model-staleness p50/p99 to
``bench_logs/SERVING_LIVE.json`` in the shared _bench_io grammar.

Memory-chaos mode (``--mem-chaos``, ISSUE 17): a tenant fleet under an
HBM budget sized BELOW its total pack bytes (forced eviction churn),
open-loop Poisson traffic while 5% of allocations OOM
(``oom:p=0.05`` — consulted at the dispatch, pack-upload and rebuild
sites), then exactly one pack-upload OOM during a publish (the
forced-eviction path). The gate FAILS (status no_result) unless: zero
torn responses (every response bit-matches its tenant's own
predict_device bits or its host-walk bits — the bisection floor may
host-walk a single request), exact per-tenant requests/shed/expired
accounting, oom_bisects/evictions/rebuilds all registered (>= 1, and
surfaced through the same stats() the front door serves as /v1/stats),
the fleet is NEVER whole-server degraded by a size-induced OOM, and
the steady-state trace count stays flat (bisection halves land in
warm row buckets). Banks ``bench_logs/SERVING_MEM.json``.

Integrity-chaos mode (``--integrity-chaos``, ISSUE 19): a canary-armed
tenant fleet under open-loop Poisson traffic while the victim tenant's
evicted pack is lazily rebuilt through an injected device-upload
bitflip (``bitflip:p=1:where=dev``), plus a resident-trainer run whose
gradients are poisoned once (``nan_grad:p=1:after=1``). The gate FAILS
(status no_result) unless: the corrupt upload is DETECTED within one
probe interval and never installed, ONLY the afflicted tenant is
quarantined to the host walk, zero torn/wrong responses (every response
bit-matches its tenant's banked device or host-walk bits), the
background probe repairs the pack and un-quarantines automatically
(device route bit-identical to pre-rot), the
``integrity_probes/integrity_mismatches/quarantines/repairs``
accounting is EXACT through the same stats() the front door serves as
/v1/stats, and the poisoned trainer's numeric-health rollback yields a
final model BIT-IDENTICAL to the fault-free run. Banks
``bench_logs/SERVING_INTEGRITY.json``.

Explain mode (``--explain``, ISSUE 20): the explanation-serving gate —
device-vs-host SHAP contribution throughput through the packed path
tensors (the >=3x target enforced on a real accelerator; recorded only
under virtual CPU devices, where the "device" kernel and the native C++
host oracle share the same silicon), a mixed predict+explain open-loop
leg through ONE solo server (0 torn responses against banked device /
host-oracle bits, 0 new steady-state traces over the warmed window, and
EXACT batcher-ledger separation — the proof score and contrib requests
never share a coalesced batch), and a two-tenant fleet leg with one
tenant quarantined mid-run (host-oracle bits, exact per-tenant
``explain_requests`` / ``explain_degraded`` accounting). Banks
``bench_logs/SERVING_SHAP.json``.

Usage:
  python scripts/serving_load.py [--clients 8] [--rows 64]
      [--duration 10] [--mode closed|open] [--rate 200]
      [--devices 2] [--trees 60] [--leaves 31] [--linger-ms 2]
      [--publish-every 0] [--skip-native] [--deadline-ms 0]
      [--max-queue-rows 0] [--chaos] [--chaos-p999-ms 10000]
      [--fleet N] [--fleet-rows 3000] [--live] [--live-crash-iter 6]
      [--mem-chaos] [--integrity-chaos] [--explain]
      [--explain-rate 16] [--explain-frac 0.3]

--devices D > 1 under JAX_PLATFORMS=cpu re-execs with D virtual XLA
devices; with the variable unset or naming an accelerator the run uses
the devices jax finds (the CPU is asked for, never assumed).
"""
from __future__ import annotations

import argparse
import ctypes
import os
import random
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
OUT = os.path.join(REPO, "bench_logs", "SERVING_LOAD.json")
OUT_CHAOS = os.path.join(REPO, "bench_logs", "SERVING_CHAOS.json")
OUT_FLEET = os.path.join(REPO, "bench_logs", "SERVING_FLEET.json")
OUT_LIVE = os.path.join(REPO, "bench_logs", "SERVING_LIVE.json")
OUT_MEM = os.path.join(REPO, "bench_logs", "SERVING_MEM.json")
OUT_INTEGRITY = os.path.join(REPO, "bench_logs", "SERVING_INTEGRITY.json")
OUT_SHAP = os.path.join(REPO, "bench_logs", "SERVING_SHAP.json")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--rows", type=int, default=32,
                    help="rows per request")
    ap.add_argument("--duration", type=float, default=10.0,
                    help="measurement seconds per route")
    ap.add_argument("--mode", choices=("closed", "open"), default="closed")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="open-loop total arrival rate (req/s)")
    ap.add_argument("--devices", type=int, default=2,
                    help="serving mesh width (>1 on CPU re-execs with "
                         "virtual devices)")
    ap.add_argument("--trees", type=int, default=60)
    ap.add_argument("--leaves", type=int, default=31)
    ap.add_argument("--linger-ms", type=float, default=2.0)
    ap.add_argument("--max-batch", type=int, default=4096)
    ap.add_argument("--publish-every", type=float, default=0.0,
                    help="hot-swap cadence: train+publish one iteration "
                         "into the live server every S seconds (0=off)")
    ap.add_argument("--skip-native", action="store_true")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline (0 = the config default)")
    ap.add_argument("--max-queue-rows", type=int, default=0,
                    help="admission-control row bound (0 = config "
                         "default)")
    ap.add_argument("--chaos", action="store_true",
                    help="run the ISSUE 9 chaos gate instead of the "
                         "plain measurement (implies open-loop; skips "
                         "the native route)")
    ap.add_argument("--chaos-p999-ms", type=float, default=10_000.0,
                    help="chaos gate: p999 latency bound")
    ap.add_argument("--fleet", type=int, default=0,
                    help="ISSUE 13: serve this many mixed-shape tenant "
                         "models from ONE FleetServer (0 = single-model "
                         "modes); banks SERVING_FLEET.json incl. the "
                         "chaos leg")
    ap.add_argument("--fleet-rows", type=int, default=3000,
                    help="training rows per fleet tenant")
    ap.add_argument("--live", action="store_true",
                    help="ISSUE 14 freshness chaos gate: the full "
                         "continual-learning service (supervised child "
                         "trainer + HTTP front door) under Poisson "
                         "HTTP load, continuous publishes and one "
                         "injected trainer crash; banks "
                         "SERVING_LIVE.json")
    ap.add_argument("--live-crash-iter", type=int, default=6,
                    help="inject the trainer crash after this many "
                         "boosting iterations of launch 1 (0 = no "
                         "crash)")
    ap.add_argument("--mem-chaos", action="store_true",
                    help="ISSUE 17 memory-pressure gate: fleet under an "
                         "HBM budget below its pack bytes + oom:p=0.05 "
                         "injection + one pack-upload OOM; banks "
                         "SERVING_MEM.json")
    ap.add_argument("--mem-budget-frac", type=float, default=0.6,
                    help="mem-chaos: HBM budget as a fraction of the "
                         "fleet's total pack bytes (must force "
                         "eviction churn)")
    ap.add_argument("--integrity-chaos", action="store_true",
                    help="ISSUE 19 integrity gate: canary-armed fleet "
                         "under load + an injected device-pack bitflip "
                         "(detect / quarantine / repair) + a nan_grad-"
                         "poisoned trainer rollback proof; banks "
                         "SERVING_INTEGRITY.json")
    ap.add_argument("--explain", action="store_true",
                    help="ISSUE 20 explanation-serving gate: device-vs-"
                         "host SHAP throughput, a mixed predict+explain "
                         "open-loop leg (independent coalescing, 0 torn, "
                         "0 new steady-state traces, exact explain "
                         "accounting) and a per-tenant fleet leg; banks "
                         "SERVING_SHAP.json")
    ap.add_argument("--explain-rate", type=float, default=16.0,
                    help="explain mode: total open-loop arrival rate of "
                         "the mixed leg (req/s)")
    ap.add_argument("--explain-frac", type=float, default=0.3,
                    help="explain mode: fraction of mixed-leg arrivals "
                         "that are contrib requests")
    ap.add_argument("--out", default=None,
                    help="record path (default SERVING_LOAD.json; "
                         "SERVING_CHAOS.json under --chaos / "
                         "SERVING_FLEET.json under --fleet / "
                         "SERVING_LIVE.json under --live / "
                         "SERVING_MEM.json under --mem-chaos / "
                         "SERVING_INTEGRITY.json under "
                         "--integrity-chaos / SERVING_SHAP.json under "
                         "--explain so the banked throughput record is "
                         "never clobbered)")
    args = ap.parse_args(argv)
    if args.out is None:
        args.out = OUT_SHAP if args.explain else \
            (OUT_INTEGRITY if args.integrity_chaos else
             (OUT_MEM if args.mem_chaos else
              (OUT_LIVE if args.live else
               (OUT_FLEET if args.fleet else
                (OUT_CHAOS if args.chaos else OUT)))))
    return args


def ensure_virtual_devices(n: int) -> None:
    """Where the caller ASKED for the CPU (``JAX_PLATFORMS=cpu``),
    re-exec with n virtual CPU devices. Anything else — the variable
    unset included — serves on the devices jax finds: this script never
    chooses the CPU because nothing was said (bench_serving.py shares
    the rule)."""
    if n <= 1:
        return
    if "cpu" not in os.environ.get("JAX_PLATFORMS", "").lower():
        return                                   # real accelerator mesh
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}").strip()
    os.execv(sys.executable, [sys.executable] + sys.argv)


def run_clients(n_clients, duration, make_request, do_request):
    """Closed-loop: each client thread submits, waits, repeats.
    Returns (latencies_sec, n_done, wall_sec, errors)."""
    lats, errs = [], []
    lock = threading.Lock()
    stop = time.perf_counter() + duration

    def client(i):
        rng = random.Random(i)
        my_lats = []
        try:
            while time.perf_counter() < stop:
                X = make_request(rng)
                t0 = time.perf_counter()
                try:
                    do_request(X)
                except Exception as e:  # noqa: BLE001 — in the record
                    with lock:
                        errs.append(repr(e))
                    return
                my_lats.append(time.perf_counter() - t0)
        finally:
            # a client that dies mid-run still contributes everything
            # it completed — dropping them would bias QPS and the
            # percentiles low while the record claims errors=1
            with lock:
                lats.extend(my_lats)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(duration + 120)
    return lats, len(lats), time.perf_counter() - t0, errs


def run_open_loop(rate, duration, make_request, submit):
    """Open loop: Poisson arrivals at `rate` req/s; latency measured
    from the INTENDED arrival time (queueing under saturation counts)."""
    rng = random.Random(0)
    pending = []
    errs = []
    t0 = time.perf_counter()
    next_t = t0
    while True:
        next_t += rng.expovariate(rate)
        if next_t - t0 > duration:
            break
        now = time.perf_counter()
        if next_t > now:
            time.sleep(next_t - now)
        try:
            pending.append((next_t, submit(make_request(rng))))
        except Exception as e:  # noqa: BLE001
            errs.append(repr(e))
    lats = []
    for intended, fut in pending:
        try:
            fut.result(timeout=120)
            lats.append(fut.t_done - intended)
        except Exception as e:  # noqa: BLE001
            errs.append(repr(e))
    return lats, len(lats), time.perf_counter() - t0, errs


def chaos_route(args, bst, srv, probe):
    """Chaos gate (ISSUE 9): open-loop Poisson traffic from
    ``args.clients`` threads under dispatch_error:p=0.05 + one
    publish_fail + a forced mid-run degradation. Every response is
    verified bit-exactly against its generation's device OR host route
    (anything else is torn/wrong), and the failure counters are
    reconciled against what the clients actually observed. Returns
    (record, failures) — a non-empty failures list fails the gate."""
    import numpy as np
    from lightgbm_tpu.robustness import faults
    from lightgbm_tpu.serving import DeadlineExceeded, Overloaded
    from lightgbm_tpu.serving.metrics import latency_summary_ms

    expected = {}          # version -> (device_bits, host_bits)

    def bank(version):
        expected[version] = (
            bst.predict(probe, device=True, raw_score=True),
            bst.predict(probe, raw_score=True))

    bank(srv.generation.version)
    s0 = srv.stats()
    lock = threading.Lock()
    results = []           # per client: [(version, out, latency_sec)]
    sheds, expireds, hard = [], [], []
    pub_failures, pub_ok = [], []
    stop_pub = threading.Event()

    def publisher():
        while not stop_pub.wait(args.publish_every):
            try:
                bst.update()
                info = srv.publish()
                bank(info.version)
                pub_ok.append(info.version)
            except Exception as e:  # noqa: BLE001 — rollback keeps serving
                pub_failures.append(repr(e))

    def client(ci):
        rng = random.Random(1000 + ci)
        rate = max(args.rate / max(args.clients, 1), 1e-6)
        futs = []
        t0 = time.perf_counter()
        next_t = t0
        while True:
            next_t += rng.expovariate(rate)
            if next_t - t0 > args.duration:
                break
            now = time.perf_counter()
            if next_t > now:
                time.sleep(next_t - now)
            try:
                futs.append((next_t, srv.submit(
                    probe, deadline_ms=args.deadline_ms or 8000.0)))
            except Overloaded as e:
                with lock:
                    sheds.append(repr(e))
            except Exception as e:  # noqa: BLE001
                with lock:
                    hard.append(repr(e))
        mine = []
        for intended, fut in futs:
            try:
                out = fut.result(60)
                mine.append((fut.generation.version, out,
                             fut.t_done - intended))
            except DeadlineExceeded as e:
                with lock:
                    expireds.append(repr(e))
            except Exception as e:  # noqa: BLE001
                with lock:
                    hard.append(repr(e))
        with lock:
            results.append(mine)

    def degrader():
        time.sleep(args.duration / 2.0)
        srv.degrade("chaos: forced mid-run degradation")

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(args.clients)]
    pub = threading.Thread(target=publisher, daemon=True)
    deg = threading.Thread(target=degrader, daemon=True)
    t_wall = time.perf_counter()
    with faults.inject("dispatch_error:p=0.05:seed=11:n=1000000,"
                       "publish_fail:n=1") as plan:
        pub.start()
        deg.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(args.duration + 120)
        stop_pub.set()
        pub.join(30)
        deg.join(args.duration)
        # let the background probe close the degrade round-trip while
        # the plan is still installed (the probe consults its sites)
        t_end = time.perf_counter() + 30
        while srv.stats()["degraded"] and time.perf_counter() < t_end:
            time.sleep(0.05)
    wall = time.perf_counter() - t_wall
    s1 = srv.stats()
    d = {k: s1[k] - s0.get(k, 0) for k in (
        "requests", "expired", "shed", "dispatch_retries",
        "dispatch_failures", "degrade_events", "recoveries",
        "degraded_batches", "publish_failures")}

    flat = [r for mine in results for r in mine]
    lats = [max(lat, 0.0) for _v, _o, lat in flat]
    torn, monotonic = 0, True
    for mine in results:
        last = 0
        for v, out, _lat in mine:
            exp = expected.get(v)
            if exp is None or not (np.array_equal(out, exp[0]) or
                                   np.array_equal(out, exp[1])):
                torn += 1
            if v < last:
                monotonic = False
            last = max(last, v)

    failures = []

    def need(cond, what):
        if not cond:
            failures.append(what)

    need(not hard, f"{len(hard)} hard client error(s): {hard[:1]}")
    need(torn == 0, f"{torn} torn/wrong response(s)")
    need(monotonic, "a client observed generations moving backwards")
    need(d["requests"] == len(flat),
         f"fulfilled accounting: server {d['requests']} != "
         f"client {len(flat)}")
    need(d["expired"] == len(expireds),
         f"expired accounting: server {d['expired']} != "
         f"client {len(expireds)}")
    need(d["shed"] == len(sheds),
         f"shed accounting: server {d['shed']} != client {len(sheds)}")
    need(d["publish_failures"] == 1 and len(pub_failures) == 1,
         f"exactly one failed publish expected (server "
         f"{d['publish_failures']}, publisher {len(pub_failures)})")
    need(srv.generation.version == 1 + len(pub_ok),
         f"generation counter not gapless-monotonic: "
         f"v{srv.generation.version} after {len(pub_ok)} good publishes")
    need(d["degrade_events"] >= 1, "forced degradation never registered")
    need(d["recoveries"] >= 1 and not s1["degraded"],
         "server never un-degraded after the forced degradation")
    need(d["degraded_batches"] >= 1,
         "no batch was ever served by the degraded host route")
    # vacuity guard: the fault site must be WIRED (consulted at least
    # once). Requiring an actual p=0.05 firing would make the gate
    # flaky under saturation (few, heavily-coalesced batches = few
    # consults); the retry path itself is gated deterministically by
    # serving_chaos_smoke.py and tests/test_serving.py.
    de = plan.faults["dispatch_error"]
    need(de.calls >= 1,
         "dispatch_error site never consulted — faults not wired")
    lat_ms = latency_summary_ms(lats)
    p999 = lat_ms.get("p999_ms", float("inf"))
    need(bool(lats) and p999 < args.chaos_p999_ms,
         f"p999 {p999} ms not under the {args.chaos_p999_ms:.0f} ms "
         "bound")

    rec = {"wall_sec": round(wall, 2), "responses": len(flat),
           "qps": round(len(flat) / wall, 1), "torn": torn,
           "shed": len(sheds), "expired": len(expireds),
           "publish_failures": len(pub_failures),
           "publishes_ok": len(pub_ok),
           "generations_served": sorted({v for v, _o, _lat in flat}),
           "dispatch_error_consults": de.calls,
           "dispatch_error_fired": de.fired,
           "counters_delta": d}
    rec.update(lat_ms)
    if failures:
        rec["failures"] = failures
    return rec, failures


def fleet_route(args, record):
    """Fleet mode (ISSUE 13): N mixed-shape tenants on one FleetServer.
    Returns (status, note): open-loop Poisson traffic across tenants
    with mixed request sizes, measuring QPS/percentiles AND the
    steady-state trace count over the warmed window, then the chaos leg
    (one tenant's publish_fail + a forced degrade) with exact
    per-tenant accounting and 0-torn verification."""
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.analysis import guards
    from lightgbm_tpu.robustness import faults
    from lightgbm_tpu.serving import DeadlineExceeded, Overloaded
    from lightgbm_tpu.serving.metrics import latency_summary_ms

    rng = np.random.default_rng(0)
    archetypes = [(31, 20, 28), (15, 12, 12), (63, 16, 20), (15, 24, 12)]
    pools = {f: np.ascontiguousarray(
        rng.normal(size=(max(args.fleet_rows, 2048), f))
        .astype(np.float32).astype(np.float64))
        for f in {a[2] for a in archetypes}}
    t0 = time.perf_counter()
    tenants = {}
    for i in range(args.fleet):
        leaves, trees, f = archetypes[i % len(archetypes)]
        X = pools[f][:args.fleet_rows]
        y = (X[:, 0] * (1 + 0.1 * (i % 7)) +
             0.5 * X[:, 1] ** 2 > 0.4).astype(np.float32)
        bst = lgb.train({"objective": "binary", "num_leaves": leaves,
                         "verbosity": -1}, lgb.Dataset(X, label=y),
                        num_boost_round=trees,
                        keep_training_booster=True)
        tenants[f"t{i:03d}"] = (bst, f)
    print(f"[load] trained {args.fleet} tenants over "
          f"{len(archetypes)} archetypes "
          f"({time.perf_counter() - t0:.1f}s)", flush=True)

    fleet = lgb.serve_fleet({k: b for k, (b, _f) in tenants.items()},
                            raw_score=True, linger_ms=args.linger_ms,
                            max_batch=args.max_batch,
                            num_devices=args.devices,
                            probe_interval_s=1.0)
    st = fleet.stats()
    record["tenants"] = args.fleet
    record["buckets"] = st["n_buckets"]
    record["fleet_shard"] = st["fleet_shard"]
    record["pack_bytes"] = st["pack_bytes"]
    sizes = sorted({max(args.rows // 2, 1), args.rows, args.rows * 2})
    keys = list(tenants)

    def request_for(r):
        k = keys[r.randrange(len(keys))]
        pool = pools[tenants[k][1]]
        n = min(sizes[r.randrange(len(sizes))], pool.shape[0])
        off = r.randrange(0, pool.shape[0] - n + 1)
        return k, pool[off:off + n]

    # warm every (shape bucket, row bucket) the traffic can touch, then
    # a short unmeasured traffic burst to warm the COALESCED totals
    for k in keys:
        for warm in (200, 500):
            fleet.predict(k, pools[tenants[k][1]][:warm], timeout=300)
    r0 = random.Random(5)
    warm_until = time.perf_counter() + min(2.0, args.duration / 4)
    while time.perf_counter() < warm_until:
        k, X = request_for(r0)
        fleet.predict(k, X, timeout=300)

    # ---- measured window: QPS/percentiles + steady-state traces ------
    lats, errs = [], []
    with guards.CompileCounter() as counter:
        rgen = random.Random(1)
        pending = []
        t0 = time.perf_counter()
        next_t = t0
        while True:
            next_t += rgen.expovariate(args.rate)
            if next_t - t0 > args.duration:
                break
            now = time.perf_counter()
            if next_t > now:
                time.sleep(next_t - now)
            k, X = request_for(rgen)
            try:
                pending.append((next_t, fleet.submit(k, X)))
            except Exception as e:  # noqa: BLE001
                errs.append(repr(e))
        for intended, fut in pending:
            try:
                fut.result(timeout=120)
                lats.append(max(fut.t_done - intended, 0.0))
            except Exception as e:  # noqa: BLE001
                errs.append(repr(e))
        wall = time.perf_counter() - t0
    record["steady_state_new_traces"] = counter.count
    if counter.count:
        record["trace_names"] = counter.names[:8]
    rec = {"qps": round(len(lats) / wall, 1), "requests": len(lats),
           "wall_sec": round(wall, 2), "errors": len(errs)}
    rec.update(latency_summary_ms(lats))
    if errs:
        rec["first_error"] = errs[0]
    record["open_loop"] = rec
    record["value"] = rec["qps"]
    print(f"[load] fleet route {rec['qps']:.0f} req/s over "
          f"{args.fleet} tenants, p50={rec.get('p50_ms')}ms "
          f"p99={rec.get('p99_ms')}ms p999={rec.get('p999_ms')}ms, "
          f"{counter.count} new traces", flush=True)

    # ---- chaos leg: one tenant's publish_fail + a forced degrade -----
    chaos_key = keys[0]
    chaos_b = tenants[chaos_key][0]
    probe = {k: pools[tenants[k][1]][:args.rows] for k in keys}
    expected = {}

    def bank(k):
        v = fleet._state.routes[k].generation.version
        expected[(k, v)] = (
            tenants[k][0].predict(probe[k], device=True, raw_score=True),
            tenants[k][0].predict(probe[k], raw_score=True))

    for k in keys:
        bank(k)
    base = fleet.counters.tenant_snapshot()
    observed = {k: {"requests": 0, "shed": 0, "expired": 0}
                for k in keys}
    results, hard = [], []
    pub_failures, pub_ok = [], []
    stop = threading.Event()

    def publisher():
        while not stop.wait(0.5):
            try:
                chaos_b.update()
                chaos_b.num_trees()          # flush outside the server
                # bank the NEXT generation's bits BEFORE it can serve —
                # banking after publish() races the clients (a fast
                # response on the new generation would read as torn)
                v = fleet._state.routes[chaos_key].generation.version
                expected[(chaos_key, v + 1)] = (
                    chaos_b.predict(probe[chaos_key], device=True,
                                    raw_score=True),
                    chaos_b.predict(probe[chaos_key], raw_score=True))
                fleet.publish(chaos_key)
                pub_ok.append(1)
            except Exception as e:  # noqa: BLE001 — rollback keeps serving
                pub_failures.append(repr(e))

    def degrader():
        time.sleep(args.duration / 2)
        fleet.degrade("fleet chaos: forced mid-run degradation")

    lock = threading.Lock()

    def client(ci):
        r = random.Random(100 + ci)
        futs = []
        t0 = time.perf_counter()
        next_t = t0
        rate = max(args.rate / max(args.clients, 1), 1e-6)
        while True:
            next_t += r.expovariate(rate)
            if next_t - t0 > args.duration:
                break
            now = time.perf_counter()
            if next_t > now:
                time.sleep(next_t - now)
            k = keys[r.randrange(len(keys))]
            try:
                futs.append((k, fleet.submit(k, probe[k],
                                             deadline_ms=8000.0)))
            except Overloaded:
                with lock:
                    observed[k]["shed"] += 1
            except Exception as e:  # noqa: BLE001
                with lock:
                    hard.append(repr(e))
        for k, fut in futs:
            try:
                out = fut.result(60)
                with lock:
                    observed[k]["requests"] += 1
                    results.append((k, fut.generation.version, out))
            except DeadlineExceeded:
                with lock:
                    observed[k]["expired"] += 1
            except Exception as e:  # noqa: BLE001
                with lock:
                    hard.append(repr(e))

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(args.clients)]
    pub = threading.Thread(target=publisher, daemon=True)
    deg = threading.Thread(target=degrader, daemon=True)
    # after=1: the publisher's pre-publish BANKING predict consults the
    # same publish_fail site first (the solo engine's pack append);
    # consult #2 is the fleet publish itself — the site under test
    with faults.inject("publish_fail:after=1:n=1"):
        pub.start()
        deg.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(args.duration + 120)
        stop.set()
        pub.join(30)
        deg.join(args.duration)
    # let the background probe close the degrade round-trip
    t_end = time.perf_counter() + 30
    while fleet.stats()["degraded"] and time.perf_counter() < t_end:
        time.sleep(0.05)

    torn = 0
    for k, v, out in results:
        exp = expected.get((k, v))
        if exp is None or not (np.array_equal(out, exp[0]) or
                               np.array_equal(out, exp[1])):
            torn += 1
    ledger = fleet.counters.tenant_snapshot()
    stats = fleet.stats()
    failures = []

    def need(cond, what):
        if not cond:
            failures.append(what)

    need(not hard, f"{len(hard)} hard client error(s): {hard[:1]}")
    need(torn == 0, f"{torn} torn/wrong response(s)")
    need(len(pub_failures) == 1,
         f"exactly one failed publish expected "
         f"(got {len(pub_failures)})")
    need(ledger[chaos_key]["publish_failures"] -
         base.get(chaos_key, {}).get("publish_failures", 0) == 1,
         "the failed publish is not in the chaos tenant's ledger")
    for k in keys:
        led = {n: ledger[k][n] - base.get(k, {}).get(n, 0)
               for n in ("requests", "shed", "expired")}
        for n in ("requests", "shed", "expired"):
            need(led[n] == observed[k][n],
                 f"tenant {k} {n} accounting: server {led[n]} != "
                 f"client {observed[k][n]}")
    need(stats["degraded"] is False,
         "fleet never un-degraded after the forced degradation")
    need(fleet.counters.get("degrade_events") >= 1 and
         fleet.counters.get("recoveries") >= 1,
         "forced degradation/recovery never registered")
    record["chaos"] = {
        "responses": len(results), "torn": torn,
        "publish_failures": len(pub_failures),
        "publishes_ok": len(pub_ok),
        "degrade_events": fleet.counters.get("degrade_events"),
        "recoveries": fleet.counters.get("recoveries"),
        "tenant_ledger_sample": {k: ledger[k] for k in keys[:3]}}
    if failures:
        record["chaos"]["failures"] = failures
        for f in failures:
            print(f"[load] FLEET CHAOS FAIL: {f}", file=sys.stderr,
                  flush=True)
    print(f"[load] fleet chaos: {len(results)} responses, {torn} torn, "
          f"{len(pub_failures)} publish failure(s), "
          f"recoveries={fleet.counters.get('recoveries')}", flush=True)
    fleet.close()
    if failures:
        return "no_result", "; ".join(failures)
    return ("measured" if not stats["degraded"] else "degraded"), None


def mem_chaos_route(args, record):
    """ISSUE 17 memory-pressure survival gate. Returns (status, note).

    Topology: N mixed-shape tenants on one FleetServer whose HBM budget
    is sized BELOW the fleet's total pack bytes (measured first on an
    unbounded probe fleet), so serving rotates packs through eviction /
    lazy rebuild continuously. Load: open-loop Poisson traffic from
    ``--clients`` threads with mixed request sizes while ``oom:p=0.05``
    fires at the dispatch, pack-upload and rebuild consult points; then
    one publish whose pack upload OOMs deterministically (``oom:n=1`` —
    the forced-eviction path). Verified: 0 torn (every response
    bit-matches its tenant's banked predict_device bits or host-walk
    bits), exact per-tenant requests/shed/expired accounting,
    oom_bisects/evictions/rebuilds all >= 1 in the same counters stats()
    surfaces as /v1/stats, never whole-fleet degraded, trace count flat
    over the measured window."""
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.analysis import guards
    from lightgbm_tpu.robustness import faults
    from lightgbm_tpu.serving import DeadlineExceeded, Overloaded
    from lightgbm_tpu.serving.metrics import latency_summary_ms

    n_tenants = args.fleet or 6
    rng = np.random.default_rng(0)
    archetypes = [(31, 20, 28), (15, 12, 12), (63, 16, 20), (15, 24, 12)]
    pools = {f: np.ascontiguousarray(
        rng.normal(size=(max(args.fleet_rows, 2048), f))
        .astype(np.float32).astype(np.float64))
        for f in {a[2] for a in archetypes}}
    t0 = time.perf_counter()
    tenants = {}
    for i in range(n_tenants):
        leaves, trees, f = archetypes[i % len(archetypes)]
        X = pools[f][:args.fleet_rows]
        y = (X[:, 0] * (1 + 0.1 * (i % 7)) +
             0.5 * X[:, 1] ** 2 > 0.4).astype(np.float32)
        bst = lgb.train({"objective": "binary", "num_leaves": leaves,
                         "verbosity": -1}, lgb.Dataset(X, label=y),
                        num_boost_round=trees,
                        keep_training_booster=True)
        tenants[f"t{i:03d}"] = (bst, f)
    print(f"[load] trained {n_tenants} tenants over "
          f"{len(archetypes)} archetypes "
          f"({time.perf_counter() - t0:.1f}s)", flush=True)
    keys = list(tenants)

    # size the budget BELOW the real pack bytes: probe unbounded first
    with lgb.serve_fleet({k: b for k, (b, _f) in tenants.items()},
                         raw_score=True, linger_ms=args.linger_ms,
                         num_devices=args.devices) as probe_fleet:
        pack_bytes = probe_fleet.stats()["pack_bytes"]
    budget_mb = pack_bytes * args.mem_budget_frac / 1e6
    fleet = lgb.serve_fleet({k: b for k, (b, _f) in tenants.items()},
                            raw_score=True, linger_ms=args.linger_ms,
                            max_batch=args.max_batch,
                            num_devices=args.devices,
                            probe_interval_s=1.0,
                            mem_budget_mb=budget_mb)
    st = fleet.stats()
    record["tenants"] = n_tenants
    record["buckets"] = st["n_buckets"]
    record["pack_bytes"] = pack_bytes
    record["mem_budget_mb"] = round(budget_mb, 4)
    record["evicted_at_start"] = st["evicted_buckets"]

    # every request is a prefix slice of its tenant's pool at one of
    # these sizes, so every (tenant, size, generation) response can be
    # banked bit-for-bit against BOTH routes ahead of time
    sizes = sorted({max(args.rows // 2, 1), args.rows, args.rows * 2})
    expected = {}

    def bank(k):
        v = fleet._state.routes[k].generation.version
        b = tenants[k][0]
        for n in sizes:
            X = pools[tenants[k][1]][:n]
            expected[(k, n, v)] = (
                b.predict(X, device=True, raw_score=True),
                b.predict(X, raw_score=True))

    for k in keys:
        bank(k)

    # warm every (shape bucket, row bucket) the traffic and its
    # bisection halves can touch, then warm the coalesced totals
    for k in keys:
        for warm in (200, 500):
            fleet.predict(k, pools[tenants[k][1]][:warm], timeout=300)
    r0 = random.Random(5)
    warm_until = time.perf_counter() + min(2.0, args.duration / 4)
    while time.perf_counter() < warm_until:
        k = keys[r0.randrange(len(keys))]
        n = sizes[r0.randrange(len(sizes))]
        fleet.predict(k, pools[tenants[k][1]][:n], timeout=300)

    base = fleet.counters.tenant_snapshot()
    base_ev = {c: fleet.counters.get(c)
               for c in ("oom_bisects", "evictions", "rebuilds")}
    observed = {k: {"requests": 0, "shed": 0, "expired": 0}
                for k in keys}
    results, hard, lats = [], [], []
    lock = threading.Lock()

    def client(ci):
        r = random.Random(100 + ci)
        futs = []
        t0 = time.perf_counter()
        next_t = t0
        rate = max(args.rate / max(args.clients, 1), 1e-6)
        while True:
            next_t += r.expovariate(rate)
            if next_t - t0 > args.duration:
                break
            now = time.perf_counter()
            if next_t > now:
                time.sleep(next_t - now)
            k = keys[r.randrange(len(keys))]
            n = sizes[r.randrange(len(sizes))]
            try:
                futs.append((k, n, next_t,
                             fleet.submit(k, pools[tenants[k][1]][:n],
                                          deadline_ms=8000.0)))
            except Overloaded:
                with lock:
                    observed[k]["shed"] += 1
            except Exception as e:  # noqa: BLE001
                with lock:
                    hard.append(repr(e))
        for k, n, intended, fut in futs:
            try:
                out = fut.result(120)
                with lock:
                    observed[k]["requests"] += 1
                    results.append((k, n, fut.generation.version, out))
                    lats.append(max(fut.t_done - intended, 0.0))
            except DeadlineExceeded:
                with lock:
                    observed[k]["expired"] += 1
            except Exception as e:  # noqa: BLE001
                with lock:
                    hard.append(repr(e))

    # measured window: Poisson load under oom:p=0.05 (the dispatch,
    # pack-upload and rebuild consult points all draw from this plan)
    # with the steady-state trace budget measured over the same window
    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(args.clients)]
    t0 = time.perf_counter()
    with guards.CompileCounter() as counter:
        with faults.inject("oom:p=0.05:seed=9:n=1000000"):
            for t in threads:
                t.start()
            for t in threads:
                t.join(args.duration + 120)
    wall = time.perf_counter() - t0
    # snapshot the ledger NOW: the publish leg's own parity predicts
    # below are server-side traffic, not part of the measured window
    ledger = fleet.counters.tenant_snapshot()
    record["steady_state_new_traces"] = counter.count
    if counter.count:
        record["trace_names"] = counter.names[:8]
    rec = {"qps": round(len(results) / wall, 1),
           "requests": len(results), "wall_sec": round(wall, 2),
           "errors": len(hard)}
    rec.update(latency_summary_ms(lats))
    record["open_loop"] = rec
    record["value"] = rec["qps"]
    print(f"[load] mem chaos {rec['qps']:.0f} req/s, "
          f"p50={rec.get('p50_ms')}ms p999={rec.get('p999_ms')}ms, "
          f"{counter.count} new traces", flush=True)

    # the deterministic pack-upload OOM: one publish whose upload dies
    # -> the coldest resident pack is force-evicted, the generation
    # still lands (bank the new bits BEFORE they can serve)
    pub_key = keys[0]
    pub_b = tenants[pub_key][0]
    pub_b.update()
    pub_b.num_trees()                    # flush outside the server
    v = fleet._state.routes[pub_key].generation.version
    for n in sizes:
        X = pools[tenants[pub_key][1]][:n]
        expected[(pub_key, n, v + 1)] = (
            pub_b.predict(X, device=True, raw_score=True),
            pub_b.predict(X, raw_score=True))
    with faults.inject("oom:n=1"):
        pub_info = fleet.publish(pub_key)
    post_pub = [fleet.predict(pub_key, pools[tenants[pub_key][1]][:n],
                              timeout=120) for n in sizes]

    torn = 0
    for k, n, v, out in results:
        exp = expected.get((k, n, v))
        if exp is None or not (np.array_equal(out, exp[0]) or
                               np.array_equal(out, exp[1])):
            torn += 1
    for n, out in zip(sizes, post_pub):
        exp = expected[(pub_key, n, pub_info.version)]
        if not (np.array_equal(out, exp[0]) or
                np.array_equal(out, exp[1])):
            torn += 1
    stats = fleet.stats()
    ev = {c: fleet.counters.get(c) - base_ev[c]
          for c in ("oom_bisects", "evictions", "rebuilds")}
    failures = []

    def need(cond, what):
        if not cond:
            failures.append(what)

    need(not hard, f"{len(hard)} hard client error(s): {hard[:1]}")
    need(torn == 0, f"{torn} torn/wrong response(s)")
    need(results, "no responses measured")
    for k in keys:
        led = {n: ledger[k][n] - base.get(k, {}).get(n, 0)
               for n in ("requests", "shed", "expired")}
        for n in ("requests", "shed", "expired"):
            need(led[n] == observed[k][n],
                 f"tenant {k} {n} accounting: server {led[n]} != "
                 f"client {observed[k][n]}")
    need(record["evicted_at_start"] >= 1 or ev["evictions"] >= 1,
         "the budget never forced an eviction (not tight enough?)")
    need(ev["oom_bisects"] >= 1,
         "oom:p=0.05 never triggered a bisection")
    need(ev["evictions"] >= 1 and ev["rebuilds"] >= 1,
         f"eviction churn never registered ({ev})")
    need(all(c in stats for c in
             ("oom_bisects", "evictions", "rebuilds",
              "resident_pack_bytes", "evicted_buckets")),
         "stats() (the /v1/stats payload) is missing the ISSUE 17 "
         "counters")
    need(stats["degraded"] is False,
         "a size-induced OOM degraded the WHOLE fleet (bisection "
         "should scope the blast radius to the failing requests)")
    need(pub_info.version == 2,
         f"the pack-upload-OOM publish never landed ({pub_info})")
    # a single pack larger than the whole budget must stay resident
    # while it serves, so the ledger is bounded by max(budget, biggest)
    biggest = max(b.nbytes for b in fleet._state.buckets.values())
    need(stats["resident_pack_bytes"] <= max(budget_mb * 1e6, biggest) + 1,
         f"resident bytes {stats['resident_pack_bytes']} over the "
         f"{budget_mb:.3f} MB budget (biggest pack {biggest})")
    need(counter.count <= 2,
         f"steady-state traces not flat: {counter.count} new "
         f"({record.get('trace_names')})")
    record["mem_chaos"] = {
        "responses": len(results), "torn": torn,
        "oom_bisects": ev["oom_bisects"],
        "evictions": ev["evictions"], "rebuilds": ev["rebuilds"],
        "resident_pack_bytes": stats["resident_pack_bytes"],
        "evicted_buckets": stats["evicted_buckets"],
        "publish_version": pub_info.version,
        "tenant_ledger_sample": {k: ledger[k] for k in keys[:3]}}
    if failures:
        record["mem_chaos"]["failures"] = failures
        for f in failures:
            print(f"[load] MEM CHAOS FAIL: {f}", file=sys.stderr,
                  flush=True)
    print(f"[load] mem chaos: {len(results)} responses, {torn} torn, "
          f"bisects={ev['oom_bisects']} evictions={ev['evictions']} "
          f"rebuilds={ev['rebuilds']}", flush=True)
    fleet.close()
    if failures:
        return "no_result", "; ".join(failures)
    return "measured", None


def integrity_chaos_route(args, record):
    """ISSUE 19 integrity-defense chaos gate. Returns (status, note).

    Topology: a mixed-shape tenant fleet on one FleetServer with the
    canary probe ARMED (``tpu_integrity_probe_interval_s`` via the
    fleet config), under open-loop Poisson traffic. Mid-window the
    victim tenant's pack is evicted and its lazy rebuild is rotted
    (``bitflip:p=1:where=dev``): the publish-channel canary verify must
    catch the corrupt upload BEFORE install, quarantine ONLY the victim
    to the host walk, and the background probe must repair the pack and
    un-quarantine — all while every response stays bit-correct. A
    second leg poisons a resident trainer's gradients
    (``nan_grad:p=1:after=1``) and proves the numeric-health rollback:
    the final model is BIT-IDENTICAL to the fault-free run. Verified:
    detection within one probe interval, blast radius = the victim
    tenant alone, 0 torn/wrong responses (each bit-matches its tenant's
    banked device or host-walk bits), automatic repair + un-quarantine,
    and EXACT ``integrity_probes/integrity_mismatches/quarantines/
    repairs`` accounting through the same ``stats()`` the front door
    serves as ``/v1/stats``. Banks ``bench_logs/SERVING_INTEGRITY.json``.
    """
    import tempfile

    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.robustness import checkpoint as ckpt
    from lightgbm_tpu.robustness import faults
    from lightgbm_tpu.serving import DeadlineExceeded, Overloaded
    from lightgbm_tpu.serving.metrics import latency_summary_ms
    from lightgbm_tpu.service import TrainerSpec, run_resident_trainer

    probe_s = 1.0
    n_tenants = args.fleet or 4
    rng = np.random.default_rng(0)
    # the victim (keys[0]) gets a UNIQUE shape so it owns its bucket:
    # the blast-radius assertion is then exact under concurrent load
    archetypes = [(31, 20, 28), (15, 12, 12), (63, 16, 20), (15, 24, 12)]
    pools = {f: np.ascontiguousarray(
        rng.normal(size=(max(args.fleet_rows, 2048), f))
        .astype(np.float32).astype(np.float64))
        for f in {a[2] for a in archetypes}}
    t0 = time.perf_counter()
    tenants = {}
    for i in range(n_tenants):
        leaves, trees, f = archetypes[i % len(archetypes)]
        X = pools[f][:args.fleet_rows]
        y = (X[:, 0] * (1 + 0.1 * (i % 7)) +
             0.5 * X[:, 1] ** 2 > 0.4).astype(np.float32)
        bst = lgb.train({"objective": "binary", "num_leaves": leaves,
                         "verbosity": -1}, lgb.Dataset(X, label=y),
                        num_boost_round=trees)
        tenants[f"t{i:03d}"] = (bst, f)
    print(f"[load] trained {n_tenants} tenants "
          f"({time.perf_counter() - t0:.1f}s)", flush=True)
    keys = list(tenants)
    victim = keys[0]

    cfg = tenants[victim][0].config.copy()
    cfg.set("tpu_integrity_probe_interval_s", probe_s)
    fleet = lgb.serve_fleet({k: b for k, (b, _f) in tenants.items()},
                            raw_score=True, linger_ms=args.linger_ms,
                            max_batch=args.max_batch,
                            num_devices=args.devices, config=cfg)
    st = fleet.stats()
    record["tenants"] = n_tenants
    record["buckets"] = st["n_buckets"]
    record["probe_interval_s"] = probe_s

    # bank every (tenant, size) response bit-for-bit against BOTH
    # routes: a quarantined tenant answers with its host-walk bits
    sizes = sorted({max(args.rows // 2, 1), args.rows, args.rows * 2})
    expected = {}
    for k in keys:
        b = tenants[k][0]
        for n in sizes:
            X = pools[tenants[k][1]][:n]
            expected[(k, n)] = (b.predict(X, device=True, raw_score=True),
                                b.predict(X, raw_score=True))
    for k in keys:                                   # warm every bucket
        for n in sizes:
            fleet.predict(k, pools[tenants[k][1]][:n], timeout=300)

    base = fleet.counters.tenant_snapshot()
    observed = {k: {"requests": 0, "shed": 0, "expired": 0}
                for k in keys}
    results, hard, lats = [], [], []
    lock = threading.Lock()

    def client(ci):
        r = random.Random(100 + ci)
        futs = []
        t0 = time.perf_counter()
        next_t = t0
        rate = max(args.rate / max(args.clients, 1), 1e-6)
        while True:
            next_t += r.expovariate(rate)
            if next_t - t0 > args.duration:
                break
            now = time.perf_counter()
            if next_t > now:
                time.sleep(next_t - now)
            k = keys[r.randrange(len(keys))]
            n = sizes[r.randrange(len(sizes))]
            try:
                futs.append((k, n, next_t,
                             fleet.submit(k, pools[tenants[k][1]][:n],
                                          deadline_ms=8000.0)))
            except Overloaded:
                with lock:
                    observed[k]["shed"] += 1
            except Exception as e:  # noqa: BLE001
                with lock:
                    hard.append(repr(e))
        for k, n, intended, fut in futs:
            try:
                out = fut.result(120)
                with lock:
                    observed[k]["requests"] += 1
                    results.append((k, n, out))
                    lats.append(max(fut.t_done - intended, 0.0))
            except DeadlineExceeded:
                with lock:
                    observed[k]["expired"] += 1
            except Exception as e:  # noqa: BLE001
                with lock:
                    hard.append(repr(e))

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(args.clients)]
    t_wall = time.perf_counter()
    for t in threads:
        t.start()

    # the rot drill, mid-window: evict the victim's pack, arm a
    # device-upload bitflip, and force the lazy rebuild with one
    # predict — the canary verify catches the corrupt pack BEFORE
    # install, so this very response is already the host walk
    time.sleep(max(args.duration * 0.35, 1.0))
    n_v = args.rows
    Xv = pools[tenants[victim][1]][:n_v]
    t_rot = time.perf_counter()
    # arm BEFORE evicting: whichever dispatch (ours or a client's)
    # triggers the lazy rebuild inside this window uploads corrupt bits
    with faults.inject("bitflip:p=1:where=dev"):
        evicted = fleet.evict(victim)
        y_rot = fleet.predict(victim, Xv, timeout=120)
    detect_sec = time.perf_counter() - t_rot
    detected = fleet.tenant_stats(victim)["quarantined"]
    with lock:
        observed[victim]["requests"] += 1
        results.append((victim, n_v, y_rot))
    print(f"[load] integrity rot drill: detected={detected} in "
          f"{detect_sec * 1e3:.0f}ms", flush=True)

    # the probe must now repair the pack and un-quarantine on its own,
    # while traffic keeps flowing
    repair_sec = None
    deadline = time.time() + args.duration + 30
    while time.time() < deadline:
        snap = fleet.counters.tenant_snapshot().get(victim, {})
        if snap.get("repairs", 0) >= 1 and \
                not fleet.tenant_stats(victim)["quarantined"]:
            repair_sec = time.perf_counter() - t_rot
            break
        time.sleep(0.05)
    for t in threads:
        t.join(args.duration + 120)
    wall = time.perf_counter() - t_wall
    ledger = fleet.counters.tenant_snapshot()
    stats = fleet.stats()

    rec = {"qps": round(len(results) / wall, 1),
           "requests": len(results), "wall_sec": round(wall, 2),
           "errors": len(hard)}
    rec.update(latency_summary_ms(lats))
    record["open_loop"] = rec
    record["value"] = rec["qps"]
    print(f"[load] integrity chaos {rec['qps']:.0f} req/s, "
          f"p50={rec.get('p50_ms')}ms p999={rec.get('p999_ms')}ms",
          flush=True)

    torn = 0
    for k, n, out in results:
        exp = expected.get((k, n))
        if exp is None or not (np.array_equal(out, exp[0]) or
                               np.array_equal(out, exp[1])):
            torn += 1
    failures = []

    def need(cond, what):
        if not cond:
            failures.append(what)

    need(not hard, f"{len(hard)} hard client error(s): {hard[:1]}")
    need(results, "no responses measured")
    need(torn == 0, f"{torn} torn/wrong response(s)")
    need(evicted, "the victim's pack was never evicted")
    need(detected, "the rotted rebuild was never detected")
    need(detect_sec <= probe_s,
         f"detection took {detect_sec:.2f}s > one probe interval "
         f"({probe_s}s)")
    need(np.allclose(y_rot, expected[(victim, n_v)][1],
                     rtol=1e-5, atol=1e-6),
         "the quarantined response is not the host walk")
    vled = ledger.get(victim, {})
    need(vled.get("integrity_mismatches", 0) == 1 and
         vled.get("quarantines", 0) == 1 and
         vled.get("repairs", 0) == 1,
         f"victim integrity accounting not exact: {vled}")
    for k in keys[1:]:
        led = ledger.get(k, {})
        need(all(led.get(c, 0) == 0 for c in
                 ("integrity_mismatches", "quarantines", "repairs")),
             f"blast radius leaked to tenant {k}: {led}")
    need(repair_sec is not None,
         "the probe never repaired + un-quarantined the victim")
    need(stats.get("quarantined") is None,
         f"tenants still quarantined at end: {stats.get('quarantined')}")
    need(stats.get("integrity_probes", 0) >= 1 and
         stats.get("integrity_mismatches", 0) == 1 and
         stats.get("quarantines", 0) == 1 and
         stats.get("repairs", 0) == 1,
         "stats() (the /v1/stats payload) integrity accounting not "
         f"exact: probes={stats.get('integrity_probes')} "
         f"mismatches={stats.get('integrity_mismatches')} "
         f"quarantines={stats.get('quarantines')} "
         f"repairs={stats.get('repairs')}")
    need(np.array_equal(fleet.predict(victim, Xv, timeout=120),
                        expected[(victim, n_v)][0]),
         "the repaired device route is not bit-identical to pre-rot")
    for k in keys:
        led = {n: ledger.get(k, {}).get(n, 0) - base.get(k, {}).get(n, 0)
               for n in ("requests", "shed", "expired")}
        for n in ("requests", "shed", "expired"):
            need(led[n] == observed[k][n],
                 f"tenant {k} {n} accounting: server {led[n]} != "
                 f"client {observed[k][n]}")
    record["integrity"] = {
        "responses": len(results), "torn": torn,
        "detect_sec": round(detect_sec, 3),
        "repair_sec": (round(repair_sec, 3)
                       if repair_sec is not None else None),
        "victim": victim, "victim_ledger": dict(vled),
        "integrity_probes": stats.get("integrity_probes", 0),
        "integrity_mismatches": stats.get("integrity_mismatches", 0),
        "quarantines": stats.get("quarantines", 0),
        "repairs": stats.get("repairs", 0)}
    fleet.close()

    # leg 2 — trainer numeric-health rollback: a single-fire nan_grad
    # poisons the cycle after the first commit; the guard refuses, the
    # trainer rolls back to the newest CRC-valid checkpoint and retries
    # the SAME window, so the final model is bit-identical to clean
    t0 = time.perf_counter()
    rngt = np.random.default_rng(3)
    Xt = rngt.standard_normal((600, 6))
    yt = (Xt[:, 0] - 0.3 * Xt[:, 2] > 0).astype(np.float64)
    rows = np.concatenate([yt[:, None], Xt], axis=1)
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 5, "verbose": -1,
              "deterministic": True, "seed": 7}

    def train_once(d, spec_fault=None):
        spec = TrainerSpec(
            params=dict(params), stream_path=stream, ckpt_dir=d,
            window_rows=4096, min_rows=256, iters_per_cycle=3,
            publish_every_iters=3, target_iterations=6, poll_sec=0.05,
            keep_last=3)
        if spec_fault:
            with faults.inject(spec_fault):
                rc = run_resident_trainer(spec)
        else:
            rc = run_resident_trainer(spec)
        need(rc == 0, f"resident trainer rc={rc} ({d})")
        found = ckpt.latest_valid_checkpoint(d)
        need(found is not None, f"no valid checkpoint in {d}")
        return found[1]["model"] if found else None

    with tempfile.TemporaryDirectory() as tmp:
        stream = os.path.join(tmp, "stream.csv")
        with open(stream, "w") as fh:
            for r in rows:
                fh.write(",".join(f"{v:.9g}" for v in r) + "\n")
        clean = train_once(os.path.join(tmp, "clean"))
        poisoned = train_once(os.path.join(tmp, "poisoned"),
                              "nan_grad:p=1:after=1")
    identical = (clean is not None and poisoned == clean)
    need(identical,
         "nan_grad rollback: final model NOT bit-identical to the "
         "fault-free run")
    record["trainer_poison"] = {
        "fault": "nan_grad:p=1:after=1",
        "rollback_bit_identical": bool(identical),
        "wall_sec": round(time.perf_counter() - t0, 2)}
    print(f"[load] trainer poison leg: bit_identical={identical} "
          f"({record['trainer_poison']['wall_sec']}s)", flush=True)

    if failures:
        record["integrity"]["failures"] = failures
        for f in failures:
            print(f"[load] INTEGRITY CHAOS FAIL: {f}", file=sys.stderr,
                  flush=True)
        return "no_result", "; ".join(failures)
    return "measured", None


def live_route(args, record):
    """ISSUE 14 freshness chaos gate. Returns (status, note).

    Topology: a SUPERVISED child-process trainer boosting on a rolling
    window of a growing synthetic stream; the serving process's publish
    pump hot-swaps each committed checkpoint; open-loop Poisson HTTP
    clients hit the front door with npy bodies (bit-exact f64 wire).
    One injected ``rank_kill`` fires on trainer launch 1 only — the
    supervisor relaunches, the trainer resumes, publishes continue.
    Verified: 0 torn responses, per-client monotone + gapless published
    generations, >= 2 post-crash generations, staleness on every
    response; banked: QPS, latency p50/p99/p999, model-staleness
    p50/p99."""
    import io as _io
    import tempfile
    import urllib.request

    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.serving.metrics import latency_summary_ms
    from _service_gate import append_rows, synth_rows, verify_responses

    rng = np.random.default_rng(0)
    d = tempfile.mkdtemp(prefix="lgbm_serving_live_")
    stream = os.path.join(d, "rows.csv")
    ck = os.path.join(d, "ck")

    def rows(n):
        return synth_rows(rng, n, f=8)

    def append(block):
        append_rows(stream, block)

    append(rows(1200))
    crash = int(args.live_crash_iter)
    t0 = time.perf_counter()
    svc = lgb.serve_continual(
        {"objective": "binary", "num_leaves": args.leaves,
         "verbosity": -1},
        stream, ck, trainer_mode="process", window_rows=2000,
        min_rows=512, iters_per_cycle=2, publish_every_iters=2,
        target_iterations=0, raw_score=True, boot_timeout_s=600,
        poll_sec=0.1, keep_last=256,
        serve_kwargs=dict(linger_ms=args.linger_ms,
                          max_batch=args.max_batch),
        attempt_env=lambda i: (
            {"LGBM_TPU_FAULTS":
             f"rank_kill:rank=0:after={max(crash - 1, 0)}"}
            if (i == 0 and crash) else {"LGBM_TPU_FAULTS": ""}))
    record["boot_sec"] = round(time.perf_counter() - t0, 1)
    record["trainer_mode"] = "process"
    record["crash_iteration"] = crash
    try:
        return _live_route_body(args, record, svc, rows, append, crash)
    finally:
        # ANY raise after boot must still stop the supervised child —
        # target_iterations=0 means an orphan polls its tmpdir stream
        # and commits checkpoints forever (close() is idempotent)
        svc.close()


def _live_route_body(args, record, svc, rows, append, crash):
    import io as _io
    import urllib.request

    import numpy as np
    import lightgbm_tpu as lgb  # noqa: F401 — verify_responses path
    from lightgbm_tpu.serving.metrics import latency_summary_ms
    from _service_gate import verify_responses

    ck = svc.ckpt_dir
    url = svc.frontdoor.address + "/v1/predict"
    probe = rows(args.rows)[:, 1:].astype(np.float64)
    buf = _io.BytesIO()
    np.save(buf, probe, allow_pickle=False)
    payload = buf.getvalue()
    print(f"[load] live service booted in {record['boot_sec']}s "
          f"(gen v{svc.generation.version}) at {url}", flush=True)

    stop = threading.Event()

    def producer():
        while not stop.wait(0.15):
            append(rows(80))

    lock = threading.Lock()
    responses, hard = [], []

    def client(ci):
        r = random.Random(500 + ci)
        rate = max(args.rate / max(args.clients, 1), 1e-6)
        t0 = time.perf_counter()
        next_t = t0
        while True:
            next_t += r.expovariate(rate)
            if next_t - t0 > args.duration:
                return
            now = time.perf_counter()
            if next_t > now:
                time.sleep(next_t - now)
            try:
                req = urllib.request.Request(
                    url, data=payload,
                    headers={"Content-Type": "application/x-npy"})
                resp = urllib.request.urlopen(req, timeout=60)
                out = np.load(_io.BytesIO(resp.read()),
                              allow_pickle=False)
                with lock:
                    responses.append((
                        ci, int(resp.headers["X-Model-Generation"]),
                        out,
                        float(resp.headers["X-Staleness-Ms"]),
                        time.perf_counter() - next_t))
            except Exception as e:  # noqa: BLE001
                with lock:
                    hard.append(repr(e))

    prod = threading.Thread(target=producer, daemon=True)
    clients = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(args.clients)]
    relaunch_seen_at_gen = None
    t_wall = time.perf_counter()
    prod.start()
    for t in clients:
        t.start()
    while any(t.is_alive() for t in clients):
        if relaunch_seen_at_gen is None and svc.trainer.relaunches:
            relaunch_seen_at_gen = svc.generation.version
        time.sleep(0.2)
    for t in clients:
        t.join(60)
    # let post-crash publishes land before stopping the world
    t_end = time.perf_counter() + 60
    while crash and time.perf_counter() < t_end:
        if relaunch_seen_at_gen is None and svc.trainer.relaunches:
            relaunch_seen_at_gen = svc.generation.version
        if relaunch_seen_at_gen is not None and \
                svc.generation.version >= relaunch_seen_at_gen + 2:
            break
        time.sleep(0.2)
    stop.set()
    wall = time.perf_counter() - t_wall
    stats = svc.stats()
    final_gen = svc.generation.version
    trainer = svc.trainer.describe()

    # ---- verification ------------------------------------------------
    failures = []

    def need(cond, what):
        if not cond:
            failures.append(what)

    # ONE shared torn/monotone/staleness pass with service_smoke.py
    # (_service_gate.py — the bit-match contract must not drift)
    torn, unverifiable = verify_responses(
        svc, ck, probe,
        ((ci, v, out, stale) for ci, v, out, stale, _lat in responses),
        failures)
    served_versions = sorted({v for _c, v, *_r in responses})
    need(not hard, f"{len(hard)} hard client error(s): {hard[:2]}")
    need(responses, "no responses")
    need(unverifiable <= len(responses) // 2,
         f"{unverifiable}/{len(responses)} unverifiable")
    # gapless: the pump's version counter only advances on a successful
    # publish, so served versions must be a subset of 1..final with no
    # version the service cannot account a watermark for
    need(all(1 <= v <= final_gen for v in served_versions),
         f"served versions {served_versions} outside 1..{final_gen}")
    need(all(svc.freshness(v) is not None for v in served_versions),
         "a served generation has no watermark entry")
    if crash:
        need(trainer.get("relaunches", 0) >= 1,
             f"injected trainer crash never relaunched: {trainer}")
        need(relaunch_seen_at_gen is not None and
             final_gen >= relaunch_seen_at_gen + 2,
             f"fewer than 2 generations after the relaunch "
             f"(at-relaunch v{relaunch_seen_at_gen}, final "
             f"v{final_gen})")
        need(stats["service"]["publish_errors"] == 0,
             f"{stats['service']['publish_errors']} publish error(s)")

    lat = latency_summary_ms([lt for *_a, lt in responses])
    stale_ms = sorted(s for _c, _v, _o, s, _l in responses)
    rec = {"responses": len(responses),
           "qps": round(len(responses) / wall, 1),
           "wall_sec": round(wall, 2), "torn": torn,
           "unverifiable": unverifiable,
           "generations_served": served_versions,
           "final_generation": final_gen,
           "served_iteration": stats["service"]["served_iteration"],
           "publishes": stats["service"]["publishes"],
           "trainer": trainer,
           "relaunch_seen_at_gen": relaunch_seen_at_gen}
    rec.update(lat)
    if stale_ms:
        from lightgbm_tpu.serving.metrics import percentile
        rec["staleness_p50_ms"] = round(percentile(stale_ms, 50.0), 1)
        rec["staleness_p99_ms"] = round(percentile(stale_ms, 99.0), 1)
        rec["staleness_max_ms"] = round(stale_ms[-1], 1)
    record["live"] = rec
    record["value"] = rec["qps"]
    record["degraded"] = bool(stats.get("degraded"))
    print(f"[load] live route {rec['qps']:.1f} req/s, "
          f"{len(responses)} responses over generations "
          f"{served_versions[:1]}..{served_versions[-1:]}, {torn} torn, "
          f"relaunches={trainer.get('relaunches')}, staleness "
          f"p50={rec.get('staleness_p50_ms')}ms "
          f"p99={rec.get('staleness_p99_ms')}ms, "
          f"p99 lat={rec.get('p99_ms')}ms", flush=True)
    if failures:
        record["live"]["failures"] = failures
        for f in failures:
            print(f"[load] LIVE CHAOS FAIL: {f}", file=sys.stderr,
                  flush=True)
        return "no_result", "; ".join(failures)
    return ("degraded" if record["degraded"] else "measured"), None


def explain_route(args, record):
    """ISSUE 20 explanation-serving gate. Returns (status, note).

    Three legs over a ``--trees x --leaves`` 28-feature model:

    1. **throughput**: device SHAP contributions through the packed
       path tensors vs the host ``predict_contrib`` walk (the native
       C++ kernel when built), chunked over 100k-row-scale traffic.
       The >=3x speedup target is enforced on a REAL accelerator only —
       under virtual XLA-CPU devices the "device" is the host CPU
       running a scatter-heavy kernel against the native C++ oracle,
       so the ratio measures nothing about the TPU route (recorded,
       not gated).
    2. **mixed open-loop**: Poisson arrivals, ``--explain-frac`` of
       them contrib requests, through ONE solo server. Gates: 0 torn
       responses (every response bit-matches the banked device bits or
       the host-oracle bits of its kind), 0 new steady-state traces
       over the warmed window, EXACT accounting — the explain
       batcher's request/row ledger must equal the client-observed
       explain traffic and the predict batcher's must equal the
       predict traffic (the proof the two families never share a
       coalesced batch), and ``explain_requests``/``explain_degraded``
       must reconcile exactly.
    3. **fleet per-tenant**: two tenants, one quarantined mid-leg —
       its explains must answer the host oracle bit-exactly and land
       in ITS ledger as ``explain_degraded``; per-tenant
       ``explain_requests`` accounting must be exact.
    """
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.analysis import guards
    from lightgbm_tpu.core.shap import predict_contrib
    from lightgbm_tpu.serving import Overloaded
    from lightgbm_tpu.serving.metrics import latency_summary_ms

    rng = np.random.default_rng(0)
    Xtr = rng.normal(size=(60_000, 28)).astype(np.float32)
    ytr = (Xtr[:, 0] + 0.5 * Xtr[:, 1] ** 2 > 0.5).astype(np.float32)
    t0 = time.perf_counter()
    bst = lgb.train({"objective": "binary", "num_leaves": args.leaves,
                     "verbosity": -1}, lgb.Dataset(Xtr, label=ytr),
                    num_boost_round=args.trees,
                    keep_training_booster=True)
    print(f"[load] trained {args.trees}x{args.leaves} "
          f"({time.perf_counter() - t0:.1f}s)", flush=True)
    pool = np.ascontiguousarray(
        rng.normal(size=(100_000, 28)).astype(np.float32)
        .astype(np.float64))
    failures = []

    def need(cond, what):
        if not cond:
            failures.append(what)

    # ---- leg 1: device vs host contribution throughput ---------------
    import jax
    on_accelerator = jax.devices()[0].platform not in ("cpu",)
    chunk = 1024 if not on_accelerator else 8192
    budget = min(args.duration, 20.0)
    bst.predict(pool[:chunk], pred_contrib=True, device=True)  # warm
    dev_lats, dev_rows = [], 0
    # jaxlint: disable=JL005 — Booster.predict returns a fetched host
    # numpy array (implicit device sync), so the wall clock brackets
    # real execution, not just dispatch.
    t0 = time.perf_counter()
    off = 0
    while time.perf_counter() - t0 < budget:
        tc = time.perf_counter()
        bst.predict(pool[off:off + chunk], pred_contrib=True,
                    device=True)
        dev_lats.append(time.perf_counter() - tc)
        dev_rows += chunk
        off = (off + chunk) % (pool.shape[0] - chunk)
    dev_wall = time.perf_counter() - t0
    host_lats, host_rows = [], 0
    t0 = time.perf_counter()
    off = 0
    while time.perf_counter() - t0 < budget:
        tc = time.perf_counter()
        predict_contrib(bst._engine, pool[off:off + chunk], 0,
                        args.trees)
        host_lats.append(time.perf_counter() - tc)
        host_rows += chunk
        off = (off + chunk) % (pool.shape[0] - chunk)
    host_wall = time.perf_counter() - t0
    dev_rps = dev_rows / dev_wall
    host_rps = host_rows / host_wall
    speedup = dev_rps / host_rps if host_rps else 0.0
    record["throughput"] = {
        "chunk_rows": chunk,
        "device_rows_per_sec": round(dev_rps, 1),
        "host_rows_per_sec": round(host_rps, 1),
        "speedup": round(speedup, 3), "speedup_target": 3.0,
        "speedup_gated": on_accelerator,
        **{f"device_{k}": v
           for k, v in latency_summary_ms(dev_lats).items()},
        **{f"host_{k}": v
           for k, v in latency_summary_ms(host_lats).items()}}
    gate_note = "gated" if on_accelerator else \
        "recorded only: virtual CPU devices"
    print(f"[load] explain throughput: device {dev_rps:.0f} rows/s vs "
          f"host {host_rps:.0f} rows/s ({speedup:.2f}x, {gate_note})",
          flush=True)
    if on_accelerator:
        need(speedup >= 3.0,
             f"device/host explain speedup {speedup:.2f}x < 3.0x")

    # ---- leg 2: mixed predict+explain open-loop through one server ---
    srv = bst.serve(linger_ms=args.linger_ms, max_batch=args.max_batch,
                    num_devices=args.devices, raw_score=True)
    Xp = np.ascontiguousarray(pool[:args.rows])
    # banked references: serving responses must bit-match one of these
    ref_pred_dev = bst.predict(Xp, device=True, raw_score=True)
    ref_pred_host = bst.predict(Xp, raw_score=True)
    ref_exp_dev = srv.explain(Xp, timeout=300)
    ref_exp_host = predict_contrib(bst._engine, Xp, 0, args.trees)
    # atol rides above the measured f32 EXTEND/UNWIND drift (~1.5e-5
    # max abs at 60 trees x 31 leaves); route bugs land orders of
    # magnitude higher.
    need(np.allclose(ref_exp_dev, ref_exp_host, rtol=1e-4, atol=1e-4),
         "device explain bits failed the host-anchor tolerance before "
         "the measured window")
    # warm every row bucket coalescing can produce for BOTH kinds —
    # all the way to each batcher's own coalescing cap (a loaded
    # machine queues deep enough to hit the cap-sized bucket)
    score_cap = srv._batcher.max_batch       # coalescing honors the cap
    explain_cap = srv._explain_batcher.max_batch
    w = args.rows
    while w <= score_cap:
        srv.predict(pool[:w], timeout=300)
        if w <= explain_cap:
            srv.explain(pool[:w], timeout=300)
        w *= 2
    s_before = srv.stats()
    c_before = srv.counters.snapshot()
    sent = {"score": 0, "contrib": 0}
    fulfilled = {"score": 0, "contrib": 0}
    shed = {"score": 0, "contrib": 0}
    torn = 0
    rgen = random.Random(1)
    pending, errs, lats = [], [], []
    with guards.CompileCounter() as counter:
        t0 = time.perf_counter()
        next_t = t0
        while True:
            next_t += rgen.expovariate(args.explain_rate)
            if next_t - t0 > args.duration:
                break
            now = time.perf_counter()
            if next_t > now:
                time.sleep(next_t - now)
            kind = "contrib" if rgen.random() < args.explain_frac \
                else "score"
            try:
                pending.append(
                    (next_t, kind, srv.submit(Xp, kind=kind)))
                sent[kind] += 1
            except Overloaded:
                shed[kind] += 1
            except Exception as e:  # noqa: BLE001
                errs.append(repr(e))
        for intended, kind, fut in pending:
            try:
                out = fut.result(timeout=120)
            except Exception as e:  # noqa: BLE001
                errs.append(repr(e))
                continue
            lats.append(max(fut.t_done - intended, 0.0))
            fulfilled[kind] += 1
            if kind == "score":
                ok = np.array_equal(out, ref_pred_dev) or \
                    np.array_equal(out, ref_pred_host)
            else:
                ok = np.array_equal(out, ref_exp_dev) or \
                    np.array_equal(out, ref_exp_host)
            if not ok:
                torn += 1
        wall = time.perf_counter() - t0
    s_after = srv.stats()
    c_after = srv.counters.snapshot()
    srv.close()
    rec = {"qps": round(len(lats) / wall, 1),
           "requests": len(lats), "wall_sec": round(wall, 2),
           "sent": dict(sent), "shed": dict(shed), "torn": torn,
           "errors": len(errs),
           "new_traces": counter.count}
    rec.update(latency_summary_ms(lats))
    if errs:
        rec["first_error"] = errs[0]
    record["mixed_open_loop"] = rec
    record["value"] = record["throughput"]["device_rows_per_sec"]
    need(torn == 0, f"{torn} torn/wrong mixed-leg response(s)")
    need(not errs, f"{len(errs)} hard mixed-leg error(s): {errs[:1]}")
    need(counter.count == 0,
         f"{counter.count} new steady-state trace(s): "
         f"{counter.names[:4]}")
    # independent coalescing, proven by exact ledger separation: the
    # explain batcher saw exactly the explain traffic, the score
    # batcher exactly the score traffic
    d_exp_req = s_after["explain"]["requests"] - \
        s_before["explain"]["requests"]
    d_exp_rows = s_after["explain"]["rows"] - \
        s_before["explain"]["rows"]
    d_score_req = (s_after["requests"] - s_before["requests"])
    d_score_rows = (s_after["rows"] - s_before["rows"])
    need(d_exp_req == sent["contrib"],
         f"explain batcher requests {d_exp_req} != "
         f"client contrib submits {sent['contrib']}")
    need(d_exp_rows == sent["contrib"] * args.rows,
         f"explain batcher rows {d_exp_rows} != "
         f"{sent['contrib']} x {args.rows}")
    need(d_score_req == sent["score"],
         f"score batcher requests {d_score_req} != "
         f"client score submits {sent['score']}")
    need(d_score_rows == sent["score"] * args.rows,
         f"score batcher rows {d_score_rows} != "
         f"{sent['score']} x {args.rows}")
    need(c_after["explain_requests"] - c_before["explain_requests"]
         == fulfilled["contrib"],
         "explain_requests counter != fulfilled contrib requests")
    need(c_after["explain_degraded"] == c_before["explain_degraded"],
         "explain_degraded moved in the steady state")
    print(f"[load] mixed leg: {rec['qps']:.1f} req/s "
          f"({sent['score']} score + {sent['contrib']} contrib), "
          f"{torn} torn, {counter.count} new traces, "
          f"p50={rec.get('p50_ms')}ms p99={rec.get('p99_ms')}ms",
          flush=True)

    # ---- leg 3: fleet per-tenant explain accounting ------------------
    tb = {}
    for i, name in enumerate(("ta", "tb")):
        y2 = (Xtr[:, 0] * (1 + 0.2 * i) + 0.5 * Xtr[:, 1] ** 2
              > 0.4).astype(np.float32)
        tb[name] = lgb.train(
            {"objective": "binary", "num_leaves": 15, "verbosity": -1},
            lgb.Dataset(Xtr[:8000], label=y2[:8000]),
            num_boost_round=8, keep_training_booster=True)
    fleet = lgb.serve_fleet(dict(tb), raw_score=True,
                            linger_ms=args.linger_ms,
                            num_devices=args.devices)
    n_a, n_b = 7, 5
    got_a = [fleet.explain("ta", Xp) for _ in range(n_a)]
    fleet._quarantine("tb", "explain gate drill")
    got_b = [fleet.explain("tb", Xp) for _ in range(n_b)]
    fleet_torn = 0
    ref_a_host = predict_contrib(tb["ta"]._engine, Xp, 0, 8)
    for out in got_a:
        if not (np.allclose(out, ref_a_host, rtol=1e-4, atol=1e-5)):
            fleet_torn += 1
    ref_b_host = predict_contrib(tb["tb"]._engine, Xp, 0, 8)
    for out in got_b:
        if not np.array_equal(out, ref_b_host):
            fleet_torn += 1
    led = fleet.counters.tenant_snapshot()
    fleet.close()
    record["fleet_leg"] = {
        "tenants": 2, "explains": {"ta": n_a, "tb": n_b},
        "torn": fleet_torn,
        "ledger": {k: {n: led[k][n] for n in
                       ("explain_requests", "explain_degraded")}
                   for k in ("ta", "tb")}}
    need(fleet_torn == 0,
         f"{fleet_torn} torn fleet-leg response(s) (quarantined "
         "tenant must serve host-oracle bits)")
    need(led["ta"]["explain_requests"] == n_a and
         led["ta"]["explain_degraded"] == 0,
         f"tenant ta ledger {led['ta']} != {n_a} device explains")
    need(led["tb"]["explain_requests"] == n_b and
         led["tb"]["explain_degraded"] == n_b,
         f"tenant tb ledger {led['tb']} != {n_b} degraded explains")
    print(f"[load] fleet leg: ta {led['ta']['explain_requests']}/"
          f"{led['ta']['explain_degraded']} tb "
          f"{led['tb']['explain_requests']}/"
          f"{led['tb']['explain_degraded']} (requests/degraded), "
          f"{fleet_torn} torn", flush=True)

    if failures:
        record["failures"] = failures
        for f in failures:
            print(f"[load] EXPLAIN GATE FAIL: {f}", file=sys.stderr,
                  flush=True)
        return "no_result", "; ".join(failures)
    return "measured", None


def route_record(lats, n_done, wall, rows_per_req, errs) -> dict:
    from lightgbm_tpu.serving.metrics import latency_summary_ms
    rec = {"qps": round(n_done / wall, 1),
           "rows_per_sec": round(n_done * rows_per_req / wall, 1),
           "requests": n_done, "wall_sec": round(wall, 2),
           "errors": len(errs)}
    rec.update(latency_summary_ms(lats))
    if errs:
        rec["first_error"] = errs[0]
    return rec


def main() -> int:
    args = parse_args()
    ensure_virtual_devices(args.devices)

    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.serving.metrics import latency_summary_ms

    record = {"metric": "serving_load_qps", "unit": "req/sec",
              "value": 0.0, "status": "no_result",
              "mode": args.mode, "clients": args.clients,
              "rows_per_request": args.rows,
              "duration_sec": args.duration, "trees": args.trees,
              "leaves": args.leaves, "linger_ms": args.linger_ms}

    from _bench_io import classify_status, status_for, write_record

    def finish(status, note=None) -> int:
        record["status"] = status
        if note:
            record["note"] = note
        write_record(args.out, record)
        return 0 if status == "measured" else 1

    try:
        import jax
        record["devices"] = len(jax.devices())

        # ---- live mode (ISSUE 14): continual service over HTTP ------
        if args.live:
            record["metric"] = "serving_live_qps"
            record["mode"] = "open"
            record["rate"] = args.rate
            status, note = live_route(args, record)
            return finish(status, note)

        # ---- explain mode (ISSUE 20): SHAP contribution serving -----
        if args.explain:
            record["metric"] = "serving_shap_rows_per_sec"
            record["unit"] = "rows/sec"
            record["mode"] = "mixed"
            record["explain_rate"] = args.explain_rate
            record["explain_frac"] = args.explain_frac
            status, note = explain_route(args, record)
            return finish(status, note)

        # ---- integrity-chaos mode (ISSUE 19): silent corruption -----
        if args.integrity_chaos:
            record["metric"] = "serving_integrity_qps"
            record["mode"] = "open"
            record["rate"] = args.rate
            status, note = integrity_chaos_route(args, record)
            return finish(status, note)

        # ---- mem-chaos mode (ISSUE 17): OOM + eviction churn --------
        if args.mem_chaos:
            record["metric"] = "serving_mem_qps"
            record["mode"] = "open"
            record["rate"] = args.rate
            record["mem_budget_frac"] = args.mem_budget_frac
            status, note = mem_chaos_route(args, record)
            return finish(status, note)

        # ---- fleet mode (ISSUE 13): N tenants, one server -----------
        if args.fleet:
            record["metric"] = "serving_fleet_qps"
            record["mode"] = "open"
            record["rate"] = args.rate
            status, note = fleet_route(args, record)
            return finish(status, note)
        rng = np.random.default_rng(0)
        Xtr = rng.normal(size=(60_000, 28)).astype(np.float32)
        ytr = (Xtr[:, 0] + 0.5 * Xtr[:, 1] ** 2 > 0.5).astype(np.float32)
        dtrain = lgb.Dataset(Xtr, label=ytr)
        t0 = time.perf_counter()
        bst = lgb.train({"objective": "binary", "num_leaves": args.leaves,
                         "verbosity": -1}, dtrain,
                        num_boost_round=args.trees)
        # jaxlint: disable=JL005 — train() returns host-materialized
        # trees (a real barrier); this times execution, not dispatch
        print(f"[load] trained {args.trees}x{args.leaves} "
              f"({time.perf_counter() - t0:.1f}s)", flush=True)
        pool = np.ascontiguousarray(
            rng.normal(size=(200_000, 28)).astype(np.float32)
            .astype(np.float64))

        def make_request(r):
            off = r.randrange(0, pool.shape[0] - args.rows)
            return pool[off:off + args.rows]

        # ---- chaos gate (ISSUE 9): failure-path verification ---------
        if args.chaos:
            record["mode"] = "open"              # chaos is always open-loop
            if args.publish_every <= 0:
                args.publish_every = 0.5
            srv = bst.serve(linger_ms=args.linger_ms,
                            max_batch=args.max_batch,
                            num_devices=args.devices, raw_score=True,
                            probe_interval_s=1.0,
                            deadline_ms=args.deadline_ms or None,
                            max_queue_rows=args.max_queue_rows or None)
            probe_req = np.ascontiguousarray(pool[:args.rows])
            srv.predict(probe_req, timeout=300)          # warm buckets
            chaos, failures = chaos_route(args, bst, srv, probe_req)
            stats = srv.stats()
            srv.close()
            record["chaos"] = chaos
            record["degraded"] = bool(stats.get("degraded"))
            record["value"] = chaos["qps"]
            print(f"[load] chaos: {chaos['responses']} responses, "
                  f"{chaos['torn']} torn, shed={chaos['shed']} "
                  f"expired={chaos['expired']} "
                  f"p999={chaos.get('p999_ms')}ms "
                  f"counters={chaos['counters_delta']}", flush=True)
            if failures:
                for f in failures:
                    print(f"[load] CHAOS FAIL: {f}", file=sys.stderr,
                          flush=True)
                return finish("no_result", "; ".join(failures))
            return finish(status_for(stats))

        # ---- single-stream baseline: one client, direct device path --
        bst.predict(make_request(random.Random(0)), device=True,
                    raw_score=True)                       # warm buckets
        lats, n, wall, errs = run_clients(
            1, min(args.duration, 5.0), make_request,
            lambda X: bst.predict(X, device=True, raw_score=True))
        if errs:
            return finish("no_result", f"single-stream: {errs[0]}")
        record["single_stream"] = route_record(lats, n, wall, args.rows,
                                               errs)
        single_rps = record["single_stream"]["rows_per_sec"]
        print(f"[load] single-stream {single_rps:.0f} rows/s "
              f"{latency_summary_ms(lats)}", flush=True)

        # ---- device route: micro-batched concurrent server -----------
        srv = bst.serve(linger_ms=args.linger_ms,
                        max_batch=args.max_batch,
                        num_devices=args.devices, raw_score=True,
                        deadline_ms=args.deadline_ms or None,
                        max_queue_rows=args.max_queue_rows or None)
        for warm_rows in {args.rows, args.rows * max(args.clients, 1)}:
            srv.predict(pool[:max(warm_rows, 1)], timeout=300)
        publisher_stop = threading.Event()
        publisher_err = []

        def publisher():
            while not publisher_stop.wait(args.publish_every):
                try:
                    bst.update()
                    srv.publish()
                except Exception as e:  # noqa: BLE001
                    publisher_err.append(repr(e))
                    return

        pub_thread = None
        if args.publish_every > 0:
            pub_thread = threading.Thread(target=publisher, daemon=True)
            pub_thread.start()
        if args.mode == "closed":
            lats, n, wall, errs = run_clients(
                args.clients, args.duration, make_request,
                lambda X: srv.predict(X, timeout=120))
        else:
            lats, n, wall, errs = run_open_loop(
                args.rate, args.duration, make_request, srv.submit)
        publisher_stop.set()
        if pub_thread is not None:
            pub_thread.join(30)
        dev = route_record(lats, n, wall, args.rows, errs)
        dev["server"] = srv.stats()
        record["degraded"] = bool(dev["server"].get("degraded"))
        if publisher_err:
            dev["publish_error"] = publisher_err[0]
        if args.publish_every > 0:
            dev["published_generations"] = srv.generation.version
        dev["speedup_vs_single_stream"] = round(
            dev["rows_per_sec"] / single_rps, 2) if single_rps else 0.0
        record["device"] = dev
        record["value"] = dev["qps"]
        srv.close()
        print(f"[load] device route {dev['qps']:.0f} req/s "
              f"({dev['rows_per_sec']:.0f} rows/s, "
              f"{dev['speedup_vs_single_stream']}x single-stream) "
              f"p50={dev.get('p50_ms')}ms p99={dev.get('p99_ms')}ms "
              f"p999={dev.get('p999_ms')}ms", flush=True)

        # ---- native C-ABI route (OMP row-parallel reference analogue) -
        if not args.skip_native:
            record["native"] = native_route(bst, make_request, args)
            if "qps" in record["native"]:
                print(f"[load] native route {record['native']['qps']:.0f} "
                      f"req/s p99={record['native'].get('p99_ms')}ms",
                      flush=True)
        if errs and not lats:
            return finish("no_result", f"device route: {errs[0]}")
        return finish(status_for(dev["server"]))
    except Exception as e:  # noqa: BLE001 — classified into the grammar
        return finish(classify_status(e), repr(e))


def native_route(bst, make_request, args) -> dict:
    """Closed-loop clients over the native C ABI (ctypes releases the
    GIL during LGBM_BoosterPredictForMat, so N python threads exercise
    the ParallelRows pool concurrently)."""
    from lightgbm_tpu.native import get_lib
    lib = get_lib()
    if lib is None:
        return {"status": "unavailable", "note": "native library missing"}
    import numpy as np
    model_file = os.path.join(REPO, "bench_logs", "serving_load_model.txt")
    os.makedirs(os.path.dirname(model_file), exist_ok=True)
    bst.save_model(model_file)
    handle = ctypes.c_void_p()
    n_iters = ctypes.c_int()
    rc = lib.LGBM_BoosterCreateFromModelfile(
        model_file.encode(), ctypes.byref(n_iters), ctypes.byref(handle))
    if rc != 0:
        return {"status": "unavailable", "note": "model load failed"}
    local = threading.local()

    def do_request(X):
        if not hasattr(local, "buf"):
            local.buf = np.empty(args.rows, np.float64)
            local.out_len = ctypes.c_int64()
        Xf = np.ascontiguousarray(X, np.float32)
        r = lib.LGBM_BoosterPredictForMat(
            handle, Xf.ctypes.data_as(ctypes.c_void_p), 0,
            ctypes.c_int32(args.rows), ctypes.c_int32(X.shape[1]), 1,
            0, 0, -1, b"", ctypes.byref(local.out_len),
            local.buf.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
        if r != 0:
            raise RuntimeError("native predict failed")

    do_request(make_request(random.Random(0)))            # warm
    lats, n, wall, errs = run_clients(args.clients, args.duration,
                                      make_request, do_request)
    return route_record(lats, n, wall, args.rows, errs)


if __name__ == "__main__":
    sys.exit(main())
