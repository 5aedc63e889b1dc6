"""Concurrent model server over the packed-forest engine (ISSUE 8/9).

``ModelServer`` turns a Booster into a sustained-QPS serving tier:

- many client threads ``submit()`` requests; the dynamic micro-batcher
  (batcher.py) coalesces them into the serving engine's pow2/octave row
  buckets and ONE dispatcher thread drives the device — mixed request
  sizes cost zero new steady-state traces;
- the packed forest is replicated across a device mesh and each
  coalesced batch is sharded over it (mesh.py, naive sharding per
  SNIPPETS [2]) for multi-device throughput;
- ``publish()`` is the zero-downtime hot-swap: it freezes an immutable
  ``ForestSnapshot`` (ops/forest.py) of the booster's CURRENT model —
  incremental pack append riding the model-generation counter — and
  atomically swaps it in. In-flight batches keep the old snapshot; a
  response is attributable to exactly ONE generation, never a torn pack.

Failure path (ISSUE 9) — a tier facing real traffic is defined by its
failure behavior:

- **deadlines**: requests carry a deadline (``tpu_serving_deadline_ms``
  default); expired requests are dropped before coalescing and fail
  with ``DEADLINE_EXCEEDED``. ``predict(timeout=)`` rides the same
  machinery, so a timed-out predict's queue slot is reclaimed by the
  dispatcher, never served into the void.
- **admission control**: ``tpu_serving_max_queue_rows`` bounds the
  queue; past it ``submit()`` fails fast with ``OVERLOADED`` carrying
  the queue depth.
- **retry + graceful degradation**: transient dispatch failures
  (classified by the shared RetryPolicy — UNAVAILABLE, timeouts) are
  retried invisibly; once the policy's budget is exhausted the server
  flips to the HOST-WALK route (the same per-tree walk
  ``Booster.predict`` owns, bit-identical to it) with a loud one-time
  warning, keeps answering every request, and probes the device in the
  background (mesh.probe) to un-degrade. Non-transient errors still
  fail their batch loudly — a code bug must never masquerade as a
  flaky device.
- **publish rollback**: a failed ``publish()`` (injected
  ``publish_fail``, real OOM) leaves the live snapshot serving the OLD
  generation intact and the version counter untouched — rollback,
  never a torn pack.

Explanation serving (ISSUE 20): ``submit(kind="contrib")`` /
``explain()`` coalesce SHAP-contribution requests into their OWN
micro-batcher — a [rows, (F+1)*K] contribution output must never share
a dispatch with [rows, K] scores — riding the same deadline, admission,
retry-then-degrade and OOM-bisection machinery. The explanation
snapshot (packed path tensors, ops/shap_pack.py) is built lazily on the
first explain after a publish, so predict-only traffic never pays for
path packing; the degrade fallback is the host ``predict_contrib`` walk
(core/shap.py) — the bit-anchoring oracle the device kernel is
validated against.

The reference's serving analogue is an OMP row-parallel pointer walk per
process (src/application/predictor.hpp:31); this is the batch-coalescing
device-dispatch counterpart the TPU needs (per-request dispatch would be
bound by the host<->device round-trip).
"""
from __future__ import annotations

import os
import threading
from typing import NamedTuple, Optional

import numpy as np

from . import mesh as mesh_mod
from .batcher import MicroBatcher, PendingRequest
from .metrics import ServingCounters
from ..ops import forest, shap_pack
from ..robustness import faults, integrity
from ..robustness.retry import (RetryError, RetryPolicy, SERVING_POLICY,
                                is_oom_error, retry_call)
from ..utils import log


class Generation(NamedTuple):
    """Identity of one published model state: ``version`` is the
    monotonically increasing publish sequence, ``num_trees`` the window
    size it serves, ``model_gen`` the engine's destructive-mutation
    counter at publish time."""
    version: int
    num_trees: int
    model_gen: int


def host_walk_scores(models, k: int, X: np.ndarray) -> np.ndarray:
    """[R, K] f64 raw scores by the HOST per-tree walk — exactly
    ``Booster.predict``'s accumulation order, so degraded responses are
    bit-identical to the host route. ONE copy shared by the
    single-model and fleet servers (a drifted duplicate here is a
    drifted degraded-parity contract)."""
    raw = np.zeros((X.shape[0], max(int(k), 1)), np.float64)
    for i, t in enumerate(models):
        raw[:, i % max(int(k), 1)] += t.predict(X)
    return raw


class _FrozenModels(NamedTuple):
    """Just enough engine surface for ``core.shap.predict_contrib`` over
    a FROZEN published model list (the live engine keeps training while
    the snapshot's generation serves)."""
    models: tuple
    num_tree_per_iteration: int
    max_feature_idx: int


def host_contrib_scores(models, k: int, n_features: int,
                        X: np.ndarray) -> np.ndarray:
    """[R, (F+1)*K] f64 SHAP contributions by the HOST TreeSHAP walk
    (``core.shap.predict_contrib``, the exact-in-f64 recursion) — the
    explanation route's degrade oracle, bit-identical to
    ``Booster.predict(pred_contrib=True)`` on the same frozen trees.
    ONE copy shared by the single-model and fleet servers, for the same
    reason as ``host_walk_scores``."""
    from ..core.shap import predict_contrib
    kk = max(int(k), 1)
    eng = _FrozenModels(tuple(models), kk, int(n_features) - 1)
    return predict_contrib(eng, X, 0, len(models) // kk)


def finish_scores(raw: np.ndarray, k: int, n_trees: int,
                  average_output: bool, objective, raw_score: bool):
    """Shared output tail (average + objective conversion) mirroring
    ``Booster.predict`` exactly; [R, K] raw scores in, per-request
    values out (squeezed for k == 1)."""
    n_iters = n_trees // max(int(k), 1)
    if average_output and n_iters > 0:
        raw = raw / n_iters
    if not raw_score and objective is not None:
        if k > 1:
            raw = objective.convert_output(raw)
        else:
            raw = np.array(raw, copy=True)
            raw[:, 0] = np.asarray(objective.convert_output(raw[:, 0]))
    return raw if k > 1 else raw[:, 0]


class DegradeControl:
    """Retry-exhaustion degradation state shared by the single-model
    server and the fleet server (ISSUE 9/13): a sticky ``degraded``
    flag flipped on dispatch-budget exhaustion (or a forced drill),
    plus the background recovery loop that runs ``probe`` every
    ``probe_interval_s`` seconds and un-degrades on the first full
    success. ``probe`` must raise while the device is unhealthy; it is
    the caller's job to make it consult the injected fault sites so a
    planned outage keeps the tier degraded until the plan disarms."""

    def __init__(self, counters: ServingCounters, probe,
                 probe_interval_s: float, what: str = "serving"):
        self.counters = counters
        self._probe = probe
        self._interval = float(probe_interval_s)
        self._what = what
        self._evt = threading.Event()
        self._lock = threading.Lock()
        self._close_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.reason: Optional[str] = None

    @property
    def degraded(self) -> bool:
        return self._evt.is_set()

    def enter(self, reason: str) -> None:
        with self._lock:
            if self._evt.is_set():
                return
            self.reason = reason
            self._evt.set()
            self.counters.inc("degrade_events")
            log.warning(
                "=" * 60 + f"\n{self._what.upper()} DEGRADED: {reason}\n"
                "flipping to the host-walk route (bit-identical to "
                "Booster.predict, correct but slow); a background probe "
                "will restore device serving when the device answers "
                "again.\n" + "=" * 60)
            if self._interval > 0 and not self._close_evt.is_set():
                self._thread = threading.Thread(
                    target=self._probe_loop, daemon=True,
                    name=f"lgbm-{self._what}-probe")
                self._thread.start()

    def _probe_loop(self) -> None:
        while self._evt.is_set():
            if self._close_evt.wait(self._interval):
                return
            try:
                self._probe()
            except Exception as e:  # noqa: BLE001 — stay degraded
                log.debug(f"{self._what} recovery probe failed: {e!r}")
                continue
            with self._lock:
                self._evt.clear()
                self.reason = None
                self.counters.inc("recoveries")
                log.warning(f"{self._what} RECOVERED: device probe "
                            "succeeded — back on the device route")
            return

    def close(self) -> None:
        self._close_evt.set()
        t = self._thread
        if t is not None:
            t.join(1.0)


class ModelServer:
    """Micro-batching, mesh-replicated, hot-swappable model server.

    Knobs default from the booster's ``tpu_serving_*`` params
    (config.py) and are overridable per server:

    - ``max_batch``: coalesced-rows cap per dispatch
    - ``linger_ms``: max wait for peers since the oldest queued request
      (the p50-vs-throughput knob)
    - ``num_devices``: serving mesh width (0 = all visible devices;
      1 device -> no mesh, programs identical to the plain engine)
    - ``queue_depth``: enqueue backpressure bound (blocking)
    - ``deadline_ms``: default per-request deadline (0 = none)
    - ``max_queue_rows``: admission-control row bound (0 = unbounded)
    - ``retry_policy``: RetryPolicy for transient dispatch failures
      (default robustness.retry.SERVING_POLICY, LGBM_TPU_RETRY_* env
      overrides honored)
    - ``probe_interval_s``: degraded-mode device-probe cadence
      (0 = sticky degradation)
    - ``raw_score``: serve raw margins (default False: converted
      outputs, exactly ``Booster.predict``'s tail)

    Usage::

        with booster.serve(linger_ms=2.0) as srv:
            fut = srv.submit(X)            # async
            y = fut.result()
            y2 = srv.predict(X2)           # sync sugar
            booster.update(); srv.publish()  # hot-swap new trees
    """

    def __init__(self, booster, max_batch: Optional[int] = None,
                 linger_ms: Optional[float] = None,
                 num_devices: Optional[int] = None,
                 queue_depth: Optional[int] = None,
                 raw_score: bool = False,
                 bucket: Optional[bool] = None,
                 deadline_ms: Optional[float] = None,
                 max_queue_rows: Optional[int] = None,
                 retry_policy: Optional[RetryPolicy] = None,
                 probe_interval_s: Optional[float] = None):
        eng = booster._engine
        if eng is None:
            raise ValueError("cannot serve an unconstructed Booster")
        cfg = getattr(booster, "config", None)

        def knob(value, name, fallback):
            if value is not None:
                return value
            if cfg is not None and hasattr(cfg, name):
                return getattr(cfg, name)
            return fallback

        self._eng = eng
        self.raw_score = bool(raw_score)
        self.k = max(int(eng.num_tree_per_iteration), 1)
        bucket = bool(knob(bucket, "tpu_predict_buckets", True))
        # pack capacity: the CONFIG cap alone is wrong for models whose
        # trees exceed it (loaded models keep the default Config; an
        # init_model continuation can carry larger trees than the
        # current num_leaves) — packing such a tree at the config cap
        # is a hard crash, so take the max over both
        cap = int(getattr(getattr(eng, "config", None), "num_leaves", 0)
                  or 0)
        cap = max([cap, 2] + [int(t.num_leaves) for t in eng.models])
        # feature width served; validated per request at submit() so a
        # malformed request fails ITS submitter, not every request it
        # would have coalesced with
        self.n_features = int(getattr(eng, "max_feature_idx", 0)) + 1
        self._raw_route = eng.serving_state()[2] is None
        # the server owns its OWN engine: foreground predict_device
        # calls on the booster never contend with the dispatcher thread
        self._srv = forest.ServingEngine(cap, self.k, bucket=bucket)
        self.mesh = mesh_mod.serving_mesh(
            int(knob(num_devices, "tpu_serving_num_devices", 0)))
        self.deadline_ms = float(knob(deadline_ms,
                                      "tpu_serving_deadline_ms", 0.0))
        self._retry_policy = (
            retry_policy if retry_policy is not None else SERVING_POLICY
        ).from_env_overrides(os.environ)
        self._probe_interval = float(knob(
            probe_interval_s, "tpu_serving_probe_interval_s", 5.0))
        self.counters = ServingCounters()
        self._degrade = DegradeControl(
            self.counters, self._recovery_probe, self._probe_interval)
        self._closed = False
        self._publish_lock = threading.Lock()
        self._active = None  # (ForestSnapshot, Generation, models) — ONE ref
        self._version = 0
        # silent-corruption canary (ISSUE 19): armed by
        # tpu_integrity_probe_interval_s > 0. The golden is the
        # publish-time device replay of a fixed canary batch (the
        # device accumulates in f32, so the host f64 walk is the
        # ANCHOR — allclose at record time — not the bit-compare
        # reference); the background probe bit-compares later replays
        # against it, and a mismatch quarantines the server to the
        # host walk (solo quarantine == whole-server degrade; there is
        # only one route) until a repair re-publish probes clean.
        self._integrity_interval = float(knob(
            None, "tpu_integrity_probe_interval_s", 0.0))
        self._canary_rows = int(knob(None, "tpu_integrity_canary_rows",
                                     16))
        self._canary_X = integrity.canary_batch(self.n_features,
                                                rows=self._canary_rows)
        self._canary = None   # (golden [rows, K], version) — ONE ref
        self._integrity_quarantined = False
        # explanation route state (ISSUE 20), all set by publish():
        # the bin mappers frozen WITH the active generation, the lazy
        # SHAP snapshot cache (snapshot, version), and the device
        # eligibility verdict (None = explainable; else the reason the
        # host oracle serves instead)
        self._route_maps = (None, None)
        self._shap_snap = None
        self._explain_block: Optional[str] = None
        self.publish()
        self._iprobe = None
        if self._integrity_interval > 0:
            self._iprobe = integrity.IntegrityProbe(
                self._integrity_check, self._integrity_interval,
                what="serving")
        self._batcher = MicroBatcher(
            self._dispatch,
            max_batch=int(knob(max_batch, "tpu_serving_max_batch", 4096)),
            linger_ms=float(knob(linger_ms, "tpu_serving_linger_ms", 2.0)),
            queue_depth=int(knob(queue_depth, "tpu_serving_queue_depth",
                                 8192)),
            max_queue_rows=int(knob(max_queue_rows,
                                    "tpu_serving_max_queue_rows",
                                    1_048_576)),
            counters=self.counters)
        # explanation serving (ISSUE 20): contrib requests coalesce in
        # their OWN batcher — a [rows, (F+1)*K] output shape must never
        # share a dispatch with [rows, K] scores — GROUPED so the
        # explain ledger counts exact per-request fulfillment. The
        # smaller max_batch default reflects the SHAP kernel's
        # [leaves, depth, rows] working set (~40x a predict dispatch
        # per row at the bench shape).
        self.explain_deadline_ms = float(knob(
            None, "tpu_serving_explain_deadline_ms", 0.0))
        self._explain_refuse = str(knob(
            None, "tpu_serving_explain_fallback", "host")) == "refuse"
        self._explain_batcher = MicroBatcher(
            self._dispatch_explain,
            max_batch=int(knob(None, "tpu_serving_explain_max_batch",
                               1024)),
            linger_ms=float(knob(None, "tpu_serving_explain_linger_ms",
                                 2.0)),
            queue_depth=int(knob(queue_depth, "tpu_serving_queue_depth",
                                 8192)),
            max_queue_rows=int(knob(
                None, "tpu_serving_explain_max_queue_rows", 262_144)),
            counters=self.counters, grouped=True)

    # ---- hot-swap ----------------------------------------------------
    def publish(self) -> Generation:
        """Freeze the booster's CURRENT model into a new immutable
        snapshot and atomically make it the serving state.

        Rides the incremental pack: same model generation + more trees
        appends only the tail (a continual-training loop publishing
        every few iterations repacks nothing); a destructive mutation
        (rollback, DART drop, set_leaf_output) bumps the generation and
        triggers a full repack. In-flight batches finish on the snapshot
        they started with — zero downtime, never a torn pack.

        Failure contract (ISSUE 9): a publish that dies — the injected
        ``publish_fail`` site here or inside the pack append, a real
        OOM — leaves the live snapshot serving the OLD generation and
        the version counter untouched (the pack append itself commits
        transactionally, ops/forest.py), then re-raises. The caller
        retries when the booster state allows; generations stay
        monotonic with no gaps for failed attempts."""
        with self._publish_lock:
            models, gen, mappers, used_map = self._eng.serving_state()
            try:
                faults.maybe_fail("publish_fail")
                snap = self._srv.snapshot(
                    models, gen, 0, len(models), mappers, used_map,
                    place_window=lambda w: mesh_mod.replicate(w, self.mesh))
                golden = None
                if self._integrity_interval > 0:
                    # record the canary golden from THIS snapshot and
                    # anchor it against the host walk: a device replay
                    # outside f32-accumulation tolerance of the host
                    # truth means the pack corrupted at/under the
                    # upload itself — fail the publish (the old clean
                    # generation keeps serving) instead of recording a
                    # poisoned golden
                    golden = self._canary_replay(snap)
                    anchor = host_walk_scores(models, self.k,
                                              self._canary_X)
                    if not np.allclose(golden, anchor, rtol=1e-5,
                                       atol=1e-6):
                        self.counters.inc("integrity_mismatches")
                        raise integrity.CanaryMismatch(
                            "publish canary replay disagrees with the "
                            "host-walk anchor beyond f32 accumulation "
                            "tolerance — the freshly placed pack is "
                            "corrupt; refusing to publish it")
            except BaseException as e:  # noqa: BLE001 — rollback + re-raise
                self.counters.inc("publish_failures")
                if self._active is not None:
                    log.warning(
                        f"serving publish FAILED ({e!r}); still serving "
                        f"generation {self._active[1].version} — rolled "
                        "back, not torn")
                raise
            # in-residency rot injection (ISSUE 19): corrupt the PLACED
            # window AFTER the golden is recorded — modeling bits that
            # flip while the pack sits on the device, which is exactly
            # what the canary probe exists to catch. (Corruption at the
            # upload itself is the fleet's upload_window consult and
            # the anchor check above.)
            if faults.check("bitflip", where="dev"):
                import jax
                import jax.numpy as jnp
                corrupt = integrity.corrupt_pack(
                    jax.tree.map(np.asarray, snap.win))
                snap = snap._replace(win=mesh_mod.replicate(
                    jax.tree.map(jnp.asarray, corrupt), self.mesh))
                log.warning("injected bitflip: published device pack "
                            "corrupted (slot-0 leaf-output sign bits)")
            self._version += 1
            info = Generation(self._version, len(models), gen)
            if golden is not None:
                self._canary = (golden, self._version)  # GIL-atomic
            # the host model list rides along so the degraded host-walk
            # route serves the SAME frozen generation the snapshot does
            self._active = (snap, info, models)  # GIL-atomic ref swap
            # invalidate the lazy explanation snapshot (rebuilt on the
            # first explain of this generation — predict-only traffic
            # never pays for path packing) and refresh the device
            # eligibility verdict for the frozen model list
            self._route_maps = (mappers, used_map)
            prev_shap = self._shap_snap
            self._shap_snap = None
            try:
                shap_pack.check_explainable(models)
                self._explain_block = None
            except ValueError as e:
                self._explain_block = str(e)
            else:
                if prev_shap is not None:
                    # explain traffic is live: pay the path-pack append
                    # HERE, at publish, so the first post-swap explain
                    # stays on the compiled kernel (the pow2-padded
                    # window keeps its shape inside the slot cap). Best
                    # effort — a failure falls back to the lazy rebuild,
                    # never fails an already-committed publish.
                    try:
                        snap2 = self._srv.snapshot_shap(
                            models, gen, 0, len(models), self.n_features,
                            mappers, used_map,
                            place_window=lambda w: mesh_mod.replicate(
                                w, self.mesh))
                        self._shap_snap = (snap2, self._version)
                    except BaseException as e:  # noqa: BLE001
                        log.warning(
                            "publish-time explanation snapshot rebuild "
                            f"failed ({e!r}); deferring to the lazy "
                            "first-explain rebuild")
            return info

    @property
    def generation(self) -> Generation:
        return self._active[1]

    # ---- request path ------------------------------------------------
    def _device_scores(self, snap, X: np.ndarray) -> np.ndarray:
        """One device attempt at scoring a batch: [R, K] f64 raw scores.
        Fault sites sit BEFORE the real dispatch (a fired fault means
        the device never saw this attempt); every retry re-consults."""
        faults.maybe_delay("slow_dispatch")
        faults.maybe_fail("dispatch_error")
        faults.maybe_fail("oom")
        place = None
        if self.mesh is not None:
            place = lambda a, ax: mesh_mod.shard_rows(a, ax, self.mesh)  # noqa: E731
        out = mesh_mod.locked_launch(
            self.mesh, forest.snapshot_scores, snap, X,
            place=place)                                     # [K, R]
        return out.T                                         # [R, K]

    def _host_scores(self, models, X: np.ndarray) -> np.ndarray:
        return host_walk_scores(models, self.k, X)

    def _adaptive_scores(self, snap, models, X: np.ndarray) -> np.ndarray:
        """Device scoring with the OOM bisection ladder (ISSUE 17).

        Transient failures retry under the serving policy as before. An
        OOM-classified failure is NOT retried (the identical allocation
        cannot succeed) — instead the batch is split in half and each
        half retried: halves of a coalesced batch land back in the same
        pow2/octave bucket family, so in steady state bisection costs
        zero new traces. Rows that still OOM at the minimum bucket size
        are served by the host walk — a per-request degrade for ONLY
        the failing rows; the server never flips to whole-server
        degradation for a size-induced OOM. Raises RetryError upward
        (transient exhaustion keeps today's degrade path) and
        non-transient non-OOM errors untouched."""
        try:
            return retry_call(
                self._device_scores, snap, X,
                policy=self._retry_policy, what="serving dispatch",
                on_retry=lambda _a, _e:
                    self.counters.inc("dispatch_retries"))
        except RetryError:
            raise
        except BaseException as e:  # noqa: BLE001 — classifier decides
            if not is_oom_error(e):
                raise
            n = int(X.shape[0])
            if n > forest.ROW_BUCKET_MIN:
                self.counters.inc("oom_bisects")
                mid = n // 2
                log.warning(
                    f"serving dispatch OOM at {n} rows ({e!r}); "
                    f"bisecting into {mid}+{n - mid} and retrying")
                return np.concatenate(
                    [self._adaptive_scores(snap, models, X[:mid]),
                     self._adaptive_scores(snap, models, X[mid:])],
                    axis=0)
            if not getattr(self, "_oom_floor_warned", False):
                self._oom_floor_warned = True
                log.warning(
                    f"serving dispatch OOM at the {n}-row bisection "
                    f"floor ({e!r}); host-walking ONLY these rows — "
                    "peers in the coalesced batch stay on the device "
                    "(warned once per server)")
            return self._host_scores(models, X)

    def _finish(self, raw: np.ndarray, info: Generation):
        """Output tail for both routes (module-level ``finish_scores``,
        shared with the fleet server)."""
        vals = finish_scores(
            raw, self.k, info.num_trees,
            bool(getattr(self._eng, "average_output", False)),
            getattr(self._eng, "objective", None), self.raw_score)
        return vals, info

    def _dispatch(self, X: np.ndarray):
        """Score ONE coalesced batch against exactly one snapshot.
        Runs on the dispatcher thread only. Transient device failures
        retry under the serving policy; budget exhaustion degrades to
        the host walk and STILL answers this batch; OOM-classified
        failures bisect the batch instead (``_adaptive_scores``) —
        non-transient non-OOM errors propagate and fail the batch (a
        code bug must never be absorbed as a flaky device)."""
        snap, info, models = self._active  # single read: atomic pairing
        if self._degrade.degraded:
            self.counters.inc("degraded_batches")
            return self._finish(self._host_scores(models, X), info)
        try:
            raw = self._adaptive_scores(snap, models, X)
        except RetryError as e:
            self.counters.inc("dispatch_failures")
            self._degrade.enter(
                f"dispatch retry budget exhausted: {e.last!r}")
            self.counters.inc("degraded_batches")
            return self._finish(self._host_scores(models, X), info)
        return self._finish(raw, info)

    # ---- explanation route (ISSUE 20) -------------------------------
    def _shap_snapshot(self, info: Generation, models):
        """The explanation snapshot paired with generation ``info`` —
        built lazily on the FIRST explain after a publish (predict-only
        traffic never pays for SHAP path packing) under the publish
        lock (the path-pack sync must not race a publish's engine
        read), then cached until the next publish invalidates it."""
        cached = self._shap_snap
        if cached is not None and cached[1] == info.version:
            return cached[0]
        with self._publish_lock:
            cached = self._shap_snap
            if cached is not None and cached[1] == info.version:
                return cached[0]
            mappers, used_map = self._route_maps
            snap = self._srv.snapshot_shap(
                models, info.model_gen, 0, info.num_trees,
                self.n_features, mappers, used_map,
                place_window=lambda w: mesh_mod.replicate(w, self.mesh))
            self._shap_snap = (snap, info.version)  # GIL-atomic
            return snap

    def _device_contrib(self, snap, X: np.ndarray) -> np.ndarray:
        """One device attempt at explaining a batch: [R, (F+1)*K] f64
        contributions. Consults the SAME fault sites as
        ``_device_scores`` — an injected outage or OOM plan must bite
        the explain route identically."""
        faults.maybe_delay("slow_dispatch")
        faults.maybe_fail("dispatch_error")
        faults.maybe_fail("oom")
        place = None
        if self.mesh is not None:
            place = lambda a, ax: mesh_mod.shard_rows(a, ax, self.mesh)  # noqa: E731
        return mesh_mod.locked_launch(
            self.mesh, shap_pack.shap_snapshot_scores, snap, X, place)

    def _host_contrib(self, models, X: np.ndarray) -> np.ndarray:
        return host_contrib_scores(models, self.k, self.n_features, X)

    def _adaptive_contrib(self, snap, models, X: np.ndarray) -> np.ndarray:
        """Device explanation with the OOM bisection ladder — the
        explain analogue of ``_adaptive_scores`` (halves rejoin the
        same pow2/octave row-bucket family, so steady-state bisection
        costs zero new traces); rows that still OOM at the floor are
        served by the host ``predict_contrib`` oracle."""
        try:
            return retry_call(
                self._device_contrib, snap, X,
                policy=self._retry_policy, what="explain dispatch",
                on_retry=lambda _a, _e:
                    self.counters.inc("dispatch_retries"))
        except RetryError:
            raise
        except BaseException as e:  # noqa: BLE001 — classifier decides
            if not is_oom_error(e):
                raise
            n = int(X.shape[0])
            if n > forest.ROW_BUCKET_MIN:
                self.counters.inc("oom_bisects")
                mid = n // 2
                log.warning(
                    f"explain dispatch OOM at {n} rows ({e!r}); "
                    f"bisecting into {mid}+{n - mid} and retrying")
                return np.concatenate(
                    [self._adaptive_contrib(snap, models, X[:mid]),
                     self._adaptive_contrib(snap, models, X[mid:])],
                    axis=0)
            if self._explain_refuse:
                raise
            log.warning(
                f"explain dispatch OOM at the {n}-row bisection floor "
                f"({e!r}); host-walking ONLY these rows")
            return self._host_contrib(models, X)

    def _explain_scores(self, info: Generation, models, X: np.ndarray):
        """([R, (F+1)*K] f64 contributions, served_by_host_oracle) for
        one coalesced explain batch. Device route unless the model is
        ineligible (linear trees / categorical splits — outside the
        packed path tensors), the server is degraded or quarantined, or
        the retry budget exhausts; the fallback is the host
        ``predict_contrib`` oracle, or a loud refusal when
        ``tpu_serving_explain_fallback="refuse"``."""
        if self._explain_block is not None:
            if self._explain_refuse:
                raise RuntimeError(
                    "explanation serving unavailable "
                    f"(fallback='refuse'): {self._explain_block}")
            log.info_once(
                "explanation serving: model is not device-explainable "
                f"({self._explain_block}); serving the host "
                "predict_contrib walk instead")
            return self._host_contrib(models, X), True
        if self._degrade.degraded:
            if self._explain_refuse:
                raise RuntimeError(
                    "explanation serving unavailable "
                    f"(fallback='refuse'): server degraded: "
                    f"{self._degrade.reason}")
            return self._host_contrib(models, X), True
        try:
            snap = self._shap_snapshot(info, models)
            return self._adaptive_contrib(snap, models, X), False
        except RetryError as e:
            self.counters.inc("dispatch_failures")
            self._degrade.enter(
                f"explain dispatch retry budget exhausted: {e.last!r}")
            if self._explain_refuse:
                raise RuntimeError(
                    "explanation serving unavailable "
                    f"(fallback='refuse'): {e.last!r}") from e
            return self._host_contrib(models, X), True

    def _dispatch_explain(self, batch):
        """Explain ONE coalesced contrib batch against exactly one
        snapshot (grouped mode: one outcome per request, exact
        ``explain_requests``/``explain_degraded`` accounting). Same
        snapshot-pairing, retry, OOM-bisection and degrade discipline
        as ``_dispatch``, but the fallback truth is the host
        ``predict_contrib`` oracle."""
        _snap, info, models = self._active  # single read: atomic pairing
        X = batch[0].X if len(batch) == 1 else \
            np.concatenate([r.X for r in batch], axis=0)
        try:
            contrib, by_host = self._explain_scores(info, models, X)
        except BaseException as e:  # noqa: BLE001 — settle per request
            return [e] * len(batch)
        self.counters.inc("explain_requests", len(batch))
        if by_host:
            self.counters.inc("explain_degraded", len(batch))
        out, off = [], 0
        for r in batch:
            out.append((contrib[off:off + r.n], info))
            off += r.n
        return out

    # ---- integrity (ISSUE 19) ---------------------------------------
    def _canary_replay(self, snap) -> np.ndarray:
        """[rows, K] device scores of the fixed canary batch against
        ``snap`` — NO fault-site consults (the canary detects wrong
        bits; availability faults belong to the retry/degrade path,
        and a probe must never burn a counted fault plan armed for
        client traffic). Rides the same row buckets as steady-state
        traffic: zero new traces."""
        place = None
        if self.mesh is not None:
            place = lambda a, ax: mesh_mod.shard_rows(a, ax, self.mesh)  # noqa: E731
        return mesh_mod.locked_launch(
            self.mesh, forest.snapshot_scores, snap, self._canary_X,
            place=place).T

    def _integrity_check(self) -> None:
        """One canary probe cycle: replay against the live snapshot and
        bit-compare with the publish-time golden. A mismatch means the
        resident pack's bits CHANGED since publish — quarantine the
        server to the bit-identical host walk (solo quarantine ==
        degrade: there is only one route) and repair by re-publishing,
        which re-places the pack from the engine's clean host state and
        re-records the golden; the recovery probe un-quarantines only
        after the repaired pack replays bit-clean."""
        if self._closed or self._degrade.degraded:
            return
        active, canary = self._active, self._canary
        if active is None or canary is None:
            return
        snap, info, _models = active
        golden, version = canary
        if info.version != version:
            return     # raced a publish; next cycle sees the new golden
        self.counters.inc("integrity_probes")
        try:
            got = self._canary_replay(snap)
        except Exception as e:  # noqa: BLE001 — availability, not bits
            log.debug(f"integrity probe replay failed: {e!r}")
            return
        if integrity.parity_equal(got, golden):
            return
        self.counters.inc("integrity_mismatches")
        self.counters.inc("quarantines")
        self._integrity_quarantined = True
        self._degrade.enter(
            f"canary parity mismatch on generation {info.version}: the "
            "resident device pack no longer replays the publish-time "
            "golden bits — silent corruption; serving the host walk "
            "while the pack is re-published")
        try:
            self.publish()       # repair: re-place from host truth
            log.warning("integrity repair: pack re-published after the "
                        "canary mismatch; the recovery probe will "
                        "un-quarantine on clean parity")
        except Exception as e:  # noqa: BLE001 — stay quarantined
            log.warning(f"integrity repair publish failed ({e!r}); "
                        "still quarantined on the host walk")

    # ---- degradation -------------------------------------------------
    def degrade(self, reason: str = "forced") -> None:
        """Flip to the host-walk route now (chaos drills, operator
        override). The background probe un-degrades as usual."""
        self._degrade.enter(reason)

    def _recovery_probe(self) -> None:
        """One recovery attempt: every serving-mesh device must answer.
        Consults the ``dispatch_error`` fault site so an injected
        persistent outage keeps the server degraded until the plan
        disarms. With the integrity canary armed, un-degrading ALSO
        requires the live snapshot to replay the golden bit-for-bit —
        a quarantined server must never return to a still-corrupt
        device route."""
        faults.maybe_fail("dispatch_error")
        mesh_mod.probe(self.mesh)
        if self._integrity_interval <= 0:
            return
        active, canary = self._active, self._canary
        if active is None or canary is None or \
                active[1].version != canary[1]:
            return
        if not integrity.parity_equal(self._canary_replay(active[0]),
                                      canary[0]):
            raise integrity.CanaryMismatch(
                "recovery probe: the device canary replay still "
                "differs bit-wise from the golden — staying on the "
                "host walk")
        if self._integrity_quarantined:
            self._integrity_quarantined = False
            self.counters.inc("repairs")

    def submit(self, X, deadline_ms: Optional[float] = None,
               kind: str = "score") -> PendingRequest:
        """Enqueue one [rows, features] request; returns a handle whose
        ``result()`` blocks and whose ``generation`` names the snapshot
        that served it. ``deadline_ms`` (default
        ``tpu_serving_deadline_ms``; 0/None = none) bounds how long the
        request may wait: past it the dispatcher drops it BEFORE
        coalescing and ``result()`` raises ``DeadlineExceeded``. A full
        queue (``max_queue_rows``) raises ``Overloaded`` here instead
        of accepting work the server cannot serve.

        ``kind="contrib"`` (ISSUE 20) requests SHAP contributions
        ([rows, (F+1)*K], reference ``pred_contrib`` layout) instead of
        scores; it rides the explain batcher — its own coalescing,
        linger and admission knobs (``tpu_serving_explain_*``), default
        deadline ``tpu_serving_explain_deadline_ms`` — so explanation
        traffic never perturbs a predict dispatch's shape.

        Per-request validation happens HERE (shape, and the raw route's
        f32-representability contract) so one malformed request raises
        to its own submitter instead of failing the whole coalesced
        batch it would have joined."""
        if kind not in ("score", "contrib"):
            raise ValueError(f"unknown request kind {kind!r} "
                             "(expected 'score' or 'contrib')")
        X = np.ascontiguousarray(np.asarray(X, np.float64))
        if X.ndim != 2 or X.shape[1] != self.n_features:
            raise ValueError(
                f"request must be [rows, {self.n_features}] "
                f"(got {X.shape})")
        if self._raw_route and X.shape[0]:
            with np.errstate(invalid="ignore"):
                f32_ok = (X.astype(np.float32).astype(np.float64) == X) \
                    | np.isnan(X)
            if not f32_ok.all():
                raise ValueError(
                    "raw device serving needs float32-representable "
                    f"requests ({int((~f32_ok).sum())} value(s) are "
                    "f64-only and could cross a split threshold under "
                    "f32 rounding)")
        if kind == "contrib":
            dl = self.explain_deadline_ms if deadline_ms is None \
                else float(deadline_ms)
            return self._explain_batcher.submit(
                X, deadline_sec=(dl / 1e3 if dl and dl > 0 else None),
                kind="contrib")
        dl = self.deadline_ms if deadline_ms is None else float(deadline_ms)
        return self._batcher.submit(
            X, deadline_sec=(dl / 1e3 if dl and dl > 0 else None))

    def predict(self, X, timeout: Optional[float] = None) -> np.ndarray:
        """Sync sugar: submit + result. ``timeout`` rides the deadline
        machinery — the request itself carries the deadline, so a
        timed-out predict cannot leak its queue slot: the dispatcher
        drops the expired request before coalescing and the slot is
        reclaimed (pre-ISSUE 9, the abandoned request was still served
        into the void and held its slot the whole time)."""
        dl_ms = None if timeout is None else timeout * 1e3
        return self.submit(X, deadline_ms=dl_ms).result(timeout)

    def explain(self, X, timeout: Optional[float] = None) -> np.ndarray:
        """Sync sugar for the explanation route (ISSUE 20): SHAP
        contributions [rows, (num_features + 1) * K] in the reference
        ``pred_contrib`` layout (per-class blocks of F+1, bias last),
        served by the packed-path device kernel with the host
        ``predict_contrib`` walk as the degrade oracle. Additivity
        holds per row: contributions + bias sum to the raw score."""
        dl_ms = None if timeout is None else timeout * 1e3
        return self.submit(X, deadline_ms=dl_ms,
                           kind="contrib").result(timeout)

    # ---- lifecycle / observability ----------------------------------
    def stats(self) -> dict:
        s = self._batcher.stats()
        s["generation"] = self.generation.version
        s["num_trees"] = self.generation.num_trees
        s["mesh_devices"] = (self.mesh.shape[mesh_mod.SERVE_AXIS]
                             if self.mesh is not None else 1)
        s["linger_ms"] = self._batcher.linger_sec * 1e3
        s["max_batch"] = self._batcher.max_batch
        s["deadline_ms"] = self.deadline_ms
        s["degraded"] = self._degrade.degraded
        if s["degraded"] and self._degrade.reason is not None:
            s["degraded_reason"] = self._degrade.reason
        if self._integrity_interval > 0:
            s["integrity_probe_interval_s"] = self._integrity_interval
            if self._integrity_quarantined:
                s["integrity_quarantined"] = True
        eb = self._explain_batcher
        s["explain"] = {"requests": eb.n_requests, "rows": eb.n_rows,
                        "batches": eb.n_batches,
                        "max_coalesced": eb.max_coalesced,
                        **eb.latency.summary_ms()}
        return s

    @property
    def closed(self) -> bool:
        """True once ``close()`` ran — a closed server never serves
        again; ``Booster.serve()`` uses this to decide whether a prior
        server is still live (ISSUE 13 satellite)."""
        return self._closed

    def close(self, timeout: Optional[float] = 30.0) -> None:
        """Stop accepting requests; every already-accepted request is
        still served before the dispatcher exits (drain-on-shutdown).
        Past ``timeout`` the drain contract fails still-pending futures
        with SHUTDOWN instead of abandoning them (batcher.close)."""
        self._closed = True
        if self._iprobe is not None:
            self._iprobe.close()    # before the drain: no probe replay
        self._degrade.close()       # before the drain: no new probe
        self._explain_batcher.close(timeout)
        self._batcher.close(timeout)

    def __enter__(self) -> "ModelServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
