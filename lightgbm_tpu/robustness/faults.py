"""Fault-injection harness: deterministic transient failures on demand.

Mirrors the ``LGBM_TPU_GUARDS`` install pattern (analysis/guards.py):
``LGBM_TPU_FAULTS`` is read once at package import (install_from_env in
lightgbm_tpu/__init__.py), so ANY process — bench, CLI, tests, worker
subprocesses — can be run under injected faults without code changes;
:func:`inject` is the scoped context-manager equivalent for tests.

Grammar (comma-separated fault specs, colon-separated options)::

    LGBM_TPU_FAULTS="collective:p=0.2,probe_timeout,write_kill"
    LGBM_TPU_FAULTS="collective:p=0.2:seed=7,write_kill:n=1:after=3"

Fault classes (the ``site`` argument of :func:`maybe_fail`):

- ``collective``  — the injected-collective host callables
  (distributed.make_injected_hooks) raise :class:`FaultInjected`
  (classified transient: its message carries ``UNAVAILABLE``).
- ``probe_timeout`` — device probes (robustness.retry.probe_device)
  raise a transient failure, simulating a device runtime that is
  cycling through recovery.
- ``write_kill`` — checkpoint writes die MID-WRITE (after the payload
  is partially written, before the atomic rename), simulating a kill
  -9 during snapshotting; raises :class:`WriteKilled`.
- ``hang`` — the heartbeat writer (robustness/heartbeat.py) stops
  writing from the moment the fault fires: the child keeps running but
  its liveness file goes silent mid-phase, which is exactly what a
  wedged runtime looks like to a supervisor. Consulted via
  :func:`check` (non-raising) inside ``Heartbeat.beat``.
- ``slow_compile`` — stretches the ``compiling`` phase by ``sec``
  seconds (default 30) while keepalives keep flowing: a benign slow
  remote compile, the case phase-aware supervision must NOT park.
  Consulted via :func:`maybe_delay` at compile-phase entry.
- ``dispatch_error`` — the serving dispatcher's device scoring
  (serving/server.py ``_device_scores``) raises a transient
  :class:`FaultInjected` BEFORE the real dispatch; each retry under the
  serving RetryPolicy re-consults the fault, and the degraded server's
  background recovery probe consults it too (so a persistent plan keeps
  the server degraded until the plan disarms).
- ``slow_dispatch`` — stretches ONE serving dispatch by ``sec`` seconds
  (default 30) via :func:`maybe_delay`: the wedged-device shape that
  request deadlines must convert into ``DEADLINE_EXCEEDED`` failures
  for the requests queued behind it, never an unbounded stall.
- ``publish_fail`` — the serving hot-swap dies: consulted in
  ``ModelServer.publish()`` (before the snapshot is built — call 1) and
  again inside the incremental pack append (ops/forest.py
  ``_IncrementalPack._append``, pre-commit — call 2), so both the
  server-level rollback and the pack's no-torn-state commit are
  exercised; a bare spec fires at the server site, ``after=1`` reaches
  the append site.
- ``rank_kill`` — one gang rank hard-exits (``os._exit`` with
  :data:`EXIT_RANK_KILLED` — no cleanup, no flush: a real kill -9
  shape) at an iteration boundary. Consulted via
  :func:`maybe_kill_rank` at the top of the gbdt training iteration;
  the ``rank=R`` option selects which rank dies (default: any rank
  that consults) and ``after=N`` skips that rank's first N iterations,
  so a chaos harness can kill rank R after exactly N iterations. The
  survivors' recovery (collective deadline + gang supervisor SIGTERM +
  relaunch-from-manifest) is the ISSUE 10 chaos gate
  (scripts/gang_chaos_smoke.py).
- ``collective_delay`` — stretches ONE injected-collective /
  allgather attempt by ``sec`` seconds via :func:`maybe_delay`, INSIDE
  the collective liveness deadline (distributed.call_with_deadline):
  the blocked-dead-peer shape that must surface as
  ``CollectiveTimeout`` (DEADLINE_EXCEEDED) instead of wedging the
  rank to the whole-gang timeout.
- ``oom`` — an allocation fails: raises :class:`OOMInjected`, whose
  message carries ``RESOURCE_EXHAUSTED`` so the retry classifier files
  it as non-transient (retrying the same allocation is futile — the
  caller must adapt, ISSUE 17). One site name, three consult points
  selected with ``p=``/``after=`` exactly like ``publish_fail``: the
  serving dispatch (serving/server.py ``_device_scores`` and
  serving/fleet.py ``_bucket_scores`` — the bisection ladder), the
  fleet pack upload (ops/forest.py ``upload_window`` — publish-forced
  eviction), and the trainer re-bin (service/trainer.py — window
  auto-shrink).
- ``bitflip`` — silent data corruption (ISSUE 19): wrong bits appear
  where correct bits were written, via :func:`check` at four
  site-targeted consult points selected with the ``where=`` option:
  ``where=dev`` corrupts a freshly uploaded device pack
  (ops/forest.py ``upload_window`` and the solo server's published
  snapshot — sign bits of the slot-0 tree's leaf outputs, guaranteed
  canary-observable), ``where=host`` corrupts the retained HOST
  window copy (serving/fleet.py ``_build_bucket`` — caught by the CRC
  fingerprint before any re-upload), ``where=ckpt`` flips one byte of
  a committed checkpoint file (robustness/checkpoint.py — caught by
  the CRC32 footer on read, so recovery anchors on the previous valid
  generation), ``where=digest`` lies about one rank's committed-tree
  digest (models/gbdt.py ``_gang_digest_check`` — the gang agreement
  sync must refuse the iteration on every rank). Without ``where=``
  the first consulted point fires.
- ``nan_grad`` — one boosting iteration's gradients are poisoned to
  NaN after the objective computes them (models/gbdt.py sync path,
  via :func:`check`): the numeric-health guard must fail the
  iteration as ``DATA_CORRUPTION`` and the continual trainer must
  roll back to the newest CRC-valid checkpoint instead of committing
  or publishing the poisoned model.
- ``loss_spike`` — the numeric-health guard's loss observation is
  inflated past its spike threshold (robustness/integrity.py
  ``NumericHealthGuard.observe_loss`` via :func:`check`): the
  finite-but-wrong corruption signature, distinct from NaN.
- ``disk_full`` — the atomic checkpoint writer's payload write raises
  ``ENOSPC`` (robustness/checkpoint.py ``atomic_write_text``): the
  publish channel's disk filled mid-write. ``write_checkpoint``
  answers by pruning beyond ``keep_last`` and retrying ONCE — the
  continual service survives one full-disk episode without losing its
  newest committed generation.

Options per spec:

- ``p=<float>``  — failure probability per call (default 1.0).
- ``n=<int>``    — at most this many injected failures, then the fault
  disarms (default: unlimited for p<1, 1 for p=1 — a bare
  ``write_kill`` kills exactly one write).
- ``after=<int>`` — skip this many calls before arming (lets a test
  kill the k-th checkpoint write precisely).
- ``seed=<int>`` — per-fault RNG seed (default 0): injections are
  deterministic and reproducible across runs and threads.
- ``sec=<float>`` — duration for delay-style faults (``slow_compile``,
  ``slow_dispatch`` and ``collective_delay``; default 30.0).
- ``rank=<int>`` — gang rank filter (``rank_kill``): only the matching
  rank's consults count or fire (default: every rank).
- ``where=<name>`` — consult-point filter (``bitflip``): only consults
  passing a matching ``where=`` count or fire (``dev`` / ``host`` /
  ``ckpt``); without it the first consulted point fires. The same
  targeting idea as ``rank=``, for corruption sites.

Counters are PER-PROCESS: an env-installed plan re-arms in every
subprocess (each child re-runs install_from_env with fresh counters).
For flows that spawn one process per attempt — the bench probe — a
count-limited spec like ``probe_timeout:n=2`` therefore fails EVERY
child, which deterministically exercises the retry-exhaustion leg
(rc=4); to exercise the retry-then-recover leg use ``p=<1`` (each
child flips its own coin) or in-process injection (``inject(...)``
around ``robustness.retry.probe_device``, as
tests/test_robustness.py::test_probe_retries_then_succeeds does).
"""
from __future__ import annotations

import os
import random
import threading
from typing import Dict, List, Optional

from ..utils import log

ENV_FAULTS = "LGBM_TPU_FAULTS"

KNOWN_SITES = ("collective", "probe_timeout", "write_kill", "hang",
               "slow_compile", "dispatch_error", "slow_dispatch",
               "publish_fail", "rank_kill", "collective_delay", "oom",
               "bitflip", "nan_grad", "loss_spike", "disk_full")

# exit code of an injected rank_kill: the gang supervisor annotates it
# in the per-rank diagnosis (distinct from EXIT_STALLED=86 so forensics
# can tell an injected death from a self-watchdogged wedge)
EXIT_RANK_KILLED = 87


class FaultInjected(Exception):
    """An injected TRANSIENT failure (message carries UNAVAILABLE so the
    retry classifier treats it exactly like the real device symptom)."""


class WriteKilled(FaultInjected):
    """An injected mid-write kill: the write never completed; whatever
    bytes hit the disk are garbage that recovery must survive."""


class OOMInjected(FaultInjected):
    """An injected allocation failure — the NON-transient member of the
    family: its message carries ``RESOURCE_EXHAUSTED`` so the retry
    classifier refuses to burn budget on it and the call site must
    adapt (bisect / evict / shrink) instead."""


class _Fault:
    def __init__(self, site: str, p: float = 1.0,
                 n: Optional[int] = None, after: int = 0,
                 seed: int = 0, sec: float = 30.0,
                 rank: Optional[int] = None,
                 where: Optional[str] = None):
        self.site = site
        self.p = float(p)
        self.sec = float(sec)
        self.rank = int(rank) if rank is not None else None
        self.where = str(where) if where is not None else None
        # a bare always-on fault (p=1, no n) fires once then disarms:
        # "kill the write" means one kill, not an unrecoverable loop
        self.n = n if n is not None else (1 if self.p >= 1.0 else None)
        self.after = int(after)
        self.calls = 0
        self.fired = 0
        self.rng = random.Random(seed)
        self.lock = threading.Lock()

    def should_fire(self) -> bool:
        with self.lock:
            self.calls += 1
            if self.calls <= self.after:
                return False
            if self.n is not None and self.fired >= self.n:
                return False
            if self.rng.random() >= self.p:
                return False
            self.fired += 1
            return True

    def __repr__(self):
        return (f"_Fault({self.site}, p={self.p}, n={self.n}, "
                f"after={self.after}, fired={self.fired}/"
                f"calls={self.calls})")


class FaultPlan:
    """Parsed set of active faults, keyed by site."""

    def __init__(self, faults: Dict[str, _Fault]):
        self.faults = faults

    @classmethod
    def parse(cls, spec: str) -> "FaultPlan":
        faults: Dict[str, _Fault] = {}
        for entry in spec.split(","):
            entry = entry.strip()
            if not entry:
                continue
            parts = entry.split(":")
            site = parts[0].strip()
            if site not in KNOWN_SITES:
                raise ValueError(
                    f"unknown fault class {site!r}; expected one of "
                    f"{KNOWN_SITES}")
            kw = {}
            for opt in parts[1:]:
                if "=" not in opt:
                    raise ValueError(
                        f"malformed fault option {opt!r} in {entry!r} "
                        "(expected key=value)")
                k, _, v = opt.partition("=")
                k = k.strip()
                if k == "p":
                    kw["p"] = float(v)
                elif k == "n":
                    kw["n"] = int(v)
                elif k == "after":
                    kw["after"] = int(v)
                elif k == "seed":
                    kw["seed"] = int(v)
                elif k == "sec":
                    kw["sec"] = float(v)
                elif k == "rank":
                    kw["rank"] = int(v)
                elif k == "where":
                    kw["where"] = v.strip()
                else:
                    raise ValueError(
                        f"unknown fault option {k!r} in {entry!r}")
            if site in faults:
                raise ValueError(f"duplicate fault class {site!r}")
            faults[site] = _Fault(site, **kw)
        return cls(faults)

    def __repr__(self):
        return f"FaultPlan({list(self.faults.values())})"


_active: Optional[FaultPlan] = None


def active_plan() -> Optional[FaultPlan]:
    return _active


def maybe_fail(site: str) -> None:
    """Raise the configured injected failure for ``site`` (no-op when no
    plan is installed or the site's fault doesn't fire this call).

    Call sites sit immediately BEFORE the real operation, so a fired
    fault means the operation did not run this attempt — exactly the
    semantics of a request lost to a flaky device."""
    plan = _active
    if plan is None:
        return
    f = plan.faults.get(site)
    if f is None or not f.should_fire():
        return
    if site == "write_kill":
        raise WriteKilled(
            f"injected mid-write kill (write #{f.calls})")
    if site == "disk_full":
        # the REAL exception shape (OSError/ENOSPC), not a FaultInjected
        # wrapper: the writer's recovery path must classify by errno,
        # exactly as it would for a genuinely full disk
        import errno
        raise OSError(errno.ENOSPC,
                      f"injected disk_full fault (write #{f.calls}, "
                      f"injection #{f.fired})")
    if site == "oom":
        raise OOMInjected(
            f"RESOURCE_EXHAUSTED: injected oom fault "
            f"(call #{f.calls}, injection #{f.fired})")
    raise FaultInjected(
        f"UNAVAILABLE: injected {site} fault "
        f"(call #{f.calls}, injection #{f.fired})")


def check(site: str, where: Optional[str] = None) -> bool:
    """Non-raising consult: True when ``site``'s fault fires this call.

    For fault kinds whose effect is behavioral rather than an exception
    (``hang`` suppresses heartbeat writes, ``bitflip`` corrupts bytes)
    the call site decides what "failing" means; counters/probability/
    arming work exactly like :func:`maybe_fail`. ``where`` names the
    consult point for site-targeted faults: a fault armed with
    ``where=X`` only counts or fires at consults passing ``where="X"``
    (consults elsewhere don't burn ``after=`` budget, mirroring the
    ``rank=`` filter)."""
    plan = _active
    if plan is None:
        return False
    f = plan.faults.get(site)
    if f is None:
        return False
    if f.where is not None and where != f.where:
        return False
    return f.should_fire()


def maybe_delay(site: str, sleep=None) -> float:
    """Delay-style injection: sleep the fault's ``sec`` when it fires
    and return the seconds slept (0.0 otherwise). Used by
    ``slow_compile`` to stretch the compiling phase without touching
    liveness."""
    plan = _active
    if plan is None:
        return 0.0
    f = plan.faults.get(site)
    if f is None or not f.should_fire():
        return 0.0
    log.warning(f"injected {site} delay: sleeping {f.sec:.1f}s "
                f"(call #{f.calls}, injection #{f.fired})")
    import time
    (sleep if sleep is not None else time.sleep)(f.sec)
    return f.sec


def maybe_kill_rank(rank: int, _exit=os._exit) -> None:
    """``rank_kill`` consult (gbdt iteration boundary): when the fault
    fires for THIS rank, hard-exit with :data:`EXIT_RANK_KILLED` — an
    ``os._exit`` so no cleanup or atexit runs, the closest injectable
    shape to a kill -9 mid-gang. A ``rank=R`` option restricts both the
    call accounting and the kill to rank R (so ``after=N`` means "after
    N of rank R's iterations"); without it every consulting rank is
    eligible, each with per-process counters.

    ``_exit`` is injectable so tests and the fault smoke can observe
    the exit code without dying."""
    plan = _active
    if plan is None:
        return
    f = plan.faults.get("rank_kill")
    if f is None:
        return
    if f.rank is not None and int(rank) != f.rank:
        return
    if not f.should_fire():
        return
    log.warning(f"injected rank_kill: rank {rank} hard-exiting "
                f"rc={EXIT_RANK_KILLED} (call #{f.calls}, injection "
                f"#{f.fired})")
    try:
        import sys
        sys.stdout.flush()
        sys.stderr.flush()
    except Exception:   # noqa: BLE001 — dying anyway
        pass
    _exit(EXIT_RANK_KILLED)


class inject:
    """Scoped fault injection::

        with faults.inject("collective:p=0.2:seed=3"):
            ...train...

    Nestable in the trivial sense (restores the previous plan on exit).
    Also usable as ``inject(None)`` to suppress an env-installed plan
    within the block.
    """

    def __init__(self, spec: Optional[str]):
        self.plan = FaultPlan.parse(spec) if spec else None
        self._saved: List[Optional[FaultPlan]] = []

    def __enter__(self) -> Optional[FaultPlan]:
        global _active
        self._saved.append(_active)
        _active = self.plan
        return self.plan

    def __exit__(self, *exc) -> None:
        global _active
        _active = self._saved.pop()


def install_from_env(env=None) -> bool:
    """Process-wide plan from ``LGBM_TPU_FAULTS`` (returns True if a
    plan was installed). Hooked into lightgbm_tpu/__init__.py so any
    importing process — including bench/probe child processes, which
    inherit the env var — runs under the plan."""
    global _active
    e = env if env is not None else os.environ
    spec = (e.get(ENV_FAULTS) or "").strip()
    if not spec or spec.lower() in ("0", "false", "off", "no"):
        return False
    _active = FaultPlan.parse(spec)
    log.warning(f"fault injection ACTIVE ({ENV_FAULTS}): {_active!r}")
    return True
