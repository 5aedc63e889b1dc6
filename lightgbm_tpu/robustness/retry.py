"""Reusable retry policy: bounded attempts, decorrelated-jitter backoff,
an overall deadline, and a transient-error classifier for jax/XLA.

A device runtime that is recovering answers
``UNAVAILABLE: TPU backend setup/compile error`` for a while
(docs/TPU_RUNBOOK.md), and a single unretried failure turns a
recovering device into a dead run. This module is the one shared answer:
``init_distributed``, the injected-collective call sites
(distributed.py) and the device probe (``probe_device`` below) all retry
through the same policy, so "how long do we believe in a flaky device" is
configured in exactly one place.

Backoff is decorrelated jitter (Brooker, "Exponential Backoff And
Jitter", AWS builders' library): ``sleep = min(cap, uniform(base,
prev_sleep * 3))`` — spreads concurrent retriers apart instead of
re-synchronizing them the way plain exponential backoff does.

Classifier table (ISSUE 17) — every failure a call site may see falls
in exactly one class, and this table is the single place the classes
are defined (tests assert the table, the docstring and the classifiers
stay in sync):

- ``TRANSIENT`` — device/network flake (UNAVAILABLE / ABORTED /
  connection errors): a later attempt of the SAME call may succeed, so
  :func:`retry_call` burns budget on it. Markers:
  :data:`TRANSIENT_MARKERS` / :data:`TRANSIENT_TYPES`.
- ``DEADLINE`` — a liveness budget expired (DEADLINE_EXCEEDED /
  timeouts). Retried like TRANSIENT (the next attempt gets a fresh
  sub-slot), but reported distinctly by :func:`classify_error` so
  forensics can tell a flake from a wedge. Markers:
  :data:`DEADLINE_MARKERS` / ``TimeoutError``.
- ``RESOURCE_EXHAUSTED`` — an allocation failed (XLA
  RESOURCE_EXHAUSTED / "out of memory" / ``MemoryError``). Retrying
  the SAME allocation is futile, so the classifier returns
  non-transient and :func:`retry_call` propagates immediately; the
  call site must ADAPT the request instead — the serving dispatcher
  bisects the batch (serving/server.py), the fleet evicts cold packs
  (serving/fleet.py), the trainer shrinks its window
  (service/trainer.py). Markers: :data:`OOM_MARKERS` /
  :data:`OOM_TYPES`.
- ``DATA_CORRUPTION`` — the call RAN but produced wrong bits
  (NaN-poisoned gradients, a canary parity mismatch, diverged gang
  digests — the :mod:`.integrity` exception family). NOT transient:
  retrying the identical call re-produces the identical corruption, so
  :func:`retry_call` propagates immediately and the call site must
  RECOVER — the continual trainer rolls back to the newest CRC-valid
  checkpoint (service/trainer.py), the serving tier quarantines the
  afflicted route and repairs the pack (serving/fleet.py), the gang
  supervisor relaunches from the manifest (robustness/gang.py).
  Markers: :data:`CORRUPTION_MARKERS`.
- ``FATAL`` — everything else (a code bug): propagates immediately,
  never retried, never adapted around.

No jax import at module scope (the classifier matches on type/message
strings precisely so it can run in processes that must not initialize a
backend).
"""
from __future__ import annotations

import dataclasses
import random
import time
from typing import Callable, Optional

from ..utils import log

# Substrings of exception text (or type name) that mark a failure as
# transient — retry may succeed. gRPC/XLA status names cover the
# device-runtime failure modes seen in early bench rounds; the plain
# words cover socket/timeout errors raised by launchers.
TRANSIENT_MARKERS = (
    "UNAVAILABLE",
    "DEADLINE_EXCEEDED",
    "ABORTED",
    "connection reset",
    "connection refused",
    "timed out",
    "timeout",
)

# Exception type names treated as transient regardless of message.
TRANSIENT_TYPES = (
    "TimeoutError",
    "ConnectionError",
    "ConnectionResetError",
    "ConnectionRefusedError",
    "BrokenPipeError",
)

# The DEADLINE sub-class of the transient markers: budget expiries that
# classify_error reports distinctly (still retried by retry_call).
DEADLINE_MARKERS = (
    "DEADLINE_EXCEEDED",
    "timed out",
    "timeout",
)

# Substrings marking RESOURCE_EXHAUSTED: the allocation itself failed,
# so re-attempting the SAME call is futile — the caller must shrink,
# bisect or evict (ISSUE 17). XLA's OOM status is the gRPC name; the
# plain phrases cover allocator messages and host MemoryError reprs.
OOM_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "out of memory",
    "failed to allocate",
)

# Exception type names treated as RESOURCE_EXHAUSTED regardless of
# message (host-side allocation failures during re-bin / pack build).
OOM_TYPES = (
    "MemoryError",
)

# Substrings marking DATA_CORRUPTION: the call ran and returned wrong
# bits (ISSUE 19). Every integrity.IntegrityError message carries the
# marker, so classification works across process boundaries (a child
# trainer's corruption surfaces to its supervisor as text).
CORRUPTION_MARKERS = (
    "DATA_CORRUPTION",
)

# The classifier table, machine-readable: class name -> one-line
# contract. tests/test_robustness.py asserts every class here appears
# in the module docstring (the drift check of the ISSUE 17 satellite).
ERROR_CLASSES = {
    "TRANSIENT": "device/network flake — retry the same call",
    "DEADLINE": "liveness budget expired — retry with a fresh slot",
    "RESOURCE_EXHAUSTED": "allocation failed — adapt, never retry",
    "DATA_CORRUPTION": "wrong bits produced — roll back, never retry",
    "FATAL": "code bug — propagate immediately",
}


def is_oom_error(exc: BaseException) -> bool:
    """True when ``exc`` is RESOURCE_EXHAUSTED-classified: the
    allocation failed, so retrying the identical call cannot succeed.
    Callers adapt instead (bisect the batch / evict a pack / shrink
    the window)."""
    for t in type(exc).__mro__:
        if t.__name__ in OOM_TYPES:
            return True
    text = f"{type(exc).__name__}: {exc}"
    upper = text.upper()
    return any(m.upper() in upper for m in OOM_MARKERS)


def is_corruption_error(exc: BaseException) -> bool:
    """True when ``exc`` is DATA_CORRUPTION-classified: the call ran
    but produced wrong bits, so retrying it re-produces the identical
    corruption. Callers roll back / quarantine / relaunch instead
    (integrity.py is the exception family; matching is on the message
    marker so child-process corruption classifies identically)."""
    text = f"{type(exc).__name__}: {exc}"
    upper = text.upper()
    return any(m.upper() in upper for m in CORRUPTION_MARKERS)


def is_transient_error(exc: BaseException) -> bool:
    """True when ``exc`` looks like a device/network failure that a
    later attempt may survive (UNAVAILABLE / DEADLINE_EXCEEDED /
    timeouts), False for anything that smells like a code bug.

    RESOURCE_EXHAUSTED is explicitly NOT transient even when the
    runtime dresses it in otherwise-transient text: retrying the same
    allocation burns the whole budget on attempts that cannot succeed
    (ISSUE 17) — :func:`retry_call` propagates it so the dispatch
    layer can adapt. DATA_CORRUPTION is NOT transient for the same
    reason (ISSUE 19): the retried call would re-produce the same
    wrong bits; the caller must roll back or repair instead.

    jaxlib's XlaRuntimeError carries the gRPC status name in its
    message, so string matching is the stable contract across jaxlib
    versions (the exception classes themselves moved modules twice).
    """
    if is_oom_error(exc) or is_corruption_error(exc):
        return False
    for t in type(exc).__mro__:
        if t.__name__ in TRANSIENT_TYPES:
            return True
    text = f"{type(exc).__name__}: {exc}"
    upper = text.upper()
    return any(m.upper() in upper for m in TRANSIENT_MARKERS)


def classify_error(exc: BaseException) -> str:
    """Classify ``exc`` into one of :data:`ERROR_CLASSES`.

    Precedence: RESOURCE_EXHAUSTED beats DATA_CORRUPTION beats
    DEADLINE beats TRANSIENT (an OOM whose message also mentions a
    timeout is still an OOM); anything unrecognized is FATAL."""
    if is_oom_error(exc):
        return "RESOURCE_EXHAUSTED"
    if is_corruption_error(exc):
        return "DATA_CORRUPTION"
    if not is_transient_error(exc):
        return "FATAL"
    for t in type(exc).__mro__:
        if t.__name__ == "TimeoutError":
            return "DEADLINE"
    upper = f"{type(exc).__name__}: {exc}".upper()
    if any(m.upper() in upper for m in DEADLINE_MARKERS):
        return "DEADLINE"
    return "TRANSIENT"


class RetryError(Exception):
    """All attempts failed (or the deadline passed). ``last`` holds the
    final underlying exception; ``attempts`` how many were made."""

    def __init__(self, msg: str, last: Optional[BaseException],
                 attempts: int):
        super().__init__(msg)
        self.last = last
        self.attempts = attempts


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded retry with decorrelated-jitter backoff and a deadline.

    - ``max_attempts``: total tries (first call included).
    - ``base_delay`` / ``max_delay``: jitter window bounds in seconds.
    - ``deadline``: wall-clock budget across ALL attempts (None = no
      deadline). No new attempt starts after it passes, and the
      pre-attempt sleep is clipped to it, so the policy can never
      outlive its budget — the property the bench watchdog relies on.
    - ``classifier``: exception -> bool (True = transient, retry).
    """

    max_attempts: int = 5
    base_delay: float = 0.5
    max_delay: float = 30.0
    deadline: Optional[float] = None
    classifier: Callable[[BaseException], bool] = is_transient_error

    def next_delay(self, prev_delay: float,
                   rng: random.Random) -> float:
        """Decorrelated jitter: uniform(base, prev*3) capped."""
        hi = max(self.base_delay, prev_delay * 3.0)
        return min(self.max_delay, rng.uniform(self.base_delay, hi))

    def from_env_overrides(self, env) -> "RetryPolicy":
        """LGBM_TPU_RETRY_* env knobs override individual fields
        (ATTEMPTS / BASE_DELAY / MAX_DELAY / DEADLINE)."""
        kw = {}
        if env.get("LGBM_TPU_RETRY_ATTEMPTS"):
            kw["max_attempts"] = int(env["LGBM_TPU_RETRY_ATTEMPTS"])
        if env.get("LGBM_TPU_RETRY_BASE_DELAY"):
            kw["base_delay"] = float(env["LGBM_TPU_RETRY_BASE_DELAY"])
        if env.get("LGBM_TPU_RETRY_MAX_DELAY"):
            kw["max_delay"] = float(env["LGBM_TPU_RETRY_MAX_DELAY"])
        if env.get("LGBM_TPU_RETRY_DEADLINE"):
            kw["deadline"] = float(env["LGBM_TPU_RETRY_DEADLINE"])
        return dataclasses.replace(self, **kw) if kw else self


# Policy used by the in-band training call sites (collectives,
# init_distributed): short sleeps — a training step is stalled while we
# wait — but enough attempts to ride out a p=0.2 injected failure rate
# with margin (P[5 consecutive failures] = 0.032%).
COLLECTIVE_POLICY = RetryPolicy(max_attempts=5, base_delay=0.05,
                                max_delay=2.0, deadline=120.0)

# Policy for device acquisition (probe / init): patient — the measured
# recovery signature is a claim that waits minutes before succeeding.
DEVICE_POLICY = RetryPolicy(max_attempts=6, base_delay=2.0,
                            max_delay=60.0, deadline=900.0)

# Policy for the serving dispatcher (serving/server.py, ISSUE 9): very
# short sleeps — every queued request is stalled while a batch retries —
# and a tight deadline: past it the server flips to the degraded
# host-walk route instead of holding its whole client population
# hostage to one wedged device.
SERVING_POLICY = RetryPolicy(max_attempts=3, base_delay=0.02,
                             max_delay=0.5, deadline=5.0)


def retry_call(fn: Callable, *args,
               policy: RetryPolicy = RetryPolicy(),
               what: str = "",
               rng: Optional[random.Random] = None,
               sleep: Callable[[float], None] = time.sleep,
               clock: Callable[[], float] = time.monotonic,
               on_retry: Optional[Callable[[int, BaseException], None]]
               = None,
               budget_kw: Optional[str] = None,
               **kwargs):
    """Call ``fn(*args, **kwargs)`` under ``policy``.

    Transient failures (per ``policy.classifier``) are retried with
    decorrelated-jitter sleeps until attempts or deadline run out;
    non-transient exceptions propagate immediately (a code bug must
    never burn the retry budget). Raises :class:`RetryError` when the
    budget is exhausted.

    Window accounting (ISSUE 4 satellite — the r05 log showed a probe
    attempt granted a 750 s slot inside an already half-spent window):

    - no attempt STARTS at or past the deadline (previously the
      deadline was only consulted after a failure, so a sleep could
      run the clock out and a fresh attempt still launch);
    - a backoff sleep that alone would exhaust the remaining deadline
      is skipped — the remaining window is spent on one final attempt
      instead of slept away;
    - ``budget_kw``: when set, every attempt receives the policy's
      remaining deadline (seconds, or None without a deadline) as that
      keyword argument, so callables that grant their own sub-slots
      (the bench probe's child timeout) can clip them to the window
      that actually remains.
    """
    rng = rng if rng is not None else random.Random()
    label = what or getattr(fn, "__name__", "call")
    start = clock()
    deadline_at = (start + policy.deadline
                   if policy.deadline is not None else None)
    delay = policy.base_delay
    last: Optional[BaseException] = None
    attempts = 0
    while attempts < policy.max_attempts:
        if (deadline_at is not None and clock() >= deadline_at and
                attempts > 0):
            break
        attempts += 1
        try:
            if budget_kw is not None:
                remaining = (max(0.0, deadline_at - clock())
                             if deadline_at is not None else None)
                return fn(*args, **{budget_kw: remaining}, **kwargs)
            return fn(*args, **kwargs)
        except BaseException as e:  # noqa: BLE001 — classifier decides
            if not policy.classifier(e):
                raise
            last = e
            if attempts >= policy.max_attempts:
                break
            if deadline_at is not None and clock() >= deadline_at:
                break
            delay = policy.next_delay(delay, rng)
            if deadline_at is not None and \
                    clock() + delay >= deadline_at:
                # the backoff alone would exhaust the window — spend
                # what remains on a final immediate attempt instead
                delay = 0.0
            if on_retry is not None:
                on_retry(attempts, e)
            log.warning(f"{label}: transient failure (attempt "
                        f"{attempts}/{policy.max_attempts}): {e!r}; "
                        f"retrying in {delay:.2f}s")
            if delay > 0.0:
                sleep(delay)
    raise RetryError(
        f"{label}: gave up after {attempts} attempt(s) over "
        f"{clock() - start:.1f}s: {last!r}", last, attempts)


# ---------------------------------------------------------------------------
# Graceful degradation: device acquisition with CPU fallback
# (config: tpu_fallback_to_cpu — ref motivation: the reference treats
# interruption as normal; we additionally treat "device never came up"
# as survivable when the user opted in).
# ---------------------------------------------------------------------------

def probe_device() -> int:
    """One device-acquisition attempt: list devices and run a trivial
    computation (forces backend init). Honors the
    fault harness's ``probe_timeout`` class so CPU tests can exercise
    the retry/fallback paths."""
    from . import faults
    faults.maybe_fail("probe_timeout")
    import jax
    devs = jax.devices()
    jax.block_until_ready(jax.numpy.zeros(8) + 1)
    return len(devs)


def ensure_device_or_fallback(fallback: bool = False,
                              policy: RetryPolicy = DEVICE_POLICY
                              ) -> bool:
    """Acquire the configured device under the retry policy; on terminal
    failure either fall back to CPU (``fallback=True``, from
    ``tpu_fallback_to_cpu``; loud warning, returns False) or re-raise.
    Returns True when the device came up.

    Call sites: engine.train (before the boosting loop) and the CLI
    runner. A no-op returning True on runs already pinned to CPU.
    """
    try:
        import os
        n = retry_call(
            probe_device,
            policy=policy.from_env_overrides(os.environ),
            what="device probe")
        log.debug(f"device probe ok ({n} device(s))")
        return True
    except Exception as e:  # noqa: BLE001
        # only a transient-classified terminal failure earns the CPU
        # fallback: a code bug (ImportError, TypeError, ...) must still
        # crash loudly rather than masquerade as a flaky device
        if not fallback or not (isinstance(e, RetryError) or
                                is_transient_error(e)):
            raise
        log.warning(
            "=" * 60 + "\n"
            f"DEVICE UNREACHABLE after retry policy exhausted: {e!r}\n"
            "tpu_fallback_to_cpu=true — CONTINUING ON CPU. Training "
            "will be correct but slow; fix the accelerator and restart "
            "to regain device speed.\n" + "=" * 60)
        import jax
        jax.config.update("jax_platforms", "cpu")
        return False
