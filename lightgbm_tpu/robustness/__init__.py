"""Fault-tolerant training runtime.

Three cooperating pieces (ISSUE 2; motivated by early bench rounds all
dying with ``device_unreachable`` and losing every iteration of
progress):

- :mod:`.retry` — a reusable retry policy (bounded attempts,
  decorrelated-jitter backoff, overall deadline) with an error
  classifier that knows which jax/XLA failures are transient
  (``UNAVAILABLE``, ``DEADLINE_EXCEEDED``, timeouts). Applied to
  ``distributed.init_distributed``, the injected-collective call sites,
  and the bench device probe; ``tpu_fallback_to_cpu=true`` degrades to
  CPU instead of aborting when the device never comes up.
- :mod:`.checkpoint` — atomic checkpoint writes (tmp + fsync + rename,
  CRC32 footer) of the full training state: model string plus loop
  state (iteration, best_iteration/best_score, eval history, bagging
  RNG snapshots). Resume auto-selects the newest *valid* checkpoint;
  corrupt/partial files are detected by CRC and skipped.
- :mod:`.faults` — a fault-injection harness (``LGBM_TPU_FAULTS`` env
  var or context manager, mirroring the ``LGBM_TPU_GUARDS`` install
  pattern) that injects transient failures into collectives, device
  probes, checkpoint writes, heartbeat liveness (``hang``) and compile
  duration (``slow_compile``), so the retry, atomicity and supervision
  guarantees are testable on CPU in tier-1.
- :mod:`.heartbeat` / :mod:`.supervisor` — phase-tagged liveness
  (ISSUE 4): instrumented children write crash-safe heartbeats
  (``compiling``/``warmup``/``measuring``/``iter N``), supervisors
  replace blind wall-clock slots with phase-aware stall deadlines
  (:class:`DeviceStallError` is transient under the retry policy), and
  an in-training watchdog raises instead of hanging forever at a
  wedged device sync.
- :mod:`.gang` — the multi-process extension (ISSUE 10): per-rank
  heartbeat supervision (:class:`~.gang.GangSupervisor` SIGTERMs the
  survivors of a dead rank instead of letting them wedge in a
  collective), coordinated gang manifests (world size + per-rank shard
  digests committed per checkpoint; resume refuses torn/mixed-world
  sets loudly), and bounded whole-gang auto-relaunch
  (:func:`~.gang.run_supervised` /
  ``distributed.launch_local(supervised=True)``).

jax is never imported at module import time (mirrors analysis/guards.py:
the CLI and host-side tools must be able to import this package without
initializing a backend).
"""
from .retry import (RetryError, RetryPolicy, is_transient_error,
                    retry_call)
from .checkpoint import (CheckpointError, atomic_write_text,
                         latest_valid_checkpoint, list_checkpoints,
                         prune_checkpoints, read_checkpoint,
                         write_checkpoint)
from .faults import (FaultInjected, active_plan, inject, install_from_env,
                     maybe_fail)
from .heartbeat import (DeviceStallError, Heartbeat, HeartbeatRecord,
                        StallPolicy, TrainingWatchdog)
from .supervisor import StillAlive, watch_child
from .gang import (GangError, GangSupervisor, GangTimeout,
                   latest_valid_manifest, run_supervised, write_manifest)

__all__ = [
    "GangError", "GangSupervisor", "GangTimeout", "run_supervised",
    "write_manifest", "latest_valid_manifest",
    "RetryPolicy", "RetryError", "retry_call", "is_transient_error",
    "CheckpointError", "atomic_write_text", "write_checkpoint",
    "read_checkpoint", "latest_valid_checkpoint", "list_checkpoints",
    "prune_checkpoints",
    "FaultInjected", "inject", "install_from_env", "maybe_fail",
    "active_plan",
    "DeviceStallError", "Heartbeat", "HeartbeatRecord", "StallPolicy",
    "TrainingWatchdog", "StillAlive", "watch_child",
]
