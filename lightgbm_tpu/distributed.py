"""Multi-host distributed training entry points.

Role-equivalent of the reference's cluster integrations — the Dask
interface (ref: python-package/lightgbm/dask.py:442 _train) and the
machines/machine-list-file socket setup (ref: src/network/linkers_socket.cpp,
config machines/num_machines/local_listen_port). The TPU translation is
SPMD: every host runs THE SAME program over one global
``jax.sharding.Mesh`` that spans all hosts' devices; jax's runtime routes
the grower's ``psum``/``all_gather`` collectives over ICI/DCN, so there is
no per-framework socket/MPI layer to configure — ``init_distributed`` is
the only cluster-shaped call, and it wraps ``jax.distributed.initialize``.

Single-host multi-device needs none of this: ``tree_learner=data`` with
``tpu_num_devices`` already shards over local devices.

Typical multi-host launch (one process per host, same script):

    import lightgbm_tpu as lgb
    from lightgbm_tpu.distributed import init_distributed

    init_distributed(coordinator_address="host0:8476",
                     num_processes=4, process_id=RANK)
    bst = lgb.train({"tree_learner": "data", ...}, lgb.Dataset(X, y))
"""
from __future__ import annotations

from typing import Optional, Sequence

from .utils import log

_initialized = False


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     local_device_ids: Optional[Sequence[int]] = None
                     ) -> int:
    """Join (or start) the multi-host world. Returns this process' index.

    Maps the reference's ``machines``/``num_machines``/``machine_list_file``
    network config onto ``jax.distributed.initialize``: the coordinator
    address replaces the machine list (every process dials the same
    coordinator), ``num_processes`` replaces ``num_machines`` and
    ``process_id`` replaces the rank derived from the list. With no
    arguments, jax's auto-detection (TPU pod metadata, SLURM, etc.) is
    used — the common TPU-pod case needs zero configuration.
    """
    global _initialized
    import jax

    if _initialized:
        log.warning("init_distributed called twice; ignoring")
        return jax.process_index()
    # joining the world is the single most failure-prone call of a
    # multi-host run (coordinator not up yet, DNS hiccup, a device
    # runtime cycling UNAVAILABLE) — retry under the shared device policy
    # instead of dying on the first connection failure
    import os

    from .robustness.retry import DEVICE_POLICY, retry_call

    def _attempt():
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
                local_device_ids=local_device_ids)
        except BaseException:
            # a failed connect leaves jax's global client/service
            # state set, and a second initialize would then raise the
            # NON-transient "should only be called once" RuntimeError —
            # reset so the next attempt is a real attempt
            try:
                jax.distributed.shutdown()
            except Exception:  # noqa: BLE001 — best-effort reset
                pass
            raise

    retry_call(_attempt,
               policy=DEVICE_POLICY.from_env_overrides(os.environ),
               what="jax.distributed.initialize")
    _initialized = True
    n = jax.process_count()
    log.info(f"Distributed world initialized: process "
             f"{jax.process_index()}/{n}, "
             f"{len(jax.local_devices())} local / "
             f"{len(jax.devices())} global devices")
    return jax.process_index()


def shutdown_distributed() -> None:
    """Leave the multi-host world (ref: Network::Dispose)."""
    global _initialized
    if not _initialized:
        return
    import jax

    jax.distributed.shutdown()
    _initialized = False


def num_processes() -> int:
    import jax
    return jax.process_count()


def process_index() -> int:
    import jax
    return jax.process_index()


def feature_slice(num_features: int, rank: int, world: int
                  ) -> "tuple[int, int]":
    """Contiguous feature-slice ownership for distributed bin finding
    (ref: dataset_loader.cpp:1175-1185 — ``num_total_features /
    num_machines`` blocks, remainder on the early ranks here via the
    ceiling step). Every feature belongs to exactly one rank, including
    ragged ``num_features % world != 0`` (late ranks may own an empty
    slice). Returns ``[lo, hi)``."""
    if world <= 1:
        return 0, num_features
    step = max((num_features + world - 1) // world, 1)
    lo = min(rank * step, num_features)
    return lo, min(lo + step, num_features)


def row_slice(num_rows: int, rank: int, world: int) -> "tuple[int, int]":
    """Contiguous row-shard ownership ``[lo, hi)`` over a global table
    of ``num_rows`` — THE shard-boundary convention of sharded
    ingestion. Every place that cuts the global table (shared-file
    slice loading, sidecar slicing, the ingest bench gang, the
    robustness workers) must use this exact math: the training table is
    the rank-order concatenation of the slices, and the bit-identity
    contract depends on all cutters agreeing. Slices partition the rows
    exactly (late ranks may be one row larger on ragged counts)."""
    if world <= 1:
        return 0, num_rows
    return rank * num_rows // world, (rank + 1) * num_rows // world


# ---------------------------------------------------------------------------
# Collective liveness (ISSUE 10): a host-level collective blocked on a
# dead peer must RAISE within a deadline, never wedge the rank until the
# whole-gang timeout. Covers allgather_bytes (the sharded-ingest
# transport) and every injected-collective call site; a rank wedged
# inside a *jitted* collective is covered by the in-training watchdog
# (robustness/heartbeat.TrainingWatchdog -> EXIT_STALLED), which the
# gang supervisor classifies the same way.
# ---------------------------------------------------------------------------

ENV_COLLECTIVE_TIMEOUT = "LGBM_TPU_COLLECTIVE_TIMEOUT"
DEFAULT_COLLECTIVE_TIMEOUT = 300.0

_collective_timeout_override: "Optional[float]" = None


class CollectiveTimeout(Exception):
    """A host-level collective exceeded its liveness deadline — a peer
    is presumed dead or wedged.

    The message carries ``DEADLINE_EXCEEDED`` so OUTER supervision (the
    gang relaunch policy, session supervisors) classifies the rank's
    death as transient; ``retried_collective`` itself does NOT retry it
    in-process — a dead peer does not come back within an in-process
    retry budget, and re-driving a gloo round while the previous one is
    still blocked in a leaked thread would desync the collective
    sequence across the gang. The correct recovery is rank death +
    whole-gang relaunch from the newest manifest."""

    def __init__(self, msg: str):
        super().__init__(f"DEADLINE_EXCEEDED: {msg}")


def set_collective_timeout(sec: Optional[float]) -> None:
    """Pin the collective liveness deadline for this process (seconds;
    ``tpu_gang_collective_timeout_s`` routes through here from dataset
    construction and the gbdt setup). None or <= 0 clears the pin back
    to the env/default resolution."""
    global _collective_timeout_override
    _collective_timeout_override = (
        float(sec) if sec is not None and float(sec) > 0 else None)


def collective_timeout() -> float:
    """Effective deadline (seconds; <= 0 disables): explicit
    :func:`set_collective_timeout` > ``LGBM_TPU_COLLECTIVE_TIMEOUT`` >
    300 s default. Pod-scale payloads (100M-row metadata allgathers)
    should raise it; it must stay well under the gang's own hard
    deadline so a dead peer surfaces as ONE rank's classified death,
    not a whole-gang timeout."""
    if _collective_timeout_override is not None:
        return _collective_timeout_override
    import os
    v = (os.environ.get(ENV_COLLECTIVE_TIMEOUT) or "").strip()
    if v:
        return float(v)
    return DEFAULT_COLLECTIVE_TIMEOUT


def call_with_deadline(fn, timeout: float, what: str = "collective"):
    """Run ``fn()`` in a watchdog thread and raise
    :class:`CollectiveTimeout` if it does not finish within ``timeout``
    seconds (<= 0 runs inline, no thread). On timeout the worker thread
    is left blocked (daemon — it holds no locks the caller needs); the
    caller is expected to let the raise propagate and die so the gang
    supervisor can relaunch, which is why timeouts are never retried
    in-process."""
    if not timeout or timeout <= 0:
        return fn()
    import threading

    done = threading.Event()
    box: dict = {}

    def _run():
        try:
            box["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — relayed to caller
            box["error"] = e
        finally:
            done.set()

    t = threading.Thread(target=_run, name="lgbm-tpu-collective",
                         daemon=True)
    t.start()
    if not done.wait(timeout):
        raise CollectiveTimeout(
            f"collective {what!r} exceeded its {timeout:.0f}s liveness "
            "deadline — a peer is presumed dead or wedged; raising so "
            "this rank dies classified instead of hanging the gang")
    if "error" in box:
        raise box["error"]
    return box["value"]


def allgather_bytes(blob: bytes, what: str = "allgather_bytes") -> list:
    """Allgather variable-length byte blobs across the process world —
    the transport of the distributed bin-finding protocol (sample
    summaries out, serialized BinMappers back; ≡ Network::Allgather of
    the size-prefixed buffers in dataset_loader.cpp:1221-1260).

    Two fixed-shape ``process_allgather`` rounds (lengths, then padded
    payloads), each driven through ``retried_collective`` so transport
    flakiness — injected via the LGBM_TPU_FAULTS ``collective`` class or
    real — is retried under the shared bounded COLLECTIVE_POLICY.
    Returns the per-rank blobs in rank order; a world of one returns
    ``[blob]`` without touching the backend."""
    import jax

    if jax.process_count() <= 1:
        return [blob]
    import numpy as np
    from jax.experimental import multihost_utils

    def _gather(a):
        return np.asarray(multihost_utils.process_allgather(a))

    arr = np.frombuffer(blob, np.uint8)
    lens = retried_collective(
        _gather, np.asarray([arr.size], np.int64),
        what=f"{what} (lengths)").reshape(-1)
    buf = np.zeros(max(int(lens.max()), 1), np.uint8)
    buf[:arr.size] = arr
    gathered = retried_collective(_gather, buf,
                                  what=f"{what} (payload)")
    return [gathered[r, :int(lens[r])].tobytes()
            for r in range(len(lens))]


# ---------------------------------------------------------------------------
# Launcher convenience layer (the Dask-analog UX).
#
# The reference's dask module resolves workers, assigns listen ports and
# builds the machines list before handing off to the socket linkers
# (ref: python-package/lightgbm/dask.py:442 _train, :300 port search).
# The SPMD translation needs exactly three facts per process —
# coordinator address, world size, rank — so the convenience layer is an
# env-var contract (works under ANY process launcher: SLURM, k8s,
# mpirun, GKE pod spec) plus a local spawner for single-machine
# multi-process runs and tests.
# ---------------------------------------------------------------------------

ENV_COORDINATOR = "LGBM_TPU_COORDINATOR"
ENV_NUM_PROCESSES = "LGBM_TPU_NUM_PROCESSES"
ENV_PROCESS_ID = "LGBM_TPU_PROCESS_ID"
ENV_CPU_DEVICES = "LGBM_TPU_CPU_DEVICES_PER_PROCESS"


def worker_env(coordinator_address: str, num_processes: int,
               process_id: int, cpu_devices_per_process: int = 0,
               base_env: Optional[dict] = None) -> dict:
    """Environment for one worker process under the launcher contract.

    ``cpu_devices_per_process`` > 0 additionally forces that many
    virtual CPU devices (hardware-free testing; on real TPU hosts leave
    it 0 so local devices are discovered normally).
    """
    import os
    env = dict(base_env if base_env is not None else os.environ)
    env[ENV_COORDINATOR] = str(coordinator_address)
    env[ENV_NUM_PROCESSES] = str(int(num_processes))
    env[ENV_PROCESS_ID] = str(int(process_id))
    if cpu_devices_per_process:
        env[ENV_CPU_DEVICES] = str(int(cpu_devices_per_process))
    return env


def init_from_env() -> int:
    """``init_distributed`` driven by the launcher env contract.

    Call this unconditionally at the top of a training script: with the
    LGBM_TPU_* variables set (by ``launch_local`` or any cluster
    launcher) it joins that world; with none set it falls back to jax's
    auto-detection (TPU pod metadata, SLURM) — and on a plain
    single-host run, to a world of one. Returns the process index.
    """
    import os
    coord = os.environ.get(ENV_COORDINATOR)
    cpu_devs = int(os.environ.get(ENV_CPU_DEVICES, "0") or 0)
    if cpu_devs:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags +
                f" --xla_force_host_platform_device_count={cpu_devs}"
            ).strip()
        import jax
        jax.config.update("jax_platforms", "cpu")
        # the default CPU backend refuses multi-process computations
        # ("Multiprocess computations aren't implemented on the CPU
        # backend"); gloo collectives make the hardware-free rehearsal
        # world real. Best-effort: jaxlibs without gloo keep the old
        # behavior (and the old error)
        try:
            jax.config.update("jax_cpu_collectives_implementation",
                              "gloo")
        except Exception as e:  # noqa: BLE001 — config absent/renamed
            log.debug(f"could not select gloo CPU collectives: {e}")
    if coord is None:
        try:
            return init_distributed()     # jax auto-detection
        except Exception as e:  # noqa: BLE001 — single-host fallback
            log.debug(f"no distributed environment detected ({e}); "
                      "running single-process")
            return 0
    return init_distributed(
        coordinator_address=coord,
        num_processes=int(os.environ[ENV_NUM_PROCESSES]),
        process_id=int(os.environ[ENV_PROCESS_ID]))


def spawn_local(argv: Sequence[str], num_processes: int,
                coordinator_port: Optional[int] = None,
                cpu_devices_per_process: int = 0,
                env_extra: Optional[dict] = None) -> list:
    """Spawn the gang and return the live ``subprocess.Popen`` handles
    (rank order). The building block under ``launch_local`` — exposed so
    supervised callers (the ingest bench, the kill-and-relaunch
    robustness test) can watch, kill or relaunch individual ranks."""
    import socket
    import subprocess
    if coordinator_port is None:
        with socket.socket() as s:
            s.bind(("", 0))
            coordinator_port = s.getsockname()[1]
    coord = f"localhost:{coordinator_port}"
    procs = []
    for rank in range(num_processes):
        env = worker_env(coord, num_processes, rank,
                         cpu_devices_per_process=cpu_devices_per_process)
        if cpu_devices_per_process:
            env.pop("XLA_FLAGS", None)    # worker rebuilds it itself
        if env_extra:
            env.update({k: str(v) for k, v in env_extra.items()})
        procs.append(subprocess.Popen(
            list(argv), env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    return procs


def launch_local(argv: Sequence[str], num_processes: int,
                 coordinator_port: Optional[int] = None,
                 cpu_devices_per_process: int = 0,
                 timeout: float = 600.0,
                 env_extra: Optional[dict] = None,
                 supervised: bool = False,
                 **gang_kw) -> list:
    """Spawn ``num_processes`` copies of ``argv`` on THIS machine, wired
    into one distributed world (the local analog of spawn-per-host; the
    per-host version is the same env contract under any real launcher).

    Returns ``[(returncode, combined_output), ...]`` per rank.

    ``supervised=True`` routes through the fault-tolerant gang
    (robustness/gang.py run_supervised; extra keywords pass through):
    per-rank heartbeat supervision under the shared StallPolicy, rank
    death SIGTERMs the survivors instead of letting them wedge in a
    collective, and the WHOLE gang is auto-relaunched under a bounded
    RetryPolicy — workers resume from the newest valid gang manifest —
    so one rank death costs one resume, not the session.

    Unsupervised (the default) keeps the blunt whole-gang timeout kill,
    but exports a heartbeat base to the workers so the
    :class:`~.robustness.gang.GangTimeout` it raises on the timeout
    path carries per-rank last-phase/last-beat forensics instead of
    nothing (it subclasses ``subprocess.TimeoutExpired`` — existing
    callers keep catching it).
    """
    if supervised:
        from .robustness.gang import run_supervised
        return run_supervised(
            argv, num_processes, coordinator_port=coordinator_port,
            cpu_devices_per_process=cpu_devices_per_process,
            timeout=timeout, env_extra=env_extra, **gang_kw)
    if gang_kw:
        raise TypeError(f"unexpected arguments {sorted(gang_kw)} "
                        "(supervised=True options)")
    import os
    import shutil
    import subprocess
    import tempfile

    from .robustness.gang import GangTimeout, gang_hb_paths
    from .robustness.heartbeat import ENV_HEARTBEAT

    extra = dict(env_extra or {})
    hb_tmp = None
    hb_base = extra.get(ENV_HEARTBEAT) or os.environ.get(ENV_HEARTBEAT)
    if not hb_base:
        hb_tmp = tempfile.mkdtemp(prefix="lgbm_gang_hb_")
        hb_base = os.path.join(hb_tmp, "gang.hb")
        extra[ENV_HEARTBEAT] = hb_base
    procs = spawn_local(argv, num_processes,
                        coordinator_port=coordinator_port,
                        cpu_devices_per_process=cpu_devices_per_process,
                        env_extra=extra)
    results = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            results.append((p.returncode, out))
        return results
    except subprocess.TimeoutExpired:
        # hung-gang forensics BEFORE the kill: each rank's last
        # phase/beat answers "why did it die" (the r03-style gap,
        # gang edition)
        from .robustness.gang import rank_diagnosis
        rcs = [p.poll() for p in procs]
        diag = rank_diagnosis(gang_hb_paths(hb_base, num_processes),
                              rcs)
        for p in procs:
            if p.poll() is None:
                p.kill()
        raise GangTimeout(
            list(argv), timeout,
            diagnosis="Per-rank diagnosis at the timeout:\n" + diag)
    finally:
        if hb_tmp is not None:
            shutil.rmtree(hb_tmp, ignore_errors=True)


# ---------------------------------------------------------------------------
# External collective injection (≡ LGBM_NetworkInitWithFunctions,
# ref: include/LightGBM/c_api.h:1674, src/network/network.cpp:49-62 —
# the reference lets an embedding host (SynapseML/Spark) supply its own
# reduce-scatter/allgather instead of the built-in socket/MPI linkers).
#
# The TPU translation: the grower's distributed hooks (reduce_hist /
# reduce_sums / reduce_max, core/grower.py make_tree_grower) are fed
# host callables through `jax.experimental.io_callback`, so EVERY
# cross-worker reduction of the training program routes through the
# injected functions — no jax.distributed world required. Each worker
# runs the ordinary serial grower on its row shard; the injected
# allreduce makes histograms/root sums global, which is exactly the
# data-parallel algebra (SURVEY.md §3.3) with user-owned transport.
# ---------------------------------------------------------------------------

_injected = None


def inject_collectives(reduce_sum, reduce_max=None, rank: int = 0,
                       num_machines: int = 1) -> None:
    """Register external collectives for subsequent Booster training.

    reduce_sum(np.ndarray) -> np.ndarray: allreduce-sum across workers
    (same shape/dtype; called for histograms [F, B, 3] f32/i32 and root
    sum triples [3]). reduce_max: allreduce-max for scalars (only
    needed with use_quantized_grad; defaults to identity). ``rank``
    decorrelates per-worker RNG (stochastic rounding).

    Rows must be pre-partitioned across workers and bin boundaries
    shared — the same contract as the reference's pre_partition=true
    external-collective mode. Inside a jax.distributed world the
    sharded-ingestion path (``pre_partition=true`` /
    ``tpu_ingest="sharded"``, io/dataset_core.py) finds globally
    consistent bins from per-shard samples automatically; with
    user-owned transport (this injection, no jax world) share bins by
    building each worker's Dataset with ``reference=`` or the same
    forcedbins file.
    """
    global _injected
    if not callable(reduce_sum):
        raise TypeError("reduce_sum must be callable")
    _injected = {
        "reduce_sum": reduce_sum,
        "reduce_max": reduce_max,
        "rank": int(rank),
        "num_machines": int(num_machines),
    }
    log.info(f"external collectives injected (rank {rank}/"
             f"{num_machines})")


def clear_collectives() -> None:
    """Remove an injected collective backend (≡ LGBM_NetworkFree)."""
    global _injected
    _injected = None


def injected_collectives():
    return _injected


def retried_collective(fn, arr, what: str = "injected collective"):
    """Drive one injected-collective call under the shared retry policy.

    Every cross-worker reduction routes through here, so this is THE
    choke point for transport flakiness: each attempt first consults
    the fault harness (LGBM_TPU_FAULTS ``collective`` class), then runs
    the user transport; transient failures — injected or real — are
    retried under the bounded COLLECTIVE_POLICY (LGBM_TPU_RETRY_* env
    overrides apply). The fault check sits INSIDE the retried attempt:
    a fired fault means "this attempt's request was lost", exactly like
    a dropped packet, and the retry must re-drive the whole operation.

    Retry-safety contract for user transports: a failing ``fn`` must
    fail ATOMICALLY — before any peer could observe the operation —
    because a retry re-drives it from scratch. A transport that can
    fail after partially synchronizing peers (e.g. after releasing a
    barrier generation) must make its own call idempotent or fence the
    retry itself; the harness's injected faults model the
    request-lost case, which every barrier/rendezvous transport
    handles naturally.

    Collective liveness (ISSUE 10): each attempt runs under
    :func:`call_with_deadline` (``collective_timeout()`` seconds), so a
    call blocked on a dead peer raises :class:`CollectiveTimeout`
    instead of wedging. Timeouts are deliberately NOT retried here —
    see CollectiveTimeout — the raise propagates, the rank dies
    classified, and the gang supervisor relaunches. The injected
    ``collective_delay`` fault stretches an attempt INSIDE the deadline
    window (the blocked-peer simulation).
    """
    import dataclasses
    import os

    from .robustness import faults
    from .robustness.retry import COLLECTIVE_POLICY, retry_call

    timeout = collective_timeout()

    def op():
        faults.maybe_delay("collective_delay")
        return fn(arr)

    def attempt():
        faults.maybe_fail("collective")
        return call_with_deadline(op, timeout, what=what)

    policy = COLLECTIVE_POLICY.from_env_overrides(os.environ)
    base_classifier = policy.classifier
    policy = dataclasses.replace(
        policy,
        classifier=lambda e: (not isinstance(e, CollectiveTimeout)
                              and base_classifier(e)))
    return retry_call(attempt, policy=policy, what=what)


def make_injected_hooks():
    """Grower hooks wrapping the injected callables via io_callback
    (ordered: comm calls must run exactly once per step, in program
    order). Returns None when nothing is injected."""
    if _injected is None:
        return None
    import functools

    import jax
    import numpy as np
    from jax.experimental import io_callback

    inj = _injected

    def _host_sum(a):
        out = retried_collective(inj["reduce_sum"], np.asarray(a),
                                 what="injected reduce_sum")
        return np.asarray(out, a.dtype).reshape(a.shape)

    def _host_max(a):
        fn = inj["reduce_max"]
        if fn is None:
            return np.asarray(a)
        out = retried_collective(fn, np.asarray(a),
                                 what="injected reduce_max")
        return np.asarray(out, a.dtype).reshape(a.shape)

    def _io(fn, x):
        return io_callback(fn, jax.ShapeDtypeStruct(x.shape, x.dtype),
                           x, ordered=True)

    return {
        "reduce_hist": lambda h, ctx=None: _io(_host_sum, h),
        "reduce_sums": lambda s: _io(_host_sum, s),
        "reduce_max": lambda x: _io(_host_max, x),
        "localize_key": functools.partial(
            jax.random.fold_in, data=inj["rank"]),
    }
