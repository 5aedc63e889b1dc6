"""Resident trainer loop — the training half of the continual-learning
service (ISSUE 14 tentpole, part 1).

The reference ships train/predict/refit as one resident application
(src/application/application.cpp task loop); this module is that loop
reimagined for a serving tier that must never stop answering:

- :func:`run_resident_trainer` boosts FOREVER (or to a target) on a
  ROLLING WINDOW of fresh rows tail-followed from a growing stream file
  (io/stream_loader.StreamFollower — the same native chunk parser the
  two-round loader uses). Each cycle re-bins the current window and
  continues the model via the text round-trip (``init_model=Booster(
  model_str=...)``) — exactly the path checkpoint resume uses, so every
  tree's thresholds rebind to the fresh window's bin space and a
  crash-relaunch continues bit-identically from the same checkpoint.
- Every ``publish_every_iters`` boosting iterations it commits a CRC-
  validated ATOMIC checkpoint (robustness/checkpoint.py) carrying the
  model AND the service watermark (rows ingested + wall-clock of the
  newest row the window saw). The checkpoint file IS the publish
  channel: the serving process's publish pump tails the directory and
  hot-swaps each new generation into the live server. A trainer that
  dies mid-write leaves the previous checkpoint set intact (atomic
  rename + CRC), so the serving side can never observe a torn model —
  trainer-crash-during-publish is a non-event by construction.
- Under supervision (:class:`TrainerSupervisor`) the loop runs in a
  child process with the ISSUE 4 heartbeat installed; a crash or a
  classified stall costs one bounded relaunch-and-resume (the gang
  discipline from PR10 applied to a single resident rank) while the
  front door keeps serving the last published generation — a trainer
  death is a freshness regression, never a serving gap.

The injected ``rank_kill`` fault (robustness/faults.py) fires at the
gbdt iteration boundary inside this loop too (the resident trainer is
rank 0 of a one-rank gang), which is how the freshness chaos gate
(scripts/serving_load.py --live) kills the trainer mid-run.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from ..utils import log

STATE_KEY = "service"          # checkpoint sub-dict carrying the watermark
EXIT_TARGET_REACHED = 0


@dataclasses.dataclass
class TrainerSpec:
    """Everything the resident trainer needs — JSON-serializable so the
    supervised child can be handed the spec on argv."""

    params: Dict                  # training params (num_leaves, obj, ...)
    stream_path: str              # growing CSV of [label, features...]
    ckpt_dir: str                 # checkpoint/publish directory
    label_col: int = 0
    window_rows: int = 8192      # rolling training window
    window_floor_rows: int = 1024  # OOM auto-shrink floor (ISSUE 17)
    min_rows: int = 256          # first fit waits for this many rows
    iters_per_cycle: int = 4     # boosting rounds per window refresh
    publish_every_iters: int = 4  # checkpoint/publish cadence
    target_iterations: int = 0   # 0 = run until stopped
    poll_sec: float = 0.2        # stream poll cadence
    keep_last: int = 3           # checkpoint retention
    sep: str = ","

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, blob: str) -> "TrainerSpec":
        return cls(**json.loads(blob))


def _split_window(window: np.ndarray, label_col: int):
    y = np.ascontiguousarray(window[:, label_col], np.float32)
    X = np.ascontiguousarray(
        np.delete(window, label_col, axis=1), np.float32)
    return X, y


def run_resident_trainer(spec: TrainerSpec,
                         stop: Optional[threading.Event] = None,
                         on_cycle: Optional[Callable] = None) -> int:
    """The loop body (runs in-thread or as the supervised child).

    Resume contract: the newest CRC-valid checkpoint in ``ckpt_dir``
    wins — model text, iteration count and the stream watermark all
    come from it, and the rolling window is rebuilt from the stream
    tail, so a relaunched trainer continues the SAME model (bit-exact
    via the PR2 text round-trip) on the freshest data. Returns 0 when
    ``target_iterations`` is reached or ``stop`` is set.
    """
    import lightgbm_tpu as lgb
    from ..io.stream_loader import StreamFollower
    from ..robustness import checkpoint as ckpt
    from ..robustness import faults
    from ..robustness import heartbeat
    from ..robustness.retry import is_corruption_error, is_oom_error

    heartbeat.install_from_env()
    heartbeat.beat("boot", 0)
    follower = StreamFollower(spec.stream_path, sep=spec.sep)
    window: Optional[np.ndarray] = None
    model_str: Optional[str] = None
    iteration = 0
    # memory-pressure auto-shrink (ISSUE 17): the EFFECTIVE rolling
    # window, halved on an OOM'd cycle down to the floor and grown back
    # after sustained pressure-free cycles — a freshness regression,
    # never a crash loop
    win_rows = int(spec.window_rows)
    win_floor = max(1, min(int(spec.window_floor_rows), win_rows))
    ok_cycles = 0
    shrink_warned = False
    # numeric-health rollback (ISSUE 19): consecutive cycles refused as
    # DATA_CORRUPTION — one refusal retries the SAME window against the
    # rolled-back model (a transient poisoning replays clean and
    # bit-identical); a second in a row condemns the window itself and
    # training resumes PAST it on fresh stream rows
    corrupt_cycles = 0
    # the resident trainer always trains under the numeric-health guard
    # unless the operator explicitly disabled it: a long-lived
    # unattended loop must refuse poisoned iterations instead of
    # committing them to the publish channel
    params = dict(spec.params)
    params.setdefault("tpu_integrity_numeric_guard", True)

    found = ckpt.latest_valid_checkpoint(spec.ckpt_dir)
    if found is not None:
        _path, state = found
        model_str = state["model"]
        iteration = int(state["iteration"])
        svc = state.get(STATE_KEY) or {}
        # restore the stream cursor, rewound by roughly one window of
        # bytes so the rolling window refills from the tail instead of
        # (a) re-parsing the whole stream from byte 0 — a multi-minute
        # stall-classifiable catch-up on a long-lived stream — or
        # (b) starting at the exact offset with an empty window and
        # waiting for min_rows of NEW rows. rows_seen stays the
        # checkpointed value (the re-read tail double-counts a little;
        # the watermark is monitoring, not accounting).
        offset = int(svc.get("stream_offset", 0))
        rows_seen = int(svc.get("watermark_rows", 0))
        # the poison-row count survives relaunch: a relaunched trainer
        # must not report skipped_rows=0 while the .deadletter sidecar
        # holds quarantined lines (the tail re-read may re-skip a few —
        # monitoring, not accounting, same as rows_seen)
        follower.rows_skipped = int(svc.get("skipped_rows", 0))
        if offset > 0 and rows_seen > 0:
            bytes_per_row = max(offset // rows_seen, 1)
            rewind = min(offset,
                         int(spec.window_rows * bytes_per_row * 1.25))
            follower.offset = offset - rewind
            follower.rows_seen = max(rows_seen -
                                     rewind // bytes_per_row, 0)
            # re-anchor on a line boundary (the rewound offset lands
            # mid-line almost surely)
            try:
                with open(spec.stream_path, "rb") as f:
                    f.seek(follower.offset)
                    if follower.offset:
                        f.readline()          # discard the partial line
                    follower.offset = f.tell()
            except OSError:
                follower.offset = 0
        log.info(f"resident trainer resuming at iteration {iteration} "
                 f"from {_path} (stream cursor {follower.offset})")

    def drain() -> None:
        nonlocal window
        while True:
            fresh = follower.poll()
            if fresh is None or not len(fresh):
                return
            window = fresh if window is None else \
                np.concatenate([window, fresh], axis=0)
            if len(window) > win_rows:
                window = window[-win_rows:]
            # a large backlog drains in many 64MB polls: keep beating
            # so catch-up reads as alive, never as a stall
            heartbeat.beat("ingest", int(follower.rows_seen))

    def wait_for_window() -> bool:
        """Block until the rolling window holds ``min_rows`` (False =
        stop requested). Used for the first window AND to refill after
        a condemned-window rollback drops the poisoned rows."""
        while True:
            drain()
            if window is not None and len(window) >= spec.min_rows:
                return True
            if stop is not None and stop.is_set():
                return False
            heartbeat.beat("waiting_for_rows",
                           0 if window is None else len(window))
            time.sleep(spec.poll_sec)

    # first window: wait for min_rows (resume re-reads the stream tail —
    # the window itself is deliberately NOT checkpointed; fresh rows are
    # strictly better training data than the dead trainer's snapshot)
    if not wait_for_window():
        return 0

    def commit(booster) -> None:
        state = ckpt.booster_state(booster, iteration)
        state[STATE_KEY] = {
            "watermark_rows": int(follower.rows_seen),
            "watermark_ts": float(follower.last_row_time or time.time()),
            "stream_offset": int(follower.offset),
            "window_rows": int(len(window)),
            "window_rows_target": int(win_rows),
            "skipped_rows": int(follower.rows_skipped),
        }
        # keep_last rides into the writer for the ENOSPC survival path
        # (ISSUE 19): a full disk prunes beyond the retention floor and
        # retries the write ONCE before giving up
        ckpt.write_checkpoint(spec.ckpt_dir, state,
                              keep_last=spec.keep_last)
        ckpt.prune_checkpoints(spec.ckpt_dir, spec.keep_last)

    last_commit = iteration
    while True:
        if stop is not None and stop.is_set():
            return 0
        if spec.target_iterations and iteration >= spec.target_iterations:
            log.info(f"resident trainer reached the "
                     f"{spec.target_iterations}-iteration target")
            return EXIT_TARGET_REACHED
        drain()
        heartbeat.beat("cycle", iteration)
        k = spec.iters_per_cycle
        if spec.target_iterations:
            k = min(k, spec.target_iterations - iteration)
        try:
            faults.maybe_fail("oom")       # the re-bin oom site
            X, y = _split_window(window, spec.label_col)
            ds = lgb.Dataset(X, label=y)
            init = lgb.Booster(model_str=model_str) \
                if model_str is not None else None
            booster = lgb.train(dict(params), ds,
                                num_boost_round=k, init_model=init)
        except BaseException as e:  # noqa: BLE001 — classifier decides
            if is_corruption_error(e):
                # numeric-health rollback (ISSUE 19): the cycle was
                # refused as DATA_CORRUPTION (NaN gradients, poisoned
                # leaves, a loss spike). Roll back to the newest CRC-
                # valid checkpoint — the publish channel never saw the
                # poisoned trees — and retry; a second consecutive
                # refusal condemns the window and resumes past it.
                corrupt_cycles += 1
                found = ckpt.latest_valid_checkpoint(spec.ckpt_dir)
                if found is not None:
                    model_str = found[1]["model"]
                    iteration = int(found[1]["iteration"])
                else:
                    model_str, iteration = None, 0
                last_commit = iteration
                log.warning(
                    f"resident trainer cycle refused as corrupt ({e}); "
                    "rolled back to the newest CRC-valid checkpoint "
                    f"(iteration {iteration})")
                if corrupt_cycles >= 2:
                    log.warning(
                        "second consecutive corrupt cycle: condemning "
                        f"the {len(window)}-row rolling window and "
                        "resuming past it on fresh stream rows")
                    window = None
                    corrupt_cycles = 0
                    if not wait_for_window():
                        return 0
                continue
            # window auto-shrink (ISSUE 17): an OOM'd re-bin/train
            # cycle halves the rolling window down to the floor and
            # keeps publishing — freshness regression, never a crash
            # loop. At the floor a genuine exhaustion is re-raised.
            if not is_oom_error(e) or win_rows <= win_floor:
                raise
            win_rows = max(win_rows // 2, win_floor)
            ok_cycles = 0
            if len(window) > win_rows:
                window = window[-win_rows:]
            if not shrink_warned:
                shrink_warned = True
                log.warning(
                    f"resident trainer cycle OOM'd ({e!r}); rolling "
                    f"window halved to {win_rows} rows (floor "
                    f"{win_floor}) — training continues on less "
                    "history; the window grows back when pressure "
                    "clears (warned once)")
            else:
                log.info(f"trainer cycle OOM'd again; window now "
                         f"{win_rows} rows")
            continue
        iteration = booster.current_iteration()
        model_str = booster.model_to_string()
        corrupt_cycles = 0
        if win_rows < spec.window_rows:
            # pressure-clear recovery: grow the window back after a
            # few consecutive clean cycles
            ok_cycles += 1
            if ok_cycles >= 4:
                ok_cycles = 0
                win_rows = min(win_rows * 2, int(spec.window_rows))
                log.info(f"memory pressure cleared: rolling window "
                         f"grown back to {win_rows} rows")
        if iteration - last_commit >= spec.publish_every_iters or \
                (spec.target_iterations and
                 iteration >= spec.target_iterations):
            commit(booster)
            last_commit = iteration
        if on_cycle is not None:
            on_cycle(iteration, follower)
        # pace the loop only when the stream is dry (fresh rows pending
        # should be trained on, not slept through)
        try:
            dry = os.path.getsize(spec.stream_path) <= follower.offset
        except OSError:
            dry = True
        if dry:
            if stop is not None:
                if stop.wait(spec.poll_sec):
                    return 0
            else:
                time.sleep(spec.poll_sec)


class ThreadTrainer:
    """In-process resident trainer (tests, single-process deployments,
    the <30 s service smoke). Crash domain == serving process; use
    :class:`TrainerSupervisor` when a trainer death must not take the
    front door down."""

    def __init__(self, spec: TrainerSpec):
        self.spec = spec
        self._stop = threading.Event()
        self.error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="lgbm-resident-trainer")
        self._thread.start()

    def _run(self) -> None:
        try:
            run_resident_trainer(self.spec, stop=self._stop)
        except BaseException as e:     # noqa: BLE001 — surfaced in stats
            self.error = e
            log.warning(f"resident trainer thread died: {e!r}")

    @property
    def alive(self) -> bool:
        return self._thread.is_alive()

    @property
    def relaunches(self) -> int:
        return 0

    def describe(self) -> dict:
        d = {"mode": "thread", "alive": self.alive, "relaunches": 0}
        if self.error is not None:
            d["error"] = repr(self.error)
        return d

    def stop(self, timeout: float = 30.0) -> None:
        self._stop.set()
        self._thread.join(timeout)


class TrainerSupervisor:
    """Supervised subprocess trainer with bounded auto-relaunch — the
    PR10 gang discipline applied to one resident rank.

    The child runs :func:`run_resident_trainer` under the ISSUE 4
    heartbeat; the supervisor watches it with the shared
    :class:`~..robustness.supervisor.watch_child` (phase-aware stall
    classification, SIGTERM-never-SIGKILL). Any death — crash, injected
    ``rank_kill``, classified stall — costs one relaunch that resumes
    from the newest committed checkpoint, up to ``max_relaunches``
    (``LGBM_TPU_TRAINER_RELAUNCHES``, default 2) attempts; the serving
    tier keeps answering on the last published generation throughout.

    ``attempt_env(i)`` (0-based) lets a chaos harness arm faults on one
    specific launch — e.g. ``{"LGBM_TPU_FAULTS": "rank_kill:after=2"}``
    on attempt 0 only — exactly the gang chaos idiom.
    """

    def __init__(self, spec: TrainerSpec,
                 max_relaunches: Optional[int] = None,
                 attempt_env: Optional[Callable[[int], Dict]] = None,
                 heartbeat_base: Optional[str] = None):
        from ..robustness.heartbeat import ENV_HEARTBEAT
        self.spec = spec
        if max_relaunches is None:
            max_relaunches = int(os.environ.get(
                "LGBM_TPU_TRAINER_RELAUNCHES", "2"))
        self.max_relaunches = int(max_relaunches)
        self._attempt_env = attempt_env
        self._hb_env = ENV_HEARTBEAT
        self._hb_base = heartbeat_base or os.path.join(
            spec.ckpt_dir, "trainer.hb")
        self.relaunches = 0
        self.attempt = 0
        self.last_rc: Optional[int] = None
        self.error: Optional[BaseException] = None
        self._stop = threading.Event()
        self._proc: Optional[subprocess.Popen] = None
        self._lock = threading.Lock()
        os.makedirs(spec.ckpt_dir, exist_ok=True)
        self._thread = threading.Thread(
            target=self._supervise, daemon=True,
            name="lgbm-trainer-supervisor")
        self._thread.start()

    # -- child management ---------------------------------------------
    def _hb_path(self, attempt: int) -> str:
        # fresh file per attempt: a dead attempt's stale beats must
        # never be classified as this attempt's liveness (PR10 lesson)
        return f"{self._hb_base}.{attempt}"

    @staticmethod
    def _child_platform_env() -> dict:
        """Where the child trains. A caller-set ``JAX_PLATFORMS`` is
        inherited as is. Unset, the child gets jax's default — unless
        THIS process sits on an accelerator: a chip belongs to one
        process, the serving parent holds it, and a child that asked for
        it would fail or hang, so the child is put on the CPU and says
        so loudly (``trainer_mode="thread"`` is the one-process mode
        that trains on the chip)."""
        if os.environ.get("JAX_PLATFORMS"):
            return {}
        import jax
        backend = jax.default_backend()
        if backend == "cpu":
            return {}
        log.warning(
            "=" * 60 + f"\nresident trainer child runs on platform=cpu: "
            f"this process holds the {backend} and a chip belongs to one "
            "process. serve_continual(trainer_mode='thread') trains on "
            "the chip inside this process instead.\n" + "=" * 60)
        return {"JAX_PLATFORMS": "cpu"}

    def _launch(self) -> subprocess.Popen:
        from ..utils.jit_cache import ENV_COMPILE_CACHE, resolve_cache_dir
        env = dict(os.environ)
        env[self._hb_env] = self._hb_path(self.attempt)
        env.update(self._child_platform_env())
        # the child must import lightgbm_tpu the same way THIS process
        # did (often a bare sys.path insert, not an install): prepend
        # the package root to PYTHONPATH — never overwrite it wholesale
        pkg_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        parts = [pkg_root] + [p for p in
                              env.get("PYTHONPATH", "").split(os.pathsep)
                              if p and p != pkg_root]
        env["PYTHONPATH"] = os.pathsep.join(parts)
        # ONE persistent compile cache exported to every attempt (the
        # ISSUE 4 supervisor discipline): a relaunched trainer resumes
        # past the multi-minute grower compile instead of repaying it
        env.setdefault(ENV_COMPILE_CACHE, resolve_cache_dir())
        if self._attempt_env is not None:
            env.update({k: str(v) for k, v in
                        (self._attempt_env(self.attempt) or {}).items()})
        cmd = [sys.executable, "-m", "lightgbm_tpu.service.trainer",
               self.spec.to_json()]
        log.info(f"launching resident trainer (attempt {self.attempt}) "
                 f"on platform={env.get('JAX_PLATFORMS') or 'jax default'}")
        # stderr lands in the checkpoint dir, not DEVNULL: a child that
        # dies before its first heartbeat must leave a diagnosable trace
        self._err_path = os.path.join(
            self.spec.ckpt_dir, f"trainer.{self.attempt}.err")
        errf = open(self._err_path, "wb")
        try:
            return subprocess.Popen(cmd, env=env,
                                    stdout=subprocess.DEVNULL,
                                    stderr=errf)
        finally:
            errf.close()          # the child holds its own fd

    def _supervise(self) -> None:
        from ..robustness.heartbeat import DeviceStallError, StallPolicy
        from ..robustness.supervisor import watch_child
        policy = StallPolicy.from_env()
        while not self._stop.is_set():
            with self._lock:
                if self._stop.is_set():
                    return
                self._proc = proc = self._launch()
            try:
                rc = watch_child(proc, self._hb_path(self.attempt),
                                 policy=policy, poll=0.5,
                                 label="resident trainer")
            except DeviceStallError as e:
                rc = None
                self.error = e
            self.last_rc = rc
            if self._stop.is_set():
                return
            if rc == 0:
                return                      # target reached: clean exit
            if self.relaunches >= self.max_relaunches:
                log.warning(
                    f"resident trainer died (rc={rc}) with no relaunch "
                    f"budget left ({self.relaunches}/"
                    f"{self.max_relaunches}); serving continues on the "
                    "last published generation")
                return
            self.relaunches += 1
            self.attempt += 1
            log.warning(f"resident trainer died (rc={rc}); relaunching "
                        f"({self.relaunches}/{self.max_relaunches}) — "
                        "resume from the newest committed checkpoint")

    @property
    def alive(self) -> bool:
        if self._thread.is_alive():
            return True
        p = self._proc
        return p is not None and p.poll() is None

    def describe(self) -> dict:
        d = {"mode": "process", "alive": self.alive,
             "relaunches": self.relaunches, "attempt": self.attempt}
        if self.last_rc is not None:
            d["last_rc"] = self.last_rc
        if self.error is not None:
            d["error"] = repr(self.error)
        err_path = getattr(self, "_err_path", None)
        if err_path and not self.alive:
            try:
                with open(err_path, "rb") as f:
                    tail = f.read()[-2048:].decode("utf-8", "replace")
                if tail.strip():
                    d["stderr_tail"] = tail.strip()[-500:]
            except OSError:
                pass
        return d

    def stop(self, timeout: float = 30.0) -> None:
        from ..robustness.supervisor import terminate_gently
        self._stop.set()
        with self._lock:
            proc = self._proc
        if proc is not None and proc.poll() is None:
            terminate_gently(proc, timeout, "resident trainer")
        self._thread.join(timeout)


def main(argv: Optional[List[str]] = None) -> int:
    """Child entry: ``python -m lightgbm_tpu.service.trainer '<spec json>'``
    (or a path to a spec file)."""
    argv = argv if argv is not None else sys.argv[1:]
    if len(argv) != 1:
        print("usage: python -m lightgbm_tpu.service.trainer "
              "<spec-json-or-path>", file=sys.stderr)
        return 2
    blob = argv[0]
    if os.path.exists(blob):
        with open(blob, encoding="utf-8") as f:
            blob = f.read()
    spec = TrainerSpec.from_json(blob)
    return run_resident_trainer(spec)


if __name__ == "__main__":
    sys.exit(main())
