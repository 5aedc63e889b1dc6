"""Runtime dispatch guards for the training hot path (opt-in).

jaxlint (the static side of this subsystem) catches what the AST can see;
these guards catch the same hazard classes at runtime:

- :class:`CompileCounter` / :func:`compile_budget` count jit retrace/
  lower events, so a training loop that recompiles per iteration fails
  its budget instead of silently running 100x slow. Counting hooks the
  "Compiling <name> ..." records jax's lowering path emits (logger
  ``jax._src.interpreters.pxla``) — persistent-XLA-cache hits
  still lower, so the count reflects Python-level retraces, which is
  exactly the per-iteration recompile signal.
- :func:`no_implicit_transfers` wraps ``jax.transfer_guard("disallow")``:
  implicit device->host syncs (``float(arr)``, ``arr.item()``,
  ``np.asarray(arr)`` — ``__array__`` counts as implicit) raise, while
  explicit ``jax.device_get`` / ``jax.device_put`` stay allowed — the
  deliberate fetches in models/gbdt.py (_flush_pending,
  _async_stop_check) go through ``jax.device_get`` and keep working.
- :func:`install_from_env` wires both process-wide from the
  ``LGBM_TPU_GUARDS`` env var (``1``/``log`` = log mode, ``strict`` =
  disallow implicit transfers; ``LIGHTGBM_TPU_GUARDS`` is an alias).
  lightgbm_tpu/__init__.py calls it at import, so any run — bench,
  scripts, tests — is audited without code changes.
- ``LGBM_TPU_GUARDS`` is comma-separable: the ``lockorder`` token
  installs the runtime lock-order tracker (:mod:`.lockorder` — pure
  stdlib, no jax) and the REMAINING tokens keep their transfer-guard
  meaning, so ``LGBM_TPU_GUARDS=lockorder,strict`` turns on both.
  ``lockorder`` alone does not initialize a backend.

jax is imported lazily: importing this module (e.g. from the jaxlint CLI
process) must not initialize a backend.
"""
from __future__ import annotations

import logging
import os
from contextlib import contextmanager
from typing import List, Optional

# pxla emits "Compiling <name> with global shapes and types ..." when a
# function is traced+lowered (DEBUG unless jax_log_compiles);
# compiler.py logs "Persistent compilation cache hit for '<name>' ..."
# when the lowered program is served from the on-disk cache instead of
# XLA-compiled (the signal the relaunch-skips-recompilation test
# asserts on). Checked against the installed jax 0.9.0.
_COMPILE_LOGGERS = ("jax._src.interpreters.pxla", "jax._src.compiler")


class CompileBudgetExceeded(AssertionError):
    """Raised by compile_budget() — AssertionError so pytest renders it as
    a plain test failure, not an error."""


class CompileCounter(logging.Handler):
    """Context manager counting jit retrace/lower events while active.

    ``names`` records what compiled (eager primitive ops appear under
    their primitive name, e.g. "broadcast_in_dim"; jitted functions under
    their function name). After a warmed-up training loop ANY event is a
    recompile symptom, so the budget tests count them all.
    """

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.names: List[str] = []
        # programs served from the PERSISTENT on-disk cache while
        # active: these lowered (so they appear in ``names`` too) but
        # did NOT pay an XLA compile — the warm-relaunch signal
        self.cache_hits: List[str] = []
        self._saved = []

    @property
    def count(self) -> int:
        return len(self.names)

    def emit(self, record: logging.LogRecord) -> None:
        try:
            msg = record.getMessage()
        except Exception:
            return
        if msg.startswith("Compiling "):
            self.names.append(msg.split(" ", 2)[1])
        elif msg.startswith("Persistent compilation cache hit"):
            # "Persistent compilation cache hit for '<name>' with key …"
            try:
                self.cache_hits.append(msg.split("'", 2)[1])
            except IndexError:
                self.cache_hits.append(msg)

    def __enter__(self) -> "CompileCounter":
        # when the user asked for the compile audit (jax_log_compiles,
        # e.g. via LGBM_TPU_GUARDS), records must keep flowing to their
        # handlers even while we count — only silence the DEBUG spray
        # that exists solely because of our own level lowering
        keep_propagating = False
        try:
            import jax
            keep_propagating = bool(jax.config.jax_log_compiles)
        except Exception:
            pass
        for name in _COMPILE_LOGGERS:
            lg = logging.getLogger(name)
            self._saved.append((lg, lg.level, lg.propagate))
            lg.addHandler(self)
            lg.setLevel(logging.DEBUG)
            if not keep_propagating:
                lg.propagate = False
        return self

    def __exit__(self, *exc) -> None:
        for lg, level, prop in self._saved:
            lg.removeHandler(self)
            lg.setLevel(level)
            lg.propagate = prop
        self._saved.clear()


@contextmanager
def compile_budget(max_compiles: int, where: str = ""):
    """Fail (CompileBudgetExceeded) if the block compiles more than
    ``max_compiles`` distinct programs. Use AFTER a warmup pass: a warmed
    steady-state training loop should sit at ~0.

        with compile_budget(2, "train_one_iter x5"):
            for _ in range(5):
                booster.update()
    """
    with CompileCounter() as counter:
        yield counter
    if counter.count > max_compiles:
        label = f" in {where}" if where else ""
        raise CompileBudgetExceeded(
            f"compile budget exceeded{label}: {counter.count} "
            f"compilation(s) > budget {max_compiles}; compiled: "
            f"{counter.names[:12]}"
            + (" ..." if counter.count > 12 else ""))


@contextmanager
def no_implicit_transfers():
    """Disallow implicit device<->host transfers inside the block.

    ``float(arr)`` / ``arr.item()`` / ``np.asarray(arr)`` raise
    XlaRuntimeError (jax treats the ``__array__`` protocol as an IMPLICIT
    transfer); only explicit ``jax.device_get``/``device_put`` stay
    allowed, so deliberate materialization points must use those — as
    models/gbdt.py's batched fetches do.
    """
    import jax
    with jax.transfer_guard("disallow"):
        yield


def install_from_env(env=None) -> bool:
    """Process-wide guards from ``LGBM_TPU_GUARDS`` (returns True if on).

    - ``1`` / ``log``: log-mode transfer guard + jax_log_compiles — every
      implicit transfer and every compile shows up on stderr.
    - ``strict`` / ``disallow``: implicit transfers RAISE (the training
      hot path must be transfer-free); compiles are logged.
    - ``lockorder`` (combinable: ``lockorder,strict``): install the
      runtime lock-order tracker over the instrumented threaded modules
      — pure stdlib, raises LockOrderViolation at the acquisition that
      closes an inversion cycle. This token alone never imports jax.
    """
    tokens = _guard_tokens(env)
    on = False
    if "lockorder" in tokens:
        # BEFORE any jax work and before package submodules import, so
        # their module-level locks are created through the patched
        # factories
        from . import lockorder
        lockorder.install()
        on = True
    mode = guard_mode(env)
    if mode is None:
        return on
    import jax
    jax.config.update("jax_transfer_guard", mode)
    jax.config.update("jax_log_compiles", True)
    return True


def _guard_tokens(env=None) -> List[str]:
    e = env if env is not None else os.environ
    val = (e.get("LGBM_TPU_GUARDS") or
           e.get("LIGHTGBM_TPU_GUARDS") or "").strip().lower()
    return [t.strip() for t in val.split(",") if t.strip()]


def guard_mode(env=None) -> Optional[str]:
    """The LGBM_TPU_GUARDS transfer-guard mode install_from_env applies
    (the ``lockorder`` token is orthogonal and ignored here).

    ``LIGHTGBM_TPU_GUARDS`` is honored as an alias so the toggle also
    answers to the package's established env-var prefix
    (LIGHTGBM_TPU_PLATFORM / LIGHTGBM_TPU_DEBUG_CHECKS)."""
    tokens = [t for t in _guard_tokens(env) if t != "lockorder"]
    if not tokens or tokens[0] in ("0", "false", "off", "no"):
        return None
    return ("disallow" if any(t in ("strict", "disallow", "2")
                              for t in tokens) else "log")
