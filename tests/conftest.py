"""Test configuration: force JAX onto CPU with 8 virtual devices so the
multi-device sharding paths run without TPU hardware (mirrors the reference's
DistributedMockup which exercises the real socket stack on localhost,
ref: tests/distributed/_test_distributed.py).

Environment notes:
- The tier-1 command passes ``JAX_PLATFORMS=cpu``; the in-process
  ``jax.config.update('jax_platforms', ...)`` below keeps a bare
  ``pytest tests/`` on the CPU too (LGBM_TPU_TEST_DEVICE overrides it).
- XLA_FLAGS must be set before the CPU client initializes (i.e., before the
  first jax operation), which conftest import-time guarantees.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

_test_platform = os.environ.get("LGBM_TPU_TEST_DEVICE", "cpu")
jax.config.update("jax_platforms", _test_platform)

# Persistent compilation cache (ISSUE 4 hermeticity rules):
# - the resolved directory is PINNED into LGBM_TPU_COMPILE_CACHE so
#   every subprocess a test spawns (bench salvage/stall children,
#   fault smokes) shares THIS run's cache instead of scribbling into
#   whatever ambient convention the child would resolve — one run, one
#   cache, no cross-talk with concurrently running suites;
# - LGBM_TPU_HERMETIC_CACHE=1 pins it to a fresh per-run tmpdir (fully
#   cold start). The default stays the shared repo cache: the tier-1
#   verify runs under a fixed wall-clock window and the measured warm
#   cache (~1000 entries) is worth tens of passed tests within it —
#   XLA cache keys hash the full HLO, so a stale entry can never serve
#   a changed program, only cost disk;
# - tests that ASSERT cache behavior (test_heartbeat.py) create their
#   own tmpdir caches and are hermetic regardless of this default.
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from lightgbm_tpu.utils.jit_cache import (ENV_COMPILE_CACHE,  # noqa: E402
                                          enable_persistent_cache)

if os.environ.get("LGBM_TPU_HERMETIC_CACHE", "").strip().lower() in \
        ("1", "true", "yes", "on"):
    os.environ[ENV_COMPILE_CACHE] = tempfile.mkdtemp(
        prefix="lgbm_tpu_compile_cache_")
os.environ[ENV_COMPILE_CACHE] = enable_persistent_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from lightgbm_tpu.analysis import guards as _guards  # noqa: E402

# Opt-in runtime dispatch guards (LGBM_TPU_GUARDS=1|log|strict): transfer
# guard + jax_log_compiles for the whole test process, so any tier-1 run
# can be audited for silent host round-trips without code changes.
# (lightgbm_tpu/__init__.py already installs them at import; this call is
# a deliberate second anchor in case the import-time hook ever moves.)
_guards.install_from_env()


_JAXLINT_STATUS = None


def _wants_jaxlint_status(config) -> bool:
    """Pay the ~5 s repo-wide AST scan only for suite-level invocations
    (directory args, as the tier-1 verify command passes `tests/`) —
    single-file / single-test dev runs skip it. LGBM_TPU_JAXLINT_STATUS
    =1/0 forces it on/off."""
    forced = os.environ.get("LGBM_TPU_JAXLINT_STATUS")
    if forced is not None:
        return forced.strip().lower() not in ("", "0", "false", "off",
                                              "no")
    args = getattr(config, "args", None) or []
    return all(os.path.isdir(a) for a in args)


def _jaxlint_status() -> str:
    """One-line jaxlint state (pure stdlib AST pass over the package,
    a few seconds; memoized so header + terminal summary share one scan)."""
    global _JAXLINT_STATUS
    if _JAXLINT_STATUS is not None:
        return _JAXLINT_STATUS
    try:
        from lightgbm_tpu.analysis import (default_baseline_path,
                                           default_targets,
                                           diff_against_baseline,
                                           load_baseline, run_paths)
        root = os.path.join(os.path.dirname(__file__), "..")
        findings = run_paths(default_targets(root), root)
        # JL000 syntax errors are never baselined — count them as new so
        # this line agrees with the scripts/jaxlint.py gate's exit code
        baseline = load_baseline(default_baseline_path(root))
        new, known = diff_against_baseline(findings, baseline)
        _JAXLINT_STATUS = (f"jaxlint: {len(new)} new finding(s), "
                           f"{len(known)} known (baselined)")
    except Exception as e:  # never break a test run over a lint status
        _JAXLINT_STATUS = f"jaxlint: status unavailable ({e!r})"
    return _JAXLINT_STATUS


def pytest_configure(config):
    # tier-1 runs with -m 'not slow'; register the marker so the
    # slow-marked tier-2 cases don't spray UnknownMarkWarnings
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 verify window (-m 'not slow'); "
        "run explicitly with -m slow or no marker filter")


_EXIT_STATUS = None


def pytest_sessionfinish(session, exitstatus):
    global _EXIT_STATUS
    _EXIT_STATUS = int(exitstatus)


@pytest.hookimpl(trylast=True)
def pytest_unconfigure(config):
    # fast exit (tier-1 window discipline): after a full suite the
    # interpreter holds multi-GB of live arrays/jit caches and the
    # ordinary teardown (GC + atexit) burns 30-120 s AFTER the summary
    # line — time the 870 s verify window still charges against rc
    # delivery. All output is flushed and every result is recorded by
    # unconfigure time, so hard-exit with the real status instead.
    # LGBM_TPU_FAST_EXIT=0 opts out (e.g. under coverage tooling).
    if os.environ.get("LGBM_TPU_FAST_EXIT", "1").strip().lower() in \
            ("0", "false", "off", "no"):
        return
    if _EXIT_STATUS is not None:
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(_EXIT_STATUS)


def pytest_report_header(config):
    if not _wants_jaxlint_status(config):
        return None
    return _jaxlint_status()


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    # also emit at the END of the run: the tier-1 verify log is tailed,
    # and `-q` suppresses the report header
    if _wants_jaxlint_status(config):
        terminalreporter.write_line(_jaxlint_status())


@pytest.fixture
def compile_budget():
    """Compile-count budget guard (lightgbm_tpu.analysis.guards).

    Usage::

        def test_steady_state(compile_budget):
            ...warmup...
            with compile_budget(2, "train x5"):
                for _ in range(5):
                    booster.update()

    Raises CompileBudgetExceeded (an AssertionError) when the block
    compiles more than the budgeted number of programs."""
    return _guards.compile_budget


@pytest.fixture
def rng():
    return np.random.default_rng(42)


GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "data", "golden")


def pack_words(rm):
    """uint8 [R, F] -> uint32 [R, ceil(F/4)], byte k of word w = column
    4w+k (the layout models/gbdt.py uploads for tpu_packed_bins)."""
    R, F = rm.shape
    W = (F + 3) // 4
    full = np.zeros((R, W * 4), np.uint8)
    full[:, :F] = rm
    return full.view(np.uint32).reshape(R, W)


def load_golden_csv(name):
    """Parse a golden CSV (label first; empty fields = missing) ->
    (labels, X). Shared by the consistency and codegen suites."""
    rows = []
    with open(os.path.join(GOLDEN_DIR, name)) as fh:
        for line in fh:
            rows.append([np.nan if v == "" else float(v)
                         for v in line.rstrip("\n").split(",")])
    arr = np.asarray(rows, np.float64)
    return arr[:, 0], arr[:, 1:]
