"""Auxiliary subsystems (SURVEY §5): jax.profiler tracing hook, the
multi-host entry points, and the generated parameter docs."""
import pytest
import os

import sys

import numpy as np

import lightgbm_tpu as lgb


@pytest.mark.slow
def test_profiler_trace_capture(rng, tmp_path):
    X = rng.normal(size=(2000, 6))
    y = X[:, 0]
    d = str(tmp_path / "trace")
    lgb.train({"objective": "regression", "verbose": -1,
               "tpu_profile_dir": d}, lgb.Dataset(X, label=y),
              num_boost_round=3)
    files = [f for _, _, fs in os.walk(d) for f in fs]
    assert any(f.endswith(".xplane.pb") for f in files)


def test_timer_table(rng):
    from lightgbm_tpu.utils.timer import global_timer
    was = global_timer.enabled
    try:
        global_timer.enabled = True
        global_timer.reset()
        X = rng.normal(size=(1000, 4))
        lgb.train({"objective": "regression", "verbose": -1},
                  lgb.Dataset(X, label=X[:, 0]), num_boost_round=2)
        table = global_timer.table()
        assert "TreeLearner::Train" in table
        assert "GBDT::Boosting" in table
    finally:
        global_timer.enabled = was
        global_timer.reset()


def test_distributed_module_surface():
    from lightgbm_tpu import distributed

    assert callable(distributed.init_distributed)
    assert callable(distributed.shutdown_distributed)
    # without init, helpers still answer for the single-process world
    assert distributed.num_processes() >= 1
    assert distributed.process_index() >= 0


def test_parameter_docs_in_sync():
    """docs/Parameters.md must regenerate identically from the registry
    (no filesystem mutation: compare against main()'s returned text)."""
    repo = os.path.join(os.path.dirname(__file__), "..")
    sys.path.insert(0, os.path.join(repo, "docs"))
    try:
        import gen_parameters
        fresh = gen_parameters.main()
    finally:
        sys.path.pop(0)
    committed = open(os.path.join(repo, "docs", "Parameters.md")).read()
    assert committed == fresh, \
        "docs/Parameters.md is stale; rerun docs/gen_parameters.py"


def test_debug_checks_env_flag(tmp_path):
    """LIGHTGBM_TPU_DEBUG_CHECKS turns on the jax sanitizers (SURVEY §5
    race/sanitizer analogue): NaN production inside jitted code fails
    loudly instead of corrupting training downstream."""
    import subprocess
    import sys
    code = (
        "import os\n"
        "os.environ['LIGHTGBM_TPU_DEBUG_CHECKS'] = '1'\n"
        "os.environ['LIGHTGBM_TPU_PLATFORM'] = 'cpu'\n"
        "import lightgbm_tpu  # activates the flags\n"
        "import jax, jax.numpy as jnp\n"
        "assert jax.config.jax_debug_nans\n"
        "assert jax.config.jax_check_tracer_leaks\n"
        "try:\n"
        "    jax.jit(lambda x: x / 0.0 * 0.0)(jnp.float32(1.0))\n"
        "except FloatingPointError:\n"
        "    print('SANITIZER-OK')\n"
    )
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=300)
    assert "SANITIZER-OK" in out.stdout, (out.stdout, out.stderr)


def test_native_artifact_is_keyed_by_source_contents(tmp_path, monkeypatch):
    """The built library's name is a hash of the sources' bytes: a
    checkout copied with fresh mtimes can never load a stale .so."""
    import shutil

    from lightgbm_tpu import native
    srcs = []
    for src in native._SRCS:
        dst = tmp_path / os.path.basename(src)
        shutil.copy(src, dst)
        srcs.append(str(dst))
    monkeypatch.setattr(native, "_SRCS", srcs)
    first = native.artifact_path()
    os.utime(srcs[0], (1, 1))                    # mtime alone: same name
    assert native.artifact_path() == first
    with open(srcs[-1], "ab") as f:
        f.write(b" ")                            # one byte: new name
    assert native.artifact_path() != first
