"""Persistent XLA compilation cache setup (shared by engine/tests/CLI).

The grower programs for realistic shapes take minutes to compile on TPU;
a warm on-disk cache turns a retried or relaunched attempt's compile into
a file read. One helper so the cache directory convention and tuning
thresholds live in one place (the engine and ``chip_smoke.py`` route
through it).

The cache can be PLACED from outside: where ``JAX_COMPILATION_CACHE_DIR``
is set, jax already points at it and this module sets no other directory
— not from the ``tpu_compile_cache_dir`` param, not from
``LGBM_TPU_COMPILE_CACHE``. Where it is unset the cache is a fixed path
(``<checkout>/.jax_cache`` by default), never a temporary name: a
directory that moves between runs never hits.
"""
from __future__ import annotations

import os

# jax's own knob; read by jax at import. Whoever runs the program (a
# chip tool, a CI job) places the cache with it.
ENV_JAX_CACHE = "JAX_COMPILATION_CACHE_DIR"
# in-repo knob (supervisors export it to every child so retried attempts
# share one cache); consulted only where ENV_JAX_CACHE is unset
ENV_COMPILE_CACHE = "LGBM_TPU_COMPILE_CACHE"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def resolve_cache_dir(cache_dir: str | None = None,
                      env=None) -> str:
    """Resolution order: ``JAX_COMPILATION_CACHE_DIR`` (verbatim, beats
    everything), explicit argument (the ``tpu_compile_cache_dir`` config
    param routes here), ``LGBM_TPU_COMPILE_CACHE``,
    ``<checkout>/.jax_cache``."""
    e = env if env is not None else os.environ
    placed = e.get(ENV_JAX_CACHE)
    if placed:
        return placed
    return os.path.abspath(cache_dir or e.get(ENV_COMPILE_CACHE) or
                           os.path.join(_CHECKOUT, ".jax_cache"))


def enable_persistent_cache(cache_dir: str | None = None) -> str:
    """Turn on jax's persistent compilation cache at the directory
    :func:`resolve_cache_dir` names and return it. Safe to call
    repeatedly. With ``JAX_COMPILATION_CACHE_DIR`` set the directory is
    jax's own and is left alone; otherwise the last call wins, and the
    cache object is reset when the directory actually changes after
    first use (jax binds it lazily to the dir seen at the first compile,
    so a mid-process ``tpu_compile_cache_dir`` would otherwise be
    silently ignored)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    cache_dir = resolve_cache_dir(cache_dir)
    if not os.environ.get(ENV_JAX_CACHE) and \
            jax.config.jax_compilation_cache_dir != cache_dir:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return cache_dir


def enable_if_configured(cache_dir: str | None = None) -> str | None:
    """Library entry points (``engine.train``, the gbdt engine setup)
    enable the cache only when something asks for it — the
    ``tpu_compile_cache_dir`` param or either environment variable; a
    plain ``lgb.train`` with none of them leaves jax's setting alone."""
    if cache_dir or os.environ.get(ENV_JAX_CACHE) or \
            os.environ.get(ENV_COMPILE_CACHE):
        return enable_persistent_cache(cache_dir or None)
    return None
