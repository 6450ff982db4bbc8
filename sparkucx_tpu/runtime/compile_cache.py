"""Persistent XLA compile cache — the production conf seam.

The exchange steps cost minutes of XLA compile per fresh process, so
``service.connect()`` + ``warmup()`` would re-pay that on every restart
without a persistent cache. This module is wired into
:class:`~sparkucx_tpu.runtime.node.TpuNode` init (and therefore every
``connect()``, the benchmark's set-up included):

    spark.shuffle.tpu.compile.cacheEnabled        master switch (default on)
    spark.shuffle.tpu.compile.minCompileTimeSecs  persistence threshold

Where the cache lives is decided outside the program: when
``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and that
directory IS the cache — this module sets no other. When it is not set,
the cache goes to :data:`DEFAULT_CACHE_DIR`, one fixed directory inside
the checkout derived from the package's own location (gitignored). The
directory is part of each entry's key, so a path carrying a pid, a
temp name or a time would never hit across processes.

The in-process half (shuffle/stepcache.py) sits above it: a step
signature that misses there still hits here if ANY process compiled it.

Best-effort throughout: a backend that cannot serialize programs or an
unwritable dir just logs and runs uncached — cache plumbing must never
fail a shuffle.
"""

from __future__ import annotations

import os
from typing import Optional

from sparkucx_tpu.utils.logging import get_logger

log = get_logger("runtime.compile_cache")

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir() -> str:
    """The cache directory this process uses: the env var when set,
    else the fixed in-checkout default."""
    return os.environ.get(ENV_VAR) or DEFAULT_CACHE_DIR


def configure_compile_cache(conf) -> Optional[str]:
    """Apply the conf's persistent-compile-cache keys to this process's
    jax config. Returns the active cache dir, or None when disabled or
    unavailable. Idempotent."""
    if not conf.compile_cache_enabled:
        log.debug("persistent compile cache disabled by conf")
        return None
    path = cache_dir()
    try:
        os.makedirs(path, exist_ok=True)
        import jax
        if not os.environ.get(ENV_VAR):
            jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          conf.compile_min_compile_time_secs)
        log.info("persistent compile cache at %s (minCompileTimeSecs=%s)",
                 path, conf.compile_min_compile_time_secs)
        return path
    except Exception as e:
        # never let cache plumbing cost a shuffle
        log.warning("persistent compile cache unavailable (%s); "
                    "compiles will not persist", e)
        return None


def cache_entry_count(cache_dir: str) -> int:
    """Number of persisted program entries in ``cache_dir`` (jax writes
    one ``*-cache`` file per program). 0 for a missing dir — the
    cold-start probe's before/after evidence."""
    try:
        return sum(1 for n in os.listdir(cache_dir) if n.endswith("-cache"))
    except OSError:
        return 0
