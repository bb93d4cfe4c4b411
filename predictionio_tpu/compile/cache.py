"""Managed persistent XLA compilation cache.

JAX can serialize every backend-compiled executable to disk and
deserialize it in any later process whose computation hashes the same
(``jax_compilation_cache_dir``). This module owns that cache for the
whole product:

- **Location** — one rule, placeable from outside. If
  ``$JAX_COMPILATION_CACHE_DIR`` is set, that directory, exactly, is
  the cache and no code path sets another (an ``enable_persistent_cache
  (root=...)`` argument loses to it). If it is unset, the cache is
  ``<checkout>/.xla_cache`` — a fixed path that does not depend on
  ``PIO_FS_BASEDIR``, ``$HOME``, the pid or any temp name, because the
  directory is part of JAX's cache key and a cache that moves never
  hits. ``PIO_XLA_CACHE=off`` disables.
- **Thresholds** — min-compile-time and min-entry-size are zeroed:
  the serve/fold programs this repo cares about are small and fast to
  compile on CPU but minutes on TPU; caching everything costs little
  and makes the CPU test container exercise the same code path.
- **Counters** — ``pio_compile_requests_total{executable,outcome}``
  (``cache_loaded`` / ``compiled``) and the ``cache_load`` stage of
  ``pio_compile_seconds_total``: jax fires the cache's events on the
  compiling thread, so obs/costmon's executable label attributes them
  to the dispatch scope that loaded (or compiled) the executable.

``enable_persistent_cache()`` is idempotent and safe before or after
jax's first use — config updates apply to every later compile.
"""

from __future__ import annotations

import logging
import os
import shutil
import threading
from typing import Dict, Optional

logger = logging.getLogger(__name__)

_lock = threading.Lock()
_enabled_dir: Optional[str] = None

#: the in-checkout default (listed in .gitignore)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".xla_cache")


def cache_disabled() -> bool:
    return os.environ.get("PIO_XLA_CACHE", "").lower() in (
        "off", "0", "false", "no")


def cache_dir(root: Optional[str] = None) -> str:
    """Where the cache lives: ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else ``root`` (tests pointing at their own tmp dir), else the
    in-checkout default."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR") or root
            or DEFAULT_CACHE_DIR)


def enable_persistent_cache(root: Optional[str] = None) -> Optional[str]:
    """Point jax at the persistent cache directory (:func:`cache_dir`).
    Idempotent; returns the active directory, or None when disabled."""
    global _enabled_dir
    if cache_disabled():
        return None
    target = cache_dir(root)
    if _enabled_dir == target:
        return _enabled_dir
    try:
        os.makedirs(target, exist_ok=True)
    except OSError as e:
        logger.warning("compile cache dir %s unusable (%s); compiling "
                       "without a persistent cache", target, e)
        return None
    import jax
    from jax._src import compilation_cache as _cc
    with _lock:
        if _enabled_dir != target:
            # jax latches cache usability at the FIRST compile of the
            # process (and the directory at first initialization): a
            # process that already compiled before this call — or a
            # test re-pointing the directory — must reset, or the new
            # configuration is silently ignored
            _cc.reset_cache()
            jax.config.update("jax_compilation_cache_dir", target)
            # cache EVERYTHING: the serve/fold programs are small on
            # CPU (the test container) but minutes of XLA on TPU, and
            # the acceptance tests measure the same code path on both
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", -1)
            _enabled_dir = target
    # per-executable hit/miss attribution rides costmon's label
    from predictionio_tpu.obs import costmon
    costmon.install()
    return _enabled_dir


def disable_persistent_cache() -> None:
    """Detach jax from the persistent cache (tests; an operator uses
    PIO_XLA_CACHE=off before process start instead). Safe to call when
    never enabled."""
    global _enabled_dir
    with _lock:
        if _enabled_dir is None:
            return
        import jax
        from jax._src import compilation_cache as _cc
        jax.config.update("jax_compilation_cache_dir", None)
        _cc.reset_cache()
        _enabled_dir = None


def persistent_cache_enabled() -> bool:
    return _enabled_dir is not None


def _dir_stats(path: str):
    entries = 0
    nbytes = 0
    try:
        for name in os.listdir(path):
            p = os.path.join(path, name)
            if os.path.isfile(p):
                entries += 1
                nbytes += os.path.getsize(p)
    except OSError:
        pass
    return entries, nbytes


def cache_status() -> Dict:
    """Operator view for ``pio cache status`` / ``/stats.json``."""
    from predictionio_tpu.obs import costmon
    totals = costmon.pcache_totals()
    out = {
        "enabled": persistent_cache_enabled(),
        "disabledByEnv": cache_disabled(),
        "dir": _enabled_dir,
        "entries": 0,
        "bytes": 0,
        "hits": totals["hits"],
        "misses": totals["misses"],
    }
    if _enabled_dir:
        out["entries"], out["bytes"] = _dir_stats(_enabled_dir)
    return out


def clear_cache() -> Dict:
    """Remove the cached executables (safe while processes run — jax
    re-creates entries on the next compile)."""
    target = _enabled_dir or (None if cache_disabled() else cache_dir())
    removed = nbytes = 0
    if target and os.path.isdir(target):
        removed, nbytes = _dir_stats(target)
        shutil.rmtree(target)
        # a live process keeps writing here: re-create it
        os.makedirs(target, exist_ok=True)
    return {"removed": removed, "bytes": nbytes, "dir": target}
