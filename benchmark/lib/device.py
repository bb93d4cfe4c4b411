"""The device a run is on: the look for a chip, the compile cache's place,
peak memory, and compilations counted inside the window."""

from __future__ import annotations

import os
import sys
import threading


def prepare_environment(repo: str) -> None:
    """Before JAX or the program is imported: keep every cache and every
    file the program writes inside the checkout, at fixed paths."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(repo, ".xla_cache"))
    work = os.path.join(repo, ".bench_work")
    os.environ.setdefault("PIO_FS_BASEDIR", os.path.join(work, "store"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.makedirs(os.environ["PIO_FS_BASEDIR"], exist_ok=True)


def device_info() -> dict:
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def require_chip(chips: int) -> dict:
    """No accelerator, or fewer chips than the cell asks for: no result."""
    try:
        info = device_info()
    except RuntimeError as e:
        sys.exit(f"benchmark: no accelerator: {e}")
    if info["platform"] != "tpu" or info["count"] < chips:
        sys.exit(f"benchmark: the cell needs {chips} TPU chip(s); JAX "
                 f"reports {info['count']} x {info['kind']} "
                 f"({info['platform']})")
    return info


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip; 0 where the backend does not
    report it (the CPU)."""
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileCounter:
    """Programs that reach the backend compiler, counted from
    jax.monitoring's own events. One answered by the persistent cache fires
    the event too: a shape met for the first time inside the window counts
    whether it compiles or loads."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0
        self.seconds = 0.0
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, *a, **kw):
        if name == self._EVENT:
            with self._lock:
                self.count += 1
                self.seconds += float(secs)

    def snapshot(self) -> tuple[int, float]:
        with self._lock:
            return self.count, self.seconds
