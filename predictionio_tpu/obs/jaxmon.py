"""JAX runtime telemetry: host<->device bytes, device memory.

ISSUE 2 tentpole piece 3. TPU-scale systems (ALX, arxiv 2112.02194)
make per-stage transfer accounting a first-class metric because the
host<->device link — not the MXU — can bound fold-in and serve
latency. Two instruments, on the process-wide registry so both HTTP
servers' ``/metrics`` expose them (compiles are obs/costmon's, whose
listener ``install()`` puts in place: the process has one):

- **transfer byte counters** incremented by the code paths that
  actually move data (``utils/device_cache.cached_put``, the ALS
  plan upload, ``utils/arrays.to_host``), so fold-in's per-tick upload
  cost (the ROADMAP open item) is measurable per tick via
  ``h2d_delta()`` around a solve;
- **device memory gauges** sampled from ``Device.memory_stats()`` at
  collect time (TPU/GPU report ``bytes_in_use``/``bytes_limit``; CPU
  devices report nothing and render no samples). Reading them
  initializes the backend, so only a process that already owns the
  device registers them (``install_device_memory_gauge``: the trainer
  and the engine server) — a scrape of the event server's or the
  dashboard's ``/metrics`` must never create a TPU client.

``install()`` is idempotent and never touches a backend.
"""

from __future__ import annotations

import threading

from predictionio_tpu.obs.metrics import get_registry

_lock = threading.Lock()
_installed = False
_m_h2d = None
_m_d2h = None
# per-thread upload accounting: lets a caller price ITS OWN uploads
# (the fold tick) without attributing a concurrent /reload's or
# serving cache-miss's bytes on another thread to itself
_tls = threading.local()


def _register_metrics(reg):
    """One-time family registration — runs once per process under
    ``install()``'s flag+lock, never per request (COST003 init-time)."""
    global _m_h2d, _m_d2h
    _m_h2d = reg.counter(
        "pio_jax_host_to_device_bytes_total",
        "Bytes uploaded host->device by instrumented paths "
        "(model tables, solve plans, fold-in uploads)")
    _m_d2h = reg.counter(
        "pio_jax_device_to_host_bytes_total",
        "Bytes fetched device->host by instrumented paths "
        "(model gathers, predict results)")


def install(registry=None):
    """Register the transfer counters on the process registry (or
    ``registry``) and the compile listener (obs/costmon). Idempotent;
    initializes no backend."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
        _register_metrics(registry or get_registry())
    from predictionio_tpu.obs import costmon
    costmon.install()


def install_device_memory_gauge(registry=None):
    """Register ``pio_jax_device_memory_bytes`` — for processes that
    own the device (``pio train``, the engine server). Collecting it
    calls ``jax.local_devices()``, which would initialize a backend in
    a process that has none, so host-only servers never register it."""
    (registry or get_registry()).gauge_func(
        "pio_jax_device_memory_bytes",
        "Per-device memory from Device.memory_stats() "
        "(kind=bytes_in_use|bytes_limit; absent on CPU backends)",
        _device_memory_samples)


_MEMORY_KINDS = ("bytes_in_use", "bytes_limit", "peak_bytes_in_use")


def device_memory() -> dict:
    """``{"tpu:0": {"bytes_in_use": ..., "bytes_limit": ...,
    "peak_bytes_in_use": ...}}`` for every local device whose backend
    reports ``memory_stats()`` (CPU devices report nothing). Touches
    the backend: only for processes that own the device."""
    import jax
    out = {}
    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats:
            out[f"{d.platform}:{d.id}"] = {
                k: int(stats[k]) for k in _MEMORY_KINDS if k in stats}
    return out


def _device_memory_samples():
    return [({"device": dev, "kind": kind}, float(v))
            for dev, kinds in device_memory().items()
            for kind, v in kinds.items()]


def _ensure():
    if not _installed:
        install()


def record_h2d(nbytes: int):
    """Count an instrumented host->device upload."""
    if nbytes:
        _ensure()
        _m_h2d.inc(float(nbytes))
        _tls.h2d = getattr(_tls, "h2d", 0.0) + float(nbytes)


def record_d2h(nbytes: int):
    """Count an instrumented device->host fetch (the serve readback
    plane routes every window through here — ops/readback, ISSUE 19)."""
    if nbytes:
        _ensure()
        _m_d2h.inc(float(nbytes))
        _tls.d2h = getattr(_tls, "d2h", 0.0) + float(nbytes)


def h2d_total() -> float:
    _ensure()
    return _m_h2d.value


def thread_d2h_total() -> float:
    """Bytes fetched device->host BY THE CALLING THREAD — the d2h
    mirror of :func:`thread_h2d_total`, same delta-snapshot contract."""
    return getattr(_tls, "d2h", 0.0)


def thread_h2d_total() -> float:
    """Bytes uploaded BY THE CALLING THREAD — the scheduler snapshots
    this around a fold so its per-tick upload cost excludes concurrent
    uploads (serving cache misses, a /reload) on other threads."""
    return getattr(_tls, "h2d", 0.0)


def h2d_delta(before: float) -> float:
    """Calling thread's bytes uploaded since a prior
    ``thread_h2d_total()`` snapshot."""
    return thread_h2d_total() - before


def nbytes_of(arrays) -> int:
    """Total nbytes across a flat iterable of array-likes (items
    without ``nbytes`` count zero)."""
    total = 0
    for a in arrays:
        total += int(getattr(a, "nbytes", 0) or 0)
    return total
