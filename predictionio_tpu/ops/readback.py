"""Serve readback plane: packed payloads + overlapped d2h (ISSUE 19).

The live TPU capture said serving lost to the READBACK, not the
compute: ``d2h_floor_ms`` is 74.8 ms — a fixed device→host latency
paid once per serve window while the device idles — and it is
IDENTICAL for 40-byte and 400KB payloads (latency-bound, not
bandwidth-bound). Two conclusions, both implemented here:

* **Fewer walls.** One contiguous on-device payload per window instead
  of two full-width arrays: int32 ids + float16-quantized scores,
  ``k x batch x 6`` bytes (:func:`pack_device`, fused INSIDE the
  jitted serve kernels so the AOT bucket's output aval IS the packed
  array and steady-state packing compiles nothing). Even with packing
  off, the begin/finish closures route both result arrays through ONE
  :func:`begin_fetch` call — one d2h wall per window, never two.
* **Overlapped walls.** :func:`begin_fetch` initiates
  ``copy_to_host_async()`` at DISPATCH time, on the formation thread —
  the transfer rides behind the device compute and behind neighboring
  windows' completions. The finish() closure only *waits* on an
  already-in-flight copy, so with ``PIO_SERVE_INFLIGHT`` >= 3 the K
  in-flight windows' d2h walls overlap instead of serialize (the d2h
  dual of the PR 16 ``DeviceStager`` h2d slots in dataplane/upload.py:
  each in-flight window holds its own device output slot, bounded by
  the executor's inflight semaphore).

This module is the ONE sanctioned serve d2h site (the d2h mirror of
``ops/staging.py`` for h2d): it lives in the ops layer so the
pipelined modules (serving/, tenancy/, dataplane/) stay host-sync-free
(the JAX006 contract), and every byte it moves is attributed —
``jaxmon.record_d2h``, ``pio_serve_d2h_seconds_total{phase}``,
``pio_serve_d2h_bytes_total``, per-tenant bytes via the obs-plane
tenant context, and a module snapshot (:func:`stats_snapshot`) that
bench turns into ``serve_d2h_overlap_frac`` /
``serve_readback_bytes_per_window``.

Env gates:

* ``PIO_SERVE_PACK=on`` (default) — f16-quantized packed payloads.
* ``PIO_SERVE_PACK=exact`` — packed single payload, full f32 scores
  (8 bytes/slot): one wall, bit-exact scores.
* ``PIO_SERVE_PACK=off`` — legacy two-array results (still fetched
  through one overlapped wall).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Optional, Tuple

import numpy as np

from predictionio_tpu.obs import TRACER, jaxmon, tenantctx
from predictionio_tpu.obs.metrics import get_registry

# -- pack modes (the AOT bucket dim ``p``) -------------------------------

#: legacy two-array results (scores f32 + ids i32, two avals)
PACK_OFF = 0
#: one uint8 payload per window: i32 ids + f16 scores = 6 bytes/slot
PACK_F16 = 1
#: one uint8 payload per window: i32 ids + f32 scores = 8 bytes/slot
PACK_EXACT = 2

#: bytes per (id, score) slot by pack mode
SLOT_BYTES = {PACK_F16: 6, PACK_EXACT: 8}


def pack_flag() -> int:
    """The pack mode serving currently runs under — read per dispatch
    (cheap) so tests and operators can flip ``PIO_SERVE_PACK`` live.
    The value rides the bucket dims as ``p``, so each mode owns its own
    AOT programs and flipping modes never invalidates warmed buckets of
    the other."""
    v = os.environ.get("PIO_SERVE_PACK", "on").strip().lower()
    if v in ("off", "0", "false", "no"):
        return PACK_OFF
    if v == "exact":
        return PACK_EXACT
    return PACK_F16


# -- device-side pack (called INSIDE jitted serve kernels) ---------------

def pack_device(scores, idx, p: int):
    """Fuse ``(scores [B,K] f32, idx [B,K] i32)`` into one contiguous
    ``[B, K, slot]`` uint8 payload ON DEVICE — ranking happened before
    this point, so ids are byte-identical to the unpacked path; scores
    are f16-quantized under :data:`PACK_F16` (wire format: 4 id bytes
    then 2 or 4 score bytes per slot, device-native little-endian).
    Must be traced inside the serve kernel's jit so the executable
    emits the packed aval directly (one output buffer, one transfer)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    with jax.named_scope("pio.serve.pack"):
        ids8 = lax.bitcast_convert_type(idx.astype(jnp.int32), jnp.uint8)
        if p == PACK_EXACT:
            sc8 = lax.bitcast_convert_type(scores.astype(jnp.float32),
                                           jnp.uint8)
        else:
            sc8 = lax.bitcast_convert_type(scores.astype(jnp.float16),
                                           jnp.uint8)
        return jnp.concatenate([ids8, sc8], axis=-1)


def unpack_host(buf: np.ndarray, p: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side inverse of :func:`pack_device`: ``[B, K, slot]`` uint8
    → ``(scores f32 [B,K], idx i32 [B,K])``. Pure numpy views + one
    cast — no device interaction (the payload already crossed in
    :func:`begin_fetch`'s single wall). f16 scores upcast to f32 so
    downstream finite-filters and serialization see the usual dtype."""
    b = np.asarray(buf)
    ids = np.ascontiguousarray(b[..., :4]).view(np.int32)[..., 0]
    if p == PACK_EXACT:
        sc = np.ascontiguousarray(b[..., 4:8]).view(np.float32)[..., 0]
    else:
        sc = np.ascontiguousarray(
            b[..., 4:6]).view(np.float16)[..., 0].astype(np.float32)
    return sc, ids


# -- the instrumented overlapped d2h site --------------------------------

class _Stats:
    """Cumulative readback accounting (process-global, lock-guarded).

    ``span_s`` is wall time from copy initiation to fetch completion;
    ``submit_s + wait_s`` is the part of it a thread was actually
    blocked. Their ratio is the overlap fraction: ~0 when completions
    serialize their full readback (the pre-ISSUE-19 behavior), →1 when
    the copy finished behind other windows' work and the completion
    thread only picked up bytes already on the host."""

    def __init__(self):
        self.lock = threading.Lock()
        self.windows = 0
        self.bytes = 0
        self.submit_s = 0.0
        self.wait_s = 0.0
        self.span_s = 0.0


_STATS = _Stats()
_TLS = threading.local()
_metrics_lock = threading.Lock()
_metrics = {}


def _get_metrics():
    with _metrics_lock:
        if not _metrics:
            reg = get_registry()
            _metrics["seconds"] = reg.counter(
                "pio_serve_d2h_seconds_total",
                "Serve readback device->host seconds by phase "
                "(submit = async-copy initiation, wait = blocked "
                "completion wait)", labelnames=("phase",))
            _metrics["bytes"] = reg.counter(
                "pio_serve_d2h_bytes_total",
                "Serve readback bytes fetched device->host")
            _metrics["windows"] = reg.counter(
                "pio_serve_readback_windows_total",
                "Serve windows fetched through the readback plane")
            _metrics["tenant_bytes"] = reg.counter(
                "pio_tenant_serve_d2h_bytes_total",
                "Serve readback bytes by tenant",
                labelnames=("tenant",))
        return _metrics


def thread_wait_s() -> float:
    """Seconds THIS thread has spent blocked inside :func:`begin_fetch`
    waits, cumulative. The pipelined executor samples the delta around
    ``finish()`` to decompose its completion stage into wait-for-copy
    vs post-process without itself touching a device handle (JAX006)."""
    return getattr(_TLS, "wait_s", 0.0)


def thread_ready_t() -> float:
    """``time.perf_counter()`` at which THIS thread's last readback wait
    returned (0.0 before the first): the d2h-ready instant of the
    serving account's dispatch record."""
    return getattr(_TLS, "t_ready", 0.0)


def thread_d2h_bytes() -> int:
    """Bytes THIS thread has fetched through the readback plane,
    cumulative — same delta-sampling contract as :func:`thread_wait_s`."""
    return getattr(_TLS, "bytes", 0)


def begin_fetch(*arrays, tenant: Optional[str] = None
                ) -> Callable[[], Tuple[np.ndarray, ...]]:
    """Initiate the device→host copy of ``arrays`` NOW (async,
    non-blocking — call this on the dispatch/formation thread right
    after enqueueing the serve kernel) and return a ``wait()`` callable
    that blocks until the bytes are on the host and returns them as
    numpy arrays, attributing seconds/bytes to the obs plane.

    Passing MULTIPLE arrays still costs one d2h wall: every copy is
    in flight before the first wait starts, so the transfers overlap
    each other (this is the packing-off fusion path). The per-window
    device outputs double-buffer naturally — each in-flight window
    owns its own output slot until its ``wait()`` drains it, bounded
    by the executor's ``PIO_SERVE_INFLIGHT`` semaphore."""
    if tenant is None:
        tenant = tenantctx.current_tenant()
    t0 = time.perf_counter()
    for a in arrays:
        start = getattr(a, "copy_to_host_async", None)
        if start is not None:
            start()
    submit_s = time.perf_counter() - t0

    def wait() -> Tuple[np.ndarray, ...]:
        t1 = time.perf_counter()
        with TRACER.region("readback.wait"):
            host = tuple(np.asarray(a) for a in arrays)
        t2 = time.perf_counter()
        wait_s = t2 - t1
        _TLS.t_ready = t2
        nbytes = sum(int(h.nbytes) for h in host)
        _TLS.wait_s = getattr(_TLS, "wait_s", 0.0) + wait_s
        _TLS.bytes = getattr(_TLS, "bytes", 0) + nbytes
        jaxmon.record_d2h(nbytes)
        m = _get_metrics()
        m["seconds"].labels(phase="submit").inc(submit_s)
        m["seconds"].labels(phase="wait").inc(wait_s)
        m["bytes"].inc(nbytes)
        m["windows"].inc()
        if tenant:
            m["tenant_bytes"].labels(tenant=str(tenant)).inc(nbytes)
        with _STATS.lock:
            _STATS.windows += 1
            _STATS.bytes += nbytes
            _STATS.submit_s += submit_s
            _STATS.wait_s += wait_s
            _STATS.span_s += t2 - t0
        return host
    return wait


def begin_fetch_packed(packed, p: int, tenant: Optional[str] = None
                       ) -> Callable[[], Tuple[np.ndarray, np.ndarray]]:
    """:func:`begin_fetch` + :func:`unpack_host` in one closure: the
    shape every packed serve path wants — async copy initiated now,
    ``wait() -> (scores, idx)`` host arrays later."""
    fetch = begin_fetch(packed, tenant=tenant)

    def wait() -> Tuple[np.ndarray, np.ndarray]:
        (buf,) = fetch()
        return unpack_host(buf, p)
    return wait


def stats_snapshot() -> dict:
    """Cumulative readback counters + derived overlap fraction — bench
    diffs two snapshots around its timed phase to report
    ``serve_d2h_overlap_frac`` and ``serve_readback_bytes_per_window``."""
    with _STATS.lock:
        s = {"windows": _STATS.windows, "bytes": _STATS.bytes,
             "submit_s": _STATS.submit_s, "wait_s": _STATS.wait_s,
             "span_s": _STATS.span_s}
    s["overlap_frac"] = overlap_frac(s)
    return s


def overlap_frac(snap: dict, base: Optional[dict] = None) -> float:
    """Fraction of the readback span hidden behind other work:
    ``1 - blocked/span`` over ``snap`` (optionally minus a ``base``
    snapshot). 1.0 for an empty window (nothing exposed, nothing to
    hide — the DeviceStager convention)."""
    keys = ("submit_s", "wait_s", "span_s")
    d = {k: snap[k] - (base[k] if base else 0.0) for k in keys}
    if d["span_s"] <= 0.0:
        return 1.0
    return max(0.0, min(1.0, 1.0 - (d["submit_s"] + d["wait_s"])
                        / d["span_s"]))
