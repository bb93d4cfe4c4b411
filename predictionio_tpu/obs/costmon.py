"""Per-executable compile/cost attribution, and the set-up account.

ISSUE 6 tentpole piece 3; since ISSUE 37 the process's one compile
listener. This module attributes compile time to a stable **executable
label** — the handful of jitted programs the system actually runs
(``als_sweep``, ``fold_side``, ``batch_predict``, ``gates_probe``) —
which is the evidence base for the AOT/compile-cache ROADMAP item: the
label whose seconds dominate is the one to AOT-lower first.

Mechanics: call sites wrap their jit dispatch in ``executable(label)``
(or a whole phase in ``phase(label)``). jax.monitoring fires its compile
events synchronously on the compiling thread, so a contextvar label and a
thread-local request attribute each to the scope that triggered it. One
compile request is, in jax's order: the jaxpr trace
(``jaxpr_trace_duration``; nested jits report their own, inside the
outer one), the MLIR lowering (``jaxpr_to_mlir_module_duration``), the
persistent cache's ``cache_hits`` and ``cache_retrieval_time_sec`` on a
read or ``cache_misses`` once the compiled executable is written (no
event with no cache), and ``backend_compile_duration``, which holds the
read or the compile and closes the request. From them:

- ``pio_compile_seconds_total{executable, stage}``: ``trace``,
  ``lower``, ``compile`` (backend seconds less the retrieval) and
  ``cache_load`` (the retrieval);
- ``pio_compile_requests_total{executable, outcome}``: ``compiled`` or
  ``cache_loaded`` (a climbing count in steady state = shape churn on
  that executable, the classic silent TPU perf bug);
- one ``setup.compile`` record per request on ``obs.trace.TRACER``
  (``COMPILE_FIELDS``), inside a ``pio.compile`` annotation (attribute
  ``executable``) from the request's first event to its close;
- ``pio_compile_cache_hits_total{executable}`` /
  ``pio_compile_cache_misses_total{executable}``: an ``executable()``
  scope that triggered no backend compile was answered by XLA's jit
  cache.

``setup_account()`` summarizes the compile records with the upload
records ``utils/device_cache`` writes: ``/stats.json``'s ``setup``,
``als_train``'s telemetry, ``pio status --telemetry``.

``analyze_jit`` banks XLA ``cost_analysis()`` FLOPs/bytes per label
(``pio_executable_flops{executable}`` /
``pio_executable_bytes_accessed{executable}``) — explicit lowering,
meant for bench/smoke paths that accept paying one compile.

``install()`` also mounts ``pio_hbm_table_bytes{table}``: per-resident-
table device bytes sampled from ``utils/device_cache``'s residency
slots at scrape time — the per-tenant HBM accounting the multi-tenant
ROADMAP item builds on (ALX-style per-core memory budgeting).

Device-time attribution (ISSUE 11): compile seconds explain the warmup;
``device_timed(label, fn, *args)`` explains the steady state. Every
AOT/jit dispatch through it counts its **dispatch wall** (the async
enqueue — µs) into ``pio_dispatch_seconds_total{executable,tenant}``,
and a 1-in-N sampled dispatch additionally ``block_until_ready``s the
result to measure the **true device wall**, incrementing
``pio_device_time_seconds_total{executable,tenant}`` by ``wall * N``
(the standard sampled extrapolation — unbiased as long as the sampled
dispatch is exchangeable with its window, which steady serving traffic
is). The synced walls also feed a per-label rolling ring
(``device_time_percentiles``) and the ``pio_device_occupancy`` EWMA
gauge — the ALX-style "which executable owns the accelerator"
accounting the sharding/multi-tenant ROADMAP items need.
``PIO_DEVICE_SYNC_EVERY`` tunes N (default 16; 0 disables the sync,
leaving only the dispatch-wall counters).

Tenant dimension (ISSUE 17): the ``tenant`` label value is the active
``obs.tenantctx`` scope — entered at host routing, the pipelined
batcher's formation/completion threads, and tenant-attached scheduler
ticks — mapped through ``metric_tenant_label`` so cardinality stays
bounded by registered tenants (unregistered scopes book under ``""``,
the shared/untenanted series). Per-tenant occupancy shares ride the
same ~1s window as the process EWMA: each window's attributed seconds
split by tenant feed ``pio_tenant_occupancy_share{tenant}`` (EWMA,
decayed when a tenant goes quiet), and the cumulative device-seconds
split backs ``tenant_device_time_share()`` — the noisy-neighbor
signal ``GET /tenants/signals.json`` serves.
"""

from __future__ import annotations

import collections
import contextvars
import itertools
import logging
import os
import threading
import time
from contextlib import contextmanager
from typing import Dict, Optional

from predictionio_tpu.obs.metrics import get_registry
from predictionio_tpu.obs.tenantctx import metric_tenant_label

logger = logging.getLogger(__name__)

#: the canonical labels (call sites may add more; these are the ones
#: bench artifacts and docs talk about)
ALS_SWEEP = "als_sweep"
FOLD_SIDE = "fold_side"
BATCH_PREDICT = "batch_predict"
BATCH_PREDICT_MASKED = "batch_predict_masked"
#: the masked top-k whose candidate mask is composed on the device from
#: resident filter data (ops/similarity.composed_top_k_batch_begin)
BATCH_PREDICT_COMPOSED = "batch_predict_composed"
GATES_PROBE = "gates_probe"
#: the training path's set-up (ops/als): the plan upload's device work,
#: the implicit Gram (+ eigh), the per-chip sweep's recompile for its
#: exchange bytes, and everything else als_train compiles
ALS_UPLOAD = "als_upload"
ALS_GRAM = "als_gram"
ALS_SWEEP_EXCHANGE = "als_sweep_exchange"
ALS_TRAIN = "als_train"

_label_ctx: contextvars.ContextVar = contextvars.ContextVar(
    "pio_exec_label", default=None)
#: the shape bucket of an ``executable()`` scope (the AOT registry's)
_bucket_ctx: contextvars.ContextVar = contextvars.ContextVar(
    "pio_exec_bucket", default="")
#: a phase's label (``phase()``): what a compile with no executable
#: scope of its own is booked under; an executable scope never defers
#: to it
_phase_ctx: contextvars.ContextVar = contextvars.ContextVar(
    "pio_exec_phase", default=None)
_tls = threading.local()
UNLABELED = "unlabeled"

_lock = threading.Lock()
_installed = False
_c_stage_s = None
_c_requests = None
_c_hits = None
_c_misses = None
_g_flops = None
_g_bytes = None
_g_cg_ratio = None
_g_exchange = None
_c_dispatch_s = None
_c_device_s = None
_c_device_syncs = None
_g_occupancy = None
_g_tenant_occ = None


def install(registry=None):
    """Register the listener + gauges. Idempotent; never raises."""
    global _installed, _c_stage_s, _c_requests, _c_hits, _c_misses, \
        _g_flops, _g_bytes, _c_dispatch_s, _c_device_s, \
        _c_device_syncs, _g_occupancy, _g_tenant_occ, _g_cg_ratio, \
        _g_exchange
    with _lock:
        if _installed:
            return
        _installed = True
        reg = registry or get_registry()
        _c_stage_s = reg.counter(
            "pio_compile_seconds_total",
            "Seconds of each stage of the compile requests an executable "
            "label's dispatches made: trace (jaxpr), lower (MLIR), "
            "compile (XLA backend, less the persistent-cache read) and "
            "cache_load (that read)", labelnames=("executable", "stage"))
        _c_requests = reg.counter(
            "pio_compile_requests_total",
            "Compile requests by executable label and outcome: compiled "
            "(XLA ran) or cache_loaded (the persistent cache answered)",
            labelnames=("executable", "outcome"))
        _c_hits = reg.counter(
            "pio_compile_cache_hits_total",
            "executable() scopes answered without a backend compile "
            "(XLA jit cache hit)", labelnames=("executable",))
        _c_misses = reg.counter(
            "pio_compile_cache_misses_total",
            "executable() scopes that triggered a backend compile",
            labelnames=("executable",))
        _g_flops = reg.gauge(
            "pio_executable_flops",
            "XLA cost_analysis() FLOPs of the last analyzed "
            "executable per label", labelnames=("executable",))
        _g_bytes = reg.gauge(
            "pio_executable_bytes_accessed",
            "XLA cost_analysis() bytes accessed of the last analyzed "
            "executable per label", labelnames=("executable",))
        _g_cg_ratio = reg.gauge(
            "pio_als_cg_iterations_run_ratio",
            "CG iterations the Pallas solves of the last ALS iteration "
            "ran over the iterations their budgets allowed (the kernel "
            "stops a tile whose systems have converged; 1 = every solve "
            "ran to its cap)")
        _g_exchange = reg.gauge(
            "pio_als_exchange_bytes",
            "output bytes of the collectives one run of the last ALS "
            "half-sweep over row-sharded tables executes, from its "
            "compiled program (parallel/collective_stats), by side and "
            "collective kind; absent where tables are not sharded",
            labelnames=("side", "op"))
        reg.gauge_func(
            "pio_hbm_table_bytes",
            "Device bytes held by each named residency slot in "
            "utils/device_cache (per-table HBM accounting)",
            _hbm_table_samples)
        reg.gauge_func(
            "pio_table_rows",
            "Rows of each resident factor table at its last upload: "
            "what=live the model's own, what=bucket the device array's "
            "(its rung of compile/buckets' table ladder); the difference "
            "is padding every scan of the table reads",
            _table_rows_samples)
        _c_dispatch_s = reg.counter(
            "pio_dispatch_seconds_total",
            "Wall time spent in device dispatch calls (the async "
            "enqueue, NOT device execution) by executable label and "
            "serving tenant (empty = untenanted)",
            labelnames=("executable", "tenant"))
        _c_device_s = reg.counter(
            "pio_device_time_seconds_total",
            "Estimated device execution wall time by executable and "
            "serving tenant: each 1-in-N sampled dispatch is synced "
            "(block_until_ready) and its wall extrapolated by the "
            "sampling factor",
            labelnames=("executable", "tenant"))
        _c_device_syncs = reg.counter(
            "pio_device_syncs_total",
            "Sampled dispatches that paid a block_until_ready to "
            "measure true device wall",
            labelnames=("executable", "tenant"))
        _g_occupancy = reg.gauge(
            "pio_device_occupancy",
            "EWMA fraction of wall-clock time the device spent "
            "executing attributed work (clamped to 1; from the sampled "
            "device-time estimates)")
        _g_tenant_occ = reg.gauge(
            "pio_tenant_occupancy_share",
            "Per-tenant EWMA share of wall-clock device occupancy "
            "(from the sampled device-time estimates; decays when a "
            "tenant stops dispatching)", labelnames=("tenant",))
    try:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
    except Exception as e:
        logger.debug("costmon monitoring listener unavailable: %s", e)


# -- the compile listener (ISSUE 37) ---------------------------------
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
STAGES = ("trace", "lower", "compile", "cache_load")
COMPILED, CACHE_LOADED = "compiled", "cache_loaded"


class _Request:
    """One compile request in the making, on the thread that makes it."""

    __slots__ = ("t_start", "label", "trace_s", "lower_s", "hit", "load_s",
                 "ann")

    def __init__(self, t_start: float, label: str):
        self.t_start, self.label = t_start, label
        self.trace_s = self.lower_s = self.load_s = 0.0
        self.hit = None
        self.ann = None


def _label() -> str:
    return _label_ctx.get() or _phase_ctx.get() or UNLABELED


def _request(t_start: float, renew: bool = False) -> _Request:
    """The calling thread's open request; a new one where there is none,
    where the open one was made under another label, or where ``renew``
    and the open one has gone past its trace (a request lowered and never
    compiled: the jit's in-memory cache held the executable)."""
    req = getattr(_tls, "req", None)
    label = _label()
    if req is not None and (req.label != label or renew and (
            req.lower_s or req.hit is not None)):
        _close_annotation(req)
        req = None
    if req is None:
        req = _tls.req = _Request(t_start, label)
        from predictionio_tpu.obs.trace import _annotate
        ann = _annotate("compile", {"executable": label})
        try:
            ann.__enter__()
            req.ann = ann
        except Exception:
            pass
    req.t_start = min(req.t_start, t_start)
    return req


def _close_annotation(req: _Request) -> None:
    if req.ann is not None:
        ann, req.ann = req.ann, None
        try:
            ann.__exit__(None, None, None)
        except Exception:
            pass


def _on_duration(name, secs, *a, **kw):
    try:
        secs = float(secs)
    except (TypeError, ValueError):
        return
    if name == _TRACE:
        # nested jits report their traces first, each inside the outer
        # one: the outer trace is the longest of the request's
        now = time.perf_counter()
        req = _request(now - secs, renew=True)
        req.trace_s = max(req.trace_s, secs)
    elif name == _LOWER:
        now = time.perf_counter()
        _request(now - secs).lower_s += secs
    elif name == _RETRIEVAL:
        _request(time.perf_counter() - secs).load_s += secs
    elif name == _BACKEND:
        _close_request(secs)


def _on_event(name, *a, **kw):
    if name == _HIT or name == _MISS:
        _request(time.perf_counter()).hit = name == _HIT


def _close_request(backend_s: float) -> None:
    """``backend_compile_duration`` fired: the request is whole. Book its
    stages and write its record."""
    t_end = time.perf_counter()
    req = _request(t_end - backend_s)
    _tls.req = None
    _close_annotation(req)
    _tls.compile_s = getattr(_tls, "compile_s", 0.0) + backend_s
    load_s = min(req.load_s, backend_s) if req.hit else 0.0
    stages = (req.trace_s, req.lower_s, backend_s - load_s, load_s)
    outcome = CACHE_LOADED if req.hit else COMPILED
    label = req.label
    try:
        for stage, secs in zip(STAGES, stages):
            _c_stage_s.labels(executable=label, stage=stage).inc(secs)
        _c_requests.labels(executable=label, outcome=outcome).inc()
        from predictionio_tpu.obs.trace import COMPILE, TRACER
        TRACER.record(COMPILE, (req.t_start, t_end, label,
                                _bucket_ctx.get(),
                                threading.current_thread().name)
                      + stages + (outcome,))
    except Exception:
        logger.debug("compile record dropped", exc_info=True)


def _hbm_table_samples():
    from predictionio_tpu.utils import device_cache
    sizes = device_cache.resident_sizes()
    return [({"table": name}, float(nbytes))
            for name, nbytes in sorted(sizes.items())]


def _table_rows_samples():
    from predictionio_tpu.utils import device_cache
    return [({"table": t, "what": what}, float(rows[what]))
            for t, rows in device_cache.table_rows().items()
            for what in ("live", "bucket")]


@contextmanager
def phase(label: str):
    """Book every compile inside this scope that no ``executable()``
    scope claims under ``label``: the small programs a phase such as a
    train or a deploy runs around its executables (syncs, slices, the
    sentinel's checks). Counts nothing itself; for set-up paths, not
    per-dispatch ones."""
    if not _installed:
        install()
    token = _phase_ctx.set(label)
    try:
        yield
    finally:
        _phase_ctx.reset(token)


@contextmanager
def executable(label: str, defer_to_outer: bool = False,
               bucket: str = ""):
    """Attribute any compile triggered inside this scope to ``label``
    (and ``bucket``, the AOT registry's shape bucket, on its records)
    and count the scope as a cache hit/miss. Cheap enough for per-
    window dispatch paths (~1-2 µs; one contextvar set/reset and two
    float reads).

    ``defer_to_outer``: a shared kernel dispatched from several
    higher-level executables (the ALS sweep under train vs fold)
    defers entirely to the caller's scope when one is active —
    attribution AND the hit/miss count follow the executable the
    OPERATOR names (counting in both scopes would double every
    hit/miss under the adopted label)."""
    if not _installed:
        install()
    if defer_to_outer and _label_ctx.get() is not None:
        yield                      # the outer scope owns all accounting
        return
    token = _label_ctx.set(label)
    b_token = _bucket_ctx.set(bucket) if bucket else None
    before = getattr(_tls, "compile_s", 0.0)
    ok = False
    try:
        yield
        ok = True
    finally:
        _label_ctx.reset(token)
        if b_token is not None:
            _bucket_ctx.reset(b_token)
        # clean exits only: a body that raises before dispatching
        # (fault injection, malformed golden query) compiled nothing —
        # counting it as a "hit" would inflate the ratio the AOT /
        # shape-churn diagnosis reads
        if ok:
            try:
                if getattr(_tls, "compile_s", 0.0) > before:
                    _c_misses.labels(executable=label).inc()
                else:
                    _c_hits.labels(executable=label).inc()
            except Exception:
                pass


# -- device-time attribution (ISSUE 11) ---------------------------------

def _sync_every_default() -> int:
    try:
        return max(0, int(os.environ.get("PIO_DEVICE_SYNC_EVERY", 16)))
    except (TypeError, ValueError):
        return 16


class _DeviceState:
    """Per-(label, tenant) hot-path state: pre-resolved counter
    children (no .labels() lock per dispatch), an atomic dispatch tick
    for the 1-in-N sampling decision, and a bounded ring of sampled
    device walls for percentile views."""

    __slots__ = ("dispatch_s", "device_s", "syncs", "tick", "ring",
                 "every", "tenant")

    def __init__(self, label: str, tenant: str, every: int):
        self.tenant = tenant
        self.dispatch_s = _c_dispatch_s.labels(executable=label,
                                               tenant=tenant)
        self.device_s = _c_device_s.labels(executable=label,
                                           tenant=tenant)
        self.syncs = _c_device_syncs.labels(executable=label,
                                            tenant=tenant)
        self.tick = itertools.count()       # next() is GIL-atomic
        self.ring = collections.deque(maxlen=128)
        self.every = every


_dev_lock = threading.Lock()
# (executable label, tenant label value) -> state; the tenant half is
# already cardinality-bounded by metric_tenant_label
_dev_state: Dict[tuple, _DeviceState] = {}
_block_until_ready = None
# process occupancy state: estimated device seconds ACCUMULATE into a
# ~1s wall window shared by every label, and the EWMA updates once per
# window — a single last-sample timestamp would let two interleaved
# labels' syncs divide one label's 16-dispatch estimate by the OTHER
# label's 10ms-old stamp and read "saturated" at modest load
_OCC_WINDOW_S = 1.0
_occ_window_t0: Optional[float] = None
_occ_acc = 0.0
_occ_ewma = 0.0
# per-tenant split of the same window: tenant label value -> attributed
# seconds this window, and the EWMA share map signals.json reads
_occ_acc_tenant: Dict[str, float] = {}
_occ_share_ewma: Dict[str, float] = {}


def _device_state(label: str, tenant: str = "") -> _DeviceState:
    st = _dev_state.get((label, tenant))
    if st is None:
        if not _installed:
            install()
        with _dev_lock:
            st = _dev_state.get((label, tenant))
            if st is None:
                every = _sync_every_default()
                # a tenant's sampling cadence (tests override
                # st.every) applies to every scope it dispatches
                # under: inherit the untenanted state's cadence so
                # `st.every = 0` keeps governing label-wide
                base = _dev_state.get((label, ""))
                if base is not None:
                    every = base.every
                st = _DeviceState(label, tenant, every)
                _dev_state[(label, tenant)] = st
    return st


def _note_device_time(est_s: float, tenant: str = ""):
    """Fold one sampled dispatch's extrapolated device seconds into the
    occupancy window; when the window (~1s) closes, its accumulated
    estimate over its wall becomes the instantaneous occupancy feeding
    the EWMA (clamped to 1 — concurrent dispatch threads can attribute
    more than wall). The same window's per-tenant split feeds the
    ``pio_tenant_occupancy_share`` EWMAs; tenants absent from a window
    decay toward 0 instead of freezing at their last busy share."""
    global _occ_window_t0, _occ_acc, _occ_ewma
    with _dev_lock:
        now = time.monotonic()
        if _occ_window_t0 is None:
            _occ_window_t0 = now
        _occ_acc += est_s
        if tenant:
            _occ_acc_tenant[tenant] = \
                _occ_acc_tenant.get(tenant, 0.0) + est_s
        wall = now - _occ_window_t0
        if wall >= _OCC_WINDOW_S:
            inst = min(_occ_acc / wall, 1.0)
            _occ_ewma = (inst if _occ_ewma == 0.0
                         else 0.7 * _occ_ewma + 0.3 * inst)
            _g_occupancy.set(round(_occ_ewma, 4))
            for t in set(_occ_share_ewma) | set(_occ_acc_tenant):
                inst_t = min(_occ_acc_tenant.get(t, 0.0) / wall, 1.0)
                old = _occ_share_ewma.get(t, 0.0)
                share = (inst_t if old == 0.0
                         else 0.7 * old + 0.3 * inst_t)
                if share < 1e-6:
                    _occ_share_ewma.pop(t, None)
                    share = 0.0
                else:
                    _occ_share_ewma[t] = share
                if _g_tenant_occ is not None:
                    _g_tenant_occ.labels(tenant=t).set(round(share, 4))
            _occ_window_t0 = now
            _occ_acc = 0.0
            _occ_acc_tenant.clear()


def device_timed(label: str, fn, *args):
    """Dispatch ``fn(*args)`` under device-time attribution for
    ``label``. The unsampled path costs two perf_counter reads, one
    dict get, one atomic tick, and one cached-child counter inc
    (~1 µs — guarded by tests/test_obs_overhead.py). Every
    ``PIO_DEVICE_SYNC_EVERY``-th dispatch per label (first included)
    additionally blocks until the result is device-complete and books
    the measured wall, extrapolated by the sampling factor, as device
    time — separating true device seconds from dispatch wall without
    paying a sync per request. Inside an active trace the sampled sync
    annotates the current span (``deviceMs``) so slow-query waterfalls
    gain a device_sync stage.

    The active tenant scope (obs.tenantctx — entered by host routing,
    the batcher's pipeline threads, scheduler ticks) selects the
    ``{executable,tenant}`` series; the added cost on the unsampled
    path is one contextvar read and a tuple-keyed dict get (still
    priced by tests/test_obs_overhead.py)."""
    st = _device_state(label, metric_tenant_label())
    t0 = time.perf_counter()
    compile_before = getattr(_tls, "compile_s", 0.0)
    out = fn(*args)
    dispatch_dt = time.perf_counter() - t0
    st.dispatch_s.inc(dispatch_dt)
    if st.every and next(st.tick) % st.every == 0:
        global _block_until_ready
        if _block_until_ready is None:
            from jax import block_until_ready
            _block_until_ready = block_until_ready
        from predictionio_tpu.obs.trace import TRACER
        with TRACER.region("device_sync"):
            try:
                _block_until_ready(out)
            except Exception:
                pass   # host-side fallback output: already complete
        wall = time.perf_counter() - t0
        # what the sync alone held this thread for (the dispatch wall is
        # paid either way): the serving account reads it per dispatch
        _tls.sync_s = getattr(_tls, "sync_s", 0.0) + wall - dispatch_dt
        if getattr(_tls, "compile_s", 0.0) > compile_before:
            # the sampled dispatch paid an XLA compile (cold jit
            # fallback — the backend_compile listener fired on this
            # thread): the wall is compile, not steady-state device
            # time, and extrapolating it by N would poison the
            # attribution for the process lifetime (one compile is
            # orders of magnitude over a steady-state dispatch). Skip
            # the estimate — the next sampled dispatch is warm.
            return out
        est = wall * st.every
        st.device_s.inc(est)
        st.syncs.inc()
        with _dev_lock:   # scrape-time percentile reads copy under it
            st.ring.append(wall)
        _note_device_time(est, st.tenant)
        TRACER.annotate(deviceMs=round(wall * 1000.0, 3),
                        deviceSampled=st.every)
    return out


def thread_sync_s() -> float:
    """Seconds THIS thread has spent blocked in :func:`device_timed`'s
    sampled sync, cumulative: the batcher samples the delta around a
    window's begin() for the dispatch's account record."""
    return getattr(_tls, "sync_s", 0.0)


def occupancy() -> float:
    """The current ``pio_device_occupancy`` EWMA (0..1) — the adaptive
    micro-batch sizer's device-pressure signal (ISSUE 14): a lock-free
    float read, cheap enough for every dispatch decision."""
    return _occ_ewma


def tenant_occupancy_shares() -> Dict[str, float]:
    """{tenant: EWMA occupancy share} — each tenant's share of wall-
    clock device time over the recent windows (ISSUE 17). Values decay
    once a tenant stops dispatching; the sum is bounded by the process
    occupancy (itself clamped to 1)."""
    with _dev_lock:
        return {t: round(v, 4) for t, v in _occ_share_ewma.items()}


def device_time_by_tenant() -> Dict[str, float]:
    """{tenant label value: cumulative estimated device seconds}
    summed across executables (``""`` = untenanted dispatches)."""
    out: Dict[str, float] = {}
    if _c_device_s is None:
        return out
    for labels, v in _c_device_s.samples():
        if not labels:
            continue
        t = labels.get("tenant", "")
        out[t] = out.get(t, 0.0) + v
    return {t: round(v, 4) for t, v in out.items()}


def tenant_device_time_share() -> Dict[str, float]:
    """{tenant: fraction of ALL attributed device seconds} — the
    cumulative cost-attribution split behind signals.json's
    ``device_time_share``. Includes the ``""`` untenanted share, so
    the values sum to 1.0 whenever any device time was booked (and the
    named tenants' shares alone sum to <= 1.0)."""
    by_tenant = device_time_by_tenant()
    total = sum(by_tenant.values())
    if total <= 0:
        return {}
    return {t: round(v / total, 4) for t, v in by_tenant.items()}


def device_time_by_executable() -> Dict[str, float]:
    """{label: estimated device seconds} — the bench/stats view."""
    return {k: round(v, 4)
            for k, v in _labeled_values(_c_device_s).items()}


def dispatch_seconds_by_executable() -> Dict[str, float]:
    return {k: round(v, 4)
            for k, v in _labeled_values(_c_dispatch_s).items()}


def device_time_percentiles(label: str) -> Optional[Dict[str, float]]:
    """p50/p99 of the SAMPLED per-dispatch device walls (ms) for one
    label (merged across tenants); None before the first sampled
    sync."""
    states = [st for (lab, _t), st in list(_dev_state.items())
              if lab == label]
    if not states:
        return None
    with _dev_lock:   # appenders hold it too — no mutation mid-sort
        walls = sorted(w for st in states for w in st.ring)
    if not walls:
        return None
    def pick(q):
        return walls[min(len(walls) - 1, int(q / 100.0 * len(walls)))]
    return {"p50_ms": round(pick(50) * 1000.0, 4),
            "p99_ms": round(pick(99) * 1000.0, 4),
            "samples": len(walls)}


def device_snapshot() -> Dict[str, object]:
    """The /stats.json ``deviceTime`` block: estimated device seconds
    per executable, the occupancy EWMA, and the sampling factor."""
    out = {
        "secondsByExecutable": device_time_by_executable(),
        "dispatchSecondsByExecutable":
            dispatch_seconds_by_executable(),
        "occupancy": round(_occ_ewma, 4),
        "syncEvery": _sync_every_default(),
    }
    by_tenant = device_time_by_tenant()
    if any(t for t in by_tenant):
        out["secondsByTenant"] = by_tenant
        out["tenantOccupancyShare"] = tenant_occupancy_shares()
    labels = {lab for (lab, _t) in list(_dev_state)}
    pct = {label: device_time_percentiles(label) for label in labels}
    out["sampledWallMs"] = {k: v for k, v in pct.items()
                            if v is not None}
    return out


def record_cost_analysis(label: str, compiled) -> Optional[dict]:
    """Bank ``compiled.cost_analysis()`` FLOPs/bytes under ``label``.
    Accepts a jax ``Compiled`` (or anything exposing cost_analysis);
    returns the extracted {"flops", "bytes_accessed"} or None."""
    if not _installed:
        install()
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        flops = float(cost.get("flops", 0.0))
        nbytes = float(cost.get("bytes accessed", 0.0))
    except Exception as e:
        logger.debug("cost_analysis unavailable for %s: %s", label, e)
        return None
    _g_flops.labels(executable=label).set(flops)
    _g_bytes.labels(executable=label).set(nbytes)
    return {"flops": flops, "bytes_accessed": nbytes}


def record_cg_iterations(run: float, budget: float) -> None:
    """Bank what `ops/als.last_cg_iterations` read after a train: the
    share of their budget the Pallas CG solves ran. Nothing to bank when
    no solve went through that kernel (budget 0)."""
    if not _installed:
        install()
    if budget > 0:
        _g_cg_ratio.set(run / budget)


def record_exchange_bytes(side: str, by_op: dict) -> None:
    """Bank what a per-chip ALS half-sweep's programs exchange
    (`ops/als.sweep_exchange`'s {collective: bytes}; its "sent" total is
    telemetry's alone): one gauge value per collective kind."""
    if not _installed:
        install()
    for op, n_bytes in by_op.items():
        if op != "sent":
            _g_exchange.labels(side=side, op=op).set(n_bytes)


def analyze_jit(label: str, fn, *args, **kwargs) -> Optional[dict]:
    """Lower+compile ``jax.jit(fn)`` for ``args`` under ``label`` and
    bank its cost analysis. Pays one explicit compile — bench/smoke
    only, never a serving path."""
    import jax
    try:
        with executable(label):
            compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    except Exception as e:
        logger.debug("analyze_jit(%s) failed: %s", label, e)
        return None
    return record_cost_analysis(label, compiled)


# -- bench/JSON views ---------------------------------------------------
def _labeled_values(counter) -> Dict[str, float]:
    """Sum per executable label (families that also carry a tenant
    label collapse across tenants here — the per-executable view)."""
    if counter is None:
        return {}
    out: Dict[str, float] = {}
    for labels, v in counter.samples():
        if not labels:
            continue
        k = labels["executable"]
        out[k] = out.get(k, 0.0) + v
    return out


def compile_stage_seconds() -> Dict[str, Dict[str, float]]:
    """{label: {stage: seconds}} from ``pio_compile_seconds_total``."""
    out: Dict[str, Dict[str, float]] = {}
    if _c_stage_s is None:
        return out
    for labels, v in _c_stage_s.samples():
        if labels:
            by = out.setdefault(labels["executable"], {})
            by[labels["stage"]] = by.get(labels["stage"], 0.0) + v
    return out


def compile_seconds_by_executable() -> Dict[str, float]:
    """{label: backend-compile seconds}: XLA's own, with what the
    persistent cache's reads took where it answered (the benchmark's
    filtered serve job reads this)."""
    return {k: round(st.get("compile", 0.0) + st.get("cache_load", 0.0), 4)
            for k, st in compile_stage_seconds().items()}


def compile_requests() -> Dict[str, Dict[str, float]]:
    """{outcome: {label: requests}} from ``pio_compile_requests_total``."""
    out: Dict[str, Dict[str, float]] = {COMPILED: {}, CACHE_LOADED: {}}
    if _c_requests is None:
        return out
    for labels, v in _c_requests.samples():
        if labels:
            by = out.setdefault(labels["outcome"], {})
            by[labels["executable"]] = by.get(labels["executable"], 0) + v
    return out


def compile_totals() -> Dict[str, float]:
    """Process-wide compile requests and their seconds over every
    stage: the steady-phase delta the dataplane reads."""
    req = compile_requests()
    return {"requests": sum(v for by in req.values() for v in by.values()),
            "seconds": sum(v for st in compile_stage_seconds().values()
                           for v in st.values())}


def cache_counts() -> Dict[str, Dict[str, float]]:
    """{"hits": {label: n}, "misses": {label: n}}."""
    return {"hits": _labeled_values(_c_hits),
            "misses": _labeled_values(_c_misses)}


def pcache_totals() -> Dict[str, float]:
    """Process-wide persistent-cache answers ("hits": requests the cache
    loaded) and compiles ("misses": requests XLA compiled)."""
    req = compile_requests()
    return {"hits": sum(req[CACHE_LOADED].values()),
            "misses": sum(req[COMPILED].values())}


def setup_account(since: Optional[float] = None) -> Dict[str, object]:
    """The set-up account from ``TRACER``'s ``setup.compile`` and
    ``setup.upload`` records (those starting at or after ``since``, a
    ``time.perf_counter()`` stamp; all that the rings hold by default):
    per executable label its stage seconds and outcomes, per table its
    uploads, bytes, host preparation and seconds from the upload's start
    to its arrays being ready, and the totals. Unlabelled compiles are
    not the program's and are left out. ``/stats.json``'s ``setup``,
    ``als_train``'s telemetry, ``pio status --telemetry``."""
    from predictionio_tpu.obs.trace import (COMPILE, COMPILE_FIELDS, TRACER,
                                            UPLOAD, UPLOAD_FIELDS)
    t0 = float("-inf") if since is None else since
    execs: Dict[str, dict] = {}
    for rec in TRACER.recent(COMPILE):
        r = dict(zip(COMPILE_FIELDS, rec))
        if r["t_start"] < t0 or r["executable"] == UNLABELED:
            continue
        e = execs.setdefault(r["executable"], dict(
            {f"{st}_s": 0.0 for st in STAGES},
            **{COMPILED: 0, CACHE_LOADED: 0}))
        for st in STAGES:
            e[f"{st}_s"] += r[f"{st}_s"]
        e[r["outcome"]] += 1
    tables: Dict[str, dict] = {}
    spans = []
    for rec in TRACER.recent(UPLOAD):
        r = dict(zip(UPLOAD_FIELDS, rec))
        if r["t_start"] < t0:
            continue
        t = tables.setdefault(r["table"], {
            "uploads": 0, "bytes": 0, "host_prep_s": 0.0, "ready_s": 0.0})
        t["uploads"] += 1
        t["bytes"] += r["bytes"]
        t["host_prep_s"] += r["host_prep_s"]
        t["ready_s"] += r["t_ready"] - r["t_start"]
        spans.append((r["t_start"], r["t_ready"]))
    total = {f"{st}_s": sum(e[f"{st}_s"] for e in execs.values())
             for st in STAGES}
    total.update(
        {o: sum(e[o] for e in execs.values())
         for o in (COMPILED, CACHE_LOADED)},
        upload_bytes=sum(t["bytes"] for t in tables.values()),
        upload_ready_s=sum(t["ready_s"] for t in tables.values()),
        upload_wall_s=_union_seconds(spans))
    return {"executables": {k: _rounded(e) for k, e in sorted(execs.items())},
            "tables": {k: _rounded(t) for k, t in sorted(tables.items())},
            "total": _rounded(total)}


def _rounded(d: dict) -> dict:
    return {k: round(v, 4) if isinstance(v, float) else v
            for k, v in d.items()}


def _union_seconds(spans) -> float:
    """Seconds covered by the union of (start, end) intervals."""
    covered, end = 0.0, float("-inf")
    for a, b in sorted(spans):
        if b > end:
            covered += b - max(a, end)
            end = b
    return covered
