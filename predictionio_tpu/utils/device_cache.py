"""Host-array -> device-array cache.

Serving-path fix for SURVEY hard part #4 (serve-time latency from HBM):
model factor tables live in host numpy after deserialization; without a
cache every jitted predict call would re-transfer them host->device (an
ML-20M-sized table is ~130 MB per query). `cached_put` uploads once per (array identity, sharding) and evicts when the host array
is garbage-collected.
"""

from __future__ import annotations

import atexit
import contextlib
import contextvars
import os
import queue
import threading
import time
import weakref
from typing import Any, Dict, Optional, Tuple

_lock = threading.Lock()
_cache: Dict[Tuple[int, Any], Tuple[Any, Any]] = {}

# ---------------------------------------------------------------------------
# Tenant attribution (ISSUE 15)
#
# A multi-tenant serving host packs many engines' factor tables into one
# device's HBM. Every upload that lands in this cache (and every residency
# slot) is tagged with the tenant active at put time, so the HBM budget
# manager (tenancy/budget.py) can read per-tenant resident bytes and evict
# one cold tenant's tables without touching another's. The scope is a
# contextvar: it follows the query/fold call stack across the serving
# lock, not threads created inside it.
# ---------------------------------------------------------------------------

# The scope itself moved to obs/tenantctx (ISSUE 17): the same
# contextvar now also drives device-time attribution, flight/trace/
# slowlog stamping and incident naming. These names stay re-exported —
# every PR 15 call site (and test) keeps working unchanged.
from predictionio_tpu.obs.tenantctx import (_tenant_var,   # noqa: F401
                                            current_tenant, tenant_scope)

# cache key -> tenant (entries whose upload ran under a tenant scope)
_tenant_keys: Dict[Any, str] = {}
# residency slot name -> tenant
_tenant_slots: Dict[str, str] = {}


def _tag_key(key):
    """Record the active tenant for a just-stored cache key. Caller
    holds ``_lock``."""
    t = _tenant_var.get()
    if t is not None:
        _tenant_keys[key] = t


def _evict_cache_key(key):
    """Weakref eviction callback body: lock-free (gc may run it while
    this thread already holds ``_lock``; dict pops are GIL-atomic)."""
    _cache.pop(key, None)
    _tenant_keys.pop(key, None)


def _sharding_key(sharding) -> Any:
    """Canonical cache-key component for a sharding: structurally
    distinct between no-sharding, replicated, and each sharded layout,
    and stable across equal-but-distinct NamedSharding objects. Keying
    on the raw object worked only as long as every caller passed the
    same layout for a given array — once replicated and model-sharded
    payloads of the SAME host array coexist (the sharded online
    plane), a layout must never be able to alias another's entry."""
    if sharding is None:
        return None
    spec = getattr(sharding, "spec", None)
    mesh = getattr(sharding, "mesh", None)
    if spec is None or mesh is None:
        return ("opaque", sharding)
    return ("named", id(mesh), tuple(spec))


class TableBudgetExceeded(RuntimeError):
    """A factor-table upload would exceed the enforced per-device
    table-byte budget (``PIO_TABLE_BUDGET_BYTES``)."""


def table_budget_bytes() -> Optional[int]:
    """The enforced per-device factor-table budget, or None (no
    enforcement — the default). The over-budget acceptance scenario
    sets this to prove a vocabulary genuinely does not fit one
    device: the replicated upload path refuses while the model-sharded
    path, paying only table/N per device, proceeds."""
    raw = os.environ.get("PIO_TABLE_BUDGET_BYTES", "").strip()
    if not raw:
        return None
    try:
        b = int(float(raw))
    except ValueError:
        return None
    return b if b > 0 else None


def _row_shards(sharding) -> int:
    """How many ways a sharding splits dim 0 (1 for None/replicated):
    the divisor turning table bytes into per-device bytes."""
    spec = getattr(sharding, "spec", None)
    mesh = getattr(sharding, "mesh", None)
    if not spec or mesh is None or not len(spec) or not spec[0]:
        return 1
    axes = spec[0] if isinstance(spec[0], tuple) else (spec[0],)
    try:
        n = 1
        for ax in axes:
            n *= int(mesh.shape[ax])
        return max(n, 1)
    except Exception:
        return 1


def check_table_budget(per_device_bytes: int, table: str = "table"):
    """Raise :class:`TableBudgetExceeded` when ``per_device_bytes``
    breaks the enforced budget. No-op (zero cost beyond one getenv)
    when no budget is set."""
    budget = table_budget_bytes()
    if budget is not None and int(per_device_bytes) > budget:
        raise TableBudgetExceeded(
            f"{table}: {int(per_device_bytes)} bytes per device "
            f"exceeds the enforced table budget of {budget} bytes "
            f"(PIO_TABLE_BUDGET_BYTES); shard the table over the mesh "
            f"model axis (factor_sharding='model') or raise the budget")


def _put_rows(arr, target: int, sharding, table: str):
    """Upload ``arr`` with dim 0 zero-padded to ``target`` rows, inside a
    ``pio.setup.upload`` region (attribute ``table``), counting its bytes
    (obs.jaxmon: only cache MISSES get here, so the counter means actual
    link traffic) and leaving its ``setup.upload`` record to be written
    once the device copy is ready (``note_upload``)."""
    import jax
    import numpy as np

    from predictionio_tpu.obs import TRACER, jaxmon
    with TRACER.region("setup.upload", table=table):
        t_start = time.perf_counter()
        padded = arr
        if target and target > arr.shape[0]:
            padded = np.concatenate(
                [arr, np.zeros((target - arr.shape[0],) + arr.shape[1:],
                               arr.dtype)])
        t_prep = time.perf_counter()
        dev = jax.device_put(padded, sharding) if sharding is not None \
            else jax.device_put(padded)
        t_put = time.perf_counter()
    nbytes = int(getattr(padded, "nbytes", 0) or 0)
    jaxmon.record_h2d(nbytes)
    note_upload(dev, table, nbytes, t_start, t_put, t_prep - t_start)
    return dev


# -- the set-up account's upload records (ISSUE 37) -------------------
# One waiter thread stamps each upload's readiness: the uploading thread
# hands it the device arrays and goes on, so no upload path (a serve-time
# cache miss, a fold tick, a promotion) waits on the transfer for the
# sake of the record.
_ready_queue: "Optional[queue.SimpleQueue]" = None
_ready_cv = threading.Condition()
_ready_pending = 0


def note_upload(arrays, table: str, nbytes: int, t_start: float,
                t_put: float, host_prep_s: float) -> None:
    """Have the ``setup.upload`` record of an upload (``UPLOAD_FIELDS``;
    ``time.perf_counter()`` stamps) written once ``arrays`` are ready on
    the device; returns at once."""
    global _ready_queue, _ready_pending
    with _ready_cv:
        if _ready_queue is None:
            _ready_queue = queue.SimpleQueue()
            threading.Thread(target=_await_ready, args=(_ready_queue,),
                             name="pio-upload-ready", daemon=True).start()
            atexit.register(settle_uploads)
        _ready_pending += 1
    _ready_queue.put((arrays, (t_start, t_put), table, int(nbytes),
                      float(host_prep_s)))


def _await_ready(q) -> None:
    global _ready_pending
    import jax

    from predictionio_tpu.obs.trace import TRACER, UPLOAD
    while True:
        arrays, stamps, table, nbytes, prep = q.get()
        try:
            jax.block_until_ready(arrays)
        except Exception:
            pass   # donated or deleted since: its transfer had landed
        TRACER.record(UPLOAD, stamps + (time.perf_counter(), table,
                                        nbytes, prep))
        del arrays
        with _ready_cv:
            _ready_pending -= 1
            _ready_cv.notify_all()


def settle_uploads(timeout: float = 30.0) -> bool:
    """Wait until every noted upload's record is written (``als_train``'s
    telemetry, after its own syncs; interpreter exit). True if none is
    left."""
    with _ready_cv:
        return _ready_cv.wait_for(lambda: _ready_pending == 0, timeout)


def cached_put(arr, sharding=None, table: str = "cached_put"):
    """device_put with identity-based memoization. `arr` must be a
    weakref-able host array (numpy ndarray); ``table`` names its upload
    record."""
    key = (id(arr), _sharding_key(sharding))
    with _lock:
        entry = _cache.get(key)
        if entry is not None and entry[0]() is arr:
            return entry[1]
    dev = _put_rows(arr, 0, sharding, table)
    try:
        ref = weakref.ref(arr, lambda r, k=key: _evict_cache_key(k))
    except TypeError:
        return dev  # not weakref-able; skip caching
    with _lock:
        _cache[key] = (ref, dev)
        _tag_key(key)
    return dev


def is_cached(arr, sharding=None) -> bool:
    """Whether :func:`cached_put` holds a device copy of exactly this host
    array (a caller that counts bytes sent asks before it puts)."""
    with _lock:
        entry = _cache.get((id(arr), _sharding_key(sharding)))
        return entry is not None and entry[0]() is arr


def cached_put_padded(arr, sharding, row_multiple: int):
    """cached_put for sharded uploads whose dim-0 must divide the axis
    size: pads rows with zeros before upload, memoized on
    (array identity, sharding, multiple) so per-query serve calls reuse
    the resident padded table."""
    key = (id(arr), _sharding_key(sharding), "pad", row_multiple)
    with _lock:
        entry = _cache.get(key)
        if entry is not None and entry[0]() is arr:
            return entry[1]
    n = arr.shape[0]
    target = ((n + row_multiple - 1) // row_multiple) * row_multiple
    dev = _put_rows(arr, target, sharding, "cached_put_padded")
    try:
        ref = weakref.ref(arr, lambda r, k=key: _evict_cache_key(k))
    except TypeError:
        return dev
    with _lock:
        _cache[key] = (ref, dev)
        _tag_key(key)
    return dev


# table name -> (live rows, bucket rows) of its last bucketed upload:
# the sample source behind ``pio_table_rows{table,what}``
_table_rows: Dict[str, Tuple[int, int]] = {}


def note_table_rows(table: str, live: int, bucket: int) -> None:
    """Record what a resident table named ``table`` holds: ``live``
    rows in a device array of ``bucket`` rows (the rest is padding that
    every scan of the table reads)."""
    _table_rows[table] = (int(live), int(bucket))


def table_rows() -> "Dict[str, Dict[str, float]]":
    """table -> {live, bucket, paddedShare}: ``/stats.json``'s
    ``tableRows`` block and the gauge's samples."""
    return {t: {"live": live, "bucket": bucket,
                "paddedShare": (bucket - live) / bucket if bucket else 0.0}
            for t, (live, bucket) in sorted(_table_rows.items())}


def cached_put_rows(arr, target_rows: int, sharding=None,
                    table: Optional[str] = None):
    """cached_put with dim-0 zero-padded to ``target_rows`` — the
    vocab-bucket upload of the compile plane (ISSUE 9): serving tables
    are uploaded at their shape-bucket size so vocabulary growth inside
    the bucket reuses both the resident device copy AND every compiled
    executable that reads it. Memoized on (array identity, rows,
    sharding); a smaller ``target_rows`` than the array has rows
    uploads unpadded (callers pass a covering bucket). ``table`` names
    the upload for ``pio_table_rows`` ("user", "item") and its upload
    record."""
    import numpy as np

    target = max(int(target_rows), arr.shape[0])
    key = (id(arr), "rows", target, _sharding_key(sharding))
    with _lock:
        entry = _cache.get(key)
        if entry is not None and entry[0]() is arr:
            return entry[1]
    # the enforced per-device budget (over-budget acceptance): an
    # unsharded/replicated serving table costs its FULL padded bytes
    # on every device — exactly what a too-large vocabulary must not
    # be allowed to do silently
    row_bytes = int(np.prod(arr.shape[1:], dtype=np.int64)
                    * arr.dtype.itemsize) if arr.ndim > 1 \
        else arr.dtype.itemsize
    check_table_budget(target * row_bytes // _row_shards(sharding),
                       table="cached_put_rows")
    dev = _put_rows(arr, target, sharding, table or "cached_put_rows")
    if table is not None:
        note_table_rows(table, arr.shape[0], target)
    try:
        ref = weakref.ref(arr, lambda r, k=key: _evict_cache_key(k))
    except TypeError:
        return dev
    with _lock:
        _cache[key] = (ref, dev)
        _tag_key(key)
    return dev


def cache_size() -> int:
    with _lock:
        return len(_cache)


def clear():
    with _lock:
        _cache.clear()
        _resident.clear()
        _tenant_keys.clear()
        _tenant_slots.clear()
        _table_rows.clear()


# ---------------------------------------------------------------------------
# Versioned residency slots (fold ticks)
#
# A fold tick starts from the DEPLOYED factor tables and ends by publishing
# grown/updated tables; the next tick starts from exactly those. A named
# slot keeps the tick's final device arrays resident, keyed by the host
# arrays of the published model version — when the next tick presents the
# same host arrays, it reuses the device copies and uploads only the
# touched-row deltas (the ALX device-resident-shard discipline; ROADMAP
# open item). One live version per name; a slot dies with its key arrays
# (weakref callbacks), so an undeployed model never pins HBM.
# ---------------------------------------------------------------------------

_resident: Dict[str, Tuple[tuple, dict, Any]] = {}
# name -> (key_refs, payload, sharding_token)


def get_resident(name: str, key_arrays,
                 sharding: Any = None) -> "dict | None":
    """The slot's payload iff it was stored against exactly these host
    arrays (identity match via weakrefs) AND under the same sharding
    token; None on any mismatch. The token is what keeps a replicated
    payload from shadowing a sharded one (or vice versa) when both
    layouts of the same logical table coexist in one process — the
    latent aliasing the sharded online plane would otherwise hit on a
    ``factor_sharding`` config change."""
    with _lock:
        entry = _resident.get(name)
    if entry is None:
        return None
    refs, payload, token = entry
    if token != sharding or len(refs) != len(key_arrays):
        return None
    if all(r() is a for r, a in zip(refs, key_arrays)):
        return payload
    return None


def put_resident(name: str, key_arrays, payload: dict,
                 sharding: Any = None):
    """Store device arrays for ``name``, valid while every array in
    ``key_arrays`` (the published model version's host tables) is alive
    and identical; replaces the slot's previous version. ``sharding``
    is the layout token (e.g. ``"replicated"`` / ``"model:4"``) the
    matching :func:`get_resident` must present."""
    # NOTE: no lock in the callback — gc may run it while this thread
    # already holds _lock (dict pop is GIL-atomic; same discipline as
    # cached_put's eviction callback)
    try:
        refs = tuple(weakref.ref(a, lambda r, k=name: _evict_slot(k))
                     for a in key_arrays)
    except TypeError:
        return  # not weakref-able: skip residency rather than leak HBM
    with _lock:
        _resident[name] = (refs, payload, sharding)
        t = _tenant_var.get()
        if t is not None:
            _tenant_slots[name] = t


def _evict_slot(name: str):
    """Residency weakref callback body (lock-free, see put_resident)."""
    _resident.pop(name, None)
    _tenant_slots.pop(name, None)


def drop_resident(name: str):
    with _lock:
        _resident.pop(name, None)
        _tenant_slots.pop(name, None)


def resident_count() -> int:
    with _lock:
        return len(_resident)


def _device_nbytes(arr) -> int:
    """Bytes ONE device holds for ``arr``: a host/replicated array
    costs its full ``nbytes`` per device, while a dim-0-sharded device
    array costs only its largest per-device shard total — so the HBM
    gauge reads ~1/N per shard for model-sharded tables (the ALX
    scale-out claim, directly observable)."""
    shards = getattr(arr, "addressable_shards", None)
    if shards is None:
        return int(getattr(arr, "nbytes", 0) or 0)
    per: Dict[Any, int] = {}
    try:
        for sh in shards:
            d = sh.device
            per[d] = per.get(d, 0) + int(
                getattr(sh.data, "nbytes", 0) or 0)
    except Exception:
        return int(getattr(arr, "nbytes", 0) or 0)
    return max(per.values(), default=0)


def _payload_nbytes(obj) -> int:
    """Per-device bytes held by a residency payload: dicts/sequences
    are walked one level deep (fold payloads are flat dicts of device
    arrays / (array, gram) pairs); anything without ``nbytes`` counts
    zero."""
    if isinstance(obj, dict):
        return sum(_payload_nbytes(v) for v in obj.values())
    if isinstance(obj, (list, tuple)):
        return sum(_payload_nbytes(v) for v in obj)
    return _device_nbytes(obj)


def resident_sizes() -> "Dict[str, int]":
    """name -> per-device bytes for every live residency slot — the
    sample source behind ``pio_hbm_table_bytes{table}``
    (obs/costmon.py)."""
    with _lock:
        items = list(_resident.items())
    return {name: _payload_nbytes(payload)
            for name, (_refs, payload, _tok) in items}


def _payload_arrays(obj):
    """Flatten a residency payload into its array-like leaves (the
    same one-level walk as :func:`_payload_nbytes`)."""
    if isinstance(obj, dict):
        for v in obj.values():
            yield from _payload_arrays(v)
    elif isinstance(obj, (list, tuple)):
        for v in obj:
            yield from _payload_arrays(v)
    elif obj is not None and getattr(obj, "nbytes", 0):
        yield obj


def tenant_device_arrays() -> "Dict[str, list]":
    """tenant -> live device arrays this cache/residency layer holds
    for it (cache entries + residency payload leaves). The budget
    manager sums these identity-DEDUPED together with each slot's own
    handles — a fold tick attaches the same device arrays to its
    ShardedTables AND its residency payload, and counting them twice
    would double the gauge and thrash eviction."""
    with _lock:
        keys = [(k, t) for k, t in _tenant_keys.items() if k in _cache]
        devs = [(t, _cache[k][1]) for k, t in keys]
        slots = [(n, t) for n, t in _tenant_slots.items()
                 if n in _resident]
        payloads = [(t, _resident[n][1]) for n, t in slots]
    out: Dict[str, list] = {}
    for t, dev in devs:
        out.setdefault(t, []).append(dev)
    for t, payload in payloads:
        out.setdefault(t, []).extend(_payload_arrays(payload))
    return out


def tenant_sizes() -> "Dict[str, int]":
    """tenant -> per-device resident bytes across this cache AND the
    residency slots, measured from the live device arrays (not from
    put-time estimates), identity-deduped — the raw half of the
    sample source behind ``pio_engine_hbm_bytes{tenant}``
    (tenancy/budget.py adds each slot's ShardedTable handles). Tenants
    with nothing resident simply have no entry."""
    out: Dict[str, int] = {}
    for t, arrs in tenant_device_arrays().items():
        seen = set()
        total = 0
        for a in arrs:
            if id(a) in seen:
                continue
            seen.add(id(a))
            total += _device_nbytes(a)
        out[t] = total
    return out


def evict_tenant(tenant: str) -> Tuple[int, int]:
    """Drop every cache entry and residency slot attributed to
    ``tenant``; the device arrays are freed once no in-flight dispatch
    holds them (JAX arrays are refcounted — an enqueued window's
    closure keeps its inputs alive, so eviction never corrupts a
    dispatched computation; it only stops pinning HBM for the NEXT
    one). Returns (entries_dropped, per_device_bytes_freed). The host
    mirrors — the model objects' numpy tables — are untouched: the next
    hit re-uploads through the budget-checked ``cached_put*`` /
    ``ShardedTable.device`` paths."""
    tenant = str(tenant)
    with _lock:
        doomed_keys = [k for k, t in _tenant_keys.items() if t == tenant]
        doomed_slots = [n for n, t in _tenant_slots.items() if t == tenant]
        freed = 0
        dropped = 0
        seen = set()   # identity-dedup: a residency payload may hold
        #                the same device arrays a cache entry does
        for k in doomed_keys:
            entry = _cache.pop(k, None)
            _tenant_keys.pop(k, None)
            if entry is not None:
                dropped += 1
                if id(entry[1]) not in seen:
                    seen.add(id(entry[1]))
                    freed += _device_nbytes(entry[1])
        for n in doomed_slots:
            entry = _resident.pop(n, None)
            _tenant_slots.pop(n, None)
            if entry is not None:
                dropped += 1
                for a in _payload_arrays(entry[1]):
                    if id(a) not in seen:
                        seen.add(id(a))
                        freed += _device_nbytes(a)
    return dropped, freed
