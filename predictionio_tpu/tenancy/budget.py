"""HBM budget manager: per-tenant residency accounting + eviction.

ALX (arXiv:2112.02194) frames TPU factorization throughput as a
function of what you keep resident in HBM; a multi-tenant host makes
that a *policy* question — which tenants' factor tables deserve the
device right now. This module owns the answer:

- **Accounting**: every upload and residency slot a tenant's
  query/fold paths create is tagged in ``utils/device_cache`` (the
  ``tenant_scope`` contextvar the slot servers and schedulers enter);
  :meth:`HBMBudgetManager.sizes` reads the live per-device bytes per
  tenant from the device arrays themselves — plus each slot's
  :class:`~predictionio_tpu.parallel.sharded_table.ShardedTable`
  resident handles via a host-provided sizer. The
  ``pio_engine_hbm_bytes{tenant}`` gauge samples exactly this.
- **Admission control**: a tenant whose PADDED tables (the
  compile-plane vocab buckets the serve path actually uploads at)
  exceed the whole budget can never fit — :meth:`admit` refuses it
  with :class:`TableBudgetExceeded` before it serves a single query,
  naming the sharded exit the error already documents.
- **Eviction**: when the budget is tight, :meth:`ensure_room` evicts
  the coldest unpinned tenants (priority first, then LRU by last hit)
  back to their host mirrors. Eviction drops device references only —
  the numpy/host-shard mirrors stay the source of truth, and the next
  hit re-uploads through the budget-checked ``cached_put_rows`` /
  ``ShardedTable.device`` cold paths. The host wires a per-slot
  evictor that quiesces in-flight windows first (PR 13 snapshot
  semantics extended to residency handles).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from predictionio_tpu.utils import device_cache
from predictionio_tpu.utils.device_cache import TableBudgetExceeded

logger = logging.getLogger(__name__)


def _iter_tables(models: Sequence[Any]):
    """Yield every distinct 2-D factor-table-shaped array (numpy or
    ShardedTable) reachable one attribute level deep from the models —
    the serve/fold paths keep exactly these resident."""
    from predictionio_tpu.parallel.sharded_table import is_sharded
    seen = set()
    frontier = []
    for m in models:
        frontier.append(m)
        als = getattr(m, "als", None)
        if als is not None:
            frontier.append(als)
    for obj in frontier:
        try:
            attrs = vars(obj)
        except TypeError:
            continue
        for v in attrs.values():
            if id(v) in seen:
                continue
            if is_sharded(v) or (isinstance(v, np.ndarray)
                                 and v.ndim == 2):
                seen.add(id(v))
                yield v


def estimate_padded_bytes(models: Sequence[Any]) -> int:
    """Per-device bytes the models' tables would pin once fully
    resident at their compile-plane vocab buckets — the admission
    estimate. Replicated tables cost their full padded bytes on every
    device; a sharded table costs its padded bytes / n_shards."""
    from predictionio_tpu.compile import buckets as B
    from predictionio_tpu.parallel.sharded_table import is_sharded
    total = 0
    for t in _iter_tables(models):
        n, width = t.shape
        itemsize = np.dtype(t.dtype).itemsize
        if is_sharded(t):
            padded = B.bucket_table_rows_sharded(n, t.n_shards)
            total += (padded // t.n_shards) * width * itemsize
        else:
            total += B.bucket_table_rows(n) * width * itemsize
    return int(total)


class _TenantState:
    __slots__ = ("tenant", "expected_bytes", "priority", "pinned",
                 "last_hit", "admitted_at", "evictions", "sizer",
                 "evictor")

    def __init__(self, tenant: str, expected_bytes: int,
                 priority: int = 0, pinned: bool = False,
                 sizer: Optional[Callable[[], int]] = None,
                 evictor: Optional[Callable[[], None]] = None):
        self.tenant = tenant
        self.expected_bytes = int(expected_bytes)
        self.priority = int(priority)
        self.pinned = bool(pinned)
        self.last_hit = time.monotonic()
        self.admitted_at = time.time()
        self.evictions = 0
        # host-provided extras: sizer() returns the DEVICE ARRAYS this
        # tenant holds that device_cache cannot see (ShardedTable._dev
        # handles live on the table object) — arrays, not bytes, so
        # sizes() can identity-dedup them against the residency
        # payloads that carry the same handles; evictor() is the full
        # quiesce-then-drop mechanism
        self.sizer = sizer
        self.evictor = evictor

    def snapshot(self) -> dict:
        return {
            "expectedPaddedBytes": self.expected_bytes,
            "priority": self.priority,
            "pinned": self.pinned,
            "idleSec": round(time.monotonic() - self.last_hit, 3),
            "admittedAt": self.admitted_at,
            "evictions": self.evictions,
        }


class HBMBudgetManager:
    """Thread-safe per-tenant HBM accounting + eviction policy for one
    serving host. ``budget_bytes`` defaults to the enforced
    ``PIO_TABLE_BUDGET_BYTES`` (None = accounting only, no budget
    pressure — eviction still works by operator request)."""

    def __init__(self, budget_bytes: Optional[int] = None,
                 registry=None):
        self.budget_bytes = (int(budget_bytes) if budget_bytes
                             else device_cache.table_budget_bytes())
        self._lock = threading.RLock()
        self._tenants: Dict[str, _TenantState] = {}
        self._c_evictions = None
        if registry is not None:
            registry.gauge_func(
                "pio_engine_hbm_bytes",
                "Per-device HBM bytes resident per serving tenant "
                "(factor tables + fold residency payloads), measured "
                "from the live device arrays",
                self._hbm_samples)
            registry.gauge_func(
                "pio_tenant_hbm_budget_bytes",
                "Enforced per-device HBM table budget for the host "
                "(0 = unenforced)",
                lambda: float(self.budget_bytes or 0))
            self._c_evictions = registry.counter(
                "pio_tenant_evictions_total",
                "Tenant factor-table evictions back to host mirrors, "
                "by tenant and reason (budget = room made for another "
                "tenant, operator = pio tenants evict / HTTP, "
                "remove = tenant removal)",
                labelnames=("tenant", "reason"))

    def _hbm_samples(self):
        sizes = self.sizes()
        with self._lock:
            # admitted-but-cold tenants sample 0 explicitly, so a
            # scrape distinguishes "evicted" from "unknown tenant"
            return [({"tenant": t}, float(sizes.get(t, 0)))
                    for t in sorted(self._tenants)]

    # -- lifecycle ----------------------------------------------------------
    def admit(self, tenant: str, models: Sequence[Any], *,
              priority: int = 0, pinned: bool = False,
              sizer: Optional[Callable[[], int]] = None,
              evictor: Optional[Callable[[], None]] = None
              ) -> _TenantState:
        """Admission control: register ``tenant`` iff its padded tables
        could ever fit the budget ALONE on an otherwise-empty device.
        Raises :class:`TableBudgetExceeded` otherwise — the same loud
        exit the sharded plane's replicated-upload refusal uses, and
        the same remedies apply (shard the table or raise the
        budget)."""
        tenant = str(tenant)
        expected = estimate_padded_bytes(models)
        if self.budget_bytes is not None \
                and expected > self.budget_bytes:
            raise TableBudgetExceeded(
                f"tenant {tenant!r}: padded factor tables need "
                f"{expected} bytes per device, over the host HBM "
                f"budget of {self.budget_bytes} bytes — this tenant "
                f"can NEVER fit; shard its tables over the mesh model "
                f"axis (factor_sharding='model'), shrink the vocab, "
                f"or raise PIO_TABLE_BUDGET_BYTES")
        st = _TenantState(tenant, expected, priority=priority,
                          pinned=pinned, sizer=sizer, evictor=evictor)
        with self._lock:
            self._tenants[tenant] = st
        return st

    def forget(self, tenant: str):
        with self._lock:
            self._tenants.pop(str(tenant), None)

    def touch(self, tenant: str):
        st = self._tenants.get(str(tenant))
        if st is not None:
            st.last_hit = time.monotonic()

    def pin(self, tenant: str, pinned: bool = True) -> bool:
        with self._lock:
            st = self._tenants.get(str(tenant))
            if st is None:
                return False
            st.pinned = bool(pinned)
            return True

    # -- accounting ---------------------------------------------------------
    def sizes(self) -> Dict[str, int]:
        """tenant -> per-device resident bytes, measured from the live
        device arrays: the tagged device-cache entries + residency
        payloads, plus each slot's sharded-table handles via its
        sizer — identity-DEDUPED, because a fold tick attaches the
        same device arrays to its ShardedTables and its residency
        payload (double-counting would inflate the gauge and make
        ensure_room evict neighbors that actually fit)."""
        arrays = device_cache.tenant_device_arrays()
        with self._lock:
            sizers = [(t, st.sizer) for t, st in self._tenants.items()
                      if st.sizer is not None]
        for t, sizer in sizers:
            try:
                arrays.setdefault(t, []).extend(sizer() or ())
            except Exception:
                logger.debug("tenant sizer failed for %s", t,
                             exc_info=True)
        out: Dict[str, int] = {}
        for t, arrs in arrays.items():
            seen = set()
            total = 0
            for a in arrs:
                if a is None or id(a) in seen:
                    continue
                seen.add(id(a))
                total += device_cache._device_nbytes(a)
            out[t] = total
        return out

    def resident_bytes(self) -> int:
        return sum(self.sizes().values())

    # -- policy -------------------------------------------------------------
    def _evictable(self, protect: str, sizes: Dict[str, int]
                   ) -> List[_TenantState]:
        """Cold candidates, coldest first: unpinned tenants (never
        ``protect``) holding resident bytes, ordered by (priority
        ascending, last_hit ascending) — low-priority idle tenants go
        first. Caller holds the lock."""
        cands = [st for t, st in self._tenants.items()
                 if t != protect and not st.pinned
                 and sizes.get(t, 0) > 0]
        cands.sort(key=lambda s: (s.priority, s.last_hit))
        return cands

    def ensure_room(self, tenant: str) -> int:
        """Make the budget hold once ``tenant``'s tables come resident:
        while (other tenants' resident bytes + this tenant's expected
        padded bytes) exceed the budget and a cold candidate exists,
        evict the coldest. Returns evictions performed. No-op without a
        budget.

        Best-effort by design: when every other tenant is pinned or
        hot, the upload proceeds and residency overshoots the
        manager's budget (logged loudly below). Note the per-UPLOAD
        backstop in ``cached_put_rows``/``ShardedTable.device`` reads
        only ``PIO_TABLE_BUDGET_BYTES`` — a ``HostConfig.budget_bytes``
        set programmatically governs admission + eviction policy
        here, not the put paths; deployments that want hard per-table
        refusal must set the env var (the runbook's recommendation)."""
        if self.budget_bytes is None:
            return 0
        tenant = str(tenant)
        evicted = 0
        for _ in range(len(self._tenants) + 1):
            sizes = self.sizes()
            with self._lock:
                st = self._tenants.get(tenant)
                need = st.expected_bytes if st is not None else 0
                projected = sum(b for t, b in sizes.items()
                                if t != tenant) \
                    + max(need, sizes.get(tenant, 0))
                if projected <= self.budget_bytes:
                    return evicted
                cands = self._evictable(tenant, sizes)
                if not cands:
                    logger.warning(
                        "tenant %s: projected residency %d bytes "
                        "exceeds the %d-byte budget and no unpinned "
                        "cold tenant is left to evict — overcommitting"
                        " (unpin a neighbor, raise the budget, or set "
                        "PIO_TABLE_BUDGET_BYTES for hard per-upload "
                        "refusal)", tenant, projected,
                        self.budget_bytes)
                    return evicted
                victim = cands[0].tenant
            self.evict(victim, reason="budget")
            evicted += 1
        return evicted

    def evict(self, tenant: str, reason: str = "operator") -> dict:
        """Evict one tenant's device residency back to host mirrors.
        Runs the host-provided evictor when set (quiesce + sharded
        handles + device-cache drop), else the plain device-cache
        drop. Returns {"tenant", "reason", "bytesFreed"}."""
        tenant = str(tenant)
        before = self.sizes().get(tenant, 0)
        with self._lock:
            st = self._tenants.get(tenant)
            evictor = st.evictor if st is not None else None
        if evictor is not None:
            evictor()
        else:
            device_cache.evict_tenant(tenant)
        freed = max(before - self.sizes().get(tenant, 0), 0)
        with self._lock:
            st = self._tenants.get(tenant)
            if st is not None:
                st.evictions += 1
        if self._c_evictions is not None:
            self._c_evictions.labels(tenant=tenant, reason=reason).inc()
        try:
            from predictionio_tpu.obs.flight import FLIGHT
            FLIGHT.record("tenant_eviction", tenant=tenant,
                          reason=reason, bytesFreed=int(freed))
        except Exception:
            logger.debug("tenant eviction flight record failed",
                         exc_info=True)
        logger.info("tenant %s evicted (%s): %d bytes freed",
                    tenant, reason, freed)
        return {"tenant": tenant, "reason": reason,
                "bytesFreed": int(freed)}

    # -- introspection ------------------------------------------------------
    def snapshot(self) -> dict:
        sizes = self.sizes()
        with self._lock:
            tenants = {t: dict(st.snapshot(),
                               hbmBytes=int(sizes.get(t, 0)))
                       for t, st in self._tenants.items()}
        return {
            "budgetBytes": self.budget_bytes,
            "residentBytes": int(sum(sizes.values())),
            "tenants": tenants,
        }
