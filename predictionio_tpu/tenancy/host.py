"""ServingHost: route queries by app/engine key to per-tenant slots.

One process, one accelerator, many engines. Each tenant is a full
:class:`~predictionio_tpu.serving.server.EngineServer` slot — its own
micro-batcher/pipelined executor, canary controller, rollback anchors,
scheduler attachment and tenant-namespaced result-cache view — loaded
from its own engine instance and addressed as
``/engines/<tenant>/...``. What the slots SHARE is the device: the
process-wide compile-plane bucket ladder (two tenants with identical
shapes reuse the same AOT executables — the packing payoff), the
persistent XLA cache, and the HBM the
:class:`~predictionio_tpu.tenancy.budget.HBMBudgetManager` arbitrates.

Isolation contracts (tested by tests/test_tenancy.py):

- a query for tenant A can never be answered from tenant B's cached
  result (tenant-prefixed result-cache keys, ISSUE 15 satellite);
- tenant B's eviction never touches tenant A's models, caches,
  canary state or last-known-good pins;
- eviction never fires mid-dispatch on an in-flight window: the
  evictor quiesces the slot first (the PR 13 snapshot discipline
  extended to residency handles) and skips the drop on timeout;
- an evicted tenant's next query re-uploads from host mirrors and
  serves byte-identical rankings (the mirrors are the truth).
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from dataclasses import dataclass
from typing import Dict, Optional

from predictionio_tpu.obs import FLIGHT, MetricsRegistry, fleet, \
    get_registry
from predictionio_tpu.obs.tenantctx import register_tenant, tenant_scope
from predictionio_tpu.parallel.mesh import device_platform, device_stats
from predictionio_tpu.serving.server import EngineServer, ServerConfig
from predictionio_tpu.tenancy import props as tenant_props
from predictionio_tpu.tenancy.auth import AccessKeyGate, auth_enabled
from predictionio_tpu.tenancy.budget import HBMBudgetManager, _iter_tables
from predictionio_tpu.utils import device_cache
from predictionio_tpu.utils.http import (HttpServer, Request, Response,
                                         Router)

logger = logging.getLogger(__name__)

#: characters a tenant key must not contain: path separators (the key
#: is a URL segment) and the result-cache namespace separator
_FORBIDDEN = set("/\x1f\n\r")


def _check_key(key: str) -> str:
    key = str(key)
    if not key or _FORBIDDEN.intersection(key):
        raise ValueError(f"invalid tenant key {key!r}")
    return key


@dataclass
class TenantSpec:
    """One tenant: which engine instance to serve, and its packing
    policy. ``key`` is the routing segment (conventionally
    ``<app>-<engine>`` or the engine id). Higher ``priority`` evicts
    later; ``pinned`` never auto-evicts (operator evict still works)."""
    key: str
    engine_id: Optional[str] = None
    engine_version: str = "0"
    engine_variant: str = "engine.json"
    engine_instance_id: Optional[str] = None
    priority: int = 0
    pinned: bool = False
    #: full per-slot ServerConfig override; None derives one from the
    #: engine coordinates above with stock serving defaults
    server_config: Optional[ServerConfig] = None


class TenantSlot:
    """One admitted tenant: its engine server plus the in-flight gate
    the evictor quiesces against."""

    def __init__(self, spec: TenantSpec, server: EngineServer):
        self.key = spec.key
        self.spec = spec
        self.server = server
        self.scheduler = None
        self.scheduler_config = None
        self.requests = 0
        self.errors = 0
        self.admitted_at = time.time()
        #: True when this tenant's tables may not be resident (fresh
        #: admission or post-eviction) — the next query calls
        #: ensure_room before dispatching
        self.cold = True
        self._cond = threading.Condition()
        self._inflight = 0
        self._evicting = False

    # -- the in-flight gate --------------------------------------------------
    @contextlib.contextmanager
    def serving(self):
        """Count one request in flight; entry waits out an active
        eviction (eviction windows are bounded by the quiesce
        timeout)."""
        with self._cond:
            while self._evicting:
                self._cond.wait(timeout=1.0)
            self._inflight += 1
        try:
            yield
        finally:
            with self._cond:
                self._inflight -= 1
                if self._inflight <= 0:
                    self._cond.notify_all()

    @contextlib.contextmanager
    def quiesced(self, timeout_s: float):
        """Block new requests and wait for in-flight ones to drain;
        yields True when drained (the evictor may drop residency) or
        False on timeout (it must NOT — an in-flight window's inputs
        stay pinned)."""
        with self._cond:
            self._evicting = True
            deadline = time.monotonic() + timeout_s
            while self._inflight > 0:
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                self._cond.wait(timeout=left)
            drained = self._inflight == 0
        try:
            yield drained
        finally:
            with self._cond:
                self._evicting = False
                self._cond.notify_all()

    def inflight(self) -> int:
        with self._cond:
            return self._inflight

    def status(self) -> dict:
        srv = self.server
        return {
            "tenant": self.key,
            "engineId": self.spec.engine_id,
            "engineVersion": self.spec.engine_version,
            "engineVariant": self.spec.engine_variant,
            "modelVersion": srv.model_version,
            "lastGoodVersion": srv.last_good_version,
            "requests": self.requests,
            "errors": self.errors,
            "inflight": self.inflight(),
            "cold": self.cold,
            "scheduler": self.scheduler is not None,
            "canary": srv.canary.stats(),
            "modelSharding": srv._model_sharding(),
            "admittedAt": self.admitted_at,
        }


@dataclass
class HostConfig:
    ip: str = "0.0.0.0"
    port: int = 8100
    #: per-device HBM table budget for the whole host; None reads the
    #: enforced PIO_TABLE_BUDGET_BYTES (None there too = accounting
    #: only)
    budget_bytes: Optional[int] = None
    #: one shared result cache for every tenant (tenant-namespaced
    #: keys); budgets are host-wide so a hot tenant can use the pool
    result_cache: bool = True
    result_cache_max_entries: int = 8192
    result_cache_max_bytes: int = 64 << 20
    #: how long an eviction may wait for a slot's in-flight windows
    #: before giving up (the drop is skipped, never forced)
    evict_quiesce_timeout_s: float = 10.0


class ServingHost:
    def __init__(self, config: Optional[HostConfig] = None):
        self.config = config or HostConfig()
        # a host owns the device for all its tenants: fail at
        # construction, not at the first admit, when it cannot have it
        device_platform()
        self._lock = threading.RLock()
        self.slots: Dict[str, TenantSlot] = {}
        self.start_time = time.time()
        self.metrics = MetricsRegistry(parent=get_registry())
        self.budget = HBMBudgetManager(self.config.budget_bytes,
                                       registry=self.metrics)
        self._c_requests = self.metrics.counter(
            "pio_tenant_requests_total",
            "Queries routed to each serving tenant",
            labelnames=("tenant",))
        self.metrics.gauge_func(
            "pio_host_tenants",
            "Tenant slots admitted on this serving host",
            lambda: len(self.slots))
        from predictionio_tpu.serving import result_cache as RC
        self.result_cache = None
        if self.config.result_cache and RC.cache_enabled():
            self.result_cache = RC.ResultCache(
                max_entries=self.config.result_cache_max_entries,
                max_bytes=self.config.result_cache_max_bytes,
                metrics=self.metrics)
        self.server: Optional[HttpServer] = None
        self._fleet_id: Optional[str] = None
        # per-tenant traffic EWMA state: key -> [t, requests, ewma]
        self._traffic: Dict[str, list] = {}
        # placement generation fence (ISSUE 18): key -> the newest
        # generation a control action (admit/remove) named. A stale
        # controller retry or a router holding an old placement can
        # never act or serve against a superseded generation. Kept
        # monotonic even after removal, so a delayed re-admit of an
        # already-migrated tenant is refused.
        self._placement_gen: Dict[str, int] = {}
        # access-key gate (PIO_AUTH=on, ISSUE 18 satellite): armed at
        # construction so the per-request cost is one None-check
        self._auth = AccessKeyGate() if auth_enabled() else None
        # per-host fold-tick fairness gate, created with the first
        # attached scheduler (online/scheduler.FoldTickGate)
        self.tick_gate = None
        self.router = self._build_router()

    # -- tenant lifecycle ---------------------------------------------------
    def _slot_config(self, spec: TenantSpec) -> ServerConfig:
        if spec.server_config is not None:
            return spec.server_config
        return ServerConfig(
            engine_instance_id=spec.engine_instance_id,
            engine_id=spec.engine_id,
            engine_version=spec.engine_version,
            engine_variant=spec.engine_variant)

    def add_tenant(self, spec: TenantSpec, engine=None,
                   engine_params=None) -> TenantSlot:
        """Load + admit one tenant. The load happens OUTSIDE the host
        lock (model deserialization can be slow; other tenants keep
        serving); admission control runs before the slot becomes
        routable — a tenant whose padded tables can never fit raises
        :class:`TableBudgetExceeded` and leaves no slot behind."""
        key = _check_key(spec.key)
        register_tenant(key)   # bounded metric-label cardinality
        self._overlay_props(spec)
        with self._lock:
            if key in self.slots:
                raise ValueError(f"tenant {key!r} already admitted")
        server = EngineServer(self._slot_config(spec), engine=engine,
                              engine_params=engine_params, tenant=key,
                              shared_result_cache=self.result_cache)
        with device_cache.tenant_scope(key):
            server.load()
        slot = TenantSlot(spec, server)
        try:
            self.budget.admit(
                key, server.models, priority=spec.priority,
                pinned=spec.pinned,
                sizer=lambda s=slot: self._sharded_devs(s),
                evictor=lambda s=slot: self._evict_slot(s))
        except Exception:
            server.stop()
            raise
        with self._lock:
            self.slots[key] = slot
        FLIGHT.record("tenant_admitted", tenant=key,
                      model_version=server.model_version,
                      expectedPaddedBytes=self.budget.snapshot()
                      ["tenants"][key]["expectedPaddedBytes"])
        logger.info("tenant %s admitted (instance %s)", key,
                    server.model_version)
        self._publish_roster()
        return slot

    def _overlay_props(self, spec: TenantSpec):
        """Overlay the durable per-tenant props (tenancy/props.py) on
        the static spec: a ``pio tenants pin`` taken before a host
        restart must survive it (ISSUE 18 satellite)."""
        stored = tenant_props.load_props(spec.key)
        if not stored:
            return
        if "priority" in stored:
            spec.priority = int(stored["priority"])
        if "pinned" in stored:
            spec.pinned = bool(stored["pinned"])

    def admit_server(self, spec: TenantSpec,
                     server: EngineServer) -> TenantSlot:
        """Admit a pre-built, already-loaded :class:`EngineServer` as a
        tenant slot (bench/test path; production slots go through
        :meth:`add_tenant`, which loads from the engine-instance
        store). The server must have been constructed with
        ``tenant=spec.key`` so its uploads carry the attribution tag —
        refused otherwise (untagged uploads would make this tenant
        unevictable AND unaccounted)."""
        key = _check_key(spec.key)
        if server.tenant != key:
            raise ValueError(
                f"server.tenant {server.tenant!r} != spec.key {key!r}: "
                f"construct the EngineServer with tenant=<key>")
        register_tenant(key)
        self._overlay_props(spec)
        with self._lock:
            if key in self.slots:
                raise ValueError(f"tenant {key!r} already admitted")
        slot = TenantSlot(spec, server)
        self.budget.admit(
            key, server.models, priority=spec.priority,
            pinned=spec.pinned,
            sizer=lambda s=slot: self._sharded_devs(s),
            evictor=lambda s=slot: self._evict_slot(s))
        with self._lock:
            self.slots[key] = slot
        self._publish_roster()
        return slot

    def remove_tenant(self, key: str) -> bool:
        with self._lock:
            slot = self.slots.pop(key, None)
        if slot is None:
            return False
        if slot.scheduler is not None:
            try:
                slot.scheduler.stop()
            except Exception:
                logger.exception("tenant %s scheduler stop failed", key)
        self.budget.evict(key, reason="remove")
        self.budget.forget(key)
        slot.server.stop()
        FLIGHT.record("tenant_removed", tenant=key)
        self._publish_roster()
        return True

    def attach_scheduler(self, key: str, config, **kw):
        """Attach a fold-in scheduler to one tenant slot — every fold
        tick runs under the tenant's device attribution scope, and its
        publishes hot-swap only this slot. All schedulers on one host
        share the host's :class:`FoldTickGate`, so contending tenants
        round-robin the device by staleness instead of FIFO thread
        wakeup (ISSUE 18 satellite)."""
        from predictionio_tpu.online.scheduler import (FoldTickGate,
                                                       attach_scheduler)
        with self._lock:
            if self.tick_gate is None:
                self.tick_gate = FoldTickGate(registry=self.metrics)
            gate = self.tick_gate
        kw.setdefault("tick_gate", gate)
        slot = self._slot(key)
        sched = attach_scheduler(slot.server, config, tenant=key, **kw)
        slot.scheduler = sched
        slot.scheduler_config = config
        self._publish_roster()
        return sched

    # -- eviction mechanism -------------------------------------------------
    @staticmethod
    def _sharded_tables(slot: TenantSlot):
        from predictionio_tpu.parallel.sharded_table import is_sharded
        return [t for t in _iter_tables(slot.server.models)
                if is_sharded(t)]

    def _sharded_devs(self, slot: TenantSlot) -> list:
        """The slot's resident ShardedTable device handles — arrays,
        not bytes: the budget manager identity-dedupes them against
        the fold-residency payloads carrying the same handles."""
        return [t._dev for t in self._sharded_tables(slot)
                if t._dev is not None]

    def _evict_slot(self, slot: TenantSlot):
        """The per-slot evictor the budget manager calls: quiesce the
        in-flight gate, then drop the tenant's device-cache entries,
        residency slots and sharded-table handles. On quiesce timeout
        the drop is SKIPPED — an in-flight window must complete against
        the handles it snapshotted (PR 13 semantics; its closures pin
        the arrays anyway, so a forced drop would only lie about
        freed bytes)."""
        with slot.quiesced(self.config.evict_quiesce_timeout_s) \
                as drained:
            if not drained:
                logger.warning(
                    "tenant %s eviction skipped: %d windows still in "
                    "flight after %.1fs", slot.key, slot.inflight(),
                    self.config.evict_quiesce_timeout_s)
                return
            device_cache.evict_tenant(slot.key)
            for t in self._sharded_tables(slot):
                t.drop_device()
            slot.cold = True

    def evict_tenant(self, key: str, reason: str = "operator") -> dict:
        self._slot(key)   # KeyError on unknown tenant
        return self.budget.evict(key, reason=reason)

    # -- routing ------------------------------------------------------------
    def _slot(self, key: str) -> TenantSlot:
        slot = self.slots.get(key)
        if slot is None:
            raise KeyError(key)
        return slot

    def _tenant_query(self, req: Request) -> Response:
        key = req.path_args[0]
        slot = self.slots.get(key)
        if slot is None:
            return Response(404, {"message": f"unknown tenant {key!r}"})
        if self._auth is not None:
            denied = self._auth.check(
                req, getattr(slot.server.config, "accesskey", None)
                or None)
            if denied is not None:
                return denied
        # generation fence (ISSUE 18): a router that attaches the
        # placement generation it routed by gets an honest 409 when
        # that placement has been superseded — refresh, don't serve
        gen_hdr = req.headers.get("x-pio-placement-gen") \
            if req.headers else None
        if gen_hdr is not None:
            try:
                if int(gen_hdr) < self._placement_gen.get(key, 0):
                    return Response(409, {
                        "message": "stale placement route",
                        "tenant": key,
                        "generation": self._placement_gen.get(key, 0)})
            except (TypeError, ValueError):
                pass
        # tenant attribution scope (ISSUE 17): everything this request
        # touches on the way down — budget room-making, slowlog
        # captures, flight records, trace roots, device dispatch — is
        # stamped/booked under this tenant
        with tenant_scope(key):
            self._c_requests.labels(tenant=key).inc()
            slot.requests += 1
            self.budget.touch(key)
            if slot.cold:
                # fresh admission or post-eviction readmission: make
                # the budget hold before this tenant's tables come
                # (back) resident — evicts the coldest neighbors if
                # needed
                self.budget.ensure_room(key)
                slot.cold = False
            req.path = "/queries.json"
            with slot.serving():
                resp = slot.server.router.dispatch(req)
        if resp.status >= 500:
            slot.errors += 1
        return resp

    def _delegate(self, req: Request) -> Response:
        """Forward ``/engines/<key>/<endpoint>`` to the slot server's
        own router (stats, metrics, health, reload, ...)."""
        key = req.path_args[0]
        slot = self.slots.get(key)
        if slot is None:
            return Response(404, {"message": f"unknown tenant {key!r}"})
        req.path = req.path[len(f"/engines/{key}"):]
        with tenant_scope(key), slot.serving():
            return slot.server.router.dispatch(req)

    # -- host surfaces ------------------------------------------------------
    def _tenants_block(self) -> dict:
        budget = self.budget.snapshot()
        out = {}
        with self._lock:
            slots = list(self.slots.values())
        for slot in slots:
            entry = slot.status()
            entry.update(budget["tenants"].get(slot.key, {}))
            out[slot.key] = entry
        return out

    def _stats(self, req: Request) -> Response:
        budget = self.budget.snapshot()
        with self._lock:
            total = sum(s.requests for s in self.slots.values())
        out = {
            "role": "serving_host",
            **device_stats(),
            "startTime": self.start_time,
            "requestCount": total,
            "tenants": self._tenants_block(),
            "budget": {k: budget[k]
                       for k in ("budgetBytes", "residentBytes")},
        }
        if self.result_cache is not None:
            out["resultCache"] = self.result_cache.stats()
        try:
            from predictionio_tpu.compile.aot import get_aot
            out["aot"] = get_aot().snapshot()
        except Exception:
            logger.debug("aot stats unavailable", exc_info=True)
        return Response(200, out)

    def _tenants(self, req: Request) -> Response:
        return Response(200, {"tenants": self._tenants_block()})

    def _tenant_evict(self, req: Request) -> Response:
        key = req.path_args[0]
        try:
            return Response(200, self.evict_tenant(key))
        except KeyError:
            return Response(404, {"message": f"unknown tenant {key!r}"})

    def _tenant_pin(self, req: Request) -> Response:
        key = req.path_args[0]
        pinned = not req.path.endswith("/unpin")
        if not self.budget.pin(key, pinned):
            return Response(404, {"message": f"unknown tenant {key!r}"})
        # persist the pin as a durable tenant prop so a host restart
        # re-admits with it (ISSUE 18 satellite); the in-memory ledger
        # flip above is the serving truth either way
        persisted = tenant_props.save_props(key, pinned=pinned)
        slot = self.slots.get(key)
        if slot is not None:
            slot.spec.pinned = pinned
        self._publish_roster()
        return Response(200, {"tenant": key, "pinned": pinned,
                              "persisted": persisted is not None})

    # -- control plane (ISSUE 18): remote admit/remove + roster -------------
    _SCHED_FIELDS = ("app_name", "channel_name", "event_names",
                     "max_deltas", "max_staleness_s", "poll_interval_s",
                     "tail_batch_limit", "filtered_reads")

    def _sched_dict(self, cfg) -> dict:
        out = {}
        for k in self._SCHED_FIELDS:
            v = getattr(cfg, k, None)
            if v is not None:
                out[k] = list(v) if isinstance(v, tuple) else v
        return out

    def _publish_roster(self):
        """Re-publish this host's member record with its full tenant
        roster (spec + generation + scheduler config). The roster must
        live ON the record, refreshed at every admit/remove/pin: when
        this process is SIGKILLed, the corpse record is the failover
        controller's only source for which tenants to re-place and how
        to rebuild them (engine coords -> registry lineage, scheduler
        config -> fold-tail catch-up)."""
        with self._lock:
            fid = self._fleet_id
            slots = list(self.slots.values())
            gens = dict(self._placement_gen)
        if not fid:
            return
        roster = {}
        for slot in slots:
            spec = slot.spec
            entry = {
                "engineId": spec.engine_id,
                "engineVersion": spec.engine_version,
                "engineVariant": spec.engine_variant,
                "engineInstanceId": spec.engine_instance_id,
                "priority": spec.priority,
                "pinned": spec.pinned,
                "generation": gens.get(slot.key, 0),
            }
            if slot.scheduler_config is not None:
                entry["scheduler"] = self._sched_dict(
                    slot.scheduler_config)
            roster[slot.key] = entry
        fleet.update_member(fid, {"tenants": roster})

    def _fence(self, key: str, gen) -> Optional[Response]:
        """409 when ``gen`` is older than the newest generation a
        control action named for this tenant; otherwise records it."""
        try:
            gen = int(gen or 0)
        except (TypeError, ValueError):
            return Response(400, {"message": "generation must be int"})
        with self._lock:
            cur = self._placement_gen.get(key, 0)
            if gen < cur:
                return Response(409, {
                    "message": "stale placement generation",
                    "tenant": key, "generation": cur})
            self._placement_gen[key] = gen
        return None

    def _tenant_admit(self, req: Request) -> Response:
        """``POST /tenants/<key>/admit`` — the controller's remote
        admission path. Body: engine coordinates (+ optional priority/
        pinned/scheduler config) and the placement ``generation``.
        Loads from registry lineage, AOT-warms before the slot becomes
        routable (add_tenant -> EngineServer.load), attaches the fold
        scheduler when configured (its cursor resumes from the
        published lineage — the fold-tail catch-up), and refuses
        honestly on budget exhaustion (409, the controller re-plans)."""
        key = req.path_args[0]
        try:
            body = req.json() or {}
        except ValueError:
            return Response(400, {"message": "body must be JSON"})
        fence = self._fence(key, body.get("generation"))
        if fence is not None:
            return fence
        with self._lock:
            if key in self.slots:
                return Response(200, {"tenant": key,
                                      "alreadyAdmitted": True})
        spec = TenantSpec(
            key=key,
            engine_id=body.get("engineId"),
            engine_version=str(body.get("engineVersion") or "0"),
            engine_variant=body.get("engineVariant") or "engine.json",
            engine_instance_id=body.get("engineInstanceId"),
            priority=int(body.get("priority") or 0),
            pinned=bool(body.get("pinned")))
        from predictionio_tpu.tenancy.budget import TableBudgetExceeded
        try:
            self.add_tenant(spec)
        except TableBudgetExceeded as e:
            return Response(409, {"message": f"admission refused: {e}",
                                  "tenant": key})
        except ValueError as e:
            return Response(409, {"message": str(e), "tenant": key})
        except Exception as e:
            logger.exception("tenant %s remote admission failed", key)
            return Response(500, {"message": f"admission failed: {e}",
                                  "tenant": key})
        sched = body.get("scheduler")
        if isinstance(sched, dict) and sched.get("app_name"):
            try:
                from predictionio_tpu.online.registry import \
                    ModelVersionRegistry
                from predictionio_tpu.online.scheduler import \
                    SchedulerConfig
                cfg = SchedulerConfig(**{
                    k: sched[k] for k in self._SCHED_FIELDS
                    if k in sched})
                self.attach_scheduler(
                    key, cfg, registry=ModelVersionRegistry()).start()
            except Exception:
                # the tenant serves; a broken fold attachment is an
                # incident, not a failed admission
                logger.exception("tenant %s scheduler attach failed",
                                 key)
        slot = self.slots.get(key)
        return Response(200, {
            "tenant": key,
            "generation": self._placement_gen.get(key, 0),
            "modelVersion": slot.server.model_version if slot else None,
            "scheduler": bool(slot and slot.scheduler is not None)})

    def _tenant_remove(self, req: Request) -> Response:
        """``POST /tenants/<key>/remove`` — generation-fenced removal,
        the last step of a planned migration (the target host owns the
        newer generation by then, so a stale retry cannot re-kill)."""
        key = req.path_args[0]
        try:
            body = req.json() or {}
        except ValueError:
            body = {}
        fence = self._fence(key, body.get("generation"))
        if fence is not None:
            return fence
        if not self.remove_tenant(key):
            return Response(404, {"message": f"unknown tenant {key!r}"})
        return Response(200, {"tenant": key, "removed": True,
                              "generation":
                                  self._placement_gen.get(key, 0)})

    def _placement(self, req: Request) -> Response:
        """``GET /placement.json`` — the host's placement truth: per
        tenant the generation, spec and budget row the controller
        plans against."""
        budget = self.budget.snapshot()
        with self._lock:
            slots = list(self.slots.values())
            gens = dict(self._placement_gen)
        tenants = {}
        for slot in slots:
            spec = slot.spec
            tenants[slot.key] = {
                "generation": gens.get(slot.key, 0),
                "engineId": spec.engine_id,
                "engineVersion": spec.engine_version,
                "engineVariant": spec.engine_variant,
                "engineInstanceId": spec.engine_instance_id,
                "priority": spec.priority,
                "pinned": spec.pinned,
                "cold": slot.cold,
                "scheduler": slot.scheduler is not None,
                "expectedPaddedBytes": budget["tenants"].get(
                    slot.key, {}).get("expectedPaddedBytes", 0),
            }
        return Response(200, {
            "memberId": self._fleet_id,
            "budgetBytes": budget["budgetBytes"],
            "residentBytes": budget["residentBytes"],
            "generations": gens,
            "tenants": tenants,
        })

    def _metrics(self, req: Request) -> Response:
        """One scrape for the whole host: the host/process families
        plus every slot registry's OWN families re-labeled with
        ``tenant`` (ISSUE 17) — so serve histograms, canary counters
        and cache stats from different slots are distinct series under
        shared family names, and the fleet federator's ``{role,pid}``
        relabeling stacks on top."""
        from predictionio_tpu.obs.fleet import merge_scrapes
        from predictionio_tpu.utils.prometheus import CONTENT_TYPE
        with self._lock:
            slots = list(self.slots.values())
        parts = [(self.metrics.render(), {})]
        for slot in slots:
            try:
                parts.append(
                    (slot.server.metrics.render(include_parent=False),
                     {"tenant": slot.key}))
            except Exception:
                logger.debug("tenant %s metrics render failed",
                             slot.key, exc_info=True)
        return Response(200, merge_scrapes(parts),
                        content_type=CONTENT_TYPE)

    def _health(self, req: Request) -> Response:
        """Worst-of rollup across tenant slots' SLO engines. Each
        slot's breach transitions are noted under its tenant scope, so
        a breached slot captures an incident bundle naming THAT tenant
        (and only its forensics slice) — the noisy neighbor stays out
        of the victim's postmortem and vice versa."""
        from predictionio_tpu.obs import health_response
        rank = {"ok": 0, "burning": 1, "no_data": 0, "breached": 2}
        worst, tenants = "ok", {}
        with self._lock:
            slots = list(self.slots.values())
        for slot in slots:
            with tenant_scope(slot.key):
                h = health_response(slot.server.slo, extra={
                    "modelVersion": slot.server.model_version,
                    "tenant": slot.key})
                try:
                    slot.server._note_slo_breaches(h)
                except Exception:
                    logger.debug("tenant %s breach note failed",
                                 slot.key, exc_info=True)
            tenants[slot.key] = h
            if rank.get(h.get("status"), 0) > rank.get(worst, 0):
                worst = h["status"]
        return Response(200, {"status": worst, "tenants": tenants})

    # -- per-tenant signals (ISSUE 17) --------------------------------------
    def _traffic_ewma(self, key: str, requests: int) -> float:
        """Lazily-updated per-tenant request-rate EWMA (alpha 0.3 per
        observation window), advanced on each signals read from the
        slot's cumulative request counter."""
        now = time.monotonic()
        st = self._traffic.get(key)
        if st is None:
            self._traffic[key] = [now, requests, 0.0]
            return 0.0
        last_t, last_n, ewma = st
        dt = now - last_t
        if dt >= 0.2:   # too-close reads would amplify quantization
            inst = max(0.0, requests - last_n) / dt
            ewma = inst if ewma == 0.0 else 0.7 * ewma + 0.3 * inst
            self._traffic[key] = [now, requests, ewma]
        return ewma

    def tenant_signals(self) -> dict:
        """The ``GET /tenants/signals.json`` body: one row per tenant
        with its traffic, latency, burn, memory and device-time
        attribution — the single surface that answers "who is eating
        the device" (docs/operations.md)."""
        from predictionio_tpu.obs import costmon
        from predictionio_tpu.obs.metrics import get_registry
        budget = self.budget.snapshot()
        dev_share = costmon.tenant_device_time_share()
        occ_share = costmon.tenant_occupancy_shares()
        # per-tenant serve readback bytes (ISSUE 19): the packed d2h
        # plane attributes every fetched byte to the obs-plane tenant
        # context, so the bill decomposes transfer cost too
        d2h_bytes = {}
        fam = get_registry().get("pio_tenant_serve_d2h_bytes_total")
        if fam is not None:
            for labels, value in fam.samples():
                if labels:
                    d2h_bytes[labels.get("tenant", "")] = int(value)
        with self._lock:
            slots = list(self.slots.values())
        tenants = {}
        for slot in slots:
            srv = slot.server
            row = {
                "requests": slot.requests,
                "errors": slot.errors,
                "trafficEwmaRps": round(
                    self._traffic_ewma(slot.key, slot.requests), 3),
                "deviceTimeShare": dev_share.get(slot.key, 0.0),
                "occupancyShare": occ_share.get(slot.key, 0.0),
                "serveD2hBytes": d2h_bytes.get(slot.key, 0),
                "modelStalenessS": srv.model_staleness_s(),
                "modelVersion": srv.model_version,
            }
            b = budget["tenants"].get(slot.key, {})
            row["hbmBytes"] = b.get("hbmBytes", 0)
            row["evictions"] = b.get("evictions", 0)
            fam = srv.metrics.get("pio_engine_query_seconds")
            if fam is not None and getattr(fam, "count", 0):
                p50, p99 = fam.percentile(50), fam.percentile(99)
                row["serveP50Ms"] = round(p50 * 1000.0, 3) \
                    if p50 is not None else None
                row["serveP99Ms"] = round(p99 * 1000.0, 3) \
                    if p99 is not None else None
            else:
                row["serveP50Ms"] = row["serveP99Ms"] = None
            try:
                h = srv.slo.evaluate()
                row["sloStatus"] = h["status"]
                serve = next((s for s in h["slo"]
                              if s["name"] == "serve_p99"), {})
                row["burnFast"] = serve.get("burnFast")
                row["burnSlow"] = serve.get("burnSlow")
            except Exception:
                row["sloStatus"] = "no_data"
                row["burnFast"] = row["burnSlow"] = None
            tenants[slot.key] = row
        return {
            "tenants": tenants,
            # the full attribution maps, "" = untenanted process work:
            # the smoke check asserts sum(deviceTimeShare) <= 1.0 over
            # THESE (per-slot rows omit departed tenants' residue)
            "deviceTimeShare": dev_share,
            "occupancyShare": occ_share,
            "budgetBytes": budget["budgetBytes"],
            "residentBytes": budget["residentBytes"],
        }

    def _signals(self, req: Request) -> Response:
        return Response(200, self.tenant_signals())

    def _status_page(self, req: Request) -> Response:
        return Response(200, {
            "role": "serving_host",
            "tenants": sorted(self.slots),
            "budget": self.budget.snapshot()["budgetBytes"],
        })

    def _build_router(self) -> Router:
        r = Router()
        r.add("GET", "/", self._status_page)
        r.add("POST", "/engines/<key>/queries.json", self._tenant_query)
        for ep in ("stats.json", "metrics", "health.json",
                   "plugins.json", "slow.json", "flight.json",
                   "traces.json"):
            r.add("GET", f"/engines/<key>/{ep}", self._delegate)
        r.add("POST", "/engines/<key>/reload", self._delegate)
        r.add("GET", "/engines/<key>/reload", self._delegate)
        r.add("GET", "/stats.json", self._stats)
        r.add("GET", "/tenants.json", self._tenants)
        r.add("GET", "/tenants/signals.json", self._signals)
        r.add("GET", "/placement.json", self._placement)
        r.add("POST", "/tenants/<key>/evict", self._tenant_evict)
        r.add("POST", "/tenants/<key>/admit", self._tenant_admit)
        r.add("POST", "/tenants/<key>/remove", self._tenant_remove)
        r.add("POST", "/tenants/<key>/pin", self._tenant_pin)
        r.add("POST", "/tenants/<key>/unpin", self._tenant_pin)
        r.add("GET", "/metrics", self._metrics)
        r.add("GET", "/health.json", self._health)
        return r

    # -- lifecycle ----------------------------------------------------------
    def start(self, background: bool = True) -> "ServingHost":
        from predictionio_tpu.obs import profiler
        profiler.ensure_started()
        srv = HttpServer(self.router, self.config.ip, self.config.port)
        self.server = srv

        def _bound(s):
            self.config.port = s.port
            fid = fleet.register_member("serving_host", port=s.port,
                                        host=self.config.ip)
            with self._lock:
                self._fleet_id = fid
            # the record now exists with the advertised url; stamp the
            # current roster on it so a crash any time after bind
            # leaves a forensically-complete corpse
            self._publish_roster()
            logger.info("Serving host started on %s:%d (%d tenants)",
                        self.config.ip, s.port, len(self.slots))

        srv.on_bound = _bound
        srv.start(background=background)
        return self

    def stop(self):
        with self._lock:
            fleet_id = self._fleet_id
            self._fleet_id = None
            keys = list(self.slots)
        fleet.deregister_member(fleet_id)
        if self.server:
            self.server.stop()
            self.server = None
        for key in keys:
            try:
                self.remove_tenant(key)
            except Exception:
                logger.exception("tenant %s removal failed on stop", key)
