"""The engine server: deployed query HTTP service.

Rebuilds the reference's ``CreateServer``
(reference: core/src/main/scala/io/prediction/workflow/CreateServer.scala:
ServerConfig :80-98, model restore + prepareDeploy :206-265, ServerActor
routes `/`, `/queries.json`, `/reload`, `/stop`, `/plugins.json` :461-708,
query path :490-641, feedback loop :526-596, serving counters :418-420).

TPU notes: models restored from the model store are re-uploaded to device
HBM lazily by each algorithm's first predict; the query path is host ->
jitted device scoring -> host JSON, with business-rule event reads kept off
the device path (the templates handle that). requestCount / avgServingSec /
lastServingSec counters match the reference status page.
"""

from __future__ import annotations

import collections
import json
import logging
import threading
import time
import urllib.request

import numpy as np
from dataclasses import dataclass
from typing import List, Optional

from predictionio_tpu.core.engine import Engine, EngineParams
from predictionio_tpu.data.event import format_event_time, utcnow
from predictionio_tpu.data.storage.registry import Storage
from predictionio_tpu.models import get_engine_factory
from predictionio_tpu.obs import (FLIGHT, MetricsRegistry, SLOEngine,
                                  TRACER, default_engine_specs, fleet,
                                  flight_response, get_incidents,
                                  get_registry, health_response,
                                  ingress_trace_kwargs, jaxmon,
                                  slow_response, trace_context_headers,
                                  traces_response)
from predictionio_tpu.obs.slowlog import (capture_slow_query,
                                          slow_threshold_s)
from predictionio_tpu.parallel.mesh import device_platform, device_stats
from predictionio_tpu.serving.plugins import EngineServerPluginContext
from predictionio_tpu.utils.http import (HttpServer, Request, Response,
                                         Router)

logger = logging.getLogger(__name__)


@dataclass
class ServerConfig:
    """(CreateServer.scala:80-98)"""
    ip: str = "0.0.0.0"
    port: int = 8000
    engine_instance_id: Optional[str] = None
    engine_id: Optional[str] = None
    engine_version: Optional[str] = None
    engine_variant: str = "engine.json"
    batch: str = ""
    accesskey: str = ""
    event_server_ip: str = "0.0.0.0"
    event_server_port: int = 7070
    feedback: bool = False
    # >1 coalesces concurrent queries into one batched device call
    # (beyond-parity). On by default so a plain `pio deploy` gets the same
    # concurrency mitigation the benchmarks measure. Coalescing is
    # drain-first and self-regulating (serving/batcher.py); the window
    # is held only while more submitted-but-unanswered queries exist
    # than the batch holds, so idle and closed-loop-serial traffic pay
    # nothing and max_wait_ms is just the stall bound on a counted
    # straggler between its submit and its enqueue — not a per-query
    # tax, and not a knob that needs tuning per link anymore.
    micro_batch: int = 16
    micro_batch_wait_ms: float = 5.0
    # optional cap on how long the oldest query may sit in the
    # coalescing stage (ms), for tail-latency-sensitive deployments
    micro_batch_latency_budget_ms: Optional[float] = None
    # pipelined serving executor (ISSUE 14): device batches allowed in
    # flight — batch N's device compute overlaps batch N+1's formation
    # and batch N-1's readback/serialization. None reads
    # PIO_SERVE_INFLIGHT (default 2); 1 restores the synchronous loop.
    # Forced to 1 under a multi-process mesh (collective ordering).
    serve_inflight: Optional[int] = None
    # adaptive batch sizing (ISSUE 14): scale the coalescing hold with
    # the pio_device_occupancy EWMA + queue depth instead of the fixed
    # wait-window, snapping targets to the warmed pow2 AOT buckets
    adaptive_batching: bool = True
    # touched-row-invalidated top-k result cache (ISSUE 14;
    # serving/result_cache.py). PIO_SERVE_CACHE=off also disables.
    result_cache: bool = True
    result_cache_max_entries: int = 8192
    result_cache_max_bytes: int = 64 << 20
    # multi-process mesh serving: per-query broadcast buffer size; raise
    # it when large micro-batched windows of filter-heavy queries exceed
    # the default 64 KiB (every broadcast ships the full buffer, so keep
    # it as small as the workload allows)
    mesh_broadcast_bytes: int = 1 << 16
    # watchdog deadline for the primary's per-query broadcast collective:
    # if a worker process dies, the collective never completes — after
    # this many seconds the coordinator poisons itself and answers 503
    # (serving/mesh_serving.py MeshServingUnavailable) instead of
    # queueing every subsequent query forever
    mesh_broadcast_timeout_s: float = 30.0
    # guarded deploys (ISSUE 5, guard/canary.py): when canary_fraction
    # > 0, swap_models stages the new version as a CANDIDATE serving
    # only that traffic share (responses tagged X-PIO-Canary); a
    # watchdog compares error-rate / NaN-score / latency against the
    # incumbent and either promotes (after a clean canary_window_s) or
    # rolls back to the incumbent automatically. 0 keeps the PR 1
    # immediate-swap behavior.
    canary_fraction: float = 0.0
    canary_window_s: float = 30.0
    canary_min_requests: int = 20
    canary_max_error_ratio: float = 2.0
    canary_max_latency_ratio: float = 3.0
    canary_nan_tolerance: int = 0


class EngineServer:
    def __init__(self, config: ServerConfig,
                 engine: Optional[Engine] = None,
                 engine_params: Optional[EngineParams] = None,
                 plugin_context: Optional[EngineServerPluginContext] = None,
                 mesh_coordinator=None,
                 tenant: Optional[str] = None,
                 shared_result_cache=None):
        self.config = config
        # multi-tenant serving (ISSUE 15): when this server is one slot
        # of a tenancy.ServingHost, `tenant` names it — every device
        # upload the query/warm paths trigger runs under a
        # device_cache.tenant_scope so the HBM budget manager can
        # account and evict this tenant's tables independently, and the
        # (host-shared) result cache is namespaced per tenant.
        self.tenant = str(tenant) if tenant is not None else None
        if self.tenant is not None:
            # bounded metric-label cardinality: only registered
            # tenants get a named ``tenant`` label value (ISSUE 17)
            from predictionio_tpu.obs.tenantctx import register_tenant
            register_tenant(self.tenant)
        self._lock = threading.RLock()
        # the one backend initialization of this process: anything but
        # a TPU is an error unless JAX_PLATFORMS=cpu says otherwise
        self.device = device_platform()
        # multi-process mesh serving: under a >1-process JAX mesh every
        # process must run each query's SPMD program, so the primary
        # broadcasts payloads and workers mirror the pipeline
        # (serving/mesh_serving.py; CreateServer.scala:490-641 role)
        if mesh_coordinator is None:
            from predictionio_tpu.serving.mesh_serving import \
                MeshQueryCoordinator
            mesh_coordinator = MeshQueryCoordinator.create_if_distributed(
                max_bytes=config.mesh_broadcast_bytes,
                broadcast_timeout_s=config.mesh_broadcast_timeout_s)
        self.coordinator = mesh_coordinator
        self.engine = engine
        self.engine_params = engine_params
        self.engine_instance = None
        self.algorithms = []
        self.models = []
        self.serving = None
        self.plugin_context = (plugin_context or
                               EngineServerPluginContext.load_from_env())
        # serving counters (CreateServer.scala:418-420), plus a predict-time
        # split so operators can tell device/score time from HTTP+serve
        # overhead (beyond-parity observability)
        self.request_count = 0
        self.serving_seconds = 0.0
        self.last_serving_sec = 0.0
        self.predict_seconds = 0.0
        # per-request serving-time ring for tail percentiles (p50/p95/p99
        # in /stats.json); 4096 samples bounds memory and keeps the
        # percentiles a rolling view of recent traffic
        self._lat_ring = collections.deque(maxlen=4096)
        # online-update counters (ISSUE 1 hot-swap observability): every
        # model replacement after the initial load counts as a swap —
        # /reload instance swaps and in-process fold-in swaps alike
        self.swap_count = 0
        self.fold_in_count = 0
        self.fold_in_events = 0
        self.model_version: Optional[str] = None
        # graceful degradation (ISSUE 3): when a fold-in publish/hot-swap
        # fails the server keeps answering from the stale-but-valid
        # model and advertises its age via the X-PIO-Model-Staleness-Ms
        # response header until a swap lands again
        self.publish_degraded = False
        self.publish_failures = 0
        self._last_swap_wall = time.time()
        self.start_time = utcnow()
        self.server: Optional[HttpServer] = None
        # ISSUE 2: this server's metrics registry, chained onto the
        # process-wide one (JAX telemetry, fold/train instruments ride
        # along on /metrics). Per-server counters keep the server as
        # their single source of truth and are sampled via func
        # collectors at scrape time; latency distributions are native
        # registry histograms.
        jaxmon.install()
        jaxmon.install_device_memory_gauge()
        self.metrics = MetricsRegistry(parent=get_registry())
        self._h_query = self.metrics.histogram(
            "pio_engine_query_seconds",
            "Per-query serving latency (batched queries observe the "
            "window's wall time each)")
        # diagnostics plane (ISSUE 6): per-executable compile/HBM
        # attribution, flight-recorder metric context from this
        # server's families, burn-rate SLOs at GET /health.json, and
        # an incident-bundle provider exposing serving + lineage state
        from predictionio_tpu.obs import costmon
        costmon.install()
        FLIGHT.add_source(self.metrics)
        # a tenant slot evaluates per-tenant spec thresholds
        # (PIO_SLO_*__<TENANT> overrides) and reads only its own
        # tenant's children out of tenant-labeled process families
        self.slo = SLOEngine(default_engine_specs(self.tenant),
                             registries=[self.metrics],
                             tenant=self.tenant)
        # last-seen status per SLO name: the ok->breached transition
        # detector behind the ISSUE 11 auto-capture in _health
        self._slo_status: dict = {}
        get_incidents().register_provider(
            "engine_server" if self.tenant is None
            else f"engine_server.{self.tenant}", self._incident_state)
        # guarded deploys (ISSUE 5): canary controller + rollback
        # anchors. last_good_version tracks the newest version this
        # server trusts (the loaded instance, then every promotion);
        # on_canary_decision lets the attached scheduler pin the
        # registry and escalate on rollback.
        from predictionio_tpu.guard.canary import (CanaryConfig,
                                                   CanaryController)
        self.canary = CanaryController(CanaryConfig(
            fraction=config.canary_fraction,
            window_s=config.canary_window_s,
            min_requests=config.canary_min_requests,
            max_error_ratio=config.canary_max_error_ratio,
            max_latency_ratio=config.canary_max_latency_ratio,
            nan_tolerance=config.canary_nan_tolerance),
            registry=self.metrics)
        self.last_good_version: Optional[str] = None
        self.on_canary_decision = None
        # compile plane (ISSUE 9): swap-to-first-query measurement.
        # _swap_marker = (version, t0, candidate_only) armed by every
        # model change (load/swap/canary stage/promote); the first query
        # completion that matches closes it into
        # last_swap_to_first_query_ms + a flight record — the end-to-end
        # number the AOT warm path exists to shrink.
        self._swap_marker = None
        self.last_swap_to_first_query_ms: Optional[float] = None
        self.last_aot_warm: Optional[dict] = None
        # fleet member record id (ISSUE 13), set by start()'s on_bound
        # hook under _lock (stop() may run on a /stop route thread)
        self._fleet_id: Optional[str] = None
        self._register_metrics()
        # pipelined executor + result cache (ISSUE 14): single-process
        # servers only. Under a multi-process mesh every query is a
        # collective whose enqueue/readback ordering must stay strictly
        # serialized across processes — and a cache hit on the primary
        # alone would (a) skip the collective the workers are waiting
        # to mirror and (b) keep answering 200 for hot queries after a
        # worker death, masking the coordinator's loud-503 poisoned
        # contract (ISSUE 3).
        single_process = (self.coordinator is None
                          or not self.coordinator.multi_process)
        from predictionio_tpu.serving import result_cache as RC
        self.result_cache = None
        if config.result_cache and single_process \
                and RC.cache_enabled():
            if shared_result_cache is not None and self.tenant is not None:
                # one host-wide budget, tenant-namespaced keys: two
                # tenants' byte-identical queries can never alias
                self.result_cache = RC.TenantResultCache(
                    shared_result_cache, self.tenant)
            else:
                self.result_cache = RC.ResultCache(
                    max_entries=config.result_cache_max_entries,
                    max_bytes=config.result_cache_max_bytes,
                    metrics=self.metrics)
        self.batcher = None
        if config.micro_batch > 1:
            from predictionio_tpu.serving.batcher import MicroBatcher
            self.batcher = MicroBatcher(
                self.handle_query_batch, max_batch=config.micro_batch,
                max_wait_ms=config.micro_batch_wait_ms,
                latency_budget_ms=config.micro_batch_latency_budget_ms,
                metrics=self.metrics, tenant=self.tenant,
                process_batch_begin=(self.handle_query_batch_begin
                                     if single_process else None),
                inflight=(config.serve_inflight
                          if single_process else 1),
                adaptive=config.adaptive_batching)
        # says what the process was doing whenever requests wait and no
        # dispatch moves for 0.4 s (obs/stallwatch.py); runs from start()
        # to stop()
        self.stallwatch = None
        if self.batcher is not None:
            from predictionio_tpu.obs.stallwatch import StallWatch
            b = self.batcher
            self.stallwatch = StallWatch(
                waiting=lambda: b._inflight,
                progress=lambda: (b.n_batches, b._inflight_batches),
                metrics=self.metrics)
        self.router = self._build_router()

    def _register_metrics(self):
        """Mount every serving counter on the registry. The func
        collectors sample the live attributes under no extra locks —
        scrape-time reads of GIL-atomic ints/floats."""
        m = self.metrics
        m.counter_func("pio_engine_requests_total", "Queries served",
                       lambda: self.request_count)
        m.counter_func("pio_engine_serving_seconds_total",
                       "Cumulative serve wall time",
                       lambda: self.serving_seconds)
        m.counter_func("pio_engine_predict_seconds_total",
                       "Cumulative device/predict time",
                       lambda: self.predict_seconds)
        m.counter_func("pio_engine_model_swaps_total",
                       "Hot model swaps since start (reloads + fold-ins)",
                       lambda: self.swap_count)
        m.counter_func("pio_engine_fold_ins_total",
                       "Online fold-in swaps since start",
                       lambda: self.fold_in_count)
        m.counter_func("pio_engine_fold_in_events_total",
                       "Events absorbed by online fold-ins",
                       lambda: self.fold_in_events)
        m.summary_func("pio_engine_serving_seconds",
                       "Recent serving-time quantiles (rolling ring)",
                       self._quantile_samples)
        m.gauge_func("pio_engine_model_stale",
                     "1 while serving a stale model because a fold-in "
                     "publish/hot-swap failed",
                     lambda: int(self.publish_degraded))
        m.gauge_func("pio_engine_model_staleness_seconds",
                     "Age of the serving model (since last load/swap)",
                     lambda: self.model_staleness_s())
        m.counter_func("pio_engine_publish_failures_total",
                       "Fold-in publish/hot-swap failures reported by "
                       "the scheduler",
                       lambda: self.publish_failures)
        m.gauge_func("pio_guard_canary_state",
                     "1 while a canary candidate version serves a "
                     "fraction of this server's traffic",
                     lambda: int(self.canary.active))
        m.gauge_func("pio_engine_swap_to_first_query_ms",
                     "Wall ms from the latest model change (load, "
                     "hot-swap, canary stage/promote) to its first "
                     "served query — compile-free when the AOT warm "
                     "path did its job",
                     lambda: self.last_swap_to_first_query_ms or 0.0)
        if self.coordinator is not None:
            m.gauge_func("pio_engine_mesh_processes",
                         "Processes in the serving mesh",
                         lambda: self.coordinator.health()["processes"])
            m.gauge_func("pio_engine_mesh_poisoned",
                         "1 when a mesh broadcast failed and every query "
                         "answers 503 until redeploy",
                         lambda: int(
                             self.coordinator.health()["poisoned"]))

    def _incident_state(self) -> dict:
        """Serving + model-lineage state frozen into incident bundles
        (obs/incidents.py). Lock-free attribute reads — an incident
        capture must never contend with the query path."""
        inst = self.engine_instance
        return {
            "modelVersion": self.model_version,
            "lastGoodVersion": self.last_good_version,
            "engineInstance": getattr(inst, "id", None),
            "lineage": getattr(inst, "batch", None),
            "requestCount": self.request_count,
            "modelSwaps": self.swap_count,
            "foldIns": self.fold_in_count,
            "publishDegraded": self.publish_degraded,
            "publishFailures": self.publish_failures,
            "modelStalenessSec": self.model_staleness_s(),
            "canary": self.canary.stats(),
        }

    def _model_sharding(self) -> list:
        """Per-algorithm factor-table layout for /stats.json (ISSUE
        12): operators reading the over-budget runbook confirm from
        here that a deployment actually serves sharded tables — and
        what one shard costs a device."""
        from predictionio_tpu.parallel.sharded_table import is_sharded
        out = []
        for m in list(self.models):
            als = getattr(m, "als", None) or m
            t = getattr(als, "item_factors", None)
            if is_sharded(t):
                out.append({"layout": "model", "shards": t.n_shards,
                            "rows": t.n_rows,
                            "perShardBytes": t.per_shard_nbytes,
                            "resident": t._dev is not None})
            else:
                out.append({"layout": "replicated"})
        return out

    def _quantile_samples(self):
        with self._lock:
            pct = self._ring_percentiles()
        if pct is None:
            return None
        return [({"quantile": q}, float(v))
                for q, v in zip(("0.5", "0.95", "0.99"), pct)]

    # -- model loading (createServerActorWithEngine, :206-265) -------------
    def load_engine_instance(self):
        instances = Storage.get_meta_data_engine_instances()
        cfg = self.config
        if cfg.engine_instance_id:
            instance = instances.get(cfg.engine_instance_id)
            if instance is None:
                raise ValueError(
                    f"Invalid engine instance id {cfg.engine_instance_id}")
        else:
            instance = instances.get_latest_completed(
                cfg.engine_id or "default", cfg.engine_version or "0",
                cfg.engine_variant)
            if instance is None:
                raise ValueError(
                    f"No valid engine instance found for engine "
                    f"{cfg.engine_id} {cfg.engine_version} "
                    f"{cfg.engine_variant}. Try running `pio train` first.")
        return instance

    def load(self):
        """Restore models and build the serving pipeline (the deploy path)."""
        with self._lock:
            instance = self.load_engine_instance()
            if self.engine is None:
                factory = get_engine_factory(instance.engine_factory)
                self.engine = factory.apply()
            if self.engine_params is None:
                variant = {
                    "datasource": json.loads(
                        instance.data_source_params or "{}"),
                    "preparator": json.loads(
                        instance.preparator_params or "{}"),
                    "algorithms": json.loads(
                        instance.algorithms_params or "[]"),
                    "serving": json.loads(instance.serving_params or "{}"),
                }
                self.engine_params = self.engine.json_to_engine_params(
                    variant)
            model = Storage.get_model_data_models().get(instance.id)
            if model is None:
                raise ValueError(
                    f"No model found for engine instance {instance.id}")
            persisted = self.engine.deserialize_models(model.models)
            result = self.engine.prepare_deploy(
                self.engine_params, persisted, instance.id)
            was_loaded = bool(self.algorithms)
            self.engine_instance = instance
            self.algorithms = result.algorithms
            self.models = result.models
            self.serving = self.engine.make_serving(self.engine_params)
            self.model_version = instance.id
            # an operator-initiated (re)load is a trusted deploy: it is
            # the rollback anchor, and it supersedes any undecided
            # canary (whose candidate referenced the old pipeline)
            self.last_good_version = instance.id
            self.canary.abandon("full (re)load of instance "
                                + instance.id)
            self._last_swap_wall = time.time()
            self.publish_degraded = False
            if was_loaded:
                self.swap_count += 1  # /reload hot-swap, not first load
            logger.info("Engine instance %s loaded (%d algorithm(s))",
                        instance.id, len(self.algorithms))
        # a full (re)load rebuilds vocabularies/models wholesale — no
        # touched-row lineage, so every cached ranking is suspect
        if self.result_cache is not None:
            self.result_cache.invalidate_all("reload")
        # compile plane (ISSUE 9): AOT-compile the serving executables
        # at deploy time — outside the serving lock (an in-flight query
        # during /reload keeps answering from the jit path meanwhile).
        # The FIRST load is the deploy: a bucket that does not compile
        # there fails the deploy; a /reload stays fail-soft.
        self._warm_aot(self.models, instance.id, strict=not was_loaded)
        self._arm_swap_marker(instance.id, models_token=self.models)
        FLIGHT.record("hot_swap" if was_loaded else "model_load",
                      model_version=instance.id, source="load")
        return self

    def _tenant_cm(self):
        """Attribution scope for device uploads on this server's paths
        (ISSUE 15): a nullcontext for single-tenant deployments."""
        if self.tenant is None:
            import contextlib
            return contextlib.nullcontext()
        from predictionio_tpu.utils import device_cache
        return device_cache.tenant_scope(self.tenant)

    # -- compile plane (ISSUE 9) --------------------------------------------
    def _warm_aot(self, models, version: Optional[str],
                  strict: bool = False):
        """AOT-compile the serving executables for ``models`` BEFORE
        they take a request (the caller — scheduler publish thread,
        canary stage, deploy load — pays the compile, never a query).
        Hot-swaps are fail-soft: a warm failure leaves the jit fallback
        path serving correctly. ``strict`` (the deploy-time load) turns
        any failed bucket into an error — a server whose executables do
        not compile on this device must not come up looking healthy."""
        try:
            from predictionio_tpu.compile.aot import warm_models
            with self._tenant_cm():
                summary = warm_models(
                    self.algorithms, models,
                    batch_hint=max(self.config.micro_batch, 1))
            self.last_aot_warm = dict(summary, version=version)
            if summary.get("compiled"):
                FLIGHT.record("aot_warm", model_version=version,
                              **{k: summary[k] for k in
                                 ("compiled", "skipped", "wallS")
                                 if k in summary})
        except Exception:
            if strict:
                raise
            logger.warning("AOT warm failed; serving falls back to "
                           "jit dispatch", exc_info=True)
            return
        if strict and summary.get("failed"):
            raise RuntimeError(
                f"deploy-time AOT warm: {summary['failed']} serving "
                f"executable bucket(s) failed to compile on "
                f"{self.device['platform']} "
                f"({self.device['device_kind']}); see the warnings "
                f"above for the failing specs")

    def _arm_swap_marker(self, version: Optional[str],
                         candidate_only: bool = False,
                         models_token=None):
        """``models_token`` is the exact model-list object installed by
        the change: only a query that SERVED it may close the marker (a
        query already in flight against the old models at swap time
        would otherwise bank a fake ~0 ms first-query wall). Canary
        stages pass no token — the CANDIDATE arm check is the gate."""
        with self._lock:
            self._swap_marker = (version, time.perf_counter(),
                                 candidate_only, models_token)

    def _close_swap_marker(self, arm: str, models_used=None):
        """First matching query after a model change: bank the
        swap-to-first-query wall. Candidate-only markers (canary stage)
        wait for the first CANDIDATE-served query — the one that would
        pay any un-warmed compile."""
        marker = self._swap_marker
        if marker is None:
            return
        version, t0, candidate_only, token = marker
        from predictionio_tpu.guard.canary import CANDIDATE
        if candidate_only and arm != CANDIDATE:
            return
        if token is not None and models_used is not token:
            return  # an in-flight query against the pre-swap models
        with self._lock:
            if self._swap_marker is not marker:
                return
            self._swap_marker = None
            ms = (time.perf_counter() - t0) * 1000.0
            self.last_swap_to_first_query_ms = ms
        FLIGHT.record("first_query_after_swap", model_version=version,
                      swapToFirstQueryMs=round(ms, 3),
                      canary=candidate_only)

    def swap_models(self, models, version: Optional[str] = None,
                    fold_in_events: int = 0,
                    touched_entities: Optional[dict] = None):
        """Atomic in-process hot-swap (the fold-in publish path): replace
        the whole model list under the serving lock so no query ever sees
        a mixed-version set. The query paths snapshot (algorithms, models,
        serving) under the same lock, and fold-in produces NEW model
        objects rather than mutating deployed ones — both halves of the
        no-torn-read guarantee.

        ``touched_entities`` ({"user": ids, "item": ids}, ISSUE 14): the
        exact rows this publish re-solved — the result cache drops ONLY
        their entries, so untouched hot users keep their cached rankings
        across the swap. None (an unattributed model change) clears the
        whole cache.

        Compile plane (ISSUE 9): the incoming models' serving
        executables are AOT-warmed HERE, on the publishing thread,
        before the swap/stage — so the first query against the new
        version (including a guarded rollback's return to the
        incumbent, whose executables are already resident) runs zero
        XLA compiles."""
        models = list(models)
        if len(models) != len(self.algorithms):
            raise ValueError(
                f"swap_models got {len(models)} models for "
                f"{len(self.algorithms)} algorithms")
        self._warm_aot(models, version)
        # guarded deploys (ISSUE 5): with canarying on, the new version
        # becomes a CANDIDATE serving canary_fraction of traffic; the
        # watchdog promotes or rolls back — the incumbent keeps
        # answering the rest and stays fully live either way. Not under
        # a multi-process mesh: per-request model choice on the primary
        # only would run mismatched SPMD programs across processes
        # (the same reason /reload is rejected there).
        single_process = (self.coordinator is None
                          or not self.coordinator.multi_process)
        if single_process and self.canary.stage(models, version,
                                                int(fold_in_events)):
            # the candidate is warm BEFORE its first routed request:
            # measure stage -> first candidate-served query
            self._arm_swap_marker(version, candidate_only=True)
            FLIGHT.record("canary_staged", model_version=version,
                          fraction=self.canary.config.fraction,
                          foldInEvents=int(fold_in_events))
            return
        with self._lock:
            self.models = models
            self.swap_count += 1
            self.fold_in_count += 1
            self.fold_in_events += int(fold_in_events)
            if version is not None:
                self.model_version = version
            # a landed swap ends any stale-model degradation window
            self._last_swap_wall = time.time()
            self.publish_degraded = False
        if self.result_cache is not None:
            from predictionio_tpu.serving.result_cache import entity_tags
            if touched_entities is not None:
                # fold-tick lineage: drop exactly the touched entities'
                # entries; untouched cached rankings survive the swap
                self.result_cache.invalidate_entities(
                    entity_tags(touched_entities), reason="fold_swap")
            else:
                self.result_cache.invalidate_all("swap")
        self._arm_swap_marker(version, models_token=models)
        FLIGHT.record("hot_swap", model_version=version,
                      source="fold_publish",
                      foldInEvents=int(fold_in_events))
        logger.info("Hot-swapped models (swap #%d, version %s)",
                    self.swap_count, version or "<in-process>")

    # -- graceful degradation (ISSUE 3) -------------------------------------
    def note_publish_failure(self):
        """The scheduler reports a failed fold-in publish/hot-swap: keep
        serving the stale-but-valid model, but say so — queries gain the
        X-PIO-Model-Staleness-Ms header and /metrics flips
        pio_engine_model_stale until a swap lands."""
        with self._lock:
            self.publish_degraded = True
            self.publish_failures += 1

    def model_staleness_s(self) -> float:
        return max(time.time() - self._last_swap_wall, 0.0)

    # -- canary plumbing (ISSUE 5) ------------------------------------------
    def _canary_route(self):
        """(models_override, version, arm) for this request; the plain
        (None, None, incumbent) when canarying is off or idle — the
        default query path pays one config read."""
        from predictionio_tpu.guard.canary import CANDIDATE, INCUMBENT
        if not self.canary.enabled:
            return None, None, INCUMBENT
        routed = self.canary.route()
        if routed is None:
            return None, None, INCUMBENT
        models, version = routed
        return models, version, CANDIDATE

    def _canary_observe(self, arm, pred_dicts=None, error: bool = False,
                        latency_s: Optional[float] = None, n: int = 1):
        """Record per-arm outcomes and run the watchdog decision."""
        if not self.canary.enabled:
            return
        from predictionio_tpu.guard.canary import count_nonfinite
        nonfinite = 0
        if pred_dicts:
            nonfinite = sum(count_nonfinite(d) for d in pred_dicts)
        self.canary.record(arm, error=error, nonfinite=nonfinite,
                           latency_s=latency_s, n=n)
        self._apply_canary_decision()

    def _apply_canary_decision(self):
        decision = self.canary.take_decision()
        if decision is None:
            return
        if decision["decision"] == "promote":
            with self._lock:
                self.models = decision["models"]
                self.swap_count += 1
                self.fold_in_count += 1
                self.fold_in_events += decision["foldInEvents"]
                if decision["candidateVersion"]:
                    self.model_version = decision["candidateVersion"]
                self.last_good_version = self.model_version
                self._last_swap_wall = time.time()
                self.publish_degraded = False
            if self.result_cache is not None:
                # the staged candidate's touched-row lineage is gone by
                # promote time; a full clear is the safe contract (a
                # ROLLBACK keeps the incumbent — entries stay valid)
                self.result_cache.invalidate_all("canary_promote")
            # the promoted candidate's executables are already resident
            # (warmed at stage): promote -> first query is compile-free
            self._arm_swap_marker(decision["candidateVersion"],
                                  models_token=decision["models"])
            FLIGHT.record("hot_swap",
                          model_version=decision["candidateVersion"],
                          source="canary_promote")
            logger.info("Hot-swapped models after clean canary "
                        "(swap #%d, version %s)", self.swap_count,
                        decision["candidateVersion"] or "<in-process>")
        hook = self.on_canary_decision
        if hook is not None:
            try:
                hook({k: v for k, v in decision.items()
                      if k != "models"})
            except Exception:
                logger.exception("on_canary_decision hook failed")
        elif decision["candidateVersion"] \
                and getattr(self.engine_instance, "engine_id", None):
            # standalone deploy (no attached scheduler to delegate to):
            # make the verdict durable directly — pin a promotion as
            # last-known-good, demote a rolled-back version so the next
            # /reload or restart cannot resolve it
            try:
                from predictionio_tpu.online.registry import \
                    ModelVersionRegistry
                inst = self.engine_instance
                if decision["decision"] == "promote":
                    ModelVersionRegistry().pin_last_good(
                        inst.engine_id, inst.engine_version,
                        inst.engine_variant,
                        decision["candidateVersion"])
                else:
                    ModelVersionRegistry().demote_version(
                        decision["candidateVersion"])
            except Exception:
                logger.exception("durable canary verdict failed")

    # -- query path (ServerActor.myRoute /queries.json, :490-641) ----------
    def handle_query(self, query_dict: dict) -> dict:
        t0 = time.perf_counter()
        with self._lock:
            algorithms = self.algorithms
            models = self.models
            serving = self.serving
        canary_models, canary_version, arm = self._canary_route()
        if canary_models is not None:
            models = canary_models
        if not algorithms:
            raise RuntimeError("no engine loaded")
        # decode via the first algorithm's query class (JsonExtractor :499)
        qc = algorithms[0].query_class
        query = qc.from_dict(query_dict) if qc is not None else query_dict
        try:
            with self._tenant_cm(), self._spmd_guard(query_dict):
                with TRACER.span("supplement"):
                    supplemented = serving.supplement(query)
                tp = time.perf_counter()
                with TRACER.span("predict", algorithms=len(algorithms)):
                    predictions = [algo.predict(model, supplemented)
                                   for algo, model in zip(algorithms,
                                                          models)]
                predict_dt = time.perf_counter() - tp
            with TRACER.span("post_process"):
                prediction = serving.serve(query, predictions)
                pred_dict = (prediction.to_dict()
                             if hasattr(prediction, "to_dict")
                             else prediction)
                if not isinstance(pred_dict, dict):
                    pred_dict = {"result": pred_dict}
        except Exception:
            self._canary_observe(arm, error=True,
                                 latency_s=time.perf_counter() - t0)
            raise
        if self.config.feedback:
            pr_id = query_dict.get("prId") or self.engine_instance.id
            pred_dict = dict(pred_dict, prId=pr_id)
            self._send_feedback(query_dict, pred_dict, pr_id)
        pred_dict = self.plugin_context.apply_output(
            self.engine_instance, query_dict, pred_dict)
        dt = time.perf_counter() - t0
        with self._lock:
            self.request_count += 1
            self.serving_seconds += dt
            self.last_serving_sec = dt
            self.predict_seconds += predict_dt
            self._lat_ring.append(dt)
        self._h_query.observe(dt)
        self._close_swap_marker(arm, models_used=models)
        self._canary_observe(arm, pred_dicts=(pred_dict,), latency_s=dt)
        if canary_models is not None:
            # response tagging: the HTTP layer turns this into the
            # X-PIO-Canary header so clients/tests can tell which arm
            # answered
            pred_dict = dict(pred_dict,
                             _pioCanary=canary_version or "candidate")
        return pred_dict

    def _spmd_guard(self, payload):
        """Broadcast `payload` to mesh workers and hold the SPMD slot for
        this query's device work; a no-op for single-process serving and
        on the worker side (whose ordering is its sequential loop)."""
        if self.coordinator is None:
            import contextlib
            return contextlib.nullcontext()
        return self.coordinator.serialized(payload)

    def serve_mesh_worker(self):
        """Run this process as a mesh serve worker: mirror the primary's
        predict pipeline for every broadcast query — the executor side of
        the reference's distributed-model serve (CreateServer.scala:
        490-641; PAlgorithm.predictBase on cluster-resident models)."""
        if self.coordinator is None or self.coordinator.is_primary:
            raise RuntimeError(
                "serve_mesh_worker requires a multi-process mesh and "
                "process_index > 0")
        # workers mirror only the device work: per-query side effects
        # (feedback events, output plugins) belong to the primary alone,
        # else every query's feedback would be posted N times
        if self.config.feedback:
            import dataclasses
            self.config = dataclasses.replace(self.config, feedback=False)
        self.plugin_context = EngineServerPluginContext()

        def handler(obj):
            if isinstance(obj, list):
                self.handle_query_batch(obj)
            else:
                self.handle_query(obj)

        logger.info("mesh serve worker ready (process %d)",
                    __import__("jax").process_index())
        self.coordinator.worker_loop(handler)

    def handle_query_batch(self, query_dicts: List[dict]) -> List[dict]:
        """Batched query path: one Algorithm.batch_predict device call for
        all queries in the window (serving/batcher.py). Canary routing is
        per WINDOW — a coalesced batch runs against ONE model set, so the
        traffic fraction is realized across windows."""
        return self.handle_query_batch_begin(query_dicts)()

    def handle_query_batch_begin(self, query_dicts: List[dict]):
        """Pipelined batch path, stage 1 (ISSUE 14): snapshot the model
        set, decode + supplement, and ENQUEUE the device call (JAX async
        dispatch — the call returns the moment the work is queued on the
        device stream). Returns ``finish() -> List[dict]`` — stage 2:
        the deferred device->host readback, post-process and per-query
        result dicts, safe to run on the batcher's completion thread
        while the next window forms and dispatches.

        Version-mixing safety with K windows in flight: everything a
        window touches — algorithms, models, serving — is snapshotted
        here, once, under the serving lock; ``finish`` closes over the
        snapshot, so a hot-swap/rollback landing mid-flight never mixes
        versions inside a window (fold-in publishes new model OBJECTS,
        the deployed ones are immutable)."""
        import sys
        t0 = time.perf_counter()
        with self._lock:
            algorithms = self.algorithms
            models = self.models
            serving = self.serving
        canary_models, canary_version, arm = self._canary_route()
        if canary_models is not None:
            models = canary_models
        if not algorithms:
            raise RuntimeError("no engine loaded")
        qc = algorithms[0].query_class
        queries = [qc.from_dict(d) if qc is not None else d
                   for d in query_dicts]
        # the SPMD guard is entered here and exited after the readback:
        # with pipelining off (mesh / direct calls) finish() runs
        # immediately, preserving the old guard extent; the pipelined
        # single-process path gets a nullcontext anyway
        guard_holder = [self._spmd_guard(query_dicts)]
        guard_holder[0].__enter__()

        def _exit_guard(exc_info=(None, None, None)):
            g = guard_holder and guard_holder.pop()
            if g:
                g.__exit__(*exc_info)

        try:
            with self._tenant_cm():
                with TRACER.span("supplement"):
                    indexed = [(i, serving.supplement(q))
                               for i, q in enumerate(queries)]
                tp = time.perf_counter()
                with TRACER.span("predict", batch=len(queries),
                                 algorithms=len(algorithms)):
                    fetchers = []
                    for algo, model in zip(algorithms, models):
                        begin = getattr(algo, "batch_predict_begin",
                                        None)
                        if begin is not None:
                            fetchers.append(begin(model, indexed))
                        else:
                            # no async split for this algorithm: run
                            # the full (sync) batch predict in this
                            # stage — correct, just without overlap
                            res = algo.batch_predict(model, indexed)
                            fetchers.append(lambda res=res: res)
                dispatch_dt = time.perf_counter() - tp
        except BaseException as e:
            _exit_guard(sys.exc_info())
            if isinstance(e, Exception):
                self._canary_observe(arm, error=True,
                                     latency_s=time.perf_counter() - t0,
                                     n=len(queries))
            raise

        def finish() -> List[dict]:
            try:
                from predictionio_tpu.ops import readback as _rb
                tr = time.perf_counter()
                rb_w0, rb_b0 = _rb.thread_wait_s(), _rb.thread_d2h_bytes()
                with TRACER.span("readback") as rb_span:
                    # the window's d2h copy went in flight at dispatch
                    # (ops/readback, ISSUE 19) — this is the wait on
                    # that copy + host unpack, the pipeline's ONE
                    # inherent sync (results must reach the host to
                    # serialize); costmon's 1-in-N sampled sync inside
                    # the dispatch stays the only other deliberate one
                    per_algo = [dict(f()) for f in fetchers]
                    if rb_span is not None:
                        rb_span.attrs["d2hWaitMs"] = round(
                            (_rb.thread_wait_s() - rb_w0) * 1000.0, 3)
                        rb_span.attrs["d2hBytes"] = (
                            _rb.thread_d2h_bytes() - rb_b0)
                readback_dt = time.perf_counter() - tr
            except BaseException as e:
                _exit_guard(sys.exc_info())
                if isinstance(e, Exception):
                    self._canary_observe(
                        arm, error=True,
                        latency_s=time.perf_counter() - t0,
                        n=len(queries))
                raise
            _exit_guard()
            try:
                out = []
                with TRACER.span("post_process"):
                    for i, (q, d) in enumerate(zip(queries,
                                                   query_dicts)):
                        prediction = serving.serve(
                            q, [pa[i] for pa in per_algo])
                        pred_dict = (prediction.to_dict()
                                     if hasattr(prediction, "to_dict")
                                     else prediction)
                        if not isinstance(pred_dict, dict):
                            pred_dict = {"result": pred_dict}
                        if self.config.feedback:
                            pr_id = (d.get("prId")
                                     or self.engine_instance.id)
                            pred_dict = dict(pred_dict, prId=pr_id)
                            self._send_feedback(d, pred_dict, pr_id)
                        out.append(self.plugin_context.apply_output(
                            self.engine_instance, d, pred_dict))
            except Exception:
                self._canary_observe(arm, error=True,
                                     latency_s=time.perf_counter() - t0,
                                     n=len(queries))
                raise
            dt = time.perf_counter() - t0
            with self._lock:
                self.request_count += len(queries)
                self.serving_seconds += dt
                self.last_serving_sec = dt / max(len(queries), 1)
                self.predict_seconds += dispatch_dt + readback_dt
                # every query in the window experienced the window's
                # wall time inside the server: one ring sample each
                self._lat_ring.extend([dt] * len(queries))
            for _ in queries:
                self._h_query.observe(dt)
            self._close_swap_marker(arm, models_used=models)
            self._canary_observe(arm, pred_dicts=out, latency_s=dt,
                                 n=len(queries))
            if canary_models is not None:
                return [dict(d, _pioCanary=canary_version or "candidate")
                        for d in out]
            return out
        return finish

    # -- feedback loop (:526-596) ------------------------------------------
    def _send_feedback(self, query: dict, prediction: dict, pr_id: str):
        event = {
            "event": "predict", "entityType": "pio_pr", "entityId": pr_id,
            "properties": {"query": query, "prediction": prediction},
            "eventTime": format_event_time(utcnow()),
        }
        url = (f"http://{self.config.event_server_ip}:"
               f"{self.config.event_server_port}/events.json"
               f"?accessKey={self.config.accesskey}")
        # capture the query's trace context NOW (ISSUE 13): the POST
        # runs on a fresh thread whose contextvars are empty, and the
        # event server adopting this id is what ties the feedback
        # event's ingest to the query that produced it across processes
        headers = {"Content-Type": "application/json",
                   **trace_context_headers()}

        def _post():
            try:
                req = urllib.request.Request(
                    url, data=json.dumps(event).encode(),
                    headers=headers, method="POST")
                urllib.request.urlopen(req, timeout=5).read()
            except Exception as e:
                logger.error("feedback event POST failed: %s", e)

        threading.Thread(target=_post, daemon=True).start()

    # -- routes -------------------------------------------------------------
    def _ring_percentiles(self):
        """(p50, p95, p99) of recent serving seconds, or None when no
        traffic yet. Callers must hold self._lock."""
        if not self._lat_ring:
            return None
        return np.percentile(list(self._lat_ring), (50, 95, 99))

    def _status_page(self, req: Request) -> Response:
        with self._lock:
            avg = (self.serving_seconds / self.request_count
                   if self.request_count else 0.0)
            inst = self.engine_instance
            pct = self._ring_percentiles()
            tail = ""
            if pct is not None:
                p50, p95, p99 = pct
                tail = (f"<tr><td>p50 / p95 / p99 serving time</td>"
                        f"<td>{p50:.6f} / {p95:.6f} / {p99:.6f} s"
                        f"</td></tr>")
            if self.coordinator is not None:
                h = self.coordinator.health()
                state = ("POISONED — redeploy the mesh" if h["poisoned"]
                         else "healthy")
                tail += (f"<tr><td>Mesh coordinator "
                         f"({h['processes']} processes)</td>"
                         f"<td>{state}</td></tr>")
        html = f"""<html><head><title>Engine Server at
{self.config.ip}:{self.config.port}</title></head><body>
<h1>Engine Server</h1>
<table border=1>
<tr><td>Started</td><td>{self.start_time.isoformat()}</td></tr>
<tr><td>Engine instance</td><td>{inst.id if inst else '-'}</td></tr>
<tr><td>Engine factory</td><td>{inst.engine_factory if inst else '-'}</td></tr>
<tr><td>Request count</td><td>{self.request_count}</td></tr>
<tr><td>Average serving time</td><td>{avg:.6f} s</td></tr>
<tr><td>Last serving time</td><td>{self.last_serving_sec:.6f} s</td></tr>
{tail}</table></body></html>"""
        return Response(200, html, content_type="text/html; charset=UTF-8")

    @staticmethod
    def _request_deadline_s(req: Request) -> Optional[float]:
        """Deadline budget propagated from HTTP ingress (ISSUE 3):
        ``X-PIO-Deadline-Ms`` header or ``deadlineMs`` query param —
        how long the CLIENT will still care about the answer. Fed to
        the batcher's admission control so saturated queues shed
        out-of-deadline work with 503 + Retry-After."""
        raw = (req.headers.get("X-PIO-Deadline-Ms")
               or req.params.get("deadlineMs"))
        if not raw:
            return None
        try:
            ms = float(raw)
        except ValueError:
            raise ValueError(f"bad deadline {raw!r}: want milliseconds")
        if ms <= 0:
            raise ValueError("deadline must be positive milliseconds")
        return ms / 1000.0

    def _degraded_headers(self) -> Optional[dict]:
        """The stale-model advisory header while a fold-in publish
        failure leaves this server behind the event stream."""
        if not self.publish_degraded:
            return None
        return {"X-PIO-Model-Staleness-Ms":
                str(int(self.model_staleness_s() * 1000))}

    def _cache_usable(self) -> bool:
        """The result cache serves/stores only when a response is a
        pure function of (query, deployed models): no canary split in
        progress (two model sets answer concurrently), no feedback
        loop (each query must land its predict event), no output
        plugins (sniffers must see every prediction), and no algorithm
        with live filters (``LIVE_FILTERS``: the e-commerce engine reads
        the user's seen items and the unavailable list at predict time,
        so a stored answer would be older than the filters the next
        request must be held to; no invalidation reaches it, since the
        events that change it are not model changes)."""
        if self.result_cache is None:
            return False
        if any(getattr(a, "LIVE_FILTERS", False)
               for a in self.algorithms or ()):
            return False
        if self.canary.active:
            return False
        if self.config.feedback:
            return False
        p = self.plugin_context.plugins
        return not any(p.get(k) for k in p)

    @staticmethod
    def _result_item_ids(out) -> tuple:
        """Item ids a response ranks (strict-mode invalidation join) —
        ALL of them: a cap would silently exempt deep rankings from
        the PIO_SERVE_CACHE_STRICT drop-if-contains-touched-item
        contract (num is client-bounded, so this stays small)."""
        try:
            return tuple(str(s["item"])
                         for s in out.get("itemScores", ()))
        except Exception:
            return ()

    def _serve_cache_hit(self, body: bytes, t_q0: float) -> Response:
        """Account + answer one result-cache hit (no trace is minted:
        an empty span tree is not worth a double-digit-percent tax on
        the measured hit path; hits stay fully counted in the request
        metrics and latency histogram)."""
        dt = time.perf_counter() - t_q0
        with self._lock:
            self.request_count += 1
            self.serving_seconds += dt
            self.last_serving_sec = dt
            self._lat_ring.append(dt)
        self._h_query.observe(dt)
        return Response(200, body, headers=self._degraded_headers())

    def _queries(self, req: Request) -> Response:
        t_q0 = time.perf_counter()
        # result cache (ISSUE 14): a hit returns the stored serialized
        # bytes — no queue, no batch, no device, no re-serialization
        # (byte-identical across hot-swaps that did not touch this
        # query's entities). The exact-bytes alias answers a repeat
        # client BEFORE the JSON body is even parsed.
        from predictionio_tpu.serving import result_cache as RC
        key = generation = None
        # the serving account's request record: a cache hit or a
        # refusal keeps dispatch -1, the batcher's submit fills it in
        TRACER.note_request(tenant=self.tenant)
        cacheable = self._cache_usable()
        if cacheable:
            with TRACER.region("query.cache_lookup"):
                body = self.result_cache.get_raw(req.body)
            if body is not None:
                return self._serve_cache_hit(body, t_q0)
        d = req.json()
        if not isinstance(d, dict):
            raise ValueError("query must be a JSON object")
        if cacheable:
            key = RC.query_key(d)
            with TRACER.region("query.cache_lookup"):
                body = self.result_cache.get(key)
            if body is not None:
                return self._serve_cache_hit(body, t_q0)
            # store-time freshness fence: any invalidation landing
            # while this query computes refuses the store (the result
            # may reflect the pre-swap models)
            generation = self.result_cache.generation
        deadline_s = self._request_deadline_s(req)
        # ingress trace: minted per query — or ADOPTED from an inbound
        # X-PIO-Trace-Id (ISSUE 13), so a traced upstream caller's id
        # spans this process's serve waterfall too. In batched mode the
        # device work happens under the batcher thread's own
        # batch_predict trace; submit() records the two-way link so
        # /traces.json ties a query to the coalesced window that
        # answered it.
        with TRACER.trace("query",
                          **ingress_trace_kwargs(req.headers)) as qt:
            if self.batcher is not None:
                out = self.batcher.submit(d, deadline_s=deadline_s)
            else:
                out = self.handle_query(d)
            total_s = time.perf_counter() - t_q0
            headers = self._degraded_headers()
            if isinstance(out, dict) and "_pioCanary" in out:
                # the canary tag rides the result dict out of the (
                # possibly batched) predict path; surface it as the
                # X-PIO-Canary response header instead of body noise
                out = dict(out)
                version = out.pop("_pioCanary")
                headers = dict(headers or {})
                headers["X-PIO-Canary"] = str(version)
                cacheable = False   # a canary arm answered after all
            body = None
            if cacheable and key is not None and isinstance(out, dict):
                # serialize ONCE: the same bytes answer this request
                # and every future hit (the serialize stage is paid
                # exactly once per distinct query per model version)
                try:
                    body = json.dumps(out).encode("utf-8")
                except (TypeError, ValueError):
                    body = None
                if body is not None:
                    self.result_cache.put(
                        key, body, RC.query_entities(d),
                        result_items=self._result_item_ids(out),
                        generation=generation, raw=req.body)
            if total_s >= slow_threshold_s():
                # slow-query forensics (ISSUE 11): this request already
                # blew the SLO latency bound — capture its stage
                # waterfall (all capture work is off the fast path by
                # construction)
                self._capture_slow(qt, d, out, total_s)
            return Response(200, body if body is not None else out,
                            headers=headers)

    def _capture_slow(self, qt, query_dict: dict, out, total_s: float):
        """Build + record the slow request's waterfall; never raises
        into the response path."""
        try:
            with TRACER.region("slow.capture"):
                # the serialize stage IS a second json.dumps of the
                # response: tens of µs on a request that already took
                # >=250 ms (<0.05%), paid only on the slow path — and
                # when the payload is big enough for this to matter, a
                # serialize-dominated tail is exactly the diagnosis the
                # stage exists to surface
                t0 = time.perf_counter()
                try:
                    json.dumps(out, default=str)
                except Exception:
                    pass
                serialize_s = time.perf_counter() - t0
                # the batcher's submit() linked the coalesced window's
                # batch_predict trace onto this query trace, and noted
                # the dispatch's sequence number for this thread
                batch_tid = next(iter(qt.links), None)
                capture_slow_query(
                    qt, total_s, query=query_dict,
                    model_version=self.model_version,
                    serialize_s=serialize_s, batch_trace_id=batch_tid,
                    tenant=self.tenant,
                    dispatch=TRACER.dispatch_record(
                        TRACER.noted_dispatch_seq()))
        except Exception:
            logger.debug("slow-query capture failed", exc_info=True)

    def _slow(self, req: Request) -> Response:
        """GET /slow.json — recent slow-query stage waterfalls
        (?n=; obs/slowlog.py). Each entry's traceId resolves via
        /traces.json?trace_id= to the full span tree."""
        return Response(200, slow_response(req.params))

    def _reload(self, req: Request) -> Response:
        """Hot-swap to the latest COMPLETED instance (:337-358). When
        the POST carries an inbound trace id (a cross-process
        scheduler's publish hop, ISSUE 13) the reload runs under it, so
        this process's hot_swap flight record and load spans join the
        fold tick's fleet-stitched story."""
        kw = ingress_trace_kwargs(req.headers)
        if kw:
            with TRACER.trace("reload", **kw):
                return self._reload_inner(req)
        return self._reload_inner(req)

    def _reload_inner(self, req: Request) -> Response:
        if self.coordinator is not None and self.coordinator.multi_process:
            # reload is per-process: swapping models on the primary only
            # would serve mismatched shards (wrong scores or a collective
            # shape hang). Redeploy the whole mesh instead.
            return Response(400, {
                "message": "reload is not supported under a multi-process "
                           "mesh; redeploy all processes"})
        cfg = self.config
        if cfg.engine_instance_id is None and self.engine_instance:
            cfg.engine_id = self.engine_instance.engine_id
            cfg.engine_version = self.engine_instance.engine_version
            cfg.engine_variant = self.engine_instance.engine_variant
        self.engine_params = None  # re-derive from the new instance
        self.load()
        return Response(200, {"message": "Reloaded"})

    def _stop(self, req: Request) -> Response:
        threading.Thread(target=self.stop, daemon=True).start()
        return Response(200, {"message": "Shutting down."})

    def _plugins(self, req: Request) -> Response:
        return Response(200, self.plugin_context.to_dict())

    def _stats(self, req: Request) -> Response:
        """JSON serving counters with the predict/total latency split: how
        much of the serving time is the algorithm's device scoring vs
        serve/HTTP overhead."""
        if self.canary.enabled:
            # idle-traffic watchdog kick: a stats poll can land the
            # promote/rollback decision when no query has since
            self._apply_canary_decision()
        with self._lock:
            n = self.request_count
            trained = getattr(self.engine_instance, "env", None) or {}
            out = {
                # the device THIS process computes on, as JAX reported
                # it at start-up, and what trained the loaded model
                **device_stats(),
                "solver": trained.get("solver"),
                "computeDtype": trained.get("compute_dtype"),
                "requestCount": n,
                "avgServingSec": self.serving_seconds / n if n else 0.0,
                "lastServingSec": self.last_serving_sec,
                "avgPredictSec": self.predict_seconds / n if n else 0.0,
                "microBatch": self.config.micro_batch,
                "startTime": self.start_time.isoformat(),
                # online-update observability (ISSUE 1): how many times
                # the serving models were hot-swapped, how many fold-ins
                # landed, and which version answers queries right now
                "modelSwaps": self.swap_count,
                "foldIns": self.fold_in_count,
                "foldInEvents": self.fold_in_events,
                "modelVersion": self.model_version,
                # graceful-degradation state (ISSUE 3): is this server
                # knowingly serving a stale model, and how stale
                "publishDegraded": self.publish_degraded,
                "publishFailures": self.publish_failures,
                "modelStalenessSec": self.model_staleness_s(),
                # guarded deploys (ISSUE 5): canary arm state and the
                # in-memory rollback anchor
                "canary": self.canary.stats(),
                "lastGoodVersion": self.last_good_version,
                # compile plane (ISSUE 9): how fast the last model
                # change reached its first served query, and the last
                # deploy-time warm summary
                "swapToFirstQueryMs": self.last_swap_to_first_query_ms,
                "aotWarm": self.last_aot_warm,
                # sharded online plane (ISSUE 12): per-algorithm factor
                # table layout (+ per-shard HBM cost when sharded)
                "modelSharding": self._model_sharding(),
            }
            if self.tenant is not None:
                out["tenant"] = self.tenant
            pct = self._ring_percentiles()
            if pct is not None:
                out.update({"p50ServingSec": float(pct[0]),
                            "p95ServingSec": float(pct[1]),
                            "p99ServingSec": float(pct[2])})
            # registry-derived distributions (ISSUE 2): bucketed
            # percentiles for the query path, and batch-wait when the
            # micro-batcher is on — same instruments /metrics exposes
            out["queryLatency"] = self._h_query.snapshot()
            if self.batcher is not None and self.batcher.wait_hist \
                    is not None:
                out["batchWait"] = self.batcher.wait_hist.snapshot()
            if self.batcher is not None:
                # realized coalescing (avg/max batch size) — the datum
                # for tuning micro_batch_wait_ms on a given link
                out.update(self.batcher.stats())
            if self.result_cache is not None:
                # result cache (ISSUE 14): hit rate + residency next
                # to the coalescing numbers they offload
                out["resultCache"] = self.result_cache.stats()
            if self.coordinator is not None:
                out["meshCoordinator"] = self.coordinator.health()
        # AOT registry + persistent-cache state (ISSUE 9 satellite):
        # executables resident, buckets compiled, dispatch hit/miss and
        # persistent-cache counters since start — outside the serving
        # lock (snapshot takes the registry's own lock; cache status
        # does a small dir listing)
        try:
            from predictionio_tpu.compile.aot import get_aot
            from predictionio_tpu.compile.cache import cache_status
            from predictionio_tpu.utils import device_cache
            out["aot"] = get_aot().snapshot()
            # live rows, bucket rows and the padded share of each
            # resident factor table: what every scan reads for nothing
            out["tableRows"] = device_cache.table_rows()
            out["xlaCache"] = cache_status()
        except Exception:
            logger.debug("aot stats unavailable", exc_info=True)
        # runtime attribution (ISSUE 11): estimated device seconds per
        # executable + occupancy — where the accelerator's time goes
        try:
            from predictionio_tpu.obs import costmon
            out["deviceTime"] = costmon.device_snapshot()
        except Exception:
            logger.debug("device time stats unavailable",
                         exc_info=True)
        return Response(200, out)

    def _profile(self, req: Request) -> Response:
        """``/profile.json`` — profiling surface (obs/profiler.py,
        ISSUE 11): POST ``{"action": "start"|"stop"}`` toggles the
        jax.profiler device trace with the ISSUE 2 idempotent
        semantics (state machine now lives in obs/profiler so the
        event server shares it); ``action=report`` (GET or POST)
        returns the always-on sampling profiler's folded-stack
        report."""
        from predictionio_tpu.obs import profiler
        status, body = profiler.profile_response_from_request(req)
        return Response(status, body)

    def _metrics(self, req: Request) -> Response:
        """Prometheus text exposition, rendered solely by the shared
        metrics registry (ISSUE 2): this server's families (counters,
        quantile summary, query/batch-wait histograms, batcher and mesh
        collectors) plus the process-wide ones (JAX runtime, fold/train
        instruments) through the parent chain. ``?exemplars=1`` (or an
        OpenMetrics Accept header) switches to the exemplar-bearing
        OpenMetrics exposition (ISSUE 11) — the default body stays
        classic-parser safe."""
        from predictionio_tpu.utils.prometheus import (
            CONTENT_TYPE, OPENMETRICS_CONTENT_TYPE, wants_exemplars)
        om = wants_exemplars(req)
        return Response(
            200, self.metrics.render(exemplars=om),
            content_type=OPENMETRICS_CONTENT_TYPE if om
            else CONTENT_TYPE)

    def _traces(self, req: Request) -> Response:
        """GET /traces.json — recent span trees from the process-wide
        tracer (?n=, ?kind=, ?trace_id=, ?sort=slowest)."""
        return Response(200, traces_response(req.params))

    def _flight(self, req: Request) -> Response:
        """GET /flight.json — recent lifecycle wide events from the
        process flight recorder (?n=, ?kind=, ?trace_id=)."""
        return Response(200, flight_response(req.params))

    def _health(self, req: Request) -> Response:
        """GET /health.json — SLO verdicts with fast/slow burn rates
        (ISSUE 6): serve p99, fold-tick duration, model staleness and
        the guarded-deploys event budget. A latency SLO transitioning
        into ``breached`` auto-captures an incident bundle (ISSUE 11):
        the slow_queries + profiler providers put the top waterfalls
        and the sampling profiler's stacks into it, so the p99
        postmortem starts with evidence, not with reproduction."""
        out = health_response(self.slo, extra={
            "modelVersion": self.model_version,
            "publishDegraded": self.publish_degraded})
        try:
            self._note_slo_breaches(out)
        except Exception:
            logger.debug("slo breach capture failed", exc_info=True)
        return Response(200, out)

    def _note_slo_breaches(self, health: dict):
        """Fire one incident capture per ok->breached transition of a
        latency SLO (the per-kind cooldown in IncidentManager bounds a
        flapping SLO). State is per-server, in-memory — a restart
        re-captures, which is the right bias for forensics."""
        for s in health.get("slo", ()):
            name, status = s.get("name"), s.get("status")
            if name is None:
                continue
            prev = self._slo_status.get(name)
            self._slo_status[name] = status
            if status == "breached" and prev != "breached" \
                    and s.get("kind") == "latency":
                # tenant scope (None = no-op): a slot's breach record
                # and bundle name the tenant, and the bundle's
                # forensics keep to that tenant's slice (ISSUE 17)
                from predictionio_tpu.obs.tenantctx import tenant_scope
                with tenant_scope(self.tenant):
                    FLIGHT.record("slo_breach", slo=name,
                                  burnFast=s.get("burnFast"),
                                  burnSlow=s.get("burnSlow"))
                    get_incidents().capture(
                        "slo_breach",
                        f"latency SLO {name} breached "
                        f"(burn fast/slow = {s.get('burnFast')}/"
                        f"{s.get('burnSlow')})",
                        context={"slo": s},
                        tenant=self.tenant)

    # -- fleet federation (ISSUE 13) ----------------------------------------
    def _fleet_status(self, req: Request) -> Response:
        """GET /fleet/status.json — member registry with liveness."""
        return Response(200, fleet.fleet_status_response(req.params))

    def _fleet_health(self, req: Request) -> Response:
        """GET /fleet/health.json — worst-of SLO rollup across live
        members."""
        return Response(200, fleet.fleet_health_response(req.params))

    def _fleet_metrics(self, req: Request) -> Response:
        """GET /fleet/metrics — every live member's scrape merged with
        {role,pid} labels (obs/fleet.py)."""
        from predictionio_tpu.utils.prometheus import CONTENT_TYPE
        return Response(200, fleet.fleet_metrics_response(req.params),
                        content_type=CONTENT_TYPE)

    def _fleet_traces(self, req: Request) -> Response:
        """GET /fleet/traces.json?trace_id= — one trace stitched
        fleet-wide into a cross-process waterfall."""
        return Response(200, fleet.fleet_traces_response(req.params))

    def _incidents_list(self, req: Request) -> Response:
        """GET /incidents.json — bundle index (`pio incidents list
        --url`)."""
        from predictionio_tpu.obs.incidents import incidents_response
        return Response(200, incidents_response(req.params))

    def _incident_show(self, req: Request) -> Response:
        from predictionio_tpu.obs.incidents import incident_response
        status, body = incident_response(req.path_args[0])
        return Response(status, body)

    def _build_router(self) -> Router:
        r = Router()
        r.add("GET", "/", self._status_page)
        r.add("POST", "/queries.json", self._queries)
        r.add("GET", "/reload", self._reload)
        r.add("POST", "/reload", self._reload)
        r.add("POST", "/stop", self._stop)
        r.add("GET", "/stop", self._stop)
        r.add("GET", "/plugins.json", self._plugins)
        r.add("GET", "/stats.json", self._stats)
        r.add("GET", "/metrics", self._metrics)
        r.add("GET", "/traces.json", self._traces)
        r.add("GET", "/flight.json", self._flight)
        r.add("GET", "/health.json", self._health)
        r.add("GET", "/fleet/status.json", self._fleet_status)
        r.add("GET", "/fleet/health.json", self._fleet_health)
        r.add("GET", "/fleet/metrics", self._fleet_metrics)
        r.add("GET", "/fleet/traces.json", self._fleet_traces)
        r.add("GET", "/incidents.json", self._incidents_list)
        r.add("GET", "/incidents/<id>.json", self._incident_show)
        r.add("GET", "/slow.json", self._slow)
        r.add("POST", "/profile.json", self._profile)
        r.add("GET", "/profile.json", self._profile)
        return r

    # -- lifecycle ----------------------------------------------------------
    def start(self, background: bool = True) -> "EngineServer":
        # always-on sampling profiler (ISSUE 11; PIO_PROFILER=off to
        # disable): server processes sample from first request on, so
        # a p99 postmortem never starts with "restart with profiling"
        from predictionio_tpu.obs import profiler
        profiler.ensure_started()
        TRACER.watch_gc(True)
        if self.stallwatch is not None:
            self.stallwatch.start()
        srv = HttpServer(self.router, self.config.ip, self.config.port)
        self.server = srv

        def _bound(s):
            # post-bind / pre-serve: publish the resolved port (fleet
            # member record, ISSUE 13) before a foreground
            # serve_forever blocks
            self.config.port = s.port
            fid = fleet.register_member(
                "engine_server", port=s.port, host=self.config.ip)
            with self._lock:
                self._fleet_id = fid
            logger.info("Engine server started on %s:%d",
                        self.config.ip, s.port)

        srv.on_bound = _bound
        srv.start(background=background)
        return self

    def stop(self):
        # order matters for a clean drain: stop ACCEPTING first (the
        # HTTP listener), then the batcher (which fails any still-queued
        # waiters so their request threads return 500 instead of
        # blocking forever), then release the mesh workers. self.server
        # is nulled LAST — deploy's foreground loop watches it, and
        # signaling "stopped" before the worker-release broadcast lets
        # the primary's interpreter exit mid-collective and strand the
        # workers (observed as a poisoned release bcast in the 2-proc
        # test)
        # /stop runs this on a spawned thread while start()'s on_bound
        # hook writes _fleet_id from the serving thread: swap it out
        # under the serving lock, deregister (file IO) outside it
        with self._lock:
            fleet_id = self._fleet_id
            self._fleet_id = None
        fleet.deregister_member(fleet_id)
        if self.server:
            self.server.stop()
            TRACER.watch_gc(False)
        if self.stallwatch is not None:
            self.stallwatch.stop()
        if self.batcher is not None:
            self.batcher.stop()
        if self.coordinator is not None:
            self.coordinator.shutdown()
        self.server = None
