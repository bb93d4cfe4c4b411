"""Delta-training scheduler: tail the event store, fold in, hot-swap.

The background loop that closes the event->model gap (ISSUE 1 tentpole
piece 2). Each tick:

  1. TAIL — read events newer than the cursor through the ``LEvents``
     store (``EventStore.find`` with an event-time ``start_time`` cursor;
     channel-scoped when the engine's data source names a channel) and
     fold them into per-entity delta state with the same monoid machinery
     the property aggregator uses (``EntityDelta.merge`` is duck-type
     compatible with ``data/aggregator.merge_aggregations``, so partition
     merges reuse that code path verbatim).
  2. TRIGGER — when the accumulated delta count or the oldest delta's
     staleness crosses its threshold (or ``tick(force=True)``), run a
     fold-in: re-read the training data through the engine's own data
     source, and ask each algorithm that supports online updates
     (``algo.fold_in``) for a model with only the touched rows re-solved.
  3. DRIFT GATE — folded rows are exact GIVEN the frozen counterpart
     rows, so repeated fold-ins drift from the retrain fixed point. The
     post-fold training loss is compared against the anchor loss (the
     loss right after the last full train / first fold); when the ratio
     exceeds ``drift_ratio`` the scheduler stops folding and escalates
     through ``on_retrain``.
  4. PUBLISH — swap the attached in-process server atomically
     (zero dropped queries; the server counts swaps and fold-ins for
     ``/stats.json`` and ``/metrics``) and/or publish a new model version
     through the registry + POST ``/reload`` to a remote deployment.

Cursor semantics: the cursor is the max event time seen, inclusive-start
on re-read with an id set de-duplicating the boundary instant — events
back-dated BEFORE an already-advanced cursor are not observed until the
next full retrain (the same visibility rule a batch ``pio train`` run at
the cursor instant would have had).

Cost model: the touched rows' solves need their COMPLETE histories, and
item columns can span the corpus — but nothing outside the touched
entities. When the data source supports entity-filtered reads
(``read_training_touched``, backed by the storage layer's
``find_columnar_by_entities`` pushdown) and the touched set is small
(``filtered_read_max_entities``), a tick reads O(touched histories)
instead of running the full columnar scan (~22 s at ML-20M for a tick
touching a handful of users); larger touched sets, or data sources
without the hook, fall back to the full scan. The choice — and the rows
it read — is recorded in the ``fold_tick`` trace, the fold report
(``readPath``/``readRows``) and ``pio_fold_read_rows_total{path=...}``.
"""

from __future__ import annotations

import contextlib
import datetime as _dt
import logging
import threading
import time as _time
import urllib.request
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Set

from predictionio_tpu.data.aggregator import merge_aggregations
from predictionio_tpu.data.event import Event, utcnow
from predictionio_tpu.data.store import LEventStore
from predictionio_tpu.guard.gates import (GateConfig, GateRejected,
                                          QualityGatekeeper)
from predictionio_tpu.obs import TRACER, get_registry, jaxmon

logger = logging.getLogger(__name__)


@dataclass(frozen=True)
class EntityDelta:
    """Mergeable per-entity delta state — the rating-event analog of the
    aggregator's ``EventOp`` (same monoid laws: commutative, associative,
    time-keyed), consumable by ``merge_aggregations``."""
    count: int = 0
    first_t: Optional[_dt.datetime] = None
    last_t: Optional[_dt.datetime] = None

    @staticmethod
    def from_event(e: Event) -> "EntityDelta":
        return EntityDelta(count=1, first_t=e.event_time,
                           last_t=e.event_time)

    def merge(self, other: "EntityDelta") -> "EntityDelta":
        def opt(a, b, f):
            if a is None:
                return b
            if b is None:
                return a
            return f(a, b)
        return EntityDelta(
            count=self.count + other.count,
            first_t=opt(self.first_t, other.first_t, min),
            last_t=opt(self.last_t, other.last_t, max))


@dataclass(frozen=True)
class SchedulerConfig:
    app_name: str
    channel_name: Optional[str] = None
    # None = the engine data source's event_names (plus $set, which marks
    # property-only freshness the next retrain picks up)
    event_names: Optional[Sequence[str]] = None
    max_deltas: int = 256          # fold in after this many fresh events
    max_staleness_s: float = 30.0  # ... or once the oldest delta is this old
    drift_ratio: float = 1.5       # post-fold loss / anchor loss escalation
    poll_interval_s: float = 2.0   # background loop cadence
    tail_batch_limit: int = 50_000  # max events consumed per tick
    # entity-filtered tail reads (the O(touched) cutover): when the data
    # source exposes read_training_touched and the touched entity count
    # is at most filtered_read_max_entities, the fold reads only the
    # touched histories; otherwise the full scan runs. The threshold is
    # the cost-model knob: past a few thousand entities the per-id
    # pushdown probes approach the cost of one sequential scan.
    filtered_reads: bool = True
    filtered_read_max_entities: int = 1024
    # supervision (ISSUE 3): consecutive tick failures back off
    # exponentially (poll_interval * 2^k, capped), and after
    # max_tick_failures the scheduler stops folding and escalates to a
    # full retrain through on_retrain — a wedged fold loop must not
    # retry on the same cadence forever while the model quietly ages
    max_tick_failures: int = 5
    failure_backoff_cap_s: float = 60.0
    # breaker over the event-store tail read: a down store makes ticks
    # skip the read (no thread pile-up on a dead backend) until the
    # half-open probe sees it recover
    tail_breaker_failures: int = 3
    tail_breaker_reset_s: float = 10.0
    # pre-swap quality gates (ISSUE 5, guard/gates.py): every fold's
    # candidate models must pass finiteness, norm/score-drift and
    # golden-query gates against the LIVE models before a publish is
    # attempted; a rejection restores the deltas and counts toward the
    # retrain escalation (the same data will fold the same way again)
    gates: bool = True
    gate_config: GateConfig = GateConfig()


class FoldTickGate:
    """Per-host fold-tick fairness (ISSUE 18 satellite).

    Several attached schedulers contend for ONE device: without a
    gate, tick admission is FIFO thread wakeup — a chatty tenant whose
    poll interval happens to phase-align with the device going idle
    can starve a quieter tenant's folds indefinitely. Every scheduler
    a :class:`~predictionio_tpu.tenancy.host.ServingHost` attaches
    shares the host's gate; ``turn(tenant)`` admits exactly one tick
    at a time, and among waiters the grant goes to the tenant whose
    LAST grant is oldest (never-granted first, then arrival order) —
    round-robin by staleness, so every tenant's fold lag is bounded by
    (tenants × tick time) rather than by luck.

    The queue is observable: ``pio_fold_tick_wait_seconds{tenant}``
    records how long each tenant's tick waited for its turn — the
    direct "is the device over-subscribed for folding" signal.
    """

    def __init__(self, registry=None):
        reg = registry or get_registry()
        self._h_wait = reg.histogram(
            "pio_fold_tick_wait_seconds",
            "Time a tenant's fold tick waited for its turn at the "
            "shared per-host tick gate",
            labelnames=("tenant",))
        self._cond = threading.Condition()
        self._busy: Optional[str] = None
        self._seq = 0
        self._waiters: List[tuple] = []
        self._last_grant: Dict[str, float] = {}
        # per-tenant histogram children resolved once (gate calls run
        # on scheduler control threads, but there is no reason to
        # re-resolve labels every tick either)
        self._children: Dict[str, Any] = {}

    def _child(self, tenant: str):
        c = self._children.get(tenant)
        if c is None:
            if len(self._children) >= 4096:
                self._children.clear()
            c = self._children[tenant] = self._h_wait.labels(
                tenant=tenant)
        return c

    def _pick(self) -> Optional[tuple]:
        """The waiter whose tenant has gone longest without a grant
        (never-granted first; arrival order breaks ties)."""
        if not self._waiters:
            return None
        return min(self._waiters, key=lambda w: (
            self._last_grant.get(w[0], float("-inf")), w[1]))

    @contextlib.contextmanager
    def turn(self, tenant: str):
        tenant = tenant or ""
        t0 = _time.monotonic()
        with self._cond:
            me = (tenant, self._seq)
            self._seq += 1
            self._waiters.append(me)
            while self._busy is not None or self._pick() != me:
                self._cond.wait(timeout=1.0)
            self._waiters.remove(me)
            self._busy = tenant
        self._child(tenant).observe(_time.monotonic() - t0)
        try:
            yield
        finally:
            with self._cond:
                self._busy = None
                if len(self._last_grant) >= 4096:
                    self._last_grant.clear()
                self._last_grant[tenant] = _time.monotonic()
                self._cond.notify_all()

    def stats(self) -> dict:
        with self._cond:
            return {"busy": self._busy,
                    "waiting": [w[0] for w in sorted(
                        self._waiters, key=lambda w: w[1])]}


class DeltaTrainingScheduler:
    """One scheduler follows one deployed engine.

    ``server``: an in-process ``EngineServer`` to hot-swap (tests,
    single-process deployments). ``registry`` + ``reload_url``: publish
    each folded version through the model-version registry and poke a
    REMOTE deployment's ``/reload`` (the `pio update --follow` path).
    Either, both, or neither (dry runs) may be given.
    """

    def __init__(self, engine, engine_params, instance,
                 algorithms: Sequence[Any], models: Sequence[Any],
                 config: SchedulerConfig,
                 server=None, registry=None, reload_url: Optional[str] = None,
                 on_retrain: Optional[Callable[[dict], None]] = None,
                 event_store=None, cursor: Optional[_dt.datetime] = None,
                 tenant: Optional[str] = None, tick_gate=None):
        # multi-tenant serving (ISSUE 15): when this scheduler follows
        # one tenant slot of a ServingHost, its fold ticks' device
        # uploads and residency slots run under the tenant's
        # device_cache attribution scope — so the HBM budget manager
        # can evict THIS tenant's fold-resident tables by name
        self.tenant = str(tenant) if tenant is not None else None
        # shared per-host fold-tick fairness gate (ISSUE 18): when
        # several schedulers contend for one device, background ticks
        # take turns through it instead of racing FIFO thread wakeup
        self._tick_gate: Optional[FoldTickGate] = tick_gate
        self.engine = engine
        self.engine_params = engine_params
        self.instance = instance
        self.algorithms = list(algorithms)
        self.models = list(models)
        self.config = config
        self.server = server
        self.registry = registry
        self.reload_url = reload_url
        self.on_retrain = on_retrain
        self.events = event_store or LEventStore
        # cursor: events at/after this instant are "fresh". Default: a
        # training instance's start (everything before it is inside the
        # model); an ONLINE version instead carries the tail cursor its
        # fold read up to in its lineage tag — the publish-time
        # start_time would skip events that landed between the fold's
        # data read and the publish.
        self._cursor: Optional[_dt.datetime] = (
            cursor if cursor is not None
            else self._instance_cursor(instance))
        self._seen_at_cursor: Set[str] = set()
        # attach-time boundary dedup (ISSUE 11 triage): event times are
        # stored at millisecond precision, so events that landed in the
        # SAME millisecond the cursor anchor was stamped in sit exactly
        # AT the cursor instant — and the tail's inclusive-start read
        # would re-count them as fresh on every (re)attach, although
        # they are already inside the model this scheduler resumes from
        # (training reads its corpus after start_time is stamped; a
        # lineage cursor is the max event time the fold consumed).
        # Seed the boundary-dedup set the running tail already
        # maintains with the ids currently at the cursor instant. A
        # failed pre-read degrades to the old behavior: those events
        # double-count once.
        # Trade, chosen deliberately: an event whose (client-supplied)
        # event_time lands in the anchor's exact millisecond AND that
        # was ingested in the gap between the corpus/fold read and
        # this attach gets marked seen without having been folded. The
        # alternative re-folds EVERY genuine boundary event on EVERY
        # attach (the bug this fixes). The skipped event stays in the
        # store — the next entity touch or any retrain (drift
        # escalation, `pio train`) reads it — whereas the old behavior
        # corrupted fold accounting on every restart unconditionally.
        if self._cursor is not None:
            try:
                self._seen_at_cursor = {
                    e.event_id for e in self.events.find(
                        app_name=config.app_name,
                        channel_name=config.channel_name,
                        start_time=self._cursor,
                        until_time=self._cursor
                        + _dt.timedelta(milliseconds=1),
                        event_names=self._event_names())
                    if e.event_id is not None}
            except Exception:
                logger.debug(
                    "cursor-boundary pre-read failed; boundary events "
                    "may double-count once", exc_info=True)
        self._user_deltas: Dict[str, EntityDelta] = {}
        self._item_deltas: Dict[str, EntityDelta] = {}
        self._pending_events = 0   # fresh events since last fold (1/event)
        # ingest-trace ids of the pending events (resolved at tail time
        # via the tracer's event map): the fold tick's trace links them
        # so /traces.json ties an ingested event to the fold that
        # absorbed it (ISSUE 2 end-to-end causality)
        self._pending_trace_ids: Set[str] = set()
        # process-wide fold instruments (get-or-create: schedulers in
        # one process share the families, and both HTTP servers expose
        # them through the registry parent chain). Every family
        # carries a ``tenant`` label (ISSUE 17 cost attribution; ""
        # for an untenanted scheduler) — the child for THIS
        # scheduler's tenant is resolved once here, so the tick path
        # observes exactly as before, and a host's per-tenant SLO
        # engines read only their own tenant's series out of the
        # shared families.
        if self.tenant is not None:
            from predictionio_tpu.obs.tenantctx import register_tenant
            register_tenant(self.tenant)
        self._metric_tenant = self.tenant or ""
        reg = get_registry()
        self._h_tick = reg.histogram(
            "pio_fold_tick_seconds",
            "Wall time of a scheduler tick that ran a fold-in "
            "(tail read + touched-row solves + publish + swap)",
            labelnames=("tenant",)).labels(tenant=self._metric_tenant)
        self._c_fold_events = reg.counter(
            "pio_fold_events_total",
            "Fresh events absorbed by completed fold-ins",
            labelnames=("tenant",)).labels(tenant=self._metric_tenant)
        self._c_fold_h2d = reg.counter(
            "pio_fold_upload_bytes_total",
            "Host->device bytes uploaded by fold-in solves (the "
            "per-tick upload cost; ROADMAP open item)",
            labelnames=("tenant",)).labels(tenant=self._metric_tenant)
        self._c_tick_failures = reg.counter(
            "pio_fold_tick_failures_total",
            "Scheduler ticks that raised (tail read, solve, or publish "
            "failure); consecutive failures back off exponentially",
            labelnames=("tenant",)).labels(tenant=self._metric_tenant)
        self._c_fold_read_rows = reg.counter(
            "pio_fold_read_rows_total",
            "Training-data rows read by fold ticks, by read path "
            "(entity_filtered = O(touched) pushdown, full_scan = the "
            "whole corpus)", labelnames=("path", "tenant"))
        self._c_gate_rejects = reg.counter(
            "pio_guard_gate_rejects_total",
            "Fold publishes refused by the pre-swap quality gates "
            "(the live model kept serving)",
            labelnames=("tenant",)).labels(tenant=self._metric_tenant)
        self.gatekeeper = (QualityGatekeeper(config.gate_config, reg)
                           if config.gates else None)
        self.gate_rejects = 0
        # breaker over the event-store tail read (ISSUE 3)
        from predictionio_tpu.resilience import CircuitBreaker
        self._tail_breaker = CircuitBreaker(
            "scheduler_tail",
            failure_threshold=config.tail_breaker_failures,
            reset_timeout_s=config.tail_breaker_reset_s)
        self.consecutive_failures = 0
        self.last_error: Optional[str] = None
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # counters (mirrored onto the attached server's /stats.json)
        self.fold_in_count = 0
        self.events_folded = 0
        self.retrain_requested = False
        self.anchor_loss: Optional[float] = None
        self.last_loss: Optional[float] = None
        self.last_report: Optional[dict] = None
        # incident forensics (ISSUE 6): bundles capture the fold
        # lineage (cursor, counts, breaker state) at incident time
        from predictionio_tpu.obs.incidents import get_incidents
        get_incidents().register_provider("scheduler", self.stats)

    @staticmethod
    def _instance_cursor(instance) -> Optional[_dt.datetime]:
        """Resume point for a (re)attached scheduler: the lineage cursor
        of an online version, else the instance's training start."""
        from predictionio_tpu.data.event import parse_event_time
        from predictionio_tpu.online.registry import ONLINE_BATCH_TAG
        batch = getattr(instance, "batch", "") or ""
        if batch.startswith(ONLINE_BATCH_TAG + ":"):
            try:
                import json as _json
                lineage = _json.loads(batch[len(ONLINE_BATCH_TAG) + 1:])
                if lineage.get("cursor"):
                    return parse_event_time(lineage["cursor"])
            except (ValueError, KeyError):
                logger.warning("unparseable online lineage tag %r", batch)
        return getattr(instance, "start_time", None)

    # -- event-store tail ---------------------------------------------------
    def _event_names(self) -> Optional[List[str]]:
        if self.config.event_names is not None:
            return list(self.config.event_names)
        _, ds_params = self.engine_params.data_source_params
        names = getattr(ds_params, "event_names", None)
        if names is None:
            return None
        # $set rides along: a property-only update (new item metadata)
        # counts as freshness so the next fold re-derives filter metadata
        out = list(names)
        if "$set" not in out:
            out.append("$set")
        return out

    def poll_events(self) -> int:
        """Advance the tail: fold fresh events into the delta state.
        Returns the number of NEW events observed (each event counts
        once, however many entities it touches)."""
        cfg = self.config
        fresh = 0
        # breaker-gated tail: while the event store is down, ticks skip
        # the read entirely (CircuitOpenError propagates — the loop's
        # supervision waits for the probe window); after the reset
        # timeout one probe read is admitted and a success closes the
        # breaker. The iterator stays LAZY (a full 50k-event tick never
        # materializes twice); delta state commits only after the loop
        # completes, so a mid-iteration read failure is side-effect-free.
        self._tail_breaker.allow()
        new_users: Dict[str, EntityDelta] = {}
        new_items: Dict[str, EntityDelta] = {}
        new_trace_ids: Set[str] = set()
        miss_ids: List[str] = []
        max_t = self._cursor
        boundary: Set[str] = set()
        # only STORE work (find + iterator pulls) is attributed to the
        # breaker; a poisoned event that raises during delta processing
        # must land in the supervision loop's counted/escalating branch
        # (the breaker staying closed is what routes it there), not
        # masquerade as a store outage
        try:
            it = iter(self.events.find(
                app_name=cfg.app_name, channel_name=cfg.channel_name,
                start_time=self._cursor, event_names=self._event_names(),
                limit=cfg.tail_batch_limit))
        except Exception:
            self._tail_breaker.record_failure()
            raise
        while True:
            try:
                e = next(it)
            except StopIteration:
                break
            except Exception:
                self._tail_breaker.record_failure()
                raise
            try:
                if e.event_id is not None \
                        and e.event_id in self._seen_at_cursor:
                    continue  # boundary-instant re-read
                fresh += 1
                if e.event_id is not None:
                    tid = TRACER.trace_id_for_event(e.event_id)
                    if tid:
                        new_trace_ids.add(tid)
                    elif len(miss_ids) < 256:
                        # minted in another process (ISSUE 13): batch-
                        # resolved against fleet peers after the read
                        miss_ids.append(e.event_id)
                d = EntityDelta.from_event(e)
                # route by entity TYPE: a rate/buy/view event's subject
                # is a user and its target an item; a $set on an item
                # is an item-side delta even though it arrives in
                # entity_id
                if e.entity_id:
                    side = (new_items if e.entity_type == "item"
                            else new_users)
                    prev = side.get(e.entity_id)
                    side[e.entity_id] = d if prev is None \
                        else prev.merge(d)
                if e.target_entity_id and e.target_entity_type != "user":
                    prev = new_items.get(e.target_entity_id)
                    new_items[e.target_entity_id] = (
                        d if prev is None else prev.merge(d))
                if max_t is None or e.event_time > max_t:
                    max_t = e.event_time
                    boundary = {e.event_id} if e.event_id else set()
                elif e.event_time == max_t and e.event_id:
                    boundary.add(e.event_id)
            except Exception:
                # delta PROCESSING failed, but the store was answering:
                # close out the breaker interaction with the verdict
                # the read evidence supports (this also releases a
                # half-open probe slot allow() may hold — without it
                # the breaker would be stuck half-open forever), then
                # let the supervision loop's counted branch own the
                # failure (breaker closed routes it there).
                self._tail_breaker.record_success()
                raise
        self._tail_breaker.record_success()
        if miss_ids:
            # cross-process ingest traces (ISSUE 13): resolve the local
            # misses against fleet peers' event maps. Fail-soft and
            # peers-only — co-located servers share this process's
            # tracer, so a local miss means another pid or no trace at
            # all (directly-inserted training rows).
            try:
                from predictionio_tpu.obs import fleet
                new_trace_ids.update(
                    fleet.resolve_event_traces(miss_ids).values())
            except Exception:
                logger.debug("fleet event-trace resolution failed",
                             exc_info=True)
        with self._lock:
            # partition merge through the aggregator's monoid machinery
            self._user_deltas = merge_aggregations(
                [self._user_deltas, new_users])
            self._item_deltas = merge_aggregations(
                [self._item_deltas, new_items])
            self._pending_events += fresh
            # bounded: link fidelity degrades gracefully under a flood
            # (Trace.MAX_LINKS caps the fold trace's side anyway)
            room = 256 - len(self._pending_trace_ids)
            if room > 0:
                for tid in new_trace_ids:
                    self._pending_trace_ids.add(tid)
                    room -= 1
                    if room <= 0:
                        break
            if max_t is not None and (self._cursor is None
                                      or max_t > self._cursor):
                self._cursor = max_t
                self._seen_at_cursor = boundary
            elif max_t is not None:
                self._seen_at_cursor |= boundary
        return fresh

    # -- trigger logic ------------------------------------------------------
    def pending_deltas(self) -> int:
        """Fresh EVENTS accumulated since the last fold (each event
        counts once — max_deltas means events, as documented)."""
        with self._lock:
            return self._pending_events

    def should_fold(self, now: Optional[_dt.datetime] = None) -> bool:
        cfg = self.config
        with self._lock:
            if self._pending_events == 0:
                return False
            if self._pending_events >= cfg.max_deltas:
                return True
            firsts = [d.first_t for d in list(self._user_deltas.values())
                      + list(self._item_deltas.values())
                      if d.first_t is not None]
            if not firsts:
                return False
            now = now or utcnow()
            return (now - min(firsts)).total_seconds() >= cfg.max_staleness_s

    # -- the fold-in step ---------------------------------------------------
    def _read_training_data(self):
        """Full-scan read through the engine's own data source (the
        fallback path; kept zero-arg so tests and subclasses can stub
        it)."""
        data_source = self.engine.make_data_source(self.engine_params)
        return data_source.read_training()

    @staticmethod
    def _td_rows(td) -> Optional[int]:
        """Row count of a template's training payload (ratings for the
        recommendation shape, view + like events for similarproduct);
        None when the shape is unknown."""
        total = None
        for attr in ("ratings", "view_events", "like_events"):
            rows = getattr(td, attr, None)
            if rows is None:
                continue
            try:
                n = int(len(rows))
            except TypeError:
                continue
            total = n if total is None else total + n
        return total

    def _read_training(self, touched_users, touched_items):
        """The cost-model cutover: entity-filtered read when the data
        source supports it and the touched set is small, else the full
        scan. Returns ``(td, info)`` where info carries readPath/
        readRows for the trace, report and metrics."""
        cfg = self.config
        n_touched = len(touched_users) + len(touched_items)
        if cfg.filtered_reads and 0 < n_touched \
                <= cfg.filtered_read_max_entities:
            data_source = self.engine.make_data_source(self.engine_params)
            reader = getattr(data_source, "read_training_touched", None)
            if reader is not None:
                td = reader(touched_users, touched_items)
                return td, {"readPath": "entity_filtered",
                            "readRows": self._td_rows(td)}
        td = self._read_training_data()
        return td, {"readPath": "full_scan",
                    "readRows": self._td_rows(td)}

    def fold_in(self) -> dict:
        """Run one fold-in over the accumulated deltas and publish."""
        with self._lock:
            user_deltas = self._user_deltas
            item_deltas = self._item_deltas
            n_events = self._pending_events
            trace_ids = self._pending_trace_ids
            self._user_deltas = {}
            self._item_deltas = {}
            self._pending_events = 0
            self._pending_trace_ids = set()
        touched_users = list(user_deltas.keys())
        touched_items = list(item_deltas.keys())
        # two-way causality links: the fold trace names the ingest
        # traces it absorbs, and each ingest trace gains a link to the
        # fold (so either end of /traces.json walks to the other)
        tick_trace = TRACER.current_trace()
        if tick_trace is not None:
            for tid in trace_ids:
                tick_trace.link(tid)
                TRACER.link_completed(tid, tick_trace.trace_id)
        # this thread's uploads only: a concurrent serving cache miss
        # or /reload on another thread must not inflate the fold's cost
        h2d_before = jaxmon.thread_h2d_total()
        try:
            with TRACER.span("tail_data_read") as sp:
                td, read_info = self._read_training(touched_users,
                                                    touched_items)
                if sp is not None:
                    sp.attrs.update(read_info)
            new_models: List[Any] = []
            reports: List[dict] = []
            folded_any = False
            # the fold must replay the Preparator's data policy (dedup
            # mode, exclusion lists) even though it cannot run prepare()
            # itself (prepare rebuilds vocabularies, shuffling the
            # deployed dense indices)
            _, prep_params = self.engine_params.preparator_params
            for algo, model in zip(self.algorithms, self.models):
                fold = getattr(algo, "fold_in", None)
                if fold is None:
                    new_models.append(model)  # not online-capable: keep
                    continue
                with TRACER.span("fold_solve",
                                 touchedUsers=len(touched_users),
                                 touchedItems=len(touched_items)):
                    new_model, report = fold(
                        model, td, touched_users, touched_items,
                        preparator_params=prep_params)
                new_models.append(new_model)
                reports.append(report)
                folded_any = True
        except Exception:
            # transient failure (storage hiccup, solve error): restore
            # the popped deltas so the NEXT tick retries these events
            # instead of silently dropping them until a full retrain
            self._restore_deltas(user_deltas, item_deltas, n_events,
                                 trace_ids)
            raise
        report = {
            "foldIn": self.fold_in_count + 1,
            "touchedUsers": len(touched_users),
            "touchedItems": len(touched_items),
            "events": n_events,
            "algorithms": reports,
            # per-tick upload cost through instrumented paths — the
            # ROADMAP open item as a first-class number
            "h2dBytes": jaxmon.h2d_delta(h2d_before),
            # which read path the cost model chose, and what it cost
            **read_info,
        }
        # sharded online plane (ISSUE 12): the tick's table layout
        # rides the report + trace so MULTICHIP artifacts and
        # /traces.json can separate sharded from replicated ticks
        sharding = next((r.get("sharding") for r in reports
                         if r.get("sharding")), None)
        if sharding is not None:
            report["sharding"] = sharding
            TRACER.annotate(sharding=sharding)
        TRACER.annotate(h2dBytes=report["h2dBytes"])
        if read_info.get("readRows") is not None:
            self._c_fold_read_rows.labels(
                path=read_info["readPath"],
                tenant=self._metric_tenant).inc(read_info["readRows"])
        if not folded_any:
            logger.warning("no algorithm supports fold_in; deltas dropped")
            self.last_report = report
            return report
        if all(nm is old for nm, old in zip(new_models, self.models)):
            # degenerate tick (ISSUE 5 satellite): every online
            # algorithm no-opped (empty touched set after filtering,
            # all-zero ratings) — nothing to gate or publish, and the
            # consumed events are spent (refolding them would no-op
            # identically, so they are NOT restored)
            report["degenerate"] = True
            TRACER.annotate(degenerate=True)
            logger.info("fold tick was a clean no-op (%d event(s) "
                        "contributed nothing solvable)", n_events)
            self.last_report = report
            return report
        # pre-swap quality gates (ISSUE 5): the candidate set must pass
        # against the LIVE models before any publish is attempted
        guard_wall_s = sum(r.get("guardWallS") or 0.0 for r in reports)
        if self.gatekeeper is not None:
            g0 = _time.perf_counter()
            with TRACER.span("guard_gates") as sp:
                gate_report = self.gatekeeper.evaluate(
                    new_models, self.models, self.algorithms)
                if sp is not None:
                    sp.attrs["passed"] = gate_report["passed"]
                    sp.attrs["verdicts"] = {
                        g["gate"]: g["verdict"]
                        for g in gate_report["gates"]}
            guard_wall_s += _time.perf_counter() - g0
            report["gateReport"] = gate_report
        # the robustness tax, first-class: sentinel + gate wall per tick
        report["guardOverheadMs"] = round(guard_wall_s * 1000, 3)
        if self.gatekeeper is not None:
            TRACER.annotate(gatesPassed=gate_report["passed"])
            # flight record (ISSUE 6): every gate verdict is a
            # lifecycle transition — the pass that precedes a publish
            # as much as the reject that blocks one
            from predictionio_tpu.obs.flight import FLIGHT
            FLIGHT.record(
                "gate_verdict",
                model_version=getattr(self.instance, "id", None),
                passed=gate_report["passed"],
                verdicts={g["gate"]: g["verdict"]
                          for g in gate_report["gates"]},
                events=n_events)
            if not gate_report["passed"]:
                # the events are restored for the record, but the same
                # data folds the same way — the supervision loop's
                # escalation to a full retrain is the real exit
                self._restore_deltas(user_deltas, item_deltas, n_events,
                                     trace_ids)
                self._c_gate_rejects.inc()
                self.gate_rejects += 1
                if self.server is not None:
                    self.server.note_publish_failure()
                self.last_report = report
                # incident bundle (ISSUE 6): a refused publish is a
                # postmortem-worthy event — freeze the gate report,
                # the tick's trace and the fold lineage now
                from predictionio_tpu.obs.incidents import INCIDENTS
                tick = TRACER.current_trace()
                INCIDENTS.capture(
                    "gate_rejected",
                    "fold publish refused by quality gate(s): "
                    + ", ".join(g["gate"] for g in gate_report["gates"]
                                if g["verdict"] == "fail"),
                    context={"gateReport": gate_report,
                             "events": n_events,
                             "baseInstance": getattr(self.instance,
                                                     "id", None)},
                    trace_ids=(tick.trace_id,) if tick else ())
                raise GateRejected(gate_report)
        # drift gate: anchor = the first post-fold loss after (re)deploy
        losses = [r["loss"] for r in reports if r.get("loss") is not None]
        loss = max(losses) if losses else None
        report["loss"] = loss
        if loss is not None:
            self.last_loss = loss
            if self.anchor_loss is None:
                self.anchor_loss = loss
            elif loss > self.config.drift_ratio * self.anchor_loss:
                self.retrain_requested = True
                report["retrainRequested"] = True
                logger.warning(
                    "fold-in drift: loss %.5f > %.2f x anchor %.5f — "
                    "escalating to full retrain", loss,
                    self.config.drift_ratio, self.anchor_loss)
        report["anchorLoss"] = self.anchor_loss
        if report.get("retrainRequested") and self.on_retrain is not None:
            self.on_retrain(report)
        try:
            self._publish(new_models, report,
                          touched_entities={"user": touched_users,
                                            "item": touched_items})
        except Exception:
            # a publish failure (registry insert, in-process swap) means
            # the SERVED model never advanced: restore the deltas so the
            # next tick re-solves and re-publishes, and count nothing as
            # folded — /stats.json must not claim events the serving
            # path never absorbed. The re-solve is deterministic over
            # the re-read data, so the retry is idempotent. The attached
            # server keeps answering from the stale model and says so
            # (X-PIO-Model-Staleness-Ms) until a publish lands.
            self._restore_deltas(user_deltas, item_deltas, n_events,
                                 trace_ids)
            if self.server is not None:
                self.server.note_publish_failure()
            raise
        self.models = new_models
        self.fold_in_count += 1
        self.events_folded += n_events
        self._c_fold_events.inc(n_events)
        self._c_fold_h2d.inc(report["h2dBytes"])
        self.last_report = report
        return report

    def _restore_deltas(self, user_deltas, item_deltas, n_events: int,
                        trace_ids: Optional[Set[str]] = None):
        with self._lock:
            self._user_deltas = merge_aggregations(
                [user_deltas, self._user_deltas])
            self._item_deltas = merge_aggregations(
                [item_deltas, self._item_deltas])
            self._pending_events += n_events
            if trace_ids:
                self._pending_trace_ids |= trace_ids

    def _publish(self, models: Sequence[Any], report: dict,
                 touched_entities: Optional[dict] = None):
        """``touched_entities`` ({"user": ids, "item": ids}): the exact
        rows this fold tick re-solved — forwarded to the attached
        server's hot-swap so its result cache invalidates per entity
        instead of clearing (ISSUE 14); a cross-process /reload has no
        such lineage and clears the remote cache wholesale."""
        version = None
        if self.registry is not None:
            with self._lock:
                cursor = self._cursor
            meta = {"foldIn": report["foldIn"],
                    "events": report["events"]}
            if cursor is not None:
                # recorded so a RESTARTED follower resumes tailing from
                # the folded data's horizon, not from the publish
                # instant (events landing in the read->publish window
                # would otherwise be skipped forever). Conservative: a
                # boundary re-read refolds, which is idempotent.
                meta["cursor"] = cursor.isoformat()
            with TRACER.span("registry_publish"):
                version = self.registry.publish(
                    self.engine, self.engine_params, self.instance,
                    models, meta=meta)
            TRACER.annotate(version=version)
            report["publishedVersion"] = version
        from predictionio_tpu.obs.flight import FLIGHT
        FLIGHT.record("fold_publish", model_version=version,
                      events=report["events"],
                      foldIn=report["foldIn"],
                      readPath=report.get("readPath"))
        if self.server is not None:
            with TRACER.span("hot_swap", version=version or ""):
                self.server.swap_models(
                    models, version=version,
                    fold_in_events=report["events"],
                    touched_entities=touched_entities)
        if self.reload_url is not None:
            with TRACER.span("reload", url=self.reload_url):
                try:
                    # cross-process publish hop (ISSUE 13): the engine
                    # server adopts this fold tick's trace id, so its
                    # hot_swap flight record and load spans join the
                    # fleet-stitched story
                    from predictionio_tpu.obs.trace import \
                        trace_context_headers
                    req = urllib.request.Request(
                        self.reload_url, method="POST", data=b"",
                        headers=trace_context_headers())
                    urllib.request.urlopen(req, timeout=30).read()
                    report["reloaded"] = True
                except Exception as e:
                    report["reloaded"] = False
                    logger.error("POST %s failed: %s", self.reload_url, e)

    # -- tick / loop --------------------------------------------------------
    def tick(self, force: bool = False) -> Optional[dict]:
        """One scheduler step: tail, then fold if a threshold fired (or
        ``force``). Returns the fold-in report, or None if no fold ran.

        Each tick that observes fresh events or runs a fold records a
        ``fold_tick`` trace (tail read -> touched-row solves ->
        registry publish -> hot swap), linked to the ingest traces of
        the events it absorbed; idle ticks are discarded so the poll
        loop doesn't flood the trace ring."""
        if self.tenant is not None:
            from predictionio_tpu.utils.device_cache import tenant_scope
            with tenant_scope(self.tenant):
                return self._tick_inner(force)
        return self._tick_inner(force)

    def _tick_inner(self, force: bool = False) -> Optional[dict]:
        t0 = _time.perf_counter()
        with TRACER.trace("fold_tick") as tr:
            with TRACER.span("tail_read") as sp:
                fresh = self.poll_events()
                if sp is not None:
                    sp.attrs["freshEvents"] = fresh
            tr.discard = fresh == 0   # kept only if a fold runs below
            if self.retrain_requested and not force:
                return None  # drifted: wait for the full retrain
            if force or self.should_fold():
                if self.pending_deltas() == 0:
                    return None
                tr.discard = False
                report = self.fold_in()
                self._h_tick.observe(_time.perf_counter() - t0)
                tr.root.attrs["events"] = report["events"]
                return report
            return None

    def start(self) -> "DeltaTrainingScheduler":
        if self._thread is not None:
            return self
        self._stop.clear()
        # fleet member record (ISSUE 13): a following scheduler is a
        # fleet citizen — no HTTP port, but its liveness governs flight
        # GC and shows up in `pio fleet status` / incident bundles
        from predictionio_tpu.obs import fleet
        self._fleet_id = fleet.register_member("scheduler")

        def loop():
            # supervised ticks (ISSUE 3): consecutive failures back off
            # exponentially (a down event store is probed at the breaker
            # cadence, not hammered at poll cadence), and a persistently
            # failing fold loop escalates to a full retrain instead of
            # retrying on the same cadence forever
            from predictionio_tpu.resilience import CircuitOpenError
            cfg = self.config
            delay = cfg.poll_interval_s
            while True:
                if self._stop.wait(delay):
                    return
                try:
                    if self._tick_gate is not None:
                        with self._tick_gate.turn(self.tenant or ""):
                            self.tick()
                    else:
                        self.tick()
                    self.consecutive_failures = 0
                    self.last_error = None
                    delay = cfg.poll_interval_s
                except CircuitOpenError as e:
                    # the tail breaker fast-failing is the INTENDED
                    # degradation while the store is down — wait for
                    # the probe window; it must not count toward the
                    # retrain escalation (a retrain needs the store
                    # too, and a recovered store should resume folding)
                    self.last_error = str(e)
                    delay = min(max(e.retry_after_s,
                                    cfg.poll_interval_s),
                                cfg.failure_backoff_cap_s)
                    logger.warning(
                        "scheduler tail breaker open; next probe in "
                        "%.1fs", delay)
                except Exception as e:
                    self.last_error = str(e)
                    self._c_tick_failures.inc()
                    if self._tail_breaker.state != "closed":
                        # the failure tripped (or re-tripped, on a
                        # failed half-open probe) the tail breaker: the
                        # breaker owns store-read outages — wait for
                        # its probe cadence, and like the fast-fail
                        # path above do NOT count toward the retrain
                        # escalation. Everything else (solve, publish,
                        # poisoned-event processing) leaves the breaker
                        # closed — poll_events attributes only store
                        # work to it — so those failures always land in
                        # the counted, escalating branch below.
                        delay = max(cfg.poll_interval_s,
                                    min(cfg.tail_breaker_reset_s,
                                        cfg.failure_backoff_cap_s))
                        logger.warning(
                            "scheduler tail read failed and the "
                            "breaker is %s; next attempt in %.1fs",
                            self._tail_breaker.state, delay)
                        continue
                    self.consecutive_failures += 1
                    delay = min(
                        cfg.poll_interval_s
                        * (2 ** self.consecutive_failures),
                        cfg.failure_backoff_cap_s)
                    logger.exception(
                        "scheduler tick failed (%d consecutive)",
                        self.consecutive_failures)
                    if (self.consecutive_failures
                            >= cfg.max_tick_failures
                            and not self.retrain_requested):
                        self.retrain_requested = True
                        report = {
                            "retrainRequested": True,
                            "reason": "consecutive_tick_failures",
                            "failures": self.consecutive_failures,
                            "lastError": self.last_error,
                        }
                        logger.error(
                            "scheduler: %d consecutive tick failures — "
                            "escalating to full retrain",
                            self.consecutive_failures)
                        from predictionio_tpu.obs.flight import FLIGHT
                        FLIGHT.record(
                            "retrain_escalation",
                            failures=self.consecutive_failures,
                            lastError=self.last_error)
                        if self.on_retrain is not None:
                            try:
                                self.on_retrain(report)
                            except Exception:
                                logger.exception("on_retrain failed")

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="pio-delta-scheduler")
        self._thread.start()
        return self

    def stop(self):
        from predictionio_tpu.obs import fleet
        fleet.deregister_member(getattr(self, "_fleet_id", None))
        self._fleet_id = None
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None

    # -- canary feedback (ISSUE 5) ------------------------------------------
    def note_canary_decision(self, decision: dict):
        """The attached server's canary watchdog decided. On promote,
        pin the version as last-known-good in the registry (the durable
        rollback target). On rollback, the fold lineage has produced a
        bad-serving model the gates could not see: re-anchor on what is
        actually serving and escalate to a full retrain."""
        if decision.get("decision") == "promote":
            version = decision.get("candidateVersion")
            if self.registry is not None and version:
                try:
                    inst = self.instance
                    self.registry.pin_last_good(
                        inst.engine_id, inst.engine_version,
                        inst.engine_variant, version)
                except Exception:
                    logger.exception("last-good pin failed")
            return
        if decision.get("decision") == "rollback":
            if self.server is not None:
                self.models = list(self.server.models)
            self.retrain_requested = True
            version = decision.get("candidateVersion")
            if self.registry is not None and version:
                # make the verdict durable: the rejected version must
                # not stay newest-COMPLETED, or the next /reload or
                # restart would deploy it to 100% of traffic
                try:
                    self.registry.demote_version(version)
                except Exception:
                    logger.exception("demoting %s failed", version)
            logger.error(
                "canary rollback of %s (%s): scheduler re-anchored on "
                "the serving models and escalated to a full retrain",
                decision.get("candidateVersion"),
                decision.get("reason"))

    # -- introspection ------------------------------------------------------
    def stats(self) -> dict:
        with self._lock:
            pending = self._pending_events
        return {
            "foldIns": self.fold_in_count,
            "eventsFolded": self.events_folded,
            "pendingEvents": pending,
            "cursor": self._cursor.isoformat() if self._cursor else None,
            "anchorLoss": self.anchor_loss,
            "lastLoss": self.last_loss,
            "retrainRequested": self.retrain_requested,
            "consecutiveFailures": self.consecutive_failures,
            "lastError": self.last_error,
            "tailBreaker": self._tail_breaker.state,
            "gateRejects": self.gate_rejects,
        }


def attach_scheduler(server, config: SchedulerConfig,
                     registry=None, **kw) -> DeltaTrainingScheduler:
    """Build a scheduler bound to a LOADED in-process EngineServer: the
    engine, params, instance and live model set all come from the server,
    and every fold-in hot-swaps it atomically."""
    if not server.algorithms:
        raise RuntimeError("server has no engine loaded; call load() first")
    sched = DeltaTrainingScheduler(
        engine=server.engine, engine_params=server.engine_params,
        instance=server.engine_instance, algorithms=server.algorithms,
        models=server.models, config=config, server=server,
        registry=registry, **kw)
    # canary feedback loop (ISSUE 5): watchdog promotions pin the
    # last-known-good version; rollbacks re-anchor the fold lineage and
    # escalate to a full retrain
    server.on_canary_decision = sched.note_canary_decision
    return sched
