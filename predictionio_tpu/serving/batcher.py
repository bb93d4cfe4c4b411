"""Micro-batching for the query path (beyond-parity).

The reference serves queries one at a time per request thread
(CreateServer.scala:515 "TODO: Parallelize"). On a TPU the per-call
dispatch + device->host fetch dominates single-query latency, so under
concurrent load the server can coalesce queries that arrive within a short
window into ONE batched device call (Algorithm.batch_predict) and fan the
results back out — the standard accelerator-serving pattern.

Opt-in via ServerConfig.micro_batch > 1. Coalescing is DRAIN-FIRST:
each dispatch takes everything that queued while the previous batch was
on the device — under load the queue grows, so batches grow, which is
the self-regulating part that delivers the throughput. On top of that,
the door is held open (up to `max_wait_ms`) only while MORE queries are
known to be in flight (submitted, unanswered, not yet dispatched, not
in this batch) than the batch holds: that covers the instants between a
submit's counter increment and its queue put, and nothing else — a
query still being HTTP-parsed is invisible to the server and no window
can wait for it honestly. A lone closed-loop client (serial requests)
always sees `batch == undispatched` and dispatches immediately with no
window cost; so does an idle server. Two earlier designs were rejected
by measurement: an unconditional window (rounds 2-3) charged every
serial query the full window, and an EMA-of-arrival-gaps gate charged
them the same way because one closed-loop client's gaps equal the
service time — dense by any rate heuristic. `latency_budget_ms`, when
set, caps how long the OLDEST query may sit in the coalescing stage
(the knob for tail-latency-sensitive deployments; it bounds queueing
delay, not device time).

Pipelined executor (ISSUE 14): with ``process_batch_begin`` provided
and ``inflight`` > 1 (PIO_SERVE_INFLIGHT, default 2), the serve path
runs as a two-stage pipeline exploiting JAX async dispatch — the
FORMATION thread forms batch N+1 and enqueues its device call while
batch N's compute is still on the device, and a dedicated COMPLETION
thread performs batch N's deferred device->host readback,
post-processing and waiter wakeup. A bounded semaphore caps the
windows between dispatch and completion at ``inflight`` (backpressure:
formation blocks when the device/completion side lags). Host-side
stages (formation, supplement, serialization) thereby overlap device
compute; the costmon 1-in-N sampled sync inside the dispatch stays the
only deliberate sync besides the completion readback itself.

Adaptive batch sizing (ISSUE 14): instead of the fixed wait-window
alone, each window derives a pow2-snapped target batch size from the
known demand (queue depth + undispatched count) and scales its hold
with the ``pio_device_occupancy`` EWMA — a busy device earns fuller
windows (fewer, larger dispatches), an idle one dispatches at the
first pow2 boundary covering demand. Targets never exceed
``max_batch`` and snap to the same pow2 buckets the AOT warm ladder
compiled, so adaptation can never mint a program or trigger a compile.
"""

from __future__ import annotations

import logging
import os
import queue
import threading
import time
from typing import Any, Callable, Optional

from predictionio_tpu.obs.slo import lock_probe, timed_acquire

logger = logging.getLogger(__name__)


def _inflight_default() -> int:
    """Dispatched-not-completed window cap. Since the readback plane
    (ISSUE 19) every in-flight window's d2h copy is already in flight
    at dispatch, so this is also the TRANSFER-depth knob: K windows'
    d2h walls overlap instead of serialize, and values > 2 genuinely
    deepen the pipeline on a real accelerator (bench sweeps it as
    ``serve_inflight_sweep``)."""
    try:
        return max(1, int(os.environ.get("PIO_SERVE_INFLIGHT", 2)))
    except (TypeError, ValueError):
        return 2


def _adapt_occ_default() -> float:
    """Occupancy above which the adaptive sizer doubles its target
    toward the next pow2 bucket (the device is the bottleneck: fuller
    windows cut per-dispatch overhead)."""
    try:
        return float(os.environ.get("PIO_SERVE_ADAPT_OCC", 0.4))
    except (TypeError, ValueError):
        return 0.4


class ShedError(RuntimeError):
    """Load shed: the queue's wait bound exceeds the request's deadline,
    so the server answers 503 + Retry-After NOW instead of burning a
    thread on an answer the client will have abandoned (ISSUE 3
    graceful degradation). ``retry_after_s`` is the server's own wait
    bound — the honest earliest time a retry could be served."""

    http_status = 503

    def __init__(self, wait_bound_s: float, deadline_s: float):
        super().__init__(
            f"overloaded: queue wait bound {wait_bound_s * 1000:.0f}ms "
            f"exceeds request deadline {deadline_s * 1000:.0f}ms")
        self.retry_after_s = wait_bound_s


class ShutdownError(RuntimeError):
    """The micro-batcher is stopping; queued requests fail explicitly
    instead of hanging their futures."""

    http_status = 503

    def __init__(self, message: str = "server shutting down"):
        super().__init__(message)


class _Pending:
    __slots__ = ("query", "event", "result", "error", "t_enqueue",
                 "trace_id", "batch_trace_id", "seq", "t_result")

    def __init__(self, query):
        self.query = query
        self.event = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None
        self.t_enqueue = time.perf_counter()
        # ingress trace of the submitting request thread; the dispatch
        # loop links it to the batch_predict trace (and back)
        self.trace_id: Optional[str] = None
        self.batch_trace_id: Optional[str] = None
        # the serving account (ISSUE 25): the dispatch that answered it
        # and when its result was set, for the request's own record
        self.seq = -1
        self.t_result = 0.0


class _InFlight:
    """One dispatched-not-completed window riding the completion
    queue: its members, the deferred finish() closure, the (open)
    batch_predict trace, the dispatch timestamps, and the front of its
    record in the serving account (obs/trace DISPATCH_FIELDS up to
    ``t_gate``), which the completion thread finishes."""

    __slots__ = ("batch", "finish", "trace", "t_dispatch", "t_ready",
                 "account", "sync_s")

    def __init__(self, batch, finish, trace, t_dispatch, t_ready,
                 account, sync_s):
        self.batch = batch
        self.finish = finish
        self.trace = trace
        self.t_dispatch = t_dispatch
        self.t_ready = t_ready          # process_batch_begin returned
        self.account = account
        self.sync_s = sync_s


class MicroBatcher:
    def __init__(self, process_batch, max_batch: int = 32,
                 max_wait_ms: float = 5.0,
                 latency_budget_ms: Optional[float] = None,
                 metrics=None,
                 process_batch_begin: Optional[Callable] = None,
                 inflight: Optional[int] = None,
                 adaptive: bool = True,
                 tenant: Optional[str] = None):
        """process_batch: fn(List[query]) -> List[result].
        ``process_batch_begin``: fn(List[query]) -> finish() -> results
        — the two-stage split enabling the pipelined executor; with it
        and ``inflight`` > 1 the batcher overlaps device compute with
        formation/completion (see module docstring). `metrics`: an
        obs.MetricsRegistry to mount the coalescing telemetry on — the
        counters below stay the single source of truth (stats() reads
        them directly) and the registry samples them at scrape time;
        the batch-wait distribution is a native histogram."""
        # device dispatch runs on the formation/completion threads,
        # not the request thread — so tenant attribution (ISSUE 17
        # costmon device-time booking, flight/trace stamps) must be
        # entered HERE, once per thread, not per request
        self.tenant = str(tenant) if tenant is not None else None
        self.process_batch = process_batch
        self.process_batch_begin = process_batch_begin
        self.max_batch = max_batch
        self.max_wait_s = max_wait_ms / 1000.0
        self.latency_budget_s = (latency_budget_ms / 1000.0
                                 if latency_budget_ms is not None else None)
        self.inflight = max(1, int(inflight) if inflight is not None
                            else _inflight_default())
        self.pipelined = (process_batch_begin is not None
                          and self.inflight > 1)
        self.adaptive = bool(adaptive)
        self._adapt_occ = _adapt_occ_default()
        # realized coalescing telemetry (read via /stats.json): whether
        # concurrent load actually forms full batches is THE datum for
        # tuning micro_batch_wait_ms on a given link
        self.n_batches = 0
        self.n_queries = 0
        self.max_batch_seen = 0
        # batches dispatched without ever blocking on the window —
        # includes idle/serial traffic AND fully-drained batches under
        # saturated load; (batches - immediateBatches) is the number of
        # dispatches that actually waited for a straggler
        self.n_immediate = 0
        # WHY each dispatch closed its batch — the attribution data for a
        # realized avg batch below micro_batch under concurrent load
        # (e.g. the pinned serve_avg_batch_size=8.0 at micro_batch=16):
        #   exitFullBatch   — hit max_batch (device-bound; raising
        #                     micro_batch could coalesce more)
        #   exitDrainGate   — queue empty and undispatched <= batch: the
        #                     CLIENT POOL was the limit (every submitted-
        #                     undispatched query is already in this batch
        #                     — with N closed-loop clients the steady-
        #                     state batch is at most N whatever the
        #                     window)
        #   exitWindow      — the hold expired waiting on a counted
        #                     straggler (max_wait_ms / latency budget
        #                     bound; raising the window could help)
        #   exitAdaptive    — the pow2-snapped adaptive target was
        #                     reached (ISSUE 14): demand covered, no
        #                     point holding for stragglers past the
        #                     bucket boundary the padding pays anyway
        self.n_exit_full = 0
        self.n_exit_drain_gate = 0
        self.n_exit_window = 0
        self.n_exit_adaptive = 0
        # sum of inflight observed at dispatch: avg inflight is the
        # effective concurrent-client count the batcher actually saw
        self.inflight_at_dispatch_sum = 0
        # queries submitted and not yet answered — feeds the queue wait
        # bound and stats
        self._inflight = 0
        # queries submitted and not yet taken into a dispatched batch —
        # the adaptive window's signal: hold only while the batch is
        # smaller than this. Distinct from _inflight since pipelining
        # (ISSUE 14): members of an earlier window awaiting completion
        # are in flight but NOT coming to this window — gating on them
        # would hold every window open for stragglers that can never
        # arrive.
        self._undispatched = 0
        # windows dispatched to the device and not yet completed
        self._inflight_batches = 0
        self._flight_lock = threading.Lock()
        # deadline shedding (ISSUE 3): EWMA of per-batch service time
        # feeds the queue wait bound; requests whose deadline the bound
        # already exceeds are refused at admission with 503+Retry-After
        self._service_ewma_s = 0.0
        self.n_shed = 0
        self.n_shutdown_failed = 0
        # formation blocked on the in-flight cap (ISSUE 14): the
        # backpressure signal — the device/completion side is the
        # bottleneck, not batch formation
        self.n_pipeline_stalls = 0
        self._q: "queue.Queue[_Pending]" = queue.Queue()
        self._stop = threading.Event()
        # contention probe (ISSUE 6): request threads' wait on the
        # admission lock, as pio_lock_wait_seconds{lock=batcher_inflight}
        self._lock_wait = lock_probe("batcher_inflight")
        self.wait_hist = None
        self.stage_hist = None
        if metrics is not None:
            self.wait_hist = metrics.histogram(
                "pio_engine_batch_wait_seconds",
                "Per-query time in the coalescing stage "
                "(enqueue -> dispatch)")
            # pipeline-stage decomposition (ISSUE 14): where a window's
            # wall goes — formation, device dispatch (enqueue), the
            # sit in the completion queue, and readback+post-process
            self.stage_hist = metrics.histogram(
                "pio_serve_stage_seconds",
                "Per-window wall time by pipeline stage (formation = "
                "first dequeue -> dispatch, dispatch = async enqueue, "
                "completion_wait = dispatched -> completion thread "
                "pickup, readback = blocked on the in-flight d2h copy "
                "(ops/readback wait), completion = post-process + "
                "waiter wakeup minus the readback wait; gate = the wait "
                "on the in-flight cap, begin = dispatch - gate, "
                "turnaround = begin returned -> d2h ready)",
                labelnames=("stage",))
            # children resolved eagerly (the ISSUE 6 self-metrics
            # precedent): a quiet server scrapes zeroed stage series,
            # not an empty family
            for st in ("formation", "dispatch", "completion_wait",
                       "readback", "completion", "gate", "begin",
                       "turnaround"):
                self.stage_hist.labels(stage=st)
            metrics.counter_func(
                "pio_engine_batches_total", "Micro-batch dispatches",
                lambda: self.n_batches)
            metrics.counter_func(
                "pio_engine_batched_queries_total",
                "Queries through the micro-batcher",
                lambda: self.n_queries)
            metrics.counter_func(
                "pio_engine_immediate_batches_total",
                "Dispatches that never blocked on the window",
                lambda: self.n_immediate)
            metrics.gauge_func(
                "pio_engine_max_batch_size", "Largest coalesced batch",
                lambda: self.max_batch_seen)
            metrics.counter_func(
                "pio_engine_batch_exits_total",
                "Why each dispatch closed its batch (attributes a "
                "sub-micro_batch realized batch size: drain_gate = "
                "client pool was the limit, window = straggler hold "
                "expired, full = max_batch hit, adaptive = pow2 "
                "demand target reached)",
                lambda: [({"reason": "full"}, self.n_exit_full),
                         ({"reason": "drain_gate"},
                          self.n_exit_drain_gate),
                         ({"reason": "window"}, self.n_exit_window),
                         ({"reason": "adaptive"},
                          self.n_exit_adaptive)])
            metrics.gauge_func(
                "pio_engine_avg_inflight_at_dispatch",
                "Mean submitted-unanswered queries at dispatch (the "
                "effective concurrent-client count)",
                lambda: round(self.inflight_at_dispatch_sum
                              / self.n_batches, 3)
                if self.n_batches else 0.0)
            metrics.counter_func(
                "pio_engine_shed_total",
                "Queries refused at admission because the queue wait "
                "bound exceeded their deadline (503 + Retry-After)",
                lambda: self.n_shed)
            metrics.gauge_func(
                "pio_engine_queue_wait_bound_seconds",
                "Current admission-time wait bound (queue depth x EWMA "
                "batch service time + window)",
                lambda: self.queue_wait_bound_s())
            metrics.gauge_func(
                "pio_serve_inflight_batches",
                "Windows dispatched to the device and not yet "
                "completed (bounded by PIO_SERVE_INFLIGHT)",
                lambda: self._inflight_batches)
            metrics.counter_func(
                "pio_serve_pipeline_stalls_total",
                "Formation blocked on the in-flight window cap "
                "(backpressure: device/completion is the bottleneck)",
                lambda: self.n_pipeline_stalls)
        # pipelined executor threads (ISSUE 14): formation forms +
        # dispatches; completion reads back + wakes waiters. The
        # semaphore caps dispatched-not-completed windows.
        self._inflight_sem = threading.Semaphore(self.inflight)
        self._completions: "queue.Queue[Optional[_InFlight]]" = \
            queue.Queue()
        self._completion_thread = None
        if self.pipelined:
            self._completion_thread = threading.Thread(
                target=self._completion_loop, daemon=True,
                name="pio-serve-completion")
            self._completion_thread.start()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def stats(self) -> dict:
        # the counters are updated together by the dispatch thread just
        # before each process_batch call; snapshotting queries BEFORE
        # batches keeps the derived average internally consistent
        # (avg <= max_batch) even when a batch lands mid-read
        nq = self.n_queries
        nb = self.n_batches
        mx = self.max_batch_seen
        return {"batches": nb, "batchedQueries": nq,
                "avgBatchSize": (nq / nb if nb else 0.0),
                "maxBatchSize": mx,
                "immediateBatches": self.n_immediate,
                "exitFullBatch": self.n_exit_full,
                "exitDrainGate": self.n_exit_drain_gate,
                "exitWindow": self.n_exit_window,
                "exitAdaptive": self.n_exit_adaptive,
                "shedQueries": self.n_shed,
                "queueWaitBoundSec": self.queue_wait_bound_s(),
                "pipelined": self.pipelined,
                "inflightWindows": self.inflight,
                "inflightBatches": self._inflight_batches,
                "pipelineStalls": self.n_pipeline_stalls,
                "avgInflightAtDispatch": (
                    self.inflight_at_dispatch_sum / nb if nb else 0.0)}

    def queue_wait_bound_s(self) -> float:
        """Upper bound on how long a query enqueued NOW waits before its
        batch dispatches: the window(s) currently on the device plus
        every queued batch ahead of it costs one EWMA service time
        each, plus one coalescing window. An idle batcher returns 0 —
        the drain gate dispatches a lone query immediately, so nothing
        with a deadline is ever shed at zero load. This is the
        admission-control signal AND the Retry-After value on sheds —
        the server's honest estimate, not a constant. With pipelining
        the in-flight windows overlap, so this stays an upper bound."""
        depth = self._q.qsize()
        if self.pipelined:
            busy = self._inflight_batches
        else:
            # inflight > queued means a dispatched batch occupies the
            # device
            busy = 1 if self._inflight > depth else 0
        batches_ahead = (depth + self.max_batch - 1) // self.max_batch \
            + busy
        if batches_ahead == 0:
            return 0.0
        ewma = self._service_ewma_s
        if self.pipelined:
            # the EWMA measures dispatch -> completion, which at
            # steady saturation already INCLUDES the wait behind the
            # other in-flight windows (~inflight x device time);
            # charging every window ahead the full EWMA would
            # double-count the overlap and shed ~2x too eagerly
            ewma /= max(self.inflight, 1)
        return batches_ahead * ewma + self.max_wait_s

    def submit(self, query, deadline_s: Optional[float] = None) -> Any:
        """Blocking: enqueue and wait for the batched result.

        ``deadline_s``: the request's remaining deadline budget
        (propagated from HTTP ingress). When the queue's wait bound
        already exceeds it the query is shed at admission with
        ``ShedError`` (503 + Retry-After) — wasted-work protection
        under saturation while in-deadline queries still answer."""
        from predictionio_tpu.obs import TRACER
        if deadline_s is not None:
            bound = self.queue_wait_bound_s()
            if bound > deadline_s:
                self.n_shed += 1
                from predictionio_tpu.obs.flight import FLIGHT
                FLIGHT.record("shed", coalesce_s=1.0,
                              waitBoundS=round(bound, 4),
                              deadlineS=round(deadline_s, 4))
                raise ShedError(bound, deadline_s)
        p = _Pending(query)
        p.trace_id = TRACER.current_trace_id()
        with timed_acquire(self._flight_lock, self._lock_wait):
            # check-and-enqueue is atomic with stop()'s set-and-sweep
            # (both under _flight_lock), so no submitter can slip a
            # pending item in after the shutdown sweep ran
            if self._stop.is_set():
                raise ShutdownError("micro-batcher is shut down")
            self._inflight += 1
            self._undispatched += 1
            self._q.put(p)
        with TRACER.span("batch_wait"):
            p.event.wait()
        TRACER.note_request(p.t_enqueue, p.t_result, p.seq, self.tenant)
        if p.batch_trace_id is not None:
            # tie this query's ingress trace to the coalesced window
            # that answered it (the dispatch loop recorded the reverse
            # link before waking us)
            cur = TRACER.current_trace()
            if cur is not None:
                cur.link(p.batch_trace_id)
        if p.error is not None:
            raise p.error
        return p.result

    # -- adaptive sizing (ISSUE 14) -----------------------------------------
    def _occupancy(self) -> float:
        try:
            from predictionio_tpu.obs import costmon
            return costmon.occupancy()
        except Exception:
            return 0.0

    def _target_batch(self) -> int:
        """The pow2-snapped batch target for this window: cover the
        known demand (undispatched + queued), and when the device
        occupancy EWMA says the device is busy, aim one bucket higher
        (fuller windows cut per-dispatch overhead exactly when
        dispatches are the contended resource). Always a pow2 <=
        max_batch — the same buckets the AOT warm ladder compiled, so
        adaptation can never trigger a compile."""
        from predictionio_tpu.compile.buckets import bucket_batch
        demand = min(max(self._undispatched, self._q.qsize() + 1),
                     self.max_batch)
        if self._occupancy() >= self._adapt_occ:
            demand = min(demand * 2, self.max_batch)
        return min(bucket_batch(max(demand, 1)), self.max_batch)

    def _window_deadline(self, t_first: float, first: _Pending) -> float:
        """The straggler-hold deadline for one window. Adaptive mode
        scales the base window with device pressure: an idle device
        holds briefly (latency matters, batches add little), a busy or
        backlogged one may hold the full window (throughput matters).
        The latency budget still caps the oldest query's stage time."""
        window_s = self.max_wait_s
        if self.adaptive:
            depth = self._q.qsize()
            scale = min(1.0, 0.25 + self._occupancy()
                        + depth / max(self.max_batch, 1))
            window_s = self.max_wait_s * scale
        deadline = t_first + window_s
        if self.latency_budget_s is not None:
            # cap the oldest query's time in the coalescing stage
            deadline = min(deadline,
                           first.t_enqueue + self.latency_budget_s)
        return deadline

    # -- formation loop ------------------------------------------------------
    def _enter_tenant(self):
        """Pin this thread's context to the batcher's tenant. The
        formation/completion threads serve exactly one tenant for
        their whole lifetime, so a one-shot contextvar set (no scope
        exit) is correct and free on the per-batch path."""
        if self.tenant is not None:
            from predictionio_tpu.obs.tenantctx import _tenant_var
            _tenant_var.set(self.tenant)

    def _loop(self):
        from predictionio_tpu.compile.buckets import bucket_batch
        from predictionio_tpu.obs import TRACER, costmon
        from predictionio_tpu.obs.trace import DISPATCH
        self._enter_tenant()
        while not self._stop.is_set():
            try:
                first = self._q.get(timeout=0.1)
            except queue.Empty:
                continue
            t_first = time.perf_counter()   # batch-formation stage t0
            with TRACER.region("batch.form"):
                batch, held, exit_reason = self._form_batch(first, t_first)
            self.n_batches += 1
            self.n_queries += len(batch)
            self.max_batch_seen = max(self.max_batch_seen, len(batch))
            self.inflight_at_dispatch_sum += self._inflight
            if exit_reason == "full":
                self.n_exit_full += 1
            elif exit_reason == "drain_gate":
                self.n_exit_drain_gate += 1
            elif exit_reason == "adaptive":
                self.n_exit_adaptive += 1
            else:
                self.n_exit_window += 1
            if not held:
                self.n_immediate += 1
            with self._flight_lock:
                # members of this batch are no longer awaiting dispatch
                # (they await COMPLETION — _inflight still counts them)
                self._undispatched -= len(batch)
            if self._stop.is_set():
                # stop landed while this batch was collecting: fail its
                # members explicitly rather than racing a device call
                # against interpreter teardown
                with self._flight_lock:
                    self._inflight -= len(batch)
                for p in batch:
                    self.n_shutdown_failed += 1
                    p.error = ShutdownError()
                    p.event.set()
                continue
            t_dispatch = time.perf_counter()
            if self.wait_hist is not None:
                for p in batch:
                    self.wait_hist.observe(t_dispatch - p.t_enqueue)
            if self.stage_hist is not None:
                self.stage_hist.labels(stage="formation").observe(
                    t_dispatch - t_first)
            if self.pipelined:
                self._dispatch_pipelined(batch, t_first, t_dispatch)
                continue
            seq = TRACER.next_dispatch_seq()
            sync0 = costmon.thread_sync_s()
            t_ready = t_dispatch
            try:
                with TRACER.region("batch.begin"):
                    results = self._run_batch(
                        batch, formation_s=t_dispatch - t_first)
                if len(results) != len(batch):
                    raise RuntimeError(
                        f"batch handler returned {len(results)} results "
                        f"for {len(batch)} queries")
                with self._flight_lock:
                    self._inflight -= len(batch)
                t_ready = time.perf_counter()
                for p, r in zip(batch, results):
                    p.result = r
                    p.seq, p.t_result = seq, t_ready
                    p.event.set()
            except BaseException as e:  # propagate to every waiter
                with self._flight_lock:
                    self._inflight -= len(batch)
                for p in batch:
                    p.error = e
                    p.event.set()
            t_done = time.perf_counter()
            # one synchronous call is begin, device and readback at once:
            # the account's gate is the batch's close, and begin, pickup
            # and d2h-ready are the call's return
            TRACER.record(DISPATCH, (
                seq, first.t_enqueue, t_first, t_dispatch, t_dispatch,
                t_ready, t_ready, t_ready, t_done, len(batch),
                bucket_batch(len(batch)),
                costmon.thread_sync_s() - sync0, self.tenant))
            # EWMA of batch service time: the queue wait bound's basis.
            # Updated on the dispatch thread only; alpha 0.2 smooths
            # device-warmup spikes without lagging a real slowdown.
            self._note_service_time(t_done - t_dispatch)

    def _form_batch(self, first: _Pending, t_first: float):
        """Coalesce one window behind ``first``: (batch, whether the
        door was ever held open, why it closed)."""
        batch = [first]
        # Drain-first batching: take the backlog that accumulated
        # while the previous batch was on the device (the
        # self-regulating coalescing), then hold the door open ONLY
        # while more queries are known in flight (submitted,
        # unanswered, not yet dispatched, not in this batch) —
        # i.e. between their counter increment and queue put,
        # microseconds away. When batch == undispatched nobody else
        # is known to be coming: a closed-loop serial client, or an
        # idle server, dispatches with zero window cost. The
        # (adaptive) window bounds the hold in case a counted
        # straggler stalls before reaching the queue; the adaptive
        # target dispatches at a pow2 boundary once demand is
        # covered.
        held = False
        exit_reason = "full"   # loop falls through => max_batch hit
        deadline = self._window_deadline(t_first, first)
        target = self._target_batch() if self.adaptive \
            else self.max_batch
        while len(batch) < self.max_batch:
            try:
                batch.append(self._q.get_nowait())
                continue
            except queue.Empty:
                pass
            if self._undispatched <= len(batch):
                exit_reason = "drain_gate"
                break          # nobody else known in flight
            if self.adaptive and len(batch) >= target:
                exit_reason = "adaptive"
                break          # demand target (pow2) covered
            remaining = deadline - time.perf_counter()
            if remaining <= 0:
                exit_reason = "window"
                break
            held = True
            try:
                batch.append(self._q.get(timeout=remaining))
            except queue.Empty:
                exit_reason = "window"
                break
        return batch, held, exit_reason

    def _note_service_time(self, dt: float):
        self._service_ewma_s = (dt if self._service_ewma_s == 0.0
                                else 0.8 * self._service_ewma_s
                                + 0.2 * dt)

    def _fail_batch(self, batch, err: BaseException):
        with self._flight_lock:
            self._inflight -= len(batch)
        for p in batch:
            p.error = err
            p.event.set()

    # -- pipelined dispatch/completion (ISSUE 14) ----------------------------
    def _dispatch_pipelined(self, batch, t_first: float,
                            t_dispatch: float):
        """Stage 1 tail: enqueue the window's device call via
        ``process_batch_begin`` and hand the deferred finish() to the
        completion thread. Blocks on the in-flight semaphore first —
        at most ``inflight`` windows sit between dispatch and
        completion (backpressure onto formation, and transitively onto
        the admission queue + shed bound)."""
        from predictionio_tpu.obs import TRACER, costmon
        if not self._inflight_sem.acquire(blocking=False):
            # the device/completion side is the bottleneck right now:
            # count the stall once, then wait (poll so stop() can't be
            # held hostage by a wedged completion)
            self.n_pipeline_stalls += 1
            with TRACER.region("batch.gate"):
                while not self._inflight_sem.acquire(timeout=0.1):
                    if self._stop.is_set():
                        self.n_shutdown_failed += len(batch)
                        self._fail_batch(batch, ShutdownError())
                        return
        t_gate = time.perf_counter()
        sync0 = costmon.thread_sync_s()
        member_traces = [p.trace_id for p in batch if p.trace_id]
        bt = None
        if member_traces:
            bt = TRACER.begin_trace(
                "batch_predict", batch=len(batch),
                formationMs=round((t_dispatch - t_first) * 1000.0, 3),
                pipelined=True)
            for tid in member_traces:
                bt.link(tid)
            for p in batch:
                p.batch_trace_id = bt.trace_id
        try:
            queries = [p.query for p in batch]
            if bt is not None:
                with TRACER.resume(bt), TRACER.region("batch.begin"):
                    finish = self.process_batch_begin(queries)
            else:
                with TRACER.region("batch.begin"):
                    finish = self.process_batch_begin(queries)
        except BaseException as e:
            self._inflight_sem.release()
            if bt is not None:
                # commit the failed window's trace so ?trace_id=
                # resolves it from the members' links
                with self._note_exc(bt):
                    pass
            self._fail_batch(batch, e)
            self._note_service_time(time.perf_counter() - t_dispatch)
            return
        t_begin = time.perf_counter()
        if self.stage_hist is not None:
            self.stage_hist.labels(stage="dispatch").observe(
                t_begin - t_dispatch)
            self.stage_hist.labels(stage="gate").observe(
                t_gate - t_dispatch)
            self.stage_hist.labels(stage="begin").observe(
                t_begin - t_gate)
        with self._flight_lock:
            self._inflight_batches += 1
        self._completions.put(_InFlight(
            batch, finish, bt, t_dispatch, t_begin,
            (TRACER.next_dispatch_seq(), batch[0].t_enqueue, t_first,
             t_dispatch, t_gate),
            costmon.thread_sync_s() - sync0))

    def _note_exc(self, bt):
        """Commit an open batch trace from an error path."""
        from predictionio_tpu.obs import TRACER
        return TRACER.resume(bt, commit=True)

    def _completion_loop(self):
        self._enter_tenant()
        while True:
            item = self._completions.get()
            if item is None:        # stop() sentinel
                break
            self._finish_one(item)

    def _finish_one(self, item: _InFlight):
        """Stage 2: the deferred readback + post-process for one
        window, result fan-out, in-flight bookkeeping. Runs on the
        dedicated completion thread — overlapping the formation
        thread's next window and the device's current one."""
        from predictionio_tpu.compile.buckets import bucket_batch
        from predictionio_tpu.obs import TRACER
        from predictionio_tpu.obs.trace import DISPATCH
        from predictionio_tpu.ops import readback as _readback
        batch, finish, bt = item.batch, item.finish, item.trace
        t_c0 = time.perf_counter()
        wait_s = t_c0 - item.t_ready
        # readback decomposition (ISSUE 19): finish() internally waits
        # on the window's already-in-flight d2h copy through the
        # ops/readback plane; sampling this thread's cumulative wait
        # around the call splits completion into wait-for-copy vs
        # post-process without this module touching a device handle
        # (the JAX006 contract)
        rb0 = _readback.thread_wait_s()
        try:
            if bt is not None:
                bt.root.attrs["completionWaitMs"] = round(
                    wait_s * 1000.0, 3)
                with TRACER.resume(bt, commit=True), \
                        TRACER.region("batch.post"):
                    results = finish()
            else:
                with TRACER.region("batch.post"):
                    results = finish()
            if len(results) != len(batch):
                raise RuntimeError(
                    f"batch handler returned {len(results)} results "
                    f"for {len(batch)} queries")
        except BaseException as e:
            self._inflight_sem.release()
            with self._flight_lock:
                self._inflight_batches -= 1
            self._fail_batch(batch, e)
            self._note_service_time(time.perf_counter()
                                    - item.t_dispatch)
            return
        self._inflight_sem.release()
        with self._flight_lock:
            self._inflight -= len(batch)
            self._inflight_batches -= 1
        seq = item.account[0]
        t_result = time.perf_counter()
        with TRACER.region("batch.wake"):
            for p, r in zip(batch, results):
                p.result = r
                p.seq, p.t_result = seq, t_result
                p.event.set()
        t_done = time.perf_counter()
        # d2h ready: when this thread's last readback wait returned (a
        # handler that fetched nothing was ready at pickup)
        t_d2h = max(t_c0, _readback.thread_ready_t())
        if self.stage_hist is not None:
            rb_s = max(0.0, _readback.thread_wait_s() - rb0)
            self.stage_hist.labels(stage="completion_wait").observe(
                wait_s)
            self.stage_hist.labels(stage="readback").observe(rb_s)
            self.stage_hist.labels(stage="completion").observe(
                max(0.0, t_done - t_c0 - rb_s))
            self.stage_hist.labels(stage="turnaround").observe(
                t_d2h - item.t_ready)
        TRACER.record(DISPATCH, item.account + (
            item.t_ready, t_c0, t_d2h, t_done, len(batch),
            bucket_batch(len(batch)), item.sync_s, self.tenant))
        self._note_service_time(t_done - item.t_dispatch)

    def _run_batch(self, batch, formation_s: float = 0.0):
        """One synchronous dispatch (non-pipelined mode). When any
        member carries an ingress trace, the device call runs under its
        own batch_predict trace linked both ways — the dispatch thread
        has no request context, so the link set is how /traces.json
        ties a query to its window. ``formation_s`` (first dequeue ->
        dispatch) rides the trace as the slow-query waterfall's
        batch_formation stage."""
        member_traces = [p.trace_id for p in batch if p.trace_id]
        if not member_traces:
            return self.process_batch([p.query for p in batch])
        from predictionio_tpu.obs import TRACER
        with TRACER.trace("batch_predict", batch=len(batch),
                          formationMs=round(formation_s * 1000.0, 3)
                          ) as bt:
            for tid in member_traces:
                bt.link(tid)
            for p in batch:
                p.batch_trace_id = bt.trace_id
            return self.process_batch([p.query for p in batch])

    def stop(self, join_timeout_s: float = 10.0):
        """Drain-on-stop: the dispatch thread is given time to finish
        the batch on the device (pipelined mode: the completion thread
        finishes every already-dispatched window — its device work is
        enqueued, the readback completes it), then every request still
        queued (or collected but not yet dispatched) fails with an
        explicit "server shutting down" 503 — no future ever hangs.
        Atomic with submit()'s check-and-enqueue via _flight_lock, so
        nothing can enqueue after the sweep."""
        self._stop.set()
        self._thread.join(timeout=join_timeout_s)
        if self._thread.is_alive():
            logger.warning(
                "micro-batcher dispatch thread still busy after %.1fs; "
                "sweeping the queue anyway", join_timeout_s)
        if self._completion_thread is not None:
            # sentinel AFTER the formation thread stopped enqueuing:
            # every already-dispatched window completes first, in order
            self._completions.put(None)
            self._completion_thread.join(timeout=join_timeout_s)
            if self._completion_thread.is_alive():
                logger.warning(
                    "completion thread still busy after %.1fs; failing "
                    "its undelivered windows", join_timeout_s)
            # a wedged (or sentinel-raced) completion queue: fail any
            # window still undelivered so no waiter hangs forever
            while True:
                try:
                    item = self._completions.get_nowait()
                except queue.Empty:
                    break
                if item is None:
                    continue
                with self._flight_lock:
                    self._inflight_batches -= 1
                self.n_shutdown_failed += len(item.batch)
                self._fail_batch(item.batch, ShutdownError())
        with self._flight_lock:
            while True:
                try:
                    p = self._q.get_nowait()
                except queue.Empty:
                    break
                self._inflight -= 1
                self._undispatched -= 1
                self.n_shutdown_failed += 1
                p.error = ShutdownError()
                p.event.set()
