"""Request/fold trace spans with context propagation (ISSUE 2 piece 2).

A ``trace_id`` is minted at ingress — an Event Server POST, an Engine
Server query, a scheduler fold tick, a training run — and carried
through nested ``span()`` scopes via a contextvar, so the storage
write, tail read, fold-in solve, registry publish, hot-swap, and
batched predict all land in one span tree with per-stage wall timings.

Cross-trace causality uses **links** (the OpenTelemetry span-link idea):
one fold tick absorbs many ingested events, so the tick's trace links
the events' ingest traces (and vice versa) instead of pretending to be
their parent. The Event Server registers ``event_id -> trace_id`` at
write time; the scheduler's tail read resolves the fresh events it
consumed back to their ingest traces.

Completed traces live in per-kind ring buffers (an in-memory,
process-wide view — query traces at serving QPS must not evict the
day's fold ticks) served at ``GET /traces.json`` on both HTTP servers:
last N, filterable by kind, sortable by slowest.

One clock for host and device (ISSUE 25): ``trace``/``resume``/``span``
enter a ``jax.profiler.TraceAnnotation("pio.<name>")`` beside the
``Span`` they make, so whenever a ``jax.profiler`` session runs (the
benchmark's ``--trace 1`` slice, ``pio profile trace start``,
``/profile.json``) every program span lies in the profiler's own trace,
on the clock of the device's operations. With no session the annotation
is an inactive TraceMe; "off" is "no profiler session" and nothing else.
``region()`` is the same for the loops that have no request context
(the batcher's threads, the training driver): a span inside a trace, the
annotation alone outside one. JAX is never imported for the sake of a
span: the annotation class is taken from ``sys.modules`` at first use,
so a process that never loads JAX (the event server) pays nothing.

The serving account (ISSUE 25): beside the trace rings the tracer keeps
two bounded rings of plain tuples, one per dispatch and one per request
(``DISPATCH_FIELDS`` / ``REQUEST_FIELDS``), written by the batcher, the
engine server and the HTTP layer and read with ``recent(kind, n)``.
They outlive the server that wrote them.

Hot-path cost: ``span()`` outside any active trace returns a shared
no-op context manager (~0.3 µs); inside a trace it is one object append,
two ``perf_counter`` calls and the annotation (guarded by
tests/test_obs_overhead.py).
"""

from __future__ import annotations

import collections
import contextvars
import itertools
import os
import re
import sys
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

_span_seq = itertools.count(1)

# -- the profiler's clock (ISSUE 25) -----------------------------------
_annotation_cls = None


def _annotation():
    """``jax.profiler.TraceAnnotation`` (entering it yields None, as a
    span outside a trace does) once some other module has loaded JAX,
    else None. Never imports JAX: the event server has no use for it."""
    global _annotation_cls
    if _annotation_cls is None:
        base = getattr(getattr(sys.modules.get("jax"), "profiler", None),
                       "TraceAnnotation", None)
        if base is None:
            return None   # not latched: JAX may still be loaded later

        class _Annotation(base):
            def __enter__(self):
                super().__enter__()

        _annotation_cls = _Annotation
    return _annotation_cls


class _NoScope:
    """``span()`` outside a trace, ``region()`` with no JAX loaded."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NO_SCOPE = _NoScope()


def _annotate(name: str, attrs: Optional[dict] = None):
    """The ``pio.<name>`` annotation, not yet entered; the shared no-op
    where JAX is not loaded."""
    cls = _annotation()
    if cls is None:
        return _NO_SCOPE
    return cls("pio." + name, **attrs) if attrs else cls("pio." + name)


class _SpanScope:
    """A child span of the current trace, with its annotation: what
    ``span()`` and ``region()`` return inside a trace."""

    __slots__ = ("_var", "_ctx", "_name", "_attrs", "_span", "_token",
                 "_ann")

    def __init__(self, var, ctx, name: str, attrs: dict):
        self._var, self._ctx, self._name, self._attrs = (var, ctx, name,
                                                         attrs)

    def __enter__(self):
        trace, parent = self._ctx
        self._span = s = Span(self._name, parent.span_id)
        if self._attrs:
            s.attrs.update(self._attrs)
        trace.spans.append(s)
        self._token = self._var.set((trace, s))
        cls = _annotation()
        if cls is None:
            self._ann = None
        else:
            self._ann = cls("pio." + self._name, **self._attrs)
            self._ann.__enter__()
        return s

    def __exit__(self, exc_type, exc, tb):
        s = self._span
        ann = self._ann
        if ann is not None:
            if len(s.attrs) > len(self._attrs) and ann.is_enabled():
                # what the body learned (a wait, a count of evictions)
                # goes into the profiler's event too
                ann.set_metadata(**{k: v for k, v in s.attrs.items()
                                    if k not in self._attrs})
            ann.__exit__(exc_type, exc, tb)
        if exc is not None:
            s.error = f"{type(exc).__name__}: {exc}"
        self._var.reset(self._token)
        s.end()
        return False


# -- the serving account (ISSUE 25) ------------------------------------
# One plain tuple per dispatch and per request: floats of
# time.perf_counter(), ints, and the tenant id the traces carry (or
# None), so the collector does not track them.
DISPATCH = "serve.dispatch"
REQUEST = "serve.request"
DISPATCH_FIELDS = ("seq", "t_enqueue", "t_dequeue", "t_closed", "t_gate",
                   "t_begin", "t_pickup", "t_ready", "t_done", "batch",
                   "bucket", "sync_s", "tenant")
REQUEST_FIELDS = ("t_start", "t_enqueue", "t_result", "t_written",
                  "dispatch_seq", "tenant")
_ACCOUNT_CAPACITY = {DISPATCH: 4096, REQUEST: 16384}
_dispatch_seq = itertools.count(1)
_request_note = threading.local()

# -- cross-process propagation (ISSUE 13) ------------------------------
# The header contract every HTTP hop in the stack speaks: an ingress
# that finds X-PIO-Trace-Id adopts that id instead of minting a fresh
# one, and every in-repo client (eventserver_client, the scheduler's
# reload POST, the engine server's feedback loop, the spill replayer)
# injects the ACTIVE trace context — so one trace id survives event
# POST -> fold tick -> hot swap -> served query across OS processes,
# and `pio fleet traces <id>` stitches the per-process span trees back
# into one waterfall.
TRACE_HEADER = "X-PIO-Trace-Id"
PARENT_SPAN_HEADER = "X-PIO-Parent-Span"

#: inbound ids are VALIDATED, not trusted: a trace id is hex (ours are
#: 16 hex chars; foreign tracers up to 128-bit/32 chars ride too), and
#: a garbage header must mint a fresh id rather than poison the rings
_TID_RE = re.compile(r"^[0-9a-fA-F]{8,64}$")
_PARENT_RE = re.compile(r"^[0-9A-Za-z_.:-]{1,128}$")


def inbound_trace_id(headers) -> Optional[str]:
    """The validated inbound trace id, or None (absent/garbage)."""
    try:
        raw = headers.get(TRACE_HEADER)
    except Exception:
        return None
    if not raw:
        return None
    raw = str(raw).strip()
    return raw if _TID_RE.match(raw) else None


def ingress_trace_kwargs(headers) -> dict:
    """Kwargs for a server-side ``TRACER.trace(kind, **kw)``: adopts
    the caller's trace id when the propagation headers are present and
    valid, recording the remote parent span (``<pid>:<span_id>``) as a
    root attr so a stitched waterfall can anchor this process's tree
    under the hop that caused it. Empty dict = mint as before."""
    tid = inbound_trace_id(headers)
    if not tid:
        return {}
    kw: dict = {"trace_id": tid}
    try:
        parent = headers.get(PARENT_SPAN_HEADER)
    except Exception:
        parent = None
    if parent:
        parent = str(parent).strip()
        if _PARENT_RE.match(parent):
            kw["remoteParent"] = parent
    return kw


def trace_context_headers() -> Dict[str, str]:
    """The outbound propagation headers for the ACTIVE trace context
    ({} when none): the trace id plus this process's current span as
    ``<pid>:<span_id>`` — the value a downstream ingress records as
    its remote parent. One contextvar read on the no-trace path."""
    ctx = TRACER._ctx.get()
    if ctx is None:
        return {}
    trace, span = ctx
    return {TRACE_HEADER: trace.trace_id,
            PARENT_SPAN_HEADER: f"{os.getpid()}:{span.span_id}"}


class Span:
    __slots__ = ("name", "span_id", "parent_id", "t_wall", "_t0",
                 "duration_s", "attrs", "error")

    def __init__(self, name: str, parent_id: Optional[int]):
        self.name = name
        self.span_id = next(_span_seq)
        self.parent_id = parent_id
        self.t_wall = time.time()
        self._t0 = time.perf_counter()
        self.duration_s: Optional[float] = None
        self.attrs: Dict[str, object] = {}
        self.error: Optional[str] = None

    def end(self):
        if self.duration_s is None:
            self.duration_s = time.perf_counter() - self._t0

    def to_dict(self) -> dict:
        d = {"name": self.name, "spanId": self.span_id,
             "start": self.t_wall,
             "durationMs": (round(self.duration_s * 1000.0, 3)
                            if self.duration_s is not None else None)}
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        if self.error:
            d["error"] = self.error
        return d


_tid_pool = threading.local()


def _stamp_tenant(root: "Span"):
    """Tenant attribution (ISSUE 17): a trace minted inside an active
    tenant scope carries the tenant id as a root attr — the key that
    lets waterfalls, incident slices and the dashboard tell one
    tenant's requests from another's. An explicit ``tenant=`` attr
    passed by the caller wins; one contextvar read otherwise."""
    if "tenant" in root.attrs:
        return
    from predictionio_tpu.obs.tenantctx import current_tenant
    t = current_tenant()
    if t is not None:
        root.attrs["tenant"] = t


def _new_trace_id() -> str:
    """16-hex trace id, entropy drawn 128 ids at a time into a
    thread-local pool — one request-path os.urandom syscall (with its
    GIL release/reacquire round trip) per 128 traces instead of per
    trace, mirroring event.new_event_id."""
    off = getattr(_tid_pool, "off", None)
    if not off:   # None or exhausted (0)
        _tid_pool.hexes = os.urandom(8 * 128).hex()
        off = 128
    _tid_pool.off = off - 1
    i = (off - 1) << 4
    return _tid_pool.hexes[i:i + 16]


class Trace:
    """One span tree. The root span shares the trace's kind as its
    name; ``links`` are trace_ids of causally-related traces (event
    ingest <-> fold tick), capped so a fold absorbing thousands of
    events can't bloat its /traces.json entry (``linksDropped``
    records the overflow)."""

    MAX_LINKS = 64

    def __init__(self, kind: str, trace_id: Optional[str] = None):
        self.trace_id = trace_id or _new_trace_id()
        self.kind = kind
        self.root = Span(kind, None)
        self.spans: List[Span] = [self.root]
        self.links: "collections.OrderedDict[str, None]" = \
            collections.OrderedDict()
        self.links_dropped = 0
        self.discard = False   # set True to skip the ring (empty ticks)

    @property
    def duration_s(self) -> Optional[float]:
        return self.root.duration_s

    def link(self, other_trace_id: str):
        if not other_trace_id or other_trace_id == self.trace_id:
            return
        if other_trace_id in self.links:
            return
        if len(self.links) >= self.MAX_LINKS:
            self.links_dropped += 1
            return
        self.links[other_trace_id] = None

    def to_dict(self) -> dict:
        by_parent: Dict[Optional[int], List[Span]] = {}
        for s in self.spans:
            by_parent.setdefault(s.parent_id, []).append(s)

        def build(span: Span) -> dict:
            d = span.to_dict()
            kids = by_parent.get(span.span_id)
            if kids:
                d["children"] = [build(k) for k in kids]
            return d

        d = {"traceId": self.trace_id, "kind": self.kind,
             # the owning process: fleet-stitched waterfalls group the
             # per-process trees by this (ISSUE 13)
             "pid": os.getpid(),
             "start": self.root.t_wall,
             "durationMs": (round(self.root.duration_s * 1000.0, 3)
                            if self.root.duration_s is not None
                            else None),
             "links": list(self.links),
             "root": build(self.root)}
        if self.links_dropped:
            d["linksDropped"] = self.links_dropped
        return d


class Tracer:
    """Process-wide trace collector + context propagation."""

    def __init__(self, per_kind_capacity: int = 128,
                 event_map_capacity: int = 8192):
        self.per_kind_capacity = per_kind_capacity
        self._lock = threading.Lock()
        self._done: Dict[str, collections.deque] = {}
        # trace_id -> committed Trace, kept in lockstep with the rings
        # so link_completed is O(1) instead of a ring scan (a fold can
        # absorb thousands of events per tick)
        self._by_id: Dict[str, Trace] = {}
        self._ctx: contextvars.ContextVar = contextvars.ContextVar(
            "pio_trace_ctx", default=None)
        # event_id -> trace_id, bounded FIFO: lets the scheduler's tail
        # read resolve fresh events back to their ingest traces
        self._event_traces: "collections.OrderedDict[str, str]" = \
            collections.OrderedDict()
        self._event_map_capacity = event_map_capacity
        # the serving account: appends are atomic under the interpreter
        # lock and the readers copy, so the rings take no lock
        self._account: Dict[str, collections.deque] = {
            kind: collections.deque(maxlen=cap)
            for kind, cap in _ACCOUNT_CAPACITY.items()}
        self._gc_ann = None
        self._gc_watchers = 0

    # -- context -------------------------------------------------------
    def current_trace(self) -> Optional[Trace]:
        ctx = self._ctx.get()
        return ctx[0] if ctx else None

    def current_trace_id(self) -> Optional[str]:
        t = self.current_trace()
        return t.trace_id if t else None

    @contextmanager
    def trace(self, kind: str, trace_id: Optional[str] = None, **attrs):
        """Mint a trace and make it current for the calling thread's
        scope. Exceptions mark the root span and re-raise. Set
        ``trace.discard = True`` inside to skip recording (e.g. an
        empty scheduler tick)."""
        t = Trace(kind, trace_id=trace_id)
        if attrs:
            t.root.attrs.update(attrs)
        _stamp_tenant(t.root)
        token = self._ctx.set((t, t.root))
        try:
            with _annotate(kind):
                yield t
        except BaseException as e:
            t.root.error = f"{type(e).__name__}: {e}"
            raise
        finally:
            self._ctx.reset(token)
            t.root.end()
            if not t.discard:
                self._commit(t)

    def begin_trace(self, kind: str, **attrs) -> Trace:
        """Mint a trace WITHOUT making it current or committing it —
        the cross-thread half of :meth:`trace` for the pipelined
        serving executor (ISSUE 14): the formation thread begins the
        ``batch_predict`` trace, each stage re-enters it via
        :meth:`resume`, and the completion stage's ``resume(...,
        commit=True)`` ends + commits it."""
        t = Trace(kind)
        if attrs:
            t.root.attrs.update(attrs)
        _stamp_tenant(t.root)
        return t

    @contextmanager
    def resume(self, t: Trace, commit: bool = False):
        """Make an EXISTING (uncommitted) trace current for this
        thread's scope — spans recorded inside land on it. With
        ``commit`` the trace's root is ended and the trace committed
        on exit: the resuming stage is its final owner. Exceptions
        mark the root span and re-raise (matching :meth:`trace`)."""
        token = self._ctx.set((t, t.root))
        try:
            with _annotate(t.kind):
                yield t
        except BaseException as e:
            t.root.error = f"{type(e).__name__}: {e}"
            raise
        finally:
            self._ctx.reset(token)
            if commit:
                t.root.end()
                if not t.discard:
                    self._commit(t)

    def span(self, name: str, **attrs):
        """A child span of the current trace, entered with its
        ``pio.<name>`` annotation; a shared no-op when no trace is
        active (so instrumented code needs no caller checks)."""
        ctx = self._ctx.get()
        if ctx is None:
            return _NO_SCOPE
        return _SpanScope(self._ctx, ctx, name, attrs)

    def region(self, name: str, **attrs):
        """:meth:`span` for code with no request context (the batcher's
        loops, the training driver, the HTTP layer): inside a trace it
        IS ``span()``; outside one it is the ``pio.<name>`` annotation
        alone (entering it yields None: no ``Span``, no ring), and
        nothing at all where JAX is not loaded."""
        ctx = self._ctx.get()
        if ctx is not None:
            return _SpanScope(self._ctx, ctx, name, attrs)
        return _annotate(name, attrs)

    # -- the serving account (ISSUE 25) ---------------------------------
    @staticmethod
    def next_dispatch_seq() -> int:
        """Process-wide, so that hosts with several batchers still join
        a request to its dispatch by this number alone."""
        return next(_dispatch_seq)

    def record(self, kind: str, rec: tuple):
        """Append one account record (``DISPATCH_FIELDS`` or
        ``REQUEST_FIELDS`` order) to its bounded ring."""
        self._account[kind].append(rec)

    def recent(self, kind: str, n: Optional[int] = None) -> List[tuple]:
        """The newest ``n`` records of ``DISPATCH`` or ``REQUEST``
        (all that the ring holds by default), oldest first."""
        recs = list(self._account[kind])
        return recs if n is None else recs[max(0, len(recs) - int(n)):]

    def dispatch_record(self, seq: int) -> Optional[tuple]:
        """The dispatch a request's ``dispatch_seq`` names, while the
        ring still holds it."""
        for rec in reversed(self._account[DISPATCH]):
            if rec[0] == seq:
                return rec
            if rec[0] < seq:
                break
        return None

    @staticmethod
    def note_request(t_enqueue: float = 0.0, t_result: float = 0.0,
                     dispatch_seq: int = -1,
                     tenant: Optional[str] = None):
        """What the layers under the HTTP handler know of the request
        this thread is answering: the engine server opens the note (a
        cache hit or a refusal keeps ``dispatch_seq`` -1), the batcher's
        ``submit`` fills it in; :meth:`request_written` closes it."""
        _request_note.rec = (t_enqueue, t_result, dispatch_seq, tenant)

    @staticmethod
    def noted_dispatch_seq() -> int:
        rec = getattr(_request_note, "rec", None)
        return rec[2] if rec is not None else -1

    def request_written(self, t_start: float, t_written: float):
        """The HTTP layer, after a response's last byte: one request
        record if a query was answered on this thread, nothing for any
        other route."""
        rec = getattr(_request_note, "rec", None)
        if rec is not None:
            _request_note.rec = None
            self._account[REQUEST].append(
                (t_start, rec[0], rec[1], t_written, rec[2], rec[3]))

    # -- collector pauses on the profiler's clock ------------------------
    def _on_gc(self, phase: str, info: dict):
        if info.get("generation") != 2:
            return
        if phase == "start":
            cls = _annotation()
            if cls is not None:
                self._gc_ann = cls("pio.gc.gen2")
                self._gc_ann.__enter__()
        elif self._gc_ann is not None:
            ann, self._gc_ann = self._gc_ann, None
            ann.__exit__(None, None, None)

    def watch_gc(self, on: bool):
        """Each serving process puts its generation-2 collections into
        the profiler's trace as ``pio.gc.gen2`` from ``start()`` to
        ``stop()`` (counted: a host runs several engine servers). A
        collection holds the interpreter's lock on the thread that
        tripped it, so its start and stop come on one thread."""
        import gc
        with self._lock:
            self._gc_watchers += 1 if on else -1
            hooked = self._on_gc in gc.callbacks
            if self._gc_watchers > 0 and not hooked:
                gc.callbacks.append(self._on_gc)
            elif self._gc_watchers <= 0 and hooked:
                self._gc_watchers = 0
                gc.callbacks.remove(self._on_gc)

    def annotate(self, **attrs):
        """Attach attributes to the current span, if any."""
        ctx = self._ctx.get()
        if ctx is not None:
            ctx[1].attrs.update(attrs)

    # -- commit / ring -------------------------------------------------
    def _commit(self, t: Trace):
        with self._lock:
            ring = self._done.get(t.kind)
            if ring is None:
                ring = collections.deque(maxlen=self.per_kind_capacity)
                self._done[t.kind] = ring
            if len(ring) == ring.maxlen:   # evicting: drop its index
                # ... only if the index still points at the evicted
                # object: since ISSUE 13 an ADOPTED inbound id can
                # put two traces under one id in this process (a
                # co-located hop), and the older ring entry must not
                # unhook the newer trace from ?trace_id= lookup
                old = ring[0]
                if self._by_id.get(old.trace_id) is old:
                    self._by_id.pop(old.trace_id, None)
            ring.append(t)
            self._by_id[t.trace_id] = t

    # -- cross-trace causality ------------------------------------------
    def register_event(self, event_id: Optional[str],
                       trace_id: Optional[str]):
        if not event_id or not trace_id:
            return
        with self._lock:
            self._event_traces[str(event_id)] = trace_id
            while len(self._event_traces) > self._event_map_capacity:
                self._event_traces.popitem(last=False)

    def trace_id_for_event(self, event_id) -> Optional[str]:
        with self._lock:
            return self._event_traces.get(str(event_id))

    def get(self, trace_id: str) -> Optional[Trace]:
        """The committed Trace for ``trace_id``, or None once it has
        rotated out of its ring (O(1); slow-query waterfalls read the
        batch trace that answered a request this way)."""
        with self._lock:
            return self._by_id.get(trace_id)

    def link_completed(self, trace_id: str, other_trace_id: str):
        """Add a link onto an already-committed trace (the back-link
        from an event's ingest trace to the fold tick that absorbed
        it). O(1); no-op when the trace already left the ring."""
        with self._lock:
            t = self._by_id.get(trace_id)
            if t is not None:
                t.link(other_trace_id)

    # -- the /traces.json view -----------------------------------------
    def snapshot(self, limit: int = 50, kind: Optional[str] = None,
                 slowest: bool = False) -> List[dict]:
        with self._lock:
            if kind is not None:
                traces = list(self._done.get(kind, ()))
            else:
                traces = [t for ring in self._done.values()
                          for t in ring]
        if slowest:
            traces.sort(key=lambda t: t.duration_s or 0.0, reverse=True)
        else:
            traces.sort(key=lambda t: t.root.t_wall, reverse=True)
        return [t.to_dict() for t in traces[:max(0, int(limit))]]

    def related(self, trace_id: str, limit: int = 50) -> List[dict]:
        """The trace plus its causal neighborhood, for incident
        correlation (ISSUE 6 satellite): the trace itself, every
        committed trace it links, and every committed trace linking
        it — so one ``?trace_id=`` query walks an ingest event to the
        fold tick that absorbed it (or back) without client-side grep
        over whole rings. Every committed trace CARRYING the id is
        returned, not just the newest (an adopted inbound id can put
        a query trace and a feedback-ingest trace under one id in one
        process — ISSUE 13 — and the stitched waterfall needs both
        legs)."""
        with self._lock:
            target = self._by_id.get(trace_id)
            linked = set(target.links) if target is not None else set()
            out = [] if target is None else [target]
            for ring in self._done.values():
                for t in ring:
                    if t is target:
                        continue
                    if (t.trace_id == trace_id
                            or t.trace_id in linked
                            or trace_id in t.links):
                        out.append(t)
        out.sort(key=lambda t: t.root.t_wall, reverse=True)
        return [t.to_dict() for t in out[:max(0, int(limit))]]

    def clear(self):
        with self._lock:
            self._done.clear()
            self._by_id.clear()
            self._event_traces.clear()
        for ring in self._account.values():
            ring.clear()


# The process-wide tracer.
TRACER = Tracer()


def traces_response(params: dict):
    """Shared ``GET /traces.json`` handler body for every HTTP server:
    ``?n=``/``?limit=`` (default 50), ``?kind=`` filter,
    ``?sort=slowest``, and ``?trace_id=`` — which returns the named
    trace plus its linked neighborhood (ISSUE 6 satellite: correlating
    one incident no longer means dumping whole rings and grepping
    client-side). ``?event_ids=a,b,c`` (ISSUE 13) instead answers the
    event-id -> ingest-trace-id map from this process's bounded event
    registry — the hop a cross-process scheduler uses to link the fold
    tick back to ingest traces minted in the event server's process."""
    event_ids = params.get("event_ids") or params.get("eventIds")
    if event_ids:
        out = {}
        for eid in str(event_ids).split(",")[:1024]:
            eid = eid.strip()
            if not eid:
                continue
            tid = TRACER.trace_id_for_event(eid)
            if tid:
                out[eid] = tid
        return {"eventTraces": out}
    limit = int(params.get("n", params.get("limit", 50)))
    trace_id = params.get("trace_id") or params.get("traceId")
    if trace_id:
        return {"traces": TRACER.related(trace_id, limit=limit)}
    return {"traces": TRACER.snapshot(
        limit=limit, kind=params.get("kind"),
        slowest=params.get("sort") == "slowest")}
