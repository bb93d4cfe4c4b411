"""Always-on sampling profiler + jax.profiler trace control.

ISSUE 11 tentpole piece 4. The attribution counters say WHICH
executable owns the device; when the time is going somewhere else —
JSON parsing, a lock convoy, a storage read — an operator needs to see
the Python stacks that were actually running during the spike, without
having restarted anything with a profiler attached. Two tools, one
module:

- ``SamplingProfiler`` — a low-Hz (default ``PIO_PROFILER_HZ`` = 19)
  folded-stack sampler over every live thread via
  ``sys._current_frames()``. Cheap enough to leave on for the process
  lifetime (one frame walk per thread per tick; the sampler's own
  cumulative wall is self-accounted in ``spent_s`` and exported so the
  bench can price it — ``profiler_overhead_ms``). Stacks aggregate as
  ``leaf-last "file:func;file:func" -> count`` folded lines (the
  flamegraph input format), bounded to ``max_stacks`` distinct stacks
  with an ``(other)`` overflow bucket. 19 Hz is deliberately prime-ish:
  a sampler at a round frequency phase-locks with periodic loops and
  sees only their sleeps.
- ``JaxTraceController`` — the idempotent ``/profile.json``
  start/stop state machine for ``jax.profiler`` device traces, moved
  here from ``serving/server.py`` (ISSUE 11 satellite) so the event
  server exposes the same endpoint; semantics unchanged from ISSUE 2
  (second start reports the running trace, stop without a trace
  reports idle, every response carries state).

``profile_response`` is the shared HTTP handler body both servers
mount at ``/profile.json``: POST ``{"action": "start"|"stop"}``
toggles the jax trace; ``action=report`` (GET or POST) returns the
sampler's report — the ``pio profile top`` surface. An SLO-breach
incident bundle embeds the same report via the ``profiler`` provider
(obs/incidents.py), so every serve-p99 postmortem carries the stacks
that were running.

``PIO_PROFILER=off`` disables the sampler (the jax-trace toggle stays
available); ``PIO_PROFILER_HZ`` tunes the rate.
"""

from __future__ import annotations

import atexit
import logging
import os
import sys
import threading
import time
from typing import Dict, List, Optional

logger = logging.getLogger(__name__)

_FOLD_SKIP_PREFIXES = ("<",)   # <string>, <frozen importlib...>


def profiler_enabled() -> bool:
    return os.environ.get("PIO_PROFILER", "").strip().lower() not in (
        "off", "0", "false", "no")


def _hz_default() -> float:
    try:
        hz = float(os.environ.get("PIO_PROFILER_HZ", 19.0))
    except (TypeError, ValueError):
        hz = 19.0
    return min(max(hz, 0.1), 250.0)


def _fold(frame) -> str:
    """One thread's stack as a folded line, root first, leaf last —
    ``file:func;file:func``. File paths compress to their basename
    (the repo has no duplicate module basenames worth a full path)."""
    parts: List[str] = []
    while frame is not None:
        code = frame.f_code
        fname = code.co_filename
        if not fname.startswith(_FOLD_SKIP_PREFIXES):
            fname = fname.rsplit("/", 1)[-1]
        parts.append(f"{fname}:{code.co_name}")
        frame = frame.f_back
    parts.reverse()
    return ";".join(parts)


class SamplingProfiler:
    """Process-wide folded-stack sampler. ``start()`` is idempotent;
    the sampling thread is a daemon and excludes itself from samples.
    All public methods are thread-safe."""

    def __init__(self, hz: Optional[float] = None,
                 max_stacks: int = 1024):
        self.hz = hz if hz is not None else _hz_default()
        self.max_stacks = max_stacks
        self._lock = threading.Lock()
        self._stacks: Dict[str, int] = {}
        self._other = 0              # samples past the max_stacks bound
        self.samples = 0             # thread-stacks recorded
        self.ticks = 0               # sampling rounds completed
        self.spent_s = 0.0           # the sampler's own cumulative wall
        self.started_at: Optional[float] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._registered = False
        self._stops_at_exit = False
        self._register_metrics()

    def _register_metrics(self):
        if self._registered:
            return
        self._registered = True
        from predictionio_tpu.obs.metrics import get_registry
        reg = get_registry()
        # eager, first-registrant-wins (the FLIGHT/incidents pattern):
        # a quiet server scrapes 0, not absent
        reg.counter_func(
            "pio_profiler_samples_total",
            "Thread-stack samples recorded by the always-on sampling "
            "profiler", lambda: self.samples)
        reg.counter_func(
            "pio_profiler_spent_seconds_total",
            "Cumulative wall time the sampling profiler spent walking "
            "stacks (its own overhead)", lambda: self.spent_s)
        reg.gauge_func(
            "pio_profiler_running",
            "1 while the sampling profiler thread is alive",
            lambda: int(self.running))

    # -- lifecycle -----------------------------------------------------
    @property
    def running(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    def start(self) -> bool:
        """Idempotent; returns True when the sampler is (now) running.
        Respects ``PIO_PROFILER=off``."""
        if not profiler_enabled():
            return False
        with self._lock:
            if self.running:
                return True
            self._stop.clear()
            if self.started_at is None:
                self.started_at = time.time()
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="pio-profiler")
            self._thread.start()
            if not self._stops_at_exit:
                # the sampler holds other threads' frames for a tick, so
                # it is where their locals die when those threads have
                # moved on: a jaxlib object freed here releases the GIL,
                # and a daemon thread that takes the GIL back while the
                # interpreter finalises is unwound through jaxlib's C++
                # and aborts the process (exit 134 after a clean run;
                # seen in one of six runs of a server with live filters,
                # whose pool threads end at exit: ISSUE 31). atexit runs
                # before finalisation begins: the thread is gone by then
                atexit.register(self.stop)
                self._stops_at_exit = True
        return True

    def stop(self, join_timeout_s: float = 2.0):
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=join_timeout_s)
        self._thread = None

    # -- sampling ------------------------------------------------------
    def _loop(self):
        interval = 1.0 / self.hz
        me = threading.get_ident()
        while not self._stop.wait(interval):
            t0 = time.perf_counter()
            try:
                frames = sys._current_frames()
                folded = [_fold(f) for tid, f in frames.items()
                          if tid != me]
            except Exception:
                continue
            with self._lock:
                self.ticks += 1
                for line in folded:
                    self.samples += 1
                    cur = self._stacks.get(line)
                    if cur is not None:
                        self._stacks[line] = cur + 1
                    elif len(self._stacks) < self.max_stacks:
                        self._stacks[line] = 1
                    else:
                        self._other += 1
                self.spent_s += time.perf_counter() - t0

    def reset(self):
        with self._lock:
            self._stacks.clear()
            self._other = 0
            self.samples = 0
            self.ticks = 0
            self.started_at = time.time() if self.running else None

    # -- reads ---------------------------------------------------------
    def report(self, top: int = 30) -> dict:
        """The operator view (``/profile.json?action=report``,
        ``pio profile top``, incident bundles): top folded stacks by
        sample count with percentages, plus the sampler's own
        self-accounting."""
        with self._lock:
            stacks = sorted(self._stacks.items(), key=lambda kv: -kv[1])
            samples, ticks = self.samples, self.ticks
            other, spent = self._other, self.spent_s
            started = self.started_at
        wall_s = (time.time() - started) if started else 0.0
        out = {
            "running": self.running,
            "hz": self.hz,
            "samples": samples,
            "ticks": ticks,
            "distinctStacks": len(stacks),
            "otherSamples": other,
            "wallS": round(wall_s, 3),
            "spentS": round(spent, 6),
            # the sampler's own cost as a fraction of the window it
            # covered — what profiler_overhead_ms prices per-tick
            "overheadPct": (round(100.0 * spent / wall_s, 4)
                            if wall_s > 0 else 0.0),
            "topStacks": [
                {"stack": line, "count": n,
                 "pct": round(100.0 * n / samples, 2) if samples else 0}
                for line, n in stacks[:max(0, int(top))]],
        }
        return out

    def report_state(self) -> dict:
        """Compact provider view for incident bundles (top 15)."""
        return self.report(top=15)


class JaxTraceController:
    """The idempotent jax.profiler device-trace toggle — the ISSUE 2
    ``/profile.json`` semantics, verbatim, now shared by both servers:
    a second start reports the running trace instead of 500ing, a stop
    without a trace reports idle, and every response carries state."""

    def __init__(self):
        self._lock = threading.Lock()
        self._dir: Optional[str] = None

    @property
    def tracing(self) -> bool:
        return self._dir is not None

    def start(self, trace_dir: str) -> dict:
        import jax
        with self._lock:
            if self._dir is not None:
                return {"message": "already tracing",
                        "tracing": True, "dir": self._dir}
            try:
                jax.profiler.start_trace(trace_dir)
            except RuntimeError as e:
                # jax-level tracer already running (started outside
                # this endpoint): adopt it so a later stop can
                # actually stop it, and report instead of 500ing
                self._dir = trace_dir
                return {"message": f"profiler already active: {e}",
                        "tracing": True, "dir": trace_dir}
            self._dir = trace_dir
        return {"message": "tracing", "tracing": True,
                "dir": trace_dir}

    def stop(self) -> dict:
        import jax
        with self._lock:
            if self._dir is None:
                return {"message": "not tracing", "tracing": False}
            trace_dir, self._dir = self._dir, None
            try:
                jax.profiler.stop_trace()
            except RuntimeError as e:
                # adopted/raced trace already gone: still idle
                return {"message": f"trace already stopped: {e}",
                        "tracing": False, "dir": trace_dir}
        return {"message": "trace stopped", "tracing": False,
                "dir": trace_dir}


# Process-wide singletons (module import = process singleton, the
# FLIGHT/INCIDENTS pattern).
PROFILER = SamplingProfiler()
JAX_TRACE = JaxTraceController()


def get_profiler() -> SamplingProfiler:
    return PROFILER


def ensure_started() -> bool:
    """Both servers call this at start(): the sampler is ALWAYS ON for
    server processes unless ``PIO_PROFILER=off``."""
    return PROFILER.start()


def profile_response(action: Optional[str],
                     body: Optional[dict] = None):
    """Shared ``/profile.json`` handler body for both HTTP servers.
    Returns ``(http_status, response_dict)``.

    - ``start``/``stop`` — the jax.profiler device-trace toggle
      (ISSUE 2 idempotent semantics);
    - ``report`` — the sampling profiler's folded-stack report
      (``?top=`` bounds the stack list).
    """
    body = body or {}
    if action == "start":
        return 200, JAX_TRACE.start(body.get("dir", "/tmp/pio_trace"))
    if action == "stop":
        return 200, JAX_TRACE.stop()
    if action == "report":
        try:
            top = int(body.get("top", 30))
        except (TypeError, ValueError):
            top = 30
        out = PROFILER.report(top=top)
        out["message"] = "profiler report"
        out["tracing"] = JAX_TRACE.tracing
        return 200, out
    return 400, {"message": "action must be start|stop|report",
                 "tracing": JAX_TRACE.tracing}


def profile_response_from_request(req):
    """The shared Request-to-response body both servers' /profile.json
    handlers delegate to: action from the JSON body or query params
    (GET report carries no body), with the ``top`` query param
    promoted for reports. Returns ``(http_status, response_dict)``."""
    d = req.json() or {}
    action = d.get("action") or req.params.get("action")
    if action == "report" and "top" not in d and "top" in req.params:
        d = dict(d, top=req.params["top"])
    return profile_response(action, d)
