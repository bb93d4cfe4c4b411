"""Tiny threaded HTTP server + router on the stdlib.

Plays the role of the reference's Spray/Akka HTTP layer (reference:
data/src/main/scala/io/prediction/data/api/EventServer.scala,
core/src/main/scala/io/prediction/workflow/CreateServer.scala) without
external dependencies: a ThreadingHTTPServer dispatching to route handlers.
Request-level concurrency comes from the thread pool; device work stays
serialized behind the algorithm's own jit calls (XLA queues per-device).
"""

from __future__ import annotations

import gzip
import json
import logging
import re
import threading
import time
import urllib.parse
from dataclasses import dataclass
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional, Tuple

from predictionio_tpu.obs.trace import TRACER

logger = logging.getLogger(__name__)


class Headers(dict):
    """Case-insensitive header mapping (RFC 9110 §5.1: field names are
    case-insensitive; a client sending ``authorization:`` must match a
    handler's ``.get("Authorization")``). Keys are stored lower-cased
    and every access path folds its probe key, so mutation and copying
    preserve the invariant."""

    def __init__(self, items=()):
        if hasattr(items, "items"):
            items = items.items()
        super().__init__((k.lower(), v) for k, v in items)

    def get(self, key, default=None):
        return super().get(key.lower(), default)

    def __getitem__(self, key):
        return super().__getitem__(key.lower())

    def __contains__(self, key):
        return super().__contains__(key.lower())

    def __setitem__(self, key, value):
        super().__setitem__(key.lower(), value)

    def __delitem__(self, key):
        super().__delitem__(key.lower())

    def pop(self, key, *default):
        return super().pop(key.lower(), *default)

    def setdefault(self, key, default=None):
        return super().setdefault(key.lower(), default)

    def update(self, items=(), **kw):
        if hasattr(items, "items"):
            items = items.items()
        for k, v in items:
            self[k] = v
        for k, v in kw.items():
            self[k] = v

    def copy(self):
        return Headers(self)


@dataclass
class Request:
    method: str
    path: str
    params: Dict[str, str]
    headers: Dict[str, str]
    body: bytes
    path_args: Tuple[str, ...] = ()

    def json(self) -> Any:
        if not self.body:
            return None
        return json.loads(self.body.decode("utf-8"))

    def form(self) -> Dict[str, str]:
        parsed = urllib.parse.parse_qs(self.body.decode("utf-8"),
                                       keep_blank_values=True)
        return {k: v[0] for k, v in parsed.items()}


@dataclass
class Response:
    status: int = 200
    body: Any = None           # dict/list -> JSON; str -> as-is
    content_type: str = "application/json; charset=UTF-8"
    # extra response headers (Retry-After on sheds, model-staleness on
    # degraded serving); None avoids a dict per ordinary response
    headers: Optional[Dict[str, str]] = None

    def payload(self) -> bytes:
        if self.body is None:
            return b""
        if isinstance(self.body, (bytes, bytearray)):
            return bytes(self.body)
        if isinstance(self.body, str):
            return self.body.encode("utf-8")
        return json.dumps(self.body).encode("utf-8")


Handler = Callable[[Request], Response]


def fetch_json(url: str, timeout: float = 3.0) -> Any:
    """GET a JSON endpoint, mapping any failure to {"error": str} —
    the polling pattern shared by `pio status --telemetry` and the
    dashboard's /telemetry view (an unreachable server is a row in the
    report, not an exception)."""
    import urllib.request
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return json.loads(resp.read())
    except Exception as e:
        return {"error": str(e)}


def fetch_text(url: str, timeout: float = 3.0) -> Optional[str]:
    """GET a text endpoint (a /metrics scrape); None on any failure or
    non-200 — the fleet federation treats that as a down member row,
    not an exception (obs/fleet.py)."""
    import urllib.request
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            if resp.status != 200:
                return None
            return resp.read().decode("utf-8", "replace")
    except Exception:
        return None


def _accepts_gzip(value: str) -> bool:
    """True when an Accept-Encoding value allows gzip — token match, not
    substring (``gzip;q=0`` is an explicit refusal)."""
    for part in value.split(","):
        bits = part.strip().split(";")
        if bits[0].strip().lower() != "gzip":
            continue
        for b in bits[1:]:
            b = b.strip().lower()
            if b.startswith("q="):
                try:
                    if float(b[2:]) == 0.0:
                        return False
                except ValueError:
                    pass
        return True
    return False


class Router:
    """Method+path-regex routing. Patterns use <name> wildcards that match
    one path segment and arrive as positional path_args."""

    def __init__(self):
        self.routes: List[Tuple[str, re.Pattern, Handler]] = []

    def add(self, method: str, pattern: str, handler: Handler):
        regex = re.compile(
            "^" + re.sub(r"<[^>]+>", r"([^/]+)", pattern) + "$")
        self.routes.append((method.upper(), regex, handler))

    def dispatch(self, req: Request) -> Response:
        matched_path = False
        for method, regex, handler in self.routes:
            m = regex.match(req.path)
            if m:
                matched_path = True
                if method == req.method:
                    req.path_args = m.groups()
                    return handler(req)
        if matched_path:
            return Response(405, {"message": "method not allowed"})
        return Response(404, {"message": "not found"})


class _BurstTolerantServer(ThreadingHTTPServer):
    # socketserver's default accept backlog is 5: a burst of as many
    # simultaneous connects as one micro-batch holds (16) overflows it
    # and some clients see "connection reset by peer"
    request_queue_size = 128


class HttpServer:
    def __init__(self, router: Router, host: str = "0.0.0.0",
                 port: int = 8000):
        self.router = router
        self.host = host
        self.port = port
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        # invoked with this server once the socket is bound (port
        # resolved) but BEFORE serve_forever — the only window where a
        # foreground server can publish its resolved port (the fleet
        # member registration, ISSUE 13). Must not raise.
        self.on_bound: Optional[Callable[["HttpServer"], None]] = None
        # latched by stop(): a stop that lands BEFORE the socket exists
        # (e.g. SIGTERM during the bind-retry window) must still win —
        # start() checks it after binding and tears down immediately
        # instead of serving as a zombie
        self._stop_requested = False
        # True once the current start() reached serving; lets stop()
        # tell "idempotent cleanup after a completed lifecycle" (no-op)
        # apart from "stop racing a bind in progress" (latch)
        self._has_served = False

    def _make_handler(self):
        router = self.router

        class _Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            # one buffered write + TCP_NODELAY: without these, the
            # header/body write split interacts with Nagle + delayed ACK
            # for ~40-200 ms per response
            wbufsize = 1 << 16
            disable_nagle_algorithm = True

            def _handle(self):
                # pio.http.request: request parsed -> last byte written;
                # a query answered on this thread leaves its record in
                # the serving account (obs/trace REQUEST_FIELDS)
                t_start = time.perf_counter()
                with TRACER.region("http.request"):
                    self._answer()
                TRACER.request_written(t_start, time.perf_counter())

            def _answer(self):
                parsed = urllib.parse.urlsplit(self.path)
                # keep_blank_values: `targetEntityType=` (empty string)
                # is meaningful — the event API maps it to "target
                # absent" — and must not be silently dropped
                params = {k: v[0] for k, v in
                          urllib.parse.parse_qs(
                              parsed.query,
                              keep_blank_values=True).items()}
                length = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(length) if length else b""
                req = Request(method=self.command, path=parsed.path,
                              params=params,
                              headers=Headers(self.headers.items()),
                              body=body)
                try:
                    resp = router.dispatch(req)
                except ValueError as e:
                    resp = Response(400, {"message": str(e)})
                except KeyError as e:
                    # missing required field in a JSON body
                    resp = Response(400, {"message":
                                          f"missing field {e}"})
                except Exception as e:
                    # exceptions that know their HTTP status (e.g. mesh
                    # coordinator poisoned -> 503, a shed query, an
                    # open circuit breaker) pass it through; a
                    # retry_after_s attribute becomes the Retry-After
                    # header so well-behaved clients back off for the
                    # server-known recovery window
                    status = getattr(e, "http_status", None)
                    if status:
                        logger.error("handler error (%d): %s", status, e)
                        resp = Response(int(status), {"message": str(e)})
                        ra = getattr(e, "retry_after_s", None)
                        if ra is not None:
                            resp.headers = {
                                "Retry-After":
                                    str(max(1, int(float(ra) + 0.5)))}
                    else:
                        logger.exception("handler error")
                        resp = Response(500, {"message": str(e)})
                payload = resp.payload()
                self.send_response(resp.status)
                self.send_header("Content-Type", resp.content_type)
                for hk, hv in (resp.headers or {}).items():
                    self.send_header(hk, hv)
                # transparent gzip for clients that ask: bulk JSON (the
                # columnar training reads) compresses ~10x, which is the
                # difference on a thin link; tiny responses skip the
                # CPU cost. Header names are case-insensitive — use the
                # Message object, not the plain dict.
                accept = self.headers.get("Accept-Encoding") or ""
                if (_accepts_gzip(accept) and len(payload) >= 1024):
                    payload = gzip.compress(payload, compresslevel=1)
                    self.send_header("Content-Encoding", "gzip")
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

            do_GET = do_POST = do_DELETE = do_PUT = _handle

            def log_message(self, fmt, *args):
                logger.debug("%s %s", self.address_string(), fmt % args)

        return _Handler

    def start(self, background: bool = True, bind_retries: int = 3,
              retry_delay: float = 1.0):
        # bind retry x3 mirrors the reference MasterActor
        # (CreateServer.scala:363-373)
        self._has_served = False   # new lifecycle attempt begins
        last_err = None
        for attempt in range(bind_retries):
            try:
                self._httpd = _BurstTolerantServer(
                    (self.host, self.port), self._make_handler())
                break
            except OSError as e:
                last_err = e
                logger.warning("bind %s:%d failed (%s), retry %d/%d",
                               self.host, self.port, e, attempt + 1,
                               bind_retries)
                time.sleep(retry_delay)
        else:
            raise last_err
        self.port = self._httpd.server_address[1]  # resolve port 0
        if self._stop_requested:   # stop() raced the bind — honor it
            self._httpd.server_close()
            self._httpd = None
            self._stop_requested = False  # consumed; start() works again
            return
        self._has_served = True
        if self.on_bound is not None:
            try:
                self.on_bound(self)
            except Exception:
                logger.exception("on_bound hook failed")
        if background:
            self._thread = threading.Thread(
                target=self._httpd.serve_forever, daemon=True)
            self._thread.start()
        else:
            self._httpd.serve_forever()
        return self

    def stop(self):
        if self._httpd is None and self._has_served:
            # idempotent cleanup after a completed lifecycle (a second
            # stop(), a try/finally sweep): nothing to do, and latching
            # here would make the NEXT start() bind-then-die
            return
        self._stop_requested = True
        if self._httpd:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
            # the stop acted on a live server, so the latch is consumed:
            # an HttpServer is restartable (round-4 advisor); the latch
            # persists only when stop() fired before/at bind time, where
            # the pending start() must still honor it
            self._stop_requested = False
