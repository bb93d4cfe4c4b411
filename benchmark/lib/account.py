"""The program's serving account (predictionio_tpu/obs/trace.py: one record
per dispatch and per request, kept by the process-wide TRACER and so still
there after the server has stopped), cut to a run's window: the window is the
last traffic the server saw, so its records are the newest ones. A program
that keeps no such rings (the commit before they were added) reads None, and
so does a ring whose newest records span more than the slice."""

from __future__ import annotations

import math

SLACK_S = 0.5       # the slice's last requests are answered after its end

# a dispatch's and a request's stamps, cut into consecutive parts: (name,
# later field, earlier field); each list telescopes to its last - first
DISPATCH_PARTS = (("queue", "t_dequeue", "t_enqueue"),
                  ("form", "t_closed", "t_dequeue"),
                  ("gate", "t_gate", "t_closed"),
                  ("begin", "t_begin", "t_gate"),
                  ("turnaround", "t_ready", "t_begin"),
                  ("post", "t_done", "t_ready"))
REQUEST_PARTS = (("before_enqueue", "t_enqueue", "t_start"),
                 ("enqueue_to_result", "t_result", "t_enqueue"),
                 ("result_to_written", "t_written", "t_result"))


def _newest(kind: str, fields_name: str, n: int) -> list[dict] | None:
    try:
        from predictionio_tpu.obs import TRACER
        from predictionio_tpu.obs import trace as program_trace
        fields = getattr(program_trace, fields_name)
        records = TRACER.recent(kind, int(n))
    except (ImportError, AttributeError, KeyError):
        return None
    if not n or len(records) < int(n):
        return None
    return [dict(zip(fields, r)) for r in records]


def window_dispatches(ctx: dict) -> list[dict] | None:
    """The window's dispatches, oldest first, as {field: value}."""
    window = ctx["window"]
    recs = _newest("serve.dispatch", "DISPATCH_FIELDS",
                   window.get("dispatches") or 0)
    if not recs or (recs[-1]["t_done"] - recs[0]["t_enqueue"]
                    > window["wall_s"] + SLACK_S):
        return None
    return recs


def window_requests(ctx: dict) -> list[dict] | None:
    """The window's requests, in the order their last byte was written."""
    window = ctx["window"]
    recs = _newest("serve.request", "REQUEST_FIELDS",
                   window.get("attempted") or 0)
    if not recs or (recs[-1]["t_written"] - min(r["t_start"] for r in recs)
                    > window["wall_s"] + SLACK_S):
        return None
    return recs


def mean_ms(recs: list[dict] | None, later: str, earlier: str
            ) -> float | None:
    """Mean of later - earlier over the records, in milliseconds."""
    if not recs:
        return None
    return 1e3 * sum(r[later] - r[earlier] for r in recs) / len(recs)


def percentile(values: list[float], q: float) -> float:
    """The smallest value with at least q% of all at or under it (the
    jobs' own definition of a latency percentile)."""
    values = sorted(values)
    return values[max(0, math.ceil(q / 100.0 * len(values)) - 1)]


def server_ms_p50(ctx: dict) -> float | None:
    """Median wall of the window's requests inside the server: last byte
    written - `pio.http.request` start."""
    recs = window_requests(ctx)
    if not recs:
        return None
    return 1e3 * percentile([r["t_written"] - r["t_start"] for r in recs],
                            50)


def device_ms_per_dispatch(ctx: dict) -> float | None:
    """Device milliseconds per run of the batched top-k executables in the
    traced slice (the jitted modules whose name holds `users_topk`)."""
    calls = seconds = 0.0
    for name, m in ctx["trace"]["modules"].items():
        if "users_topk" in name:
            calls += m["count"]
            seconds += m["seconds"]
    return 1e3 * seconds / calls if calls and seconds else None
