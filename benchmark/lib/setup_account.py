"""The program's set-up account (predictionio_tpu/obs/trace.py: one record
per compile request and one per upload of a table or a plan, kept by the
process-wide TRACER), cut to a run's set-up: the records that the program's
own executables and upload sites wrote (a compile with no executable label is
the benchmark's or the reference's, and not set-up's), and in a serve cell
those that started before the window's first dispatch. A program that keeps
no such rings (the commit before they were added) reads None."""

from __future__ import annotations

from benchmark.lib import account

UNLABELED = "unlabeled"      # obs/costmon's label of a compile no scope claims


def _records(kind: str, fields_name: str) -> list[dict] | None:
    try:
        from predictionio_tpu.obs import TRACER
        from predictionio_tpu.obs import trace as program_trace
        fields = getattr(program_trace, fields_name)
        records = TRACER.recent(kind)
    except (ImportError, AttributeError, KeyError):
        return None
    return [dict(zip(fields, r)) for r in records]


def _cut(ctx: dict) -> float:
    """Where set-up ends for the reader: the window's first dispatch in a
    serve cell, nowhere in a train cell (its window compiles nothing)."""
    if not ctx["window"].get("dispatches"):
        return float("inf")
    recs = account.window_dispatches(ctx)
    return recs[0]["t_enqueue"] if recs else float("inf")


def compiles(ctx: dict) -> list[dict] | None:
    """The set-up's compile requests, as {field: value}."""
    recs = _records("setup.compile", "COMPILE_FIELDS")
    if recs is None:
        return None
    end = _cut(ctx)
    return [r for r in recs
            if r["executable"] != UNLABELED and r["t_start"] < end]


def uploads(ctx: dict) -> list[dict] | None:
    """The set-up's uploads, as {field: value}."""
    recs = _records("setup.upload", "UPLOAD_FIELDS")
    if recs is None:
        return None
    end = _cut(ctx)
    return [r for r in recs if r["t_start"] < end]


def union_seconds(spans) -> float:
    """Seconds covered by the union of (start, end) intervals."""
    covered, end = 0.0, float("-inf")
    for a, b in sorted(spans):
        if b > end:
            covered += b - max(a, end)
            end = b
    return covered
