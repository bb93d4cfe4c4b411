"""From a profiler trace to device busy and idle time, time per device
operation and per jitted module, and idle gaps named by what the host was
doing. `load` turns the profiler's file into plain lists; `reduce` works on
those lists alone, so it is checked on a small recorded trace."""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
GAP_FLOOR_S = 20e-6
EXAMPLE_CHARS = 120         # an operation's name is its whole HLO line


def start(directory: str) -> None:
    """Trace device operations and the host's named spans, without the
    Python function tracer (it would drown a server's threads)."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=options)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def load(directory: str) -> list[dict]:
    """The newest trace under `directory` as
    [{"name", "lines": [{"name", "events": [(name, start_s, duration_s)]}]}].
    """
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no trace under {directory}")
    planes = []
    for plane in ProfileData.from_file(files[-1]).planes:
        lines = [{"name": line.name,
                  "events": [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                             for e in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def sample(planes: list[dict], per_line: int = 60,
           name_chars: int = 200) -> list[dict]:
    """The first events of every line of the device and host planes, their
    names cut short: a recorded trace small enough to keep with the tests."""
    return [{"name": p["name"],
             "lines": [{"name": ln["name"],
                        "events": [(n[:name_chars], s, d) for n, s, d in
                                   sorted(ln["events"],
                                          key=lambda e: e[1])[:per_line]]}
                       for ln in p["lines"] if ln["events"]]}
            for p in planes
            if p["name"].startswith((DEVICE_PLANE, HOST_PLANE))]


def _op_kind(name: str) -> str:
    """"%closed_call.349 = ... custom_call_target="tpu_custom_call"" ->
    "closed_call tpu_custom_call": one program names each instance of an
    operation apart, and a sweep has hundreds."""
    kind = name.split(" = ")[0].lstrip("%").split(".")[0]
    if 'custom_call_target="' in name:
        kind += " " + name.split('custom_call_target="')[1].split('"')[0]
    return kind


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def _host_spans(planes: list[dict]) -> list[tuple[float, float, str]]:
    spans = []
    for plane in planes:
        if not plane["name"].startswith(HOST_PLANE):
            continue
        for line in plane["lines"]:
            spans += [(s, s + d, name) for name, s, d in line["events"]
                      if d > 0]
    return sorted(spans)


def _name_gap(spans, starts, lo: float, hi: float) -> str:
    """The innermost host span over the gap's middle."""
    mid = 0.5 * (lo + hi)
    best, best_len = "no host span", float("inf")
    i = bisect.bisect_right(starts, mid)
    # spans are sorted by start: walk back over those that could cover mid
    for s, e, name in reversed(spans[max(0, i - 4096):i]):
        if e >= mid and e - s < best_len:
            best, best_len = name, e - s
    return best


def reduce(planes: list[dict], window_s: float | None = None) -> dict:
    """busy_s: the union of the intervals in which an operation ran on a
    device, averaged over the device planes; window_s: the traced window,
    which is the host's wall of the slice as given, or the time from the
    first to the last device event where that is longer (requests sent in
    the slice's last moments run on the device after its offered time is up,
    and the trace holds them: counted as busy, they count as time, so busy_s
    never passes window_s); device_ops and modules: seconds by name, summed
    over devices; idle_gaps: idle seconds by what the host was doing, from
    the first device's gaps."""
    devices = [p for p in planes if p["name"].startswith(DEVICE_PLANE)]
    ops_by_name: dict[str, float] = defaultdict(float)
    longest: dict[str, tuple[str, float]] = {}
    modules: dict[str, list[float]] = defaultdict(lambda: [0, 0.0])
    busy = []
    first, last = float("inf"), float("-inf")
    gaps: list[tuple[float, float]] = []
    for n, plane in enumerate(devices):
        intervals = []
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                for name, s, d in line["events"]:
                    intervals.append((s, s + d))
                    # a loop is on the line beside the operations of its
                    # body: naming both would count the body twice
                    if " while(" not in name:
                        kind = _op_kind(name)
                        ops_by_name[kind] += d
                        if d > longest.get(kind, ("", 0.0))[1]:
                            longest[kind] = (name, d)
            elif line["name"] == MODULES_LINE:
                for name, s, d in line["events"]:
                    # "jit_fn(1234567)": the number is the program's id
                    key = name.split("(")[0]
                    modules[key][0] += 1
                    modules[key][1] += d
        merged = _union(intervals)
        busy.append(sum(hi - lo for lo, hi in merged))
        if merged:
            first, last = min(first, merged[0][0]), max(last, merged[-1][1])
        if n == 0:
            gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])
                    if b[0] - a[1] >= GAP_FLOOR_S]
    if not devices or not busy or last <= first:
        return {"busy_s": 0.0, "window_s": window_s or 0.0,
                "device_ops": [], "modules": {}, "idle_gaps": []}
    spans = _host_spans(planes)
    starts = [s for s, _, _ in spans]
    by_host: dict[str, float] = defaultdict(float)
    for lo, hi in gaps:
        by_host[_name_gap(spans, starts, lo, hi)] += hi - lo

    def top(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"busy_s": sum(busy) / len(busy),
            "window_s": max(window_s or 0.0, last - first),
            "device_ops": [[f"{kind}: {longest[kind][0][:EXAMPLE_CHARS]}", v]
                           for kind, v in top(ops_by_name)],
            "modules": {k: {"count": c, "seconds": s}
                        for k, (c, s) in modules.items()},
            "idle_gaps": top(by_host)}
