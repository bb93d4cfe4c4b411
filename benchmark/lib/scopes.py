"""From a profiler trace to device seconds by the program's own stage
names. The program wraps its device stages in `jax.named_scope("pio.…")` and
its host work in `jax.profiler.TraceAnnotation("pio.…")` (obs/trace.py), so a
trace says which stage an operation belongs to and what the host was doing in
a gap, in names a refactor of the kernels does not change.

A device event's scope is in its `tf_op` stat (XLA's op_name: the path of
scopes the operation was traced under), which the event's *metadata* holds
and `jax.profiler.ProfileData` does not show: `load` therefore reads the
profiler's file itself, as far as it needs (a protobuf is fields of
(number, type, bytes); no schema library). `reduce` works on plain lists, and
is checked on a small recorded trace.

An operation the compiler adds or moves (a layout `copy` of a parameter, a
`pad`) carries no scope of its own: it is counted under the stage of the
operation that consumes it, found by the operand names in the consumer's HLO
line, and the seconds so counted are reported apart (`inherited`)."""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

from benchmark.lib.trace import (DEVICE_PLANE, GAP_FLOOR_S, HOST_PLANE,
                                 MODULES_LINE, OPS_LINE, _op_kind, _union)

SCOPE = re.compile(r"pio\.[A-Za-z0-9_.]+")
PALLAS = re.compile(r"pio_[a-z]+_(primal|dual)_b\d+_n\d+")
_OPERAND = re.compile(r"%([A-Za-z0-9_.\-]+)")
NO_SPAN = "no pio span"
# spans of the request threads: hundreds are open at any moment, so they
# say nothing about why the device idles
WAITERS = ("pio.http.request", "pio.query", "pio.batch_wait")


# -- the profiler's file ------------------------------------------------

def _varint(b, i):
    x = s = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << s
        if c < 0x80:
            return x, i
        s += 7


def _fields(b):
    """(field number, value) of one message: an int for a varint, the bytes
    for a length-delimited or fixed field."""
    i, n = 0, len(b)
    b = memoryview(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v = b[i:i + ln]
            i += ln
        else:
            ln = 8 if wire == 1 else 4
            v = b[i:i + ln]
            i += ln
        yield key >> 3, v


def _map_value(entry):
    for f, v in _fields(entry):
        if f == 2:
            return v
    return b""


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _plane(b) -> dict:
    """One XPlane: name 2, lines 3, event_metadata 4, stat_metadata 5."""
    name, lines, event_md, stat_names = "", [], {}, {}
    for f, v in _fields(b):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 5:
            d = dict(_fields(_map_value(v)))
            stat_names[d.get(1, 0)] = _text(d.get(2, b""))
        elif f == 4:
            event_md[len(event_md)] = _map_value(v)
    tf_op = {i for i, n in stat_names.items() if n == "tf_op"}
    metadata = {}
    for raw in event_md.values():
        # XEventMetadata: id 1, name 2, stats 5; XStat: metadata_id 1,
        # str_value 5, bytes_value 6, ref_value 7
        md_id, md_name, op_name = 0, "", ""
        for f, v in _fields(raw):
            if f == 1:
                md_id = v
            elif f == 2:
                md_name = _text(v)
            elif f == 5 and tf_op:
                st = dict(_fields(v))
                if st.get(1) in tf_op:
                    op_name = (_text(st[5]) if 5 in st else
                               _text(st[6]) if 6 in st else
                               stat_names.get(st.get(7), ""))
        metadata[md_id] = (md_name, op_name)
    out = []
    for raw in lines:
        # XLine: name 2, timestamp_ns 3, events 4; XEvent: metadata_id 1,
        # offset_ps 2, duration_ps 3
        line_name, t0_ns, events = "", 0, []
        for f, v in _fields(raw):
            if f == 2:
                line_name = _text(v)
            elif f == 3:
                t0_ns = v
            elif f == 4:
                e = dict(_fields(v))
                md_name, op_name = metadata.get(e.get(1, 0), ("", ""))
                events.append([md_name, e.get(2, 0), e.get(3, 0), op_name])
        for e in events:
            e[1] = (t0_ns + e[1] * 1e-3) * 1e-9
            e[2] = e[2] * 1e-12
        out.append({"name": line_name,
                    "events": [tuple(e) for e in events]})
    return {"name": name, "lines": out}


def load(directory: str) -> list[dict]:
    """The newest trace under `directory`, its device and host planes as
    [{"name", "lines": [{"name", "events": [(name, start_s, duration_s,
    op_name)]}]}]: what lib/trace.load gives, with each device event's
    op_name (its path of scopes; "" on the host's lines) kept."""
    files = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no trace under {directory}")
    with open(files[-1], "rb") as f:
        raw = f.read()
    planes = [_plane(v) for f, v in _fields(raw) if f == 1]
    return [p for p in planes
            if p["name"].startswith((DEVICE_PLANE, HOST_PLANE))]


def sample(planes: list[dict], runs: int = 3, per_line: int = 16,
           name_chars: int = 400) -> list[dict]:
    """A recorded trace small enough to keep with the tests: the device's
    lines up to the end of its first `runs` module runs (whole programs: an
    operation stays beside the one that consumes it), and of each host line
    the program's own spans that start before then, and a few others."""
    ends = sorted(s + d for p in planes if p["name"].startswith(DEVICE_PLANE)
                  for ln in p["lines"] if ln["name"] == MODULES_LINE
                  for _n, s, d, _o in ln["events"])
    until = ends[min(runs, len(ends)) - 1] if ends else float("inf")
    out = []
    for p in planes:
        lines = []
        for ln in p["lines"]:
            events = sorted((e for e in ln["events"] if e[1] <= until),
                            key=lambda e: e[1])
            if p["name"].startswith(HOST_PLANE):
                mine = [e for e in events if e[0].startswith("pio.")]
                events = (mine[-per_line:]
                          + [e for e in events
                             if not e[0].startswith("pio.")][-per_line // 4:])
            else:
                events = [e for e in events if e[1] + e[2] <= until]
            if events:
                lines.append({"name": ln["name"],
                              "events": [(n[:name_chars], s, d, op)
                                         for n, s, d, op in events]})
        out.append({"name": p["name"], "lines": lines})
    return out


# -- the reduction --------------------------------------------------------

def scope_of(op_name: str) -> str | None:
    """The innermost `pio.*` scope on an operation's path."""
    found = SCOPE.findall(op_name)
    return found[-1].rstrip(".") if found else None


def _instruction(name: str) -> str:
    """"%fusion.3 = bf16[...] fusion(...)" -> "fusion.3"."""
    return name.split(" = ")[0].lstrip("%").strip()


def _consumers(events) -> dict[str, str]:
    """{instruction: the first scoped stage that reads it}, from the
    operands named in each scoped operation's HLO line, and on through the
    unscoped operations between (a copy of a copy)."""
    scoped, reads = {}, {}
    for name, _s, _d, op_name in events:
        me = _instruction(name)
        if me in reads:
            continue
        _, _, tail = name.partition(" = ")
        reads[me] = [o for o in _OPERAND.findall(tail) if o != me]
        sc = scope_of(op_name)
        if sc:
            scoped[me] = sc
    stage: dict[str, str] = {}
    for _hop in range(4):
        grew = False
        for me, operands in reads.items():
            sc = scoped.get(me) or stage.get(me)
            if not sc:
                continue
            for o in operands:
                if o not in scoped and o not in stage:
                    stage[o] = sc
                    grew = True
        if not grew:
            break
    return stage


def reduce(planes: list[dict]) -> dict:
    """Seconds of the first device's operations by stage. `by_scope`: under
    a `pio.*` scope of their own (a fusion carries its root's); `inherited`:
    unscoped, counted under the stage that consumes them in the same
    jitted module (found by the line of module runs); `unscoped`: the
    rest, by kind of operation; `op_s`: all of them (a loop's own event is
    left out: its body's operations are on the line beside it), `busy_s`
    the union of their intervals; `pallas`: the named Pallas solves, by
    primal or dual and by kernel; `host_spans`: the program's own host
    spans, seconds and count by name; `idle_by_span`: the device's idle
    gaps by the innermost `pio.*` span over each gap's middle (the request
    threads' own spans, WAITERS, left out)."""
    devices = [p for p in planes if p["name"].startswith(DEVICE_PLANE)]
    by_scope: dict[str, float] = defaultdict(float)
    inherited: dict[str, float] = defaultdict(float)
    unscoped: dict[str, float] = defaultdict(float)
    pallas: dict[str, float] = defaultdict(float)
    kernels: dict[str, float] = defaultdict(float)
    op_s, busy_s, gaps = 0.0, 0.0, []
    for plane in devices[:1]:
        runs = sorted((s, s + d, name) for line in plane["lines"]
                      if line["name"] == MODULES_LINE
                      for name, s, d, _o in line["events"])
        run_starts = [s for s, _e, _n in runs]

        def program(start: float) -> str:
            """The jitted module that was running: two programs name
            their instructions alike (`%copy`, `%fusion`)."""
            i = bisect.bisect_right(run_starts, start) - 1
            return runs[i][2] if i >= 0 and start <= runs[i][1] else ""
        for line in plane["lines"]:
            if line["name"] != OPS_LINE:
                continue
            by_program: dict[str, list] = defaultdict(list)
            for e in line["events"]:
                if " while(" not in e[0]:
                    by_program[program(e[1])].append(e)
            for events in by_program.values():
                stage = _consumers(events)
                for name, _s, d, op_name in events:
                    op_s += d
                    sc = scope_of(op_name)
                    k = PALLAS.search(name) or PALLAS.search(op_name)
                    if k:
                        pallas[k.group(1)] += d
                        kernels[k.group(0)] += d
                    if sc:
                        by_scope[sc] += d
                    elif _instruction(name) in stage:
                        inherited[stage[_instruction(name)]] += d
                    else:
                        unscoped[_op_kind(name)] += d
            merged = _union([(s, s + d) for _n, s, d, _o in line["events"]])
            busy_s = sum(hi - lo for lo, hi in merged)
            gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])
                    if b[0] - a[1] >= GAP_FLOOR_S]
    spans = []
    host: dict[str, list] = defaultdict(lambda: [0, 0.0])
    for plane in planes:
        if not plane["name"].startswith(HOST_PLANE):
            continue
        for line in plane["lines"]:
            for name, s, d, _op in line["events"]:
                if name.startswith("pio."):
                    if name not in WAITERS:
                        spans.append((s, s + d, name))
                    host[name][0] += 1
                    host[name][1] += d
    spans.sort()
    starts = [s for s, _e, _n in spans]
    idle: dict[str, float] = defaultdict(float)
    for lo, hi in gaps:
        mid, best, best_len = 0.5 * (lo + hi), NO_SPAN, float("inf")
        i = bisect.bisect_right(starts, mid)
        # sorted by start: walk back over the spans that could cover mid
        for s, e, name in reversed(spans[max(0, i - 4096):i]):
            if e >= mid and e - s < best_len:
                best, best_len = name, e - s
        idle[best] += hi - lo

    def ranked(d):
        return dict(sorted(d.items(), key=lambda kv: -kv[1]))
    named = sum(by_scope.values()) + sum(inherited.values())
    return {"busy_s": busy_s, "op_s": op_s,
            "scoped_pct": 100.0 * named / op_s if op_s else 0.0,
            "by_scope": ranked(by_scope), "inherited": ranked(inherited),
            "unscoped": ranked(unscoped), "pallas": ranked(pallas),
            "pallas_kernels": ranked(kernels),
            "host_spans": {k: {"count": c, "seconds": s}
                           for k, (c, s) in sorted(
                               host.items(), key=lambda kv: -kv[1][1])},
            "idle_by_span": ranked(idle)}
