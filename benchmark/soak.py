"""A builder's tool: several windows of a serve cell in one process, one of
them under the profiler, to place what a server meets only after it has run
for a while (PERF.md section 7, row 21).

    python3 benchmark/soak.py --workload <name> --seed <n> --windows 3 \\
        --seconds 40 --traced 1

Per window, from the load generator's latencies and the program's serving
account (lib/account.py; read after each window, while the rings still hold
it): how many requests took over `--slow-ms`, when, and where their time went
(before the batcher, in its queue, at the gate, in begin, in turnaround, in
post, between the result and the last byte), beside the same for the other
requests. For the traced window also the program's host spans (lib/scopes),
inside and outside the slow stretch, on the one clock: a `pio.soak.mark`
region entered at a known time.perf_counter() ties the two. One object goes
to chiprun_out/soak.<workload>.<seed>.json."""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from collections import defaultdict

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import account, device, scopes, trace   # noqa: E402
from benchmark.lib.spec import Spec                        # noqa: E402

def _parts(recs: list[dict], parts) -> dict:
    """Median and largest of each part, in milliseconds."""
    out = {}
    for name, later, earlier in parts:
        v = np.array([r[later] - r[earlier] for r in recs])
        if v.size:
            out[name] = {"p50_ms": 1e3 * float(np.median(v)),
                         "max_ms": 1e3 * float(v.max())}
    return out


def window_account(w: dict, slow_s: float) -> dict:
    """Where the window's slow requests spent their time, and the others."""
    ctx = {"window": w}
    reqs = account.window_requests(ctx)
    disp = account.window_dispatches(ctx)
    if not reqs or not disp:
        return {"account": None}
    by_seq = {d["seq"]: d for d in disp}
    t0 = min(r["t_start"] for r in reqs)
    slow = [r for r in reqs
            if r["t_written"] - r["t_start"] > slow_s
            and r["dispatch_seq"] >= 0]
    rest = [r for r in reqs
            if r["t_written"] - r["t_start"] <= slow_s
            and r["dispatch_seq"] >= 0]
    out = {"requests": len(reqs), "slow_in_server": len(slow),
           "rest": {"request": _parts(rest, account.REQUEST_PARTS),
                    "dispatch": _parts(
                        [by_seq[r["dispatch_seq"]] for r in rest
                         if r["dispatch_seq"] in by_seq],
                        account.DISPATCH_PARTS)}}
    if slow:
        slow_d = {r["dispatch_seq"]: by_seq[r["dispatch_seq"]]
                  for r in slow if r["dispatch_seq"] in by_seq}
        out["slow"] = {
            "from_s": min(r["t_start"] for r in slow) - t0,
            "to_s": max(r["t_written"] for r in slow) - t0,
            "request": _parts(slow, account.REQUEST_PARTS),
            "dispatches": len(slow_d),
            "dispatch": _parts(list(slow_d.values()),
                               account.DISPATCH_PARTS),
            "synced_dispatches": sum(d["sync_s"] > 0
                                     for d in slow_d.values()),
            "stretch": (min(r["t_start"] for r in slow),
                        max(r["t_written"] for r in slow))}
    return out


def host_spans(planes, mark_t: float, stretch) -> dict:
    """Seconds and count of each `pio.*` host span, all over the trace and
    inside the slow stretch (perf_counter times, moved onto the trace's
    clock by the mark)."""
    spans = [(n, s, d) for p in planes
             if p["name"].startswith(scopes.HOST_PLANE)
             for ln in p["lines"] for n, s, d, _op in ln["events"]
             if n.startswith("pio.")]
    marks = [s for n, s, _d in spans if n == "pio.soak.mark"]
    if not marks:
        return {}
    shift = mark_t - marks[0]           # perf_counter = trace + shift
    out: dict = {"all": defaultdict(lambda: [0, 0.0, 0.0])}
    if stretch:
        out["slow_stretch"] = defaultdict(lambda: [0, 0.0, 0.0])
    for n, s, d in spans:
        where = ["all"]
        if stretch and stretch[0] <= s + shift <= stretch[1]:
            where.append("slow_stretch")
        for k in where:
            e = out[k][n]
            e[0] += 1
            e[1] += d
            e[2] = max(e[2], d)
    return {k: {n: {"count": c, "seconds": s, "longest_s": m}
                for n, (c, s, m) in sorted(v.items(),
                                           key=lambda kv: -kv[1][1])}
            for k, v in out.items()}


def flight_kinds(since_t: float) -> dict:
    """The program's flight records (obs/flight.py: slow queries, SLO
    breaches, incidents, swaps) written since `since_t`, counted by kind:
    what the instrumentation did in a window, profiler or not."""
    from predictionio_tpu.obs.flight import FLIGHT
    kinds: dict = defaultdict(int)
    for rec in FLIGHT.tail(2048):
        if rec["t"] >= since_t:
            kinds[rec["kind"]] += rec.get("coalesced", 0) + 1
    return dict(kinds)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--windows", type=int, default=3)
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--traced", type=int, default=1,
                   help="the window (from 0) under the profiler; -1: none")
    p.add_argument("--slow-ms", type=float, default=200.0)
    a = p.parse_args()
    from predictionio_tpu.obs import TRACER
    spec = Spec(REPO)
    device.prepare_environment(REPO)
    cell = spec.cell(a.workload)
    info = device.require_chip(cell["chips"])
    job = spec.job(cell).Job(cell, a.seed, {})
    trace_dir = os.path.join(REPO, ".bench_work", "trace")
    windows = []
    try:
        job.setup()
        t_serving = time.perf_counter()
        for i in range(a.windows):
            traced = i == a.traced
            mark_t = None
            if traced:
                shutil.rmtree(trace_dir, ignore_errors=True)
                trace.start(trace_dir)
                mark_t = time.perf_counter()
                with TRACER.region("soak.mark"):
                    pass
            try:
                # another draw of users in every window: a repeat would be
                # answered by the result cache
                started_s = time.perf_counter() - t_serving
                t_wall = time.time()
                w = job.window(a.seconds, salt=10 + i)
            finally:
                if traced:
                    trace.stop()
            detail = w.pop("detail")
            lat = np.array([np.inf if x is None else x
                            for x in detail["latency_s"]])
            due = np.array(detail["due_s"])
            slow = lat > a.slow_ms / 1e3
            line = {k: v for k, v in w.items()
                    if isinstance(v, (int, float, dict))}
            line.update(
                window=i, traced=traced, started_after_serving_s=started_s,
                slow_requests=int(slow.sum()),
                slow_from_s=float(due[slow].min()) if slow.any() else None,
                slow_to_s=float((due + np.where(np.isfinite(lat), lat, 0))
                                [slow].max()) if slow.any() else None,
                worst_ms=1e3 * float(lat[np.isfinite(lat)].max()),
                gc_pauses=detail["gc_pauses"],
                flight_records=flight_kinds(t_wall),
                cache=(job.server.result_cache.stats()
                       if job.server.result_cache is not None else None))
            line.update(window_account(w, a.slow_ms / 1e3))
            if traced:
                planes = scopes.load(trace_dir)
                stretch = (line.get("slow") or {}).get("stretch")
                line["host_spans"] = host_spans(planes, mark_t, stretch)
                line["idle_by_span"] = scopes.reduce(planes)["idle_by_span"]
                del planes
                shutil.rmtree(trace_dir, ignore_errors=True)
            windows.append(line)
            print(json.dumps({k: v for k, v in line.items()
                              if k not in ("host_spans", "gc_pauses")}),
                  flush=True)
    finally:
        job.close()
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out",
                           f"soak.{a.workload}.{a.seed}.json"), "w") as f:
        json.dump({"workload": a.workload, "seed": a.seed, "device": info,
                   "windows": windows}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
