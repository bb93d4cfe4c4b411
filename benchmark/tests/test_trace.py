"""The reduction from a trace to busy and idle time: on a small hand-made
trace in the shape `trace.load` gives (plain lists: it needs no profiler to
check), and on a sample recorded on the chip in a traced run of the serve
cell (`trace.sample`, through prove.py --trace 1: the first events of every
line)."""

import json
import os

from benchmark.lib import trace

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "trace_sample.serve.json")

PLANES = [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ("jit__users_topk_b_packed(123)", 1.0, 0.5),
            ("jit__users_topk_b_packed(123)", 2.0, 0.5)]},
        {"name": "XLA Ops", "events": [
            ("%while.3 = (s32[]) while(%tuple.1)", 1.0, 0.5),  # a container
            ("fusion.1", 1.0, 0.3), ("fusion.2", 1.2, 0.3),   # overlap
            ("fusion.1", 2.0, 0.5)]},
        {"name": "Steps", "events": [("0", 0.0, 9.0)]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [
            ("bench.window", 0.0, 4.0), ("formation", 1.6, 0.3)]}]},
]


def test_busy_is_the_union_and_gaps_are_named_by_the_host():
    r = trace.reduce(PLANES, window_s=4.0)
    assert abs(r["busy_s"] - 1.0) < 1e-9          # 1.0-1.5 and 2.0-2.5
    assert r["window_s"] == 4.0
    # instances of one kind of operation are summed under the kind, with
    # the longest instance as the example
    (name, seconds), = r["device_ops"]
    assert name == "fusion: fusion.1" and abs(seconds - 1.1) < 1e-9
    m = r["modules"]["jit__users_topk_b_packed"]
    assert m["count"] == 2 and abs(m["seconds"] - 1.0) < 1e-9
    # the one idle gap between the device's operations, 1.5-2.0, falls
    # under the innermost host span over its middle
    assert r["idle_gaps"] == [["formation", 0.5]]


def test_device_work_past_the_given_wall_counts_as_time():
    """A saturated device that drains the slice's last requests after the
    offered time is up: the window stretches to the device's own span, so
    busy_s never passes window_s (the driver refuses a line where it does).
    """
    r = trace.reduce(PLANES, window_s=0.9)
    assert abs(r["busy_s"] - 1.0) < 1e-9
    assert abs(r["window_s"] - 1.5) < 1e-9        # 1.0 to 2.5
    assert 0 < r["busy_s"] <= r["window_s"]
    assert trace.reduce(PLANES)["window_s"] == r["window_s"]


def test_no_device_plane_reads_nothing():
    r = trace.reduce([PLANES[1]], window_s=4.0)
    assert r["busy_s"] == 0.0 and r["device_ops"] == []


def test_recorded_trace_reduces_to_what_its_events_say():
    with open(RECORDED) as f:
        planes = [{"name": p["name"],
                   "lines": [{"name": ln["name"],
                              "events": [tuple(e) for e in ln["events"]]}
                             for ln in p["lines"]]} for p in json.load(f)]
    (device,) = [p for p in planes if p["name"].startswith("/device:TPU:")]
    ops = next(ln for ln in device["lines"] if ln["name"] == "XLA Ops")
    r = trace.reduce(planes, window_s=0.0)
    first = min(s for _, s, _ in ops["events"])
    last = max(s + d for _, s, d in ops["events"])
    # the slice is at least the device's own first-to-last span, busy time
    # is the union (no more than the span, no more than the sum), and the
    # per-operation sums are the events' durations, loops left out
    assert abs(r["window_s"] - (last - first)) < 1e-9
    total = sum(d for _, _, d in ops["events"])
    assert 0 < r["busy_s"] <= min(r["window_s"], total) + 1e-12
    named = sum(d for n, _, d in ops["events"] if " while(" not in n)
    assert len(r["device_ops"]) <= 10
    assert sum(v for _, v in r["device_ops"]) <= named + 1e-12
    # the serve executable is found by its jitted module's name
    assert any("users_topk" in name for name in r["modules"])
    assert trace.reduce(planes, window_s=10.0)["window_s"] == 10.0
