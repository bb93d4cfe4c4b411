"""obs/stallwatch.py: a report when requests wait and nothing moves, and
when the watch's own tick comes late; none while dispatches go on or
nothing waits."""

import threading
import time

import pytest

from predictionio_tpu.obs.metrics import MetricsRegistry
from predictionio_tpu.obs.stallwatch import StallWatch


class _Batcher:
    waiting = 0
    done = 0


def _watch(b, **kw):
    return StallWatch(lambda: b.waiting, lambda: b.done,
                      tick_s=0.01, threshold_s=0.08, **kw).start()


def _settle(cond, timeout=3.0):
    t0 = time.perf_counter()
    while not cond() and time.perf_counter() - t0 < timeout:
        time.sleep(0.01)
    return cond()


def test_a_report_when_requests_wait_and_nothing_moves():
    b = _Batcher()
    metrics = MetricsRegistry()
    w = _watch(b, metrics=metrics)
    try:
        blocked = threading.Event()
        t = threading.Thread(target=blocked.wait, name="the-blocked-one",
                             daemon=True)
        t.start()
        b.waiting = 3
        assert _settle(lambda: w.n_stalls == 1)
        r = w.reports()[0]
        assert r["waiting"] == 3 and "duration_s" not in r
        assert r["at"] - r["since"] >= 0.08
        assert "the-blocked-one" in r["stacks"]
        assert "pio-stall-watch" not in r["stacks"]
        assert r["process_cpu_s"] >= 0 and "machine_s" in r
        b.done += 1                       # progress: the stall is over
        assert _settle(lambda: "duration_s" in w.reports()[0])
        assert w.n_stalls == 1
        assert w.stall_s == pytest.approx(w.reports()[0]["duration_s"])
        assert metrics.get("pio_serve_stalls_total") is not None
        blocked.set()
    finally:
        w.stop()


@pytest.mark.parametrize("waiting", [0, 2])
def test_no_report_while_idle_or_while_dispatches_go_on(waiting):
    b = _Batcher()
    b.waiting = waiting
    w = _watch(b)
    try:
        for _ in range(30):
            time.sleep(0.01)
            b.done += bool(waiting)
        assert w.n_stalls == 0 and w.reports() == []
    finally:
        w.stop()


def test_a_late_tick_is_a_stall_whatever_moved_meanwhile(monkeypatch):
    """The watch's thread itself held up (as when no thread of the process
    runs): reported even though a dispatch completed before it woke."""
    b = _Batcher()
    b.waiting = 1
    w = StallWatch(lambda: b.waiting, lambda: b.done, tick_s=0.01,
                   threshold_s=0.08)
    real_wait = w._stop.wait
    held = []

    def wait(timeout):
        if not held:
            held.append(1)
            time.sleep(0.2)               # this tick comes 0.2 s late
            b.done += 1                   # and the batcher moved first
        return real_wait(timeout)

    monkeypatch.setattr(w._stop, "wait", wait)
    w.start()
    try:
        assert _settle(lambda: w.n_stalls == 1)
        r = w.reports()[0]
        assert r["tick_late_s"] >= 0.15
        assert r["duration_s"] == pytest.approx(r["tick_late_s"])
    finally:
        w.stop()


def test_the_engine_server_runs_one_from_start_to_stop():
    from predictionio_tpu.models import recommendation as R
    from predictionio_tpu.serving import EngineServer, ServerConfig
    server = EngineServer(ServerConfig(ip="127.0.0.1", port=0,
                                       micro_batch=4),
                          engine=R.RecommendationEngineFactory.apply())
    assert server.stallwatch is not None and server.stallwatch._thread is None
    server.start()
    try:
        assert server.stallwatch._thread.is_alive()
        assert server.metrics.get("pio_serve_stalls_total") is not None
    finally:
        server.stop()
    assert server.stallwatch._thread is None
    assert EngineServer(ServerConfig(ip="127.0.0.1", port=0, micro_batch=1),
                        engine=R.RecommendationEngineFactory.apply()
                        ).stallwatch is None
