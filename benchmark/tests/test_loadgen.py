"""The load generator against a fake server that stalls: requests are timed
from when they were due, and the lateness of the sends is reported."""

import http.server
import threading
import time

import numpy as np

from benchmark.lib import loadgen

MIX = {"rate_qps": 10.0, "arrivals": "uniform", "users": "uniform",
       "num": 10, "connections": 1, "request_timeout_s": 5.0}


class _Stalling(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    stall_at, seen = 5, 0

    def do_POST(self):
        self.rfile.read(int(self.headers["Content-Length"]))
        cls = type(self)
        cls.seen += 1
        if cls.seen == cls.stall_at:
            time.sleep(0.4)
        body = b'{"itemScores": []}'
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


def test_a_stall_delays_the_requests_behind_it():
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Stalling)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        due, users = loadgen.schedule(MIX, 3, 2.0, 100)
        assert due.size == 20 and (np.diff(due) > 0).all() and due[-1] < 2.0
        r = loadgen.drive(server.server_address[1], due, users, 10,
                          MIX["connections"], 5.0, keep={0})
    finally:
        server.shutdown()
        server.server_close()
    assert r["ok"].all() and 0 in r["bodies"]
    # one connection: the stalled request holds back those due behind it,
    # whose sends run late and whose latency counts the wait
    assert r["latency"][4] >= 0.4
    assert r["late"][5] > 0.3 and r["latency"][5] > 0.3
    assert np.percentile(r["late"], 95) > 0.1
    assert r["late"][:4].max() < 0.08


def test_every_seed_offers_the_same_work_in_another_order():
    mix = dict(MIX, arrivals="poisson", rate_qps=200.0)
    a, ua = loadgen.schedule(mix, 1, 5.0, 10**6)
    b, ub = loadgen.schedule(mix, 2**31 + 1, 5.0, 10**6)
    assert a.size == b.size == 1000
    gaps = lambda d: np.sort(np.diff(np.concatenate([[0.0], d])))
    assert np.allclose(gaps(a), gaps(b)) and not np.allclose(a, b)
    assert not (ua == ub).all()
    again, _ = loadgen.schedule(mix, 1, 5.0, 10**6)
    assert (a == again).all()


def test_collector_pauses_are_watched_and_the_collector_left_alone():
    import gc

    from benchmark.lib.pauses import CollectorPauses
    was = (gc.isenabled(), gc.get_threshold(), gc.get_freeze_count())
    watch = CollectorPauses()
    try:
        t0 = time.perf_counter()
        gc.collect()                       # a generation-2 collection
        t1 = time.perf_counter()
        (start, gen, seconds), = [e for e in watch.between(t0, t1)
                                  if e[1] == 2]
        assert t0 <= start and 0 < seconds <= t1 - t0
        assert watch.between(t1, t1 + 1.0) == []
    finally:
        watch.close()
    assert (gc.isenabled(), gc.get_threshold(),
            gc.get_freeze_count()) == was
    n = len(watch.events)
    gc.collect()
    assert len(watch.events) == n          # closed: it watches no more
