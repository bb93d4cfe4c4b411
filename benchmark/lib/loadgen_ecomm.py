"""Open-loop HTTP load for the e-commerce cell, from a process of its own
that never imports JAX:

    python3 benchmark/lib/loadgen_ecomm.py < spec.json > result.json

As benchmark/lib/loadgen.py, whose connection and schedule it uses: a
request is due at a time fixed beforehand and timed from that time, not
from its send. What differs is the request: the parent draws each one from
the seed (benchmark/lib/datagen_ecomm.py: six kinds, with category names and
item lists) and hands over the JSON bodies, and the result says when each
was sent on the clock both processes share, so that the parent can tell
which re-set of the unavailable list had been acknowledged by then.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib.loadgen import _Connection, schedule  # noqa: E402


def frame(body: str) -> bytes:
    body = body.encode()
    return (b"POST /queries.json HTTP/1.1\r\nHost: bench\r\n"
            b"Content-Type: application/json\r\nContent-Length: "
            + str(len(body)).encode()
            + b"\r\nConnection: keep-alive\r\n\r\n" + body)


def drive(port: int, due: np.ndarray, bodies: list, connections: int,
          timeout: float, keep: set) -> dict:
    """Offer the schedule; per request the lateness of its send, its
    latency from its due time, whether it was answered with status 200, and
    the answers of the requests in `keep`."""
    n = int(due.size)
    frames = [frame(b) for b in bodies]
    late = np.full(n, np.nan)
    latency = np.full(n, np.inf)
    ok = np.zeros(n, bool)
    answers: dict = {}
    nxt = [0]
    lock = threading.Lock()
    t0 = time.perf_counter() + 0.05

    def worker():
        conn = _Connection(port, timeout)
        while True:
            with lock:
                i = nxt[0]
                nxt[0] += 1
            if i >= n:
                break
            wait = t0 + due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            late[i] = time.perf_counter() - (t0 + due[i])
            try:
                status, body = conn.roundtrip(frames[i])
            except Exception:
                continue
            latency[i] = time.perf_counter() - (t0 + due[i])
            ok[i] = status == 200
            if i in keep:
                answers[i] = body.decode("utf-8", "replace")
        conn.close()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(connections)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {"late": late, "latency": latency, "ok": ok, "bodies": answers,
            "t0": t0, "wall_s": time.perf_counter() - t0}


def main() -> int:
    spec = json.load(sys.stdin)
    mix = spec["mix"]
    due, _users = schedule(mix, spec["seed"], spec["seconds"], 1,
                           spec["salt"])
    r = drive(spec["port"], due, spec["bodies"], mix["connections"],
              mix["request_timeout_s"], set(spec["keep"]))
    json.dump({"due": due.tolist(), "late": r["late"].tolist(),
               "latency": [x if x != float("inf") else None
                           for x in r["latency"].tolist()],
               "ok": r["ok"].tolist(), "t0": r["t0"], "wall_s": r["wall_s"],
               "bodies": {str(k): v for k, v in r["bodies"].items()}},
              sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
