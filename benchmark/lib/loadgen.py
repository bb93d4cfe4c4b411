"""Open-loop HTTP load, from a process of its own that never imports JAX:

    python3 benchmark/lib/loadgen.py < spec.json > result.json

The parent (which owns the chip) writes the spec and reads the result. A
request is due at a time fixed beforehand and is timed from that time, not
from when it was sent, so a stall delays the requests behind it and shows
in their latency; how late each send ran is reported beside it.

The schedule is a function of the mix alone (the same gaps for every seed);
the seed orders the gaps and draws the users, so that every seed offers the
same amount of work.
"""

from __future__ import annotations

import json
import socket
import sys
import threading
import time

import numpy as np


def request_count(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["rate_qps"] * seconds)))


def schedule(mix: dict, seed: int, seconds: float, n_users: int,
             salt: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """(due times in [0, seconds), user of each request)."""
    n = request_count(mix, seconds)
    if mix["arrivals"] == "poisson":
        gaps = np.random.default_rng(77).exponential(1.0, n)
    elif mix["arrivals"] == "uniform":
        gaps = np.ones(n)
    else:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    rng = np.random.default_rng([int(seed), 3, salt])
    # every gap is kept, so every seed's schedule is the same multiset of
    # gaps; the last request is due one mean gap before the window closes
    gaps = rng.permutation(gaps) * (seconds * n / (n + 1) / gaps.sum())
    due = np.cumsum(gaps)
    if mix["users"] != "uniform":
        raise ValueError(f"unknown user draw {mix['users']!r}")
    return due, rng.integers(0, n_users, n)


def frame(user: int, num: int) -> bytes:
    body = json.dumps({"user": str(int(user)), "num": int(num)}).encode()
    return (b"POST /queries.json HTTP/1.1\r\nHost: bench\r\n"
            b"Content-Type: application/json\r\nContent-Length: "
            + str(len(body)).encode()
            + b"\r\nConnection: keep-alive\r\n\r\n" + body)


class _Connection:
    def __init__(self, port: int, timeout: float):
        self.port, self.timeout = port, timeout
        self.sock = None
        self.buf = b""

    def _open(self):
        self.sock = socket.create_connection(("127.0.0.1", self.port),
                                             timeout=self.timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.buf = b""

    def roundtrip(self, framed: bytes) -> tuple[int, bytes]:
        if self.sock is None:
            self._open()
        try:
            self.sock.sendall(framed)
            while b"\r\n\r\n" not in self.buf:
                chunk = self.sock.recv(65536)
                if not chunk:
                    raise ConnectionError("closed")
                self.buf += chunk
            head, _, rest = self.buf.partition(b"\r\n\r\n")
            status = int(head.split(None, 2)[1])
            length = 0
            for line in head.split(b"\r\n")[1:]:
                if line[:15].lower() == b"content-length:":
                    length = int(line[15:])
                    break
            while len(rest) < length:
                chunk = self.sock.recv(65536)
                if not chunk:
                    raise ConnectionError("closed")
                rest += chunk
            self.buf = rest[length:]
            return status, rest[:length]
        except Exception:
            self.close()
            raise

    def close(self):
        if self.sock is not None:
            try:
                self.sock.close()
            except OSError:
                pass
            self.sock = None


def drive(port: int, due: np.ndarray, users: np.ndarray, num: int,
          connections: int, timeout: float, keep: set[int]) -> dict:
    """Offer the schedule; returns per request (arrays over requests) the
    lateness of its send, its latency from its due time, whether it was
    answered with status 200, and the bodies of the requests in `keep`."""
    n = int(due.size)
    frames = [frame(u, num) for u in users]
    late = np.full(n, np.nan)
    latency = np.full(n, np.inf)
    ok = np.zeros(n, bool)
    bodies: dict[int, str] = {}
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
            sent = time.perf_counter()
            late[i] = sent - (t0 + due[i])
            try:
                status, body = conn.roundtrip(frames[i])
            except Exception:
                continue
            latency[i] = time.perf_counter() - (t0 + due[i])
            ok[i] = status == 200
            if i in keep:
                bodies[i] = body.decode("utf-8", "replace")
        conn.close()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(connections)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {"late": late, "latency": latency, "ok": ok, "bodies": bodies,
            "t0": t0, "wall_s": time.perf_counter() - t0}


def main() -> int:
    spec = json.load(sys.stdin)
    due, users = schedule(spec["mix"], spec["seed"], spec["seconds"],
                          spec["n_users"], spec["salt"])
    r = drive(spec["port"], due, users, spec["mix"]["num"],
              spec["mix"]["connections"], spec["mix"]["request_timeout_s"],
              set(spec["keep"]))
    json.dump({"due": due.tolist(), "users": users.tolist(),
               "late": r["late"].tolist(),
               "latency": [x if x != float("inf") else None
                           for x in r["latency"].tolist()],
               "ok": r["ok"].tolist(), "t0": r["t0"], "wall_s": r["wall_s"],
               "bodies": {str(k): v for k, v in r["bodies"].items()}},
              sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
