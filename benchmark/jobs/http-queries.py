"""Job kind `http-queries`: an EngineServer in this process (the one owner of
the chip) over factor tables made from the seed, and open-loop
`POST /queries.json` from a load generator in a process of its own that
never imports JAX (benchmark/lib/loadgen.py), at the rate fixed in the
traffic mix.

The server is built as `pio deploy` builds it (ServerConfig's defaults but
for what the configuration's `serve` group states, the deploy-time AOT warm,
the result cache on), without the storage round trip: no training runs in
set-up, and random tables are enough for speed and for the comparison.

`correct`: once the window has closed and the server's state is freed, a
sample of the window's answers drawn from the seed (ids and scores as
served, through HTTP decode, the batcher, the packed top-k at every batch
bucket the window used, the readback unpack and the JSON encoder) is held
against the configuration's plain reference ranking over the same tables.
"""

from __future__ import annotations

import datetime as dt
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from benchmark.lib import counts, datagen, loadgen
from benchmark.lib.pauses import CollectorPauses

STAGES = ("formation", "dispatch", "completion_wait", "readback",
          "completion")


class Job:
    def __init__(self, cell: dict, seed: int, spans: dict):
        self.cell, self.seed, self.spans = cell, int(seed), spans
        self.config = cell["config"]
        self.mix = cell["traffic"]
        self.resolved: dict = {}
        self.server = None
        self._children: list[subprocess.Popen] = []
        # the collector is left as the program leaves it, and watched
        self.pauses = CollectorPauses()

    # -- set-up -----------------------------------------------------------
    def setup(self):
        from predictionio_tpu.compile.cache import enable_persistent_cache
        from predictionio_tpu.core import FirstServing
        from predictionio_tpu.data.storage.base import EngineInstance
        from predictionio_tpu.models import recommendation as R
        from predictionio_tpu.ops.als import ALSModel
        from predictionio_tpu.serving import EngineServer, ServerConfig
        enable_persistent_cache()
        c, serve = self.config, self.config["serve"]
        t0 = time.perf_counter()
        self.U, self.V = datagen.served_tables(c, self.seed)
        self.spans["tables_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        n_users, n_items = self.U.shape[0], self.V.shape[0]
        user_ix, item_ix = _id_map(n_users), _id_map(n_items)
        self.spans["id_maps_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        als_model = ALSModel(self.U, self.V, int(c["rank"]))
        model = R.RecommendationModel(als_model, user_ix, item_ix)
        algo = R.ALSAlgorithm(R.ALSAlgorithmParams(rank=int(c["rank"])))
        server = EngineServer(
            ServerConfig(ip="127.0.0.1", port=0,
                         micro_batch=int(serve["micro_batch"]),
                         result_cache=bool(serve["result_cache"])),
            engine=R.RecommendationEngineFactory.apply())
        now = dt.datetime.now(dt.timezone.utc)
        server.engine_instance = EngineInstance(
            id="bench", status="COMPLETED", start_time=now, end_time=now,
            engine_id="bench", engine_version="0", engine_variant="bench",
            engine_factory="recommendation")
        server.algorithms, server.models = [algo], [model]
        server.serving = FirstServing()
        # the deploy-time warm: every batch bucket's executable compiled
        # (or loaded from the cache) before a request is taken
        server._warm_aot(server.models, "bench", strict=True)
        self.spans["aot_warm_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        # each bucket executed once (the tables reach the device at the
        # first), then the server takes the mix itself for a moment
        b = 1
        while b <= int(serve["micro_batch"]):
            algo.batch_predict(model, [
                (i, R.Query(user=str(i), num=int(self.mix["num"])))
                for i in range(b)])
            b *= 2
        # the program compiles the next row bucket's executables in the
        # background once a table passes three quarters of its bucket (the
        # users stand at 95.7% of 2^23): that belongs to warm-up, so wait
        # for those threads (compile/aot.py names them) before any traffic
        for t in threading.enumerate():
            if t.name.startswith("pio-aot-"):
                t.join()
        self.spans["first_dispatches_s"] = time.perf_counter() - t0
        server.start()
        self.server = server
        t0 = time.perf_counter()
        warm = self._offer(self.mix["warm_seconds"], salt=1, keep=[])
        if not all(warm["ok"]):
            raise RuntimeError(
                f"warm-up: {warm['ok'].count(False)} of {len(warm['ok'])} "
                f"requests failed")
        self.spans["warm_traffic_s"] = time.perf_counter() - t0
        self.resolved = {"micro_batch": server.config.micro_batch,
                         "serve_inflight": getattr(server.batcher,
                                                   "inflight", None)}

    def _offer(self, seconds: float, salt: int, keep: list[int]) -> dict:
        """One phase of load from a child process; returns its result."""
        spec = {"mix": self.mix, "seed": self.seed, "seconds": seconds,
                "n_users": int(self.config["n_users"]), "salt": salt,
                "port": self.server.config.port, "keep": keep}
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        child = subprocess.Popen(
            [sys.executable, loadgen.__file__], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, env=env)
        self._children.append(child)
        out, _ = child.communicate(json.dumps(spec).encode())
        self._children.remove(child)
        if child.returncode != 0:
            raise RuntimeError(f"load generator exited {child.returncode}")
        return json.loads(out)

    def _counters(self) -> dict:
        hist = self.server.metrics.get("pio_serve_stage_seconds")
        stages = {}
        for st in STAGES:
            h = hist.labels(stage=st)
            stages[st] = (h.count, h.sum)
        b = self.server.batcher.stats()
        cache = (self.server.result_cache.stats()
                 if self.server.result_cache is not None else {})
        return {"stages": stages, "batches": b["batches"],
                "queries": b["batchedQueries"], "cache": cache}

    # -- the timed path ---------------------------------------------------
    def window(self, seconds: float, salt: int = 0) -> dict:
        n = loadgen.request_count(self.mix, seconds)
        rng = np.random.default_rng([self.seed, 4])
        keep = np.sort(rng.choice(
            n, min(n, int(self.mix["check_requests"])), replace=False))
        before = self._counters()
        r = self._offer(seconds, salt=salt, keep=keep.tolist())
        after = self._counters()
        latency = np.array([np.inf if x is None else x
                            for x in r["latency"]])
        ok = np.array(r["ok"], bool)
        # a failed or refused request misses every limit: it stays in the
        # tail as an infinite latency
        latency[~ok] = np.inf
        late = np.array(r["late"], float)
        # the child's clock is this process's (CLOCK_MONOTONIC): the
        # collections that started while load was offered
        collections = self.pauses.between(r["t0"], r["t0"] + seconds)
        self.failed_requests = int((~ok).sum())
        self.kept = {"index": keep, "users": np.array(r["users"])[keep],
                     "bodies": r["bodies"]}
        d_batches = after["batches"] - before["batches"]
        stage_ms = {}
        for st in STAGES:
            dn = after["stages"][st][0] - before["stages"][st][0]
            ds = after["stages"][st][1] - before["stages"][st][1]
            if dn > 0:
                stage_ms[st] = 1e3 * ds / dn
        done_in_window = int((ok & (latency + np.array(r["due"])
                                    <= seconds)).sum())
        return {
            "attempted": n, "failed": self.failed_requests,
            "wall_s": seconds,
            "query_p50_ms": 1e3 * _percentile(latency, 50),
            "query_p95_ms": 1e3 * _percentile(latency, 95),
            "queries_per_s": done_in_window / seconds,
            "loadgen_late_ms_p95": 1e3 * float(np.nanpercentile(late, 95)),
            "query_p99_ms": 1e3 * _percentile(latency, 99),
            "avg_batch": ((after["queries"] - before["queries"]) / d_batches
                          if d_batches else None),
            "dispatches": d_batches, "stage_ms": stage_ms,
            "cache_hits": (after["cache"].get("hits", 0)
                           - before["cache"].get("hits", 0)),
            "gc2_pause_pct": 100.0 * sum(
                d for _, gen, d in collections if gen == 2) / seconds,
            "gc2_collections": sum(gen == 2 for _, gen, _d in collections),
            # per request and per collection: for the builder's tools, and
            # never part of a run's last line
            "detail": {"due_s": r["due"], "latency_s": r["latency"],
                       "ok": r["ok"], "late_s": r["late"],
                       "gc_pauses": [[t - r["t0"], gen, d]
                                     for t, gen, d in collections]},
        }

    # -- after the window -------------------------------------------------
    def collect(self) -> dict:
        """Stop the server and free what it holds on the device."""
        self.close()
        from predictionio_tpu.utils import device_cache
        device_cache.clear()
        return self.kept

    def compare(self, kept: dict, reference,
                precision: str | None = None) -> dict:
        from benchmark.lib import compare
        answers, unanswered = [], 0
        for i in kept["index"]:
            body = kept["bodies"].get(str(int(i)))
            if body is None:
                unanswered += 1
                answers.append(None)
                continue
            answers.append(compare.parse_answer(body))
        numbers = compare.topk_numbers(
            answers, self.U[kept["users"]], self.V, reference,
            int(self.mix["num"]), precision)
        numbers["unanswered"] = unanswered
        numbers["failed_requests"] = self.failed_requests
        return numbers

    def release(self) -> None:
        """Let go of the host tables (a process that runs several seeds
        would otherwise hold each seed's 8 GB)."""
        self.U = self.V = self.kept = None

    def close(self) -> None:
        for child in list(self._children):
            child.kill()
            child.wait()
        self._children.clear()
        if self.server is not None:
            self.server.stop()
            self.server = None
        self.pauses.close()

    def work(self) -> dict:
        """What one query and one dispatch need, from the table sizes."""
        c = self.config
        return {"query_flops": counts.topk_query_flops(
                    int(c["n_items"]), int(c["rank"])),
                "n_items": int(c["n_items"]), "rank": int(c["rank"]),
                "factor_bytes": np.dtype(c["factor_dtype"]).itemsize}


def _id_map(n: int):
    """Entity ids "0".."n-1" in the program's own map."""
    from predictionio_tpu.data.bimap import BiMap, EntityIdIxMap
    return EntityIdIxMap(BiMap({str(i): i for i in range(n)}))


def _percentile(x: np.ndarray, q: float) -> float:
    """The smallest value with at least q% of all requests at or under it;
    infinite where that many were never answered."""
    x = np.sort(x)
    return float(x[max(0, int(np.ceil(q / 100.0 * x.size)) - 1)])
