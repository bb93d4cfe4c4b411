"""Job kind `http-queries-ecomm`: an EngineServer over the e-commerce
recommendation engine in this process (the one owner of the chip), a
nativelog event store in a temporary directory holding the seen events the
engine reads at predict time, open-loop `POST /queries.json` of six kinds
from a load generator in a process of its own that never imports JAX
(benchmark/lib/loadgen_ecomm.py), and a writer thread in this process that
re-sets `constraint/unavailableItems` through the storage API while the load
runs and, once a re-set is acknowledged, sends the check's own probes (a
whiteList of the ids that just became unavailable: the right answer is
empty).

The server is built as `pio deploy` builds it (the ECommerceEngineFactory,
ServerConfig's defaults but for what the configuration's `serve` group
states, the deploy-time AOT warm), without the training round trip: the
model is made from the seed's tables as `ECommAlgorithm.train` would leave
it (item_factors_normalized by the program's own normalize_rows, the item ->
category arrays in the program's own class).

`correct`: once the window has closed and the server's state is freed, a
sample of the window's answers drawn from the seed, at least
`check_after_reset` of them sent after a re-set (ids and scores as served:
HTTP, the batcher, the live reads, the composed-mask executable at every
bucket the window used, the packed readback, the JSON), is held against the
configuration's plain reference over the same tables and the reference's own
copy of the filter data: the seed's item -> category map, the user's seen
pairs from the draw, the unavailable list as of the request's send by the
writer's record of acknowledgements.
"""

from __future__ import annotations

import datetime as dt
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

from benchmark.lib import counts_masked, datagen, datagen_ecomm, loadgen
from benchmark.lib import loadgen_ecomm
from benchmark.lib.pauses import CollectorPauses

STAGES = ("formation", "dispatch", "completion_wait", "readback",
          "completion")
APP = "bench"
STORE_ENV = {
    "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "pio_event",
    "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "NATIVELOG",
    "PIO_STORAGE_SOURCES_NATIVELOG_TYPE": "nativelog",
}
COUNTERS = {"filter_h2d_bytes": "pio_filter_h2d_bytes_total",
            "seen_timeouts": "pio_filter_seen_timeouts_total",
            "constraint_reloads": "pio_filter_constraint_reloads_total",
            "constraint_failures": "pio_filter_constraint_failures_total"}
FILTER_STAGES = ("seen_read", "constraint_read", "lists")
FAULTS = {"fault:category_ignored": "category_ignored",
          "fault:bitmap_behind": "bitmap_behind"}


class Job:
    def __init__(self, cell: dict, seed: int, spans: dict):
        self.cell, self.seed, self.spans = cell, int(seed), spans
        self.config = cell["config"]
        self.mix = cell["traffic"]
        self.resolved: dict = {}
        self.server = None
        self.store_dir = None
        self._saved_env: dict = {}
        self._children: list[subprocess.Popen] = []
        self.pauses = CollectorPauses()

    # -- set-up -----------------------------------------------------------
    def setup(self):
        from predictionio_tpu.compile.cache import enable_persistent_cache
        from predictionio_tpu.core import FirstServing
        from predictionio_tpu.data.storage.base import EngineInstance
        from predictionio_tpu.models import ecommerce as E
        from predictionio_tpu.ops.similarity import (ItemCategories,
                                                     normalize_rows)
        from predictionio_tpu.serving import EngineServer, ServerConfig
        enable_persistent_cache()
        c, serve = self.config, self.config["serve"]
        t0 = time.perf_counter()
        self.U, self.V = datagen.served_tables(c, self.seed)
        normalized = normalize_rows(self.V)
        self.spans["tables_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        self.popular = datagen_ecomm.Popularity(c, self.seed)
        self.cat = datagen_ecomm.item_categories(c, self.seed)
        self.seen = datagen_ecomm.seen_pairs(c, self.seed, self.popular)
        self.visitor_views = datagen_ecomm.visitor_views(
            c, self.mix, self.seed, self.popular)
        # more re-sets than one window makes: sweep.py offers several
        self.versions = datagen_ecomm.unavailable_versions(
            c, self.seed, 64)
        self.spans["draw_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        self._open_store()
        self._populate()
        self.spans["populate_s"] = time.perf_counter() - t0
        self.spans["populate_events_per_s"] = (
            self.spans["store_events"] / self.spans["populate_s"])

        t0 = time.perf_counter()
        model = E.ECommerceModel(
            rank=int(c["rank"]), user_factors=self.U, item_factors=self.V,
            item_factors_normalized=normalized,
            user_ix=_id_map(self.U.shape[0]),
            item_ix=_id_map(self.V.shape[0]), items={},
            item_categories=ItemCategories(
                self.cat[:, None],
                {f"c{k}": k for k in range(int(c["n_categories"]))}))
        algo = E.ECommAlgorithm(E.ECommAlgorithmParams(
            app_name=APP, unseen_only=bool(c["unseen_only"]),
            seen_events=tuple(c["seen_events"]), rank=int(c["rank"])))
        self.spans["model_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        server = EngineServer(
            ServerConfig(ip="127.0.0.1", port=0,
                         micro_batch=int(serve["micro_batch"]),
                         result_cache=bool(serve["result_cache"])),
            engine=E.ECommerceEngineFactory.apply())
        now = dt.datetime.now(dt.timezone.utc)
        server.engine_instance = EngineInstance(
            id="bench", status="COMPLETED", start_time=now, end_time=now,
            engine_id="bench", engine_version="0", engine_variant="bench",
            engine_factory="ecommerce")
        server.algorithms, server.models = [algo], [model]
        server.serving = FirstServing()
        # the deploy-time warm: every (batch, list) bucket's executable
        # compiled (or loaded from the cache) before a request is taken
        server._warm_aot(server.models, "bench", strict=True)
        self.spans["aot_warm_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        # every batch bucket of both routes executed once (the tables, the
        # category array and the bitmap reach the device at the first)
        users = datagen_ecomm.store_users(c)
        b = 1
        while b <= int(serve["micro_batch"]):
            for ids in ([str(int(u)) for u in users[:b]],
                        [f"v{j}" for j in range(b)]):
                algo.batch_predict(model, [
                    (i, E.Query(user=u, num=int(self.mix["num"])))
                    for i, u in enumerate(ids)])
            b *= 2
        # and every (batch, list) bucket the warm compiled, once: users
        # the store holds nothing of, with a blackList that fills the list
        # bucket from just above the bucket below. The items stand at 99.2%
        # of 2^22, so each bucket's first dispatch starts the compile of
        # its twin at the next row bucket in the background, which belongs
        # to warm-up (compile/aot.py names those threads)
        stride = int(c["store_user_stride"])
        outside = [str(u) for u in range(1, 4 * int(serve["micro_batch"]))
                   if u % stride][:int(serve["micro_batch"])]
        floor = min(d["t"] for _, d in algo.aot_warm_specs(
            model, int(serve["micro_batch"])))
        for _, d in algo.aot_warm_specs(model, int(serve["micro_batch"])):
            if d["t"] == floor:
                continue
            black = tuple(str(i) for i in range(d["t"] // 4 // d["b"] + 1))
            algo.batch_predict(model, [
                (i, E.Query(user=u, num=int(self.mix["num"]),
                            black_list=black))
                for i, u in enumerate(outside[:d["b"]])])
        for t in threading.enumerate():
            if t.name.startswith("pio-aot-"):
                t.join()
        self.spans["first_dispatches_s"] = time.perf_counter() - t0
        server.start()
        self.server = server
        t0 = time.perf_counter()
        warm = self._offer(self.mix["warm_seconds"], salt=1, keep=[],
                           resets=False)
        if not all(warm["ok"]):
            raise RuntimeError(
                f"warm-up: {warm['ok'].count(False)} of {len(warm['ok'])} "
                f"requests failed")
        self.spans["warm_traffic_s"] = time.perf_counter() - t0
        self.resolved = {"micro_batch": server.config.micro_batch,
                         "serve_inflight": getattr(server.batcher,
                                                   "inflight", None),
                         "result_cache_used": server._cache_usable()}

    def _open_store(self):
        from predictionio_tpu.data.storage import registry
        from predictionio_tpu.data.storage.base import App
        self.store_dir = tempfile.mkdtemp(prefix="pio-bench-store-")
        env = dict(STORE_ENV, PIO_FS_BASEDIR=self.store_dir,
                   PIO_STORAGE_SOURCES_NATIVELOG_PATH=os.path.join(
                       self.store_dir, "eventlog"))
        self._saved_env = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        registry.clear_cache()
        self.app_id = registry.Storage.get_meta_data_apps().insert(
            App(0, APP))
        self.events = registry.Storage.get_events()
        self.events.init(self.app_id)

    def _populate(self):
        """The store's events: a `view` for every seen pair and a `buy` for
        those bought, the visitors' recent views, and the unavailable list
        as first set; written through the storage API's columnar route."""
        from predictionio_tpu.data.columnar import ColumnarBatch
        u, i, bought = self.seen
        day = dt.datetime(2017, 11, 25, tzinfo=dt.timezone.utc)
        stamp = day.strftime("%Y-%m-%dT%H:%M:%S.000Z")
        written = 0
        for name, sel in (("view", slice(None)), ("buy", bought)):
            uu, ii = u[sel], i[sel]
            for lo in range(0, uu.size, 1 << 19):
                ids = [str(x) for x in uu[lo:lo + (1 << 19)].tolist()]
                self.events.insert_columnar(ColumnarBatch(
                    len(ids), name, "user", ids, "item",
                    [str(x) for x in ii[lo:lo + (1 << 19)].tolist()],
                    None, stamp), self.app_id)
                written += len(ids)
        # a visitor's views, one second apart, so that "recent" is an order
        v = self.visitor_views
        times = [(day + dt.timedelta(days=1, seconds=s)).strftime(
            "%Y-%m-%dT%H:%M:%S.000Z") for s in range(v.shape[1])]
        self.events.insert_columnar(ColumnarBatch(
            v.size, "view", "user",
            [f"v{j}" for j in range(v.shape[0]) for _ in range(v.shape[1])],
            "item", [str(x) for x in v.ravel().tolist()], None,
            times * v.shape[0]), self.app_id)
        self.spans["store_events"] = written + v.size
        self.spans["store_users"] = int(np.unique(u).size)
        self._set_unavailable(0)

    def _unavailable_event(self, version: int):
        """The `$set` that carries the list's `version` (made before the
        load is offered: an inventory service brings its list ready)."""
        from predictionio_tpu.data import Event
        from predictionio_tpu.data.datamap import DataMap
        return Event(
            event="$set", entity_type="constraint",
            entity_id="unavailableItems",
            properties=DataMap({"items": [
                str(x) for x in self.versions[version].tolist()]}))

    def _set_unavailable(self, version: int, event=None
                         ) -> tuple[float, float]:
        """Write the list's `version` as a `$set` through the storage API;
        returns when the write began and when it was acknowledged (on the
        clock the load generator shares)."""
        event = event or self._unavailable_event(version)
        begun = time.perf_counter()
        self.events.insert(event, self.app_id)
        return begun, time.perf_counter()

    def _offer(self, seconds: float, salt: int, keep: list[int],
               resets: bool = True, requests: list | None = None) -> dict:
        """One phase of load from a child process, with the writer's re-sets
        beside it; returns the child's result, `acks` added."""
        if requests is None:
            n = loadgen.request_count(self.mix, seconds)
            requests = datagen_ecomm.requests(
                self.config, self.mix, self.seed, n, salt, self.cat,
                self.popular)
        num = int(self.mix["num"])
        spec = {"mix": self.mix, "seed": self.seed, "seconds": seconds,
                "salt": salt, "port": self.server.config.port, "keep": keep,
                "bodies": [json.dumps(datagen_ecomm.body(q, num))
                           for q in requests]}
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        child = subprocess.Popen(
            [sys.executable, loadgen_ecomm.__file__], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, env=env)
        self._children.append(child)
        acks: list[tuple[float, float]] = []
        stop = threading.Event()

        every = self._reset_every(seconds)
        ready = [self._unavailable_event(v) for v in range(
            self.version + 1, min(len(self.versions), self.version + 1
                                  + int(np.ceil(seconds / every)) - 1))
                 ] if resets else []

        probes: list[dict] = []

        def writer(t_start: float):
            for k, event in enumerate(ready, start=1):
                if stop.wait(max(0.0, t_start + k * every
                                 - time.perf_counter())):
                    return
                self.version += 1
                acks.append(self._set_unavailable(self.version, event))
                for q in datagen_ecomm.reset_probes(
                        self.config, self.seed, self.version,
                        int(self.mix.get("reset_probes", 0)),
                        self.versions[self.version - 1],
                        self.versions[self.version]):
                    probes.append(self._probe(q, num))

        # the child needs about half a second to start; the re-sets are
        # spaced from then, and judged by when they were acknowledged
        thread = threading.Thread(
            target=writer, args=(time.perf_counter() + 0.5,), daemon=True)
        if resets:
            thread.start()
        try:
            out, _ = child.communicate(json.dumps(spec).encode())
        finally:
            stop.set()
            if resets:
                thread.join()
            self._children.remove(child)
        if child.returncode != 0:
            raise RuntimeError(f"load generator exited {child.returncode}")
        r = json.loads(out)
        r["acks"] = acks
        r["probes"] = probes
        return r

    def _probe(self, q: dict, num: int) -> dict:
        """One request of the check's own, from this process: the request,
        when it was sent and answered on the clock the load generator
        shares, and the answer (None where it failed)."""
        data = json.dumps(datagen_ecomm.body(q, num)).encode()
        sent = time.perf_counter()
        try:
            with urllib.request.urlopen(urllib.request.Request(
                    f"http://127.0.0.1:{self.server.config.port}"
                    "/queries.json", data=data,
                    headers={"Content-Type": "application/json"}),
                    timeout=float(self.mix["request_timeout_s"])) as resp:
                body = resp.read().decode("utf-8", "replace")
        except Exception:
            body = None
        return {"request": q, "sent": sent,
                "answered": time.perf_counter(), "body": body}

    version = 0

    def _reset_every(self, seconds: float) -> float:
        """The mix's interval, or a quarter of a window too short for
        three of those: every window sees at least three re-sets."""
        every = float(self.mix["reset_every_s"])
        return every if seconds >= 4 * every else seconds / 4.0

    def _counters(self) -> dict:
        m = self.server.metrics
        hist = m.get("pio_serve_stage_seconds")
        stages = {}
        for st in STAGES:
            h = hist.labels(stage=st)
            stages[st] = (h.count, h.sum)
        b = self.server.batcher.stats()
        out = {"stages": stages, "batches": b["batches"],
               "queries": b["batchedQueries"]}
        for key, name in COUNTERS.items():
            counter = m.get(name)
            out[key] = counter.value if counter is not None else 0.0
        from predictionio_tpu.obs import costmon
        out["compile_s"] = sum(
            costmon.compile_seconds_by_executable().values())
        # every dispatch's filter work, by stage (a program without the
        # histogram, the parent, has none to read)
        filt = m.get("pio_filter_seconds")
        if filt is not None:
            out["filter"] = {st: (h.count, h.sum, h.bucket_counts())
                             for st in FILTER_STAGES
                             for h in [filt.labels(stage=st)]}
        watch = getattr(self.server, "stallwatch", None)
        if watch is not None:
            out["stalls"] = (watch.n_stalls, watch.stall_s)
        return out

    def _filter_stages(self, before: dict, after: dict, dispatches: int
                       ) -> dict:
        """Over the whole window, from `pio_filter_seconds` as differences:
        the host's filter work a dispatch (`lists` + `constraint_read`) and
        the seen reads' median and 95th percentile (interpolated inside the
        histogram's bucket)."""
        if "filter" not in after or "filter" not in before or not dispatches:
            return {}
        d = {st: after["filter"][st][1] - before["filter"][st][1]
             for st in FILTER_STAGES}
        out = {"filter_host_ms": 1e3 * (d["lists"] + d["constraint_read"])
               / dispatches}
        seen = self.server.metrics.get("pio_filter_seconds").labels(
            stage="seen_read")
        for q in (50, 95):
            v = seen.percentile_since(before["filter"]["seen_read"][2], q)
            if v is not None:
                out[f"seen_read_ms_p{q}"] = 1e3 * v
        return out

    def _stalls(self, before: dict, after: dict, r: dict,
                latency: np.ndarray, late: np.ndarray) -> dict:
        """What the window shows of a freeze. From the load generator
        alone: the longest time in which no request completed and how late
        the generator itself sent the requests due inside it (a process
        with no JAX that shares nothing with the server but the machine:
        late sends there mean the machine stood still, not the program).
        From the program's stall watch, where it has one: the stalls it
        counted and their seconds; its reports and stacks go to `spans`."""
        due = np.array(r["due"])
        done = np.sort((due + latency)[np.isfinite(latency)])
        out = {}
        if done.size > 1:
            g = int(np.argmax(np.diff(done)))
            inside = (due > done[g]) & (due < done[g + 1])
            out["completion_gap_max_ms"] = 1e3 * float(done[g + 1] - done[g])
            out["late_in_gap_ms_p50"] = (
                1e3 * float(np.nanmedian(late[inside]))
                if inside.any() and np.isfinite(late[inside]).any()
                else 0.0)
        if "stalls" in after and "stalls" in before:
            out["stalls"] = after["stalls"][0] - before["stalls"][0]
            out["stall_ms"] = 1e3 * (after["stalls"][1]
                                     - before["stalls"][1])
            watch = self.server.stallwatch
            out["tick_late_max_ms"] = 1e3 * watch.max_tick_late_s
            reports = [x for x in watch.reports()
                       if x["at"] >= r["t0"]]
            if reports:      # on the run's line of spans
                self.spans["stall_reports"] = [
                    {k: v for k, v in x.items() if k != "stacks"}
                    for x in reports]
                self.spans["stall_stacks"] = [x["stacks"][:6000]
                                              for x in reports[:3]]
        return out

    # -- the timed path ---------------------------------------------------
    def window(self, seconds: float, salt: int = 0) -> dict:
        n = loadgen.request_count(self.mix, seconds)
        requests = datagen_ecomm.requests(
            self.config, self.mix, self.seed, n, salt, self.cat,
            self.popular)
        due, _ = loadgen.schedule(self.mix, self.seed, seconds, 1, salt)
        keep = self._draw_checks(due, seconds)
        first_version = self.version
        before = self._counters()
        r = self._offer(seconds, salt=salt, keep=keep.tolist(),
                        requests=requests)
        after = self._counters()
        latency = np.array([np.inf if x is None else x
                            for x in r["latency"]])
        ok = np.array(r["ok"], bool)
        # a failed or refused request misses every limit: it stays in the
        # tail as an infinite latency
        latency[~ok] = np.inf
        late = np.array(r["late"], float)
        collections = self.pauses.between(r["t0"], r["t0"] + seconds)
        self.failed_requests = int((~ok).sum())
        sent = r["t0"] + np.array(r["due"]) + np.nan_to_num(late)
        # the check's probes ride behind the kept requests, numbered on
        # from the window's last request
        probes = r["probes"]
        bodies = dict(r["bodies"])
        bodies.update({str(n + j): p["body"] for j, p in enumerate(probes)
                       if p["body"] is not None})
        self.kept = {
            "index": np.concatenate([keep, n + np.arange(len(probes))]),
            "requests": [requests[i] for i in keep]
            + [p["request"] for p in probes],
            "bodies": bodies,
            "sent": np.concatenate([sent[keep],
                                    [p["sent"] for p in probes]]),
            "answered": np.concatenate([
                (r["t0"] + np.array(r["due"]) + latency)[keep],
                [p["answered"] for p in probes]]),
            "acks": np.array(r["acks"], float).reshape(-1, 2),
            "first_version": first_version,
            "seen_timeouts": after["seen_timeouts"]
            - before["seen_timeouts"],
            "constraint_failures": after["constraint_failures"]
            - before["constraint_failures"]}
        d_batches = after["batches"] - before["batches"]
        stage_ms = {}
        for st in STAGES:
            dn = after["stages"][st][0] - before["stages"][st][0]
            ds = after["stages"][st][1] - before["stages"][st][1]
            if dn > 0:
                stage_ms[st] = 1e3 * ds / dn
        done_in_window = int((ok & (latency + np.array(r["due"])
                                    <= seconds)).sum())
        out = {
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
            "resets": len(r["acks"]),
            "constraint_reloads": (after["constraint_reloads"]
                                   - before["constraint_reloads"]),
            "seen_timeouts": self.kept["seen_timeouts"],
            "constraint_failures": self.kept["constraint_failures"],
            "reset_probes": len(probes),
            # backend compiles on any thread while the load ran, seconds
            "compile_s_in_window": after["compile_s"] - before["compile_s"],
            "filter_h2d_bytes_per_dispatch": (
                (after["filter_h2d_bytes"] - before["filter_h2d_bytes"])
                / d_batches if d_batches else None),
            "gc2_pause_pct": 100.0 * sum(
                d for _, gen, d in collections if gen == 2) / seconds,
            "gc2_collections": sum(gen == 2 for _, gen, _d in collections),
            "detail": {"due_s": r["due"], "latency_s": r["latency"],
                       "ok": r["ok"], "late_s": r["late"],
                       "kind": [q["kind"] for q in requests],
                       "acks_s": [a - r["t0"] for _, a in r["acks"]],
                       "gc_pauses": [[t - r["t0"], gen, d]
                                     for t, gen, d in collections]},
        }
        out.update(self._filter_stages(before, after, d_batches))
        out.update(self._stalls(before, after, r, latency, late))
        for kind in datagen_ecomm.KINDS:
            sel = np.array([q["kind"] == kind for q in requests])
            if sel.any():
                out[f"query_p50_ms.{kind}"] = 1e3 * _percentile(
                    latency[sel], 50)
        return out

    def _draw_checks(self, due: np.ndarray, seconds: float) -> np.ndarray:
        """The requests whose answers are kept: `check_requests` drawn from
        the seed, at least `check_after_reset` of them due after the first
        re-set can have been acknowledged."""
        rng = np.random.default_rng([self.seed, 4])
        n = due.size
        want = min(n, int(self.mix["check_requests"]))
        late = np.flatnonzero(due > self._reset_every(seconds) + 1.0)
        after = rng.choice(late, min(late.size, want,
                                     int(self.mix["check_after_reset"])),
                           replace=False)
        rest = np.setdiff1d(np.arange(n), after)
        return np.sort(np.concatenate([after, rng.choice(
            rest, want - after.size, replace=False)]))

    # -- after the window -------------------------------------------------
    def collect(self) -> dict:
        """Stop the server and free what it holds on the device."""
        self.close()
        from predictionio_tpu.utils import device_cache
        device_cache.clear()
        return self.kept

    def _reference_queries(self, kept: dict) -> list[dict]:
        """The kept requests as the reference wants them, with the
        constraint versions in force from each one's send to its answer by
        the writer's acknowledgements."""
        u, i, _bought = self.seen
        out = []
        for q, sent, answered in zip(kept["requests"], kept["sent"],
                                     kept["answered"]):
            v0 = kept["first_version"] + int(
                (kept["acks"][:, 1] < sent).sum())
            # a re-set begun before the answer may already have been read
            v1 = kept["first_version"] + int(
                (kept["acks"][:, 0] < answered).sum())
            d = {"kind": q["kind"], "categories": q["categories"],
                 "black": q["black"], "white": q["white"],
                 "versions": list(range(v0, max(v0, v1) + 1))}
            if q["user"] >= 0:
                lo, hi = np.searchsorted(u, [q["user"], q["user"] + 1])
                d.update(route="dot", vector=self.U[q["user"]],
                         seen=i[lo:hi])
            else:
                views = self.visitor_views[q["visitor"]]
                d.update(route="cos", recent=views, seen=views)
            out.append(d)
        return out

    def compare(self, kept: dict, reference,
                precision: str | None = None) -> dict:
        """The numbers of the comparison. With a `precision` the reference
        at that lower operand precision stands in for the served answers
        (the control); with `fault:<name>` the reference under that planted
        fault does."""
        from benchmark.lib import compare
        k = int(self.mix["num"])
        queries = self._reference_queries(kept)
        filter_data = {"item_category": self.cat,
                       "unavailable": self.versions}
        numbers = {"filter_violations": 0, "malformed": 0, "unanswered": 0,
                   "answers": len(queries),
                   "after_reset": sum(q["versions"][0] > kept[
                       "first_version"] for q in queries),
                   "ambiguous": sum(len(q["versions"]) > 1
                                    for q in queries),
                   "probes": sum(q["kind"] == "reset_probe"
                                 for q in queries)}
        for route in ("dot", "cos"):
            rows = [j for j, q in enumerate(queries) if q["route"] == route]
            if not rows:
                continue
            qs = [queries[j] for j in rows]
            best_s, best_i = reference.rank(qs, self.V, filter_data, route, k)
            if precision is None:
                ids = np.full((len(rows), k), -1, np.int64)
                served = np.full((len(rows), k), np.nan)
                for at, j in enumerate(rows):
                    body = kept["bodies"].get(str(int(kept["index"][j])))
                    if body is None:
                        numbers["unanswered"] += 1
                        continue
                    a = compare.parse_answer(body)
                    if (a is None or len(a["ids"]) > k
                            or len(set(a["ids"])) != len(a["ids"])):
                        numbers["malformed"] += 1
                        continue
                    ids[at, :len(a["ids"])] = a["ids"]
                    served[at, :len(a["ids"])] = a["scores"]
            else:
                served, ids = reference.rank(
                    qs, self.V, filter_data, route, k,
                    **({"faults": (FAULTS[precision],)}
                       if precision in FAULTS
                       else {"precision": precision}))
            exact = reference.scores_of(qs, self.V, route, ids)
            gaps, errs = [], []
            for at, q in enumerate(qs):
                got = ids[at] >= 0
                numbers["filter_violations"] += int(
                    (~reference.allowed_of(q, filter_data,
                                           ids[at][got])).sum())
                if len(q["versions"]) > 1:
                    continue      # ranked under either list: rule only
                n_ref = int(np.isfinite(best_s[at]).sum())
                if int(got.sum()) != n_ref:
                    numbers["malformed"] += 1
                    continue
                if n_ref == 0:
                    continue
                scale = abs(best_s[at, 0])
                gaps.append(float(np.max(np.maximum(
                    best_s[at, :n_ref] - exact[at, :n_ref], 0.0)) / scale))
                errs.append(float(np.max(np.abs(
                    served[at, :n_ref] - exact[at, :n_ref])) / scale))
            numbers[f"{route}_answers"] = len(rows)
            numbers[f"{route}_rank_gap_max"] = max(gaps) if gaps else None
            numbers[f"{route}_score_err_max"] = max(errs) if errs else None
            if gaps:
                numbers[f"{route}_rank_gap_p50"] = float(np.median(gaps))
                numbers[f"{route}_score_err_p50"] = float(np.median(errs))
        numbers["seen_timeouts"] = int(kept["seen_timeouts"])
        numbers["constraint_failures"] = int(kept["constraint_failures"])
        numbers["failed_requests"] = self.failed_requests
        return numbers

    def release(self) -> None:
        self.U = self.V = self.kept = self.seen = self.cat = None
        self.versions = self.popular = None

    def close(self) -> None:
        for child in list(self._children):
            child.kill()
            child.wait()
        self._children.clear()
        if self.server is not None:
            self.server.stop()
            self.server = None
        self.pauses.close()
        if self.store_dir is not None:
            from predictionio_tpu.data.storage import registry
            registry.clear_cache()
            for k, v in self._saved_env.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            shutil.rmtree(self.store_dir, ignore_errors=True)
            self.store_dir = None

    def work(self) -> dict:
        """What one query and one dispatch need, from the configuration."""
        c = self.config
        return {"query_flops": counts_masked.query_flops(
                    int(c["n_items"]), int(c["rank"])),
                "n_items": int(c["n_items"]), "rank": int(c["rank"]),
                "category_slots": 1,
                "listed_per_query": float(c["assumed"]["seen_mean"]),
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
