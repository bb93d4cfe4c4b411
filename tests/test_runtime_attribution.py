"""Runtime attribution & tail forensics (ISSUE 11 acceptance).

- device-time attribution: the serve dispatch path books estimated
  device seconds under its costmon executable label;
- slow-query forensics: a query over the SLO-derived threshold lands
  in /slow.json with a >=4-stage waterfall whose trace id resolves via
  /traces.json?trace_id=, plus a slow_query flight record;
- SLO breach -> incident bundle carrying the top waterfalls and a
  sampling-profiler report (the slow_queries/profiler providers);
- always-on sampling profiler: folded stacks + /profile.json report on
  BOTH servers (event server behind --stats), jax-trace toggle moved
  to obs/profiler with the ISSUE 2 idempotent semantics intact;
- obs overhead: the new per-request instrumentation (exemplar observe,
  unsampled dispatch timing, slow-threshold check) stays <= 1% of the
  measured serve p50.
"""

import datetime as dt
import json
import time
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.core import FirstServing
from predictionio_tpu.data.bimap import BiMap, EntityIdIxMap
from predictionio_tpu.data.storage.base import EngineInstance
from predictionio_tpu.models import recommendation as R
from predictionio_tpu.ops.als import ALSModel
from predictionio_tpu.serving import EngineServer, ServerConfig


def _mini_server(port: int = 0, micro_batch: int = 16) -> EngineServer:
    """A servable engine with no storage: model + algorithm installed
    directly (the test_distributed HTTP fixture pattern)."""
    rng = np.random.default_rng(7)
    als = ALSModel(rng.standard_normal((30, 6)).astype(np.float32),
                   rng.standard_normal((20, 6)).astype(np.float32), 6)
    model = R.RecommendationModel(
        als, EntityIdIxMap(BiMap({f"u{i}": i for i in range(30)})),
        EntityIdIxMap(BiMap({f"i{i}": i for i in range(20)})))
    algo = R.ALSAlgorithm(R.ALSAlgorithmParams(rank=6))
    s = EngineServer(ServerConfig(ip="127.0.0.1", port=port,
                                  micro_batch=micro_batch))
    now = dt.datetime.now(dt.timezone.utc)
    s.engine_instance = EngineInstance(
        id="attr", status="COMPLETED", start_time=now, end_time=now,
        engine_id="attr", engine_version="0", engine_variant="attr",
        engine_factory="recommendation")
    s.algorithms = [algo]
    s.models = [model]
    s.serving = FirstServing()
    return s


def _get(port, path, timeout=10):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
        return json.loads(r.read())


def _post(port, path, body, timeout=15):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


class TestDeviceTimeAttribution:
    def test_serve_dispatch_books_device_seconds(self):
        """users_topk_serve routes through AOTRegistry.dispatch ->
        costmon.device_timed: the batch_predict label must own
        non-zero estimated device seconds after a few dispatches."""
        from predictionio_tpu.obs import costmon
        rng = np.random.default_rng(3)
        als = ALSModel(
            rng.standard_normal((40, 8)).astype(np.float32),
            rng.standard_normal((24, 8)).astype(np.float32), 8)
        from predictionio_tpu.ops.als import users_topk_serve
        # earlier tests in a full-suite run may have advanced this
        # label's sampling tick arbitrarily: force every dispatch to
        # sync so the assertion is deterministic
        st = costmon._device_state(costmon.BATCH_PREDICT)
        old_every, st.every = st.every, 1
        try:
            before = costmon.device_time_by_executable().get(
                costmon.BATCH_PREDICT, 0.0)
            for _ in range(3):
                scores, idx = users_topk_serve(als, [0, 3, 7], 5)
        finally:
            st.every = old_every
        assert scores.shape[0] == 3
        after = costmon.device_time_by_executable().get(
            costmon.BATCH_PREDICT, 0.0)
        assert after > before
        disp = costmon.dispatch_seconds_by_executable().get(
            costmon.BATCH_PREDICT, 0.0)
        assert disp > 0.0

    def test_fold_side_books_device_seconds(self):
        """The fold solve path (solve_rows -> _run_side) is wrapped
        the same way under the fold_side label."""
        from predictionio_tpu.obs import costmon
        from predictionio_tpu.online.fold_in import (FoldInConfig,
                                                     solve_rows)
        rng = np.random.default_rng(4)
        V = rng.standard_normal((12, 4)).astype(np.float32)
        st = costmon._device_state(costmon.FOLD_SIDE)
        old_every, st.every = st.every, 1
        try:
            before = costmon.device_time_by_executable().get(
                costmon.FOLD_SIDE, 0.0)
            # twice: a cold process's first solve pays the XLA compile
            # and its sample is (correctly) discarded as
            # compile-tainted; the second dispatch is warm and books
            for _ in range(2):
                out = solve_rows(
                    V, np.array([0, 0, 1], dtype=np.int64),
                    np.array([1, 2, 3], dtype=np.int32),
                    np.array([4.0, 3.0, 5.0], dtype=np.float32),
                    2, FoldInConfig(lam=0.1))
        finally:
            st.every = old_every
        assert out.shape == (2, 4)
        after = costmon.device_time_by_executable().get(
            costmon.FOLD_SIDE, 0.0)
        assert after > before

    def test_stats_json_exposes_device_time_block(self):
        from predictionio_tpu.obs import costmon
        st = costmon._device_state(costmon.BATCH_PREDICT)
        old_every, st.every = st.every, 1
        s = _mini_server()
        s.start()
        try:
            try:
                # twice: the first query in a cold process compiles and
                # its device sample is discarded as compile-tainted
                _post(s.config.port, "/queries.json",
                      {"user": "u0", "num": 5})
                _post(s.config.port, "/queries.json",
                      {"user": "u0", "num": 5})
            finally:
                st.every = old_every
            stats = _get(s.config.port, "/stats.json")
            assert "deviceTime" in stats
            dt_block = stats["deviceTime"]
            assert "secondsByExecutable" in dt_block
            assert "occupancy" in dt_block
            assert dt_block["secondsByExecutable"].get(
                "batch_predict", 0.0) > 0.0
        finally:
            s.stop()


class TestSlowQueryForensics:
    @pytest.fixture()
    def slow_server(self, monkeypatch):
        # every query is "slow": the threshold is the point under test,
        # not the latency
        monkeypatch.setenv("PIO_SLOW_QUERY_MS", "0.001")
        s = _mini_server()
        s.start()
        yield s
        s.stop()

    def test_slow_query_waterfall_end_to_end(self, slow_server):
        port = slow_server.config.port
        # the slow_query flight kind coalesces at 1s (storm
        # protection): step past any prior test's burst window so THIS
        # query's record is the one emitted
        time.sleep(1.1)
        status, out = _post(port, "/queries.json",
                            {"user": "u1", "num": 5})
        assert status == 200 and out["itemScores"]
        slow = _get(port, "/slow.json")
        assert slow["recorded"] >= 1
        entry = slow["slow"][0]
        stages = [st["stage"] for st in entry["stages"]]
        # the acceptance bar: a >=4-stage waterfall
        assert len(stages) >= 4, stages
        assert "queue_wait" in stages
        assert "dispatch" in stages
        assert "serialize" in stages
        # every stage carries a wall
        assert all(st["ms"] >= 0.0 for st in entry["stages"])
        # the exemplar trace id resolves to the actual span tree
        tr = _get(port,
                  f"/traces.json?trace_id={entry['traceId']}")
        assert tr["traces"], "slow entry's trace id did not resolve"
        kinds = {t["kind"] for t in tr["traces"]}
        assert "query" in kinds
        # and the flight recorder carries the slow_query kind
        fl = _get(port, "/flight.json?kind=slow_query")
        assert fl["records"]
        assert any(r.get("traceId") == entry["traceId"]
                   for r in fl["records"])

    def test_batched_waterfall_names_batch_stages(self, slow_server):
        port = slow_server.config.port
        _post(port, "/queries.json", {"user": "u2", "num": 3})
        entry = _get(port, "/slow.json")["slow"][0]
        stages = [st["stage"] for st in entry["stages"]]
        # micro_batch > 1: the window stages ride the batch trace
        assert "batch_formation" in stages
        assert entry.get("batchTraceId")

    def test_histogram_exemplar_names_a_replayable_trace(
            self, slow_server):
        port = slow_server.config.port
        _post(port, "/queries.json", {"user": "u3", "num": 5})
        stats = _get(port, "/stats.json")
        ex = stats["queryLatency"].get("exemplars")
        assert ex, "query histogram has no exemplars"
        tid = next(iter(ex.values()))["traceId"]
        tr = _get(port, f"/traces.json?trace_id={tid}")
        assert tr["traces"]


class TestSLOBreachIncident:
    def test_serve_p99_breach_bundles_waterfalls_and_profile(
            self, tmp_path, monkeypatch):
        """Force a serve-p99 breach; the ok->breached transition at
        /health.json must capture an incident bundle whose providers
        carry the slow-query waterfalls and a profiler report."""
        monkeypatch.setenv("PIO_INCIDENTS_DIR", str(tmp_path / "inc"))
        monkeypatch.setenv("PIO_SLOW_QUERY_MS", "0.001")
        from predictionio_tpu.obs.incidents import get_incidents
        from predictionio_tpu.obs.slowlog import SLOWLOG
        inc = get_incidents()
        # drop the cooldown so earlier tests' captures can't suppress
        monkeypatch.setattr(inc, "cooldown_s", 0.0)
        # the slow-query ring is the process's: a one-stage entry of
        # 1,000 ms that another file's test left there (a worker runs
        # several files) would top this server's waterfalls
        SLOWLOG.clear()
        s = _mini_server()
        s.start()
        try:
            port = s.config.port
            # baseline health sample (all good)
            _get(port, "/health.json")
            # a real slow query (fills the slowlog for the provider)
            _post(port, "/queries.json", {"user": "u0", "num": 5})
            # force the p99 SLO burn: observations far over 250ms
            for _ in range(50):
                s._h_query.observe(10.0)
            time.sleep(0.05)
            health = _get(port, "/health.json")
            serve = next(x for x in health["slo"]
                         if x["name"] == "serve_p99")
            assert serve["status"] == "breached", serve
            assert inc.drain(timeout_s=10.0)
            bundles = inc.list_incidents()
            assert any(b["kind"] == "slo_breach" for b in bundles), \
                bundles
            bid = next(b["id"] for b in bundles
                       if b["kind"] == "slo_breach")
            bundle = inc.load(bid)
            providers = bundle["providers"]
            # the waterfalls
            assert "slow_queries" in providers
            slowq = providers["slow_queries"]
            assert slowq["top"], "no waterfalls in the bundle"
            assert len(slowq["top"][0]["stages"]) >= 4
            # the profiler report
            assert "profiler" in providers
            prof = providers["profiler"]
            assert "topStacks" in prof and "hz" in prof
            # the breach context names the SLO
            assert bundle["context"]["slo"]["name"] == "serve_p99"
        finally:
            s.stop()


class TestSamplingProfiler:
    @pytest.fixture(autouse=True)
    def _profiler_on(self, monkeypatch):
        # the hermetic suite defaults PIO_PROFILER=off (conftest);
        # these tests ARE the profiler tests
        monkeypatch.setenv("PIO_PROFILER", "on")

    def test_sampler_collects_folded_stacks(self):
        from predictionio_tpu.obs.profiler import SamplingProfiler
        p = SamplingProfiler(hz=200.0)
        assert p.start()
        try:
            t0 = time.time()
            while p.samples < 5 and time.time() - t0 < 5.0:
                time.sleep(0.02)
        finally:
            p.stop()
        rep = p.report(top=10)
        assert rep["samples"] >= 5
        assert rep["topStacks"]
        top = rep["topStacks"][0]
        # folded format: file:func;file:func, root first
        assert ";" in top["stack"] or ":" in top["stack"]
        assert top["count"] >= 1 and top["pct"] > 0
        # self-accounting for the overhead bench key
        assert rep["spentS"] >= 0.0

    def test_profiler_start_is_idempotent_and_gated(self, monkeypatch):
        from predictionio_tpu.obs.profiler import SamplingProfiler
        p = SamplingProfiler(hz=50.0)
        assert p.start() and p.start()     # second start: no-op True
        p.stop()
        monkeypatch.setenv("PIO_PROFILER", "off")
        q = SamplingProfiler(hz=50.0)
        assert not q.start()
        assert not q.running

    def test_engine_server_report_endpoint(self):
        from predictionio_tpu.obs.profiler import PROFILER
        s = _mini_server()
        s.start()
        try:
            rep = _get(s.config.port, "/profile.json?action=report")
            assert rep["message"] == "profiler report"
            assert rep["running"] is True     # always-on at start()
            assert "topStacks" in rep
            # bad action still reports state (the ISSUE 2 contract)
            req = urllib.request.Request(
                f"http://127.0.0.1:{s.config.port}/profile.json",
                data=json.dumps({"action": "nope"}).encode(),
                headers={"Content-Type": "application/json"})
            try:
                urllib.request.urlopen(req, timeout=10)
                assert False, "expected 400"
            except urllib.error.HTTPError as e:
                assert e.code == 400
                body = json.loads(e.read())
                assert body["tracing"] is False
        finally:
            s.stop()
            PROFILER.stop()   # don't leave the sampler running for
            #                   the rest of the (hermetic) suite

    def test_event_server_profile_gated_by_stats(self, tmp_env):
        import urllib.error

        from predictionio_tpu.data.api.event_server import (
            EventServer, EventServerConfig)
        # without --stats: 404
        es = EventServer(EventServerConfig(ip="127.0.0.1", port=0,
                                           stats=False))
        es.start()
        try:
            try:
                _get(es.config.port, "/profile.json?action=report")
                assert False, "expected 404"
            except urllib.error.HTTPError as e:
                assert e.code == 404
        finally:
            es.stop()
        # with --stats: the full surface, including the idempotent
        # jax-trace toggle the engine server had since ISSUE 2
        es = EventServer(EventServerConfig(ip="127.0.0.1", port=0,
                                           stats=True))
        es.start()
        try:
            port = es.config.port
            rep = _get(port, "/profile.json?action=report")
            assert "topStacks" in rep
            st, body = _post(port, "/profile.json", {"action": "stop"})
            assert st == 200 and body["tracing"] is False
            st, body = _post(port, "/profile.json", {"action": "stop"})
            assert st == 200 and body["tracing"] is False
        finally:
            es.stop()


class TestObsOverheadBudget:
    def test_new_instrumentation_within_one_percent_of_serve_p50(self):
        """The acceptance bar, in microseconds a request: the ISSUE 11
        additions (exemplar observe, unsampled dispatch timing, slow-
        threshold check) and the ISSUE 25 ones (the inactive profiler
        annotations of a request and of its dispatch, the serving
        account's clock reads and its two ring appends, a dispatch
        charged whole to one request) stay under OBS_BUDGET_US. That
        was 1% of an in-process CPU serve p50, which wandered from run
        to run; the budget is fixed now, 1% of a 10 ms p50 (0.14% of
        the serve cell's 71 ms), some ten times what an idle host
        measures, and each cost is the best of five runs (as
        tests/test_obs_overhead.py takes them)."""
        from predictionio_tpu.obs import costmon
        from predictionio_tpu.obs.metrics import MetricsRegistry
        from predictionio_tpu.obs.slowlog import slow_threshold_s
        from predictionio_tpu.obs.trace import (DISPATCH, TRACER, Tracer)

        OBS_BUDGET_US = 100.0

        def best_us(fn, n=20_000, repeats=5):
            best = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                for _ in range(n):
                    fn()
                best = min(best, time.perf_counter() - t0)
            return best / n * 1e6

        h = MetricsRegistry().histogram("p50_probe_seconds", "h")
        st = costmon._device_state("p50_probe")
        st.every = 0

        with TRACER.trace("p50_probe") as t:
            t.discard = True
            exemplar_us = best_us(lambda: h.observe(0.003))
        dispatch_us = best_us(
            lambda: costmon.device_timed("p50_probe", lambda: None))
        threshold_us = best_us(slow_threshold_s)

        account = Tracer()

        def region():
            with account.region("probe"):
                pass

        def request_record():
            account.note_request()
            account.note_request(1.0, 2.0, 3)
            account.request_written(0.5, 2.5)

        front = (1, 1.0, 1.0, 1.0, 1.0)
        region_us = best_us(region)
        clock_us = best_us(time.perf_counter)
        # a request: pio.http.request, two cache lookups, pio.cache.put,
        # its note and its record; its dispatch: form, begin, post,
        # wake, pio.readback.wait, ten clock reads and the record
        request_us = 4 * region_us + best_us(request_record)
        window_us = 5 * region_us + 10 * clock_us + best_us(
            lambda: account.record(DISPATCH, front + (
                1.0, 1.0, 1.0, 1.0, 3, 4, 0.0, None)))

        obs_us = (exemplar_us + dispatch_us + threshold_us
                  + request_us + window_us)
        assert obs_us <= OBS_BUDGET_US, (
            f"obs additions {obs_us:.2f}us a request over the budget of "
            f"{OBS_BUDGET_US:.0f}us (exemplar {exemplar_us:.2f}, dispatch "
            f"{dispatch_us:.2f}, threshold {threshold_us:.2f}, request "
            f"{request_us:.2f}, window {window_us:.2f})")
