"""End-to-end telemetry (ISSUE 2 acceptance): an event POSTed to the
real Event Server is linked — via /traces.json — to the fold-in tick
that absorbed it and the model swap it triggered; both servers'
/metrics are produced solely by the shared registry and carry the
query-latency / batch-wait / fold-in-tick / event-write histograms."""

import datetime as dt
import json
import urllib.error
import urllib.request

import pytest

from predictionio_tpu.core import EngineParams
from predictionio_tpu.data.api.event_server import (EventServer,
                                                    EventServerConfig)
from predictionio_tpu.data.storage import AccessKey, App, Storage
from predictionio_tpu.models import recommendation as R
from predictionio_tpu.online import SchedulerConfig
from predictionio_tpu.online.scheduler import attach_scheduler
from predictionio_tpu.serving import EngineServer, ServerConfig
from predictionio_tpu.workflow import run_train

UTC = dt.timezone.utc


def call(port, path, body=None, method=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode() if body is not None else None,
        method=method or ("POST" if body is not None else "GET"))
    try:
        with urllib.request.urlopen(req, timeout=15) as resp:
            ct = resp.headers.get("Content-Type", "")
            data = resp.read()
            return resp.status, (json.loads(data) if "json" in ct
                                 else data.decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"null")


def engine_params():
    return EngineParams(
        data_source_params=("", R.DataSourceParams(app_name="telapp")),
        preparator_params=("", R.PreparatorParams()),
        algorithm_params_list=[("als", R.ALSAlgorithmParams(
            rank=4, num_iterations=3, lam=0.1, seed=1))],
        serving_params=("", None))


@pytest.fixture
def stack(tmp_env, mesh8):
    """Trained engine + live Event Server + live Engine Server +
    attached scheduler — the full in-process serving stack."""
    from predictionio_tpu.data import DataMap, Event
    app_id = Storage.get_meta_data_apps().insert(App(0, "telapp"))
    Storage.get_events().init(app_id)
    Storage.get_meta_data_access_keys().insert(
        AccessKey("telkey", app_id, []))
    ev = Storage.get_events()
    for u in range(8):
        for i in range(8):
            if (u + i) % 2 == 0:
                ev.insert(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties=DataMap({"rating": float(1 + (u * i) % 5)})),
                    app_id)
    engine = R.RecommendationEngineFactory.apply()
    run_train(engine, engine_params(), engine_id="tel",
              engine_version="1", engine_variant="v1",
              engine_factory="recommendation")
    es = EventServer(EventServerConfig(ip="127.0.0.1", port=0,
                                       stats=True))
    es.start()
    srv = EngineServer(ServerConfig(
        ip="127.0.0.1", port=0, engine_id="tel", engine_version="1",
        engine_variant="v1", micro_batch=4))
    srv.load()
    srv.start()
    sched = attach_scheduler(
        srv, SchedulerConfig(app_name="telapp", max_deltas=1))
    yield es, srv, sched
    srv.stop()
    es.stop()


class TestEndToEndTrace:
    def test_event_to_fold_to_swap_span_tree(self, stack):
        es, srv, sched = stack
        # 1. ingest through the REAL event server; the 201 carries the
        #    ingest trace id for correlation
        st, resp = call(
            es.config.port, "/events.json?accessKey=telkey",
            {"event": "rate", "entityType": "user", "entityId": "newbie",
             "targetEntityType": "item", "targetEntityId": "i0",
             "properties": {"rating": 5.0}})
        assert st == 201
        ingest_trace = resp["traceId"]
        assert ingest_trace
        # 2. one scheduler tick folds it and hot-swaps the server
        report = sched.tick(force=True)
        assert report is not None and report["events"] >= 1
        swaps = srv.swap_count
        assert swaps >= 1
        # 3. /traces.json on the ENGINE server links the chain
        st, body = call(srv.config.port, "/traces.json?kind=fold_tick")
        assert st == 200
        folds = [t for t in body["traces"]
                 if ingest_trace in t.get("links", [])]
        assert folds, "fold tick must link the ingested event's trace"
        tick_trace = folds[0]
        names = {c["name"] for c in tick_trace["root"]["children"]}
        assert "tail_read" in names
        assert "fold_solve" in names
        assert "hot_swap" in names
        assert tick_trace["root"]["attrs"]["events"] >= 1
        # 4. ... and the ingest trace links back to the fold tick
        st, body = call(es.config.port,
                        "/traces.json?kind=event_ingest")
        ingests = [t for t in body["traces"]
                   if t["traceId"] == ingest_trace]
        assert ingests
        assert tick_trace["traceId"] in ingests[0]["links"]
        ingest_spans = {c["name"]
                        for c in ingests[0]["root"]["children"]}
        assert "storage_write" in ingest_spans

    def test_query_traces_link_their_batch(self, stack):
        es, srv, sched = stack
        st, body = call(srv.config.port, "/queries.json",
                        {"user": "u1", "num": 2})
        assert st == 200 and body["itemScores"]
        st, body = call(srv.config.port, "/traces.json?kind=query")
        assert st == 200 and body["traces"]
        q = body["traces"][0]
        # micro-batching on: the query trace links the batch_predict
        # trace that answered it
        assert q["links"]
        st, body = call(srv.config.port,
                        "/traces.json?kind=batch_predict")
        assert any(t["traceId"] in q["links"] for t in body["traces"])


class TestMetricsSurfaces:
    def test_engine_metrics_histograms_from_registry(self, stack):
        es, srv, sched = stack
        call(srv.config.port, "/queries.json", {"user": "u1", "num": 2})
        st, text = call(srv.config.port, "/metrics")
        assert st == 200
        # the four ISSUE 2 histogram families, all registry-rendered
        assert "# TYPE pio_engine_query_seconds histogram" in text
        assert "# TYPE pio_engine_batch_wait_seconds histogram" in text
        assert "# TYPE pio_fold_tick_seconds histogram" in text
        assert "pio_engine_query_seconds_count 1" in text
        # process-wide families ride the parent chain
        assert "pio_jax_host_to_device_bytes_total" in text
        assert "pio_fold_events_total" in text
        # one compile family, by stage (ISSUE 37)
        assert 'pio_compile_seconds_total{executable="' in text
        assert "pio_jax_compile_seconds_total" not in text

    def test_event_metrics_write_histogram(self, stack):
        es, srv, sched = stack
        call(es.config.port, "/events.json?accessKey=telkey",
             {"event": "rate", "entityType": "user", "entityId": "u1",
              "targetEntityType": "item", "targetEntityId": "i1",
              "properties": {"rating": 3.0}})
        st, text = call(es.config.port, "/metrics")
        assert st == 200
        assert "# TYPE pio_event_write_seconds histogram" in text
        assert "pio_event_write_seconds_count 1" in text
        # fold-tick histogram rides along via the process registry
        assert "# TYPE pio_fold_tick_seconds histogram" in text

    def test_stats_json_histogram_blocks(self, stack):
        es, srv, sched = stack
        call(srv.config.port, "/queries.json", {"user": "u2", "num": 2})
        st, stats = call(srv.config.port, "/stats.json")
        assert st == 200
        assert stats["queryLatency"]["count"] >= 1
        assert "p99" in stats["queryLatency"]
        assert stats["batchWait"]["count"] >= 1

    def test_fold_report_carries_h2d_bytes(self, stack):
        es, srv, sched = stack
        call(es.config.port, "/events.json?accessKey=telkey",
             {"event": "rate", "entityType": "user", "entityId": "nb2",
              "targetEntityType": "item", "targetEntityId": "i2",
              "properties": {"rating": 4.0}})
        report = sched.tick(force=True)
        assert report is not None
        assert "h2dBytes" in report
