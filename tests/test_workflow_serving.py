"""Workflow + engine-server integration: train -> persist -> deploy ->
query over HTTP -> feedback -> reload (mirrors the reference's
CreateWorkflow/CreateServer behavior)."""

import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.core import EngineParams
from predictionio_tpu.data import DataMap, Event
from predictionio_tpu.data.storage import AccessKey, App, Storage
from predictionio_tpu.models import recommendation as R
from predictionio_tpu.serving import EngineServer, ServerConfig
from predictionio_tpu.workflow import run_train


class KeyedParamsFactory(R.RecommendationEngineFactory):
    """Module-level (dotted-path resolvable) factory with named
    programmatic params, for the --engine-params-key contract test."""

    @classmethod
    def engine_params(cls, key: str = "") -> EngineParams:
        assert key == "tiny", f"unexpected params key {key!r}"
        return EngineParams(
            data_source_params=("", R.DataSourceParams(app_name="wsapp")),
            preparator_params=("", R.PreparatorParams()),
            algorithm_params_list=[("als", R.ALSAlgorithmParams(
                rank=4, num_iterations=2, lam=0.1, seed=2))])


def call(port, method, path, body=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", method=method,
        data=json.dumps(body).encode() if body is not None else None)
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            ct = resp.headers.get("Content-Type", "")
            data = resp.read()
            return resp.status, (json.loads(data) if "json" in ct
                                 else data.decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"null")


@pytest.fixture
def seeded_app(tmp_env, mesh8):
    apps = Storage.get_meta_data_apps()
    app_id = apps.insert(App(0, "wsapp"))
    Storage.get_events().init(app_id)
    rng = np.random.default_rng(0)
    ev = Storage.get_events()
    for u in range(6):
        for i in range(6):
            if (u + i) % 2 == 0 or rng.random() < 0.3:
                ev.insert(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties=DataMap({"rating": float(1 + (u + i) % 5)})),
                    app_id)
    return app_id


def engine_params():
    return EngineParams(
        data_source_params=("", R.DataSourceParams(app_name="wsapp")),
        preparator_params=("", R.PreparatorParams()),
        algorithm_params_list=[("als", R.ALSAlgorithmParams(
            rank=4, num_iterations=4, lam=0.1, seed=1))],
        serving_params=("", None))


def train_once(variant="v1"):
    engine = R.RecommendationEngineFactory.apply()
    return run_train(engine, engine_params(), engine_id="recEngine",
                     engine_version="1", engine_variant=variant,
                     engine_factory="recommendation")


class TestRunTrain:
    def test_instance_lifecycle_and_model_persisted(self, seeded_app):
        iid = train_once()
        inst = Storage.get_meta_data_engine_instances().get(iid)
        assert inst.status == "COMPLETED"
        assert inst.engine_factory == "recommendation"
        algo_params = json.loads(inst.algorithms_params)
        assert algo_params[0]["name"] == "als"
        assert algo_params[0]["params"]["rank"] == 4
        assert Storage.get_model_data_models().get(iid) is not None

    def test_failed_training_marks_aborted(self, tmp_env, mesh8):
        apps = Storage.get_meta_data_apps()
        app_id = apps.insert(App(0, "wsapp"))
        Storage.get_events().init(app_id)  # no events -> sanity check fails
        engine = R.RecommendationEngineFactory.apply()
        with pytest.raises(Exception):
            run_train(engine, engine_params(), engine_id="recEngine")
        insts = Storage.get_meta_data_engine_instances().get_all()
        assert insts and all(i.status == "ABORTED" for i in insts)

    def test_latest_completed_selected(self, seeded_app):
        iid1 = train_once()
        time.sleep(0.01)
        iid2 = train_once()
        latest = Storage.get_meta_data_engine_instances() \
            .get_latest_completed("recEngine", "1", "v1")
        assert latest.id == iid2


class TestEngineServer:
    @pytest.fixture
    def server(self, seeded_app):
        train_once()
        s = EngineServer(ServerConfig(
            ip="127.0.0.1", port=0, engine_id="recEngine",
            engine_version="1", engine_variant="v1"))
        s.load()
        s.start()
        yield s
        s.stop()

    def test_query_over_http(self, server):
        status, body = call(server.config.port, "POST", "/queries.json",
                            {"user": "u1", "num": 3})
        assert status == 200
        assert len(body["itemScores"]) == 3
        assert all(set(s) == {"item", "score"} for s in body["itemScores"])

    def test_unknown_user_empty_scores(self, server):
        status, body = call(server.config.port, "POST", "/queries.json",
                            {"user": "nobody", "num": 3})
        assert status == 200 and body["itemScores"] == []

    def test_bad_query_is_400(self, server):
        status, _ = call(server.config.port, "POST", "/queries.json",
                         {"nope": 1})
        assert status in (400, 500)

    def test_status_page_counters(self, server):
        call(server.config.port, "POST", "/queries.json",
             {"user": "u1", "num": 1})
        status, html = call(server.config.port, "GET", "/")
        assert status == 200
        assert "Request count" in html
        assert server.request_count == 1
        assert server.last_serving_sec > 0

    def test_stats_json_carries_device_and_training_resolution(self, server):
        """/stats.json names the device THIS process computes on, as JAX
        reported it, and what `auto` resolved to when the loaded model
        was trained (read back from the EngineInstance env the trainer
        wrote) — the fields chip_smoke.py takes the platform from."""
        import jax
        status, body = call(server.config.port, "GET", "/stats.json")
        assert status == 200
        dev = jax.devices()
        assert (body["platform"], body["deviceKind"], body["deviceCount"]) \
            == (dev[0].platform, dev[0].device_kind, len(dev))
        assert body["pid"] == os.getpid()
        assert (body["solver"], body["computeDtype"]) == ("cholesky",
                                                          "float32")
        env = server.engine_instance.env
        assert env["platform"] == "cpu" and env["device_count"] == "8"

    def test_plugins_endpoint(self, server):
        status, body = call(server.config.port, "GET", "/plugins.json")
        assert status == 200 and "plugins" in body

    def test_reload_picks_latest(self, server):
        old_instance = server.engine_instance.id
        time.sleep(0.01)
        train_once()
        status, body = call(server.config.port, "GET", "/reload")
        assert status == 200
        assert server.engine_instance.id != old_instance
        status, body = call(server.config.port, "POST", "/queries.json",
                            {"user": "u1", "num": 2})
        assert status == 200 and len(body["itemScores"]) == 2


class TestFeedbackLoop:
    def test_feedback_event_written(self, seeded_app):
        from predictionio_tpu.data.api.event_server import (
            EventServer, EventServerConfig)
        Storage.get_meta_data_access_keys().insert(
            AccessKey("fbkey", seeded_app, []))
        es = EventServer(EventServerConfig(ip="127.0.0.1", port=0)).start()
        try:
            train_once()
            s = EngineServer(ServerConfig(
                ip="127.0.0.1", port=0, engine_id="recEngine",
                engine_version="1", engine_variant="v1", feedback=True,
                accesskey="fbkey", event_server_ip="127.0.0.1",
                event_server_port=es.config.port))
            s.load()
            s.start()
            try:
                status, body = call(s.config.port, "POST", "/queries.json",
                                    {"user": "u1", "num": 2})
                assert status == 200
                assert body["prId"] == s.engine_instance.id
                deadline = time.time() + 5
                found = []
                while time.time() < deadline and not found:
                    found = list(Storage.get_events().find(
                        seeded_app, event_names=["predict"]))
                    time.sleep(0.05)
                assert found, "feedback event not recorded"
                props = found[0].properties
                assert props.get("query", dict)["user"] == "u1"
                assert found[0].entity_type == "pio_pr"
            finally:
                s.stop()
        finally:
            es.stop()


class TestCreateWorkflowMain:
    def test_variant_file_train(self, seeded_app, tmp_path):
        from predictionio_tpu.workflow import (WorkflowConfig,
                                               create_workflow_main)
        variant = {
            "id": "recEngine", "engineFactory": "recommendation",
            "datasource": {"params": {"app_name": "wsapp"}},
            "algorithms": [{"name": "als", "params": {
                "rank": 4, "num_iterations": 3, "lam": 0.1, "seed": 2}}],
        }
        vf = tmp_path / "engine.json"
        vf.write_text(json.dumps(variant))
        iid = create_workflow_main(WorkflowConfig(engine_variant=str(vf)))
        inst = Storage.get_meta_data_engine_instances().get(iid)
        assert inst.status == "COMPLETED"
        assert inst.engine_id == "recEngine"

    def test_engine_params_key_uses_factory_params(self, seeded_app,
                                                   tmp_path):
        """`pio train --engine-params-key` takes params from the
        factory's programmatic sets, NOT the variant JSON
        (CreateWorkflow.scala:216-220). The variant here carries a
        deliberately broken algorithm name, so training only succeeds
        if the key path really bypassed it."""
        from predictionio_tpu.workflow import (WorkflowConfig,
                                               create_workflow_main)
        variant = {
            "id": "keyedEngine",
            "engineFactory":
                "tests.test_workflow_serving.KeyedParamsFactory",
            "algorithms": [{"name": "NO_SUCH_ALGO", "params": {}}]}
        vf = tmp_path / "engine.json"
        vf.write_text(json.dumps(variant))
        iid = create_workflow_main(WorkflowConfig(
            engine_variant=str(vf), engine_params_key="tiny"))
        inst = Storage.get_meta_data_engine_instances().get(iid)
        assert inst.status == "COMPLETED"
