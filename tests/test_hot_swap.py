"""Hot-swap safety (ISSUE 1 acceptance): threads hammering /queries.json
across >= 3 model swaps observe zero 5xx responses and never a torn
(mixed-version) factor read; swap/fold-in counters are visible on
/stats.json and /metrics."""

import dataclasses
import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.core import EngineParams
from predictionio_tpu.data import DataMap, Event
from predictionio_tpu.data.storage import App, Storage
from predictionio_tpu.models import recommendation as R
from predictionio_tpu.ops.als import ALSModel
from predictionio_tpu.serving import EngineServer, ServerConfig
from predictionio_tpu.workflow import run_train

RANK = 4
VERSION_CONSTS = (1.0, 2.0, 3.0, 4.0)   # user row = c, item rows = 1
# every item's score under version c is exactly RANK * c (f32-exact)
ALLOWED_SCORES = {RANK * c for c in VERSION_CONSTS}


def call(port, path, body=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode() if body is not None else None,
        method="POST" if body is not None else "GET")
    with urllib.request.urlopen(req, timeout=15) as resp:
        ct = resp.headers.get("Content-Type", "")
        data = resp.read()
        return resp.status, (json.loads(data) if "json" in ct
                             else data.decode())


@pytest.fixture
def server(tmp_env, mesh8):
    app_id = Storage.get_meta_data_apps().insert(App(0, "swapapp"))
    Storage.get_events().init(app_id)
    ev = Storage.get_events()
    for u in range(4):
        for i in range(5):
            ev.insert(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                properties=DataMap({"rating": float(1 + (u + i) % 5)})),
                app_id)
    ep = EngineParams(
        data_source_params=("", R.DataSourceParams(app_name="swapapp")),
        preparator_params=("", R.PreparatorParams()),
        algorithm_params_list=[("als", R.ALSAlgorithmParams(
            rank=RANK, num_iterations=2, lam=0.1, seed=1))],
        serving_params=("", None))
    engine = R.RecommendationEngineFactory.apply()
    run_train(engine, ep, engine_id="swap", engine_version="1",
              engine_variant="v1", engine_factory="recommendation")
    s = EngineServer(ServerConfig(
        ip="127.0.0.1", port=0, engine_id="swap", engine_version="1",
        engine_variant="v1"))
    s.load()
    s.start()
    yield s
    s.stop()


def _version_model(base: R.RecommendationModel, c: float
                   ) -> R.RecommendationModel:
    """A model whose every predicted score is exactly RANK * c: any
    response mixing scores from two versions — a torn factor read —
    is detectable from the response alone."""
    n_u, n_i = base.als.n_users, base.als.n_items
    als = ALSModel(
        user_factors=np.full((n_u, RANK), c, dtype=np.float32),
        item_factors=np.ones((n_i, RANK), dtype=np.float32),
        rank=RANK)
    return dataclasses.replace(base, als=als)


class TestHotSwapSafety:
    def test_no_5xx_no_torn_reads_across_swaps(self, server):
        base = server.models[0]
        versions = [_version_model(base, c) for c in VERSION_CONSTS]
        port = server.config.port
        stop = threading.Event()
        failures = []
        n_ok = [0]

        def hammer():
            while not stop.is_set():
                # snapshot BEFORE issuing the call: a response launched
                # while swap_count was still 0 may legitimately come
                # from the pre-swap TRAINED model, whose scores are
                # distinct — with only one version deployed no tear is
                # possible, so flagging it was a false positive (the
                # flake this suite carried since PR 1)
                pre_swaps = server.swap_count
                try:
                    st, body = call(port, "/queries.json",
                                    {"user": "u1", "num": 3})
                except Exception as e:
                    failures.append(("transport", repr(e)))
                    continue
                if st >= 500:
                    failures.append(("5xx", st, body))
                    continue
                scores = {s["score"] for s in body["itemScores"]}
                if len(scores) > 1 and (pre_swaps > 0
                                        or scores & ALLOWED_SCORES):
                    failures.append(("torn-read", sorted(scores)))
                elif scores and not scores <= ALLOWED_SCORES:
                    # the pre-swap trained model answers only before the
                    # first swap; after that every score is a version
                    # constant
                    if server.swap_count > 0 and scores & ALLOWED_SCORES:
                        failures.append(("mixed", sorted(scores)))
                n_ok[0] += 1

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        swaps_before = server.swap_count
        for k, m in enumerate(versions):
            server.swap_models([m], version=f"v-{k}", fold_in_events=k)
            # let queries land on this version before the next swap
            deadline_n = n_ok[0] + 20
            while n_ok[0] < deadline_n and not failures:
                pass
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads), "hammer hung"
        assert not failures, failures[:5]
        assert n_ok[0] > 50
        assert server.swap_count - swaps_before == len(versions) >= 3

        st, stats = call(port, "/stats.json")
        assert st == 200
        assert stats["modelSwaps"] >= 4
        assert stats["foldIns"] == 4
        assert stats["foldInEvents"] == sum(range(len(versions)))
        assert stats["modelVersion"] == f"v-{len(versions) - 1}"

        st, metrics = call(port, "/metrics")
        assert st == 200
        assert "pio_engine_model_swaps_total 4" in metrics
        assert "pio_engine_fold_ins_total 4" in metrics
        assert "pio_engine_fold_in_events_total" in metrics

    def test_swap_rejects_wrong_cardinality(self, server):
        with pytest.raises(ValueError):
            server.swap_models([])

    def test_reload_counts_as_swap(self, server):
        before = server.swap_count
        st, _ = call(server.config.port, "/stats.json")
        server.load()   # the /reload body
        assert server.swap_count == before + 1


class TestBatcherExitCounters:
    """The drain-gate vs client-pool attribution counters (a
    serve_avg_batch_size pinned at 8.0 under micro_batch=16 needs to be
    attributable from /stats.json)."""

    def test_serial_traffic_attributes_to_drain_gate(self, server):
        # server fixture has micro_batch=16 by default config
        port = server.config.port
        # distinct num per request: repeats of one query would answer
        # from the result cache (ISSUE 14) without reaching the batcher
        for i in range(6):
            call(port, "/queries.json", {"user": "u1", "num": i + 1})
        st, stats = call(port, "/stats.json")
        assert st == 200
        # a lone closed-loop client: every dispatch closed because
        # nobody else was in flight — the CLIENT POOL is the limit
        assert stats["exitDrainGate"] >= 6
        assert stats["exitFullBatch"] == 0
        assert stats["avgInflightAtDispatch"] <= 1.5
        st, metrics = call(port, "/metrics")
        assert 'pio_engine_batch_exits_total{reason="drain_gate"}' \
            in metrics
        assert "pio_engine_avg_inflight_at_dispatch" in metrics

    def test_stats_counters_consistent(self, server):
        port = server.config.port
        for _ in range(3):
            call(port, "/queries.json", {"user": "u2", "num": 1})
        st, stats = call(port, "/stats.json")
        total = (stats["exitDrainGate"] + stats["exitFullBatch"]
                 + stats["exitWindow"] + stats["exitAdaptive"])
        assert total == stats["batches"]
