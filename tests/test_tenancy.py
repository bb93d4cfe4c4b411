"""Multi-tenant serving host (ISSUE 15): per-tenant HBM accounting,
admission control, LRU eviction back to host mirrors, routing, and the
isolation contracts — cross-tenant result-cache misses, canary state
surviving a neighbor's eviction, and evictions that never fire
mid-dispatch on an in-flight window."""

import datetime as dt
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.core import FirstServing
from predictionio_tpu.data.bimap import BiMap, EntityIdIxMap
from predictionio_tpu.data.storage.base import EngineInstance
from predictionio_tpu.models import recommendation as R
from predictionio_tpu.serving import EngineServer, ServerConfig
from predictionio_tpu.tenancy import (HBMBudgetManager, HostConfig,
                                      ServingHost, TenantSpec,
                                      estimate_padded_bytes)
from predictionio_tpu.utils import device_cache
from predictionio_tpu.utils.device_cache import TableBudgetExceeded

RANK = 8


def _als_model(n_users, n_items, rank=RANK, seed=0, const=None):
    from predictionio_tpu.ops.als import ALSModel
    rng = np.random.default_rng(seed)
    if const is not None:
        u = np.full((n_users, rank), const, dtype=np.float32)
        v = np.ones((n_items, rank), dtype=np.float32)
    else:
        u = rng.standard_normal((n_users, rank)).astype(np.float32)
        v = rng.standard_normal((n_items, rank)).astype(np.float32)
    return ALSModel(user_factors=u, item_factors=v, rank=rank)


def _rec_model(n_users=64, n_items=128, seed=0, const=None):
    als = _als_model(n_users, n_items, seed=seed, const=const)
    user_ix = EntityIdIxMap(BiMap({f"u{i}": i for i in range(n_users)}))
    item_ix = EntityIdIxMap(BiMap({f"i{i}": i for i in range(n_items)}))
    return R.RecommendationModel(als, user_ix, item_ix)


def _slot_server(host, key, model=None, config=None, algo=None):
    """A loaded synthetic EngineServer slot (no storage round-trip)."""
    srv = EngineServer(
        config or ServerConfig(ip="127.0.0.1", port=0),
        engine=R.RecommendationEngineFactory.apply(), tenant=key,
        shared_result_cache=host.result_cache)
    now = dt.datetime.now(dt.timezone.utc)
    srv.engine_instance = EngineInstance(
        id=f"inst-{key}", status="COMPLETED", start_time=now,
        end_time=now, engine_id=key, engine_version="0",
        engine_variant="t", engine_factory="recommendation")
    srv.algorithms = [algo or R.ALSAlgorithm(
        R.ALSAlgorithmParams(rank=RANK))]
    srv.models = [model or _rec_model()]
    srv.serving = FirstServing()
    srv.model_version = f"inst-{key}"
    srv.last_good_version = f"inst-{key}"
    return srv


def _call(port, path, body=None, method=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode() if body is not None else None,
        method=method or ("POST" if body is not None else "GET"))
    with urllib.request.urlopen(req, timeout=15) as resp:
        data = resp.read()
        ct = resp.headers.get("Content-Type", "")
        return resp.status, (json.loads(data) if "json" in ct
                             else data.decode())


@pytest.fixture
def host(mesh8):
    h = ServingHost(HostConfig(ip="127.0.0.1", port=0))
    yield h
    h.stop()


class TestDeviceCacheTenantAttribution:
    def test_scope_tags_uploads_and_evict_frees(self, mesh8):
        device_cache.clear()
        a = np.ones((32, 8), dtype=np.float32)
        b = np.ones((16, 8), dtype=np.float32)
        with device_cache.tenant_scope("ta"):
            device_cache.cached_put(a)
        with device_cache.tenant_scope("tb"):
            device_cache.cached_put(b)
        sizes = device_cache.tenant_sizes()
        assert sizes["ta"] == a.nbytes
        assert sizes["tb"] == b.nbytes
        dropped, freed = device_cache.evict_tenant("ta")
        assert dropped == 1 and freed == a.nbytes
        sizes = device_cache.tenant_sizes()
        assert "ta" not in sizes and sizes["tb"] == b.nbytes
        # the evicted tenant's next put re-uploads and re-tags
        with device_cache.tenant_scope("ta"):
            device_cache.cached_put(a)
        assert device_cache.tenant_sizes()["ta"] == a.nbytes
        device_cache.clear()

    def test_untagged_uploads_stay_unattributed(self, mesh8):
        device_cache.clear()
        a = np.ones((8, 8), dtype=np.float32)
        device_cache.cached_put(a)
        assert device_cache.tenant_sizes() == {}
        assert device_cache.cache_size() == 1
        device_cache.clear()

    def test_resident_slots_tagged_and_evicted(self, mesh8):
        import jax
        device_cache.clear()
        key_arr = np.ones((4, 4), dtype=np.float32)
        payload = {"U": jax.device_put(key_arr)}
        with device_cache.tenant_scope("tr"):
            device_cache.put_resident("slot:tr", (key_arr,), payload)
        assert device_cache.tenant_sizes()["tr"] == key_arr.nbytes
        dropped, freed = device_cache.evict_tenant("tr")
        assert dropped == 1 and freed == key_arr.nbytes
        assert device_cache.get_resident("slot:tr", (key_arr,)) is None
        device_cache.clear()

    def test_gc_of_host_array_untags(self, mesh8):
        device_cache.clear()
        # 4 bytes off any 16-byte boundary, on purpose: the CPU backend
        # aliases a 64-byte-aligned host buffer instead of copying it,
        # the cached device array then keeps the host array alive, and
        # this test failed whenever malloc happened to hand out such a
        # buffer (4 runs in 12 at the seed). A TPU always copies.
        a = np.ones(8 * 8 + 1, dtype=np.float32)[1:].reshape(8, 8)
        with device_cache.tenant_scope("tg"):
            device_cache.cached_put(a)
        assert device_cache.tenant_sizes()["tg"] == a.nbytes
        del a
        import gc
        gc.collect()
        assert device_cache.tenant_sizes() == {}
        device_cache.clear()


class TestBudgetManager:
    def test_estimate_counts_padded_buckets(self):
        from predictionio_tpu.compile import buckets as B
        m = _rec_model(n_users=100, n_items=300)
        est = estimate_padded_bytes([m])
        expect = (B.bucket_rows(100) + B.bucket_rows(300)) * RANK * 4
        assert est == expect

    def test_admit_refuses_never_fits(self):
        mgr = HBMBudgetManager(budget_bytes=1024)
        with pytest.raises(TableBudgetExceeded, match="NEVER fit"):
            mgr.admit("big", [_rec_model(n_users=512, n_items=512)])
        # and a refused tenant leaves no state behind
        assert mgr.snapshot()["tenants"] == {}

    def test_admit_within_budget_and_snapshot(self):
        mgr = HBMBudgetManager(budget_bytes=1 << 30)
        mgr.admit("ok", [_rec_model()], priority=2, pinned=True)
        snap = mgr.snapshot()["tenants"]["ok"]
        assert snap["pinned"] and snap["priority"] == 2
        assert snap["expectedPaddedBytes"] > 0

    def test_ensure_room_evicts_coldest_unpinned(self, mesh8):
        device_cache.clear()
        mgr = HBMBudgetManager(budget_bytes=10_000)
        arrs = {}
        for t in ("cold", "warm", "pinned"):
            arrs[t] = np.ones((64, 8), dtype=np.float32)  # 2 KiB each
            mgr.admit(t, [], pinned=(t == "pinned"))
            with device_cache.tenant_scope(t):
                device_cache.cached_put(arrs[t])
        mgr.admit("incoming", [_rec_model(n_users=128, n_items=128)])
        mgr.touch("cold")
        time.sleep(0.01)
        mgr.touch("warm")
        # incoming expects 2*128 rows * 8 * 4 = 8 KiB; resident = 6 KiB
        # -> must evict the LRU-coldest unpinned tenants until it fits
        n = mgr.ensure_room("incoming")
        assert n >= 1
        sizes = mgr.sizes()
        assert "cold" not in sizes or sizes["cold"] == 0
        assert sizes.get("pinned", 0) > 0   # pinned never auto-evicts
        device_cache.clear()

    def test_no_budget_means_accounting_only(self, mesh8):
        device_cache.clear()
        mgr = HBMBudgetManager(budget_bytes=None)
        mgr.admit("t", [_rec_model(n_users=4096, n_items=4096)])
        assert mgr.ensure_room("t") == 0
        # operator eviction still works without a budget
        with device_cache.tenant_scope("t"):
            device_cache.cached_put(np.ones((8, 8), dtype=np.float32))
        out = mgr.evict("t")
        assert out["bytesFreed"] == 8 * 8 * 4
        device_cache.clear()


class TestServingHostRouting:
    def test_routes_by_key_and_isolates_results(self, host):
        # two tenants with CONSTANT but different factors: any cross-
        # tenant leak (cache or model) is visible in the scores
        host.admit_server(TenantSpec(key="a", engine_id="a"),
                          _slot_server(host, "a", _rec_model(const=1.0)))
        host.admit_server(TenantSpec(key="b", engine_id="b"),
                          _slot_server(host, "b", _rec_model(const=2.0)))
        host.start()
        port = host.config.port
        q = {"user": "u1", "num": 3}
        st, out_a = _call(port, "/engines/a/queries.json", q)
        st2, out_b = _call(port, "/engines/b/queries.json", q)
        assert st == st2 == 200
        assert {s["score"] for s in out_a["itemScores"]} == {RANK * 1.0}
        assert {s["score"] for s in out_b["itemScores"]} == {RANK * 2.0}
        # repeat the BYTE-IDENTICAL query: each tenant answers from its
        # own namespace (zero cross-tenant hits by construction)
        st, out_a2 = _call(port, "/engines/a/queries.json", q)
        assert out_a2 == out_a
        stats = host.result_cache.stats()
        assert stats["hits"] >= 1
        st, out_b2 = _call(port, "/engines/b/queries.json", q)
        assert out_b2 == out_b != out_a

    def test_unknown_tenant_404(self, host):
        host.start()
        with pytest.raises(urllib.error.HTTPError) as ei:
            _call(host.config.port, "/engines/nope/queries.json",
                  {"user": "u1", "num": 1})
        assert ei.value.code == 404

    def test_stats_and_metrics_surfaces(self, host):
        host.admit_server(TenantSpec(key="a", engine_id="a"),
                          _slot_server(host, "a"))
        host.start()
        port = host.config.port
        _call(port, "/engines/a/queries.json", {"user": "u1", "num": 2})
        st, stats = _call(port, "/stats.json")
        assert st == 200
        assert "a" in stats["tenants"]
        t = stats["tenants"]["a"]
        assert t["requests"] == 1
        assert t["modelVersion"] == "inst-a"
        assert "hbmBytes" in t and "expectedPaddedBytes" in t
        assert "budgetBytes" in stats["budget"]
        st, tl = _call(port, "/tenants.json")
        assert set(tl["tenants"]) == {"a"}
        st, mtx = _call(port, "/metrics")
        assert 'pio_tenant_requests_total{tenant="a"} 1' in mtx
        assert 'pio_engine_hbm_bytes{tenant="a"}' in mtx
        assert "pio_host_tenants 1" in mtx
        # per-tenant delegated stats carry the tenant tag
        st, ts = _call(port, "/engines/a/stats.json")
        assert ts["tenant"] == "a" and ts["requestCount"] == 1

    def test_hbm_gauge_sums_to_measured_resident_bytes(self, host):
        device_cache.clear()
        host.admit_server(TenantSpec(key="a", engine_id="a"),
                          _slot_server(host, "a"))
        host.admit_server(TenantSpec(key="b", engine_id="b"),
                          _slot_server(host, "b", _rec_model(
                              n_users=32, n_items=64)))
        host.start()
        port = host.config.port
        for k in ("a", "b"):
            _call(port, f"/engines/{k}/queries.json",
                  {"user": "u1", "num": 2})
        sizes = host.budget.sizes()
        assert sizes["a"] > 0 and sizes["b"] > 0
        # the gauge's samples == device_cache's measured tagged bytes
        # (+ sharded handles, none here)
        assert sizes == {**device_cache.tenant_sizes()}
        assert sum(sizes.values()) \
            == host.budget.snapshot()["residentBytes"]

    def test_bad_tenant_keys_refused(self, host):
        for bad in ("", "a/b", "a\x1fb"):
            with pytest.raises(ValueError):
                host.admit_server(TenantSpec(key=bad, engine_id="x"),
                                  _slot_server(host, bad or "x"))

    def test_admit_server_requires_matching_tenant_tag(self, host):
        srv = _slot_server(host, "right")
        with pytest.raises(ValueError, match="tenant"):
            host.admit_server(TenantSpec(key="wrong", engine_id="x"),
                              srv)


class TestEvictionCorrectness:
    def test_evict_readmit_serves_byte_identical(self, host):
        # cache OFF for this slot: the second serve must RECOMPUTE from
        # re-uploaded tables, not answer from stored bytes
        cfg = ServerConfig(ip="127.0.0.1", port=0, result_cache=False)
        host.admit_server(TenantSpec(key="a", engine_id="a"),
                          _slot_server(host, "a", config=cfg))
        host.start()
        port = host.config.port
        q = {"user": "u2", "num": 5}
        st, before = _call(port, "/engines/a/queries.json", q)
        assert host.budget.sizes().get("a", 0) > 0
        out = host.evict_tenant("a")
        assert out["bytesFreed"] > 0
        assert host.budget.sizes().get("a", 0) == 0
        st, after = _call(port, "/engines/a/queries.json", q)
        assert after == before    # host mirrors are the truth
        assert host.budget.sizes().get("a", 0) > 0   # re-resident
        # and the eviction counter moved
        st, mtx = _call(port, "/metrics")
        assert ('pio_tenant_evictions_total{tenant="a",'
                'reason="operator"} 1') in mtx

    def test_eviction_waits_for_inflight_window(self, host):
        release = threading.Event()
        entered = threading.Event()

        class SlowAlgo(R.ALSAlgorithm):
            def predict(self, model, query):
                entered.set()
                release.wait(timeout=10)
                return super().predict(model, query)

        cfg = ServerConfig(ip="127.0.0.1", port=0, result_cache=False,
                           micro_batch=1)   # direct path, no batcher
        host.admit_server(
            TenantSpec(key="a", engine_id="a"),
            _slot_server(host, "a",
                         algo=SlowAlgo(R.ALSAlgorithmParams(rank=RANK)),
                         config=cfg))
        host.start()
        port = host.config.port
        results = []

        def query():
            results.append(_call(port, "/engines/a/queries.json",
                                 {"user": "u1", "num": 2}))

        t = threading.Thread(target=query)
        t.start()
        assert entered.wait(timeout=10)
        # window in flight: a SHORT quiesce budget must SKIP the drop
        host.config.evict_quiesce_timeout_s = 0.05
        out = host.evict_tenant("a")
        assert out["bytesFreed"] == 0   # never fires mid-dispatch
        release.set()
        t.join(timeout=10)
        assert results and results[0][0] == 200
        # drained now: the same eviction succeeds
        host.config.evict_quiesce_timeout_s = 10.0
        sizes_before = host.budget.sizes().get("a", 0)
        out = host.evict_tenant("a")
        assert out["bytesFreed"] == sizes_before > 0

    def test_neighbor_eviction_preserves_canary_state(self, host):
        cfg = ServerConfig(ip="127.0.0.1", port=0,
                           canary_fraction=0.5, canary_window_s=3600,
                           canary_min_requests=10**6)
        slot_a = host.admit_server(
            TenantSpec(key="a", engine_id="a"),
            _slot_server(host, "a", _rec_model(const=1.0), config=cfg))
        host.admit_server(TenantSpec(key="b", engine_id="b"),
                          _slot_server(host, "b", _rec_model(const=2.0)))
        host.start()
        port = host.config.port
        # stage a canary candidate on tenant A
        slot_a.server.swap_models([_rec_model(const=3.0)],
                                  version="cand-a")
        assert slot_a.server.canary.active
        _call(port, "/engines/b/queries.json", {"user": "u1", "num": 2})
        host.evict_tenant("b")
        # tenant A's canary, lineage and rollback anchors are untouched
        assert slot_a.server.canary.active
        st = slot_a.server.canary.stats()
        assert st["candidateVersion"] == "cand-a"
        assert slot_a.server.last_good_version == "inst-a"
        # A still serves a mix of incumbent/candidate constants only
        scores = set()
        for _ in range(6):
            _st, out = _call(port, "/engines/a/queries.json",
                             {"user": "u1", "num": 1})
            scores |= {s["score"] for s in out["itemScores"]}
        assert scores <= {RANK * 1.0, RANK * 3.0}

    def test_fold_swap_invalidates_only_own_tenant(self, host):
        slot_a = host.admit_server(
            TenantSpec(key="a", engine_id="a"),
            _slot_server(host, "a", _rec_model(const=1.0)))
        host.admit_server(TenantSpec(key="b", engine_id="b"),
                          _slot_server(host, "b", _rec_model(const=2.0)))
        host.start()
        port = host.config.port
        q = {"user": "u1", "num": 2}
        _call(port, "/engines/a/queries.json", q)
        _call(port, "/engines/b/queries.json", q)
        hits0 = host.result_cache.stats()["hits"]
        # tenant A's fold tick touches u1: drops ONLY A's entry
        slot_a.server.swap_models([_rec_model(const=4.0)],
                                  version="v2-a",
                                  touched_entities={"user": ["u1"]})
        st, out_b = _call(port, "/engines/b/queries.json", q)
        assert host.result_cache.stats()["hits"] == hits0 + 1
        assert {s["score"] for s in out_b["itemScores"]} == {RANK * 2.0}
        st, out_a = _call(port, "/engines/a/queries.json", q)
        assert {s["score"] for s in out_a["itemScores"]} == {RANK * 4.0}


class TestRemoveTenant:
    def test_remove_frees_and_unroutes(self, host):
        host.admit_server(TenantSpec(key="a", engine_id="a"),
                          _slot_server(host, "a"))
        host.start()
        port = host.config.port
        _call(port, "/engines/a/queries.json", {"user": "u1", "num": 2})
        assert host.budget.sizes().get("a", 0) > 0
        assert host.remove_tenant("a")
        assert host.budget.sizes().get("a", 0) == 0
        assert "a" not in host.budget.snapshot()["tenants"]
        with pytest.raises(urllib.error.HTTPError) as ei:
            _call(port, "/engines/a/queries.json",
                  {"user": "u1", "num": 1})
        assert ei.value.code == 404
        assert not host.remove_tenant("a")   # idempotent


class TestTenantsCLI:
    def test_list_status_evict_pin(self, host, capsys):
        from predictionio_tpu.tools.cli import main as cli_main
        host.admit_server(TenantSpec(key="a", engine_id="a"),
                          _slot_server(host, "a"))
        host.start()
        url = f"http://127.0.0.1:{host.config.port}"
        _call(host.config.port, "/engines/a/queries.json",
              {"user": "u1", "num": 2})
        assert cli_main(["tenants", "list", "--url", url]) == 0
        out = capsys.readouterr().out
        assert "1 tenant(s)" in out and "a " in out
        assert cli_main(["tenants", "status", "a", "--url", url]) == 0
        out = capsys.readouterr().out
        assert '"modelVersion": "inst-a"' in out
        assert cli_main(["tenants", "pin", "a", "--url", url]) == 0
        capsys.readouterr()
        assert host.budget.snapshot()["tenants"]["a"]["pinned"]
        assert cli_main(["tenants", "unpin", "a", "--url", url]) == 0
        capsys.readouterr()
        assert cli_main(["tenants", "evict", "a", "--url", url]) == 0
        out = capsys.readouterr().out
        assert '"bytesFreed"' in out
        assert host.budget.sizes().get("a", 0) == 0
        # unknown tenant -> nonzero exit
        assert cli_main(["tenants", "evict", "zz", "--url", url]) == 1
        capsys.readouterr()


class TestAccountingDedup:
    """Review hardening: a fold tick attaches the SAME device arrays
    to its ShardedTables and its residency payload; counting them via
    both the tagged residency slot and the slot's sizer would double
    the gauge and make ensure_room evict neighbors that fit."""

    def test_sizes_identity_dedups_sizer_vs_residency(self, mesh8):
        import jax
        device_cache.clear()
        key_arr = np.ones((16, 4), dtype=np.float32)
        dev = jax.device_put(key_arr)
        with device_cache.tenant_scope("td"):
            device_cache.put_resident("fold:td", (key_arr,),
                                      {"U": dev})
        mgr = HBMBudgetManager(budget_bytes=None)
        mgr.admit("td", [], sizer=lambda: [dev])
        # one array, two accounting sources -> counted ONCE
        assert mgr.sizes()["td"] == key_arr.nbytes
        device_cache.clear()

    def test_evict_tenant_freed_bytes_deduped(self, mesh8):
        import jax
        device_cache.clear()
        key_arr = np.ones((16, 4), dtype=np.float32)
        with device_cache.tenant_scope("td"):
            dev = device_cache.cached_put(key_arr)
            device_cache.put_resident("fold:td", (key_arr,),
                                      {"U": dev})
        dropped, freed = device_cache.evict_tenant("td")
        assert dropped == 2            # cache entry + residency slot
        assert freed == key_arr.nbytes  # ...but the ARRAY counts once
        device_cache.clear()


class TestGenerationFenceIsolation:
    """Review hardening: the store-time freshness fence is per
    NAMESPACE — tenant A's fold cadence must not refuse tenant B's
    concurrent stores (nothing in B's namespace changed)."""

    def test_neighbor_invalidation_does_not_refuse_store(self):
        from predictionio_tpu.serving.result_cache import (
            ResultCache, TenantResultCache, query_key)
        inner = ResultCache(max_entries=64, max_bytes=1 << 20)
        a = TenantResultCache(inner, "ta")
        b = TenantResultCache(inner, "tb")
        gen_b = b.generation          # B snapshots, starts computing
        a.invalidate_entities(["user:u1"])   # A's fold tick lands
        a.invalidate_all("reload")
        key = query_key({"user": "u9", "num": 1})
        assert b.put(key, b"B", (), generation=gen_b)   # NOT refused
        assert b.get(key) == b"B"
        # B's OWN invalidation still fences B's stale store
        gen_b2 = b.generation
        b.invalidate_entities(["user:u9"])
        assert not b.put(key, b"B2", (), generation=gen_b2)
