"""ISSUE 9: the compile plane — shape-bucket ladder, AOT executable
registry, persistent compilation cache, and the zero-recompile /
warm-before-traffic contracts on the serve and fold paths.

The acceptance criteria these tests pin:
- growth inside a shape bucket across >= 3 consecutive fold ticks
  triggers zero recompiles (asserted via the costmon
  ``pio_compile_executable_seconds_total`` deltas);
- a canary-staged candidate's first served request runs zero XLA
  compiles (the stage-time warm already compiled its buckets);
- the persistent cache answers a simulated process restart (in-memory
  caches cleared, executables deserialized from disk).
"""

import os

import numpy as np
import pytest

from predictionio_tpu.compile import buckets as B
from predictionio_tpu.compile.aot import AOTRegistry, get_aot
from predictionio_tpu.obs import costmon


def _compile_s() -> float:
    return sum(costmon.compile_seconds_by_executable().values())


# ---------------------------------------------------------------------------
# bucket ladder
# ---------------------------------------------------------------------------

class TestBucketLadder:
    def test_bucket_rows_pow2_with_floor(self):
        assert B.bucket_rows(1) == 64
        assert B.bucket_rows(64) == 64
        assert B.bucket_rows(65) == 128
        assert B.bucket_rows(1000) == 1024
        assert B.bucket_rows(5, floor=16) == 16

    def test_bucket_batch(self):
        assert B.bucket_batch(1) == 1
        assert B.bucket_batch(3) == 4
        assert B.bucket_batch(16) == 16
        assert B.bucket_batch(17) == 32

    def test_growth_inside_bucket_is_shape_stable(self):
        for n in range(65, 129):
            assert B.bucket_rows(n) == 128

    def test_promotion_trigger(self):
        bucket = B.bucket_rows(70)          # 128
        assert not B.should_promote(70, bucket)
        assert B.should_promote(int(bucket * B.PROMOTE_AT) + 1, bucket)
        assert B.next_bucket(bucket) == 256

    def test_bucket_key_and_label_canonical(self):
        k1 = B.bucket_key({"u": 64, "b": 4})
        k2 = B.bucket_key({"b": 4, "u": 64})
        assert k1 == k2
        from predictionio_tpu.compile.buckets import bucket_label
        assert bucket_label({"u": 64, "b": 4}) == "b4-u64"


# ---------------------------------------------------------------------------
# AOT registry
# ---------------------------------------------------------------------------

def _demo_builder(n: int):
    import jax

    def impl(x):
        return (x * 2.0).sum()

    return (jax.jit(impl),
            (jax.ShapeDtypeStruct((n,), np.float32),), {})


class TestAOTRegistry:
    def test_ensure_compiles_and_dispatch_hits(self):
        reg = AOTRegistry()
        reg.register("demo", _demo_builder)
        compiled = reg.ensure("demo", {"n": 8})
        assert compiled is not None
        assert reg.lookup("demo", {"n": 8}) is compiled
        out = reg.dispatch("demo", {"n": 8}, lambda x: -1.0,
                           np.ones(8, np.float32))
        assert float(np.asarray(out)) == 16.0
        snap = reg.snapshot()
        assert snap["executablesResident"] == 1
        assert snap["compileCount"] == 1
        assert snap["bucketsCompiled"]["demo"] == ["n8"]

    def test_miss_falls_back_and_unknown_label_is_safe(self):
        reg = AOTRegistry()
        reg.register("demo", _demo_builder)
        out = reg.dispatch("demo", {"n": 4}, lambda x: "fallback",
                           np.ones(4, np.float32))
        # no executable yet: the fallback answered
        assert out == "fallback"
        assert reg.ensure("no-such-label", {"n": 4}) is None

    def test_aval_mismatch_falls_back_correctly(self):
        reg = AOTRegistry()
        reg.register("demo", _demo_builder)
        reg.ensure("demo", {"n": 8})
        # dims say bucket 8, but the caller hands a 6-element array:
        # the Compiled rejects on avals and the fallback serves
        out = reg.dispatch("demo", {"n": 8},
                           lambda x: float(np.asarray(x).sum()),
                           np.ones(6, np.float32))
        assert out == 6.0

    def test_shared_jit_memoized_and_adopt(self):
        reg = AOTRegistry()
        f1 = reg.shared_jit("k", lambda x: x + 1)
        f2 = reg.shared_jit("k", lambda x: x + 2)
        assert f1 is f2                     # first construction wins
        sentinel = object()
        assert reg.adopt("k2", sentinel) is sentinel
        assert reg.adopt("k2", object()) is sentinel
        assert "k" in reg.snapshot()["sharedJits"]

    def test_warm_summary(self):
        reg = AOTRegistry()
        reg.register("demo", _demo_builder)
        out = reg.warm([("demo", {"n": 8}), ("demo", {"n": 8}),
                        ("absent", {"n": 1})])
        assert out["compiled"] == 1
        assert out["skipped"] == 1


# ---------------------------------------------------------------------------
# serve path: vocab growth inside a bucket compiles nothing
# ---------------------------------------------------------------------------

def _als_model(n_users, n_items, rank=6, seed=0):
    from predictionio_tpu.ops.als import ALSModel
    rng = np.random.default_rng(seed)
    return ALSModel(
        user_factors=rng.random((n_users, rank), dtype=np.float32),
        item_factors=rng.random((n_items, rank), dtype=np.float32),
        rank=rank)


class TestServeBuckets:
    def test_growth_inside_bucket_zero_compiles(self):
        from predictionio_tpu.ops.als import users_topk_serve
        # sizes kept under PROMOTE_AT * 64 so no background promotion
        # compile races the delta measurement below
        m1 = _als_model(40, 44)
        s, i = users_topk_serve(m1, [1, 2, 3], 10)   # may compile
        assert np.isfinite(s).any()
        assert i[np.isfinite(s)].max() < 44
        m2 = _als_model(45, 47, seed=1)              # same 64-buckets
        before = _compile_s()
        s2, i2 = users_topk_serve(m2, [4, 5, 6], 10)
        assert _compile_s() == before, \
            "vocab growth inside the bucket must compile nothing"
        assert i2[np.isfinite(s2)].max() < 47

    def test_results_match_unbucketed_ranking(self, monkeypatch):
        from predictionio_tpu.ops.als import _users_topk, users_topk_serve
        from predictionio_tpu.utils.device_cache import cached_put
        # bucketing parity at f32 precision: pin the bit-exact packed
        # readback (the f16 wire default is parity-tested in
        # tests/test_readback.py, ISSUE 19)
        monkeypatch.setenv("PIO_SERVE_PACK", "exact")
        m = _als_model(30, 40, seed=2)
        ixs = [0, 7, 11]
        s_b, i_b = users_topk_serve(m, ixs, 5)
        s_ref, i_ref = _users_topk(
            cached_put(m.user_factors), cached_put(m.item_factors),
            np.asarray(ixs, np.int32), 5)
        s_ref, i_ref = np.asarray(s_ref), np.asarray(i_ref)
        for row in range(3):
            keep = np.isfinite(s_b[row])[:5]
            np.testing.assert_array_equal(i_b[row][:5][keep],
                                          i_ref[row][keep])
            np.testing.assert_allclose(s_b[row][:5][keep],
                                       s_ref[row][keep], rtol=1e-6)

    def test_masked_path_bucketed_matches(self):
        from predictionio_tpu.ops.similarity import masked_top_k_batch
        rng = np.random.default_rng(3)
        table = rng.random((37, 5), dtype=np.float32)
        qv = rng.random((2, 5), dtype=np.float32)
        masks = np.ones((2, 37), dtype=bool)
        masks[0, :10] = False
        s, i = masked_top_k_batch(table, qv, masks, 4,
                                  filter_positive=False)
        assert i[np.isfinite(s)].max() < 37
        assert not np.intersect1d(i[0][np.isfinite(s[0])],
                                  np.arange(10)).size


# ---------------------------------------------------------------------------
# fold path: >= 3 consecutive ticks, zero recompiles (acceptance)
# ---------------------------------------------------------------------------

class TestFoldZeroRecompile:
    def test_three_ticks_growth_inside_bucket(self):
        from predictionio_tpu.online.fold_in import (FoldInConfig,
                                                     fold_in_coo)
        from predictionio_tpu.ops.ratings import RatingsCOO
        cfg = FoldInConfig(sweeps=2)
        model = _als_model(40, 50)

        def coo(nu, ni, seed):
            r = np.random.default_rng(seed)
            return RatingsCOO(r.integers(0, nu, 400).astype(np.int32),
                              r.integers(0, ni, 400).astype(np.int32),
                              r.integers(1, 6, 400).astype(np.float32),
                              nu, ni)

        deltas = []
        for tick in range(4):
            nu, ni = 40 + tick * 3, 50 + tick * 4   # inside 64-buckets
            tu = np.unique(np.random.default_rng(100 + tick)
                           .integers(0, nu, 8))
            ti = np.unique(np.random.default_rng(200 + tick)
                           .integers(0, ni, 8))
            before = _compile_s()
            model, stats = fold_in_coo(model, coo(nu, ni, tick), tu, ti,
                                       cfg, resident_key="cp-test")
            deltas.append(_compile_s() - before)
            if tick:
                assert stats.resident_hit
            # published tables stay exact-sized (bucket padding is a
            # device-residency contract, not part of the model)
            assert model.user_factors.shape == (nu, rank_of(model))

        assert all(d == 0.0 for d in deltas[1:]), (
            f"fold ticks 2..4 must re-dispatch compiled programs, "
            f"compile deltas: {deltas}")

    def test_bucket_promotion_compiles_then_stabilizes(self):
        from predictionio_tpu.online.fold_in import (FoldInConfig,
                                                     fold_in_coo)
        from predictionio_tpu.ops.ratings import RatingsCOO
        cfg = FoldInConfig(sweeps=1)
        model = _als_model(60, 60)

        def run(nu, ni, seed):
            r = np.random.default_rng(seed)
            c = RatingsCOO(r.integers(0, nu, 300).astype(np.int32),
                           r.integers(0, ni, 300).astype(np.int32),
                           r.integers(1, 6, 300).astype(np.float32),
                           nu, ni)
            tu = np.unique(r.integers(0, nu, 8))
            ti = np.unique(r.integers(0, ni, 8))
            return fold_in_coo(model, c, tu, ti, cfg,
                               resident_key="cp-promote")

        model, _ = run(60, 60, 0)
        before = _compile_s()
        # vocab crosses the 64-bucket: promotion compiles new programs
        model, _ = run(70, 80, 1)
        assert _compile_s() > before
        # ... exactly once: the next tick in the new bucket is free
        before = _compile_s()
        model, _ = run(74, 85, 2)
        assert _compile_s() == before


def rank_of(model):
    return model.user_factors.shape[1]


# ---------------------------------------------------------------------------
# persistent cache: simulated process restart
# ---------------------------------------------------------------------------

class TestPersistentCache:
    def test_env_dir_is_the_cache_exactly(self, tmp_path, monkeypatch,
                                          request):
        """$JAX_COMPILATION_CACHE_DIR, when set, IS the cache — no
        salt level under it, and an explicit root= argument loses."""
        import jax
        from predictionio_tpu.compile import cache as C
        placed = tmp_path / "placed"
        monkeypatch.delenv("PIO_XLA_CACHE", raising=False)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(placed))
        request.addfinalizer(C.disable_persistent_cache)
        assert C.enable_persistent_cache() == str(placed)
        assert C.enable_persistent_cache(
            root=str(tmp_path / "elsewhere")) == str(placed)
        assert jax.config.jax_compilation_cache_dir == str(placed)
        assert C.cache_status()["dir"] == str(placed)
        jax.jit(lambda x: (x * 7.0 - 2.0).sum())(
            np.arange(31, dtype=np.float32))
        assert [p for p in placed.iterdir() if p.is_file()], \
            "entries land directly in the placed directory"
        assert not (tmp_path / "elsewhere").exists()

    def test_default_dir_is_in_the_checkout(self, monkeypatch):
        """Unset, the cache is <checkout>/.xla_cache: independent of
        PIO_FS_BASEDIR, $HOME, the pid or any temp name (the directory
        is part of the cache key — a cache that moves never hits)."""
        from predictionio_tpu.compile import cache as C
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        for basedir, home in (("/a/store", "/home/x"), ("/b", "/root")):
            monkeypatch.setenv("PIO_FS_BASEDIR", basedir)
            monkeypatch.setenv("HOME", home)
            assert C.cache_dir() == os.path.join(repo, ".xla_cache")
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".xla_cache/" in f.read().split()

    def test_failed_warm_bucket_is_counted(self):
        """A spec that does not compile is memoised as failed (serving
        falls back to jit) AND reported by warm() — the count the
        deploy-time load turns into a deploy failure."""
        from predictionio_tpu.obs.metrics import MetricsRegistry

        def broken(**dims):
            raise RuntimeError("Mosaic says no")
        reg = AOTRegistry(registry=MetricsRegistry())
        reg.register("broken", broken)
        out = reg.warm([("broken", {"b": 1}), ("absent", {"b": 1})])
        assert (out["compiled"], out["failed"], out["skipped"]) == (0, 1, 1)
        assert reg.snapshot()["failedBuckets"] == 1

    def test_disabled_by_env(self, monkeypatch):
        from predictionio_tpu.compile import cache as C
        monkeypatch.setenv("PIO_XLA_CACHE", "off")
        assert C.enable_persistent_cache() is None
        assert C.cache_status()["disabledByEnv"]

    def test_round_trip_across_simulated_restart(self, tmp_path,
                                                 monkeypatch, request):
        import jax
        from predictionio_tpu.compile import cache as C
        # conftest disables the cache for suite hermeticity; this test
        # IS the cache test — opt back in against a private tmp dir and
        # fully detach afterwards (a latched jax cache dir would make
        # every later compile in the suite write to disk)
        monkeypatch.delenv("PIO_XLA_CACHE", raising=False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        request.addfinalizer(C.disable_persistent_cache)
        d = C.enable_persistent_cache(root=str(tmp_path))
        if d is None:
            pytest.skip("persistent cache unavailable on this backend")
        assert str(tmp_path) in d

        @jax.jit
        def f(x):
            return (x * 3.0 + 1.0).sum() * 0.125

        x = np.arange(97, dtype=np.float32)
        f(x)                                   # compile + write to disk
        assert C.cache_status()["entries"] >= 1
        before = costmon.pcache_totals()
        jax.clear_caches()                     # "restart": RAM caches gone
        f(x)                                   # answered from disk
        after = costmon.pcache_totals()
        assert after["hits"] >= before["hits"] + 1

    def test_clear_removes_entries(self, tmp_path, monkeypatch, request):
        import jax
        from predictionio_tpu.compile import cache as C
        monkeypatch.delenv("PIO_XLA_CACHE", raising=False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        request.addfinalizer(C.disable_persistent_cache)
        d = C.enable_persistent_cache(root=str(tmp_path / "c2"))
        if d is None:
            pytest.skip("persistent cache unavailable on this backend")

        @jax.jit
        def g(x):
            return (x - 0.5).prod()

        g(np.arange(13, dtype=np.float32))
        assert C.cache_status()["entries"] >= 1
        out = C.clear_cache()
        assert out["removed"] >= 1
        assert C.cache_status()["entries"] == 0


# ---------------------------------------------------------------------------
# canary warm: the candidate's first served request compiles nothing
# ---------------------------------------------------------------------------

class _PassServing:
    def supplement(self, q):
        return q

    def serve(self, q, predictions):
        return predictions[0]


class _FakeInstance:
    id = "cp-instance"
    engine_factory = "fake"
    engine_id = None


def _real_server(model, canary_fraction=0.5):
    from predictionio_tpu.models.recommendation import (ALSAlgorithm,
                                                        ALSAlgorithmParams)
    from predictionio_tpu.serving.plugins import EngineServerPluginContext
    from predictionio_tpu.serving.server import EngineServer, ServerConfig
    cfg = ServerConfig(ip="127.0.0.1", port=0, micro_batch=0,
                       canary_fraction=canary_fraction,
                       canary_window_s=60.0, canary_min_requests=1000)
    s = EngineServer(cfg, plugin_context=EngineServerPluginContext())
    s.algorithms = [ALSAlgorithm(ALSAlgorithmParams(rank=4))]
    s.models = [model]
    s.serving = _PassServing()
    s.engine_instance = _FakeInstance()
    return s


def _rec_model(n_users, n_items, rank=4, seed=0):
    from predictionio_tpu.data.bimap import EntityIdIxMap
    from predictionio_tpu.models.recommendation import RecommendationModel
    als = _als_model(n_users, n_items, rank=rank, seed=seed)
    return RecommendationModel(
        als,
        EntityIdIxMap.build([f"u{i}" for i in range(n_users)]),
        EntityIdIxMap.build([f"i{i}" for i in range(n_items)]))


@pytest.fixture()
def warm_on(monkeypatch):
    """conftest disables deploy/swap-time warming for suite speed;
    these tests ARE the warm tests — opt back in."""
    monkeypatch.delenv("PIO_AOT_WARM", raising=False)


class TestCanaryWarm:
    def test_candidate_first_request_zero_compiles(self, warm_on):
        # sizes kept under PROMOTE_AT of their buckets: a background
        # promotion compile landing inside the measured request window
        # would fake a compile delta
        incumbent = _rec_model(40, 44)
        s = _real_server(incumbent)
        # prime the incumbent's bucket (deploy-time warm equivalent)
        s.handle_query_batch([{"user": "u1", "num": 3}])
        # candidate in a NEW vocab bucket: its executables do not exist
        # yet — the stage-time warm must compile them
        candidate = _rec_model(90, 150, seed=1)
        s.swap_models([candidate], version="v2")
        assert s.canary.active
        assert s.last_aot_warm and s.last_aot_warm["compiled"] >= 1
        # first candidate-served request: zero XLA compiles
        for attempt in range(32):
            before = _compile_s()
            out = s.handle_query_batch([{"user": "u1", "num": 3}])
            delta = _compile_s() - before
            if "_pioCanary" in out[0]:
                assert delta == 0.0, (
                    "canary candidate's first request must not "
                    f"compile (delta {delta:.4f}s)")
                break
        else:
            pytest.fail("canary never served a request")

    def test_swap_to_first_query_measured(self, warm_on):
        s = _real_server(_rec_model(40, 50), canary_fraction=0.0)
        s.swap_models([_rec_model(41, 51, seed=2)], version="v3")
        assert s.last_swap_to_first_query_ms is None
        s.handle_query_batch([{"user": "u1", "num": 3}])
        ms = s.last_swap_to_first_query_ms
        assert ms is not None and ms >= 0.0
        # second query must not overwrite the first-query measurement
        s.handle_query_batch([{"user": "u2", "num": 3}])
        assert s.last_swap_to_first_query_ms == ms

    def test_stats_json_surfaces_aot_state(self):
        s = _real_server(_rec_model(40, 50), canary_fraction=0.0)
        s.handle_query_batch([{"user": "u1", "num": 3}])

        class _Req:
            params = {}
            headers = {}

        resp = s._stats(_Req())
        body = resp.body if isinstance(resp.body, dict) else resp.body
        assert "aot" in body and "xlaCache" in body
        assert body["aot"]["executablesResident"] >= 1
        assert "swapToFirstQueryMs" in body


# ---------------------------------------------------------------------------
# warm_models plumbing
# ---------------------------------------------------------------------------

class TestWarmModels:
    def test_warm_models_compiles_ladder(self, warm_on):
        from predictionio_tpu.compile.aot import warm_models
        from predictionio_tpu.models.recommendation import (
            ALSAlgorithm, ALSAlgorithmParams)
        model = _rec_model(200, 300, seed=3)
        algo = ALSAlgorithm(ALSAlgorithmParams(rank=4))
        out = warm_models([algo], [model], batch_hint=8)
        assert out["specs"] >= 4          # b in {1, 2, 4, 8}
        aot = get_aot()
        from predictionio_tpu.ops.als import batch_predict_dims
        for b in (1, 2, 4, 8):
            dims = batch_predict_dims(model.als, b, 16)
            assert aot.lookup("batch_predict", dims) is not None

    def test_warm_models_disabled_by_env(self, monkeypatch):
        from predictionio_tpu.compile.aot import warm_models
        monkeypatch.setenv("PIO_AOT", "off")
        out = warm_models([], [], batch_hint=4)
        assert out.get("disabled")
