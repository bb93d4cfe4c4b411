"""Sharded online plane (ISSUE 12): model-sharded factor tables across
fold -> publish -> serve, with exact parity against the replicated path.

Pins the acceptance contracts that don't need the over-budget scale
harness (tests/test_sharded_scale.py, slow lane):

- fold-tick factor parity <= 1e-5 across 3 consecutive ticks, with
  residency hits and O(touched) steady-state upload bytes;
- serve top-k identical ids/scores vs the replicated path (plain,
  masked, and single-query routes);
- zero recompiles across steady-state sharded ticks (the PR 9
  acceptance extended to the sharded executables);
- quality gates run REAL verdicts against sharded candidates (no
  silent skip), and the golden replay answers through the same
  batched sharded serve executables;
- device-cache/residency sharding keys: replicated and sharded
  payloads of one host array can never alias;
- hot-swap of sharded versions is torn-read-free under hammer load;
- host_fetch refuses sharded arrays by NAMING host_fetch_sharded,
  and host_fetch_sharded round-trips the per-shard slices.
"""

import dataclasses
import json
import pickle
import threading
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.compile import buckets as B
from predictionio_tpu.obs import costmon, jaxmon
from predictionio_tpu.online.fold_in import FoldInConfig, fold_in_coo
from predictionio_tpu.ops.als import (ALSConfig, ALSModel, als_train,
                                      users_topk_serve)
from predictionio_tpu.ops.ratings import RatingsCOO
from predictionio_tpu.parallel.mesh import (host_fetch,
                                            host_fetch_sharded,
                                            model_mesh)
from predictionio_tpu.parallel.sharded_table import (ShardedTable,
                                                     is_sharded,
                                                     layout_of,
                                                     sharding_meta,
                                                     table_rows)
from predictionio_tpu.utils import device_cache

N_SHARDS = 4


def _train(n_users=96, n_items=180, rank=8, seed=3):
    rng = np.random.default_rng(seed)
    nnz = 1500
    coo = RatingsCOO(rng.integers(0, n_users, nnz),
                     rng.integers(0, n_items, nnz),
                     rng.uniform(1, 5, nnz).astype(np.float32),
                     n_users, n_items)
    model = als_train(coo, ALSConfig(rank=rank, iterations=3, seed=seed))
    return model, coo


def _sharded_copy(model: ALSModel) -> ALSModel:
    return ALSModel(
        ShardedTable.from_host(model.user_factors, N_SHARDS),
        ShardedTable.from_host(model.item_factors, N_SHARDS),
        model.rank)


# ---------------------------------------------------------------------------
# ShardedTable unit surface
# ---------------------------------------------------------------------------

class TestShardedTable:
    def test_shape_rows_to_numpy(self):
        arr = np.arange(120 * 4, dtype=np.float32).reshape(120, 4)
        t = ShardedTable.from_host(arr, N_SHARDS)
        assert t.shape == (120, 4)
        assert t.padded_rows % N_SHARDS == 0
        np.testing.assert_array_equal(t.to_numpy(), arr)
        np.testing.assert_array_equal(t.rows([0, 119, 60]),
                                      arr[[0, 119, 60]])

    def test_with_rows_copy_on_write(self):
        arr = np.zeros((256, 4), dtype=np.float32)
        t = ShardedTable.from_host(arr, N_SHARDS)     # 64 rows/shard
        t2 = t.with_rows([3, 200], np.ones((2, 4), np.float32))
        np.testing.assert_array_equal(t2.rows([3])[0], np.ones(4))
        np.testing.assert_array_equal(t.rows([3])[0], np.zeros(4))
        # untouched shards are SHARED, touched ones copied
        assert t2.shards[1] is t.shards[1]
        assert t2.shards[0] is not t.shards[0]
        assert t2.shards[3] is not t.shards[3]

    def test_grown_repartitions(self):
        arr = np.arange(100 * 2, dtype=np.float32).reshape(100, 2)
        t = ShardedTable.from_host(arr, N_SHARDS)
        g = t.grown(130, t.padded_rows * 2)
        assert g.padded_rows == t.padded_rows * 2
        assert g.n_rows == 130
        np.testing.assert_array_equal(g.to_numpy()[:100], arr)

    def test_pickle_drops_device_handle(self, mesh8):
        arr = np.ones((64, 4), dtype=np.float32)
        t = ShardedTable.from_host(arr, N_SHARDS)
        t.device(model_mesh(N_SHARDS))
        assert t._dev is not None
        t2 = pickle.loads(pickle.dumps(t))
        assert t2._dev is None
        np.testing.assert_array_equal(t2.to_numpy(), t.to_numpy())

    def test_finite_and_norm_see_logical_rows_only(self):
        arr = np.full((10, 2), 2.0, dtype=np.float32)
        t = ShardedTable.from_host(arr, 2, padded_rows=16)
        assert t.all_finite()
        assert t.max_row_norm() == pytest.approx(np.sqrt(8.0))
        bad = t.with_rows([4], np.array([[np.nan, 1.0]], np.float32))
        assert not bad.all_finite()

    def test_partial_coverage_guards(self):
        """A multi-process follower holds only SOME shards: row
        lookups outside them must raise, never wrap into the wrong
        shard (searchsorted-1 = -1 would silently read the LAST
        shard), and whole-table ops must refuse."""
        partial = ShardedTable(
            [np.full((64, 2), 7.0, dtype=np.float32)],
            offsets=[64], n_rows=250, padded_rows=256, n_shards=4)
        np.testing.assert_array_equal(partial.rows([64, 127])[0],
                                      np.full(2, 7.0))
        with pytest.raises(IndexError):
            partial.rows([10])           # precedes the held slice
        with pytest.raises(IndexError):
            partial.rows([200])          # past the held slice
        with pytest.raises(IndexError):
            partial.with_rows([10], np.zeros((1, 2), np.float32))
        with pytest.raises(ValueError):
            partial.to_numpy()
        with pytest.raises(ValueError):
            partial.grown(300, 512)

    def test_device_at_larger_bucket_zero_fills(self, mesh8):
        """Serve-time upload at a covering bucket larger than the
        table's own padding: the tail rows are zeros, the handle is
        cached at the target shape, and the table object is untouched
        (the serve path never mutates a published model)."""
        arr = np.ones((40, 4), dtype=np.float32)
        t = ShardedTable.from_host(arr, N_SHARDS, padded_rows=48)
        mesh = model_mesh(N_SHARDS)
        dev = t.device(mesh, target_rows=64)
        assert dev.shape == (64, 4)
        assert t.padded_rows == 48      # mirrors untouched
        host = np.asarray(dev)
        np.testing.assert_array_equal(host[:40], arr)
        np.testing.assert_array_equal(host[48:], np.zeros((16, 4)))
        assert t.device(mesh, target_rows=64) is dev   # cached

    def test_layout_and_meta_helpers(self):
        arr = np.ones((8, 2), dtype=np.float32)
        t = ShardedTable.from_host(arr, 2)
        assert layout_of(t) == "model:2"
        assert layout_of(arr) == "replicated"
        m = ALSModel(t, t, 2)
        assert sharding_meta([m]) == {"layout": "model", "shards": 2}
        assert sharding_meta([ALSModel(arr, arr, 2)]) is None
        np.testing.assert_array_equal(table_rows(t, [1]),
                                      table_rows(arr, [1]))


# ---------------------------------------------------------------------------
# host_fetch / host_fetch_sharded (satellite)
# ---------------------------------------------------------------------------

class TestHostFetchSharded:
    def test_roundtrip_per_shard_slices(self, mesh8):
        mesh = model_mesh(N_SHARDS)
        V = np.random.default_rng(0).standard_normal(
            (128, 4)).astype(np.float32)
        dev = ShardedTable.from_host(V, N_SHARDS).device(mesh)
        offsets, slices = host_fetch_sharded(dev)
        assert offsets == [0, 32, 64, 96]
        np.testing.assert_allclose(np.concatenate(slices), V)

    def test_refuses_non_dim0_shardings(self, mesh8):
        """An array sharded on a LATER dim has every shard at row
        offset 0 — deduping by offset would silently hand back one
        partial shard as the whole value. Must refuse loudly."""
        import jax
        mesh = model_mesh(N_SHARDS)
        arr = np.ones((8, 64), dtype=np.float32)
        dev = jax.device_put(arr, mesh.sharding(None, "model"))
        with pytest.raises(ValueError, match="dim 0"):
            host_fetch_sharded(dev)

    def test_host_fetch_error_names_sibling(self, mesh8):
        import jax
        mesh = model_mesh(N_SHARDS)
        arr = np.zeros((64, 2), dtype=np.float32)
        dev = jax.device_put(arr, mesh.model_sharded(2))
        fetched = host_fetch(dev)   # fully addressable single-process
        np.testing.assert_array_equal(fetched, arr)
        # the refusal path (multi-process) must point at the sibling:
        # simulate it by checking the message contract directly
        class _Fake:
            is_fully_addressable = False
            shape = (64, 2)

            def addressable_data(self, i):
                return np.zeros((16, 2), dtype=np.float32)

        with pytest.raises(ValueError, match="host_fetch_sharded"):
            host_fetch(_Fake())


# ---------------------------------------------------------------------------
# device cache + residency sharding keys (satellite)
# ---------------------------------------------------------------------------

class TestShardingKeyedCache:
    def test_replicated_and_sharded_puts_coexist(self, mesh8):
        mesh = model_mesh(N_SHARDS)
        arr = np.random.default_rng(1).standard_normal(
            (64, 4)).astype(np.float32)
        plain = device_cache.cached_put_rows(arr, 64)
        sharded = device_cache.cached_put_rows(
            arr, 64, sharding=mesh.model_sharded(2))
        assert plain is not sharded
        assert plain.sharding != sharded.sharding
        # each layout hits its OWN entry on re-put
        assert device_cache.cached_put_rows(arr, 64) is plain
        assert device_cache.cached_put_rows(
            arr, 64, sharding=mesh.model_sharded(2)) is sharded

    def test_equal_shardings_share_one_entry(self, mesh8):
        mesh = model_mesh(N_SHARDS)
        arr = np.ones((64, 4), dtype=np.float32)
        a = device_cache.cached_put_rows(arr, 64,
                                         sharding=mesh.model_sharded(2))
        b = device_cache.cached_put_rows(arr, 64,
                                         sharding=mesh.model_sharded(2))
        assert a is b

    def test_residency_keyed_by_sharding_token(self):
        key_arr = np.ones((4, 2), dtype=np.float32)
        device_cache.put_resident("shard_test", (key_arr,),
                                  {"x": 1}, sharding="replicated")
        assert device_cache.get_resident(
            "shard_test", (key_arr,), sharding="replicated") == {"x": 1}
        # the latent aliasing bug: a replicated hit must NOT answer a
        # sharded lookup of the same host array (or vice versa)
        assert device_cache.get_resident(
            "shard_test", (key_arr,), sharding="model:4") is None
        device_cache.put_resident("shard_test", (key_arr,),
                                  {"x": 2}, sharding="model:4")
        assert device_cache.get_resident(
            "shard_test", (key_arr,), sharding="model:4") == {"x": 2}
        device_cache.drop_resident("shard_test")

    def test_table_budget_enforced_on_replicated_upload(self, monkeypatch):
        arr = np.zeros((1024, 8), dtype=np.float32)   # 32 KiB
        monkeypatch.setenv("PIO_TABLE_BUDGET_BYTES", "16384")
        with pytest.raises(device_cache.TableBudgetExceeded):
            device_cache.cached_put_rows(arr, 1024)
        # a 4-way sharded layout costs 8 KiB/device: admitted
        t = ShardedTable.from_host(arr, N_SHARDS)
        assert t.per_shard_nbytes <= 16384
        t.device(model_mesh(N_SHARDS))

    def test_per_device_bytes_for_sharded_residency(self, mesh8):
        mesh = model_mesh(N_SHARDS)
        arr = np.zeros((256, 8), dtype=np.float32)
        t = ShardedTable.from_host(arr, N_SHARDS)
        dev = t.device(mesh)
        key = np.ones(1, dtype=np.float32)
        device_cache.put_resident("hbm_test", (key,), {"T": dev},
                                  sharding="model:4")
        sizes = device_cache.resident_sizes()
        # the gauge reads ~1/N of the table per device (ALX scale-out,
        # directly observable via pio_hbm_table_bytes{table})
        assert sizes["hbm_test"] == arr.nbytes // N_SHARDS
        device_cache.drop_resident("hbm_test")


# ---------------------------------------------------------------------------
# fold parity: 3 consecutive ticks, factors <= 1e-5, O(touched) uploads
# ---------------------------------------------------------------------------

class TestShardedFoldParity:
    @pytest.mark.parametrize("implicit", [False, True])
    def test_three_ticks_match_replicated(self, mesh8, implicit):
        model, coo = _train()
        sharded = _sharded_copy(model)
        cfg_r = FoldInConfig(sweeps=2, implicit_prefs=implicit)
        cfg_s = dataclasses.replace(cfg_r, factor_sharding="model")
        rng = np.random.default_rng(7)
        cur_r, cur_s = model, sharded
        for tick in range(3):
            tu = rng.integers(0, coo.n_users, 5)
            ti = rng.integers(0, coo.n_items, 8)
            h0 = jaxmon.thread_h2d_total()
            cur_r, st_r = fold_in_coo(cur_r, coo, tu, ti, cfg_r,
                                      resident_key=f"rep_{implicit}")
            h_replicated = jaxmon.h2d_delta(h0)
            h0 = jaxmon.thread_h2d_total()
            cur_s, st_s = fold_in_coo(cur_s, coo, tu, ti, cfg_s,
                                      resident_key=f"shd_{implicit}")
            h_sharded = jaxmon.h2d_delta(h0)
            assert st_s.sharded and not st_r.sharded
            assert is_sharded(cur_s.user_factors)
            np.testing.assert_allclose(
                cur_s.user_factors.to_numpy(), cur_r.user_factors,
                atol=1e-5)
            np.testing.assert_allclose(
                cur_s.item_factors.to_numpy(), cur_r.item_factors,
                atol=1e-5)
            if tick > 0:
                assert st_s.resident_hit, "steady tick must be resident"
                # O(touched-row plans), never a table gather: the
                # sharded steady tick uploads exactly the plan bytes
                # the replicated one does — a table re-upload would
                # add padded_rows * rank * 4 on top. (The absolute
                # plans << table bound is the scale test's job —
                # tests/test_sharded_scale.py — where the table
                # actually dwarfs a touched-row plan.)
                assert h_sharded == h_replicated

    def test_vocab_growth_inside_bucket(self, mesh8):
        model, coo = _train(n_users=90, n_items=170)
        sharded = _sharded_copy(model)
        cfg = FoldInConfig(sweeps=1, factor_sharding="model")
        # new users rate EXISTING items (and new items get existing
        # raters): a brand-new (user, item) PAIR needs the 2-sweep
        # bootstrap and would legitimately stay zero under sweeps=1
        grown = RatingsCOO(
            np.concatenate([coo.user_idx, [90, 91, 0, 1]]),
            np.concatenate([coo.item_idx, [0, 1, 170, 171]]),
            np.concatenate([coo.rating,
                            [3.0, 4.0, 5.0, 2.0]]).astype(np.float32),
            92, 172)
        out, st = fold_in_coo(sharded, grown, [90, 91, 0, 1],
                              [170, 171, 0, 1], cfg)
        assert out.n_users == 92 and out.n_items == 172
        assert out.user_factors.padded_rows \
            == sharded.user_factors.padded_rows  # same bucket
        assert np.abs(out.user_factors.rows([90, 91])).sum() > 0

    def test_bucket_promotion_repartitions(self, mesh8):
        model, coo = _train(n_users=60, n_items=120)
        sharded = _sharded_copy(model)
        old_bucket = sharded.user_factors.padded_rows
        n_new = old_bucket + 8
        ui = np.concatenate([coo.user_idx, np.arange(60, n_new)])
        ii = np.concatenate([coo.item_idx,
                             np.zeros(n_new - 60, dtype=np.int64)])
        vals = np.concatenate(
            [coo.rating, np.full(n_new - 60, 3.0, np.float32)])
        grown = RatingsCOO(ui, ii, vals.astype(np.float32),
                           n_new, coo.n_items)
        out, st = fold_in_coo(sharded, grown,
                              list(range(60, n_new)), [0],
                              FoldInConfig(factor_sharding="model"))
        assert out.user_factors.padded_rows > old_bucket
        assert out.user_factors.padded_rows % N_SHARDS == 0
        assert out.n_users == n_new


# ---------------------------------------------------------------------------
# serve parity + zero recompile
# ---------------------------------------------------------------------------

class TestShardedServeParity:
    def test_users_topk_identical_ids_and_scores(self, mesh8):
        model, _ = _train(seed=11)
        sharded = _sharded_copy(model)
        ixs = [0, 17, 33, 95]
        s_r, i_r = users_topk_serve(model, ixs, 12)
        s_s, i_s = users_topk_serve(sharded, ixs, 12)
        for row in range(len(ixs)):
            fr, fs = np.isfinite(s_r[row]), np.isfinite(s_s[row])
            np.testing.assert_array_equal(i_r[row][fr][:12],
                                          i_s[row][fs][:12])
            np.testing.assert_allclose(s_r[row][fr][:12],
                                       s_s[row][fs][:12], atol=1e-5)

    def test_sharded_route_sits_on_the_serve_rung(self, mesh8):
        """ISSUE 32: over 2^16 rows the sharded table, the sharded serve
        dims and the replicated serve dims agree on one eighth-step rung,
        and both routes give the same answers from it."""
        from predictionio_tpu.ops.als import batch_predict_dims
        rng = np.random.default_rng(19)
        model = ALSModel(
            rng.standard_normal((80, 6)).astype(np.float32),
            rng.standard_normal((70_000, 6)).astype(np.float32), 6)
        sharded = _sharded_copy(model)
        assert sharded.item_factors.padded_rows == 73728
        assert batch_predict_dims(sharded, 2, 10)["i"] == 73728
        assert batch_predict_dims(model, 2, 10)["i"] == 73728
        assert B.bucket_table_rows_sharded(73729, N_SHARDS) == 81920
        s_r, i_r = users_topk_serve(model, [3, 40], 10)
        s_s, i_s = users_topk_serve(sharded, [3, 40], 10)
        np.testing.assert_array_equal(i_r[:, :10], i_s[:, :10])
        np.testing.assert_allclose(s_r[:, :10], s_s[:, :10], atol=1e-5)

    def test_masked_topk_parity(self, mesh8):
        from predictionio_tpu.ops.similarity import masked_top_k_batch
        model, _ = _train(seed=13)
        sharded = _sharded_copy(model)
        rng = np.random.default_rng(5)
        q = table_rows(model.user_factors, [2, 9, 40])
        masks = rng.random((3, model.n_items)) > 0.3
        s_r, i_r = masked_top_k_batch(model.item_factors, q, masks, 8,
                                      filter_positive=False)
        s_s, i_s = masked_top_k_batch(sharded.item_factors, q, masks, 8,
                                      filter_positive=False)
        for row in range(3):
            fr, fs = np.isfinite(s_r[row]), np.isfinite(s_s[row])
            np.testing.assert_array_equal(i_r[row][fr][:8],
                                          i_s[row][fs][:8])
            np.testing.assert_allclose(s_r[row][fr][:8],
                                       s_s[row][fs][:8], atol=1e-5)

    def test_steady_ticks_and_serves_compile_nothing(self, mesh8):
        model, coo = _train(seed=17)
        sharded = _sharded_copy(model)
        cfg = FoldInConfig(sweeps=1, factor_sharding="model")
        rng = np.random.default_rng(23)

        def tick(m):
            tu = rng.integers(0, coo.n_users, 4)
            ti = rng.integers(0, coo.n_items, 4)
            return fold_in_coo(m, coo, tu, ti, cfg,
                               resident_key="zero_rc")[0]

        # warmup: tick 1 compiles the fold programs, tick 2 may mint
        # one more K class and absorbs the serve bucket's background
        # AOT adoption (its compile seconds land asynchronously)
        for _ in range(2):
            sharded = tick(sharded)
            users_topk_serve(sharded, [1, 2], 8)
        import time
        time.sleep(0.3)   # let any background adoption finish booking
        before = sum(costmon.compile_seconds_by_executable().values())
        for _ in range(3):                         # steady ticks 3..5
            sharded = tick(sharded)
            users_topk_serve(sharded, [3, 4], 8)
        after = sum(costmon.compile_seconds_by_executable().values())
        assert after == before, \
            "steady-state sharded ticks/serves must compile nothing"


# ---------------------------------------------------------------------------
# gates over sharded candidates (satellite)
# ---------------------------------------------------------------------------

class TestShardedGates:
    def _models(self):
        from predictionio_tpu.data.bimap import EntityIdIxMap
        from predictionio_tpu.models.recommendation import (
            ALSAlgorithm, ALSAlgorithmParams, RecommendationModel)
        base, _ = _train(seed=29)
        user_ix, _ = EntityIdIxMap.build_with_indices(
            np.array([f"u{i}" for i in range(base.n_users)]))
        item_ix, _ = EntityIdIxMap.build_with_indices(
            np.array([f"i{i}" for i in range(base.n_items)]))
        algo = ALSAlgorithm(ALSAlgorithmParams(rank=base.rank))
        mk = lambda als: RecommendationModel(als, user_ix, item_ix)
        return algo, mk, base

    def test_real_verdicts_not_skips(self, mesh8):
        from predictionio_tpu.guard.gates import QualityGatekeeper
        algo, mk, base = self._models()
        live = mk(_sharded_copy(base))
        cand_als = _sharded_copy(base)
        cand_als = ALSModel(
            cand_als.user_factors.with_rows(
                [0], cand_als.user_factors.rows([0]) * 1.01),
            cand_als.item_factors, base.rank)
        report = QualityGatekeeper().evaluate([mk(cand_als)], [live],
                                              [algo])
        verdicts = {g["gate"]: g["verdict"] for g in report["gates"]}
        assert report["passed"], report
        # every gate ran for real against the sharded tables — the
        # "no silent gate bypass for sharded models" regression
        assert verdicts["finite"] == "pass"
        assert verdicts["norm_drift"] == "pass"
        assert verdicts["score_drift"] == "pass"
        assert verdicts["golden_queries"] == "pass", report

    def test_nan_in_one_shard_fails_finite(self, mesh8):
        from predictionio_tpu.guard.gates import QualityGatekeeper
        algo, mk, base = self._models()
        live = mk(_sharded_copy(base))
        poisoned = _sharded_copy(base)
        bad_rows = np.full((1, base.rank), np.nan, dtype=np.float32)
        poisoned = ALSModel(
            poisoned.user_factors,
            poisoned.item_factors.with_rows([base.n_items - 1],
                                            bad_rows),
            base.rank)
        report = QualityGatekeeper().evaluate([mk(poisoned)], [live],
                                              [algo])
        assert not report["passed"]
        assert any(g["gate"] == "finite" and g["verdict"] == "fail"
                   for g in report["gates"])


# ---------------------------------------------------------------------------
# hot-swap of sharded versions: torn-read-free under hammer
# ---------------------------------------------------------------------------

RANK = 4
VERSION_CONSTS = (1.0, 2.0, 3.0)
ALLOWED_SCORES = {RANK * c for c in VERSION_CONSTS}


class TestShardedHotSwap:
    def _version(self, base_model, n_u, n_i, c):
        als = ALSModel(
            ShardedTable.from_host(
                np.full((n_u, RANK), c, dtype=np.float32), N_SHARDS),
            ShardedTable.from_host(
                np.ones((n_i, RANK), dtype=np.float32), N_SHARDS),
            RANK)
        return dataclasses.replace(base_model, als=als)

    def test_no_torn_reads_across_sharded_swaps(self, tmp_env, mesh8):
        from predictionio_tpu.core import EngineParams
        from predictionio_tpu.data import DataMap, Event
        from predictionio_tpu.data.storage import App, Storage
        from predictionio_tpu.models import recommendation as R
        from predictionio_tpu.serving import EngineServer, ServerConfig
        from predictionio_tpu.workflow import run_train
        app_id = Storage.get_meta_data_apps().insert(App(0, "shardswap"))
        Storage.get_events().init(app_id)
        ev = Storage.get_events()
        for u in range(4):
            for i in range(6):
                ev.insert(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item",
                    target_entity_id=f"i{i}",
                    properties=DataMap(
                        {"rating": float(1 + (u + i) % 5)})), app_id)
        ep = EngineParams(
            data_source_params=("", R.DataSourceParams(
                app_name="shardswap")),
            preparator_params=("", R.PreparatorParams()),
            algorithm_params_list=[("als", R.ALSAlgorithmParams(
                rank=RANK, num_iterations=2, lam=0.1, seed=1))],
            serving_params=("", None))
        engine = R.RecommendationEngineFactory.apply()
        run_train(engine, ep, engine_id="shardswap", engine_version="1",
                  engine_variant="v1", engine_factory="recommendation")
        server = EngineServer(ServerConfig(
            ip="127.0.0.1", port=0, engine_id="shardswap",
            engine_version="1", engine_variant="v1"))
        server.load()
        server.start()
        try:
            base = server.models[0]
            n_u, n_i = base.als.n_users, base.als.n_items
            versions = [self._version(base, n_u, n_i, c)
                        for c in VERSION_CONSTS]
            port = server.config.port
            stop = threading.Event()
            failures, n_ok = [], [0]

            def call(body):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/queries.json",
                    data=json.dumps(body).encode(), method="POST")
                with urllib.request.urlopen(req, timeout=15) as resp:
                    return resp.status, json.loads(resp.read())

            def hammer():
                while not stop.is_set():
                    pre_swaps = server.swap_count
                    try:
                        st, body = call({"user": "u1", "num": 3})
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
                    n_ok[0] += 1

            threads = [threading.Thread(target=hammer) for _ in range(3)]
            for t in threads:
                t.start()
            for k, m in enumerate(versions):
                server.swap_models([m], version=f"shard-v{k}")
                target = n_ok[0] + 15
                while n_ok[0] < target and not failures:
                    pass
            stop.set()
            for t in threads:
                t.join(timeout=30)
            assert not failures, failures[:5]
            assert n_ok[0] > 30
            # /stats.json reports the sharded layout
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/stats.json")
            with urllib.request.urlopen(req, timeout=15) as resp:
                stats = json.loads(resp.read())
            assert stats["modelSharding"][0]["layout"] == "model"
            assert stats["modelSharding"][0]["shards"] == N_SHARDS
        finally:
            stop.set()
            server.stop()


# ---------------------------------------------------------------------------
# AOT warm specs cover the sharded layout
# ---------------------------------------------------------------------------

class TestShardedWarmSpecs:
    def test_batch_predict_dims_sharded(self, mesh8):
        from predictionio_tpu.ops.als import batch_predict_dims
        model, _ = _train(seed=31)
        sharded = _sharded_copy(model)
        dims = batch_predict_dims(sharded, 16, 10)
        assert dims["s"] == N_SHARDS
        assert dims["i"] == sharded.item_factors.padded_rows
        assert "u" not in dims  # user rows come from the host mirrors
        rep = batch_predict_dims(model, 16, 10)
        assert "s" not in rep and "u" in rep
        # the two layouts can never alias one AOT bucket
        assert B.bucket_key(dims) != B.bucket_key(rep)

    def test_warm_compiles_sharded_executable(self, mesh8, monkeypatch):
        monkeypatch.setenv("PIO_AOT_WARM", "on")
        from predictionio_tpu.compile.aot import get_aot
        from predictionio_tpu.data.bimap import EntityIdIxMap
        from predictionio_tpu.models.recommendation import (
            ALSAlgorithm, ALSAlgorithmParams, RecommendationModel)
        from predictionio_tpu.obs import costmon as CM
        base, _ = _train(seed=37)
        sharded = _sharded_copy(base)
        user_ix, _ = EntityIdIxMap.build_with_indices(
            np.array([f"u{i}" for i in range(base.n_users)]))
        item_ix, _ = EntityIdIxMap.build_with_indices(
            np.array([f"i{i}" for i in range(base.n_items)]))
        model = RecommendationModel(sharded, user_ix, item_ix)
        algo = ALSAlgorithm(ALSAlgorithmParams(rank=base.rank))
        specs = algo.aot_warm_specs(model, batch_hint=4)
        assert specs and all(d.get("s") == N_SHARDS for _, d in specs)
        from predictionio_tpu.compile.aot import warm_models
        summary = warm_models([algo], [model], batch_hint=4)
        dims = specs[0][1]
        assert get_aot().lookup(CM.BATCH_PREDICT, dims) is not None, \
            summary
