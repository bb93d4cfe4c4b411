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
        bucket = B.bucket_table_rows(70)    # 128
        assert not B.should_promote_table(70, bucket)
        assert B.should_promote_table(int(bucket * B.PROMOTE_AT) + 1,
                                      bucket)
        assert B.next_table_bucket(bucket) == 256

    def test_bucket_key_and_label_canonical(self):
        k1 = B.bucket_key({"u": 64, "b": 4})
        k2 = B.bucket_key({"b": 4, "u": 64})
        assert k1 == k2
        from predictionio_tpu.compile.buckets import bucket_label
        assert bucket_label({"u": 64, "b": 4}) == "b4-u64"


# ---------------------------------------------------------------------------
# the resident-table ladder (ISSUE 32): eighth steps from 2^16 rows up
# ---------------------------------------------------------------------------

#: the benchmark's catalogues: amazonbooks users / items, taobao items /
#: users, goodreads books / users
CELL_COUNTS = (8026324, 2330066, 4162024, 987994, 2360650, 876145)
LADDER_SIZES = sorted(
    {1, 2, 63, 64, 65, 1000, 32768, 32769, 49152, 65535, 65536, 65537,
     73728, 73729, 131071, 131072, 131073, 999999, 1 << 20, (1 << 20) + 1,
     15 << 19, (15 << 19) + 1, (1 << 24) - 1, 1 << 24} | set(CELL_COUNTS))


def _octave(bucket: int) -> int:
    """The power of two at the lower end of ``bucket``'s octave:
    (2^e, 2^(e+1)] holds the rungs 9..16 eighths of 2^e."""
    return 1 << ((bucket - 1).bit_length() - 1)


class TestTableLadder:
    @pytest.mark.parametrize("n", LADDER_SIZES)
    def test_rung_covers_and_is_a_fixed_point(self, n):
        b = B.bucket_table_rows(n)
        assert b >= n
        assert B.bucket_table_rows(b) == b
        assert B.bucket_table_rows(max(n - 1, 1)) <= b     # monotone

    @pytest.mark.parametrize("n", LADDER_SIZES)
    def test_padding_and_the_shape_of_a_rung(self, n):
        b = B.bucket_table_rows(n)
        if n <= B.TABLE_FINE_FROM:
            # the old powers of two, floor 64
            assert b == B.bucket_rows(n)
            return
        assert b <= B.bucket_rows(n)
        assert (b - n) / b <= 0.125 and (b - n) / n <= 0.125
        eighth = _octave(b) // 8
        assert b % eighth == 0 and 9 <= b // eighth <= 16
        for shards in (2, 4, 8):
            assert b % shards == 0
            assert B.bucket_table_rows_sharded(n, shards) == b
        assert b % (8 * 128) == 0                # the (8, 128) tiling

    def test_three_way_axis_still_gets_equal_slices(self):
        assert B.bucket_table_rows_sharded(70, 3) == 129
        assert B.bucket_table_rows_sharded(70000, 3) % 3 == 0

    @pytest.mark.parametrize("n", LADDER_SIZES)
    def test_next_rung(self, n):
        b = B.bucket_table_rows(n)
        nxt = B.next_table_bucket(b)
        assert nxt > b and B.bucket_table_rows(b + 1) == nxt
        if b < B.TABLE_FINE_FROM:
            assert nxt == 2 * b
        else:
            assert nxt - b <= b // 8               # at most +12.5%

    @pytest.mark.parametrize("bucket", [64 << e for e in range(10)])
    @pytest.mark.parametrize("fill", [0.5, 0.7, 0.74, 0.75, 0.76, 0.9, 1.0])
    def test_trigger_is_the_old_one_on_power_of_two_rungs(self, bucket,
                                                          fill):
        n = max(int(bucket * fill), bucket // 2 + 1)
        assert B.bucket_table_rows(n) == bucket
        # the rule before ISSUE 32: occupancy past PROMOTE_AT
        assert B.should_promote_table(n, bucket) == \
            (n / bucket >= B.PROMOTE_AT)

    @pytest.mark.parametrize("n,bucket,fires", [
        (2330066, 2359296, True),       # amazonbooks items: 29,230 left
        (4162024, 1 << 22, True),       # taobao items: 32,280 left
        (8026324, 1 << 23, False),      # amazonbooks users: 362,284 left
    ])
    def test_trigger_in_the_cells(self, n, bucket, fires):
        assert B.bucket_table_rows(n) == bucket
        assert B.should_promote_table(n, bucket) is fires

    @pytest.mark.parametrize("n", LADDER_SIZES)
    def test_no_table_sits_permanently_over_the_trigger(self, n):
        """A table that has just been promoted into a rung is under it:
        the pre-compile of the rung above waits for growth."""
        b = B.bucket_table_rows(n)
        lowest = 1 if b == B.ROWS_FLOOR else \
            max(m for m in (b // 2, b - _octave(b) // 8)
                if B.bucket_table_rows(m) < b) + 1
        assert B.bucket_table_rows(lowest) == b
        assert not B.should_promote_table(lowest, b)
        assert B.should_promote_table(b, b)


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
# ISSUE 32: a table just over the fine threshold serves the same answers
# from its eighth-step rung as from its power-of-two bucket
# ---------------------------------------------------------------------------

#: live rows over TABLE_FINE_FROM: rung 73,728, power of two 131,072
FINE_N = 70_000


def _both_ladders(monkeypatch, run):
    """``run()`` with every resident table at its power-of-two bucket
    (the ladder before ISSUE 32), then on the table ladder."""
    monkeypatch.setenv("PIO_SERVE_PACK", "exact")
    with monkeypatch.context() as mp:
        mp.setattr(B, "bucket_table_rows", B.bucket_rows)
        pow2 = run()
    return pow2, run()


class TestFineRungServes:
    @pytest.mark.parametrize("ixs", [[3], [3, 50, 99]], ids=["b1", "b4"])
    def test_users_topk_same_answers(self, monkeypatch, ixs):
        from predictionio_tpu.ops.als import (batch_predict_dims,
                                              users_topk_serve)
        m = _als_model(100, FINE_N, rank=7, seed=5)

        def run():
            return (batch_predict_dims(m, len(ixs), 10)["i"],
                    *users_topk_serve(m, ixs, 10))
        (i0, s0, ix0), (i1, s1, ix1) = _both_ladders(monkeypatch, run)
        assert (i0, i1) == (131072, 73728)
        assert np.isfinite(s1).all() and ix1.max() < FINE_N
        np.testing.assert_array_equal(ix0, ix1)
        np.testing.assert_array_equal(s0, s1)

    def test_masked_family_same_answers(self, monkeypatch):
        from predictionio_tpu.ops.similarity import (masked_top_k_batch,
                                                     masked_topk_dims)
        rng = np.random.default_rng(6)
        table = rng.standard_normal((FINE_N, 5)).astype(np.float32)
        qv = rng.standard_normal((2, 5)).astype(np.float32)
        masks = rng.random((2, FINE_N)) < 0.7

        def run():
            return (masked_topk_dims(FINE_N, 5, 2, 10)["i"],
                    *masked_top_k_batch(table, qv, masks, 10))
        (i0, s0, ix0), (i1, s1, ix1) = _both_ladders(monkeypatch, run)
        assert (i0, i1) == (131072, 73728)
        assert masks[0][ix1[0][np.isfinite(s1[0])]].all()
        np.testing.assert_array_equal(ix0, ix1)
        np.testing.assert_array_equal(s0, s1)

    def test_composed_family_same_answers(self, monkeypatch):
        from predictionio_tpu.ops import similarity as S
        rng = np.random.default_rng(7)
        table = rng.standard_normal((FINE_N, 5)).astype(np.float32)
        qv = rng.standard_normal((2, 5)).astype(np.float32)
        items = np.arange(FINE_N)
        cats = S.ItemCategories.from_pairs(
            FINE_N, items, np.array([f"c{c}" for c in range(7)])[items % 7])
        gone = rng.choice(FINE_N, 300, replace=False)
        white = rng.choice(FINE_N, 500, replace=False)
        unavailable = rng.choice(FINE_N, 700, replace=False)

        def run():
            filters = S.ItemFilterData(cats)    # its bitmap spans the rung
            filters.set_unavailable(unavailable)
            scores, idx = S.composed_top_k_batch_begin(
                table, qv, filters, [cats.codes_of(["c1", "c4"]), []],
                [(gone, np.full(gone.size, S.LISTED_OUT)),
                 (white, np.full(white.size, S.LISTED_WHITE))],
                [False, True], 10)()
            return filters.available_bits.size * 32, scores, idx
        (i0, s0, ix0), (i1, s1, ix1) = _both_ladders(monkeypatch, run)
        assert (i0, i1) == (131072, 73728)
        kept = ix1[0][np.isfinite(s1[0])]
        assert kept.size and np.isin(kept % 7, (1, 4)).all()
        assert not np.isin(kept, np.concatenate([gone, unavailable])).any()
        assert np.isin(ix1[1][np.isfinite(s1[1])], white).all()
        np.testing.assert_array_equal(ix0, ix1)
        np.testing.assert_array_equal(s0, s1)

    def test_growth_inside_a_rung_is_free_and_crossing_promotes_once(self):
        """70,000 and 71,000 items share the 73,728-row executable; at
        72,000 (1,728 rows left of a step of 8,192) the 81,920-row one
        compiles in the background, once; at 74,000 it is dispatched."""
        import threading
        from predictionio_tpu.ops.als import (batch_predict_dims,
                                              users_topk_serve)

        def compiled():
            return [b for b in get_aot().snapshot()["bucketsCompiled"].get(
                costmon.BATCH_PREDICT, []) if "-r9-" in b]

        def settle():
            for t in threading.enumerate():
                if t.name.startswith("pio-aot-"):
                    t.join()

        users_topk_serve(_als_model(80, FINE_N, rank=9), [1, 2], 10)
        settle()                 # the cold bucket's background adoption
        assert compiled() == ["b2-i73728-k16-p1-r9-u128"]
        hits = get_aot().snapshot()["dispatchHits"].get(
            costmon.BATCH_PREDICT, 0)
        before = _compile_s()
        s, i = users_topk_serve(_als_model(80, 71_000, rank=9, seed=1),
                                [1, 2], 10)
        settle()
        assert _compile_s() == before, \
            "growth inside the rung must compile nothing"
        assert i.max() < 71_000
        users_topk_serve(_als_model(80, 72_000, rank=9, seed=2), [1, 2], 10)
        settle()
        assert compiled() == ["b2-i73728-k16-p1-r9-u128",
                              "b2-i81920-k16-p1-r9-u128"]
        grown = _als_model(80, 74_000, rank=9, seed=3)
        assert batch_predict_dims(grown, 2, 10)["i"] == 81920
        before = _compile_s()
        s, i = users_topk_serve(grown, [1, 2], 10)
        settle()
        assert _compile_s() == before, \
            "the promoted rung was compiled before growth needed it"
        assert len(compiled()) == 2
        assert get_aot().snapshot()["dispatchHits"][
            costmon.BATCH_PREDICT] == hits + 3
        assert i.max() < 74_000

    def test_fold_tables_sit_on_the_serve_rung(self):
        from predictionio_tpu.online.fold_in import (FoldInConfig,
                                                     fold_in_coo)
        from predictionio_tpu.ops.als import batch_predict_dims
        from predictionio_tpu.ops.ratings import RatingsCOO
        from predictionio_tpu.utils import device_cache
        model = _als_model(90, FINE_N, rank=4)
        r = np.random.default_rng(8)
        coo = RatingsCOO(r.integers(0, 90, 400).astype(np.int32),
                         r.integers(0, FINE_N, 400).astype(np.int32),
                         r.integers(1, 6, 400).astype(np.float32),
                         90, FINE_N)
        out, _ = fold_in_coo(model, coo, np.unique(coo.user_idx[:8]),
                             np.unique(coo.item_idx[:8]),
                             FoldInConfig(sweeps=1),
                             resident_key="cp-fine-rung")
        try:
            dims = batch_predict_dims(out, 1, 10)
            assert (dims["u"], dims["i"]) == (128, 73728)
            assert device_cache.resident_sizes()["cp-fine-rung"] == \
                (dims["u"] + dims["i"]) * 4 * 4
        finally:
            device_cache.drop_resident("cp-fine-rung")

    def test_gauges_and_stats_name_the_padded_share(self):
        from predictionio_tpu.obs.metrics import get_registry
        from predictionio_tpu.ops.als import users_topk_serve
        from predictionio_tpu.utils import device_cache
        users_topk_serve(_als_model(100, FINE_N, rank=3, seed=9), [1], 10)
        rows = device_cache.table_rows()
        assert rows["item"] == {"live": FINE_N, "bucket": 73728,
                                "paddedShare": (73728 - FINE_N) / 73728}
        assert rows["user"]["bucket"] == 128
        got = {(l["table"], l["what"]): v for l, v in
               get_registry().get("pio_table_rows").samples()}
        assert got[("item", "live")] == FINE_N
        assert got[("item", "bucket")] == 73728
        s = _real_server(_rec_model(40, 50), canary_fraction=0.0)
        s.handle_query_batch([{"user": "u1", "num": 3}])

        class _Req:
            params = {}
            headers = {}

        assert s._stats(_Req()).body["tableRows"]["item"] == {
            "live": 50, "bucket": 64, "paddedShare": 14 / 64}


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
