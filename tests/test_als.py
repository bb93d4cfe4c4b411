"""ALS kernel tests: plan correctness, parity with a numpy reference solver,
convergence, and mesh-sharded equivalence."""

import numpy as np
import pytest

from predictionio_tpu.ops import als as als_mod
from predictionio_tpu.ops.als import (ALSConfig, ALSModel, als_rmse,
                                      als_train, predict_ratings,
                                      recommend_products)
from predictionio_tpu.ops.ratings import (RatingsCOO, build_solve_plan,
                                          dedup_ratings, plan_for_users)


def synthetic_ratings(n_users=40, n_items=25, rank=3, density=0.5, seed=0,
                      noise=0.0):
    rng = np.random.default_rng(seed)
    U = rng.standard_normal((n_users, rank)).astype(np.float32)
    V = rng.standard_normal((n_items, rank)).astype(np.float32)
    full = U @ V.T + noise * rng.standard_normal((n_users, n_items))
    mask = rng.random((n_users, n_items)) < density
    ui, ii = np.nonzero(mask)
    return RatingsCOO(ui.astype(np.int32), ii.astype(np.int32),
                      full[ui, ii].astype(np.float32), n_users, n_items)


# ---------------------------------------------------------------------------
# numpy reference ALS (direct per-entity solves)
# ---------------------------------------------------------------------------

def np_als_half_sweep(r: RatingsCOO, factors, counter, lam, nratings_reg,
                      implicit=False, alpha=1.0):
    """Solve all user rows given item factors (call with transpose for
    items). Mirrors the exact math the kernel claims."""
    out = factors.copy()
    rank = counter.shape[1]
    gram = counter.T @ counter if implicit else None
    for u in range(r.n_users):
        sel = r.user_idx == u
        if not sel.any():
            continue
        items = r.item_idx[sel]
        vals = r.rating[sel]
        Vu = counter[items]
        n = sel.sum()
        reg = lam * max(n, 1) if nratings_reg else lam
        if implicit:
            cm1 = alpha * np.abs(vals)
            A = gram + (Vu * cm1[:, None]).T @ Vu + reg * np.eye(rank)
            pos = (vals > 0).astype(np.float64)
            b = (((1 + alpha * np.abs(vals)) * pos)[:, None] * Vu).sum(0)
        else:
            A = Vu.T @ Vu + reg * np.eye(rank)
            b = Vu.T @ vals
        out[u] = np.linalg.solve(A, b)
    return out


def np_als(r: RatingsCOO, cfg: ALSConfig):
    U = als_mod._init_factors(r.n_users, cfg.rank, cfg.seed, 1)[:-1]
    V = als_mod._init_factors(r.n_items, cfg.rank, cfg.seed, 2)[:-1]
    nr = cfg.lambda_scaling == "nratings"
    for _ in range(cfg.iterations):
        U = np_als_half_sweep(r, U, V, cfg.lam, nr, cfg.implicit_prefs,
                              cfg.alpha)
        V = np_als_half_sweep(r.transpose(), V, U, cfg.lam, nr,
                              cfg.implicit_prefs, cfg.alpha)
    return ALSModel(U, V, cfg.rank)


# ---------------------------------------------------------------------------
# plan tests
# ---------------------------------------------------------------------------

class TestSolvePlan:
    def test_plan_reconstructs_csr(self):
        r = synthetic_ratings(seed=3)
        plan = plan_for_users(r, work_budget=256, batch_multiple=4)
        got = {}
        for batch in plan.batches:
            assert batch.rows.shape[0] % 4 == 0
            for row_i, ent in enumerate(batch.rows):
                if ent < 0:
                    assert batch.mask[row_i].sum() == 0
                    continue
                m = batch.mask[row_i].astype(bool)
                got[int(ent)] = (set(zip(batch.idx[row_i][m].tolist(),
                                         batch.val[row_i][m].tolist())))
        for u in range(r.n_users):
            sel = r.user_idx == u
            expected = set(zip(r.item_idx[sel].tolist(),
                               r.rating[sel].tolist()))
            if expected:
                assert got[int(u)] == expected
            else:
                assert u not in got

    def test_bucket_shapes_sublane_aligned(self):
        r = synthetic_ratings(n_users=100, n_items=60, density=0.3)
        plan = plan_for_users(r, work_budget=1024)
        for b, k in plan.kernel_shapes:
            assert k % 8 == 0  # f32 sublane tile of the gather buffer
            assert b * k <= max(1024, k)  # budget respected (min 1 row)

    def test_bucket_lengths_ladder(self):
        from predictionio_tpu.ops.ratings import bucket_lengths
        sizes = bucket_lengths(10_000)
        # layout-granularity alignment: the gather buffer's sublane dim
        # pads K to these multiples anyway, so finer would buy nothing
        assert np.all(sizes[sizes < 128] % 8 == 0)
        assert np.all(sizes[(sizes >= 128) & (sizes < 512)] % 16 == 0)
        assert sizes[-1] >= 10_000
        # step ratio bounds per-entity padding waste; from 24 up (where
        # the 8-granularity stops dominating) steps stay under ~34%, vs
        # the 100% windows of the round-1..3 pow2 ladder
        steps = np.diff(sizes) / sizes[:-1]
        assert np.all(steps[sizes[:-1] >= 24] <= 0.34)
        assert np.all(steps <= 1.0)
        assert np.all(np.diff(sizes) > 0)

    def test_sparse_bucket_merge_bounded(self):
        """Sparse near-empty buckets merge upward (fewer compiled scan
        groups) but NEVER past 1.25x an entity's original bucket, and
        never when the bucket carries a real share of the work."""
        rng = np.random.default_rng(3)
        # many entities at count 40 (dense bucket), a FEW at count 66
        # (sparse: padded 72 -> merges to 80 within cap), and one giant
        # at 5000 (sparse but heavy; must stay put)
        gi = np.concatenate([
            np.repeat(np.arange(200), 40),
            np.repeat(np.arange(200, 203), 66),
            np.full(5000, 203),
        ]).astype(np.int64)
        ci = rng.integers(0, 50, gi.size).astype(np.int32)
        vals = rng.random(gi.size).astype(np.float32)
        plan = build_solve_plan(gi, ci, vals, 204, work_budget=1 << 14)
        ks_used = {k for _, k in plan.kernel_shapes}
        # the giant keeps its own (un-merged) bucket at its natural size
        assert max(ks_used) >= 5000
        # per-entity padding bound holds for every real row
        for b in plan.batches:
            for row_i, ent in enumerate(b.rows):
                if ent < 0:
                    continue
                c = b.mask[row_i].sum()
                assert b.shape[1] <= max(8, 1.25 * 1.125 * c + 8)

    def test_empty(self):
        plan = build_solve_plan(np.array([], dtype=np.int64),
                                np.array([], dtype=np.int32),
                                np.array([], dtype=np.float32), 5)
        assert plan.batches == ()


class TestDedup:
    def test_latest(self):
        u = [0, 0, 1]
        i = [1, 1, 2]
        v = [3.0, 5.0, 1.0]
        ts = [10, 20, 5]
        uu, ii, vv = dedup_ratings(u, i, v, ts, "latest")
        assert dict(zip(zip(uu.tolist(), ii.tolist()), vv.tolist())) == {
            (0, 1): 5.0, (1, 2): 1.0}

    def test_latest_respects_timestamp_not_position(self):
        uu, ii, vv = dedup_ratings([0, 0], [1, 1], [3.0, 5.0], [20, 10])
        assert vv.tolist() == [3.0]

    def test_sum_and_mean(self):
        u, i, v = [0, 0, 1], [1, 1, 0], [1.0, 2.0, 4.0]
        _, _, vv = dedup_ratings(u, i, v, policy="sum")
        assert sorted(vv.tolist()) == [3.0, 4.0]
        _, _, vv = dedup_ratings(u, i, v, policy="mean")
        assert sorted(vv.tolist()) == [1.5, 4.0]


# ---------------------------------------------------------------------------
# kernel parity + convergence
# ---------------------------------------------------------------------------

class TestALSExplicit:
    @pytest.mark.parametrize("lambda_scaling", ["nratings", "constant"])
    def test_matches_numpy_reference(self, mesh8, lambda_scaling):
        r = synthetic_ratings(seed=1)
        cfg = ALSConfig(rank=4, iterations=2, lam=0.1,
                        lambda_scaling=lambda_scaling, work_budget=512)
        model = als_train(r, cfg, mesh8)
        ref = np_als(r, cfg)
        np.testing.assert_allclose(model.user_factors, ref.user_factors,
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(model.item_factors, ref.item_factors,
                                   rtol=2e-3, atol=2e-3)

    def test_converges_on_low_rank_data(self, mesh8):
        r = synthetic_ratings(n_users=50, n_items=30, rank=3, density=0.6,
                              seed=2)
        cfg = ALSConfig(rank=6, iterations=8, lam=0.01)
        model = als_train(r, cfg, mesh8)
        assert als_rmse(model, r) < 0.08

    def test_rmse_decreases(self, mesh8):
        r = synthetic_ratings(seed=5, noise=0.1)
        cfg1 = ALSConfig(rank=4, iterations=1, lam=0.05)
        cfg6 = ALSConfig(rank=4, iterations=6, lam=0.05)
        assert als_rmse(als_train(r, cfg6, mesh8), r) < \
            als_rmse(als_train(r, cfg1, mesh8), r)


class TestALSImplicit:
    def test_matches_numpy_reference(self, mesh8):
        r = synthetic_ratings(seed=7)
        r = RatingsCOO(r.user_idx, r.item_idx,
                       np.abs(r.rating) + 0.5, r.n_users, r.n_items)
        cfg = ALSConfig(rank=4, iterations=2, lam=0.1, implicit_prefs=True,
                        alpha=2.0, work_budget=512)
        model = als_train(r, cfg, mesh8)
        ref = np_als(r, cfg)
        np.testing.assert_allclose(model.user_factors, ref.user_factors,
                                   rtol=3e-3, atol=3e-3)

    def test_negative_preferences_match_numpy_reference(self, mesh8):
        """MLlib trainImplicit semantics for like/dislike: c1 = alpha*|r|
        enters A for every observation, b only accumulates where r > 0."""
        r = synthetic_ratings(seed=11)
        signs = np.where(np.arange(r.nnz) % 3 == 0, -1.0, 1.0)
        r = RatingsCOO(r.user_idx, r.item_idx,
                       (np.abs(r.rating) + 0.5) * signs,
                       r.n_users, r.n_items)
        cfg = ALSConfig(rank=4, iterations=2, lam=0.1, implicit_prefs=True,
                        alpha=2.0, work_budget=512)
        model = als_train(r, cfg, mesh8)
        ref = np_als(r, cfg)
        np.testing.assert_allclose(model.user_factors, ref.user_factors,
                                   rtol=3e-3, atol=3e-3)

    def test_disliked_items_rank_below_liked(self, mesh8):
        rng = np.random.default_rng(5)
        n_users, n_items = 24, 12
        ui, ii, vv = [], [], []
        for u in range(n_users):
            for i in range(n_items):
                if rng.random() < 0.7:
                    ui.append(u)
                    ii.append(i)
                    # everyone likes even items, dislikes odd items
                    vv.append(1.0 if i % 2 == 0 else -1.0)
        r = RatingsCOO(np.array(ui, np.int32), np.array(ii, np.int32),
                       np.array(vv, np.float32), n_users, n_items)
        model = als_train(r, ALSConfig(rank=4, iterations=8, lam=0.01,
                                       implicit_prefs=True, alpha=5.0),
                          mesh8)
        scores, idx = recommend_products(model, 0, n_items)
        ranks = {int(i): pos for pos, i in enumerate(idx)}
        liked_mean = np.mean([ranks[i] for i in range(0, n_items, 2)])
        disliked_mean = np.mean([ranks[i] for i in range(1, n_items, 2)])
        assert liked_mean < disliked_mean

    def test_implicit_ranks_observed_items_high(self, mesh8):
        rng = np.random.default_rng(0)
        n_users, n_items = 30, 20
        # two user groups, each consuming one item group
        ui, ii, vv = [], [], []
        for u in range(n_users):
            group = u % 2
            for i in range(n_items):
                if i % 2 == group and rng.random() < 0.8:
                    ui.append(u)
                    ii.append(i)
                    vv.append(rng.integers(1, 5))
        r = RatingsCOO(np.array(ui, np.int32), np.array(ii, np.int32),
                       np.array(vv, np.float32), n_users, n_items)
        model = als_train(r, ALSConfig(rank=4, iterations=6, lam=0.01,
                                       implicit_prefs=True, alpha=10.0),
                          mesh8)
        # user 0 (group 0): unseen group-0 items should beat group-1 items
        seen = set(np.array(ii)[np.array(ui) == 0].tolist())
        scores, idx = recommend_products(model, 0, n_items)
        ranked = [int(i) for i in idx if int(i) not in seen]
        same_group = [i for i in ranked if i % 2 == 0]
        other_group = [i for i in ranked if i % 2 == 1]
        if same_group and other_group:
            mean_rank_same = np.mean([ranked.index(i) for i in same_group])
            mean_rank_other = np.mean([ranked.index(i) for i in other_group])
            assert mean_rank_same < mean_rank_other


class TestPrediction:
    def test_predict_and_topk(self, mesh8):
        r = synthetic_ratings(seed=9)
        model = als_train(r, ALSConfig(rank=4, iterations=4, lam=0.01), mesh8)
        pred = predict_ratings(model, r.user_idx[:10], r.item_idx[:10])
        manual = np.sum(model.user_factors[r.user_idx[:10]] *
                        model.item_factors[r.item_idx[:10]], axis=1)
        np.testing.assert_allclose(pred, manual, rtol=1e-5)

        scores, idx = recommend_products(model, 0, 5)
        assert len(idx) == 5
        assert np.all(np.diff(scores) <= 1e-6)  # descending

    def test_topk_exclusion(self, mesh8):
        r = synthetic_ratings(seed=9)
        model = als_train(r, ALSConfig(rank=4, iterations=2), mesh8)
        _, idx_all = recommend_products(model, 1, 10)
        excl = idx_all[:3]
        _, idx2 = recommend_products(model, 1, 10, exclude=excl)
        assert not set(excl.tolist()) & set(idx2.tolist())


class TestMeshEquivalence:
    def test_sharded_matches_single_device(self, mesh8):
        import jax
        from predictionio_tpu.parallel.mesh import make_mesh
        r = synthetic_ratings(seed=11)
        cfg = ALSConfig(rank=4, iterations=3, lam=0.05)
        single = make_mesh(devices=jax.devices()[:1])
        m1 = als_train(r, cfg, single)
        m8 = als_train(r, cfg, mesh8)
        np.testing.assert_allclose(m1.user_factors, m8.user_factors,
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("implicit", [False, True])
def test_sweep_chunk_matches_baseline(implicit):
    """sweep_chunk merges independent solve batches into larger scan
    steps, which changes no math, so factors must match the default path
    to float tolerance (explicit exactly: same ops, same order within
    each system)."""
    rng = np.random.default_rng(13)
    n_u, n_i, nnz = 500, 150, 7000
    ui = rng.integers(0, n_u, nnz)
    ii = rng.integers(0, n_i, nnz)
    vv = rng.uniform(1, 5, nnz).astype(np.float32)
    r = RatingsCOO(ui, ii, vv, n_u, n_i)
    kw = dict(rank=8, iterations=3, lam=0.05, seed=2, work_budget=512,
              implicit_prefs=implicit)
    base = als_train(r, ALSConfig(**kw))
    m = als_train(r, ALSConfig(sweep_chunk=3, **kw))
    np.testing.assert_allclose(m.user_factors, base.user_factors,
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(m.item_factors, base.item_factors,
                               rtol=2e-4, atol=2e-5)


def test_bucket_ratio_coarse_matches_default():
    """bucket_ratio only changes the padded segment-length ladder —
    masked padding positions contribute exact zeros, so a coarse ladder
    must train to the same factors as the default within float
    reassociation tolerance."""
    rng = np.random.default_rng(29)
    n_u, n_i, nnz = 500, 150, 8000
    ui = rng.integers(0, n_u, nnz)
    ii = rng.integers(0, n_i, nnz)
    vv = rng.uniform(1, 5, nnz).astype(np.float32)
    r = RatingsCOO(ui, ii, vv, n_u, n_i)
    kw = dict(rank=8, iterations=3, lam=0.05, seed=2, work_budget=512)
    base = als_train(r, ALSConfig(**kw))
    for ratio in (1.5, 2.0):
        m = als_train(r, ALSConfig(bucket_ratio=ratio, **kw))
        np.testing.assert_allclose(m.user_factors, base.user_factors,
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(m.item_factors, base.item_factors,
                                   rtol=2e-4, atol=2e-5)
    with pytest.raises(ValueError, match="bucket_ratio"):
        ALSConfig(bucket_ratio=1.0, **kw)


def test_dual_iters_cap_converges_like_uncapped():
    """dual_iters_cap trades the K+8 finite-termination budget for
    wall-clock; capping to ~20% of the budget (8 of up to K+8=39 at
    rank 32) must leave training quality indistinguishable — RMSE
    within 1% of the uncapped run on the same data. The ablation's
    dualcap row measures the speed side; NOTE the full-scale regime
    (rank 200, cap ~8% of budget) is harsher — re-measure accuracy
    there before flipping any default."""
    rng = np.random.default_rng(23)
    n_u, n_i, nnz = 600, 150, 9000
    ui = rng.integers(0, n_u, nnz)
    ii = rng.integers(0, n_i, nnz)
    vv = rng.uniform(1, 5, nnz).astype(np.float32)
    r = RatingsCOO(ui, ii, vv, n_u, n_i)
    # solver='cg' explicitly: the CPU default resolves to cholesky,
    # which ignores the iteration budget and would test nothing
    kw = dict(rank=32, iterations=4, lam=0.05, seed=2, work_budget=2048,
              solver="cg")
    base = als_train(r, ALSConfig(**kw))
    capped = als_train(r, ALSConfig(dual_iters_cap=8, **kw))
    rmse_base = als_rmse(base, r)
    rmse_capped = als_rmse(capped, r)
    assert abs(rmse_capped - rmse_base) < 0.01 * max(rmse_base, 1e-6), \
        (rmse_base, rmse_capped)
    with pytest.raises(ValueError, match="dual_iters_cap"):
        als_train(r, ALSConfig(dual_iters_cap=0, **kw))


def test_train_telemetry_phases():
    """als_train(telemetry=) reports what `auto` resolved to and every
    phase with sane values, and does not perturb the result (the train
    report `pio train` logs and chip_smoke.py reads)."""
    rng = np.random.default_rng(3)
    n_u, n_i, nnz = 300, 80, 4000
    ui = rng.integers(0, n_u, nnz)
    ii = rng.integers(0, n_i, nnz)
    vv = rng.uniform(1, 5, nnz).astype(np.float32)
    r = RatingsCOO(ui, ii, vv, n_u, n_i)
    cfg = ALSConfig(rank=8, iterations=3, lam=0.05, seed=1)
    tel = {}
    m1 = als_train(r, cfg, telemetry=tel)
    m2 = als_train(r, cfg)
    phases = {"plan_s", "upload_s", "iters_s", "s_per_iter", "fetch_s"}
    assert set(tel) == phases | {"solver", "compute_dtype", "sweep_chunk",
                                 "n_devices", "gather_layout",
                                 "table_shards", "batch_shards",
                                 "exchange_bytes", "setup",
                                 "cg_iters_run", "cg_iters_budget"}
    # replicated tables, batches over the data axis: GSPMD's sweep, and
    # no exchange of the per-chip kind to report
    assert (tel["table_shards"], tel["batch_shards"]) == (1,
                                                          tel["n_devices"])
    assert tel["exchange_bytes"] == {}
    # the CPU's gather has no step to pad a batch for
    assert tel["gather_layout"] == "rows"
    # no solve went through the Pallas CG, the one solver that counts
    assert (tel["cg_iters_run"], tel["cg_iters_budget"]) == (0.0, 0.0)
    assert all(tel[k] >= 0 for k in phases)
    assert (tel["solver"], tel["compute_dtype"], tel["sweep_chunk"]) == (
        "cholesky", "float32", 1)
    # the set-up account in place of an estimate: the two plans' uploads,
    # and the half-sweep among the executables the train acquired
    setup = tel["setup"]
    assert set(setup["tables"]) == {"user_plan", "item_plan"}
    assert setup["total"]["upload_bytes"] > 0
    assert "als_sweep" in setup["executables"] or \
        setup["total"]["compiled"] + setup["total"]["cache_loaded"] == 0
    assert tel["s_per_iter"] * cfg.iterations == pytest.approx(
        tel["iters_s"])
    np.testing.assert_allclose(m1.user_factors, m2.user_factors,
                               rtol=1e-5)


def test_implicit_dual_solve_matches_primal():
    """The implicit Woodbury route (eigendecomposed base + D^1/2-form
    SMW, K < rank buckets) is exact algebra: factors must match the
    primal normal-equation path through multiple alternations, including
    negative (dislike) signals whose confidence enters without
    preference."""
    rng = np.random.default_rng(7)
    n_u, n_i, nnz = 400, 120, 6000
    ui = rng.integers(0, n_u, nnz)
    ii = rng.integers(0, n_i, nnz)
    vv = rng.integers(1, 6, nnz).astype(np.float32)
    vv[rng.random(nnz) < 0.1] *= -1
    r = RatingsCOO(ui, ii, vv, n_u, n_i)
    kw = dict(rank=16, iterations=5, lam=0.05, seed=1,
              implicit_prefs=True, alpha=0.8)
    m_primal = als_train(r, ALSConfig(dual_solve="never", **kw))
    m_dual = als_train(r, ALSConfig(dual_solve="auto", **kw))
    scale = np.abs(m_primal.user_factors).max()
    assert np.abs(m_primal.user_factors
                  - m_dual.user_factors).max() < 1e-3 * scale
    assert np.abs(m_primal.item_factors
                  - m_dual.item_factors).max() < 1e-3 * scale


@pytest.mark.parametrize("implicit,alpha", [(False, 1.0), (True, 20.0)])
def test_dual_solve_large_k_buckets(implicit, alpha):
    """Dual routes for buckets with K in the 32-128 range (power-of-two
    padding below rank) must stay exact — the K-dim CG runs K+margin
    iterations, not a fixed cap, and large Hu-Koren alpha makes the
    Woodbury system genuinely ill-conditioned."""
    rng = np.random.default_rng(11)
    n_u, n_i, rank = 60, 500, 150
    # each user rates 30-120 items -> K buckets 32/64/128, all < rank
    ui, ii, vv = [], [], []
    for u in range(n_u):
        k = int(rng.integers(30, 120))
        for i in rng.choice(n_i, size=k, replace=False):
            ui.append(u)
            ii.append(int(i))
            vv.append(float(rng.integers(1, 6)))
    r = RatingsCOO(np.array(ui), np.array(ii),
                   np.array(vv, dtype=np.float32), n_u, n_i)
    # Baseline: primal + exact cholesky. The dual route runs CG on its
    # K-dim systems (solver='cg'; iters=K+8 — under the old min(48, K+8)
    # cap the K=64/128 buckets under-solve and this fails). Notably the
    # PRIMAL R-dim CG does NOT converge at alpha=20 (rel err ~0.24 vs
    # cholesky) while the dual does (~1e-3): the dual route is also a
    # numerical robustness improvement in the ill-conditioned regime.
    kw = dict(rank=rank, iterations=2, lam=0.05, seed=1,
              implicit_prefs=implicit, alpha=alpha)
    m_exact = als_train(r, ALSConfig(dual_solve="never",
                                     solver="cholesky", **kw))
    m_dual = als_train(r, ALSConfig(dual_solve="auto", solver="cg", **kw))
    scale = np.abs(m_exact.user_factors).max()
    assert np.abs(m_exact.user_factors
                  - m_dual.user_factors).max() < 2e-3 * scale


# ---------------------------------------------------------------------------
# PR 30: batches padded so that the TPU's gather takes its 256-row step
# ---------------------------------------------------------------------------

# (B, K) of rungs of the benchmark's two train plans as uploaded (goodreads
# at sweep_chunk 2, taobao at 1) with the padding each gets, then what the
# rule has to leave alone
_RUNGS = [(262144, 8, 16), (131072, 16, 8), (87380, 24, 7), (65536, 32, 4),
          (52428, 40, 4), (17476, 120, 2), (11914, 176, 3), (10082, 208, 2),
          (5041, 208, 1), (1310, 1600, 1), (138, 3584, 1), (43690, 24, 6),
          (5957, 176, 2), (95221, 8, 27), (2520, 416, 1), (32768, 64, 2),
          (655, 1600, 0), (268, 2752, 0),  # the count lies in the window
          (8, 10752, 0), (1, 118016, 0),   # under 32 systems: never padded
          (4096, 1024, 0)]                 # K a multiple of the tile: no count


@pytest.mark.parametrize("b,k,want", _RUNGS)
def test_gather_pad_rows_puts_the_count_in_the_256_step_window(b, k, want):
    lo, hi = als_mod._GATHER_STEP_256

    def in_window(extra):
        return lo <= (b + extra) * k % als_mod._GATHER_TILE <= hi

    extra = als_mod._gather_pad_rows(b, k)
    assert extra == want <= b // 32
    if extra:        # the fewest: no smaller padding reaches the window
        assert in_window(extra) and not any(map(in_window, range(extra)))
    else:            # there already, or out of reach
        assert in_window(0) or not any(map(in_window, range(b // 32 + 1)))


def _padded_upload(monkeypatch, mesh, plan, chunk):
    """`_upload_plan` as a single TPU device gets it."""
    with monkeypatch.context() as m:
        m.setattr(als_mod, "_gather_layout",
                  lambda mesh, rank=None, factor_sharding="replicated":
                  "rows+pad256")
        return als_mod._upload_plan(mesh, plan, chunk)


@pytest.mark.parametrize("chunk", [1, 3])
def test_upload_pads_groups_with_systems_that_solve_nothing(monkeypatch,
                                                            chunk):
    import jax
    from predictionio_tpu.parallel.mesh import make_mesh
    rng = np.random.default_rng(5)
    n_u, n_i, nnz = 4000, 300, 40000
    r = RatingsCOO(rng.integers(0, n_u, nnz), rng.integers(0, n_i, nnz),
                   rng.uniform(1, 5, nnz).astype(np.float32), n_u, n_i)
    plan = plan_for_users(r, work_budget=4096)
    mesh = make_mesh(devices=jax.devices()[:1])
    plain = als_mod._upload_plan(mesh, plan, chunk)
    padded = _padded_upload(monkeypatch, mesh, plan, chunk)
    assert len(plain) == len(padded)
    grown = 0
    for (rows, idx, val, mask), (prows, pidx, pval, pmask) in zip(plain,
                                                                  padded):
        n, b, k = idx.shape
        extra = als_mod._gather_pad_rows(b, k)
        grown += extra
        assert pidx.shape == (n, b + extra, k) == pval.shape == pmask.shape
        assert prows.shape == (n, b + extra)
        for x, px in ((rows, prows), (idx, pidx), (val, pval),
                      (mask, pmask)):
            assert (np.asarray(px)[:, :b] == np.asarray(x)).all()
        assert (np.asarray(prows)[:, b:] == -1).all()
        assert not np.asarray(pmask)[:, b:].any()
        assert not np.asarray(pidx)[:, b:].any()
    assert grown > 0


@pytest.mark.parametrize("solver", ["cholesky", "cg"])
@pytest.mark.parametrize("implicit", [False, True])
def test_padded_half_sweep_leaves_the_rows_of_the_unpadded(monkeypatch,
                                                           implicit, solver):
    """The padding systems scatter onto the dummy row and the batched
    arithmetic of the others does not see them: the same rows, bit for
    bit."""
    import jax
    import jax.numpy as jnp
    from predictionio_tpu.parallel.mesh import make_mesh
    rng = np.random.default_rng(6)
    n_u, n_i, nnz, rank = 3000, 200, 30000, 12
    r = RatingsCOO(rng.integers(0, n_u, nnz), rng.integers(0, n_i, nnz),
                   rng.integers(1, 6, nnz).astype(np.float32), n_u, n_i)
    plan = plan_for_users(r, work_budget=2048)
    mesh = make_mesh(devices=jax.devices()[:1])
    cfg = ALSConfig(rank=rank, implicit_prefs=implicit, solver=solver,
                    lam=0.05)
    items = jnp.asarray(als_mod._init_factors(n_i, rank, 1, 2))
    gram = als_mod._side_gram(cfg, items, n_i, "item")
    uploads = {"plain": als_mod._upload_plan(mesh, plan, 2),
               "padded": _padded_upload(monkeypatch, mesh, plan, 2)}
    systems = {name: sum(idx.shape[1] for _, idx, _, _ in groups)
               for name, groups in uploads.items()}
    assert systems["padded"] > systems["plain"]
    out = {}
    for name, groups in uploads.items():
        users = jnp.asarray(als_mod._init_factors(n_u, rank, 1, 1))
        out[name] = np.asarray(als_mod._run_side(groups, users, items, cfg,
                                                 gram))
    # all but the scatter's dummy row, which the padding systems write
    assert (out["padded"][:n_u] == out["plain"][:n_u]).all()


def test_cpu_and_mesh_uploads_are_not_padded(mesh8):
    """The rule reads what the program can see: one TPU device pads its
    batches for the gather's step; the CPU has no such step, and a mesh
    shards the batch dimension, which has to stay divisible."""
    import types

    import jax
    from predictionio_tpu.parallel.mesh import make_mesh
    r = synthetic_ratings(seed=5)
    tel = {}
    als_train(r, ALSConfig(rank=4, iterations=1, factor_sharding="model"),
              mesh8, telemetry=tel)
    assert tel["gather_layout"] == "rows"
    assert als_mod._gather_layout(mesh8) == "rows"
    assert als_mod._gather_layout(
        make_mesh(devices=jax.devices()[:1])) == "rows"

    def mesh_of(platform, n):
        devices = np.array([types.SimpleNamespace(platform=platform)
                            for _ in range(n)], dtype=object)
        return types.SimpleNamespace(n_devices=n, mesh=types.SimpleNamespace(
            devices=devices.reshape(n, 1)))

    assert als_mod._gather_layout(mesh_of("tpu", 1)) == "rows+pad256"
    assert als_mod._gather_layout(mesh_of("tpu", 1), 200) == "rows+pad256"
    # the templates' rank 10: such a table's gathers take one step always
    assert als_mod._gather_layout(mesh_of("tpu", 1), 10) == "rows"
    assert als_mod._gather_layout(mesh_of("tpu", 4)) == "rows"
    assert als_mod._gather_layout(mesh_of("gpu", 1)) == "rows"
    # row-sharded tables: each chip gathers in a program of its own
    sharded = mesh_of("tpu", 4)
    sharded.model_parallelism = 4
    assert als_mod._gather_layout(sharded, 200, "model") == "rows+pad256"
    assert als_mod._gather_layout(sharded, 200, "replicated") == "rows"
