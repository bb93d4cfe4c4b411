"""The batched SPD solvers (ops/solve.py): CG, jnp and Pallas, against
LAPACK cholesky and float64, alone and inside als_train.
"""

import numpy as np
import pytest

from predictionio_tpu.ops.solve import (cg_solve, cholesky_solve,
                                        resolve_solver, spd_solve)


def make_spd(b, r, cond, seed=0):
    """Batched SPD matrices with controlled condition number."""
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.standard_normal((b, r, r)))
    # eigenvalues geometric from 1 to 1/cond
    eig = np.geomspace(1.0, 1.0 / cond, r)
    A = np.einsum("brs,s,bts->brt", q, eig, q).astype(np.float32)
    x_true = rng.standard_normal((b, r)).astype(np.float32)
    rhs = np.einsum("brs,bs->br", A, x_true)
    return A, rhs, x_true


def cg_kernel_tile(A, rhs, iters, **kw):
    """ops/solve._cg_kernel over one tile through the Pallas interpreter:
    (x, the iterations the tile ran). `tol=0.0` is the kernel without its
    exit: the whole fixed budget."""
    import functools
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from predictionio_tpu.ops import solve as S

    x, ran = pl.pallas_call(
        functools.partial(S._cg_kernel, iters=iters, **kw),
        out_shape=(jax.ShapeDtypeStruct(rhs.shape, jnp.float32),
                   jax.ShapeDtypeStruct((1, 1, 128), jnp.int32)),
        interpret=True,
    )(jnp.asarray(A), jnp.asarray(rhs))
    ran = np.asarray(ran)
    assert (ran == ran[0, 0, 0]).all()
    return np.asarray(x), int(ran[0, 0, 0])


def als_dual_systems(b, k, rank=200, seed=0):
    """K x K dual systems as an explicit half-sweep builds them from
    fresh factors (ops/als._solve_batch): M M^T + lam n I over rows
    |N(0,1)| / sqrt(rank), lam 0.01, against ratings 1..5; the last
    eighth of every system is the plan's padding (zero rows, reg on the
    diagonal, y = 0)."""
    rng = np.random.default_rng(seed)
    n = k - k // 8
    M = np.zeros((b, k, rank), np.float32)
    M[:, :n] = np.abs(rng.standard_normal((b, n, rank))) / np.sqrt(rank)
    A = np.einsum("bkr,blr->bkl", M, M) + \
        np.float32(0.01 * n) * np.eye(k, dtype=np.float32)
    y = np.zeros((b, k), np.float32)
    y[:, :n] = rng.integers(1, 6, (b, n))
    return A.astype(np.float32), y


def float64_solve(A, rhs):
    return np.linalg.solve(A.astype(np.float64),
                           rhs.astype(np.float64)[..., None])[..., 0]


def row_errors(x, want):
    return np.linalg.norm(x - want, axis=1) / np.linalg.norm(want, axis=1)


class TestCGSolve:
    @pytest.mark.parametrize("cond,iters", [(10.0, 32), (1e3, 128),
                                            (1e4, 384)])
    def test_matches_truth(self, cond, iters):
        """Adversarial geometric spectra (Jacobi can't help a random-Q
        eigenbasis): CG needs ~sqrt(cond)*ln(1/eps) iterations, and gets
        there."""
        A, rhs, x_true = make_spd(16, 32, cond)
        x = np.asarray(cg_solve(A, rhs, iters=iters))
        rel = np.linalg.norm(x - x_true) / np.linalg.norm(x_true)
        assert rel < 1e-3, f"cond={cond}: rel error {rel}"

    def test_matches_cholesky_on_als_like_systems(self):
        rng = np.random.default_rng(1)
        B, K, R = 8, 40, 16
        V = rng.standard_normal((B, K, R)).astype(np.float32) / np.sqrt(R)
        A = np.einsum("bkr,bks->brs", V, V) + \
            0.1 * K * np.eye(R, dtype=np.float32)
        rhs = rng.standard_normal((B, R)).astype(np.float32)
        x_chol = np.asarray(cholesky_solve(A, rhs))
        x_cg = np.asarray(cg_solve(A, rhs))
        np.testing.assert_allclose(x_cg, x_chol, rtol=2e-3, atol=2e-4)

    def test_cg_pallas_interpret_smoke(self):
        """Pallas CG kernel math check via the interpreter (no TPU)."""
        A, rhs, x_true = make_spd(4, 16, 50.0)
        x, ran = cg_kernel_tile(A, rhs, iters=32)
        rel = np.linalg.norm(x - x_true) / np.linalg.norm(x_true)
        assert rel < 1e-3
        assert 0 < ran <= 32

    def test_cg_pallas_interpret_dual_shapes(self):
        """The dual path feeds the kernel [B, K, K] systems with K down to
        32 — check the kernel math at a representative small K."""
        A, rhs, x_true = make_spd(16, 48, 80.0)
        x, ran = cg_kernel_tile(A, rhs, iters=56)
        rel = np.linalg.norm(x - x_true) / np.linalg.norm(x_true)
        assert rel < 1e-3
        assert 0 < ran <= 56

    @pytest.mark.parametrize("k", [24, 40, 56, 144])
    def test_cg_pallas_interpret_new_ladder_ks(self, k):
        """The round-4 bucket ladder feeds the kernel K values that are
        multiples of 8 but not 16 (24, 40, 56, ...) — check the kernel
        math at each (that Mosaic compiles them is what chip_smoke.py
        shows on the chip: the rank-200 plan's dual route runs them)."""
        A, rhs, x_true = make_spd(8, k, 60.0)
        x, ran = cg_kernel_tile(A, rhs, iters=k + 8)
        rel = np.linalg.norm(x - x_true) / np.linalg.norm(x_true)
        assert rel < 1e-3
        assert 0 < ran <= k + 8

    def test_spd_solve_dispatch(self):
        A, rhs, _ = make_spd(4, 8, 10.0)
        for method in ("cholesky", "cg"):
            x, _ = spd_solve(A, rhs, method=method)
            np.testing.assert_allclose(
                x, np.linalg.solve(A, rhs[..., None])[..., 0],
                rtol=1e-3, atol=1e-4)
        with pytest.raises(ValueError):
            spd_solve(A, rhs, method="qr")

    def test_resolve_solver(self):
        for name in ("cholesky", "cg", "cg_pallas"):
            assert resolve_solver(name) == name
        # on the CPU test backend auto is cholesky
        assert resolve_solver("auto", 1) == "cholesky"
        assert resolve_solver("auto", 8) == "cholesky"

    def test_als_with_cg_matches_cholesky(self, mesh8):
        from predictionio_tpu.ops.als import ALSConfig, als_rmse, als_train
        from predictionio_tpu.ops.ratings import RatingsCOO

        rng = np.random.default_rng(3)
        n_u, n_i, nnz = 60, 40, 600
        ui = rng.integers(0, n_u, nnz).astype(np.int32)
        ii = rng.integers(0, n_i, nnz).astype(np.int32)
        vv = (1 + 4 * rng.random(nnz)).astype(np.float32)
        r = RatingsCOO(ui, ii, vv, n_u, n_i)
        kw = dict(rank=8, iterations=6, lam=0.1, seed=2, work_budget=512)
        m_chol = als_train(r, ALSConfig(solver="cholesky", **kw), mesh8)
        m_cg = als_train(r, ALSConfig(solver="cg", **kw), mesh8)
        assert abs(als_rmse(m_chol, r) - als_rmse(m_cg, r)) < 5e-3
        np.testing.assert_allclose(m_cg.user_factors, m_chol.user_factors,
                                   rtol=0.05, atol=0.05)


def test_als_with_cg_pallas_matches_cholesky_and_counts(monkeypatch):
    """als_train through the Pallas CG (the interpreter here), dual K x K
    and primal systems both: the factors of the Cholesky train, and a
    telemetry that carries the last iteration's CG iterations, run under
    allowed."""
    import functools
    import jax
    from predictionio_tpu.obs.metrics import get_registry
    from predictionio_tpu.ops import solve as S
    from predictionio_tpu.ops.als import ALSConfig, als_train
    from predictionio_tpu.ops.ratings import RatingsCOO
    from predictionio_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(S, "cg_solve_pallas", functools.partial(
        S.cg_solve_pallas, interpret=True))
    rng = np.random.default_rng(11)
    n_u, n_i = 48, 120
    # 36 ratings a user (K 40 < rank: Pallas dual), 14.4 an item (jnp CG)
    # and a few heavy users (K >= rank: Pallas primal)
    deg = np.where(np.arange(n_u) < 4, 100, 36)
    ui = np.repeat(np.arange(n_u), deg).astype(np.int32)
    ii = np.concatenate([rng.choice(n_i, d, replace=False)
                         for d in deg]).astype(np.int32)
    r = RatingsCOO(ui, ii, rng.integers(1, 6, ui.size).astype(np.float32),
                   n_u, n_i)
    mesh = make_mesh(devices=jax.devices()[:1])
    kw = dict(rank=48, iterations=2, lam=0.1, seed=2, work_budget=2048)
    tel = {}
    m_cg = als_train(r, ALSConfig(solver="cg_pallas", **kw), mesh,
                     telemetry=tel)
    m_chol = als_train(r, ALSConfig(solver="cholesky", **kw), mesh)
    np.testing.assert_allclose(m_cg.user_factors, m_chol.user_factors,
                               rtol=2e-3, atol=2e-4)
    assert 0 < tel["cg_iters_run"] <= tel["cg_iters_budget"]
    # whole tiles of 16 systems, the batches' padding among them
    assert tel["cg_iters_run"] % 16 == 0 == tel["cg_iters_budget"] % 16
    gauge = get_registry().get("pio_als_cg_iterations_run_ratio")
    assert gauge.value == pytest.approx(
        tel["cg_iters_run"] / tel["cg_iters_budget"])


class TestCGStopsWhenConverged:
    """The Pallas CG kernel leaves once its tile has converged, and its
    iteration budget is the cap (ISSUE 28)."""

    @pytest.mark.parametrize("k", [40, 88, 176])
    def test_als_dual_systems_leave_early_and_exact(self, k):
        A, y = als_dual_systems(16, k)
        x, ran = cg_kernel_tile(A, y, iters=k + 8)
        assert row_errors(x, float64_solve(A, y)).max() < 1e-5
        assert 0 < ran < (k + 8) // 2

    def test_hard_system_runs_to_the_cap_as_the_fixed_budget_does(self):
        """Condition 1e6: no early exit, so bit for bit the answer of the
        same kernel with no exit at all. Against the jnp CG, which sums
        in another order, 40 unconverged float32 iterations of such a
        system agree in how far they got (the tile's median residual: a
        single system's swings by several times), not in x."""
        A, rhs, _ = make_spd(16, 32, 1e6)
        x, ran = cg_kernel_tile(A, rhs, iters=40)
        assert ran == 40
        whole, ran_whole = cg_kernel_tile(A, rhs, iters=40, tol=0.0)
        assert ran_whole == 40 and np.array_equal(x, whole)

        def residual(x):
            return np.linalg.norm(np.einsum("brs,bs->br", A, x) - rhs,
                                  axis=1) / np.linalg.norm(rhs, axis=1)
        got = np.median(residual(x))
        fixed = np.median(residual(np.asarray(cg_solve(A, rhs, iters=40))))
        assert fixed / 2 < got < 2 * fixed

    def test_cap_that_is_no_whole_number_of_blocks_is_kept(self):
        A, rhs, _ = make_spd(16, 32, 1e6)
        _, ran = cg_kernel_tile(A, rhs, iters=10)
        assert ran == 10

    def test_tile_of_padding_leaves_at_once(self):
        """A = I, b = 0, as cg_solve_pallas pads a batch: zeros, no NaN,
        and the tile holds nothing back (it leaves at the first look at
        its residuals, before any block)."""
        A = np.broadcast_to(np.eye(48, dtype=np.float32), (16, 48, 48))
        x, ran = cg_kernel_tile(A, np.zeros((16, 48), np.float32), iters=56)
        assert np.array_equal(x, np.zeros((16, 48), np.float32))
        assert ran == 0

    def test_mixed_tile_runs_as_long_as_its_hard_system_needs(self):
        k = 88
        easy, y = als_dual_systems(16, k)
        hard, rhs, _ = make_spd(1, k, 100.0, seed=4)
        _, ran_easy = cg_kernel_tile(easy, y, iters=k + 8)
        alone = np.concatenate(
            [hard, np.broadcast_to(np.eye(k, dtype=np.float32),
                                   (15, k, k))])
        _, ran_hard = cg_kernel_tile(
            alone, np.concatenate([rhs, np.zeros((15, k), np.float32)]),
            iters=k + 8)
        A, b = easy.copy(), y.copy()
        A[5], b[5] = hard[0], rhs[0]
        x, ran = cg_kernel_tile(A, b, iters=k + 8)
        assert k + 8 > ran == ran_hard > 2 * ran_easy
        assert row_errors(x, float64_solve(A, b)).max() < 1e-5

    def test_cg_solve_pallas_counts_tiles(self):
        """The wrapper's grid: 40 systems are three tiles, the last one
        half padding; the count is over all 48."""
        import jax.numpy as jnp
        from predictionio_tpu.ops.solve import cg_solve_pallas
        A, y = als_dual_systems(40, 40)
        x, counted = cg_solve_pallas(jnp.asarray(A), jnp.asarray(y),
                                     iters=48, system="dual",
                                     interpret=True)
        assert x.shape == (40, 40)
        assert row_errors(np.asarray(x), float64_solve(A, y)).max() < 1e-5
        run, allowed = np.asarray(counted)
        assert allowed == 3 * 16 * 48
        assert run % 16 == 0 and 0 < run < allowed / 2

    def test_spd_solve_reports_run_and_allowed(self, monkeypatch):
        import functools
        import jax.numpy as jnp
        from predictionio_tpu.ops import solve as S
        monkeypatch.setattr(S, "cg_solve_pallas", functools.partial(
            S.cg_solve_pallas, interpret=True))
        A, y = als_dual_systems(20, 40)
        x, counted = S.spd_solve(jnp.asarray(A), jnp.asarray(y),
                                 method="cg_pallas", iters=48,
                                 system="dual")
        run, allowed = np.asarray(counted)
        assert allowed == 32 * 48 and 0 < run < allowed / 2
        _, counted = S.spd_solve(jnp.asarray(A), jnp.asarray(y),
                                 method="cg", iters=48)
        assert np.array_equal(np.asarray(counted), [0.0, 0.0])


class TestDualSolve:
    def test_dual_matches_primal(self, mesh8):
        """Woodbury/dual K<rank route produces the same factors as the
        primal normal equations (exact algebra, so tight tolerance)."""
        from predictionio_tpu.ops.als import ALSConfig, als_rmse, als_train
        from predictionio_tpu.ops.ratings import RatingsCOO

        rng = np.random.default_rng(5)
        n_u, n_i, nnz = 80, 50, 480   # ~6 ratings/user << rank
        ui = rng.integers(0, n_u, nnz).astype(np.int32)
        ii = rng.integers(0, n_i, nnz).astype(np.int32)
        vv = (1 + 4 * rng.random(nnz)).astype(np.float32)
        r = RatingsCOO(ui, ii, vv, n_u, n_i)
        kw = dict(rank=24, iterations=4, lam=0.1, seed=2, work_budget=512,
                  solver="cholesky")
        m_dual = als_train(r, ALSConfig(dual_solve="auto", **kw), mesh8)
        m_prim = als_train(r, ALSConfig(dual_solve="never", **kw), mesh8)
        np.testing.assert_allclose(m_dual.user_factors, m_prim.user_factors,
                                   rtol=2e-3, atol=2e-4)
        assert abs(als_rmse(m_dual, r) - als_rmse(m_prim, r)) < 1e-3

    def test_dual_with_cg(self, mesh8):
        from predictionio_tpu.ops.als import ALSConfig, als_rmse, als_train
        from predictionio_tpu.ops.ratings import RatingsCOO

        rng = np.random.default_rng(6)
        n_u, n_i, nnz = 60, 40, 360
        r = RatingsCOO(rng.integers(0, n_u, nnz).astype(np.int32),
                       rng.integers(0, n_i, nnz).astype(np.int32),
                       (1 + 4 * rng.random(nnz)).astype(np.float32),
                       n_u, n_i)
        kw = dict(rank=24, iterations=4, lam=0.1, seed=2, work_budget=512)
        m_cg = als_train(r, ALSConfig(solver="cg", **kw), mesh8)
        m_ch = als_train(r, ALSConfig(solver="cholesky",
                                      dual_solve="never", **kw), mesh8)
        assert abs(als_rmse(m_cg, r) - als_rmse(m_ch, r)) < 5e-3


class TestBF16FactorStorage:
    def test_bf16_tables_match_f32_quality(self, mesh8):
        """factor_dtype='bfloat16' halves gather traffic; RMSE must stay
        within bf16 rounding of the f32-stored run."""
        from predictionio_tpu.ops.als import ALSConfig, als_rmse, als_train
        from predictionio_tpu.ops.ratings import RatingsCOO

        rng = np.random.default_rng(9)
        n_u, n_i, nnz = 60, 40, 700
        r = RatingsCOO(rng.integers(0, n_u, nnz).astype(np.int32),
                       rng.integers(0, n_i, nnz).astype(np.int32),
                       (1 + 4 * rng.random(nnz)).astype(np.float32),
                       n_u, n_i)
        kw = dict(rank=8, iterations=5, lam=0.1, seed=2, work_budget=512)
        m32 = als_train(r, ALSConfig(factor_dtype="float32", **kw), mesh8)
        m16 = als_train(r, ALSConfig(factor_dtype="bfloat16", **kw), mesh8)
        assert m16.user_factors.dtype == np.float32  # host copy upcast
        rmse32, rmse16 = als_rmse(m32, r), als_rmse(m16, r)
        assert abs(rmse32 - rmse16) < 0.02, (rmse32, rmse16)


#: Names that selected code this repository no longer has (ISSUE 29).
DELETED_SOLVERS = ["schulz", "schulz_pallas", "chol_pallas", "chol_blocked",
                   "diag_gather", "diag_nosolve"]
DELETED_FIELD, DELETED_JIT = "fuse_iteration", "_solve_iteration"


@pytest.mark.parametrize("name", DELETED_SOLVERS)
def test_deleted_solver_names_are_refused(name, monkeypatch):
    """`resolve_solver` holds the solver names: another one is a
    ValueError from it, from `spd_solve` and from `als_train`, which
    refuses before it plans anything, let alone traces."""
    from predictionio_tpu.ops import als
    from predictionio_tpu.ops.ratings import RatingsCOO

    with pytest.raises(ValueError, match=name):
        resolve_solver(name)
    A, rhs, _ = make_spd(4, 8, 10.0)
    with pytest.raises(ValueError, match=name):
        spd_solve(A, rhs, method=name)

    def no_plan(*a, **kw):
        raise AssertionError("als_train planned before it refused")
    monkeypatch.setattr(als, "plan_for_users", no_plan)
    r = RatingsCOO(np.zeros(4, np.int32), np.arange(4, dtype=np.int32),
                   np.ones(4, np.float32), 1, 4)
    with pytest.raises(ValueError, match=name):
        als.als_train(r, als.ALSConfig(rank=4, iterations=1, solver=name))


def test_no_fused_iteration_option_is_left():
    """One way to run an iteration: neither ALSConfig nor an engine's
    algorithm parameters can ask for another."""
    import dataclasses
    from predictionio_tpu.models import (ecommerce, recommendation,
                                         recommendeduser, similarproduct)
    from predictionio_tpu.ops import als

    params = [als.ALSConfig, recommendation.ALSAlgorithmParams,
              similarproduct.ALSAlgorithmParams,
              ecommerce.ECommAlgorithmParams,
              recommendeduser.ALSAlgorithmParams]
    for cls in params:
        assert DELETED_FIELD not in {f.name for f in dataclasses.fields(cls)}
    assert not hasattr(als, DELETED_JIT)
