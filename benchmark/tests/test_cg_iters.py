"""`cg_iters_run_pct`: the reader over the program's count of CG iterations,
with a counter, without one (the parent), and with one that counted nothing
(a solver that is not the Pallas CG: every CPU run)."""

import pytest

import tinytree
from benchmark.lib.spec import Spec


@pytest.fixture()
def read():
    return Spec(tinytree.REPO).reader("cg_iters_run_pct.train")


def test_both_entries_share_the_reader_and_list_their_cells():
    spec = Spec(tinytree.REPO)
    assert spec.reader("cg_iters_run_pct.train_implicit").__code__.co_filename \
        == spec.reader("cg_iters_run_pct.train").__code__.co_filename
    for cell, name in (
            ("rec-goodreads-r200.train", "cg_iters_run_pct.train"),
            ("ecomm-taobao-ub-r200.train-implicit",
             "cg_iters_run_pct.train_implicit")):
        mine = [m for m in spec.metrics_of(cell, "per_layer")
                if m["name"].startswith("cg_iters_run_pct")]
        assert [m["name"] for m in mine] == [name]
        assert (mine[0]["source"], mine[0]["moves"], mine[0]["better"]) == (
            "program_counter", "train_ratings_per_s", "lower")


def test_run_over_allowed_of_the_programs_count(read, monkeypatch):
    from predictionio_tpu.ops import als
    monkeypatch.setattr(als, "last_cg_iterations",
                        lambda: (3.0e7, 1.2e8), raising=False)
    assert read({}) == 25.0


@pytest.mark.parametrize("counted", [None, (0.0, 0.0)])
def test_nothing_counted_reads_nothing(read, monkeypatch, counted):
    from predictionio_tpu.ops import als
    monkeypatch.setattr(als, "last_cg_iterations", lambda: counted,
                        raising=False)
    assert read({}) is None


def test_a_program_without_the_counter_reads_nothing(read, monkeypatch):
    from predictionio_tpu.ops import als
    monkeypatch.delattr(als, "last_cg_iterations", raising=False)
    assert read({}) is None


def test_the_count_of_real_half_sweeps(read, monkeypatch):
    """Two half-sweeps through the Pallas kernel (the interpreter here):
    the reader's share is the program's own run over allowed."""
    import functools

    import jax
    import numpy as np

    from predictionio_tpu.ops import als, solve
    from predictionio_tpu.ops.ratings import RatingsCOO, plan_for_users
    from predictionio_tpu.parallel.mesh import make_mesh
    monkeypatch.setattr(solve, "cg_solve_pallas", functools.partial(
        solve.cg_solve_pallas, interpret=True))
    rng = np.random.default_rng(0)
    n_u, n_i, rank = 40, 400, 64
    ui = np.repeat(np.arange(n_u), 40).astype(np.int32)
    ii = rng.integers(0, n_i, ui.size).astype(np.int32)
    coo = RatingsCOO(ui, ii, rng.integers(1, 6, ui.size).astype(np.float32),
                     n_u, n_i)
    mesh = make_mesh(devices=jax.devices()[:1])
    groups = als._upload_plan(mesh, plan_for_users(coo, work_budget=1 << 12))
    cfg = als.ALSConfig(rank=rank, solver="cg_pallas")
    U = als._init_factors(n_u, rank, 0, 1)
    V = als._init_factors(n_i, rank, 0, 2)
    for _ in range(2):
        U = als._run_side(groups, U, V, cfg, None)
    run, allowed = als.last_cg_iterations()
    assert 0 < run < allowed
    assert read({}) == 100.0 * run / allowed
