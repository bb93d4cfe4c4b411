"""The program's implicit-feedback half-sweep (`_gram_eig` + `_run_side`)
against the configuration's plain reference,
benchmark/references/als-implicit.py (Cholesky of the full A_u, float32 at
`highest`, nothing of the program imported): seeded random tables at a tiny
size on the CPU, every route a row can take.

Routes, by what `_solve_batch` can see (K against the rank, the solver):
  small   K < 32 under solver cg_pallas: eig-SMW with the jnp CG
  smw     32 <= K < rank: eig-SMW, the K x K dual system
  primal  K >= rank: A_u = G + sum (c - 1) y y^T formed and solved

Tolerances, as the widest relative row error |x - x_ref| / |x_ref|:
  float32   2e-4. G's top eigenvalue stands ~0.64 R / 0.36 = 85 times over
            the rest at rank 48 (every factor positive), so float32's 6e-8
            reaches a row as ~1e-5; the jnp CG stops at about the same.
            Measured here: at most 9e-6.
  bfloat16  2e-2. The Gram and right-hand-side operands rounded to 8 bits
            (2^-9 each, summed over K <= 64 terms of one sign) come to
            4e-3..8e-3 at these sizes (measured: at most 7.6e-3). A float8
            operand (2^-4) reads 6.8e-2 or more and fails it.
"""

import importlib.util
import os

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RANK, N_COUNTER, N_OUT = 48, 400, 90
LAM, ALPHA = 0.01, 1.0
TOL = {"float32": 2e-4, "bfloat16": 2e-2}
# route -> (K, solver): cg_pallas sends K < 32 to the jnp CG and is never
# asked for a Pallas call here; `cg` is the same Jacobi-CG as the TPU's
# kernel, in jax.numpy
ROUTES = {"small": (8, "cg_pallas"), "smw": (40, "cg"), "primal": (64, "cg")}


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "als_implicit_reference", os.path.join(
            REPO, "benchmark", "references", "als-implicit.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _tables(seed):
    rng = np.random.default_rng(seed)
    table = lambda n: (np.abs(rng.standard_normal((n + 1, RANK)))
                       / np.sqrt(RANK)).astype(np.float32)
    # both end in the scatter's dummy row, which is no entity; the
    # counterpart's is random, so a Gram that counts it is wrong
    return table(N_OUT), table(N_COUNTER)


def _group(seed, K, data):
    """Two batches of 32 rows: rows [N, B], idx / val / mask [N, B, K]."""
    rng = np.random.default_rng([seed, K])
    N, B = 2, 32
    rows = rng.permutation(N_OUT)[:N * B].reshape(N, B).astype(np.int32)
    idx = np.stack([np.stack([rng.permutation(N_COUNTER)[:K]
                              for _ in range(B)]) for _ in range(N)])
    val = rng.geometric(0.6, (N, B, K)).astype(np.float32)
    mask = np.ones((N, B, K), np.float32)
    if data == "padded":
        # rows shorter than their bucket: mask 0 and count 0 behind the
        # row's own length, and one padding row (-1) with no entity at all
        length = rng.integers(max(1, K // 2), K + 1, (N, B))
        mask = (np.arange(K)[None, None, :] < length[..., None]).astype(
            np.float32)
        val = val * mask
        idx = (idx * mask).astype(np.int64)
        rows[0, 3], mask[0, 3], val[0, 3], idx[0, 3] = -1, 0.0, 0.0, 0
    elif data == "negative":
        # a "dislike": confidence 1 + alpha |r| in A_u, preference 0
        val[..., ::3] *= -1.0
    return rows, idx.astype(np.int32), val, mask


def _program(cfg_kw, out0, counter, group, spoil=None):
    from predictionio_tpu.ops import als
    cfg = als.ALSConfig(rank=RANK, lam=LAM, alpha=ALPHA, implicit_prefs=True,
                        lambda_scaling="nratings", **cfg_kw)
    gram = als._gram_eig(counter, n_live=N_COUNTER)
    rows = group[0] if spoil is None else spoil(group[0])
    return np.asarray(als._run_side(((rows,) + group[1:],), out0.copy(),
                                    counter, cfg, gram))


def _reference(reference, counter, group):
    rows, idx, val, mask = group
    G = reference.gram(counter, N_COUNTER)
    want = {}
    for n in range(rows.shape[0]):
        x = np.asarray(reference.solve_rows(counter[idx[n]], val[n], mask[n],
                                            G, LAM, ALPHA, "nratings"))
        want.update({int(r): x[j] for j, r in enumerate(rows[n]) if r >= 0})
    return want


def _row_errors(got, want):
    return np.array([np.linalg.norm(got[r] - x) / np.linalg.norm(x)
                     for r, x in want.items()])


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("data", ["plain", "padded", "negative"])
@pytest.mark.parametrize("route", list(ROUTES))
def test_half_sweep_matches_the_plain_reference(reference, route, data,
                                                compute_dtype):
    K, solver = ROUTES[route]
    out0, counter = _tables(7)
    group = _group(7, K, data)
    got = _program(dict(solver=solver, compute_dtype=compute_dtype), out0,
                   counter, group)
    want = _reference(reference, counter, group)
    err = _row_errors(got, want)
    assert np.isfinite(got).all()
    assert err.max() <= TOL[compute_dtype], (route, data, err.max())
    # rows no batch names stay as they were, the dummy row apart
    untouched = np.setdiff1d(np.arange(N_OUT), list(want))
    assert (got[untouched] == out0[untouched]).all()


@pytest.mark.parametrize("route", list(ROUTES))
def test_the_direct_solver_agrees_too(reference, route):
    """`solver=auto` away from a TPU: LAPACK's Cholesky on the K x K dual
    or the R x R primal system."""
    out0, counter = _tables(11)
    group = _group(11, ROUTES[route][0], "padded")
    got = _program(dict(solver="cholesky", compute_dtype="float32"), out0,
                   counter, group)
    err = _row_errors(got, _reference(reference, counter, group))
    assert err.max() <= TOL["float32"], (route, err.max())


@pytest.mark.parametrize("route", list(ROUTES))
def test_every_second_row_left_unsolved_fails_the_comparison(reference,
                                                             route):
    K, solver = ROUTES[route]
    out0, counter = _tables(7)
    group = _group(7, K, "plain")

    def every_second(rows):
        rows = rows.copy()
        rows[:, 1::2] = -1
        return rows

    got = _program(dict(solver=solver, compute_dtype="float32"), out0,
                   counter, group, spoil=every_second)
    err = _row_errors(got, _reference(reference, counter, group))
    # the solved half reads as before, the other half has not moved
    assert (err <= TOL["float32"]).sum() == err.size // 2
    assert (err > 0.5).sum() == err.size // 2


@pytest.mark.parametrize("route", list(ROUTES))
def test_float8_operands_fail_the_bfloat16_tolerance(reference, route):
    """The tolerance is tight enough that the nearest precision below the
    stated one does not pass: the reference itself, from a table rounded
    through float8_e4m3fn."""
    _out0, counter = _tables(7)
    group = _group(7, ROUTES[route][0], "plain")
    want = _reference(reference, counter, group)
    low = _reference(reference,
                     reference.round_operands(counter, "float8_e4m3fn"),
                     group)
    err = _row_errors(low, want)
    assert err.max() > TOL["bfloat16"]


def test_the_gram_is_taken_over_the_live_rows_alone(reference):
    import jax.numpy as jnp
    from predictionio_tpu.ops import als
    _out0, counter = _tables(3)
    G, w, q = als._gram_eig(jnp.asarray(counter), n_live=N_COUNTER)
    want = counter[:N_COUNTER].astype(np.float64)
    want = want.T @ want
    assert np.abs(np.asarray(G) - want).max() <= 1e-5 * np.abs(want).max()
    # the reference's, in one block and in ragged blocks of 128 rows
    for block in (1 << 16, 128):
        assert np.abs(np.asarray(reference.gram(counter, N_COUNTER, block))
                      - want).max() <= 1e-5 * np.abs(want).max()
    # the dummy row counted: another matrix
    whole = np.asarray(als._gram(jnp.asarray(counter)))
    assert np.abs(whole - want).max() > 1e-3 * np.abs(want).max()
    # and Q diag(w) Q^T is the Gram
    back = (np.asarray(q) * np.asarray(w)) @ np.asarray(q).T
    assert np.abs(back - want).max() <= 1e-4 * np.abs(want).max()


def test_als_train_runs_the_same_half_sweeps(reference):
    """The normal path, als_train(implicit_prefs=True): its tables after two
    iterations against the reference iterated from the same init over the
    same counts."""
    from predictionio_tpu.ops import als
    from predictionio_tpu.ops.ratings import RatingsCOO
    rng = np.random.default_rng(5)
    n_users, n_items, rank = 60, 40, 8
    pairs = rng.permutation(n_users * n_items)[:700]
    u, i = (pairs // n_items).astype(np.int32), (pairs % n_items).astype(
        np.int32)
    order = np.argsort(u, kind="stable")
    u, i = u[order], i[order]
    v = rng.geometric(0.6, u.size).astype(np.float32)
    cfg = als.ALSConfig(rank=rank, iterations=2, lam=LAM, alpha=ALPHA,
                        implicit_prefs=True, seed=3, solver="cholesky",
                        sentinel=False)
    model = als.als_train(RatingsCOO(u, i, v, n_users, n_items), cfg)
    U = als._init_factors(n_users, rank, 3, 1)[:n_users]
    V = als._init_factors(n_items, rank, 3, 2)[:n_items]

    def side(mine, theirs, n_mine, counter):
        G = reference.gram(counter)
        out = np.zeros((n_mine, rank), np.float32)
        for e in range(n_mine):
            sel = np.flatnonzero(mine == e)
            if sel.size:
                out[e] = np.asarray(reference.solve_rows(
                    counter[theirs[sel]][None], v[sel][None],
                    np.ones((1, sel.size), np.float32), G, LAM, ALPHA,
                    "nratings"))[0]
        return out

    for _ in range(2):
        solved = side(u, i, n_users, V)
        U = np.where((np.bincount(u, minlength=n_users) > 0)[:, None],
                     solved, U)
        solved = side(i, u, n_items, U)
        V = np.where((np.bincount(i, minlength=n_items) > 0)[:, None],
                     solved, V)
    err = np.linalg.norm(model.item_factors - V, axis=1) / np.linalg.norm(
        V, axis=1)
    assert err.max() <= 1e-3, err.max()


@pytest.mark.parametrize("implicit,programs", [(True, 3), (False, 1)])
def test_a_long_implicit_plan_runs_as_several_programs_with_the_same_rows(
        monkeypatch, implicit, programs):
    """More batch groups than `_IMPLICIT_GROUPS_PER_PROGRAM`: an implicit
    half-sweep is dispatched as several programs, dealt round-robin, and
    every row comes out as one program leaves it; an explicit one stays
    one program."""
    from predictionio_tpu.ops import als
    out0, counter = _tables(13)
    rows = np.random.default_rng(13).permutation(N_OUT)[:80].astype(np.int32)
    groups = []
    for n, K in enumerate((8, 16, 24, 40, 56)):     # five shapes, five groups
        g = _group(13, K, "padded")
        groups.append((rows[16 * n:16 * (n + 1)].reshape(1, 16),)
                      + tuple(x[:1, :16] for x in g[1:]))
    cfg = als.ALSConfig(rank=RANK, lam=LAM, alpha=ALPHA, solver="cholesky",
                        implicit_prefs=implicit)
    gram = als._gram_eig(counter, n_live=N_COUNTER) if implicit else None
    calls = []
    real = als._solve_sweep

    def counted(factors, counter_factors, gram, part, *a, **k):
        calls.append(len(part))
        return real(factors, counter_factors, gram, part, *a, **k)

    monkeypatch.setattr(als, "_solve_sweep", counted)
    whole = np.asarray(als._run_side(tuple(groups), out0.copy(), counter,
                                     cfg, gram))
    assert calls == [5]
    calls.clear()
    monkeypatch.setattr(als, "_IMPLICIT_GROUPS_PER_PROGRAM", 2)
    split = np.asarray(als._run_side(tuple(groups), out0.copy(), counter,
                                     cfg, gram))
    assert len(calls) == programs and sum(calls) == 5
    assert (split[:N_OUT] == whole[:N_OUT]).all()
    assert als._sweep_programs((), True) == ((),)
