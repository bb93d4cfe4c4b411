"""The implicit-feedback cell's own files at a tiny size on the CPU: its
data draw, its counts, one run of its job through run.py and the faults
`correct` has to catch. Control flow and comparisons, never a device
number."""

import json
import os

import numpy as np
import pytest

import tinytree
from benchmark import run
from benchmark.lib import counts, counts_implicit, datagen_implicit
from benchmark.lib.spec import Spec

CELL = "tiny-implicit.train-implicit"
LIMITS = {"user_err_p50": 1e-3, "user_err_max": 1e-2,
          "item_err_p50": 1e-3, "item_err_max": 1e-2,
          "item_end_err_p50": 1e-3, "item_end_err_max": 1e-2,
          "nonfinite_rows_at_end": 0}


def tiny_config() -> dict:
    """The configuration's own file with its counts cut and float32
    operands (the CPU has no bfloat16 unit); every other key as it
    stands."""
    with open(os.path.join(tinytree.REPO, "benchmark", "configs",
                           "ecomm-taobao-ub-r200.json")) as f:
        c = json.load(f)
    c.update(name="tiny-implicit", source="test", n_users=3000,
             n_items=6000, n_ratings=60000, n_events=80343, rank=32,
             work_budget=1 << 14, compute_dtype="float32")
    c["assumed"] = dict(c["assumed"], user_degree_cap=400,
                        item_degree_cap=1500, item_popularity_offset=8.0)
    return c


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tinytree.build(str(tmp_path_factory.mktemp("tree")))
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-implicit.json"), "w") as f:
        json.dump(tiny_config(), f)
    with open(os.path.join(root, "benchmark", "limits", CELL + ".json"),
              "w") as f:
        json.dump(LIMITS, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-implicit", "source": "test", "reduced": [],
        "file": "benchmark/configs/tiny-implicit.json", "why": "test"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-implicit", "traffic": "train-implicit",
        "chips": 1, "why": "test"})
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if any(w.endswith(".train-implicit")
                   for w in m.get("workloads", [])):
                m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def run_cell(tree, traced=False, seconds=1.0, seed=2**31 + 27):
    return run.run_cell(Spec(tree), CELL, seed, seconds, traced,
                        need_chip=False)


# -- the data draw ------------------------------------------------------

def test_pairs_are_distinct_and_counts_sum_to_the_events():
    c = tiny_config()
    u, i, v = datagen_implicit.view_events(c, 2**31 + 5)
    assert u.size == c["n_ratings"] and (np.diff(u) >= 0).all()
    assert len(set(zip(u.tolist(), i.tolist()))) == u.size
    assert v.dtype == np.float32 and v.min() >= 1
    assert v.max() <= c["assumed"]["count_cap"]
    assert (v == np.round(v)).all()
    # the mean makes the counts sum to n_events; the cap takes a little off
    assert abs(v.sum() / c["n_events"] - 1.0) < 0.02
    # same seed, same counts; another seed, other counts on other pairs
    u2, i2, v2 = datagen_implicit.view_events(c, 2**31 + 5)
    assert (u == u2).all() and (i == i2).all() and (v == v2).all()
    _, i3, v3 = datagen_implicit.view_events(c, 2**31 + 6)
    assert not (i == i3).all() and not (v == v3).all()


def test_the_cap_is_what_keeps_the_sum_under_the_events():
    c = tiny_config()
    loose = datagen_implicit.view_counts(c, 7, 200000)
    c["assumed"] = dict(c["assumed"], count_cap=2)
    tight = datagen_implicit.view_counts(c, 7, 200000)
    assert tight.max() == 2 and tight.sum() < loose.sum()
    assert (np.minimum(loose, 2) == tight).all()


# -- the counts ---------------------------------------------------------

def test_three_user_example():
    # users view 1, 2 and 5 items; items are viewed 3, 2, 2, 1 times; R = 4
    du, di, R = np.array([1, 2, 5]), np.array([3, 2, 2, 1]), 4
    # per side the explicit sweep's work and the Gram over the N
    # counterpart rows, 2 N R^2; bytes: the counterpart table read once
    assert counts_implicit.ials_side_flops(du, 4, R) == (
        counts.als_side_flops(du, R) + 2 * 4 * 16)
    assert counts_implicit.ials_side_bytes(du, 4, R) == (
        counts.als_side_bytes(du, R) + 4 * 4 * 4)
    assert counts_implicit.ials_iteration_flops(du, di, R) == (
        counts.als_iteration_flops(du, di, R) + 2 * (4 + 3) * 16)
    assert counts_implicit.ials_iteration_bytes(du, di, R) == (
        counts.als_iteration_bytes(du, di, R) + (4 + 3) * 4 * 4)
    # an entity nobody viewed is not solved, and still a row of the Gram
    assert counts_implicit.ials_side_flops(np.array([0, 1, 2, 5]), 4, R) == \
        counts_implicit.ials_side_flops(du, 4, R)


@pytest.mark.parametrize("work_budget,bucket_ratio", [
    (1 << 10, 1.125), (1 << 12, 2.0)])
def test_counts_do_not_move_with_the_programs_plan(tree, work_budget,
                                                   bucket_ratio):
    """The job's `work` under another plan of the same data: the plan
    moves, the counts do not."""
    spec = Spec(tree)
    cell = spec.cell(CELL)
    want = _work(spec, cell)
    cell["config"] = dict(cell["config"], work_budget=work_budget,
                          bucket_ratio=bucket_ratio)
    assert _work(spec, cell) == want


def _work(spec, cell):
    job = spec.job(cell).Job(cell, 11, {})
    u, i, v = datagen_implicit.view_events(cell["config"], 11)
    job.n_users, job.n_items = (cell["config"]["n_users"],
                                cell["config"]["n_items"])
    job._draw_sample(u, i, v)
    return job.work()


# -- one run, and the faults --------------------------------------------

def test_a_sound_run_is_correct_and_reports_the_contracts_metrics(tree):
    r = run_cell(tree)
    assert r["correct"] is True and r["failed"] == 0
    assert set(r["metrics"]) == {"train_ratings_per_s", "setup_s"}
    assert r["window"]["iterations"] >= 1
    n = r["numbers"]
    # every solver route of both sides is among the sampled rows
    for side in ("user", "item"):
        assert {f"{side}_err_max.n{lo}" for lo in (1, 9, 32, 200)} <= set(n)
    assert n["user_rows"] > 64 and n["item_rows"] > 64


def test_a_traced_run_reads_the_cells_own_per_layer_metrics(tree):
    r = run_cell(tree, traced=True)
    m = r["metrics"]
    assert m["window_compiles.train_implicit"]["value"] == 0
    assert m["plan_s.train_implicit"]["value"] > 0
    # no device plane on the CPU: the shares of the device are left out
    assert not {"ials_sweep_roofline", "ials_gram_eig_pct",
                "ials_sweep_mfu", "als_sweep_mfu"} & set(m)
    assert r["correct"] is True


def test_the_readers_on_a_recorded_set_of_modules():
    from benchmark.lib.peaks import peaks_for
    spec = Spec(tinytree.REPO)
    ctx = {"window": {"iterations": 2, "wall_s": 20.0},
           "peaks": peaks_for("TPU v5 lite"),
           "work": {"iteration_flops": 197e12, "iteration_bytes": 819e9},
           "trace": {"modules": {
               "jit__solve_sweep_impl": {"count": 4, "seconds": 15.0},
               "jit__gram_eig_impl": {"count": 4, "seconds": 1.0}}}}
    assert spec.reader("ials_gram_eig_pct")(ctx) == 100.0 / 16.0
    assert spec.reader("ials_sweep_roofline")(ctx) == 100.0 / 8.0
    assert spec.reader("ials_sweep_mfu")(ctx) == 10.0
    # a program that runs no such module (the explicit cell's, the
    # parent's): nothing to read, and no error
    del ctx["trace"]["modules"]["jit__gram_eig_impl"]
    assert spec.reader("ials_gram_eig_pct")(ctx) is None
    ctx["trace"]["modules"] = {}
    assert spec.reader("ials_sweep_roofline")(ctx) is None


def test_half_of_each_batch_left_out_is_not_correct(tree, monkeypatch):
    from predictionio_tpu.ops import als
    real = als._run_side

    def half(groups, factors, *a, **k):
        cut = tuple((rows.at[:, ::2].set(-1), idx, val, mask)
                    for rows, idx, val, mask in groups)
        return real(cut, factors, *a, **k)

    monkeypatch.setattr(als, "_run_side", half)
    r = run_cell(tree)
    c = r["compared"]
    assert r["correct"] is False
    assert c["user_err_max"]["value"] > c["user_err_max"]["limit"]


def test_a_gram_that_counts_the_dummy_row_is_not_correct(tree, monkeypatch):
    """The scatter's dummy row is no entity: a Gram over the whole table
    holds it (the seed's tables carry a random one)."""
    from predictionio_tpu.ops import als
    real = als._side_gram
    monkeypatch.setattr(
        als, "_side_gram",
        lambda cfg, table, n_live, side: real(cfg, table, n_live + 1, side))
    r = run_cell(tree)
    assert r["correct"] is False


def test_a_stale_gram_inside_the_window_is_not_correct(tree, monkeypatch):
    """Set-up's half-sweeps take their Grams anew, so the comparison from
    the seed's tables passes; from the window's first call on the Gram of
    the seed's table is handed out again. Only the window's last item
    half-sweep, against the reference's own Gram of the user table it read,
    can fail."""
    from predictionio_tpu.ops import als
    real, kept, calls = als._side_gram, {}, {"n": 0}

    def stale(cfg, table, n_live, side):
        calls["n"] += 1
        if calls["n"] <= 2:
            kept[side] = real(cfg, table, n_live, side)
            return kept[side]
        return kept[side]

    monkeypatch.setattr(als, "_side_gram", stale)
    r = run_cell(tree)
    c = r["compared"]
    assert c["user_err_max"]["value"] <= c["user_err_max"]["limit"]
    assert c["item_err_max"]["value"] <= c["item_err_max"]["limit"]
    assert c["item_end_err_max"]["value"] > c["item_end_err_max"]["limit"]
    assert r["correct"] is False


@pytest.mark.parametrize("control", ["float8_e4m3fn", "fault:half"])
def test_the_controls_fail_the_limits(tree, control):
    from benchmark import prove
    from benchmark.lib import compare
    r = prove.prove_seed(Spec(tree), CELL, 2**31 + 29, 1.0,
                         controls=(control,), need_chip=False)
    assert r["correct"] is True
    assert r["control_correct:" + control] is False
    n = r["control:" + control]
    assert n["user_err_max"] > LIMITS["user_err_max"]
    assert n["item_end_err_max"] > LIMITS["item_end_err_max"]
    assert compare.decide(n, LIMITS)[0] is False


def test_a_program_without_the_live_row_gram_fails_at_once(tree, monkeypatch):
    """The parent of PR 27: the job says so before it makes any data."""
    from predictionio_tpu.ops import als
    monkeypatch.setattr(als, "_gram_eig_impl", lambda factors: None)
    spec = Spec(tree)
    cell = spec.cell(CELL)
    job = spec.job(cell).Job(cell, 1, {})
    with pytest.raises(SystemExit, match="live rows"):
        job.setup()
    assert not hasattr(job, "nnz")
