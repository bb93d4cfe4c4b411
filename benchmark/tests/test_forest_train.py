"""The forest cell's own files at a tiny size on the CPU: its data draw, its
counts, one run of its job through run.py, the readers, and the faults
`correct` has to catch. Control flow and comparisons, never a device
number."""

import json
import os

import numpy as np
import pytest

import tinytree
from benchmark import run
from benchmark.lib import counts_forest, datagen_forest
from benchmark.lib.spec import Spec

CELL = "tiny-forest.train-forest"
with open(os.path.join(tinytree.REPO, "benchmark", "limits",
                       "clf-higgs-rf64-d8.train-forest.json")) as _f:
    LIMITS = json.load(_f)


def tiny_config() -> dict:
    """The configuration's own file with its counts cut; every other key
    as it stands."""
    with open(os.path.join(tinytree.REPO, "benchmark", "configs",
                           "clf-higgs-rf64-d8.json")) as f:
        c = json.load(f)
    c.update(name="tiny-forest", source="test", n_train=6000, n_test=1500,
             num_trees=6, max_depth=4)
    return c


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tinytree.build(str(tmp_path_factory.mktemp("tree")))
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-forest.json"), "w") as f:
        json.dump(tiny_config(), f)
    with open(os.path.join(root, "benchmark", "limits", CELL + ".json"),
              "w") as f:
        json.dump(LIMITS, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-forest", "source": "test", "reduced": [],
        "file": "benchmark/configs/tiny-forest.json", "why": "test"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-forest", "traffic": "train-forest",
        "chips": 1, "why": "test"})
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if any(w.endswith(".train-forest")
                   for w in m.get("workloads", [])):
                m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def run_cell(tree, traced=False, seconds=0.5, seed=2**31 + 27):
    return run.run_cell(Spec(tree), CELL, seed, seconds, traced,
                        need_chip=False)


# -- the data draw and the counts ----------------------------------------

def test_rows_have_higgs_shape_and_the_seeds_own_values():
    c = tiny_config()
    X, y = datagen_forest.rows(c, 2**31 + 5)
    assert X.shape == (7500, 28) and X.dtype == np.float32
    assert abs(y.mean() - c["assumed"]["positive_share"]) < 0.01
    # the four b-tags take three values each: their quantile edges collide
    for f, name in enumerate(datagen_forest.COLUMNS):
        if name.endswith("_btag"):
            assert set(np.unique(X[:, f])) <= set(datagen_forest.BTAG_VALUES)
    X2, y2 = datagen_forest.rows(c, 2**31 + 5)
    assert (X == X2).all() and (y == y2).all()
    X3, _ = datagen_forest.rows(c, 2**31 + 6)
    assert not (X == X3).all()


def test_counts_at_the_published_size():
    with open(os.path.join(tinytree.REPO, "benchmark", "configs",
                           "clf-higgs-rf64-d8.json")) as f:
        c = json.load(f)
    # per split level 10.5M x 29 bytes of bins and labels and 3 x 64 x
    # 10.5M of Poisson counts and node ids: 2.32 GB, 18.6 GB a forest
    assert counts_forest.hist_level_bytes(c) == 10.5e6 * 29 + 3 * 64 * 10.5e6
    w = counts_forest.forest_hist_work(c)
    assert w["levels"] == 8 and abs(w["hist_bytes"] - 18.56e9) < 0.01e9
    assert w["hist_ops"] == 8 * 64 * 10.5e6 * 28


# -- one run, the readers, and the faults --------------------------------

def test_a_sound_run_is_correct_and_reports_the_contracts_metrics(tree):
    r = run_cell(tree)
    assert r["correct"] is True and r["failed"] == 0
    assert set(r["metrics"]) == {"train_ratings_per_s", "setup_s"}
    assert r["window"]["forests"] >= 1
    n = r["numbers"]
    assert n["trees_compared"] == 4 and n["node_count_err_max"] == 0
    assert n["test_pred_mismatch"] == 0 and n["test_accuracy"] > 0.6


def test_a_traced_run_reads_the_cells_own_per_layer_metrics(tree):
    r = run_cell(tree, traced=True)
    m = r["metrics"]
    assert m["window_compiles.train_forest"]["value"] == 0
    assert 0 < m["forest_nodes_split_pct"]["value"] <= 100
    # no device plane on the CPU: the shares of the device are left out
    assert not {"forest_hist_roofline", "forest_hist_pct",
                "device_idle_pct.train_forest"} & set(m)
    assert r["correct"] is True


def test_the_readers_on_a_recorded_set_of_modules():
    from benchmark.lib.peaks import peaks_for
    spec = Spec(tinytree.REPO)
    ctx = {"window": {"forests": 2, "nodes_split": 30, "nodes_allowed": 40},
           "peaks": peaks_for("TPU v5 lite"),
           "work": {"hist_ops": 197e12, "hist_bytes": 819e9 / 2},
           "trace": {"modules": {
               "jit__forest_hist": {"count": 16, "seconds": 8.0},
               "jit__forest_split": {"count": 16, "seconds": 2.0}}}}
    # two forests of one second's least time each in 8 s of histograms
    assert spec.reader("forest_hist_roofline")(ctx) == 25.0
    assert spec.reader("forest_hist_pct")(ctx) == 80.0
    assert spec.reader("forest_nodes_split_pct")(ctx) == 75.0
    # a program that runs no such module (the parent's): nothing to read
    del ctx["trace"]["modules"]["jit__forest_hist"]
    assert spec.reader("forest_hist_roofline")(ctx) is None
    assert spec.reader("forest_hist_pct")(ctx) is None
    assert spec.reader("forest_nodes_split_pct")({"window": {}}) is None


def test_one_flipped_split_is_not_correct(tree, monkeypatch):
    """The root of tree 0 of every forest cut at another edge of its
    feature (one of another value: a b-tag's edges collide, and a cut at an
    equal edge is the same split): the reference finds a better split."""
    from predictionio_tpu.ops import forest
    real = forest.forest_grow

    def flipped(data, **kw):
        m = real(data, **kw)
        edges = data.edges[int(m.feature[0, 0])]
        m.threshold[0, 0] = edges[edges != m.threshold[0, 0]][0]
        return m

    monkeypatch.setattr(forest, "forest_grow", flipped)
    r = run_cell(tree)
    c = r["compared"]
    assert r["correct"] is False
    assert c["split_mismatch"]["value"] >= 1


@pytest.mark.parametrize("fault", ["quantile_rule", "subset_size"])
def test_a_wrong_binning_or_subset_size_is_not_correct(tree, monkeypatch,
                                                       fault):
    """The reference takes neither from the program: edges at the data's
    own values where the configuration says linear, or auto read as one
    feature more than sqrt, each grows trees the reference does not."""
    from predictionio_tpu.ops import forest
    if fault == "quantile_rule":
        def lower(XT, max_bins):
            qs = np.arange(1, max_bins) / max_bins
            return np.stack([np.quantile(XT[f], qs, method="lower")
                             for f in range(XT.shape[0])]).astype(np.float32)
        monkeypatch.setattr(forest, "quantile_edges", lower)
    else:
        real = forest.feature_subset_size
        monkeypatch.setattr(forest, "feature_subset_size",
                            lambda s, F, T: real(s, F, T) + 1)
    r = run_cell(tree)
    assert r["correct"] is False
    assert r["compared"]["split_mismatch"]["value"] >= 1


def test_the_bf16_control_fails_the_counts(tree):
    from benchmark import prove
    from benchmark.lib import compare
    r = prove.prove_seed(Spec(tree), CELL, 2**31 + 29, 0.5,
                         controls=("bfloat16",), need_chip=False)
    assert r["correct"] is True
    assert r["control_correct:bfloat16"] is False
    n = r["control:bfloat16"]
    assert n["node_count_err_max"] > LIMITS["node_count_err_max"]
    assert compare.decide(n, LIMITS)[0] is False


def test_a_program_without_the_split_set_up_fails_at_once(tree, monkeypatch):
    """The parent: its ops/forest has no forest_setup; the job says so
    before it makes any data."""
    from predictionio_tpu.ops import forest
    monkeypatch.delattr(forest, "forest_setup")
    spec = Spec(tree)
    cell = spec.cell(CELL)
    job = spec.job(cell).Job(cell, 1, {})
    with pytest.raises(SystemExit, match="forest_setup"):
        job.setup()
    assert not hasattr(job, "X")
