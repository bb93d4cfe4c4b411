"""The random forest program (ops/forest.py) against the plain reference the
benchmark holds it to (benchmark/references/forest.py), on the CPU at small
sizes: the same draws, exact class counts at every node, the same splits
(ties within 1e-6 of a node's impurity adopted), the same majority
classes and the same predictions; a reference whose histograms add up in
bfloat16 fails; one level's histogram against np.add.at. At the end, the
grow programs compiled off the chip at the published shapes (10.5M x 28,
64 trees, depth 8) for a described v5e, against the memory the compiler
allows, and the histogram's one-hot checked to reach its product without
a relayout. Nothing runs there: bytes and ops, not times."""

import math
import os
import re

import numpy as np
import pytest

from predictionio_tpu.ops import forest as FO

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXACT = {"node_count_err_max": 0.0, "split_mismatch": 0,
         "leaf_class_mismatch": 0}


@pytest.fixture(scope="module")
def ref():
    from benchmark.lib.spec import load_module
    return load_module(os.path.join(REPO, "benchmark", "references",
                                    "forest.py"), "reference_forest_test")


def rows(seed: int, n: int = 3000, F: int = 8, classes: int = 2):
    """Normal features, one of three values (its quantile edges collide),
    a label from an interaction and noise."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    X[:, 3] = np.array([0.0, 1.1, 2.2], np.float32)[rng.integers(0, 3, n)]
    z = (X[:, 0] + 0.7 * X[:, 1] * X[:, 2] + 0.4 * X[:, 3]
         + 0.4 * rng.normal(size=n))
    y = np.digitize(z, np.quantile(z, np.linspace(0, 1, classes + 1)[1:-1]))
    return X, y.astype(np.int64)


def grow_both(ref, seed, impurity, depth, trees=5, classes=2, bins=16,
              dtype="float32"):
    X, y = rows(seed, classes=classes)
    m = FO.forest_train(X, y, num_classes=classes, num_trees=trees,
                        max_depth=depth, max_bins=bins, impurity=impurity,
                        seed=seed)
    return X, y, m, compare(ref, m, X, y, seed, impurity, depth, bins,
                            classes, dtype)


def compare(ref, m, X, y, seed, impurity, depth, bins=16, classes=2,
            dtype="float32"):
    """The reference grows the program's trees from the rows, the seed and
    the settings alone: its own edges, and the subset size reckoned here
    (auto is sqrt for a forest)."""
    program = {name: getattr(m, name) for name in (
        "feature", "threshold", "is_leaf", "leaf_class", "node_counts")}
    return ref.grow(X, y, seed, list(range(m.feature.shape[0])),
                    max_bins=bins, max_depth=depth, impurity_kind=impurity,
                    subset_k=math.ceil(math.sqrt(X.shape[1])),
                    num_classes=classes, program=program, dtype=dtype)


@pytest.mark.parametrize("seed,impurity,depth", [
    (1, "gini", 1), (2, "entropy", 2), (3, "gini", 3), (4, "entropy", 4),
    (5, "gini", 5), (6, "entropy", 6), (2**31 + 7, "gini", 6),
    (11, "entropy", 3)])
def test_program_matches_the_reference(ref, seed, impurity, depth):
    X, _y, m, numbers = grow_both(ref, seed, impurity, depth)
    assert {k: numbers[k] for k in EXACT} == EXACT
    assert numbers["tie_gain_gap_max"] <= ref.TIE
    # every example's vote through the program's arrays, both traversals
    want = ref.predict({k: getattr(m, k) for k in (
        "feature", "threshold", "is_leaf", "leaf_class")}, X,
        max_depth=depth, num_classes=2)
    assert (m.predict_batch(X).astype(np.int64) == want).all()


def test_three_classes_match_the_reference(ref):
    _X, _y, m, numbers = grow_both(ref, 21, "gini", 4, classes=3)
    assert {k: numbers[k] for k in EXACT} == EXACT
    assert m.node_counts.shape == (5, 31, 3)


def test_a_feature_of_three_values_splits_where_its_edges_collide(ref):
    """Its 15 edges hold three values: cuts at equal edges are one split,
    and the program's threshold on it is one of the three."""
    X, _y, m, numbers = grow_both(ref, 3, "gini", 5)
    edges = ref.quantile_edges(X, 16)[3]
    assert len(set(edges.tolist())) < edges.size
    on_it = ~m.is_leaf & (m.feature == 3)
    assert on_it.any()
    assert set(m.threshold[on_it].tolist()) <= {0.0, np.float32(1.1)}
    assert numbers["split_mismatch"] == 0


@pytest.mark.parametrize("bins", [16, 32, 10])
def test_the_reference_takes_the_programs_edges_by_its_own_rule(ref, bins):
    """numpy's linear quantiles at i / bins, rounded to float32: the same
    edges from the program's feature-major threads and the reference's one
    call over the rows, colliding edges included."""
    X, _y = rows(12, n=5000)
    got = FO.quantile_edges(np.ascontiguousarray(X.T), bins)
    want = ref.quantile_edges(X, bins)
    assert got.dtype == want.dtype == np.float32
    assert np.array_equal(got, want)


def test_a_threshold_off_the_references_edges_is_a_mismatch(ref):
    """The root of tree 0 cut one float32 step above its edge: the same
    rows go each way, and the split is still not the reference's."""
    X, y = rows(13)
    m = FO.forest_train(X, y, num_classes=2, num_trees=3, max_depth=2,
                        max_bins=16, seed=13)
    assert compare(ref, m, X, y, 13, "gini", 2)["split_mismatch"] == 0
    m.threshold[0, 0] = np.nextafter(m.threshold[0, 0], np.float32(np.inf))
    assert compare(ref, m, X, y, 13, "gini", 2)["split_mismatch"] == 1


@pytest.mark.parametrize("fault", ["quantile_rule", "subset_size"])
def test_a_program_with_another_binning_or_subset_size_fails(
        ref, monkeypatch, fault):
    """The reference takes neither from the program. Edges at the data's
    own values (numpy's `lower` rule) where the configuration says linear:
    the program's thresholds miss the reference's edges. auto taken as more
    features a node than sqrt: nodes pick features the reference's subsets
    do not hold."""
    if fault == "quantile_rule":
        def lower(XT, max_bins):
            qs = np.arange(1, max_bins) / max_bins
            return np.stack([np.quantile(XT[f], qs, method="lower")
                             for f in range(XT.shape[0])]).astype(np.float32)
        monkeypatch.setattr(FO, "quantile_edges", lower)
    else:
        real = FO.feature_subset_size
        monkeypatch.setattr(FO, "feature_subset_size",
                            lambda s, F, T: real(s, F, T) + 2)
    _X, _y, _m, numbers = grow_both(ref, 14, "gini", 4)
    assert numbers["split_mismatch"] > 0


def test_node_counts_are_the_bootstrap_weights_of_each_node(ref):
    """The root's counts are the class sums of the configuration's Poisson
    draws; each split node's children's counts add up to its own."""
    X, y = rows(9)
    m = FO.forest_train(X, y, num_classes=2, num_trees=4, max_depth=3,
                        max_bins=16, seed=9)
    k_w, _k_f = ref.draws(9)
    w = np.asarray(ref.weights(k_w, range(4), X.shape[0]))
    for t in range(4):
        assert (m.node_counts[t, 0] == np.bincount(y, w[t], 2)).all()
    split = ~m.is_leaf[:, :7]
    kids = m.node_counts[:, 1:15].reshape(4, 7, 2, 2).sum(axis=2)
    assert (kids[split] == m.node_counts[:, :7][split]).all()
    assert m.node_counts.dtype == np.float32


def test_bf16_accumulated_histograms_fail(ref):
    """Counts over 256 are not exact in bfloat16: the control the
    benchmark's `correct` has to fail."""
    _X, _y, _m, numbers = grow_both(ref, 4, "gini", 4, dtype="bfloat16")
    assert numbers["node_count_err_max"] > 0
    assert numbers["split_mismatch"] + numbers["leaf_class_mismatch"] > 0


def test_random_forest_algorithm_trains_the_same_forest_from_columns():
    from predictionio_tpu.models import classification as C
    X, y = rows(5)
    td = C.TrainingData(X, y.astype(np.float64))
    p = C.RandomForestAlgorithmParams(num_classes=2, num_trees=6,
                                      max_depth=4, seed=5)
    got = C.RandomForestAlgorithm(p).train(td)
    want = FO.forest_train(X, y, num_classes=2, num_trees=6, max_depth=4,
                           seed=5)
    for name in ("feature", "threshold", "is_leaf", "leaf_class",
                 "node_counts"):
        assert np.array_equal(getattr(got, name), getattr(want, name)), name
    # the existing callers still read one point a row
    assert len(td.labeled_points) == X.shape[0]
    assert td.labeled_points[0].features == tuple(float(v) for v in X[0])


def test_growth_counts_its_levels_and_sets_the_gauges():
    from predictionio_tpu.obs import get_registry
    X, y = rows(7)
    tel = {}
    FO.forest_train(X, y, num_classes=2, num_trees=4, max_depth=3,
                    max_bins=16, seed=7, telemetry=tel)
    levels = tel["levels"]
    assert [lv["depth"] for lv in levels] == [0, 1, 2]
    assert tel["nodes_allowed"] == 4 * 7
    assert tel["nodes_split"] == sum(lv["nodes_split"] for lv in levels)
    assert levels[0]["nodes_split"] == 4
    # live pairs: the examples drawn at least once, then fewer as leaves
    assert 0 < levels[-1]["live_pairs"] <= levels[0]["live_pairs"] \
        <= 4 * X.shape[0]
    assert all(lv["hist_blocks"] == 1 for lv in levels)
    text = get_registry().render()
    assert "pio_forest_nodes_split_ratio" in text
    assert "pio_forest_live_pairs_ratio" in text


def test_many_blocks_and_a_stump(monkeypatch):
    """Examples over several histogram blocks give the one-block forest;
    depth 0 is one leaf a tree, its counts the root's."""
    X, y = rows(8, n=9000)
    one = FO.forest_train(X, y, num_classes=2, num_trees=3, max_depth=3,
                          seed=8)
    monkeypatch.setattr(FO, "HIST_BLOCK", 1024)
    many = FO.forest_train(X, y, num_classes=2, num_trees=3, max_depth=3,
                           seed=8)
    stump = FO.forest_train(X, y, num_classes=2, num_trees=3, max_depth=0,
                            seed=8)
    assert np.array_equal(one.node_counts, many.node_counts)
    assert np.array_equal(one.threshold, many.threshold)
    assert stump.is_leaf.all() and stump.node_counts.shape == (3, 1, 2)
    assert (stump.node_counts[:, 0] == one.node_counts[:, 0]).all()


@pytest.mark.parametrize("classes,bins,stride,count", [
    (2, 32, 1, 1), (2, 32, 2, 4), (3, 7, 2, 2)])
def test_the_histogram_is_the_sum_of_live_weights(classes, bins, stride,
                                                  count):
    """`_forest_hist` over several blocks, with dead examples, nodes outside
    the counted slots and a padded tail, against np.add.at of the live
    weights by tree, node slot, feature, bin and class."""
    import jax.numpy as jnp
    rng = np.random.default_rng(100 * classes + bins + stride + count)
    trees, feats, block, n = 3, 5, 128, 450
    n_pad = 4 * block
    xb = np.zeros((feats, n_pad), np.int32)
    xb[:, :n] = rng.integers(0, bins, (feats, n))
    y = np.zeros(n_pad, np.int32)
    y[:n] = rng.integers(0, classes, n)
    w = np.zeros((trees, n_pad), np.float32)
    w[:, :n] = rng.poisson(1.0, (trees, n))
    node = rng.integers(0, 2 * stride * count, (trees, n_pad)).astype(
        np.int32)
    live = (w > 0) & (rng.random((trees, n_pad)) < 0.8)
    got = np.asarray(FO._forest_hist(
        jnp.asarray(xb), jnp.asarray(y), jnp.asarray(w), jnp.asarray(node),
        jnp.asarray(live), stride=stride, count=count, num_classes=classes,
        max_bins=bins, block=block))
    want = np.zeros((trees, count, feats, bins, classes), np.float32)
    t, i = np.nonzero(live & (node % stride == 0) & (node // stride < count))
    for f in range(feats):
        np.add.at(want, (t, node[t, i] // stride, f, xb[f, i], y[i]),
                  w[t, i])
    assert got.shape == want.shape and got.dtype == np.float32
    assert np.array_equal(got, want)
    assert 0 < want.sum() < w.sum() * feats


# -- the grow programs at the published shapes, off the chip ---------------

HBM = 15.75 * 2**30    # what the compiler itself allows of the chip's 16 GiB
N, F, T, B, C, D = 10_500_000, 28, 64, 32, 2, 8


@pytest.fixture(scope="module")
def sds():
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one = SingleDeviceSharding(topo.devices[0])
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    jax.config.update("jax_enable_compilation_cache", cache_was)


def _resident(sds):
    """What forest_setup leaves on the device: bins, labels, the state."""
    import jax.numpy as jnp
    block, n_pad = FO._blocking(N)
    arrays = {"xb": sds((F, n_pad), jnp.int32), "y": sds((n_pad,), jnp.int32),
              "w": sds((T, n_pad), jnp.float32),
              "node": sds((T, n_pad), jnp.int32),
              "live": sds((T, n_pad), bool)}
    return block, arrays


def _held(compiled, arrays) -> float:
    """Bytes on the chip while the program runs: every resident array
    (passed or not), the program's temporaries and the outputs that do not
    reuse a donated argument."""
    m = compiled.memory_analysis()
    resident = sum(np.prod(a.shape) * a.dtype.itemsize
                   for a in arrays.values())
    return (resident + m.temp_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes)


@pytest.mark.parametrize("depth", [0, 7])
def test_the_histogram_program_fits_at_the_published_shapes(sds, depth):
    block, a = _resident(sds)
    nd = 2 ** depth
    compiled = FO._forest_hist.lower(
        a["xb"], a["y"], a["w"], a["node"], a["live"],
        stride=1 if depth == 0 else 2, count=max(1, nd // 2),
        num_classes=C, max_bins=B, block=block).compile()
    assert _held(compiled, a) < HBM
    # no one-hot of the whole data: no array over every example has more
    # than the two axes of the state's ([T, n], [F, n])
    n_pad = a["w"].shape[1]
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"\w+\[([0-9,]+)\]", compiled.as_text())}
    assert [s for s in shapes if n_pad in s] and not [
        s for s in shapes if n_pad in s and len(s) > 2]
    # the [T, nodes, F, B, C] float32 histogram is the program's output
    out = compiled.memory_analysis().output_size_in_bytes
    assert out >= T * max(1, nd // 2) * F * B * C * 4


def test_the_histograms_one_hot_reaches_the_product_as_it_lies(sds):
    """At depth 7 and the published shapes the bins' and classes' one-hot
    of a block, [C * F * B, block] = [1792, 4096], is no reshape, copy or
    transpose's result: made classes-major, it is the product's operand
    by a bitcast, and no block pays a relayout of it."""
    block, a = _resident(sds)
    text = FO._forest_hist.lower(
        a["xb"], a["y"], a["w"], a["node"], a["live"], stride=2, count=64,
        num_classes=C, max_bins=B, block=block).compile().as_text()
    rows = C * F * B
    assert re.search(rf"\[{rows},{block}\]", text)
    moved = re.findall(
        rf"= (?:pred|bf16)\[{rows},{block}\]\S* (reshape|copy|transpose)\(",
        text)
    assert not moved


@pytest.mark.parametrize("depth", [0, 6, 7])
def test_the_split_and_draw_programs_fit_at_the_published_shapes(sds, depth):
    import jax.numpy as jnp
    block, a = _resident(sds)
    nd = 2 ** depth
    key = sds((2,), jnp.uint32)
    part = sds((T, max(1, nd // 2), F, B, C), jnp.float32)
    parent = (sds((T, nd // 2, F, B, C), jnp.float32) if depth
              else sds((), jnp.float32))
    psplit = sds((T, nd // 2), bool) if depth else sds((), jnp.float32)
    compiled = FO._forest_split.lower(
        part, parent, psplit, a["xb"], a["node"], a["live"],
        sds((F, B - 1), jnp.float32), key, depth=depth, impurity="gini",
        subset_k=6, block=block, last=depth == D - 1).compile()
    assert _held(compiled, a) < HBM
    # node ids and live flags are updated where they lie
    assert compiled.memory_analysis().alias_size_in_bytes >= (
        T * a["node"].shape[1] * 5)
    if depth == 0:
        draw = FO._forest_draw.lower((a["w"], a["node"], a["live"]), key,
                                     n=N).compile()
        assert _held(draw, a) < HBM
