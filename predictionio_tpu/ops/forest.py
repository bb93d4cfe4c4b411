"""Random decision forest — TPU-native replacement for MLlib RandomForest.

Plays the role of `org.apache.spark.mllib.tree.RandomForest.trainClassifier`
as used by the classification template's add-algorithm variant (reference:
examples/scala-parallel-classification/add-algorithm/src/main/scala/
RandomForestAlgorithm.scala:30-41): same knob surface (numClasses, numTrees,
featureSubsetStrategy, impurity, maxDepth, maxBins), same prediction rule
(per-tree class vote, majority wins).

This is not a port of MLlib's distributed tree induction (per-node task
queues + row shuffles). The TPU-first formulation is level-synchronous,
with static shapes at every level:

  * set-up (`forest_setup`): features are binned once on the host into
    ``max_bins`` ordered bins on exact quantile edges (one
    ``np.searchsorted`` per feature) and uploaded feature-major
    ([F, n] int32: the example axis is the minor one, so no feature axis is
    padded to the TPU's 128 lanes); the tree state is made on the device
    beside them: Poisson(1) bootstrap weights [T, n] f32, node ids [T, n]
    int32 and a live flag [T, n] bool, donated from program to program;
  * growth (`forest_grow`): all trees grow in lockstep, level by level.
    At depth d one program (`_forest_hist`) takes the example axis in
    blocks of ``HIST_BLOCK`` rows and accumulates the class histogram of
    every (tree, node, feature, bin) cell in float32: per block one bf16
    MXU product of the nodes' weighted one-hot [T * nodes, block] with the
    bins' and classes' one-hot [C * F * B, block] (both exact in bf16),
    classes-major so that the class axis, two or three long, is not the
    one the TPU tiles and pads: the one-hot is made in the operand's own
    layout. No one-hot of the whole data and no [T, n, nodes] array is
    ever made.
    Below the root only the left children are counted: a right child's
    histogram is its parent's less its sibling's, exact on integer counts
    under 2^24;
  * a second program (`_forest_split`) finds every node's best split (max
    gini or entropy gain over the bin-cumulative histograms, restricted to
    the node's random feature subset; no admissible gain freezes it into
    a leaf) and routes the examples, again in blocks, updating the node
    ids and live flags in place;
  * trees are heap-indexed arrays (node i's children are 2i+1 and 2i+2),
    so prediction is ``max_depth`` gathers and the forest vote is a count.

The random draws are functions of the seed alone, so that any other
implementation can draw the same: ``k_w, k_f = split(PRNGKey(seed))``;
tree t's weights are ``poisson(fold_in(k_w, t), 1.0, (n,))``; at depth d
tree t's node m considers the ``subset_k`` features with the smallest
``uniform(fold_in(fold_in(k_f, d), t), (nodes, F))[m]``, ties to the lower
feature index. Histogram memory is O(trees * 2^depth * features * bins *
classes) per level (59 MB at 64 trees, depth 7, 28 features, 32 bins, two
classes); the state is O(trees * examples): 7.3 GB at 64 trees over 10.5M
examples.
"""

from __future__ import annotations

import functools
import math
import os
import time
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.obs import TRACER

_STRATEGIES = ("auto", "all", "sqrt", "log2", "onethird")

#: examples per step of the histogram's and the routing's loops
HIST_BLOCK = 4096


def feature_subset_size(strategy: str, num_features: int,
                        num_trees: int) -> int:
    """MLlib RandomForest.scala featureSubsetStrategy semantics: "auto" is
    sqrt for a forest, all features for a single tree."""
    s = strategy.lower()
    if s not in _STRATEGIES:
        raise ValueError(
            f"featureSubsetStrategy must be one of {_STRATEGIES}, got {s!r}")
    if s == "auto":
        s = "sqrt" if num_trees > 1 else "all"
    if s == "all":
        return num_features
    # ceil throughout, as in Spark's DecisionTreeMetadata.buildMetadata.
    if s == "sqrt":
        return max(1, int(math.ceil(math.sqrt(num_features))))
    if s == "log2":
        return max(1, int(math.ceil(math.log2(max(2, num_features)))))
    return max(1, int(math.ceil(num_features / 3.0)))


@dataclass
class ForestModel:
    """Heap-layout forest. Node i: children 2i+1 / 2i+2; leaves carry the
    majority class of the training rows that reached them.
    `node_counts` holds each node's bootstrap-weighted class counts
    (MLlib's Node.impurityStats), from which `leaf_class` is read."""
    feature: np.ndarray      # [T, nodes] int32 split feature (internal nodes)
    threshold: np.ndarray    # [T, nodes] float32; go right iff x[f] > thr
    is_leaf: np.ndarray      # [T, nodes] bool
    leaf_class: np.ndarray   # [T, nodes] int32
    num_classes: int
    max_depth: int
    node_counts: Optional[np.ndarray] = None   # [T, nodes, C] float32

    @property
    def num_trees(self) -> int:
        return self.feature.shape[0]

    def predict(self, x: Sequence[float]) -> float:
        """Single-query vote on host (serve path; no device round-trip)."""
        votes = self.predict_batch(np.asarray(x, np.float32)[None, :])
        return float(votes[0])

    def predict_batch(self, X: np.ndarray) -> np.ndarray:
        X = np.asarray(X, np.float32)
        node = np.zeros((self.num_trees, X.shape[0]), np.int64)
        tree_ix = np.arange(self.num_trees)[:, None]
        for _ in range(self.max_depth):
            f = self.feature[tree_ix, node]
            go_right = X[np.arange(X.shape[0])[None, :], f] > \
                self.threshold[tree_ix, node]
            child = 2 * node + 1 + go_right
            node = np.where(self.is_leaf[tree_ix, node], node, child)
        cls = self.leaf_class[tree_ix, node]            # [T, q]
        votes = np.zeros((X.shape[0], self.num_classes), np.int64)
        for t in range(self.num_trees):
            votes[np.arange(X.shape[0]), cls[t]] += 1
        return np.argmax(votes, axis=1).astype(np.float64)


@dataclass
class ForestData:
    """What `forest_setup` leaves on the device for `forest_grow`: the
    binned examples, feature-major and padded to whole blocks, the labels,
    the bin edges, and the tree state every forest grown from them reuses
    in place."""
    xb: jax.Array            # [F, n_pad] int32 bin index
    y: jax.Array             # [n_pad] int32 class index
    edges: np.ndarray        # [F, B-1] float32: bin b <=> x <= edges[f, b]
    state: tuple             # (weights [T, n_pad] f32, node [T, n_pad]
    #                          int32, live [T, n_pad] bool)
    n: int
    num_classes: int
    num_trees: int
    max_bins: int
    block: int


def _impurity(counts, kind: str):
    """counts [..., C] -> impurity [...]. Gini or entropy (MLlib's two
    classification impurities)."""
    total = counts.sum(axis=-1, keepdims=True)
    p = counts / jnp.maximum(total, 1e-9)
    if kind == "gini":
        return 1.0 - jnp.sum(p * p, axis=-1)
    return -jnp.sum(jnp.where(p > 0, p * jnp.log2(jnp.maximum(p, 1e-12)),
                              0.0), axis=-1)


# -- set-up -------------------------------------------------------------

def _per_feature(fn, F: int) -> list:
    """fn(f) for every feature, on host threads (numpy's partition and
    searchsorted let go of the interpreter)."""
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(max_workers=min(F, os.cpu_count() or 1)) as ex:
        return list(ex.map(fn, range(F)))


def quantile_edges(XT: np.ndarray, max_bins: int) -> np.ndarray:
    """[F, B-1] float32 upper bin edges of each feature of XT [F, n]: its
    exact quantiles (numpy's linear rule) at i / B, i = 1..B-1 (MLlib
    samples them: see the configuration's notes)."""
    qs = np.arange(1, max_bins) / max_bins
    return np.stack(_per_feature(lambda f: np.quantile(XT[f], qs),
                                 XT.shape[0])).astype(np.float32)


def bin_features(XT: np.ndarray, edges: np.ndarray,
                 n_pad: int) -> np.ndarray:
    """[F, n_pad] int32 bin of each example of XT [F, n], 0 past n:
    #(edges < x), so bin b <=> x <= edges[f, b] and x > edges[f, b] <=>
    bin > b. One searchsorted per feature."""
    F, n = XT.shape
    out = np.zeros((F, n_pad), np.int32)

    def one(f):
        out[f, :n] = np.searchsorted(edges[f], XT[f], side="left")

    _per_feature(one, F)
    return out


def _check_labels(y, num_classes: int) -> np.ndarray:
    y_arr = np.asarray(y)
    if not np.all(np.equal(np.mod(y_arr, 1), 0)):
        raise ValueError("forest labels must be integer-valued class ids")
    y_ix = y_arr.astype(np.int64).astype(np.int32)
    if y_ix.size and (y_ix.min() < 0 or y_ix.max() >= num_classes):
        # MLlib's trainClassifier throws on labels outside [0, numClasses);
        # silently dropping them would zero their one-hot rows instead.
        raise ValueError(
            f"forest labels must be in [0, {num_classes}); got range "
            f"[{y_ix.min()}, {y_ix.max()}]")
    return y_ix


def _blocking(n: int) -> tuple[int, int]:
    """(block, padded n): whole blocks of a multiple of 128 examples."""
    block = min(HIST_BLOCK, -(-max(n, 1) // 128) * 128)
    return block, -(-max(n, 1) // block) * block


def forest_setup(X: np.ndarray, y: np.ndarray, *, num_classes: int,
                 num_trees: int, max_bins: int = 32) -> ForestData:
    """Bin on the host and upload once; make the tree state on the
    device. Everything `forest_grow` reads, for as many forests as are
    grown from it."""
    from predictionio_tpu.obs import costmon, jaxmon
    from predictionio_tpu.utils.device_cache import note_upload
    X = np.asarray(X, np.float32)
    y_ix = _check_labels(y, num_classes)
    n, F = X.shape
    max_bins = max(2, min(max_bins, max(2, n)))
    block, n_pad = _blocking(n)
    with TRACER.region("forest.bin"):
        XT = np.ascontiguousarray(X.T)      # a feature's values contiguous
        edges = quantile_edges(XT, max_bins)
        xb = bin_features(XT, edges, n_pad)
        del XT
        y_pad = np.zeros(n_pad, np.int32)
        y_pad[:n] = y_ix
    with TRACER.region("forest.upload"), \
            costmon.phase(costmon.FOREST_TRAIN):
        with TRACER.region("setup.upload", table="forest_bins"):
            t0 = time.perf_counter()
            dev = (jax.device_put(xb), jax.device_put(y_pad))
            t_put = time.perf_counter()
        nbytes = xb.nbytes + y_pad.nbytes
        jaxmon.record_h2d(nbytes)
        note_upload(dev, "forest_bins", nbytes, t0, t_put, 0.0)
        with TRACER.region("setup.upload", table="forest_state"):
            t0 = time.perf_counter()
            state = _forest_state(num_trees, n_pad)
            t_put = time.perf_counter()
        note_upload(state, "forest_state",
                    sum(int(a.nbytes) for a in state), t0, t_put, 0.0)
    return ForestData(xb=dev[0], y=dev[1], edges=edges, state=state, n=n,
                      num_classes=num_classes, num_trees=num_trees,
                      max_bins=max_bins, block=block)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _forest_state(num_trees: int, n_pad: int):
    return (jnp.zeros((num_trees, n_pad), jnp.float32),
            jnp.zeros((num_trees, n_pad), jnp.int32),
            jnp.zeros((num_trees, n_pad), bool))


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("n",))
def _forest_draw(state, k_w, *, n: int):
    """A forest's bootstrap into the state it reuses: tree t's Poisson(1)
    weights from fold_in(k_w, t), every example at the root, live where
    its weight is not 0."""
    w, node, _live = state
    T, n_pad = w.shape

    def tree(t, w):
        wt = jax.random.poisson(jax.random.fold_in(k_w, t), 1.0, (n,))
        wt = jnp.pad(wt.astype(jnp.float32), (0, n_pad - n))
        return jax.lax.dynamic_update_slice(w, wt[None, :], (t, 0))

    w = jax.lax.fori_loop(0, T, tree, w)
    return w, jnp.zeros_like(node), w > 0


# -- one level ----------------------------------------------------------

def _block(x, i, block):
    """Columns [i * block, (i + 1) * block) of an [..., n_pad] array."""
    return jax.lax.dynamic_slice_in_dim(x, i * block, block, axis=x.ndim - 1)


@functools.partial(
    jax.jit, static_argnames=("stride", "count", "num_classes", "max_bins",
                              "block"))
def _forest_hist(xb, y, w, node, live, *, stride: int, count: int,
                 num_classes: int, max_bins: int, block: int):
    """[T, count, F, B, C] float32 class histogram of the level's nodes
    stride * j, j < count: the bootstrap weight of every live example of
    that node, by feature, bin and class. Blocks of examples in a loop;
    each block one MXU product of bf16 one-hots (weights are small
    integers, exact in bf16), accumulated in float32 as [T * count,
    C * F * B] and reordered once after the loop. The bins' and classes'
    one-hot is made classes-major, [C, F, B, block]: with the few classes
    leading, the (F, B) rows fill the TPU's tiles, so it is the product's
    operand as it lies (a bitcast, not a relayout in every block)."""
    F, n_pad = xb.shape
    T = w.shape[0]
    B, C = max_bins, num_classes
    ids = jnp.arange(count, dtype=jnp.int32)

    def step(i, acc):
        with jax.named_scope("pio.forest.hist"):
            nb = _block(node, i, block)                          # [T, R]
            wb = jnp.where(_block(live, i, block), _block(w, i, block),
                           0.0).astype(jnp.bfloat16)
            slot = jnp.where(nb % stride == 0, nb // stride, -1)
            a = jnp.where(slot[:, None, :] == ids[None, :, None],
                          wb[:, None, :], 0).reshape(T * count, block)
            xbb, yb = _block(xb, i, block), _block(y, i, block)
            bins = ((yb[None, None, None, :]
                     == jnp.arange(C, dtype=jnp.int32)[:, None, None, None])
                    & (xbb[None, :, None, :]
                       == jnp.arange(B, dtype=jnp.int32)[None, None, :,
                                                          None]))
            bins = bins.astype(jnp.bfloat16).reshape(C * F * B, block)
            return acc + jax.lax.dot_general(
                a, bins, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(0, n_pad // block, step,
                            jnp.zeros((T * count, C * F * B), jnp.float32))
    return acc.reshape(T, count, C, F, B).transpose(0, 1, 3, 4, 2)


def _subset_mask(k_f, depth: int, num_trees: int, nd: int, F: int, k: int):
    """[T, nd, F] bool: node m of tree t at this depth considers the k
    features with the smallest uniform(fold_in(fold_in(k_f, depth), t),
    (nd, F))[m], ties to the lower feature index."""
    kd = jax.random.fold_in(k_f, depth)
    scores = jax.vmap(lambda t: jax.random.uniform(
        jax.random.fold_in(kd, t), (nd, F)))(jnp.arange(num_trees))
    f = jnp.arange(F)
    below = ((scores[..., None, :] < scores[..., :, None])
             | ((scores[..., None, :] == scores[..., :, None])
                & (f[None, :] < f[:, None])))                # [T, nd, F, F]
    return below.sum(axis=-1) < k


@functools.partial(
    jax.jit, donate_argnames=("node", "live"),
    static_argnames=("depth", "impurity", "subset_k", "block", "last"))
def _forest_split(part, parent, parent_split, xb, node, live, edges, k_f, *,
                  depth: int, impurity: str, subset_k: int, block: int,
                  last: bool):
    """One level's split search and routing. `part` is `_forest_hist`'s:
    at the root the whole level, below it the left children, whose
    siblings are `parent` (the level above's histogram) less them where
    the parent split (`parent_split`). Returns the level's histogram and
    splits for the next level, its nodes' splits, class counts and
    majorities, the class counts of its children, the routed node ids and
    live flags, and (nodes split, live pairs) counted on the device."""
    T, _, F, B, C = part.shape
    nd = 2 ** depth
    with jax.named_scope("pio.forest.split"):
        if depth == 0:
            hist = part
        else:
            right = jnp.where(parent_split[:, :, None, None, None],
                              parent - part, 0.0)
            hist = jnp.stack([part, right], axis=2).reshape(T, nd, F, B, C)
        counts = hist[:, :, 0].sum(axis=2)                   # [T, nd, C]
        total = counts.sum(axis=-1)
        majority = jnp.argmax(counts, axis=-1).astype(jnp.int32)
        parent_imp = _impurity(counts, impurity)             # [T, nd]
        # candidate split "bin <= b goes left" for b in 0..B-2
        left = jnp.cumsum(hist, axis=3)[:, :, :, :-1, :]     # [T,nd,F,B-1,C]
        right = counts[:, :, None, None, :] - left
        nl, nr = left.sum(axis=-1), right.sum(axis=-1)
        child_imp = (nl * _impurity(left, impurity)
                     + nr * _impurity(right, impurity)) \
            / jnp.maximum(nl + nr, 1e-9)
        gain = parent_imp[:, :, None, None] - child_imp      # [T,nd,F,B-1]
        fmask = _subset_mask(k_f, depth, T, nd, F, subset_k)
        valid = (nl > 0) & (nr > 0) & fmask[:, :, :, None]
        flat = jnp.where(valid, gain, -jnp.inf).reshape(T, nd, F * (B - 1))
        best = jnp.argmax(flat, axis=-1)                     # [T, nd]
        best_gain = jnp.take_along_axis(flat, best[..., None],
                                        axis=-1)[..., 0]
        best_f = (best // (B - 1)).astype(jnp.int32)
        best_b = (best % (B - 1)).astype(jnp.int32)
        # leaf iff nothing reached it, already pure, or no usable split
        leaf = ((total <= 1) | (parent_imp <= 1e-9) | (best_gain <= 0)
                | ~jnp.isfinite(best_gain))
        split = ~leaf
        feature = jnp.where(split, best_f, 0)
        threshold = jnp.where(split, edges[best_f, best_b], jnp.inf)
        # the children's class counts: the chosen cut of this histogram
        at = (best_f * (B - 1) + best_b)[..., None, None]
        lc = jnp.take_along_axis(left.reshape(T, nd, F * (B - 1), C), at,
                                 axis=2)[:, :, 0]            # [T, nd, C]
        lc = jnp.where(split[..., None], lc, 0.0)
        rc = jnp.where(split[..., None], counts - lc, 0.0)
        child_counts = jnp.stack([lc, rc], axis=2).reshape(T, 2 * nd, C)
        n_split = split.sum()

    if last:
        # the level below is all leaves: no example needs its node id
        live_pairs = live.sum(dtype=jnp.int32)
        return (hist, split, feature, threshold, leaf, counts, majority,
                child_counts, node, live, n_split, live_pairs)

    code = jnp.where(split, best_f * B + best_b, -1)         # [T, nd]
    ids = jnp.arange(nd, dtype=jnp.int32)
    fs = jnp.arange(F, dtype=jnp.int32)

    def step(i, carry):
        node, live, pairs = carry
        with jax.named_scope("pio.forest.route"):
            nb, lb = _block(node, i, block), _block(live, i, block)
            c = jnp.sum(jnp.where(nb[:, None, :] == ids[None, :, None],
                                  code[:, :, None], 0), axis=1)   # [T, R]
            f, b = c // B, c % B
            xv = jnp.sum(jnp.where(f[:, None, :] == fs[None, :, None],
                                   _block(xb, i, block)[None], 0), axis=1)
            nb = 2 * nb + (xv > b).astype(jnp.int32)
            pairs = pairs + lb.sum(dtype=jnp.int32)
            lb = lb & (c >= 0)
            node = jax.lax.dynamic_update_slice_in_dim(node, nb, i * block,
                                                       axis=1)
            live = jax.lax.dynamic_update_slice_in_dim(live, lb, i * block,
                                                       axis=1)
        return node, live, pairs

    node, live, live_pairs = jax.lax.fori_loop(
        0, node.shape[1] // block, step, (node, live, jnp.int32(0)))
    return (hist, split, feature, threshold, leaf, counts, majority,
            child_counts, node, live, n_split, live_pairs)


# -- growth -------------------------------------------------------------

def forest_grow(data: ForestData, *, max_depth: int, impurity: str = "gini",
                subset_k: int, seed: int,
                telemetry: Optional[dict] = None) -> ForestModel:
    """Grow one forest of `data.num_trees` trees from the resident data,
    level by level, and fetch it. `telemetry`, when a dict, receives per
    split level the nodes that split, the live (tree, example) pairs and
    the histogram's block count, and the gauges
    pio_forest_nodes_split_ratio / pio_forest_live_pairs_ratio are set."""
    from predictionio_tpu.obs import costmon
    if impurity not in ("gini", "entropy"):
        raise ValueError(f"impurity must be gini|entropy, got {impurity!r}")
    T, C, B, D = data.num_trees, data.num_classes, data.max_bins, max_depth
    F = data.xb.shape[0]
    block = data.block
    k_w, k_f = jax.random.split(jax.random.PRNGKey(seed))
    with costmon.phase(costmon.FOREST_TRAIN):
        edges = jnp.asarray(data.edges)
        state = _forest_draw(data.state, k_w, n=data.n)
    data.state = None            # donated: the level programs own it now
    w, node, live = state
    levels, counted = [], []
    hist = split = None
    if D == 0:
        # one leaf a tree: the root's class counts are its histogram's
        with costmon.executable(costmon.FOREST_HIST):
            child_counts = _forest_hist(
                data.xb, data.y, w, node, live, stride=1, count=1,
                num_classes=C, max_bins=B, block=block)[:, :, 0].sum(axis=2)
    for d in range(D):
        nd = 2 ** d
        with TRACER.region("forest.level", depth=d):
            with costmon.executable(costmon.FOREST_HIST):
                part = _forest_hist(
                    data.xb, data.y, w, node, live,
                    stride=1 if d == 0 else 2, count=max(1, nd // 2),
                    num_classes=C, max_bins=B, block=block)
            if d == 0:
                hist = split = jnp.zeros((), jnp.float32)
            with costmon.executable(costmon.FOREST_SPLIT):
                (hist, split, feature, threshold, leaf, counts, majority,
                 child_counts, node, live, n_split, pairs) = _forest_split(
                    part, hist, split, data.xb, node, live, edges, k_f,
                    depth=d, impurity=impurity, subset_k=subset_k,
                    block=block, last=d == D - 1)
        levels.append((feature, threshold, leaf, counts, majority))
        counted.append((n_split, pairs))
    data.state = (w, node, live)
    with TRACER.region("forest.fetch"):
        levels, counted, child_counts = jax.device_get(
            (levels, counted, child_counts))
    return _assemble(levels, counted, child_counts, data, max_depth, F,
                     block, telemetry)


def _assemble(levels, counted, bottom_counts, data: ForestData,
              max_depth: int, F: int, block: int,
              telemetry: Optional[dict]) -> ForestModel:
    """Heap arrays from the per-level outputs; the bottom level is all
    leaves, its class counts those its parents' cuts sent it."""
    from predictionio_tpu.obs import costmon
    T, C = data.num_trees, data.num_classes
    bottom = 2 ** max_depth
    feature = np.concatenate([lv[0] for lv in levels]
                             + [np.zeros((T, bottom), np.int32)], axis=1)
    threshold = np.concatenate(
        [lv[1] for lv in levels]
        + [np.full((T, bottom), np.inf, np.float32)], axis=1)
    is_leaf = np.concatenate([lv[2] for lv in levels]
                             + [np.ones((T, bottom), bool)], axis=1)
    node_counts = np.concatenate([lv[3] for lv in levels] + [bottom_counts],
                                 axis=1).astype(np.float32)
    leaf_class = np.concatenate(
        [lv[4] for lv in levels]
        + [np.argmax(bottom_counts, axis=-1).astype(np.int32)], axis=1)
    split_nodes = int(sum(int(s) for s, _ in counted))
    allowed = T * (2 ** max_depth - 1)
    pairs = [int(p) for _, p in counted]
    if counted:
        costmon.record_forest_levels(split_nodes / allowed,
                                     sum(pairs) / (len(pairs) * T * data.n))
    if telemetry is not None:
        telemetry["levels"] = [
            {"depth": d, "nodes_split": int(s), "live_pairs": int(p),
             "hist_blocks": int(data.xb.shape[1] // block)}
            for d, (s, p) in enumerate(counted)]
        telemetry["nodes_split"] = split_nodes
        telemetry["nodes_allowed"] = allowed
    return ForestModel(feature=feature.astype(np.int32),
                       threshold=threshold.astype(np.float32),
                       is_leaf=is_leaf, leaf_class=leaf_class,
                       num_classes=C, max_depth=max_depth,
                       node_counts=node_counts)


def forest_train(X: np.ndarray, y: np.ndarray, *, num_classes: int,
                 num_trees: int = 10, feature_subset_strategy: str = "auto",
                 impurity: str = "gini", max_depth: int = 5,
                 max_bins: int = 32, seed: int = 42,
                 telemetry: Optional[dict] = None) -> ForestModel:
    """Train a classification forest. `y` holds class indices 0..C-1 (MLlib
    labels are doubles with the same contract)."""
    X = np.asarray(X, np.float32)
    subset_k = feature_subset_size(feature_subset_strategy, X.shape[1],
                                   num_trees)
    data = forest_setup(X, y, num_classes=num_classes, num_trees=num_trees,
                        max_bins=max_bins)
    return forest_grow(data, max_depth=max_depth, impurity=impurity,
                       subset_k=subset_k, seed=seed, telemetry=telemetry)
