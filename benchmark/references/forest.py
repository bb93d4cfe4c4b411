"""Plain reference of the classification template's random forest
(MLlib RandomForest.trainClassifier as the template calls it), for the
trees of a sample: straightforward jax.numpy in float32, no kernels, no
one-hot products, nothing of the program's but the trees it compares. It
takes the bin edges from the rows by the configuration's `binning`
(numpy.quantile, linear, of each feature at i / max_bins), bins the
examples itself (#(edges < x)), draws by the configuration's `draws`,
builds each level's class histograms by `.at[].add` over blocks of
examples, takes the same gini or entropy gain and split rule over the
configuration's `feature_subset_size` features a node, routes by gathers,
and traverses a forest by gathers.

    k_w, k_f = split(PRNGKey(seed))
    tree t's weights:             poisson(fold_in(k_w, t), 1.0, (n,))
    depth d, tree t, node m's features: the k smallest of
                                  uniform(fold_in(fold_in(k_f, d), t),
                                          (2^d, F))[m], ties to the lower
                                  feature index

A node splits on the admissible (feature in its subset, bin b: bin <= b
goes left, both sides weighted > 0) of largest gain; it is a leaf where
its weighted count is at most 1, its impurity at most 1e-9, or no
admissible gain is above 0. Where the reference's best gain and the
program's chosen split tie within `TIE` of the node's impurity (the
largest gain a node can have), the reference adopts the program's choice
(split or leaf) and goes on, so that a tie never cascades; otherwise the
node is a mismatch and the reference follows its own choice. A program's
threshold that is not one of the reference's own edges of its feature is
a mismatch.

Departures from MLlib, shared with the program: exact quantile edges (MLlib
takes them from a sample of the data), all trees grown level by level in
lockstep (MLlib groups nodes by memory), and the draws above in place of
MLlib's own generators."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

TIE = 1e-6
# examples per step of the reference's loops, at most: the TPU compiler's
# time for a scatter grows with its updates (44 s at 2^16 examples x 4 trees
# x 6 features, 2.4 s at 2^12, compiled for a v5e)
BLOCK = 1 << 13
ROWS_PER_UPLOAD = 1 << 20


def draws(seed: int):
    return jax.random.split(jax.random.PRNGKey(seed))


def weights(k_w, trees, n: int):
    """[len(trees), n] float32 bootstrap weights."""
    return jnp.stack([jax.random.poisson(jax.random.fold_in(k_w, int(t)),
                                         1.0, (n,)).astype(jnp.float32)
                      for t in trees])


def subsets(k_f, depth: int, trees, F: int, k: int) -> np.ndarray:
    """[len(trees), 2^depth, k] feature ids each node considers."""
    kd = jax.random.fold_in(k_f, depth)
    out = []
    for t in trees:
        s = np.asarray(jax.random.uniform(jax.random.fold_in(kd, int(t)),
                                          (2 ** depth, F)))
        out.append(np.argsort(s, axis=-1, kind="stable")[:, :k])
    return np.stack(out).astype(np.int32)


def quantile_edges(X: np.ndarray, max_bins: int) -> np.ndarray:
    """[F, max_bins - 1] float32 edges of X [n, F]: numpy.quantile (linear)
    of each feature at i / max_bins, i = 1..max_bins-1."""
    qs = np.arange(1, max_bins) / max_bins
    return np.quantile(X, qs, axis=0).T.astype(np.float32)


def bins(X: np.ndarray, edges: np.ndarray, n_pad: int):
    """[F, n_pad] int32 bins of host X [n, F] on the device (padding 0)."""
    e = jnp.asarray(edges)

    @jax.jit
    def chunk(x):
        return (x.T[:, None, :] > e[:, :, None]).sum(axis=1).astype(
            jnp.int32)

    parts = [chunk(jnp.asarray(X[lo:lo + ROWS_PER_UPLOAD]))
             for lo in range(0, X.shape[0], ROWS_PER_UPLOAD)]
    out = jnp.concatenate(parts, axis=1)
    return jnp.pad(out, ((0, 0), (0, n_pad - out.shape[1])))


def impurity(counts, kind: str):
    total = counts.sum(axis=-1, keepdims=True)
    p = counts / jnp.maximum(total, 1e-9)
    if kind == "gini":
        return 1.0 - jnp.sum(p * p, axis=-1)
    return -jnp.sum(jnp.where(p > 0, p * jnp.log2(jnp.maximum(p, 1e-12)),
                              0.0), axis=-1)


@functools.partial(jax.jit,
                   static_argnames=("nd", "B", "C", "dtype", "block"))
def _histogram(xb, y, w, node, live, subset, *, nd, B, C, dtype, block):
    """[Ts, nd, k, B, C] weighted class counts by node, subset slot and
    bin, accumulated in `dtype` by scatter-add over blocks."""
    Ts, n_pad = w.shape
    k = subset.shape[-1]
    t_ix = jnp.arange(Ts)[:, None, None]
    j_ix = jnp.arange(k)[None, None, :]

    def step(i, h):
        sl = (i * block, block)
        nb = jax.lax.dynamic_slice_in_dim(node, *sl, axis=1)      # [Ts, R]
        wb = jnp.where(jax.lax.dynamic_slice_in_dim(live, *sl, axis=1),
                       jax.lax.dynamic_slice_in_dim(w, *sl, axis=1), 0.0)
        xbb = jax.lax.dynamic_slice_in_dim(xb, *sl, axis=1)       # [F, R]
        yb = jax.lax.dynamic_slice_in_dim(y, *sl)
        f = subset[t_ix, nb[:, :, None], j_ix]                    # [Ts,R,k]
        r = jnp.arange(block)[None, :, None]
        b = xbb[f, r]
        idx = ((((t_ix * nd + nb[:, :, None]) * k + j_ix) * B + b) * C
               + yb[None, :, None])
        upd = jnp.broadcast_to(wb[:, :, None], idx.shape).astype(dtype)
        return h.at[idx.ravel()].add(upd.ravel())

    h = jax.lax.fori_loop(0, n_pad // block, step,
                          jnp.zeros(Ts * nd * k * B * C, dtype))
    return h.reshape(Ts, nd, k, B, C).astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("nd", "C", "dtype", "block"))
def _class_counts(y, w, node, live, *, nd, C, dtype, block):
    """[Ts, nd, C] weighted class counts by node, by scatter-add over
    blocks."""
    Ts, n_pad = w.shape
    t_ix = jnp.arange(Ts)[:, None]

    def step(i, h):
        sl = (i * block, block)
        nb = jax.lax.dynamic_slice_in_dim(node, *sl, axis=1)
        wb = jnp.where(jax.lax.dynamic_slice_in_dim(live, *sl, axis=1),
                       jax.lax.dynamic_slice_in_dim(w, *sl, axis=1), 0.0)
        idx = (t_ix * nd + nb) * C + jax.lax.dynamic_slice_in_dim(y, *sl)
        return h.at[idx.ravel()].add(wb.astype(dtype).ravel())

    h = jax.lax.fori_loop(0, n_pad // block, step,
                          jnp.zeros(Ts * nd * C, dtype))
    return h.reshape(Ts, nd, C).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("kind",))
def _gains(hist, *, kind):
    """[Ts, nd, k, B-1] gains (-inf where not admissible) and the nodes'
    class totals and impurities, in float32."""
    tot = hist[:, :, 0].sum(axis=2)
    left = jnp.cumsum(hist, axis=3)[:, :, :, :-1, :]
    right = tot[:, :, None, None, :] - left
    nl, nr = left.sum(-1), right.sum(-1)
    child = (nl * impurity(left, kind) + nr * impurity(right, kind)) \
        / jnp.maximum(nl + nr, 1e-9)
    imp = impurity(tot, kind)
    g = imp[:, :, None, None] - child
    return jnp.where((nl > 0) & (nr > 0), g, -jnp.inf), tot, imp


@functools.partial(jax.jit, static_argnames=("B", "block"))
def _route(xb, node, live, code, *, B, block):
    """Each live example one level down by its node's (feature * B + bin)
    code, gathered; a code of -1 freezes it."""

    def step(i, carry):
        node, live = carry
        sl = (i * block, block)
        nb = jax.lax.dynamic_slice_in_dim(node, *sl, axis=1)
        lb = jax.lax.dynamic_slice_in_dim(live, *sl, axis=1)
        c = jnp.take_along_axis(code, nb, axis=1)
        xv = jax.lax.dynamic_slice_in_dim(xb, *sl, axis=1)[
            c // B, jnp.arange(block)[None, :]]
        nb = 2 * nb + (xv > c % B).astype(jnp.int32)
        lb = lb & (c >= 0)
        return (jax.lax.dynamic_update_slice_in_dim(node, nb, sl[0], 1),
                jax.lax.dynamic_update_slice_in_dim(live, lb, sl[0], 1))

    return jax.lax.fori_loop(0, node.shape[1] // block, step, (node, live))


def grow(X: np.ndarray, y: np.ndarray, seed: int, trees, *,
         max_bins: int, max_depth: int, impurity_kind: str, subset_k: int,
         num_classes: int, program: dict, dtype: str = "float32") -> dict:
    """Grow `trees` (tree ids of the forest keyed by `seed`) over X [n, F],
    y [n], comparing with `program`'s arrays of the same trees
    (`feature`, `threshold`, `is_leaf`, `leaf_class`, `node_counts`, heap
    layout) level by level. The other arguments are the configuration's.
    `dtype` is the histograms' accumulation (the control takes bfloat16).
    Returns the compared numbers."""
    with jax.default_matmul_precision("highest"):
        n, F = X.shape
        edges = quantile_edges(X, max_bins)
        B, C = max_bins, num_classes
        block = min(BLOCK, -(-n // 128) * 128)
        n_pad = -(-n // block) * block
        k_w, k_f = draws(seed)
        xb = bins(X, edges, n_pad)
        yd = jnp.pad(jnp.asarray(np.asarray(y, np.int32)), (0, n_pad - n))
        w = jnp.pad(weights(k_w, trees, n), ((0, 0), (0, n_pad - n)))
        Ts = len(trees)
        node = jnp.zeros((Ts, n_pad), jnp.int32)
        live = w > 0
        err = 0.0
        mismatch = ties = leaf_class_bad = 0
        gap_max = 0.0
        # every level's programs at the widest level's node count, so that
        # each compiles once: the nodes a level lacks count nothing
        wide = 2 ** max(max_depth - 1, 0)
        for d in range(max_depth + 1):
            nd, base = 2 ** d, 2 ** d - 1
            sl = slice(base, base + nd)
            if d == max_depth:
                tot = np.asarray(_class_counts(yd, w, node, live, nd=nd,
                                               C=C, dtype=dtype, block=block))
            else:
                sub = subsets(k_f, d, trees, F, subset_k)
                padded = np.zeros((Ts, wide, subset_k), np.int32)
                padded[:, :nd] = sub
                hist = _histogram(xb, yd, w, node, live, jnp.asarray(padded),
                                  nd=wide, B=B, C=C, dtype=dtype, block=block)
                g, tot, imp = (np.asarray(a)[:, :nd] for a in _gains(
                    hist, kind=impurity_kind))
            err = max(err, float(np.abs(
                tot - program["node_counts"][:, sl]).max()))
            leaf_class_bad += int((np.argmax(tot, axis=-1)
                                   != program["leaf_class"][:, sl]).sum())
            if d == max_depth:
                break
            code, bad, tied, gap = _decide(
                g, tot, imp, sub, edges, program["feature"][:, sl],
                program["threshold"][:, sl], program["is_leaf"][:, sl], B)
            mismatch += bad
            ties += tied
            gap_max = max(gap_max, gap)
            padded = np.full((Ts, wide), -1, np.int32)
            padded[:, :nd] = code
            node, live = _route(xb, node, live, jnp.asarray(padded), B=B,
                                block=block)
        return {"node_count_err_max": err, "split_mismatch": mismatch,
                "tie_gain_gap_max": gap_max,
                "leaf_class_mismatch": leaf_class_bad, "ties_adopted": ties}


def _decide(g, tot, imp, sub, edges, p_feature, p_threshold, p_leaf, B):
    """Per node of one level: the reference's choice, the program's, and
    what the reference goes on with. Returns ([Ts, nd] codes f * B + b or
    -1, mismatches, ties adopted, the widest gap of an adopted tie)."""
    Ts, nd, k, nb = g.shape
    flat = g.reshape(Ts, nd, k * nb)
    best = np.argmax(flat, axis=-1)
    g_star = np.take_along_axis(flat, best[..., None], -1)[..., 0]
    r_f = np.take_along_axis(sub, (best // nb)[..., None], -1)[..., 0]
    r_b = best % nb
    total = tot.sum(-1)
    structural = (total <= 1) | (imp <= 1e-9)
    r_leaf = structural | ~(g_star > 0)
    # the program's split in the reference's gain table: its feature's slot
    # in the node's subset and the first bin whose edge is its threshold,
    # which has to be one of the reference's edges of that feature
    slot = np.argmax(sub == p_feature[..., None], axis=-1)
    in_subset = (sub == p_feature[..., None]).any(-1)
    f_ix = np.clip(p_feature, 0, edges.shape[0] - 1)
    p_b = np.zeros_like(r_b)
    for t in range(Ts):
        for m in np.flatnonzero(~p_leaf[t]):
            p_b[t, m] = np.searchsorted(edges[f_ix[t, m]],
                                        p_threshold[t, m], side="left")
    on_edge = (p_b < nb) & (edges[f_ix, np.minimum(p_b, nb - 1)]
                            == p_threshold)
    g_p = np.where(in_subset & on_edge & ~p_leaf,
                   g[np.arange(Ts)[:, None], np.arange(nd)[None, :], slot,
                     np.minimum(p_b, nb - 1)], -np.inf)
    scale = np.maximum(imp, 1e-30)
    same = (~p_leaf & ~r_leaf & on_edge & (p_feature == r_f)
            & (p_b == r_b)) | (p_leaf & r_leaf)
    # the gap, as a share of the node's impurity, between the best gain and
    # the gain of the program's choice (a leaf's gain is 0)
    best_gain = np.where(r_leaf, 0.0, g_star)
    gap = (best_gain - np.where(p_leaf, 0.0, g_p)) / scale
    tie = ~same & (gap <= TIE) & (p_leaf | (np.isfinite(g_p)
                                            & ~structural))
    bad = ~same & ~tie
    adopt = same | tie
    split = np.where(adopt, ~p_leaf, ~r_leaf)
    f = np.where(adopt, p_feature, r_f)
    b = np.where(adopt, p_b, r_b)
    code = np.where(split, f * B + b, -1).astype(np.int32)
    return (code, int(bad.sum()), int(tie.sum()),
            float(np.where(tie, gap, 0.0).max(initial=0.0)))


@functools.partial(jax.jit, static_argnames=("depth", "C"))
def _votes(feature, threshold, is_leaf, leaf_class, X, *, depth, C):
    T = feature.shape[0]
    node = jnp.zeros((T, X.shape[0]), jnp.int32)
    t_ix = jnp.arange(T)[:, None]
    for _ in range(depth):
        f = feature[t_ix, node]
        right = jnp.take_along_axis(X.T, f, axis=0) > threshold[t_ix, node]
        node = jnp.where(is_leaf[t_ix, node], node, 2 * node + 1 + right)
    cls = leaf_class[t_ix, node]
    votes = (cls[:, :, None] == jnp.arange(C)[None, None, :]).sum(axis=0)
    return jnp.argmax(votes, axis=-1)


def predict(model: dict, X: np.ndarray, *, max_depth: int,
            num_classes: int, rows: int = 1 << 17) -> np.ndarray:
    """The forest's class for each row of X by the reference's own
    traversal of the program's arrays: gathers per level, a vote, the
    lowest class on a tie."""
    arrays = [jnp.asarray(model[k]) for k in
              ("feature", "threshold", "is_leaf", "leaf_class")]
    out = [np.asarray(_votes(*arrays, jnp.asarray(X[lo:lo + rows]),
                             depth=max_depth, C=num_classes))
           for lo in range(0, X.shape[0], rows)]
    return np.concatenate(out)
