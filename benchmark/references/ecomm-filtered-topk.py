"""Plain reference of the served e-commerce recommendation template
(`scala-parallel-ecommercerecommendation`, ALSAlgorithm.scala `predict`):
filtered top-k over a factor model, with the candidate rule written out. It
imports nothing of the program and takes nothing the program has made but
the answers it is shown.

A query is a dict:
    route       "dot" (known user: `vector` is the user's row, scored by dot
                product) or "cos" (unknown user: `recent` holds the item
                indices of the recent views; the score is the sum over them
                of the cosine with each item)
    categories  category codes (empty: no category filter)
    black       item indices of the blackList
    white       item indices of the whiteList, or None where none was given
    seen        item indices the user has seen (from the draw, not the store)
    versions    the constraint versions in force while the request was out:
                [as of its send, ..., as of its answer]; ranking uses the
                first, `allowed_of` asks every one
and the filter data is the reference's own copy:
    item_category  int array [I] or [I, c] (-1 pads): the seed's map
    unavailable    one sorted int array of item indices per version

The candidate rule, for item i and query q, is the template's:
    allowed(i) = (white is None or i in white)
                 and i not in black | seen | unavailable
                 and (no categories or cat(i) & categories)
                 and i < n_items,
and of the allowed items those with score > 0 are ranked.

Scores are jax.numpy float32 with every product at `highest` precision, in
blocks of item rows, wherever JAX runs; each block keeps a few times k
survivors a query, whose scores are then taken again in numpy float64 and
ranked (as als-explicit.py's serve check ranks). Departures from the
template, each without effect on which answer is right: the template
scores in double on the JVM (float32 `highest`, then float64 on the
survivors, here); it computes the cosine per recent item and sums (the
recent items' normalised rows are summed first here: the same sum); it
reads seen items and the unavailable list from the event store at predict
time (here they are arguments, taken from the draw and from the writer's
record, so that the store is among what is checked); a recent view the
model does not know contributes nothing in both.

The control is this reference in the program's place at a lower precision:
`round_operands` rounds the tables and the query vectors through that type
before they are multiplied, on the host (XLA:TPU removes a float32 ->
float8 -> float32 pair inside a jitted function as excess precision).
`faults` plants a wrong rule instead: "category_ignored" drops the category
test, "bitmap_behind" reads the constraint one version before the one in
force."""

from __future__ import annotations

import numpy as np

SURVIVORS = 4          # times k kept of every block, a query


def round_operands(table, precision: str) -> np.ndarray:
    """`table` rounded through `precision` (an ml_dtypes name), as float32,
    on the host."""
    import ml_dtypes
    return np.asarray(table, np.float32).astype(
        getattr(ml_dtypes, precision)).astype(np.float32)


def _operand(x, precision: str) -> np.ndarray:
    x = np.asarray(x, np.float32)
    return x if precision == "float32" else round_operands(x, precision)


def _normalised(rows: np.ndarray) -> np.ndarray:
    rows = np.asarray(rows, np.float64)
    norm = np.linalg.norm(rows, axis=-1, keepdims=True)
    return rows / np.maximum(norm, 1e-12)


def query_vectors(queries: list, item_table, route: str) -> np.ndarray:
    """float64 [Q, R]: the user's row, or the sum of the recent views'
    normalised rows."""
    if route == "dot":
        return np.stack([np.asarray(q["vector"], np.float64)
                         for q in queries])
    return np.stack([_normalised(item_table[np.asarray(q["recent"],
                                                       np.int64)]).sum(axis=0)
                     for q in queries])


def _version(q: dict, faults: tuple) -> int:
    v = q["versions"][0]
    return max(v - 1, 0) if "bitmap_behind" in faults else v


def allowed_block(queries: list, filter_data: dict, lo: int, hi: int,
                  faults: tuple = ()) -> np.ndarray:
    """bool [Q, hi - lo]: the candidate rule over items lo..hi-1, score test
    apart."""
    cat = np.asarray(filter_data["item_category"])[lo:hi]
    cat = cat.reshape(hi - lo, -1)
    out = np.ones((len(queries), hi - lo), bool)
    by_version = {}
    for j, q in enumerate(queries):
        row = out[j]
        if q["white"] is not None:
            w = np.asarray(q["white"], np.int64)
            w = w[(w >= lo) & (w < hi)]
            row[:] = False
            row[w - lo] = True
        v = _version(q, faults)
        if v not in by_version:
            un = np.asarray(filter_data["unavailable"][v], np.int64)
            by_version[v] = un[(un >= lo) & (un < hi)] - lo
        row[by_version[v]] = False
        gone = np.concatenate([np.asarray(q["black"], np.int64),
                               np.asarray(q["seen"], np.int64)])
        row[gone[(gone >= lo) & (gone < hi)] - lo] = False
        if len(q["categories"]) and "category_ignored" not in faults:
            want = np.asarray(q["categories"])
            row &= (np.isin(cat, want[want >= 0])).any(axis=1)
    return out


def allowed_of(q: dict, filter_data: dict, ids) -> np.ndarray:
    """bool per id: whether the rule admits it for this request under SOME
    constraint version in force while the request was out (an id that every
    such version excludes, or that any other test excludes, is a violation
    of the configuration's guarantee)."""
    ids = np.asarray(ids, np.int64)
    cat = np.asarray(filter_data["item_category"])
    n_items = cat.shape[0]
    cat = cat.reshape(n_items, -1)
    ok = (ids >= 0) & (ids < n_items)
    safe = np.where(ok, ids, 0)
    if q["white"] is not None:
        ok &= np.isin(ids, np.asarray(q["white"], np.int64))
    ok &= ~np.isin(ids, np.asarray(q["black"], np.int64))
    ok &= ~np.isin(ids, np.asarray(q["seen"], np.int64))
    out_in_all = np.ones(ids.size, bool)
    for v in q["versions"]:
        out_in_all &= np.isin(ids, filter_data["unavailable"][v])
    ok &= ~out_in_all
    if len(q["categories"]):
        want = np.asarray(q["categories"])
        ok &= np.isin(cat[safe], want[want >= 0]).any(axis=1)
    return ok


def scores_of(queries: list, item_table, route: str, ids) -> np.ndarray:
    """Exact float64 scores [Q, k] of the given item ids (ids < 0 read
    NaN)."""
    ids = np.asarray(ids, np.int64)
    u = query_vectors(queries, item_table, route)
    rows = np.asarray(item_table[np.maximum(ids, 0)], np.float64)
    if route == "cos":
        rows = _normalised(rows)
    s = np.einsum("qr,qkr->qk", u, rows)
    s[ids < 0] = np.nan
    return s


def rank(queries: list, item_table, filter_data: dict, route: str, k: int,
         precision: str = "float32", faults: tuple = (),
         block: int = 1 << 18):
    """(scores float64 [Q, k], ids int64 [Q, k]) best first; where fewer
    than k items are candidates the row ends in (-inf, -1)."""
    import jax
    import jax.numpy as jnp
    n_items = int(item_table.shape[0])
    u64 = query_vectors(queries, item_table, route)
    u = jnp.asarray(_operand(u64, precision))
    kk = SURVIVORS * k

    @jax.jit
    def block_best(u, rows, allowed):
        with jax.default_matmul_precision("highest"):
            s = jnp.einsum("qr,ir->qi", u, rows,
                           preferred_element_type=jnp.float32)
        s = jnp.where(allowed & (s > 0), s, -jnp.inf)
        return jax.lax.top_k(s, min(kk, rows.shape[0]))

    held_s, held_i = [], []
    for lo in range(0, n_items, block):
        hi = min(lo + block, n_items)
        rows = np.asarray(item_table[lo:hi], np.float32)
        if route == "cos":
            rows = _normalised(rows).astype(np.float32)
        rows = _operand(rows, precision)
        allowed = allowed_block(queries, filter_data, lo, hi, faults)
        if hi - lo < block:      # one shape for every block
            pad = block - (hi - lo)
            rows = np.concatenate(
                [rows, np.zeros((pad, rows.shape[1]), np.float32)])
            allowed = np.concatenate(
                [allowed, np.zeros((allowed.shape[0], pad), bool)], axis=1)
        s, i = block_best(u, jnp.asarray(rows), jnp.asarray(allowed))
        held_s.append(s)
        held_i.append(i + lo)
    s = np.concatenate([np.asarray(x) for x in held_s], axis=1)
    i = np.concatenate([np.asarray(x) for x in held_i], axis=1).astype(
        np.int64)
    alive = np.isfinite(s)
    if precision == "float32":
        # the survivors' scores again, exactly; the control keeps its own
        exact = scores_of(queries, item_table, route, np.where(alive, i, -1))
        s = np.where(alive & (exact > 0), exact, -np.inf)
    order = np.argsort(-s, axis=1, kind="stable")[:, :k]
    s = np.take_along_axis(s, order, axis=1)
    i = np.take_along_axis(i, order, axis=1)
    i[~np.isfinite(s)] = -1
    if s.shape[1] < k:
        pad = k - s.shape[1]
        s = np.concatenate([s, np.full((s.shape[0], pad), -np.inf)], axis=1)
        i = np.concatenate([i, np.full((i.shape[0], pad), -1)], axis=1)
    return s, i
