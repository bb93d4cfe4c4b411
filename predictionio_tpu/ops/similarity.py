"""Cosine-similarity scoring with business-rule filters — on-device top-k.

Replaces the similarproduct template's driver-side cosine scan
(reference: examples/scala-parallel-similarproduct/multi/src/main/scala/
ALSAlgorithm.scala:146-190: score = sum over query items of cosine(qf, f),
keep score > 0, apply category/white/black filters, top N) with one jitted
masked matmul + `lax.top_k` over the whole item-factor table resident in
HBM. Filters arrive as a packed boolean mask built on host.
"""

from __future__ import annotations

import functools
import threading
from typing import Optional, Sequence, Tuple

import numpy as np


@functools.partial(__import__("jax").jit, static_argnames=("k",))
def _cosine_topk(query_vecs, item_norms, allowed, k: int):
    """query_vecs [Q, R] (raw), item_norms [I, R] (L2-normalized rows),
    allowed [I] bool. Score = sum_q cos(q, item); items with score <= 0 or
    not allowed are excluded (score -> -inf)."""
    import jax
    import jax.numpy as jnp
    qn = query_vecs / jnp.maximum(
        jnp.linalg.norm(query_vecs, axis=-1, keepdims=True), 1e-12)
    scores = jnp.einsum("qr,ir->i", qn, item_norms,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(allowed & (scores > 0), scores, -jnp.inf)
    return jax.lax.top_k(scores, k)


def _masked_topk_impl(query_mat, item_table, allowed, k: int,
                      filter_positive: bool):
    """Traced body shared by the packed and unpacked masked-top-k
    executables (unjitted — always composed under one of the two jit
    wrappers below, so both variants rank identically)."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("pio.serve.score"):
        scores = jnp.einsum("br,ir->bi", query_mat, item_table,
                            preferred_element_type=jnp.float32)
        if filter_positive:
            allowed = allowed & (scores > 0)
        scores = jnp.where(allowed, scores, -jnp.inf)
    with jax.named_scope("pio.serve.topk"):
        return jax.lax.top_k(scores, k)


@functools.partial(__import__("jax").jit,
                   static_argnames=("k", "filter_positive"))
def _batched_masked_topk(query_mat, item_table, allowed, k: int,
                         filter_positive: bool):
    """query_mat [B, R], item_table [I, R], allowed [B, I] bool.
    Score = query_mat @ item_table.T; not-allowed items (and, when
    filter_positive, items with score <= 0 — the cosine templates' rule)
    are excluded (score -> -inf). One device call for the whole batch."""
    return _masked_topk_impl(query_mat, item_table, allowed, k=k,
                             filter_positive=filter_positive)


@functools.partial(__import__("jax").jit,
                   static_argnames=("k", "filter_positive", "p"))
def _batched_masked_topk_packed(query_mat, item_table, allowed, k: int,
                                filter_positive: bool, p: int):
    """:func:`_batched_masked_topk` with the readback-plane pack fused
    on (ISSUE 19): identical ranking, one contiguous ids+quantized-
    scores output payload per window."""
    from predictionio_tpu.ops import readback
    scores, idx = _masked_topk_impl(query_mat, item_table, allowed,
                                    k=k,
                                    filter_positive=filter_positive)
    return readback.pack_device(scores, idx, p)


def _aot_masked_topk_builder(b: int = 0, i: int = 0, r: int = 0,
                             k: int = 0, fp: int = 0, s: int = 0,
                             p: int = 0):
    """(jit_fn, example avals, statics) for one masked-top-k bucket
    (the compile plane's batch_predict executable for the cosine /
    filtered model families). ``s`` > 0 lowers the model-sharded
    variant with sharding-aware avals (item table over the model axis,
    masks sharded on the item dim). ``p`` > 0 lowers the packed-
    readback variant (ISSUE 19) whose single output aval is the
    contiguous payload — warmed packed buckets compile nothing at
    serve time."""
    import jax
    sds = jax.ShapeDtypeStruct
    if s:
        from predictionio_tpu.compile.aot import sharded_aval
        from predictionio_tpu.ops.topk import (make_batched_sharded_topk,
                                               sharded_k_split)
        from predictionio_tpu.parallel.mesh import model_mesh
        mesh = model_mesh(s)
        k_local, k_final = sharded_k_split(k, i, s)
        fn = make_batched_sharded_topk(mesh, k_local, k_final,
                                       has_mask=True,
                                       filter_positive=bool(fp),
                                       pack=p)
        return (fn,
                (sharded_aval((b, r), np.float32, mesh=mesh),
                 sharded_aval((i, r), np.float32, "model", None,
                              mesh=mesh),
                 sds((), np.int32),
                 sharded_aval((b, i), bool, None, "model", mesh=mesh)),
                {})
    avals = (sds((b, r), np.float32), sds((i, r), np.float32),
             sds((b, i), bool))
    if p:
        return (_batched_masked_topk_packed, avals,
                {"k": k, "filter_positive": bool(fp), "p": p})
    return (_batched_masked_topk, avals,
            {"k": k, "filter_positive": bool(fp)})


_aot_specs_registered = False


def register_aot_specs():
    """Idempotently register the masked-top-k executable spec with the
    compile plane (ISSUE 9)."""
    global _aot_specs_registered
    if _aot_specs_registered:
        return
    from predictionio_tpu.obs import costmon
    from predictionio_tpu.compile.aot import get_aot
    get_aot().register(costmon.BATCH_PREDICT_MASKED,
                       _aot_masked_topk_builder)
    _aot_specs_registered = True


def masked_topk_dims(n_items: int, rank: int, batch: int, k: int,
                     filter_positive: bool = True) -> dict:
    """Shape-bucket dims for one masked-top-k call — shared by the
    serve dispatch and the deploy/swap warm path."""
    from predictionio_tpu.compile import buckets as B
    from predictionio_tpu.ops import readback
    i_b = B.bucket_table_rows(n_items)
    return {"b": B.bucket_batch(batch), "i": i_b, "r": int(rank),
            "k": min(B.bucket_batch(k, floor=B.K_FLOOR), i_b),
            "fp": int(bool(filter_positive)),
            "p": readback.pack_flag()}


def masked_top_k_batch(item_table: np.ndarray, query_vecs: np.ndarray,
                       masks: np.ndarray, k: int,
                       filter_positive: bool = True
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Batched masked dot top-k: one jitted call for B queries.

    query_vecs [B, R] (already in the scoring space: raw user factors for
    dot scoring, summed-normalized item vectors for cosine), masks [B, I]
    bool. Every moving dim is shape-bucketed (ISSUE 9 compile plane):
    batch and k pad to powers of two, the item table uploads at its
    vocab bucket (padding rows masked out), so neither request-batch
    size, client-chosen num, NOR catalog growth inside a bucket mints a
    new program — and the dispatch resolves through the AOT registry,
    so a warmed bucket runs zero trace / zero compile.
    filter_positive additionally drops score <= 0 (cosine-template
    semantics; explicit-ALS callers pass False). Returns ([B, k'],
    [B, k']) numpy arrays with k' >= min(k, I); rows may contain -inf
    for excluded slots (caller filters non-finite and slices to its
    own num)."""
    return masked_top_k_batch_begin(item_table, query_vecs, masks, k,
                                    filter_positive=filter_positive)()


def masked_top_k_batch_begin(item_table: np.ndarray,
                             query_vecs: np.ndarray, masks: np.ndarray,
                             k: int, filter_positive: bool = True):
    """Two-phase sibling of :func:`masked_top_k_batch` (ISSUE 14
    pipelined executor): enqueue the masked ranking and return
    ``finish() -> (scores, idx)`` which performs the deferred
    device->host readback, so the completion stage can overlap the
    next window's formation."""
    from predictionio_tpu.compile.aot import (get_aot,
                                              precompile_next_rung)
    from predictionio_tpu.obs import costmon
    from predictionio_tpu.ops import readback
    from predictionio_tpu.parallel.sharded_table import is_sharded
    from predictionio_tpu.utils.device_cache import cached_put_rows
    register_aot_specs()
    if is_sharded(item_table):
        return _masked_top_k_batch_sharded_begin(
            item_table, query_vecs, masks, k, filter_positive)
    n_items = item_table.shape[0]
    n = query_vecs.shape[0]
    dims = masked_topk_dims(n_items, query_vecs.shape[1], n, k,
                            filter_positive)
    qp = np.zeros((dims["b"], query_vecs.shape[1]), dtype=np.float32)
    qp[:n] = query_vecs
    # padding rows of the bucketed table stay masked False -> -inf
    mp = np.zeros((dims["b"], dims["i"]), dtype=bool)
    mp[:n, :n_items] = masks
    k_eff, p = dims["k"], dims["p"]
    item_dev = cached_put_rows(item_table, dims["i"], table="item")
    if p:
        packed = get_aot().dispatch(
            costmon.BATCH_PREDICT_MASKED, dims,
            lambda *a: _batched_masked_topk_packed(
                *a, k=k_eff, filter_positive=filter_positive, p=p),
            qp, item_dev, mp)
        fetch = readback.begin_fetch_packed(packed, p)
    else:
        scores, idx = get_aot().dispatch(
            costmon.BATCH_PREDICT_MASKED, dims,
            lambda *a: _batched_masked_topk(
                *a, k=k_eff, filter_positive=filter_positive),
            qp, item_dev, mp)
        fetch = readback.begin_fetch(scores, idx)
    precompile_next_rung(costmon.BATCH_PREDICT_MASKED, dims, "i", n_items)

    def finish() -> Tuple[np.ndarray, np.ndarray]:
        scores_h, idx_h = fetch()
        return scores_h[:n], idx_h[:n]
    return finish


def _masked_top_k_batch_sharded_begin(item_table,
                                      query_vecs: np.ndarray,
                                      masks: np.ndarray, k: int,
                                      filter_positive: bool):
    """Sharded route of :func:`masked_top_k_batch`: the item table
    stays model-sharded in HBM (its resident handle), the padded
    [B, I] candidate mask uploads sharded over the item dim, and the
    ranking is the per-shard top-k + cross-shard merge. Same
    ``batch_predict_masked`` label; the ``s`` dim keeps sharded and
    replicated buckets from ever aliasing in the AOT registry.
    Returns the pipelined ``finish()`` readback callable."""
    from predictionio_tpu.compile import buckets as B
    from predictionio_tpu.obs import costmon
    from predictionio_tpu.ops import readback
    from predictionio_tpu.ops.topk import batched_sharded_top_k_begin
    from predictionio_tpu.parallel.mesh import model_mesh
    from predictionio_tpu.utils.device_cache import note_table_rows
    mesh = model_mesh(item_table.n_shards)
    n_items = item_table.shape[0]
    n = query_vecs.shape[0]
    i_b = max(item_table.padded_rows,
              B.bucket_table_rows_sharded(n_items, item_table.n_shards))
    note_table_rows("item", n_items, i_b)
    dims = {"b": B.bucket_batch(n), "i": i_b,
            "r": int(query_vecs.shape[1]),
            "k": min(B.bucket_batch(k, floor=B.K_FLOOR), i_b),
            "fp": int(bool(filter_positive)),
            "s": item_table.n_shards,
            "p": readback.pack_flag()}
    qp = np.zeros((dims["b"], query_vecs.shape[1]), dtype=np.float32)
    qp[:n] = query_vecs
    mp_ = np.zeros((dims["b"], dims["i"]), dtype=bool)
    mp_[:n, :n_items] = masks
    fetch = batched_sharded_top_k_begin(
        item_table.device(mesh, target_rows=i_b), qp, n_items,
        dims["k"], mesh, masks=mp_, filter_positive=filter_positive,
        label=costmon.BATCH_PREDICT_MASKED, dims=dims)

    def finish() -> Tuple[np.ndarray, np.ndarray]:
        scores, idx = fetch()
        return scores[:n], idx[:n]
    return finish


# ---------------------------------------------------------------------------
# Masked top-k with the candidate mask composed ON THE DEVICE
#
# The family above takes a [B, I] boolean mask built on the host: 4 MB a
# query at a 4M-item catalogue, beside an answer of 60 bytes. This one
# takes what a query really carries (a few category codes, tens to hundreds
# of excluded or white-listed item indices) and composes the mask inside
# the executable from filter data that lives on the device: the item ->
# category array and the availability bitmap (ItemFilterData below).
# ---------------------------------------------------------------------------

#: padding of an item's category slots
NO_CATEGORY = -1
#: padding of a query's category list
NO_QUERY_CATEGORY = -2
#: a category name the model has never seen: the query still filters by
#: category, and this code matches no item
UNKNOWN_CATEGORY = -3
#: which of a query's two lists a flat list entry belongs to (the bit plane
#: it sets): excluded items, white-listed items
LISTED_OUT = 0
LISTED_WHITE = 1


class ItemCategories:
    """item -> categories as arrays: ``ids`` int32 [I, c_max] of category
    codes padded with ``NO_CATEGORY`` (c_max from the data, at least 1) and
    ``vocab`` name -> code. Built in one pass over the items that HAVE
    categories."""

    def __init__(self, ids: np.ndarray, vocab: dict):
        self.ids = np.ascontiguousarray(ids, dtype=np.int32)
        self.vocab = vocab

    @classmethod
    def from_pairs(cls, n_items: int, item_ix: np.ndarray,
                   names: Sequence) -> "ItemCategories":
        """From flat (item index, category name) pairs."""
        item_ix = np.asarray(item_ix, dtype=np.int64)
        uniq, codes = np.unique(np.asarray(names, dtype=str),
                                return_inverse=True)
        vocab = {str(n): c for c, n in enumerate(uniq.tolist())}
        order = np.lexsort((codes, item_ix))
        item_ix, codes = item_ix[order], codes[order]
        keep = np.ones(item_ix.size, bool)      # a pair named twice
        keep[1:] = (item_ix[1:] != item_ix[:-1]) | (codes[1:] != codes[:-1])
        item_ix, codes = item_ix[keep], codes[keep]
        start = np.flatnonzero(np.r_[True, item_ix[1:] != item_ix[:-1]]) \
            if item_ix.size else np.zeros(0, np.int64)
        slot = np.arange(item_ix.size) - np.repeat(
            start, np.diff(np.r_[start, item_ix.size]))
        ids = np.full((int(n_items), int(slot.max()) + 1
                       if slot.size else 1), NO_CATEGORY, np.int32)
        ids[item_ix, slot] = codes
        return cls(ids, vocab)

    @classmethod
    def from_sets(cls, item_categories: Sequence) -> "ItemCategories":
        """From a per-item sequence of optional name collections."""
        if isinstance(item_categories, cls):
            return item_categories
        ix, names = [], []
        for i, cats in enumerate(item_categories):
            if cats:
                names.extend(cats)
                ix.extend([i] * len(cats))
        return cls.from_pairs(len(item_categories), ix, names)

    def codes_of(self, categories) -> np.ndarray:
        """int32 codes of a query's category names (distinct)."""
        return np.array(sorted({self.vocab.get(str(c), UNKNOWN_CATEGORY)
                                for c in categories}), dtype=np.int32)

    def matches(self, categories) -> np.ndarray:
        """bool [I]: the item shares a category with ``categories``."""
        want = self.codes_of(categories)
        return np.isin(self.ids, want[want >= 0]).any(axis=1)

    def __len__(self):
        return self.ids.shape[0]


def pack_available(n_rows: int, unavailable: np.ndarray) -> np.ndarray:
    """The availability bitmap of ``n_rows`` items (a multiple of 32):
    uint32 words in the layout of :func:`_bits_of` (item ``i`` is bit
    ``i // W`` of word ``i % W``, W = n_rows / 32), set where the item may
    be recommended."""
    words = int(n_rows) // 32
    un = np.asarray(unavailable, dtype=np.int64)
    un = un[(un >= 0) & (un < n_rows)]
    gone = np.zeros(words, np.uint32)
    # work proportional to the list, not to the catalogue: the bitmap is
    # rebuilt on the serving path at every new `$set`
    np.bitwise_or.at(gone, un % words,
                     np.uint32(1) << (un // words).astype(np.uint32))
    return ~gone


def _bits_of(words):
    """bool [..., 32 * W] of uint32 words [..., W], traced: item ``i`` is bit
    ``i // W`` of word ``i % W``, so the items of one bit position are W
    neighbours and the whole is 32 slabs laid end to end. With the bits of
    a word as neighbours instead (item ``i`` in word ``i // 32``) the unpack
    is a reshape of a 32-wide minor dimension, which the TPU pads to 128
    lanes and re-lays: 2 ms a query row at 2^22 items (PERF.md, PR 31)."""
    import jax.numpy as jnp
    return jnp.concatenate(
        [((words >> jnp.uint32(j)) & jnp.uint32(1)).astype(bool)
         for j in range(32)], axis=-1)


def _composed_masked_topk_impl(query_mat, item_table, cat, avail_bits,
                               n_items, q_cats, list_rows, list_cols,
                               list_vals, has_white, k: int):
    """Traced body of the composed-mask executables.

    query_mat [B, R]; item_table [I, R]; cat int32 [I, C] (NO_CATEGORY
    pads); avail_bits uint32 [I / 32]; n_items the live rows; q_cats int32
    [B, Q] (NO_QUERY_CATEGORY pads; a row of pads = no category filter);
    list_rows / list_cols / list_vals int32 [T]: the batch's flat list of
    (query, item) pairs, LISTED_OUT or LISTED_WHITE each, every (pair,
    value) at most once (pads: col = I, dropped); has_white bool [B]: the
    query gave a whiteList (an empty one admits nothing). The rule, per
    query and item, is the e-commerce template's isCandidateItem with the
    live lists folded in: live and available and (no categories or one
    shared) and not listed out and (no whiteList or white-listed), and
    score > 0.

    The lists are scattered into two bitmaps of the batch, uint32
    [2, B, I / 32] in :func:`_bits_of`'s layout (an entry adds its bit to
    its word: entries are distinct, so the sum is the union), and read back
    inside the mask's fusion. Scattering into a [B, I] array instead costs
    10 ms a dispatch at I = 2^22 whatever the list's length (PERF.md, PR
    31: the array is written, re-laid and read, 64M elements), beside a
    9 ms scan."""
    import jax
    import jax.numpy as jnp
    b, i_b = query_mat.shape[0], item_table.shape[0]
    words = i_b // 32
    with jax.named_scope("pio.serve.mask"):
        base = _bits_of(avail_bits) & (jnp.arange(i_b) < n_items)
        shared = jnp.zeros((b, i_b), bool)
        for j in range(q_cats.shape[1]):
            for c in range(cat.shape[1]):
                shared |= cat[None, :, c] == q_cats[:, j, None]
        by_category = shared | (q_cats == NO_QUERY_CATEGORY).all(
            axis=1)[:, None]
        listed = _bits_of(jnp.zeros((2, b, words), jnp.uint32).at[
            list_vals, list_rows,
            jnp.where(list_cols < i_b, list_cols % words, words)].add(
                jnp.uint32(1) << (list_cols // words).astype(jnp.uint32),
                mode="drop"))
        by_list = ~listed[LISTED_OUT] & (listed[LISTED_WHITE]
                                         | ~has_white[:, None])
        allowed = base[None, :] & by_category & by_list
    with jax.named_scope("pio.serve.score"):
        scores = jnp.einsum("br,ir->bi", query_mat, item_table,
                            preferred_element_type=jnp.float32)
        scores = jnp.where(allowed & (scores > 0), scores, -jnp.inf)
    with jax.named_scope("pio.serve.topk"):
        return jax.lax.top_k(scores, k)


@functools.partial(__import__("jax").jit, static_argnames=("k",))
def _composed_masked_topk(query_mat, item_table, cat, avail_bits, n_items,
                          q_cats, list_rows, list_cols, list_vals,
                          has_white, k: int):
    """(scores [B, k], idx [B, k]) of :func:`_composed_masked_topk_impl`;
    excluded slots carry -inf."""
    return _composed_masked_topk_impl(
        query_mat, item_table, cat, avail_bits, n_items, q_cats,
        list_rows, list_cols, list_vals, has_white, k=k)


@functools.partial(__import__("jax").jit, static_argnames=("k", "p"))
def _composed_masked_topk_packed(query_mat, item_table, cat, avail_bits,
                                 n_items, q_cats, list_rows, list_cols,
                                 list_vals, has_white, k: int, p: int):
    """:func:`_composed_masked_topk` with the readback plane's pack fused
    on: one contiguous ids + quantized scores payload a dispatch."""
    from predictionio_tpu.ops import readback
    scores, idx = _composed_masked_topk_impl(
        query_mat, item_table, cat, avail_bits, n_items, q_cats,
        list_rows, list_cols, list_vals, has_white, k=k)
    return readback.pack_device(scores, idx, p)


def _aot_composed_topk_builder(b: int = 0, i: int = 0, r: int = 0,
                               k: int = 0, c: int = 0, q: int = 0,
                               t: int = 0, p: int = 0):
    """(jit_fn, example avals, statics) for one composed-mask bucket:
    batch ``b``, item rows ``i``, rank ``r``, top ``k``, ``c`` category
    slots an item, ``q`` a query, ``t`` flat list entries, pack ``p``."""
    import jax
    sds = jax.ShapeDtypeStruct
    avals = (sds((b, r), np.float32), sds((i, r), np.float32),
             sds((i, c), np.int32), sds((i // 32,), np.uint32),
             sds((), np.int32), sds((b, q), np.int32),
             sds((t,), np.int32), sds((t,), np.int32), sds((t,), np.int32),
             sds((b,), bool))
    if p:
        return _composed_masked_topk_packed, avals, {"k": k, "p": p}
    return _composed_masked_topk, avals, {"k": k}


def composed_topk_dims(n_items: int, rank: int, batch: int, k: int,
                       c_max: int, n_query_cats: int = 0,
                       n_listed: int = 0) -> dict:
    """Shape-bucket dims of one composed-mask dispatch — shared by the
    serve dispatch and the deploy/swap warm path."""
    from predictionio_tpu.compile import buckets as B
    from predictionio_tpu.ops import readback
    i_b = B.bucket_table_rows(n_items)
    return {"b": B.bucket_batch(batch), "i": i_b, "r": int(rank),
            "k": min(B.bucket_batch(k, floor=B.K_FLOOR), i_b),
            "c": int(c_max),
            "q": B.bucket_batch(n_query_cats,
                                floor=B.QUERY_CATEGORIES_FLOOR),
            "t": B.bucket_list(n_listed), "p": readback.pack_flag()}


#: list entries a query the deploy-time warm allows for: a user's seen items
#: (the e-commerce template's usual filter, a thousand for a heavy user)
#: beside a white or black list of a few hundred to a few thousand ids. A
#: bucket that was not warmed compiles on the serving path at its first
#: dispatch, seconds during which nothing is answered (at 1,024 a query,
#: one campaign query of a heavy user froze a server for 4.7 s: PERF.md,
#: PR 31)
WARM_LISTED_PER_QUERY = 4096


def composed_topk_warm_dims(n_items: int, rank: int, batch_hint: int,
                            c_max: int) -> list:
    """The buckets a deployment warms: every batch bucket of the
    micro-batcher's ladder, each with the list buckets that batch can
    fill at up to WARM_LISTED_PER_QUERY entries a query. Longer lists and
    more than QUERY_CATEGORIES_FLOOR categories a query compile on first
    use, in the background."""
    from predictionio_tpu.compile import buckets as B
    out = []
    for e in range(B.bucket_batch(max(batch_hint, 1)).bit_length()):
        t = B.LIST_FLOOR
        while True:
            out.append(composed_topk_dims(n_items, rank, 1 << e, 16,
                                          c_max, n_listed=t))
            if t >= (1 << e) * WARM_LISTED_PER_QUERY:
                break
            t = B.bucket_list(t + 1)
    return out


class ItemFilterData:
    """What the composed-mask executables read beside the factor tables,
    one object a model: the item -> category array and the availability
    bitmap, host copies whose device copies ride utils/device_cache (the
    category array uploaded once at its row bucket; a bitmap once per
    ``set_unavailable``). The bitmap is replaced whole, never edited: a
    dispatch that has read ``available_bits`` keeps the bitmap it read."""

    def __init__(self, categories: ItemCategories):
        self.categories = categories
        self.n_items = len(categories)
        from predictionio_tpu.compile import buckets as B
        self._rows = B.bucket_table_rows(self.n_items)
        self.available_bits = pack_available(self._rows, ())
        #: what the bitmap was built from, as the owner names it (the
        #: e-commerce engine: the `$set`'s event id)
        self.unavailable_tag = None
        self._lock = threading.Lock()

    def set_unavailable(self, item_indices, tag=None) -> None:
        with self._lock:
            self._replace(item_indices, tag)

    def sync_unavailable(self, read) -> bool:
        """Follow a list kept elsewhere: ``read(tag)`` is given the tag the
        bitmap was built from and answers ``(newest tag, load)``; where the
        tags differ the bitmap is rebuilt from ``load()``'s item indices
        (an empty list where the newest tag is None) and True comes back.
        The compare and the rebuild are one step under this object's lock,
        so of two callers the later read wins and none parses a list the
        bitmap already holds."""
        with self._lock:
            tag, load = read(self.unavailable_tag)
            if tag == self.unavailable_tag:
                return False
            self._replace(load() if tag is not None else (), tag)
            return True

    def _replace(self, item_indices, tag) -> None:
        self.available_bits = pack_available(self._rows, item_indices)
        self.unavailable_tag = tag


_composed_specs_registered = False


def register_composed_aot_specs():
    global _composed_specs_registered
    if _composed_specs_registered:
        return
    from predictionio_tpu.compile.aot import get_aot
    from predictionio_tpu.obs import costmon
    get_aot().register(costmon.BATCH_PREDICT_COMPOSED,
                       _aot_composed_topk_builder)
    _composed_specs_registered = True


def _filter_h2d_counter():
    from predictionio_tpu.obs.metrics import get_registry
    return get_registry().counter(
        "pio_filter_h2d_bytes_total",
        "Bytes of filter data sent host->device for composed-mask "
        "dispatches: the queries' category codes and item lists, and "
        "each availability bitmap once")


def composed_top_k_batch_begin(item_table: np.ndarray,
                               query_vecs: np.ndarray,
                               filters: ItemFilterData,
                               query_cats: Sequence,
                               listed: Sequence,
                               has_white: Sequence[bool], k: int):
    """Enqueue one composed-mask top-k over ``item_table`` for B queries
    and return ``finish() -> (scores, idx)`` (the deferred readback).

    ``query_cats[j]``: int32 category codes of query j (empty = no
    category filter); ``listed[j]``: (item indices, LISTED_OUT or
    LISTED_WHITE for each) of query j's lists; ``has_white[j]``: the
    query gave a whiteList. Nothing of size [B, I] is built or sent: the
    upload is the category codes and the flat list, a few KB."""
    from predictionio_tpu.compile.aot import (get_aot,
                                              precompile_next_rung)
    from predictionio_tpu.obs import costmon
    from predictionio_tpu.ops import readback
    from predictionio_tpu.utils.device_cache import (cached_put,
                                                     cached_put_rows,
                                                     is_cached)
    register_composed_aot_specs()
    n_items, rank = item_table.shape
    n = query_vecs.shape[0]
    dims = composed_topk_dims(
        n_items, rank, n, k, filters.categories.ids.shape[1],
        max((len(c) for c in query_cats), default=0),
        sum(len(ix) for ix, _ in listed))
    i_b = dims["i"]
    qp = np.zeros((dims["b"], rank), np.float32)
    qp[:n] = query_vecs
    q_cats = np.full((dims["b"], dims["q"]), NO_QUERY_CATEGORY, np.int32)
    white = np.zeros(dims["b"], bool)
    white[:n] = has_white
    rows = np.zeros(dims["t"], np.int32)
    cols = np.full(dims["t"], i_b, np.int32)       # past the end: dropped
    vals = np.zeros(dims["t"], np.int32)
    at = 0
    for j in range(n):
        q_cats[j, :len(query_cats[j])] = query_cats[j]
        ix, v = listed[j]
        rows[at:at + len(ix)] = j
        cols[at:at + len(ix)] = ix
        vals[at:at + len(ix)] = v
        at += len(ix)
    cols[(cols < 0) | (cols >= n_items)] = i_b
    # an entry named twice would add its bit twice
    key = (rows[:at].astype(np.int64) * 2 + vals[:at]) * (i_b + 1) \
        + cols[:at]
    _, first = np.unique(key, return_index=True)
    if first.size < at:
        twice = np.ones(at, bool)
        twice[first] = False
        cols[:at][twice] = i_b
    bits = filters.available_bits
    sent = q_cats.nbytes + rows.nbytes + cols.nbytes + vals.nbytes \
        + white.nbytes
    if not is_cached(bits):
        sent += bits.nbytes
    _filter_h2d_counter().inc(sent)
    args = (qp, cached_put_rows(item_table, i_b, table="item"),
            cached_put_rows(filters.categories.ids, i_b),
            cached_put(bits, table="available_bits"),
            np.int32(n_items), q_cats, rows, cols, vals, white)
    k_eff, p = dims["k"], dims["p"]
    if p:
        packed = get_aot().dispatch(
            costmon.BATCH_PREDICT_COMPOSED, dims,
            lambda *a: _composed_masked_topk_packed(*a, k=k_eff, p=p),
            *args)
        fetch = readback.begin_fetch_packed(packed, p)
    else:
        scores, idx = get_aot().dispatch(
            costmon.BATCH_PREDICT_COMPOSED, dims,
            lambda *a: _composed_masked_topk(*a, k=k_eff), *args)
        fetch = readback.begin_fetch(scores, idx)
    precompile_next_rung(costmon.BATCH_PREDICT_COMPOSED, dims, "i",
                         n_items)

    def finish() -> Tuple[np.ndarray, np.ndarray]:
        scores_h, idx_h = fetch()
        return scores_h[:n], idx_h[:n]
    return finish


def unpack_top_k_rows(scores_row: np.ndarray, idx_row: np.ndarray,
                      num: int) -> Tuple[np.ndarray, np.ndarray]:
    """Per-query view of one masked_top_k_batch row: slice to the query's
    own num and drop -inf (excluded) slots."""
    scores_row = scores_row[:num]
    idx_row = idx_row[:num]
    keep = np.isfinite(scores_row)
    return scores_row[keep], idx_row[keep]


def normalize_rows(factors: np.ndarray) -> np.ndarray:
    norms = np.linalg.norm(factors, axis=-1, keepdims=True)
    return (factors / np.maximum(norms, 1e-12)).astype(np.float32)


def cosine_top_k(item_factors_normalized: np.ndarray,
                 query_vecs: np.ndarray, k: int,
                 allowed_mask: Optional[np.ndarray] = None
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Returns (scores, item_indices), length <= k, excluding -inf entries."""
    from predictionio_tpu.utils.device_cache import cached_put
    n_items = item_factors_normalized.shape[0]
    if allowed_mask is None:
        allowed_mask = np.ones(n_items, dtype=bool)
    k_eff = min(k, n_items)
    scores, idx = _cosine_topk(
        np.asarray(query_vecs, dtype=np.float32),
        cached_put(item_factors_normalized), allowed_mask, k_eff)
    scores = np.asarray(scores)
    idx = np.asarray(idx)
    keep = np.isfinite(scores)
    return scores[keep], idx[keep]


def build_filter_mask(n_items: int,
                      exclude: Sequence[int] = (),
                      white_list: Optional[Sequence[int]] = None,
                      item_categories=None,
                      categories: Optional[set] = None) -> np.ndarray:
    """Host-side candidate mask implementing isCandidateItem
    (ALSAlgorithm.scala:192+): whitelist wins, blacklist/query items
    excluded, category intersection required when given.
    ``item_categories`` is an :class:`ItemCategories` (one vectorised
    membership test) or, as older pickled models hold it, a per-item
    sequence of optional sets, converted on the way in."""
    mask = np.ones(n_items, dtype=bool)
    if white_list is not None:
        mask[:] = False
        wl = np.asarray(list(white_list), dtype=np.int64)
        wl = wl[(wl >= 0) & (wl < n_items)]
        mask[wl] = True
    ex = np.asarray(list(exclude), dtype=np.int64)
    ex = ex[(ex >= 0) & (ex < n_items)]
    mask[ex] = False
    if categories is not None and item_categories is not None:
        mask &= ItemCategories.from_sets(item_categories).matches(
            categories)
    return mask


@functools.partial(__import__("jax").jit, donate_argnums=(0,))
def _gram_accum(G, chunk):
    import jax.numpy as jnp
    return G + jnp.einsum("ci,cj->ij", chunk, chunk,
                          preferred_element_type=jnp.float32)


def item_cosine_similarities(user_ix: np.ndarray, item_ix: np.ndarray,
                             n_users: int, n_items: int,
                             threshold: float = 0.0,
                             chunk_users: int = 4096) -> np.ndarray:
    """Exact all-pairs item-column cosine similarity from binary
    (user, item) interactions — the role of RowMatrix.columnSimilarities
    in the dimsum variant (reference: examples/experimental/
    scala-parallel-similarproduct-dimsum/.../DIMSUMAlgorithm.scala:125-131).

    DIMSUM itself is a sampling approximation invented to bound Spark
    shuffle traffic; on TPU the co-occurrence Gram G = M^T M streams
    through the MXU in user-row chunks (items^2 accumulator resident in
    HBM, never a dense [users, items] matrix), so we compute the exact
    cosine and use `threshold` only to sparsify the result the way
    columnSimilarities(threshold) drops sub-threshold entries.

    Duplicate (user, item) pairs collapse to a single binary entry, same
    as the variant's "keep one copy" dedup. Diagonal is zeroed.
    """
    import jax.numpy as jnp
    order = np.argsort(user_ix, kind="stable")
    u, i = user_ix[order], item_ix[order]
    G = jnp.zeros((n_items, n_items), jnp.float32)
    for start in range(0, n_users, chunk_users):
        stop = start + chunk_users
        lo, hi = np.searchsorted(u, [start, stop])
        chunk = np.zeros((min(chunk_users, n_users - start), n_items),
                         np.float32)
        chunk[u[lo:hi] - start, i[lo:hi]] = 1.0  # set, not add: binary dedup
        G = _gram_accum(G, jnp.asarray(chunk))
    G = np.asarray(G)
    d = np.sqrt(np.maximum(np.diag(G), 1e-12))
    S = G / np.outer(d, d)
    np.fill_diagonal(S, 0.0)
    if threshold > 0:
        S[S < threshold] = 0.0
    return S.astype(np.float32)
