"""Distributed top-k over model-sharded score tables.

When an item-factor table is sharded over the mesh `model` axis (catalogs
too large for one device's HBM — ALSConfig.factor_sharding='model'), serving
must rank across shards. `sharded_top_k` runs the canonical two-phase
reduction as one jitted shard_map: each device ranks its local shard
(lax.top_k), the (k, score, index) candidates are all-gathered over ICI —
k*devices values instead of the full score row — and the final top-k picks
globally. This is the serve-time analog of the reference's distributed-model
`RDD.lookup`/collect path (SURVEY.md §2.9 L/P2L/P row).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

from predictionio_tpu.parallel.mesh import MeshContext, current_mesh


def sharded_top_k(item_factors_sharded, query_vec, k: int,
                  mesh: Optional[MeshContext] = None,
                  allowed_mask_sharded=None
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """item_factors_sharded: [I, R] jax.Array sharded over ('model', None).
    query_vec: [R] host or device. Returns host (scores, global_indices).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = mesh or current_mesh()
    n_items = item_factors_sharded.shape[0]
    mp = mesh.model_parallelism
    shard_rows = n_items // mp
    # a shard can contribute at most shard_rows candidates, and the global
    # top-k takes at most shard_rows items from any single shard — so
    # k_local candidates per shard are sufficient for an exact answer even
    # when k exceeds shard_rows
    k_local = min(k, shard_rows)
    k_final = min(k, mp * k_local)

    @functools.partial(
        jax.shard_map, mesh=mesh.mesh,
        in_specs=(P("model", None), P(), P("model")),
        out_specs=(P(), P()),
        check_vma=False)
    def _local_then_global(v_shard, q, mask_shard):
        with jax.named_scope("pio.serve.score"):
            scores = jnp.einsum("ir,r->i", v_shard, q,
                                preferred_element_type=jnp.float32)
            scores = jnp.where(mask_shard, scores, -jnp.inf)
        with jax.named_scope("pio.serve.topk"):
            local_s, local_i = jax.lax.top_k(scores, k_local)
            # globalize indices: shard offset from the model-axis
            # position
            ax = jax.lax.axis_index("model")
            local_i = local_i + ax * v_shard.shape[0]
            all_s = jax.lax.all_gather(local_s, "model").reshape(-1)
            all_i = jax.lax.all_gather(local_i, "model").reshape(-1)
            top_s, pos = jax.lax.top_k(all_s, k_final)
            return top_s, all_i[pos]

    if allowed_mask_sharded is None:
        allowed_mask_sharded = jax.device_put(
            np.ones(n_items, dtype=bool), mesh.sharding("model"))
    q = jnp.asarray(query_vec, dtype=item_factors_sharded.dtype)
    scores, idx = _local_then_global(item_factors_sharded, q,
                                     allowed_mask_sharded)
    return np.asarray(scores)[:k_final], np.asarray(idx)[:k_final]


# ---------------------------------------------------------------------------
# Batched, masked, bucket-stable serve path (sharded online plane)
#
# The single-query `sharded_top_k` above is the GSPMD reference; the
# functions below are the SERVE-plane siblings: every moving dim is
# shape-bucketed (ISSUE 9 compile plane), query vectors arrive as one
# [B, R] host batch (gathered from the published model's host shard
# mirrors — the user table never needs serving HBM), the item table
# stays model-sharded in HBM, and the ranking runs the two-phase
# reduction per shard: local top-k over the shard's rows, a k*shards
# candidate all-gather over the model axis, and a global top-k — the
# full [B, I] score matrix is never replicated to one device.
# ---------------------------------------------------------------------------

def sharded_k_split(k: int, padded_rows: int,
                    n_shards: int) -> Tuple[int, int]:
    """(k_local, k_final) for one sharded ranking: a shard contributes
    at most its row count, and the final answer at most ``n_shards *
    k_local`` candidates — exact for any k (see sharded_top_k). A pure
    function of BUCKET dims only (never of the live ``n_items``), so
    vocabulary growth inside a bucket keeps every compiled shape;
    columns past the valid items carry -inf, dropped by the callers'
    finite-filter exactly as on the replicated path."""
    shard_rows = max(padded_rows // n_shards, 1)
    k_local = min(k, shard_rows)
    return k_local, min(k, n_shards * k_local)


def make_batched_sharded_topk(mesh: MeshContext, k_local: int,
                              k_final: int, has_mask: bool,
                              filter_positive: bool, pack: int = 0):
    """The jitted batched two-phase top-k for one (mesh, statics)
    combination, resolved through the compile plane's shared-jit
    surface (one process-wide jit per key; the AOT registry lowers the
    same callable with sharded avals at warm time).

    Signature of the returned callable:
    ``(q [B, R] replicated, v_shard [I, R] model-sharded, n_items ()
    int32[, mask [B, I] bool sharded on dim 1]) -> (scores [B, k_final],
    global_indices [B, k_final])`` — or, with ``pack`` > 0 (the
    readback plane, ISSUE 19), ONE replicated ``[B, k_final, slot]``
    uint8 payload: the ids+quantized-scores pack is fused after the
    cross-shard merge inside the same program, so the sharded serve
    window also pays a single small d2h wall."""
    import jax
    import jax.numpy as jnp
    from predictionio_tpu.compile.aot import get_aot

    P = jax.sharding.PartitionSpec
    in_specs = [P(), P("model", None), P()]
    if has_mask:
        in_specs.append(P(None, "model"))
    out_specs = P() if pack else (P(), P())

    @functools.partial(jax.shard_map, mesh=mesh.mesh,
                       in_specs=tuple(in_specs), out_specs=out_specs,
                       check_vma=False)
    def _kernel(q, v_shard, n_items, *mask):
        with jax.named_scope("pio.serve.score"):
            scores = jnp.einsum("br,ir->bi", q, v_shard,
                                preferred_element_type=jnp.float32)
            ax = jax.lax.axis_index("model")
            base = ax * v_shard.shape[0]
            # bucket-padding rows (global index >= n_items) rank last
            valid = (jnp.arange(v_shard.shape[0]) + base) < n_items
            allowed = valid[None, :]
            if has_mask:
                allowed = allowed & mask[0]
            if filter_positive:
                allowed = allowed & (scores > 0)
            scores = jnp.where(allowed, scores, -jnp.inf)
        with jax.named_scope("pio.serve.topk"):
            local_s, local_i = jax.lax.top_k(scores, k_local)
            local_i = local_i + base
            all_s = jnp.moveaxis(
                jax.lax.all_gather(local_s, "model"), 0, 1
            ).reshape(local_s.shape[0], -1)
            all_i = jnp.moveaxis(
                jax.lax.all_gather(local_i, "model"), 0, 1
            ).reshape(local_i.shape[0], -1)
            top_s, pos = jax.lax.top_k(all_s, k_final)
            top_i = jnp.take_along_axis(all_i, pos, axis=1)
        if pack:
            from predictionio_tpu.ops import readback
            return readback.pack_device(top_s, top_i, pack)
        return top_s, top_i

    # one process-wide jit per (mesh, statics) key: the compile plane
    # constructs and holds it (shared_jit), so repeated calls here only
    # rebuild the cheap shard_map wrapper, never a fresh jit closure
    key = (f"topk.sharded_batched:{id(mesh.mesh)}:"
           f"{mesh.model_parallelism}:{k_local}:{k_final}:"
           f"{int(has_mask)}:{int(filter_positive)}:{int(pack)}")
    return get_aot().shared_jit(key, _kernel)


def batched_sharded_top_k(item_dev, query_vecs: np.ndarray,
                          n_items: int, k_bucket: int,
                          mesh: MeshContext,
                          masks: Optional[np.ndarray] = None,
                          filter_positive: bool = False,
                          label: Optional[str] = None,
                          dims: Optional[dict] = None
                          ) -> Tuple[np.ndarray, np.ndarray]:
    """Rank ``query_vecs`` (already padded to their batch bucket)
    against the resident model-sharded ``item_dev`` table. ``masks``
    (padded [B, I_bucket] bool, or None) is uploaded sharded over the
    item dim. Dispatches through the AOT registry when ``label`` /
    ``dims`` are given (warmed buckets run zero trace / zero
    compile), else calls the shared jit directly."""
    return batched_sharded_top_k_begin(
        item_dev, query_vecs, n_items, k_bucket, mesh, masks=masks,
        filter_positive=filter_positive, label=label, dims=dims)()


def batched_sharded_top_k_begin(item_dev, query_vecs: np.ndarray,
                                n_items: int, k_bucket: int,
                                mesh: MeshContext,
                                masks: Optional[np.ndarray] = None,
                                filter_positive: bool = False,
                                label: Optional[str] = None,
                                dims: Optional[dict] = None):
    """Two-phase sibling of :func:`batched_sharded_top_k` for the
    pipelined serving executor (ISSUE 14): uploads + enqueues the
    sharded ranking NOW and returns ``finish() -> (scores, idx)``
    which performs the deferred device->host readback — so the
    cross-shard merge of window N overlaps window N+1's host-side
    batch formation. The d2h copy of the (packed) result goes in
    flight HERE via the readback plane, so ``finish`` only waits."""
    import jax
    from predictionio_tpu.obs import jaxmon
    from predictionio_tpu.ops import readback

    padded_rows = int(item_dev.shape[0])
    k_local, k_final = sharded_k_split(k_bucket, padded_rows,
                                       mesh.model_parallelism)
    p = dims["p"] if dims and "p" in dims else readback.pack_flag()
    fn = make_batched_sharded_topk(mesh, k_local, k_final,
                                   masks is not None, filter_positive,
                                   pack=p)
    q = np.ascontiguousarray(query_vecs, dtype=np.float32)
    args = [q, item_dev, np.int32(n_items)]
    if masks is not None:
        mask_dev = jax.device_put(masks, mesh.sharding(None, "model"))
        jaxmon.record_h2d(masks.nbytes)
        args.append(mask_dev)
    jaxmon.record_h2d(q.nbytes)
    if label is not None and dims is not None:
        from predictionio_tpu.compile.aot import get_aot
        out = get_aot().dispatch(label, dims, fn, *args)
    else:
        from predictionio_tpu.obs.costmon import device_timed
        out = device_timed(label or "sharded_topk", fn, *args)
    if p:
        return readback.begin_fetch_packed(out, p)
    scores, idx = out
    fetch = readback.begin_fetch(scores, idx)

    def finish() -> Tuple[np.ndarray, np.ndarray]:
        scores_h, idx_h = fetch()
        return scores_h, idx_h
    return finish
