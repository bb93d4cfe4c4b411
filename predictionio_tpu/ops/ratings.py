"""Host-side ratings preprocessing: COO -> bucketed, padded solve plans.

This is the ragged->fixed-shape edge (SURVEY.md hard part #3): events per
user/item are power-law ragged, XLA wants static shapes. Entities are
bucketed by rating count into geometric-ladder segment lengths K
(bucket_lengths); each bucket is processed as [B, K] padded batches with B
chosen to keep B*K work roughly constant, so the whole sweep compiles to a
ladder's worth of kernel shapes consumed by one scan program per side.

Replaces the grouping/shuffle phase of MLlib's block ALS (reference consumer:
examples/scala-parallel-recommendation/custom-prepartor/src/main/scala/
ALSAlgorithm.scala:55 `ALS.train`), and the `((u,i),1).reduceByKey` rating
construction of the similarproduct template
(examples/scala-parallel-similarproduct/multi/src/main/scala/ALSAlgorithm.scala:96-133).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.obs import TRACER


@dataclass(frozen=True)
class RatingsCOO:
    """Deduplicated (user, item, rating) triples with dense int32 indices."""
    user_idx: np.ndarray   # [nnz] int32
    item_idx: np.ndarray   # [nnz] int32
    rating: np.ndarray     # [nnz] float32
    n_users: int
    n_items: int

    @property
    def nnz(self) -> int:
        return int(self.user_idx.shape[0])

    def transpose(self) -> "RatingsCOO":
        return RatingsCOO(self.item_idx, self.user_idx, self.rating,
                          self.n_items, self.n_users)


def dedup_ratings(user_idx, item_idx, rating, timestamps=None,
                  policy: str = "latest") -> Tuple[np.ndarray, np.ndarray,
                                                   np.ndarray]:
    """Collapse duplicate (user, item) pairs.

    policy:
      'latest' — keep the rating with the greatest timestamp (the reference
                 recommendation DataSource semantics for re-rated items);
                 requires `timestamps` (falls back to last occurrence).
      'sum'    — sum ratings (the similarproduct view-count semantics,
                 `((u,i),1).reduceByKey(_+_)`).
      'mean'   — average duplicates.
    """
    user_idx = np.asarray(user_idx, dtype=np.int64)
    item_idx = np.asarray(item_idx, dtype=np.int64)
    rating = np.asarray(rating, dtype=np.float32)
    if user_idx.size == 0:
        return (user_idx.astype(np.int32), item_idx.astype(np.int32), rating)
    n_items = int(item_idx.max()) + 1
    pair = user_idx * n_items + item_idx
    if policy == "latest":
        order = (np.argsort(timestamps, kind="stable")
                 if timestamps is not None else np.arange(pair.size))
        pair_o = pair[order]
        # keep the last occurrence in time order
        uniq, last_pos = np.unique(pair_o[::-1], return_index=True)
        keep = order[::-1][last_pos]
        keep.sort()
        return (user_idx[keep].astype(np.int32),
                item_idx[keep].astype(np.int32), rating[keep])
    uniq, inv = np.unique(pair, return_inverse=True)
    sums = np.bincount(inv, weights=rating.astype(np.float64))
    if policy == "mean":
        counts = np.bincount(inv)
        sums = sums / counts
    elif policy != "sum":
        raise ValueError(f"unknown dedup policy {policy!r}")
    return ((uniq // n_items).astype(np.int32),
            (uniq % n_items).astype(np.int32),
            sums.astype(np.float32))


@dataclass(frozen=True)
class SolveBatch:
    """One fixed-shape batch of entities to solve: gather `idx` rows of the
    counterpart factor table, weight by `val`, mask padding."""
    rows: np.ndarray    # [B] int32 — dense indices being solved; padding = -1
    idx: np.ndarray     # [B, K] int32 — counterpart indices; padding = 0
    val: np.ndarray     # [B, K] float32 — ratings; padding = 0
    mask: np.ndarray    # [B, K] float32 — 1 for real entries

    @property
    def shape(self) -> Tuple[int, int]:
        return self.idx.shape


@dataclass(frozen=True)
class SolvePlan:
    """All batches needed to solve one side of the factorization."""
    batches: Sequence[SolveBatch]
    n_entities: int
    nnz: int
    # entities of the counterpart side, whose table `idx` points into: what
    # tells ops/als._upload_plan which shard of a row-sharded table owns a
    # slot's row. None from a caller that did not say (the online fold).
    n_counter: Optional[int] = None

    @property
    def kernel_shapes(self):
        return sorted({b.shape for b in self.batches})

    @property
    def padded_work(self) -> int:
        """Total padded gather/Gram positions: real entities x their
        padded segment length K."""
        return sum(int(np.count_nonzero(b.rows >= 0)) * b.shape[1]
                   for b in self.batches)

    @property
    def padding_overhead(self) -> float:
        """padded work / real work — the Gram FLOP inflation from the
        ragged->fixed bucketing (1.0 = no waste)."""
        if self.nnz == 0:
            return 1.0
        return self.padded_work / self.nnz


def bucket_lengths(max_count: int, min_k: int = 8,
                   ratio: float = 1.125) -> np.ndarray:
    """Padded segment lengths: a geometric ladder (ratio ~1.125) aligned
    to the gather buffer's layout granularity — multiples of 8 (the f32
    sublane tile, so a finer K would occupy the same HBM anyway) up to
    128, then coarser powers of two (16/32/64/128) chosen so the rounding
    never dominates the geometric step. The odd multiples of 8 below 128
    (24, 40, 56, ...) are 8- but not 16-aligned: the f32 factor-row
    gather — the dominant HBM term — is exact at them, while the bf16
    compute intermediate may round its sublane dim up to the next 16, in
    which case its cost equals (never exceeds) a 16-aligned ladder's.
    Bounds the
    per-entity Gram/gather padding waste at ~12-33% (12% asymptotic,
    granularity-bound below 32) through the whole mid-range where the
    rating-count mass sits, vs the up-to-2x windows of pow2 buckets
    (rounds 1-3: (8,16],(16,32],(32,64] each cost 2x worst-case, which
    is exactly where ML-20M's 20+-ratings-per-user floor lands).
    ~50 sizes to 20k; every size is a scan group inside
    the ONE _solve_sweep program, so the cost is compile time (amortized
    by the persistent compilation cache), not dispatches."""
    sizes = []
    k = min_k
    while True:
        sizes.append(k)
        if k >= max_count:
            break
        t = k * ratio
        step = (8 if t < 128 else 16 if t < 512 else
                32 if t < 2048 else 64 if t < 8192 else 128)
        k = max(int(np.ceil(t / step) * step), k + step)
    return np.array(sizes, dtype=np.int64)


def build_solve_plan(group_idx: np.ndarray, counter_idx: np.ndarray,
                     values: np.ndarray, n_groups: int,
                     work_budget: int = 1 << 20, min_k: int = 8,
                     batch_multiple: int = 1,
                     bucket_ratio: float = 1.125,
                     n_counter: Optional[int] = None) -> SolvePlan:
    """Group COO entries by `group_idx`, bucket groups by padded segment
    length K (geometric ladder, bucket_lengths), and emit [B, K] batches
    with B ~= work_budget/K rounded up to `batch_multiple` (the mesh
    data-parallel degree).

    Vectorized host numpy — no per-entity Python loops.
    """
    group_idx = np.asarray(group_idx, dtype=np.int64)
    counter_idx = np.asarray(counter_idx, dtype=np.int32)
    values = np.asarray(values, dtype=np.float32)
    nnz = group_idx.size

    order = np.argsort(group_idx, kind="stable")
    g_sorted = group_idx[order]
    c_sorted = counter_idx[order]
    v_sorted = values[order]
    counts = np.bincount(g_sorted, minlength=n_groups).astype(np.int64)
    starts = np.zeros(n_groups + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])

    present = np.nonzero(counts)[0]
    if present.size == 0:
        return SolvePlan(batches=(), n_entities=n_groups, nnz=0,
                         n_counter=n_counter)
    sizes = bucket_lengths(int(counts[present].max()), min_k,
                           ratio=bucket_ratio)
    ks = sizes[np.searchsorted(sizes, counts[present], side="left")]
    # Merge SPARSE buckets upward: a bucket holding a handful of
    # entities still costs a whole scan group in the compiled sweep
    # (XLA program size — the finer ladder's one real cost, measured as
    # minutes of full-scale compile) for almost no work. Entities move
    # to the next ladder size while their cumulative padding stays
    # within `merge_cap` of their ORIGINAL bucket, so the tail giants
    # (one entity per bucket by nature, big nnz) never cascade into a
    # 2x-padded monster bucket.
    min_bucket, merge_cap, work_share = 32, 1.25, 0.002
    ks_orig = ks.copy()
    cnts_present = counts[present]
    for i in range(len(sizes) - 1):
        members = ks == sizes[i]
        n_mem = int(np.count_nonzero(members))
        # merge only buckets that are BOTH sparse and a negligible share
        # of the total work — at small scale every bucket is sparse and
        # merging would buy padding for nothing; at full scale this
        # fires exactly on the long tail of near-singleton buckets
        if (0 < n_mem < min_bucket
                and int(cnts_present[members].sum()) < work_share * nnz):
            movable = members & (sizes[i + 1] <= merge_cap * ks_orig)
            if movable.sum() == n_mem:
                # move only when the WHOLE bucket can go — a partial
                # move keeps the source group alive and buys padding
                # without reducing the compiled program
                ks[movable] = sizes[i + 1]

    batches: List[SolveBatch] = []
    for k in np.unique(ks):
        members = present[ks == k]  # entities padded to this K
        b_full = max(int(work_budget // k), 1)
        b_full = ((b_full + batch_multiple - 1) // batch_multiple
                  ) * batch_multiple
        for lo in range(0, members.size, b_full):
            chunk = members[lo:lo + b_full]
            b = ((chunk.size + batch_multiple - 1) // batch_multiple
                 ) * batch_multiple
            rows = np.full(b, -1, dtype=np.int32)
            rows[:chunk.size] = chunk
            idx = np.zeros((b, int(k)), dtype=np.int32)
            val = np.zeros((b, int(k)), dtype=np.float32)
            mask = np.zeros((b, int(k)), dtype=np.float32)
            # vectorized fill: flat positions row*k + [0..count)
            cnts = counts[chunk]
            row_of = np.repeat(np.arange(chunk.size), cnts)
            # position within each segment
            pos = np.arange(row_of.size) - np.repeat(
                np.concatenate([[0], np.cumsum(cnts)[:-1]]), cnts)
            src = np.repeat(starts[chunk], cnts) + pos
            idx[row_of, pos] = c_sorted[src]
            val[row_of, pos] = v_sorted[src]
            mask[row_of, pos] = 1.0
            batches.append(SolveBatch(rows, idx, val, mask))
    return SolvePlan(batches=tuple(batches), n_entities=n_groups, nnz=nnz,
                     n_counter=n_counter)


def plan_for_users(r: RatingsCOO, **kw) -> SolvePlan:
    with TRACER.region("train.plan", side="user"):
        return build_solve_plan(r.user_idx, r.item_idx, r.rating,
                                r.n_users, n_counter=r.n_items, **kw)


def plan_for_items(r: RatingsCOO, **kw) -> SolvePlan:
    with TRACER.region("train.plan", side="item"):
        return build_solve_plan(r.item_idx, r.user_idx, r.rating,
                                r.n_items, n_counter=r.n_users, **kw)
