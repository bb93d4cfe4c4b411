"""Shape-bucket ladder: the sizes the compile plane compiles for.

Every traced program's cost model keys on shapes; every shape that
changes is a recompile. The ladder quantizes the three dims that
actually move in production — vocabulary rows (users/items grow with
traffic), touched-row counts (fold ticks), and query batch sizes — to
next-power-of-two buckets with a floor, so:

- growth INSIDE a bucket changes no traced shape (zero recompiles);
- a promotion (bucket -> 2x) is one predictable compile per
  executable, cheap enough to run in the background before the shape
  is needed (``occupancy`` past ``PROMOTE_AT`` is the trigger);
- the program count per executable is bounded by log2(max size).

Pure host math — no jax imports, safe everywhere.
"""

from __future__ import annotations

from typing import Dict, Tuple

#: smallest vocabulary-row bucket: tiny models all share one program
ROWS_FLOOR = 64
#: smallest batch bucket (a single query is its own class)
BATCH_FLOOR = 1
#: smallest top-k bucket: client-chosen num in 1..16 shares one
#: program (and one deploy-time warm spec); the extra top-k positions
#: are noise next to the scoring matmul
K_FLOOR = 16
#: smallest bucket of a dispatch's flat filter list (the batch's excluded
#: and white-listed (query, item) pairs together): a query carries tens to
#: a few hundred, so most dispatches of up to 16 fit the floor or the
#: bucket above it
LIST_FLOOR = 1024
#: smallest bucket of a query's category list
QUERY_CATEGORIES_FLOOR = 4
#: fraction of a bucket in use at which the next bucket should be
#: pre-compiled in the background (before growth forces it on a tick)
PROMOTE_AT = 0.75


def _next_pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1).bit_length())


def bucket_rows(n: int, floor: int = ROWS_FLOOR) -> int:
    """Row-count bucket covering ``n`` (vocab rows, touched rows)."""
    return max(int(floor), _next_pow2(max(int(n), 1)))


def bucket_batch(n: int, floor: int = BATCH_FLOOR) -> int:
    """Query-batch bucket covering ``n``."""
    return max(int(floor), _next_pow2(max(int(n), 1)))


def bucket_list(n: int) -> int:
    """Filter-list bucket covering ``n`` entries: LIST_FLOOR times a power
    of four. List lengths are the clients' to choose, and every (batch,
    list) pair is an executable to warm: steps of four keep that ladder
    at three or four rungs where steps of two would double it, and a
    padded entry costs the device one dropped scatter update."""
    b = LIST_FLOOR
    while b < n:
        b *= 4
    return b


def bucket_rows_sharded(n: int, shards: int,
                        floor: int = ROWS_FLOOR) -> int:
    """Row bucket for a model-axis-sharded table: the pow2 bucket
    rounded up to a multiple of the shard count, so every shard gets
    an equal contiguous row slice (pow2 shard counts divide pow2
    buckets for free; a 3-way mesh axis still gets a legal layout)."""
    b = bucket_rows(n, floor=floor)
    s = max(int(shards), 1)
    return ((b + s - 1) // s) * s


def occupancy(n: int, bucket: int) -> float:
    """How full ``bucket`` is at current size ``n`` (0..1]."""
    return float(n) / float(bucket) if bucket else 1.0


def should_promote(n: int, bucket: int,
                   threshold: float = PROMOTE_AT) -> bool:
    """True when ``n`` is close enough to ``bucket`` that the next
    bucket's executables should compile now, in the background."""
    return occupancy(n, bucket) >= threshold


def next_bucket(bucket: int) -> int:
    return int(bucket) * 2


def bucket_key(dims: Dict[str, int]) -> Tuple[Tuple[str, int], ...]:
    """Canonical hashable key for a bucket-dim dict (sorted items).

    Dims need not all be sizes: flag dims ride the same key — ``s``
    (shard count, sharded vs replicated layout), ``fp`` (positive-
    score filter), ``c`` (category slots per item of the composed-mask
    family), and ``p`` (readback pack mode, ISSUE 19: the packed
    variant's single-payload output aval is a different program). Each
    flag value owns its own warmed executables, so flipping a flag at
    runtime never invalidates the other value's buckets."""
    return tuple(sorted((str(k), int(v)) for k, v in dims.items()))


def bucket_label(dims: Dict[str, int]) -> str:
    """Compact metric-label rendering: ``"b16-i2048-u1024"``. Bucket
    combinations are log-bounded per dim, so cardinality stays small."""
    return "-".join(f"{k}{v}" for k, v in bucket_key(dims))
