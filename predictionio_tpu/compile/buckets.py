"""Shape-bucket ladders: the sizes the compile plane compiles for.

Every traced program's cost model keys on shapes; every shape that
changes is a recompile. The dims that actually move in production are
quantized to ladders with a floor, so growth INSIDE a bucket changes no
traced shape (zero recompiles) and the program count per executable
stays logarithmic in the largest size. Three ladders, because the dims
pay differently for padding:

- **resident factor tables** (vocabulary rows: users, items, and what
  rides beside them row for row) take ``bucket_table_rows``: eight
  rungs an octave from ``TABLE_FINE_FROM`` rows up, powers of two
  below. Every query scans the item table whole and every padded row
  is HBM held for as long as the model is served, so a power of two's
  up-to-50% padding is paid per dispatch; an eighth step wastes at most
  12.5% and still divides by pow2 shard counts and the TPU's tilings.
  A promotion (``next_table_bucket``) is then one predictable compile
  per executable per <= 12.5% of growth, run in the background once
  the headroom left is a quarter of that step
  (``should_promote_table``); below the threshold a scan costs nothing
  and tiny models keep sharing one program.
- **touched-row counts of a fold tick and staged upload columns** take
  ``bucket_rows``, powers of two: they are not scanned per query, they
  swing tick to tick, and the recompiles a coarse ladder saves are what
  it is for.
- **query batches, k, filter lists, category lists** take
  ``bucket_batch`` / ``bucket_list``: chosen by clients, every value an
  executable to warm.

Pure host math — no jax imports, safe everywhere.
"""

from __future__ import annotations

from typing import Dict, Tuple

#: smallest vocabulary-row bucket: tiny models all share one program
ROWS_FLOOR = 64
#: resident tables of more rows than this sit on eighth steps; up to it
#: on powers of two (2^16 rows are 52 MB at rank 200: 64 us of a scan)
TABLE_FINE_FROM = 1 << 16
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
#: how far through a step of its ladder a size stands when the next
#: bucket should be pre-compiled in the background (before growth forces
#: it on a tick): on the power-of-two ladder, where a step is the bucket
#: itself, that is the fraction of the bucket in use
PROMOTE_AT = 0.75


def _next_pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1).bit_length())


def bucket_rows(n: int, floor: int = ROWS_FLOOR) -> int:
    """Power-of-two row-count bucket covering ``n`` (touched rows, plan
    lengths, staged columns). Resident tables: ``bucket_table_rows``."""
    return max(int(floor), _next_pow2(max(int(n), 1)))


def bucket_table_rows(n: int) -> int:
    """Row bucket of a resident factor table of ``n`` live rows: the
    smallest rung >= ``n`` of the form m * 2^(e-3), m in 8..15 (eight
    rungs an octave, each a multiple of an eighth of the octave's power
    of two), and ``bucket_rows`` up to TABLE_FINE_FROM."""
    b = bucket_rows(n)
    if b <= TABLE_FINE_FROM:
        return b
    step = b >> 4           # an eighth of the octave's lower bound, b / 2
    return -(-int(n) // step) * step


def next_table_bucket(bucket: int) -> int:
    """The rung above ``bucket`` on the resident-table ladder."""
    return bucket_table_rows(int(bucket) + 1)


def should_promote_table(n: int, bucket: int,
                         threshold: float = PROMOTE_AT) -> bool:
    """True when a resident table of ``n`` rows is close enough to
    ``bucket`` that the next rung's executables should compile now, in
    the background: the headroom left is at most ``1 - threshold`` of
    the step to the next rung. On a power-of-two rung the step is the
    bucket and this is "``threshold`` of the bucket in use"; on the fine
    rungs, where a table is always over 88.9% full, the step is what
    growth has to cross before the next compile is needed."""
    step = next_table_bucket(bucket) - int(bucket)
    return int(bucket) - int(n) <= (1.0 - threshold) * step


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


def bucket_table_rows_sharded(n: int, shards: int) -> int:
    """Row bucket for a model-axis-sharded resident table: its rung
    rounded up to a multiple of the shard count, so every shard gets
    an equal contiguous row slice (pow2 shard counts up to 8 divide
    every rung for free; a 3-way mesh axis still gets a legal layout).
    The rung above a resident ``bucket`` is this of ``bucket + 1``."""
    b = bucket_table_rows(n)
    s = max(int(shards), 1)
    return ((b + s - 1) // s) * s


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
