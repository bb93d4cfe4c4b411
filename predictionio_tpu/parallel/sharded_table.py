"""Host-side handle to a model-axis-sharded factor table.

The ALX discipline (PAPERS.md "Large Scale Matrix Factorization on
TPUs") keeps embedding tables sharded across the mesh and device-
resident across steps; the host never holds — or moves — the whole
table. ``ShardedTable`` is what a published model version carries in
place of one monolithic numpy array:

- **per-shard host slices** (``shards`` + ``offsets``): the durable
  mirror the registry serializes, the gates probe, and a restarted
  server re-uploads from. In a multi-process mesh each process holds
  only its addressable shards; single-process holds all of them.
- **a transient device handle** (``_dev``): the resident fast path.
  A fold tick publishes the tick's final device arrays here, so the
  next tick — and serving — reuse them without any host round trip.
  The handle is never pickled (``__getstate__`` drops it) and is
  revalidated against the mesh before reuse.

Steady-state fold ticks update the mirror **copy-on-write per shard**:
only shards containing touched rows are copied and patched (host
memcpy), and only the touched rows themselves cross the device->host
link. The table as a whole never moves — the property the over-budget
acceptance scenario asserts via ``pio_fold_upload_bytes_total``.

Tables are immutable: hot-swap/rollback replace the whole object, so
a query thread can never observe a half-patched shard set (the same
no-torn-read contract replicated models get from numpy immutability
by convention).
"""

from __future__ import annotations

import logging
import threading
from typing import Optional, Sequence, Tuple

import numpy as np

logger = logging.getLogger(__name__)


def is_sharded(table) -> bool:
    """True when ``table`` is a ShardedTable (the layout dispatch every
    serve/fold/gate call site keys on)."""
    return isinstance(table, ShardedTable)


def table_rows(table, idx) -> np.ndarray:
    """Host gather of global rows from either layout: shard mirrors
    for a ShardedTable, plain fancy-indexing for numpy."""
    if is_sharded(table):
        return table.rows(idx)
    return np.asarray(table)[np.asarray(idx, dtype=np.int64)]


def layout_of(table) -> str:
    """'model:<N>' for an N-way sharded table, else 'replicated' — the
    sharding token residency slots and caches key on."""
    if is_sharded(table):
        return f"model:{table.n_shards}"
    return "replicated"


class ShardedTable:
    """Row-partitioned factor table: ``n_shards`` contiguous row ranges
    of a ``[padded_rows, rank]`` table, rows ``>= n_rows`` being bucket
    padding (zeros). Immutable by convention — mutators return new
    tables sharing untouched shard arrays."""

    def __init__(self, shards: Sequence[np.ndarray],
                 offsets: Sequence[int], n_rows: int, padded_rows: int,
                 n_shards: int):
        self.shards: Tuple[np.ndarray, ...] = tuple(
            np.ascontiguousarray(s, dtype=np.float32) for s in shards)
        self.offsets: Tuple[int, ...] = tuple(int(o) for o in offsets)
        self.n_rows = int(n_rows)
        self.padded_rows = int(padded_rows)
        self.n_shards = int(n_shards)
        if not self.shards:
            raise ValueError("ShardedTable needs at least one shard")
        if padded_rows % self.n_shards:
            raise ValueError(
                f"padded rows {padded_rows} not divisible by "
                f"{self.n_shards} shards")
        self._dev = None          # transient device handle (never pickled)
        # serializes the cold-path upload: N serve threads racing a
        # restart must not each materialize the table (transient N x
        # per-device HBM — the overcommit the budget exists to stop)
        self._dev_lock = threading.Lock()

    # -- numpy-facing surface ------------------------------------------------
    @property
    def rank(self) -> int:
        return self.shards[0].shape[1]

    @property
    def shape(self) -> Tuple[int, int]:
        """The LOGICAL shape (bucket padding excluded) — what
        ``ALSModel.n_users``/``n_items`` and the gates read."""
        return (self.n_rows, self.rank)

    @property
    def ndim(self) -> int:
        return 2

    @property
    def dtype(self):
        return self.shards[0].dtype

    @property
    def size(self) -> int:
        return self.n_rows * self.rank

    @property
    def nbytes(self) -> int:
        """Logical table bytes (what a replicated copy would cost)."""
        return self.n_rows * self.rank * self.dtype.itemsize

    @property
    def per_shard_nbytes(self) -> int:
        """Padded bytes ONE device holds — the number the per-device
        table budget compares against."""
        return (self.padded_rows // self.n_shards) * self.rank \
            * self.dtype.itemsize

    def __len__(self) -> int:
        return self.n_rows

    def __repr__(self) -> str:
        return (f"ShardedTable(rows={self.n_rows}/{self.padded_rows}, "
                f"rank={self.rank}, shards={self.n_shards}, "
                f"resident={self._dev is not None})")

    # -- constructors --------------------------------------------------------
    @staticmethod
    def from_host(arr: np.ndarray, n_shards: int,
                  padded_rows: Optional[int] = None) -> "ShardedTable":
        """Split one host table into ``n_shards`` equal row slices,
        zero-padded to ``padded_rows`` (default: the covering sharded
        vocab bucket). The entry path for converting a replicated model
        to the sharded layout."""
        from predictionio_tpu.compile.buckets import \
            bucket_table_rows_sharded
        arr = np.ascontiguousarray(arr, dtype=np.float32)
        n = arr.shape[0]
        target = padded_rows if padded_rows is not None \
            else bucket_table_rows_sharded(max(n, 1), n_shards)
        if target < n or target % n_shards:
            raise ValueError(
                f"padded_rows {target} must cover {n} rows and divide "
                f"by {n_shards}")
        per = target // n_shards
        shards = []
        for s in range(n_shards):
            lo, hi = s * per, (s + 1) * per
            block = np.zeros((per, arr.shape[1]), dtype=np.float32)
            got = arr[lo:min(hi, n)]
            block[:got.shape[0]] = got
            shards.append(block)
        return ShardedTable(shards, [s * per for s in range(n_shards)],
                            n, target, n_shards)

    # -- host row access -----------------------------------------------------
    def _which_shard(self, idx: np.ndarray) -> np.ndarray:
        """Shard index (into ``self.shards``) owning each global row;
        raises IndexError for rows no addressable shard covers (a
        multi-process follower holds only its slices — a negative or
        past-the-slice lookup must fail loudly, never wrap into the
        wrong shard's rows)."""
        offs = np.asarray(self.offsets, dtype=np.int64)
        which = np.searchsorted(offs, idx, side="right") - 1
        if (which < 0).any():
            raise IndexError(
                f"rows {idx[which < 0]} precede this process's "
                f"addressable shards (offsets {self.offsets})")
        ends = offs + np.asarray([s.shape[0] for s in self.shards],
                                 dtype=np.int64)
        past = idx >= ends[which]
        if past.any():
            raise IndexError(
                f"rows {idx[past]} fall outside this process's "
                f"addressable shards (offsets {self.offsets})")
        return which

    def _require_full_coverage(self, what: str):
        if self.offsets[0] != 0 or sum(
                s.shape[0] for s in self.shards) != self.padded_rows:
            raise ValueError(
                f"{what} needs every shard addressable "
                f"(single-process); this process holds offsets "
                f"{self.offsets} of {self.padded_rows} rows")

    def rows(self, idx) -> np.ndarray:
        """Gather global rows from the host shard mirrors (the gates'
        probe path and the serve-side user-vector lookup — no device
        involved). Raises IndexError for rows outside the addressable
        shards (multi-process callers own only their slices)."""
        idx = np.asarray(idx, dtype=np.int64).ravel()
        out = np.empty((idx.size, self.rank), dtype=np.float32)
        if idx.size == 0:
            return out
        if (idx < 0).any() or (idx >= self.padded_rows).any():
            raise IndexError(f"row index out of range 0..{self.padded_rows}")
        which = self._which_shard(idx)
        offs = np.asarray(self.offsets, dtype=np.int64)
        for s in np.unique(which):
            sel = which == s
            out[sel] = self.shards[s][idx[sel] - offs[s]]
        return out

    def to_numpy(self) -> np.ndarray:
        """Materialize the FULL logical table on host — an explicit
        O(table) host concat for parity tests / checkpoint export, not
        a serve- or tick-path operation."""
        self._require_full_coverage("to_numpy")
        return np.concatenate(self.shards, axis=0)[:self.n_rows]

    def all_finite(self) -> bool:
        return all(np.isfinite(self._logical_view(i)).all()
                   for i in range(len(self.shards)))

    def max_row_norm(self) -> float:
        mx = 0.0
        for i in range(len(self.shards)):
            t = self._logical_view(i)
            if t.size == 0:
                continue
            with np.errstate(over="ignore", invalid="ignore"):
                n = float(np.sqrt(np.max(np.einsum("ij,ij->i", t, t))))
            if np.isfinite(n):
                mx = max(mx, n)
        return mx

    def _logical_view(self, i: int) -> np.ndarray:
        """Shard ``i`` minus bucket-padding rows (zero rows past
        ``n_rows`` must not influence finiteness/norm verdicts...
        they are zero, but a patched-row write past n_rows could)."""
        off = self.offsets[i]
        stop = max(min(self.n_rows - off, self.shards[i].shape[0]), 0)
        return self.shards[i][:stop]

    # -- mutation (copy-on-write) -------------------------------------------
    def with_rows(self, idx, values: np.ndarray,
                  n_rows: Optional[int] = None) -> "ShardedTable":
        """New table with global rows ``idx`` replaced by ``values``:
        only shards containing touched rows are copied (host memcpy of
        O(touched shards), never the device link). ``n_rows`` grows the
        logical size inside the same bucket."""
        idx = np.asarray(idx, dtype=np.int64).ravel()
        values = np.asarray(values, dtype=np.float32)
        which = self._which_shard(idx)
        offs = np.asarray(self.offsets, dtype=np.int64)
        shards = list(self.shards)
        for s in np.unique(which):
            sel = which == s
            patched = shards[s].copy()
            patched[idx[sel] - offs[s]] = values[sel]
            shards[s] = patched
        return ShardedTable(shards, self.offsets,
                            self.n_rows if n_rows is None else n_rows,
                            self.padded_rows, self.n_shards)

    def grown(self, n_rows: int, padded_rows: int) -> "ShardedTable":
        """Re-partition for a bucket promotion (``padded_rows`` grew):
        shard boundaries move, so this is the one O(table) host
        reshuffle — paid once per rung of the table ladder (at most
        12.5% of vocabulary growth from 2^16 rows up, 2x below), like
        the compile the promotion also pays. Single-process only (a
        follower holding a subset of shards cannot re-partition
        without cross-process data movement — refuse rather than
        silently misattribute rows)."""
        self._require_full_coverage("grown")
        full = np.concatenate(self.shards, axis=0)
        grown = np.zeros((padded_rows, self.rank), dtype=np.float32)
        grown[:full.shape[0]] = full
        out = ShardedTable.from_host(grown, self.n_shards,
                                     padded_rows=padded_rows)
        return ShardedTable(out.shards, out.offsets, n_rows,
                            padded_rows, self.n_shards)

    # -- device residency ----------------------------------------------------
    def device(self, mesh, target_rows: Optional[int] = None,
               record_upload=None):
        """The model-sharded device array for this table: the attached
        resident handle when it is still valid for ``mesh`` (and the
        requested row bucket), else an upload of the host shards (each
        process materializes only its addressable slices —
        ``make_array_from_callback``). The upload is the COLD path
        (restart, mesh change); steady-state ticks and serving always
        hit the handle.

        ``target_rows`` > ``padded_rows`` uploads AT the larger row
        bucket, zero-filling the extra rows inside the upload callback
        — the serve path's way to cover a table whose own padding is
        below its covering sharded bucket (e.g. a just-trained table)
        WITHOUT mutating the published model or re-partitioning the
        host mirrors (real promotions — where the mirrors must follow
        because the publish patches them — stay ``grown()``'s job, on
        the fold tick)."""
        target = max(int(target_rows or 0), self.padded_rows)
        if target % self.n_shards:
            raise ValueError(
                f"target_rows {target} not divisible by "
                f"{self.n_shards} shards")

        def _valid(dev):
            return dev is not None and dev.shape[0] == target \
                and getattr(dev.sharding, "mesh", None) == mesh.mesh

        dev = self._dev
        if _valid(dev):
            return dev
        with self._dev_lock:
            dev = self._dev       # a racing thread may have uploaded
            if _valid(dev):
                return dev
            from predictionio_tpu.utils.device_cache import \
                check_table_budget
            check_table_budget(
                (target // self.n_shards) * self.rank
                * self.dtype.itemsize, table=repr(self))
            import jax
            sharding = mesh.model_sharded(2)
            shape = (target, self.rank)

            def _cb(index):
                rows = index[0]
                start = rows.start or 0
                stop = rows.stop if rows.stop is not None else shape[0]
                return self._host_rows(start, stop)

            dev = jax.make_array_from_callback(shape, sharding, _cb)
            if record_upload is None:
                from predictionio_tpu.obs import jaxmon
                record_upload = jaxmon.record_h2d
            record_upload(target * self.rank * self.dtype.itemsize)
            self._dev = dev
            return dev

    def _host_rows(self, start: int, stop: int) -> np.ndarray:
        """Contiguous global rows from the addressable shard slices;
        rows past ``padded_rows`` (a larger upload bucket's tail) are
        zeros."""
        parts = []
        need = start
        for off, sh in zip(self.offsets, self.shards):
            lo, hi = max(start, off), min(stop, off + sh.shape[0])
            if lo < hi:
                if lo != need:
                    break
                parts.append(sh[lo - off:hi - off])
                need = hi
        if need < stop and need >= self.padded_rows:
            parts.append(np.zeros((stop - need, self.rank),
                                  dtype=np.float32))
            need = stop
        if need != stop:
            raise IndexError(
                f"rows [{start}, {stop}) not covered by addressable "
                f"shards (offsets {self.offsets})")
        return parts[0] if len(parts) == 1 else np.concatenate(parts)

    def attach_device(self, dev) -> "ShardedTable":
        """Bind the tick's final device array as the resident fast
        path (mutates only the transient handle — the host mirror and
        identity of ``self`` are unchanged)."""
        self._dev = dev
        return self

    def drop_device(self) -> int:
        """Release the transient device handle (tenant eviction,
        tenancy/budget.py): the host mirrors stay the source of truth
        and the next :meth:`device` call re-uploads through the
        budget-checked cold path. An in-flight dispatch that already
        closed over the handle keeps its own reference — dropping here
        only stops pinning HBM for future calls. Returns the per-device
        bytes the handle was pinning (0 when none was resident)."""
        with self._dev_lock:
            freed = self.device_nbytes()
            self._dev = None
        return freed

    def device_nbytes(self) -> int:
        """Per-device bytes pinned by the resident handle (0 when not
        resident) — the tenancy budget manager's sharded-table sizer."""
        dev = self._dev
        if dev is None:
            return 0
        from predictionio_tpu.utils.device_cache import _device_nbytes
        return _device_nbytes(dev)

    # -- pickling ------------------------------------------------------------
    def __getstate__(self):
        state = dict(self.__dict__)
        state["_dev"] = None       # device handles never serialize
        state.pop("_dev_lock", None)
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._dev = None
        self._dev_lock = threading.Lock()


def sharding_meta(models) -> Optional[dict]:
    """``{"layout": "model", "shards": N}`` when any model in the set
    carries sharded factor tables — the lineage tag the registry
    publishes so `pio status` / a restarted follower can tell the
    layouts apart without deserializing the blob."""
    for m in models:
        for obj in (m, getattr(m, "als", None)):
            if obj is None:
                continue
            for attr in ("user_factors", "item_factors"):
                t = getattr(obj, attr, None)
                if is_sharded(t):
                    return {"layout": "model", "shards": t.n_shards}
    return None
