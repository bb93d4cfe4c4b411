"""Bidirectional id maps: string entity ids <-> dense integer indices.

Rebuilds the reference's ``BiMap``/``EntityMap``
(reference: data/src/main/scala/io/prediction/data/storage/BiMap.scala:25-165,
EntityMap.scala:27-98). This is SURVEY.md hard-part #1: every TPU kernel
indexes embedding tables by dense int32 row, so the string->index build must
be deterministic and the serve-time lookup O(1).

Design: ids are assigned by first-occurrence order over a deterministic
iteration (``string_int``) or by sorted order (``string_int_sorted``) for
cross-host determinism without coordination. Backed by plain dicts +
a numpy array for the inverse, so device-side gathers take the int index
directly and host-side lookup is one dict probe.
"""

from __future__ import annotations

from typing import Dict, Generic, Hashable, Iterable, Iterator, List, Mapping, TypeVar

import numpy as np

K = TypeVar("K", bound=Hashable)
V = TypeVar("V", bound=Hashable)


class BiMap(Generic[K, V]):
    """Immutable one-to-one map with O(1) forward and inverse lookup."""

    __slots__ = ("_fwd", "_inv")

    def __init__(self, forward: Mapping[K, V]):
        fwd = dict(forward)
        inv: Dict[V, K] = {}
        for k, v in fwd.items():
            if v in inv:
                raise ValueError(f"BiMap values must be unique; duplicate {v!r}")
            inv[v] = k
        self._fwd = fwd
        self._inv = inv

    # -- forward ------------------------------------------------------------
    def __getitem__(self, key: K) -> V:
        return self._fwd[key]

    def __contains__(self, key: K) -> bool:
        return key in self._fwd

    def __len__(self) -> int:
        return len(self._fwd)

    def __iter__(self) -> Iterator[K]:
        return iter(self._fwd)

    def get(self, key: K, default=None):
        return self._fwd.get(key, default)

    def contains(self, key: K) -> bool:
        return key in self._fwd

    def keys(self):
        return self._fwd.keys()

    def values(self):
        return self._fwd.values()

    def items(self):
        return self._fwd.items()

    def to_map(self) -> Dict[K, V]:
        return dict(self._fwd)

    # -- inverse ------------------------------------------------------------
    def inverse(self) -> "BiMap[V, K]":
        return BiMap(self._inv)

    def inverse_get(self, value: V, default=None):
        return self._inv.get(value, default)

    def take(self, keys: Iterable[K]) -> "BiMap[K, V]":
        """Sub-map restricted to ``keys`` (BiMap.scala `take`)."""
        return BiMap({k: self._fwd[k] for k in keys})

    def __eq__(self, other: object) -> bool:
        return isinstance(other, BiMap) and self._fwd == other._fwd

    def __repr__(self) -> str:
        return f"BiMap({self._fwd!r})"

    # -- constructors (BiMap.scala:102-165) ---------------------------------
    @staticmethod
    def string_int(keys: Iterable[str]) -> "BiMap[str, int]":
        """Dense 0..n-1 indices by first-occurrence order (deterministic for a
        deterministic input order; use string_int_sorted for order-free
        determinism)."""
        fwd: Dict[str, int] = {}
        for k in keys:
            if k not in fwd:
                fwd[k] = len(fwd)
        return BiMap(fwd)

    @staticmethod
    def string_int_sorted(keys: Iterable[str]) -> "BiMap[str, int]":
        """Dense indices by lexicographic order — deterministic regardless of
        input order, so every host builds the identical vocabulary."""
        uniq = sorted(set(keys))
        return BiMap({k: i for i, k in enumerate(uniq)})

    @staticmethod
    def string_long(keys: Iterable[str]) -> "BiMap[str, int]":
        return BiMap.string_int(keys)

    @staticmethod
    def string_double(keys: Iterable[str]) -> "BiMap[str, float]":
        fwd: Dict[str, float] = {}
        for k in keys:
            if k not in fwd:
                fwd[k] = float(len(fwd))
        return BiMap(fwd)


class EntityIdIxMap:
    """entityId <-> dense row index, with a numpy inverse table for vectorized
    index->id translation (EntityMap.scala:27-63)."""

    def __init__(self, id_to_ix: BiMap):
        self._bimap = id_to_ix
        n = len(id_to_ix)
        ids: List[str] = [""] * n
        for k, v in id_to_ix.items():
            ids[int(v)] = k
        self._ids = np.array(ids, dtype=object)

    @staticmethod
    def build(keys: Iterable[str], sort: bool = True) -> "EntityIdIxMap":
        bm = (BiMap.string_int_sorted(keys) if sort else BiMap.string_int(keys))
        return EntityIdIxMap(bm)

    @staticmethod
    def build_with_indices(ids: np.ndarray
                           ) -> "tuple[EntityIdIxMap, np.ndarray]":
        """Vectorized vocabulary build: one np.unique pass yields both the
        sorted-order map (same order as ``build``) and the dense index of
        every input row — the ingest-scale replacement for building the map
        and then re-translating 20M ids through a Python dict."""
        arr = np.asarray(ids)
        if arr.dtype == object:
            arr = arr.astype(str)
        uniq, inv = np.unique(arr, return_inverse=True)
        bm = BiMap({str(k): i for i, k in enumerate(uniq)})
        return EntityIdIxMap(bm), inv.astype(np.int32)

    def __getitem__(self, entity_id: str) -> int:
        return self._bimap[entity_id]

    def get(self, entity_id: str, default: int = -1) -> int:
        return self._bimap.get(entity_id, default)

    def __contains__(self, entity_id: str) -> bool:
        return entity_id in self._bimap

    def __len__(self) -> int:
        return len(self._bimap)

    def id_of(self, ix: int) -> str:
        return str(self._ids[ix])

    def ids_of(self, ixs) -> List[str]:
        return [str(x) for x in self._ids[np.asarray(ixs, dtype=np.int64)]]

    def to_indices(self, entity_ids: Iterable[str]) -> np.ndarray:
        """id->index per element via dict probes; unknown ids map to -1.
        The probes run in C (`map` over the dict's own `get`): a list of
        tens of thousands of ids (the e-commerce template's unavailable
        items, resolved on the serving path at every re-set) costs a few
        milliseconds, not tens."""
        found = list(map(self._bimap._fwd.get, entity_ids))
        return np.array([-1 if ix is None else ix for ix in found]
                        if None in found else found, dtype=np.int32)

    def to_indices_array(self, ids: np.ndarray) -> np.ndarray:
        """Vectorized id->index for numpy id arrays (unknowns -> -1):
        binary search against a lazily-built sorted view of the key
        table. Works at full speed for grown (append-ordered, unsorted)
        maps too — the online fold-in path translates the whole corpus
        through this every tick, so a per-row dict-probe fallback would
        cost ~20M Python probes per side at ML-20M scale."""
        arr = np.asarray(ids)
        if arr.dtype == object:
            arr = arr.astype(str)
        keys = self._ids.astype(str)
        if len(keys) == 0 or arr.size == 0:
            return np.full(arr.shape, -1, dtype=np.int32)
        cache = getattr(self, "_sorted_view", None)
        if cache is None or len(cache[0]) != len(keys):
            order = np.argsort(keys)
            cache = (keys[order], order.astype(np.int32))
            self._sorted_view = cache
        sorted_keys, order = cache
        pos = np.searchsorted(sorted_keys, arr)
        pos_safe = np.clip(pos, 0, len(sorted_keys) - 1)
        hit = sorted_keys[pos_safe] == arr
        return np.where(hit, order[pos_safe], -1).astype(np.int32)

    @property
    def bimap(self) -> BiMap:
        return self._bimap

    # -- online growth (fold-in path) ---------------------------------------
    def grow(self, new_ids: Iterable[str]
             ) -> "tuple[EntityIdIxMap, np.ndarray]":
        """Append unseen ids AFTER the existing vocabulary, preserving every
        existing dense index — the invariant the online fold-in path depends
        on: factor-table row i must keep meaning the same entity across
        model versions, so grown tables are old tables plus appended rows.

        Returns (grown_map, appended_indices) where ``appended_indices`` are
        the dense indices assigned to the ids that were actually new, in
        first-occurrence order of ``new_ids``. Already-known ids are
        ignored. When nothing is new, returns (self, empty).

        Note the grown map is generally NOT in sorted order anymore;
        ``to_indices_array`` detects that and falls back to dict probes."""
        fresh: List[str] = []
        seen = set()
        for e in new_ids:
            e = str(e)
            if e not in self._bimap and e not in seen:
                seen.add(e)
                fresh.append(e)
        if not fresh:
            return self, np.empty(0, dtype=np.int32)
        base = len(self._bimap)
        fwd = dict(self._bimap.items())
        for i, e in enumerate(fresh):
            fwd[e] = base + i
        grown = EntityIdIxMap(BiMap(fwd))
        return grown, np.arange(base, base + len(fresh), dtype=np.int32)


class EntityMap(Generic[V]):
    """entityId-keyed data with dense-index access (EntityMap.scala:65-98)."""

    def __init__(self, data: Mapping[str, V], ix_map: EntityIdIxMap = None):
        self._data = dict(data)
        self._ix = ix_map or EntityIdIxMap.build(self._data.keys())

    def __getitem__(self, entity_id: str) -> V:
        return self._data[entity_id]

    def __contains__(self, entity_id: str) -> bool:
        return entity_id in self._data

    def __len__(self) -> int:
        return len(self._data)

    def get_by_index(self, ix: int) -> V:
        return self._data[self._ix.id_of(ix)]

    @property
    def ix_map(self) -> EntityIdIxMap:
        return self._ix
