"""Touched-row-invalidated top-k result cache (ISSUE 14 tentpole c).

Serving traffic is zipfian: a small set of hot users asks for the same
ranking over and over, and between fold ticks the answer is a pure
function of (query, deployed model). This cache stores the FINAL
serialized response bytes per canonical query, so a hit skips the
whole pipeline — queue, batch formation, supplement, device dispatch,
readback, post-process AND serialization — and returns bytes the HTTP
layer writes straight to the socket.

Invalidation contract (the part that makes this safe under online
updates): fold-tick publishes know exactly which user/item rows they
re-solved (EntityDelta -> touched entity ids; sharded publishes patch
the same rows through ShardedTable.with_rows), so a hot-swap from a
fold tick drops ONLY the entries registered under a touched entity —
cached rankings for untouched users survive the swap byte-identical.
Any model change whose touched set is unknown (full /reload, canary
stage/promote/rollback, an operator swap without lineage) clears the
whole cache. Within a fold tick the untouched users' factor rows are
bit-identical by construction (touched-row solves never move other
rows), so a surviving entry equals a recompute against its own row;
item-row movement can perturb an untouched user's ranking by at most
the touched rows' score deltas — the documented staleness trade, on
by default and bounded by the fold cadence. ``PIO_SERVE_CACHE=off``
(or ``ServerConfig.result_cache=False``) disables;
``PIO_SERVE_CACHE_STRICT=1`` additionally drops every entry whose
CACHED RESULT contains a touched item id (exact-result freshness at
the cost of broader invalidation).

Budget: hard entry and byte caps, LRU eviction, O(1) per lookup.
Telemetry: ``pio_serve_cache_{hits,misses,invalidations}_total``,
entry/byte gauges, eviction counter.
"""

from __future__ import annotations

import collections
import json
import os
import threading
from typing import Dict, Iterable, List, Optional, Tuple

from predictionio_tpu.obs import TRACER

#: query-dict fields that name cacheable entities, and the tag prefix
#: their values register under (the invalidation join key)
_ENTITY_FIELDS = (("user", "user"), ("item", "item"), ("items", "item"))

#: namespace separator for tenant-prefixed keys/tags (ISSUE 15
#: satellite): a control character no JSON-canonical query key or
#: entity id produced by ``query_key``/``entity_tags`` can contain, so
#: a namespaced key can never collide with (or alias) an unnamespaced
#: one
NS_SEP = "\x1f"


def cache_enabled() -> bool:
    return os.environ.get("PIO_SERVE_CACHE", "").lower() not in (
        "off", "0", "false", "no")


def strict_items() -> bool:
    """Strict mode: entries whose cached result CONTAINS a touched
    item are dropped too (exact freshness; broader invalidation)."""
    return os.environ.get("PIO_SERVE_CACHE_STRICT", "").lower() in (
        "1", "on", "true", "yes")


def query_key(query_dict: dict) -> Optional[str]:
    """Canonical cache key for one query body; None = uncacheable
    (non-JSON-canonical content)."""
    try:
        return json.dumps(query_dict, sort_keys=True,
                          separators=(",", ":"))
    except (TypeError, ValueError):
        return None


def query_entities(query_dict: dict) -> Tuple[str, ...]:
    """The entity tags a query's cached result registers under —
    exactly the ids a fold tick names when it touches the entity."""
    tags: List[str] = []
    for field, prefix in _ENTITY_FIELDS:
        v = query_dict.get(field)
        if v is None:
            continue
        if isinstance(v, (list, tuple)):
            tags.extend(f"{prefix}:{x}" for x in v)
        else:
            tags.append(f"{prefix}:{v}")
    return tuple(tags)


def entity_tags(touched: Dict[str, Iterable]) -> List[str]:
    """{"user": ids, "item": ids} -> flat tag list."""
    out: List[str] = []
    for kind, ids in (touched or {}).items():
        out.extend(f"{kind}:{i}" for i in ids)
    return out


class _Entry:
    __slots__ = ("body", "entities", "result_items", "nbytes", "raw")

    def __init__(self, body: bytes, entities: Tuple[str, ...],
                 result_items: Tuple[str, ...],
                 raw: Optional[bytes] = None):
        self.body = body
        self.entities = entities
        self.result_items = result_items
        self.nbytes = len(body)
        # the exact request bytes that produced this entry (one per
        # entry): a repeat client resends byte-identical bodies, so
        # the hot-path lookup can skip JSON parse + canonicalization
        self.raw = raw


class ResultCache:
    """Thread-safe LRU of serialized response bytes, indexed by entity
    tag for O(touched) fold-swap invalidation."""

    def __init__(self, max_entries: int = 8192,
                 max_bytes: int = 64 << 20, metrics=None):
        self.max_entries = int(max_entries)
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()
        self._entries: "collections.OrderedDict[str, _Entry]" = \
            collections.OrderedDict()
        # exact request bytes -> canonical key (one alias per entry):
        # the zero-parse hot-path lookup for repeat clients
        self._raw_alias: Dict[bytes, str] = {}
        # entity tag -> keys whose cached entry registered it
        self._by_entity: Dict[str, set] = {}
        self._bytes = 0
        #: bumped by every invalidation — the store-time freshness
        #: fence: a caller snapshots it before computing and passes it
        #: to put(); a mismatch (a swap landed mid-compute) refuses the
        #: store, so a result reflecting pre-swap models can never be
        #: cached after its invalidation already ran
        self.generation = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        # per-reason invalidation counts (fold_swap / full / budget ...)
        self.invalidations: Dict[str, int] = {}
        if metrics is not None:
            metrics.counter_func(
                "pio_serve_cache_hits_total",
                "Queries answered from the serving result cache "
                "(skipping batch formation, dispatch and serialization)",
                lambda: self.hits)
            metrics.counter_func(
                "pio_serve_cache_misses_total",
                "Cacheable queries that missed the result cache",
                lambda: self.misses)
            metrics.counter_func(
                "pio_serve_cache_invalidations_total",
                "Cache entries dropped by invalidation, by reason "
                "(fold_swap = touched-entity drop, full = whole-cache "
                "clear on an unattributed model change)",
                lambda: [({"reason": r}, n) for r, n in
                         sorted(self.invalidations.items())]
                or [(None, 0)])
            metrics.counter_func(
                "pio_serve_cache_evictions_total",
                "Entries evicted by the entry/byte budget (LRU)",
                lambda: self.evictions)
            metrics.gauge_func(
                "pio_serve_cache_entries",
                "Entries resident in the serving result cache",
                lambda: len(self._entries))
            metrics.gauge_func(
                "pio_serve_cache_bytes",
                "Serialized bytes resident in the serving result cache",
                lambda: self._bytes)

    # -- lookup/store -------------------------------------------------------
    def get(self, key: Optional[str]) -> Optional[bytes]:
        if key is None:
            return None
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return e.body

    def get_raw(self, raw: bytes) -> Optional[bytes]:
        """Exact-request-bytes lookup — the zero-parse hot path: a
        repeat client resends byte-identical bodies, so a hit here
        costs two dict probes and NO JSON parse/canonicalization.
        None on miss (the caller falls back to the canonical key and
        counts the miss there — a raw miss is not a cache miss)."""
        with self._lock:
            key = self._raw_alias.get(raw)
            if key is None:
                return None
            e = self._entries.get(key)
            if e is None:
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return e.body

    def put(self, key: Optional[str], body: bytes,
            entities: Tuple[str, ...],
            result_items: Tuple[str, ...] = (),
            generation: Optional[int] = None,
            raw: Optional[bytes] = None) -> bool:
        """Store one serialized response under its entity tags.
        ``result_items``: the item ids the response ranks — consulted
        only in strict mode. ``generation``: the caller's pre-compute
        snapshot of :attr:`generation`; a mismatch refuses the store.
        ``raw``: the exact request bytes, registered as the zero-parse
        alias for :meth:`get_raw`. Oversized bodies are refused (one
        giant response must not evict the whole hot set)."""
        if key is None or len(body) > self.max_bytes // 4:
            return False
        with TRACER.region("cache.put") as span, self._lock:
            if generation is not None and generation != self.generation:
                return False
            evicted0 = self.evictions
            old = self._entries.pop(key, None)
            if old is not None:
                self._unindex(key, old)
                self._bytes -= old.nbytes
            e = _Entry(bytes(body), entities, tuple(result_items),
                       raw=bytes(raw) if raw is not None else None)
            self._entries[key] = e
            self._bytes += e.nbytes
            if e.raw is not None:
                self._raw_alias[e.raw] = key
            for tag in entities:
                self._by_entity.setdefault(tag, set()).add(key)
            while (len(self._entries) > self.max_entries
                   or self._bytes > self.max_bytes):
                k, victim = self._entries.popitem(last=False)
                self._unindex(k, victim)
                self._bytes -= victim.nbytes
                self.evictions += 1
            if span is not None and self.evictions > evicted0:
                span.attrs["evictions"] = self.evictions - evicted0
        return True

    def _unindex(self, key: str, e: _Entry):
        if e.raw is not None and self._raw_alias.get(e.raw) == key:
            self._raw_alias.pop(e.raw, None)
        for tag in e.entities:
            keys = self._by_entity.get(tag)
            if keys is not None:
                keys.discard(key)
                if not keys:
                    self._by_entity.pop(tag, None)

    # -- invalidation -------------------------------------------------------
    def invalidate_entities(self, tags: Iterable[str],
                            reason: str = "fold_swap") -> int:
        """Drop exactly the entries registered under any touched tag
        (plus, in strict mode, entries whose cached result contains a
        touched item id). O(touched + dropped), never a full scan —
        untouched entries are not even visited.

        Tags may carry a tenant namespace prefix (``<ns>\\x1f<tag>``,
        :class:`TenantResultCache`): the strict-mode result-item join
        then only considers entries in the SAME namespace — tenant A's
        fold tick touching item i must never drop tenant B's cached
        rankings of a same-named item."""
        tags = list(tags)
        strict = strict_items()
        # touched item ids per namespace ("" = unnamespaced keys)
        touched_by_ns: Dict[str, set] = {}
        if strict:
            for t in tags:
                ns, sep, rest = t.rpartition(NS_SEP)
                if rest.startswith("item:"):
                    touched_by_ns.setdefault(
                        ns + sep, set()).add(rest.split(":", 1)[1])
        with self._lock:
            self.generation += 1
            doomed = set()
            for tag in tags:
                doomed |= self._by_entity.get(tag, set())
            if touched_by_ns:
                for k, e in self._entries.items():
                    for nsp, items in touched_by_ns.items():
                        if nsp:
                            if not k.startswith(nsp):
                                continue
                        elif NS_SEP in k:
                            continue
                        if items.intersection(e.result_items):
                            doomed.add(k)
                            break
            for k in doomed:
                e = self._entries.pop(k, None)
                if e is None:
                    continue
                self._unindex(k, e)
                self._bytes -= e.nbytes
            if doomed:
                self.invalidations[reason] = \
                    self.invalidations.get(reason, 0) + len(doomed)
            return len(doomed)

    def invalidate_prefix(self, prefix: str, reason: str = "full") -> int:
        """Drop every entry whose key starts with ``prefix`` — the
        tenant-scoped analog of :meth:`invalidate_all` on a shared
        cache (one tenant's /reload must not clear its neighbors' hot
        sets). O(entries) like invalidate_all, paid only on
        unattributed model changes."""
        with self._lock:
            self.generation += 1
            doomed = [k for k in self._entries if k.startswith(prefix)]
            for k in doomed:
                e = self._entries.pop(k, None)
                if e is None:
                    continue
                self._unindex(k, e)
                self._bytes -= e.nbytes
            if doomed:
                self.invalidations[reason] = \
                    self.invalidations.get(reason, 0) + len(doomed)
            return len(doomed)

    def invalidate_all(self, reason: str = "full") -> int:
        with self._lock:
            self.generation += 1
            n = len(self._entries)
            self._entries.clear()
            self._raw_alias.clear()
            self._by_entity.clear()
            self._bytes = 0
            if n:
                self.invalidations[reason] = \
                    self.invalidations.get(reason, 0) + n
            return n

    # -- introspection ------------------------------------------------------
    def stats(self) -> dict:
        with self._lock:
            total = self.hits + self.misses
            return {
                "entries": len(self._entries),
                "bytes": self._bytes,
                "maxEntries": self.max_entries,
                "maxBytes": self.max_bytes,
                "hits": self.hits,
                "misses": self.misses,
                "hitRate": (self.hits / total if total else None),
                "evictions": self.evictions,
                "invalidations": dict(self.invalidations),
            }


class TenantResultCache:
    """Tenant-namespaced view over a shared :class:`ResultCache`
    (ISSUE 15 satellite bugfix). The underlying cache keyed entries on
    request bytes / canonical query JSON / entity ids ONLY — correct
    for one engine per process, but the moment a serving host packs
    many engines, two tenants sending byte-identical queries (every
    template shares the ``{"user": ..., "num": ...}`` wire shape) would
    collide: tenant B could be answered with tenant A's cached ranking.
    Every canonical key, exact-request-bytes alias and entity tag is
    prefixed here with the tenant id + ``NS_SEP``, so cross-tenant hits
    are structurally impossible while all tenants still share ONE
    entry/byte budget and LRU order (a hot tenant can use the whole
    pool when its neighbors are idle)."""

    def __init__(self, inner: ResultCache, tenant: str):
        tenant = str(tenant)
        if NS_SEP in tenant:
            raise ValueError("tenant id must not contain NS_SEP")
        self.inner = inner
        self.tenant = tenant
        self._kp = tenant + NS_SEP
        self._rp = self._kp.encode("utf-8")
        # per-NAMESPACE store-time freshness fence: only THIS tenant's
        # invalidations bump it. Proxying the shared inner counter
        # would let tenant A's fold cadence refuse tenant B's
        # concurrent stores (nothing in B's namespace changed) —
        # cross-tenant hit-rate interference the isolation contract
        # forbids. Int read/write under the GIL; the worst race is one
        # refused store, the safe direction.
        self._generation = 0

    @property
    def generation(self) -> int:
        return self._generation

    def get(self, key: Optional[str]) -> Optional[bytes]:
        return self.inner.get(None if key is None else self._kp + key)

    def get_raw(self, raw: bytes) -> Optional[bytes]:
        return self.inner.get_raw(self._rp + raw)

    def put(self, key: Optional[str], body: bytes,
            entities: Tuple[str, ...],
            result_items: Tuple[str, ...] = (),
            generation: Optional[int] = None,
            raw: Optional[bytes] = None) -> bool:
        # the fence is enforced HERE against the per-tenant counter;
        # the inner cache's (cross-tenant) generation is bypassed
        if generation is not None and generation != self._generation:
            return False
        return self.inner.put(
            None if key is None else self._kp + key, body,
            tuple(self._kp + t for t in entities),
            result_items=result_items, generation=None,
            raw=None if raw is None else self._rp + raw)

    def invalidate_entities(self, tags: Iterable[str],
                            reason: str = "fold_swap") -> int:
        self._generation += 1
        return self.inner.invalidate_entities(
            [self._kp + t for t in tags], reason=reason)

    def invalidate_all(self, reason: str = "full") -> int:
        # tenant-scoped: this tenant's /reload or canary event clears
        # ONLY its namespace; the neighbors' hot sets survive
        self._generation += 1
        return self.inner.invalidate_prefix(self._kp, reason=reason)

    def stats(self) -> dict:
        return dict(self.inner.stats(), tenant=self.tenant, shared=True)
