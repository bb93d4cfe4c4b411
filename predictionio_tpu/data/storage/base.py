"""Metadata records and abstract DAO / event-store interfaces.

Rebuilds the reference's metadata case classes and DAO traits
(reference: data/src/main/scala/io/prediction/data/storage/{Apps,AccessKeys,
Channels,EngineInstances,EngineManifests,EvaluationInstances,Models}.scala)
and the event-store traits ``LEvents`` (LEvents.scala:37) / ``PEvents``
(PEvents.scala:35). In the TPU build there is one synchronous `Events`
interface; bulk training reads return host numpy-friendly iterators that the
parallel ingest layer (predictionio_tpu.parallel.dataset) shards onto the
device mesh — the analog of PEvents returning an RDD.
"""

from __future__ import annotations

import abc
import datetime as _dt
import re
from dataclasses import dataclass, field, replace
from typing import Dict, Iterable, Iterator, List, Optional, Sequence

from predictionio_tpu.data.aggregator import aggregate_properties
from predictionio_tpu.data.datamap import PropertyMap
from predictionio_tpu.data.event import (Event, from_millis,
                                         to_millis as _millis)

# Sentinel for "filter requires this field to be absent" (the reference's
# Option[Option[String]] = Some(None) case in LEvents.futureFind).
ABSENT = object()

#: default rows per chunk for ``Events.find_columnar_chunked`` — sized so
#: a chunk's decoded columns stay comfortably inside CPU cache pressure
#: while still amortizing per-window scan overhead (~256k rows ≈ 10–25 MB
#: of wire columns).
DEFAULT_CHUNK_ROWS = 262_144


class SQLError(Exception):
    """Server-reported SQL error, dialect-neutral: wire clients (pgwire,
    mywire) subclass it so the shared DAO layer can branch on semantic
    conditions without knowing the backend (the reference's JDBC backend
    serves both PG and MySQL through one DAO set —
    data/.../jdbc/StorageClient.scala:33-54)."""

    @property
    def unique_violation(self) -> bool:
        return False


# ---------------------------------------------------------------------------
# Metadata records
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class App:
    id: int
    name: str
    description: Optional[str] = None


@dataclass(frozen=True)
class AccessKey:
    key: str
    appid: int
    events: Sequence[str] = ()  # whitelist; empty = all events allowed


_CHANNEL_NAME_RE = re.compile(r"^[a-zA-Z0-9-]{1,16}$")


@dataclass(frozen=True)
class Channel:
    id: int
    name: str  # unique within an app
    appid: int

    NAME_CONSTRAINT = "Only alphanumeric and - characters are allowed and max length is 16."

    def __post_init__(self):
        if not Channel.is_valid_name(self.name):
            raise ValueError(
                f"Invalid channel name: {self.name}. {Channel.NAME_CONSTRAINT}")

    @staticmethod
    def is_valid_name(name: str) -> bool:
        return bool(_CHANNEL_NAME_RE.match(name))


@dataclass(frozen=True)
class EngineInstance:
    """One training run record (EngineInstances.scala:43-58)."""
    id: str
    status: str
    start_time: _dt.datetime
    end_time: _dt.datetime
    engine_id: str
    engine_version: str
    engine_variant: str
    engine_factory: str
    batch: str = ""
    env: Dict[str, str] = field(default_factory=dict)
    spark_conf: Dict[str, str] = field(default_factory=dict)
    data_source_params: str = ""
    preparator_params: str = ""
    algorithms_params: str = ""
    serving_params: str = ""

    def with_(self, **kw) -> "EngineInstance":
        return replace(self, **kw)


@dataclass(frozen=True)
class EngineManifest:
    id: str
    version: str
    name: str
    description: Optional[str] = None
    files: Sequence[str] = ()
    engine_factory: str = ""


@dataclass(frozen=True)
class EvaluationInstance:
    id: str = ""
    status: str = ""
    start_time: _dt.datetime = field(default_factory=lambda: _dt.datetime.now(_dt.timezone.utc))
    end_time: _dt.datetime = field(default_factory=lambda: _dt.datetime.now(_dt.timezone.utc))
    evaluation_class: str = ""
    engine_params_generator_class: str = ""
    batch: str = ""
    env: Dict[str, str] = field(default_factory=dict)
    spark_conf: Dict[str, str] = field(default_factory=dict)
    evaluator_results: str = ""
    evaluator_results_html: str = ""
    evaluator_results_json: str = ""

    def with_(self, **kw) -> "EvaluationInstance":
        return replace(self, **kw)


@dataclass(frozen=True)
class Model:
    """Serialized trained model blob (Models.scala:30)."""
    id: str
    models: bytes


# ---------------------------------------------------------------------------
# Metadata DAO interfaces
# ---------------------------------------------------------------------------

class Apps(abc.ABC):
    @abc.abstractmethod
    def insert(self, app: App) -> Optional[int]:
        """Insert; returns generated id when app.id == 0."""

    @abc.abstractmethod
    def get(self, app_id: int) -> Optional[App]: ...

    @abc.abstractmethod
    def get_by_name(self, name: str) -> Optional[App]: ...

    @abc.abstractmethod
    def get_all(self) -> List[App]: ...

    @abc.abstractmethod
    def update(self, app: App) -> bool: ...

    @abc.abstractmethod
    def delete(self, app_id: int) -> bool: ...


class AccessKeys(abc.ABC):
    @abc.abstractmethod
    def insert(self, k: AccessKey) -> Optional[str]:
        """Insert; generates a random key when k.key is empty."""

    @abc.abstractmethod
    def get(self, key: str) -> Optional[AccessKey]: ...

    @abc.abstractmethod
    def get_all(self) -> List[AccessKey]: ...

    @abc.abstractmethod
    def get_by_app_id(self, app_id: int) -> List[AccessKey]: ...

    @abc.abstractmethod
    def update(self, k: AccessKey) -> bool: ...

    @abc.abstractmethod
    def delete(self, key: str) -> bool: ...


class Channels(abc.ABC):
    @abc.abstractmethod
    def insert(self, channel: Channel) -> Optional[int]: ...

    @abc.abstractmethod
    def get(self, channel_id: int) -> Optional[Channel]: ...

    @abc.abstractmethod
    def get_by_app_id(self, app_id: int) -> List[Channel]: ...

    @abc.abstractmethod
    def delete(self, channel_id: int) -> bool: ...


class EngineInstances(abc.ABC):
    @abc.abstractmethod
    def insert(self, i: EngineInstance) -> str: ...

    @abc.abstractmethod
    def get(self, instance_id: str) -> Optional[EngineInstance]: ...

    @abc.abstractmethod
    def get_all(self) -> List[EngineInstance]: ...

    @abc.abstractmethod
    def get_latest_completed(self, engine_id: str, engine_version: str,
                             engine_variant: str) -> Optional[EngineInstance]: ...

    @abc.abstractmethod
    def get_completed(self, engine_id: str, engine_version: str,
                      engine_variant: str) -> List[EngineInstance]: ...

    @abc.abstractmethod
    def update(self, i: EngineInstance) -> bool: ...

    @abc.abstractmethod
    def delete(self, instance_id: str) -> bool: ...


class EngineManifests(abc.ABC):
    @abc.abstractmethod
    def insert(self, m: EngineManifest) -> None: ...

    @abc.abstractmethod
    def get(self, manifest_id: str, version: str) -> Optional[EngineManifest]: ...

    @abc.abstractmethod
    def get_all(self) -> List[EngineManifest]: ...

    @abc.abstractmethod
    def update(self, m: EngineManifest, upsert: bool = False) -> None: ...

    @abc.abstractmethod
    def delete(self, manifest_id: str, version: str) -> bool: ...


class EvaluationInstances(abc.ABC):
    @abc.abstractmethod
    def insert(self, i: EvaluationInstance) -> str: ...

    @abc.abstractmethod
    def get(self, instance_id: str) -> Optional[EvaluationInstance]: ...

    @abc.abstractmethod
    def get_all(self) -> List[EvaluationInstance]: ...

    @abc.abstractmethod
    def get_completed(self) -> List[EvaluationInstance]: ...

    @abc.abstractmethod
    def update(self, i: EvaluationInstance) -> bool: ...

    @abc.abstractmethod
    def delete(self, instance_id: str) -> bool: ...


class Models(abc.ABC):
    @abc.abstractmethod
    def insert(self, model: Model) -> None: ...

    @abc.abstractmethod
    def get(self, model_id: str) -> Optional[Model]: ...

    @abc.abstractmethod
    def delete(self, model_id: str) -> bool: ...


# ---------------------------------------------------------------------------
# Event store interface (LEvents + PEvents unified, synchronous)
# ---------------------------------------------------------------------------

class Events(abc.ABC):
    """Event CRUD + query per (appId, channelId) namespace.

    Covers the reference's LEvents (init/remove/insert/get/delete/find,
    LEvents.scala:50-164) and the bulk-read role of PEvents (PEvents.scala:77).
    """

    @abc.abstractmethod
    def init(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        """Initialize storage for a (app, channel) namespace."""

    @abc.abstractmethod
    def remove(self, app_id: int, channel_id: Optional[int] = None) -> bool:
        """Remove all events for a namespace."""

    def close(self) -> None:
        pass

    @abc.abstractmethod
    def insert(self, event: Event, app_id: int,
               channel_id: Optional[int] = None) -> str:
        """Insert one event; returns its eventId."""

    def insert_batch(self, events: Sequence[Event], app_id: int,
                     channel_id: Optional[int] = None) -> List[str]:
        return [self.insert(e, app_id, channel_id) for e in events]

    def insert_columnar(self, batch, app_id: int,
                        channel_id: Optional[int] = None) -> List[str]:
        """Bulk write from a ``ColumnarBatch`` of parallel arrays (the
        /events/columnar.json write mode, ISSUE 7). The default
        materializes ``Event`` objects and rides ``insert_batch``;
        backends with a vectorized path (nativelog, sqlite) override to
        skip the per-event object round trip entirely."""
        return self.insert_batch(batch.to_events(), app_id, channel_id)

    @abc.abstractmethod
    def get(self, event_id: str, app_id: int,
            channel_id: Optional[int] = None) -> Optional[Event]: ...

    @abc.abstractmethod
    def delete(self, event_id: str, app_id: int,
               channel_id: Optional[int] = None) -> bool: ...

    @abc.abstractmethod
    def find(self, app_id: int, channel_id: Optional[int] = None,
             start_time: Optional[_dt.datetime] = None,
             until_time: Optional[_dt.datetime] = None,
             entity_type: Optional[str] = None,
             entity_id: Optional[str] = None,
             event_names: Optional[Sequence[str]] = None,
             target_entity_type=None,  # str | ABSENT | None
             target_entity_id=None,    # str | ABSENT | None
             limit: Optional[int] = None,
             reversed_order: bool = False) -> Iterator[Event]:
        """Query events (LEvents.futureFind semantics, LEvents.scala:164).

        ``target_entity_type=ABSENT`` matches events with no target entity
        (the reference's Some(None)); ``None`` means no filter. ``limit=-1``
        means no limit. ``reversed_order`` sorts by eventTime descending and
        is only allowed when entity_type/entity_id are specified (enforced by
        callers, as in the reference).
        """

    def latest_event_id(self, app_id: int, entity_type: str,
                        entity_id: str,
                        channel_id: Optional[int] = None,
                        event_names: Optional[Sequence[str]] = None
                        ) -> Optional[str]:
        """The id of the entity's newest event (by eventTime), or None:
        what a reader that has already parsed one event asks before it
        parses the next (the e-commerce template's `unavailableItems`
        `$set`, a list of tens of thousands of ids, read at every
        dispatch). This default reads the event itself; a backend whose
        index holds times and ids answers without touching a payload."""
        for e in self.find(app_id, channel_id=channel_id,
                           entity_type=entity_type, entity_id=entity_id,
                           event_names=event_names, limit=1,
                           reversed_order=True):
            return e.event_id
        return None

    def find_columnar(self, app_id: int,
                      channel_id: Optional[int] = None,
                      property_field: Optional[str] = None,
                      **filters) -> Dict[str, "object"]:
        """Columnar bulk read for training ingest (the PEvents scan role,
        PEvents.scala:77, shaped for vectorized numpy consumption instead of
        an RDD): returns {'entity_id', 'target_entity_id', 'event', 't',
        'prop'} as flat numpy arrays — no per-event Python objects on the
        hot path. `prop` is float32 (NaN where `property_field` is missing)
        and only present when `property_field` is given; `t` is event-time
        millis. Backends with a query engine override this with a projected
        scan; this default streams `find`.
        """
        import numpy as np

        ents: list = []
        tgts: list = []
        names: list = []
        ts: list = []
        props: list = []
        for e in self.find(app_id, channel_id=channel_id, **filters):
            ents.append(e.entity_id)
            tgts.append(e.target_entity_id or "")
            names.append(e.event)
            ts.append(_millis(e.event_time))
            if property_field is not None:
                v = e.properties.get_opt(property_field, float)
                props.append(np.nan if v is None else v)
        out = {
            "entity_id": np.array(ents, dtype=str),
            "target_entity_id": np.array(tgts, dtype=str),
            "event": np.array(names, dtype=str),
            "t": np.array(ts, dtype=np.int64),
        }
        if property_field is not None:
            out["prop"] = np.array(props, dtype=np.float32)
        return out

    def find_columnar_chunked(self, app_id: int,
                              channel_id: Optional[int] = None,
                              property_field: Optional[str] = None,
                              chunk_rows: Optional[int] = None,
                              start_time: Optional[_dt.datetime] = None,
                              until_time: Optional[_dt.datetime] = None,
                              **filters) -> Iterator[Dict[str, "object"]]:
        """Streaming columnar read: a generator of ``find_columnar``-shaped
        column dicts of roughly ``chunk_rows`` rows each, in ascending
        event-time order — the bulk data plane's cursor contract (the
        dataplane reader drains it into bounded queues so read, decode
        and upload overlap instead of draining the store in one shot).

        Chunks break ONLY at complete milliseconds (a millisecond's rows
        are never split across chunks; a single-millisecond burst larger
        than ``chunk_rows`` comes back as one oversized chunk), so the
        concatenation of all chunks is byte-identical to one
        ``find_columnar`` call over the same range: within a chunk the
        backend's own intra-millisecond order is preserved, and no row
        is dropped or duplicated at a boundary. The reader is a forward
        cursor, not a repeatable snapshot: rows inserted mid-stream
        at/after the cursor are seen, rows landing behind it are not.

        This default is keyset pagination through ``find_columnar``
        (``start_time`` cursor + ``limit``), which backends with a query
        engine already push down (sqlite/pgsql: ``WHERE eventtime >= ?
        ORDER BY eventtime LIMIT ?`` against the time index); nativelog
        overrides it with a per-shard planned-window scan and the event
        server client with wire-level pagination. ``reversed_order`` is
        not part of the contract."""
        import numpy as np

        if filters.pop("reversed_order", False):
            raise ValueError(
                "find_columnar_chunked streams ascending event time only")
        if filters.pop("limit", None) not in (None, -1):
            raise ValueError(
                "find_columnar_chunked is unbounded; bound by until_time")
        chunk_rows = int(chunk_rows or DEFAULT_CHUNK_ROWS)
        if chunk_rows <= 0:
            raise ValueError("chunk_rows must be positive")
        cursor = start_time
        while True:
            cols = self.find_columnar(
                app_id, channel_id=channel_id,
                property_field=property_field, start_time=cursor,
                until_time=until_time, limit=chunk_rows + 1, **filters)
            t = cols["t"]
            n = len(t)
            if n <= chunk_rows:
                # the store has no more than a chunk left past the
                # cursor: this is the final chunk
                if n:
                    yield cols
                return
            last = int(t[-1])
            cut = int(np.searchsorted(t, last, side="left"))
            if cut == 0:
                # the whole fetch is one millisecond and it overflows
                # the chunk: fetch that millisecond whole (bounded by
                # events-per-ms) so it is never split
                cols = self.find_columnar(
                    app_id, channel_id=channel_id,
                    property_field=property_field,
                    start_time=from_millis(last),
                    until_time=from_millis(last + 1), limit=-1,
                    **filters)
                if len(cols["t"]):
                    yield cols
                cursor = from_millis(last + 1)
            else:
                # drop the trailing (possibly incomplete) millisecond;
                # the next window refetches it whole
                yield {k: v[:cut] for k, v in cols.items()}
                cursor = from_millis(last)

    def find_columnar_by_entities(self, app_id: int,
                                  channel_id: Optional[int] = None,
                                  entity_ids: Optional[Sequence[str]] = None,
                                  target_entity_ids:
                                      Optional[Sequence[str]] = None,
                                  property_field: Optional[str] = None,
                                  start_time: Optional[_dt.datetime] = None,
                                  until_time: Optional[_dt.datetime] = None,
                                  entity_type: Optional[str] = None,
                                  target_entity_type=None,
                                  event_names: Optional[Sequence[str]] = None,
                                  limit: Optional[int] = None
                                  ) -> Dict[str, "object"]:
        """Entity-set-filtered columnar read — the fold tick's O(touched)
        ingest. Returns the `find_columnar` column shape for exactly the
        rows that pass the shared filters AND whose ``entity_id`` is in
        ``entity_ids`` OR whose ``target_entity_id`` is in
        ``target_entity_ids`` (union: a touched user's whole history plus
        every event landing on a touched item — what the touched-row
        least-squares solves consume). ``None`` for a side means that
        side contributes nothing; both sides empty returns empty columns
        (callers wanting the full corpus use ``find_columnar``). Rows
        come back event-time ascending; intra-instant order is
        backend-defined, as in ``find``.

        This default streams ``find`` and filters host-side — correct
        but O(corpus). Every registered backend overrides it with real
        pushdown (SQL id-list predicates, the nativelog entity-index
        sidecar, the in-memory index, the event-server batched POST);
        the storage registry enforces the override at registration
        (`registry.get_data_object`), so a backend cannot silently ship
        the full-scan fallback as its "filtered" read.
        """
        eset = {str(x) for x in entity_ids} if entity_ids else set()
        tset = {str(x) for x in target_entity_ids} \
            if target_entity_ids else set()
        out = []
        bounded = limit is not None and limit >= 0
        if (eset or tset) and not (bounded and limit == 0):
            for e in self.find(
                    app_id, channel_id=channel_id, start_time=start_time,
                    until_time=until_time, entity_type=entity_type,
                    target_entity_type=target_entity_type,
                    event_names=event_names, limit=-1):
                if e.entity_id in eset or (
                        e.target_entity_id is not None
                        and e.target_entity_id in tset):
                    out.append(e)
                    if bounded and len(out) >= limit:
                        break
        return events_to_columnar(out, property_field)

    # -- derived queries ----------------------------------------------------
    def aggregate_properties(self, app_id: int,
                             channel_id: Optional[int] = None,
                             entity_type: str = "",
                             start_time: Optional[_dt.datetime] = None,
                             until_time: Optional[_dt.datetime] = None,
                             required: Optional[Sequence[str]] = None
                             ) -> Dict[str, PropertyMap]:
        """Aggregate $set/$unset/$delete into per-entity PropertyMaps
        (LEvents.futureAggregateProperties / PEvents.aggregateProperties)."""
        events = self.find(
            app_id=app_id, channel_id=channel_id, start_time=start_time,
            until_time=until_time, entity_type=entity_type,
            event_names=list(aggregate_event_names()))
        result = aggregate_properties(events)
        if required:
            req = set(required)
            result = {k: v for k, v in result.items()
                      if req.issubset(v.key_set)}
        return result

    def write(self, events: Iterable[Event], app_id: int,
              channel_id: Optional[int] = None) -> None:
        """Bulk write (PEvents.write, PEvents.scala:181)."""
        self.insert_batch(list(events), app_id, channel_id)


def aggregate_event_names():
    return ("$set", "$unset", "$delete")


def columnar_from_union_rows(rows_by_id: Dict[str, tuple],
                             property_field: Optional[str] = None,
                             limit: Optional[int] = None
                             ) -> Dict[str, "object"]:
    """Assemble the ``find_columnar`` dict from an entity-union SQL
    read: ``rows_by_id`` maps event id -> (entityid, targetentityid,
    event, eventtime[, prop]) — the id keying IS the cross-side dedup
    (a row matching both the entity and target predicates counts once).
    Sorts time-ascending and applies ``limit`` after the merge. Shared
    by the sqlite and pgsql/mysql pushdowns so the union semantics
    cannot diverge."""
    import numpy as np

    rows = sorted(rows_by_id.values(), key=lambda r: int(r[3]))
    if limit is not None and limit >= 0:
        rows = rows[:limit]
    if not rows:
        out = {"entity_id": np.array([], dtype=str),
               "target_entity_id": np.array([], dtype=str),
               "event": np.array([], dtype=str),
               "t": np.array([], dtype=np.int64)}
        if property_field is not None:
            out["prop"] = np.array([], dtype=np.float32)
        return out
    ents, tgts, names, ts, *rest = zip(*rows)
    out = {
        "entity_id": np.array(ents, dtype=str),
        "target_entity_id": np.array([x or "" for x in tgts], dtype=str),
        "event": np.array(names, dtype=str),
        "t": np.array([int(t) for t in ts], dtype=np.int64),
    }
    if property_field is not None:
        out["prop"] = np.array(
            [np.nan if v is None else float(v) for v in rest[0]],
            dtype=np.float32)
    return out


def events_to_columnar(events, property_field: Optional[str] = None
                       ) -> Dict[str, "object"]:
    """[Event] -> the ``find_columnar`` column dict (shared by backends
    whose entity-filtered reads materialize Event objects: memory's
    index, nativelog's sidecar seek+read, the streamed default)."""
    import numpy as np

    ents: list = []
    tgts: list = []
    names: list = []
    ts: list = []
    props: list = []
    for e in events:
        ents.append(e.entity_id)
        tgts.append(e.target_entity_id or "")
        names.append(e.event)
        ts.append(_millis(e.event_time))
        if property_field is not None:
            v = e.properties.get_opt(property_field, float)
            props.append(np.nan if v is None else v)
    out = {
        "entity_id": np.array(ents, dtype=str),
        "target_entity_id": np.array(tgts, dtype=str),
        "event": np.array(names, dtype=str),
        "t": np.array(ts, dtype=np.int64),
    }
    if property_field is not None:
        out["prop"] = np.array(props, dtype=np.float32)
    return out


def match_event(e: Event,
                start_time=None, until_time=None, entity_type=None,
                entity_id=None, event_names=None, target_entity_type=None,
                target_entity_id=None) -> bool:
    """Shared predicate implementing find() filter semantics; backends that
    cannot push filters down (memory, file) use this."""
    if start_time is not None and e.event_time < start_time:
        return False
    if until_time is not None and e.event_time >= until_time:
        return False
    if entity_type is not None and e.entity_type != entity_type:
        return False
    if entity_id is not None and e.entity_id != entity_id:
        return False
    if event_names is not None and e.event not in event_names:
        return False
    if target_entity_type is not None:
        if target_entity_type is ABSENT:
            if e.target_entity_type is not None:
                return False
        elif e.target_entity_type != target_entity_type:
            return False
    if target_entity_id is not None:
        if target_entity_id is ABSENT:
            if e.target_entity_id is not None:
                return False
        elif e.target_entity_id != target_entity_id:
            return False
    return True
