"""App-name-keyed event access for engine components.

Rebuilds the reference's ``PEventStore`` / ``LEventStore``
(reference: data/src/main/scala/io/prediction/data/store/PEventStore.scala:30-116,
LEventStore.scala:30-142, Common.scala appNameToId): engines refer to apps by
*name* (+ optional channel name); the store resolves ids through the metadata
DAOs and forwards to the configured Events backend.

The P/L split collapses here: one synchronous API serves both the bulk
training reads (PEvents role — feed ``parallel.dataset`` ingest) and the
serve-time point lookups with a deadline (LEvents role; the ecommerce
template's 200 ms business-rule reads)."""

from __future__ import annotations

import datetime as _dt
import threading
import time
from typing import Dict, Iterator, Optional, Sequence, Tuple

from predictionio_tpu.data.datamap import PropertyMap
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.storage.registry import Storage


class EventStore:
    def __init__(self, apps=None, channels=None, events=None):
        self._apps = apps
        self._channels = channels
        self._events = events

    @property
    def apps(self):
        return self._apps or Storage.get_meta_data_apps()

    @property
    def channels(self):
        return self._channels or Storage.get_meta_data_channels()

    @property
    def events(self):
        return self._events or Storage.get_events()

    def resolve(self, app_name: str,
                channel_name: Optional[str] = None) -> tuple:
        """app/channel name -> ids (store/Common.scala appNameToId)."""
        app = self.apps.get_by_name(app_name)
        if app is None:
            raise ValueError(
                f"Invalid app name {app_name!r}: app does not exist.")
        channel_id = None
        if channel_name is not None:
            match = [c for c in self.channels.get_by_app_id(app.id)
                     if c.name == channel_name]
            if not match:
                raise ValueError(
                    f"Invalid channel name {channel_name!r} for app "
                    f"{app_name!r}.")
            channel_id = match[0].id
        return app.id, channel_id

    # -- bulk reads (PEventStore.find, PEventStore.scala:54) ---------------
    def find(self, app_name: str, channel_name: Optional[str] = None,
             start_time: Optional[_dt.datetime] = None,
             until_time: Optional[_dt.datetime] = None,
             entity_type: Optional[str] = None,
             entity_id: Optional[str] = None,
             event_names: Optional[Sequence[str]] = None,
             target_entity_type=None, target_entity_id=None,
             limit: Optional[int] = None,
             reversed_order: bool = False) -> Iterator[Event]:
        app_id, channel_id = self.resolve(app_name, channel_name)
        return self.events.find(
            app_id=app_id, channel_id=channel_id, start_time=start_time,
            until_time=until_time, entity_type=entity_type,
            entity_id=entity_id, event_names=event_names,
            target_entity_type=target_entity_type,
            target_entity_id=target_entity_id, limit=limit,
            reversed_order=reversed_order)

    def find_columnar(self, app_name: str,
                      channel_name: Optional[str] = None,
                      property_field: Optional[str] = None,
                      timeout_ms: Optional[int] = None,
                      **filters) -> Dict[str, "object"]:
        """Columnar read (see Events.find_columnar): flat numpy arrays
        for vectorized training ingest — the PEvents-scan-to-RDD role
        (PEvents.scala:77) without per-event Python objects. With
        `timeout_ms` it is a serve-time read under the point reads'
        deadline (:meth:`find_by_entity`): what a caller that wants one
        column of an entity's events asks for (the e-commerce engine's
        seen items: the target ids of a user's views and buys, a thousand
        events for a heavy user, none of which it needs as an Event)."""
        def _query():
            app_id, channel_id = self.resolve(app_name, channel_name)
            return self.events.find_columnar(
                app_id=app_id, channel_id=channel_id,
                property_field=property_field, **filters)
        return self._within_deadline(_query, timeout_ms)

    def find_columnar_chunked(self, app_name: str,
                              channel_name: Optional[str] = None,
                              property_field: Optional[str] = None,
                              chunk_rows: Optional[int] = None,
                              **filters) -> Iterator[Dict[str, "object"]]:
        """Streaming columnar bulk read (see
        Events.find_columnar_chunked): a generator of chunk-sized column
        dicts whose concatenation is byte-identical to ``find_columnar``
        — the bulk data plane's cursor into the store (dataplane reader
        threads drain it so read/decode/upload overlap)."""
        app_id, channel_id = self.resolve(app_name, channel_name)
        return self.events.find_columnar_chunked(
            app_id=app_id, channel_id=channel_id,
            property_field=property_field, chunk_rows=chunk_rows,
            **filters)

    def find_columnar_by_entities(self, app_name: str,
                                  channel_name: Optional[str] = None,
                                  entity_ids=None, target_entity_ids=None,
                                  property_field: Optional[str] = None,
                                  **filters) -> Dict[str, "object"]:
        """Entity-set-filtered columnar read (see
        Events.find_columnar_by_entities): the fold tick's O(touched)
        ingest — rows whose subject is a touched entity OR whose target
        is a touched target, with each backend's real pushdown behind
        it."""
        app_id, channel_id = self.resolve(app_name, channel_name)
        return self.events.find_columnar_by_entities(
            app_id=app_id, channel_id=channel_id, entity_ids=entity_ids,
            target_entity_ids=target_entity_ids,
            property_field=property_field, **filters)

    # -- property aggregation (PEventStore.aggregateProperties) ------------
    def aggregate_properties(self, app_name: str, entity_type: str,
                             channel_name: Optional[str] = None,
                             start_time: Optional[_dt.datetime] = None,
                             until_time: Optional[_dt.datetime] = None,
                             required: Optional[Sequence[str]] = None
                             ) -> Dict[str, PropertyMap]:
        app_id, channel_id = self.resolve(app_name, channel_name)
        return self.events.aggregate_properties(
            app_id=app_id, channel_id=channel_id, entity_type=entity_type,
            start_time=start_time, until_time=until_time, required=required)

    # -- serve-time point reads (LEventStore.findByEntity) -----------------

    #: cap on concurrently outstanding deadline-guarded point reads. A
    #: timed-out read's worker thread keeps running against the slow
    #: backend (Python threads cannot be killed); the permit it holds is
    #: released only when the backend finally answers, so at most this
    #: many wedged readers can pile up — past that, new deadline reads
    #: fail fast instead of minting another stuck thread each.
    POINT_READ_MAX_INFLIGHT = 8

    _point_read_sem = threading.BoundedSemaphore(POINT_READ_MAX_INFLIGHT)

    def _timeout_counter(self):
        from predictionio_tpu.obs import get_registry
        return get_registry().counter(
            "pio_event_point_read_timeout_total",
            "Deadline-guarded event point reads that timed out (their "
            "late results are discarded; the worker permit is bounded)")

    def find_by_entity(self, app_name: str, entity_type: str, entity_id: str,
                       channel_name: Optional[str] = None,
                       event_names: Optional[Sequence[str]] = None,
                       target_entity_type=None, target_entity_id=None,
                       start_time=None, until_time=None,
                       limit: Optional[int] = None, latest: bool = True,
                       timeout_ms: Optional[int] = None) -> list:
        """Point lookup with an optional deadline (LEventStore.scala:30 — the
        reference's Duration timeout; the ecommerce template calls this with
        200 ms). Runs in a worker thread when a timeout is given so a slow
        backend cannot stall the serving path; timed-out workers are
        BOUNDED (POINT_READ_MAX_INFLIGHT permits — a wedged backend can
        strand at most that many threads, after which deadline reads
        fail fast) and counted under
        ``pio_event_point_read_timeout_total``."""
        def _query():
            return list(self.find(
                app_name=app_name, channel_name=channel_name,
                entity_type=entity_type, entity_id=entity_id,
                event_names=event_names,
                target_entity_type=target_entity_type,
                target_entity_id=target_entity_id, start_time=start_time,
                until_time=until_time, limit=limit, reversed_order=latest))
        return self._within_deadline(_query, timeout_ms)

    def latest_event(self, app_name: str, entity_type: str, entity_id: str,
                     channel_name: Optional[str] = None,
                     event_names: Optional[Sequence[str]] = None,
                     known_id: Optional[str] = None,
                     timeout_ms: Optional[int] = None
                     ) -> Tuple[Optional[str], Optional[Event]]:
        """``(event id, event)`` of the entity's newest event; ``(None,
        None)`` when it has none. When the newest is still the event
        `known_id` the answer is ``(known_id, None)`` and nothing is read
        or parsed (the e-commerce template's `unavailableItems` list is
        asked for at every dispatch and changes a few times an hour). Same
        deadline as :meth:`find_by_entity`."""
        def _query():
            app_id, channel_id = self.resolve(app_name, channel_name)
            eid = self.events.latest_event_id(
                app_id, entity_type, entity_id, channel_id=channel_id,
                event_names=event_names)
            if eid is None or eid == known_id:
                return eid, None
            e = self.events.get(eid, app_id, channel_id)
            if e is None or (e.entity_type, e.entity_id) != (entity_type,
                                                             entity_id):
                return None, None     # deleted since, or a hash collision
            return eid, e
        return self._within_deadline(_query, timeout_ms)

    def _within_deadline(self, _query, timeout_ms: Optional[int]):
        """Run `_query` in a worker thread under the point-read deadline
        (see :meth:`find_by_entity`); inline when there is none."""
        if timeout_ms is None:
            return _query()
        # the permit wait SHARES the deadline: a healthy burst past the
        # permit count queues briefly and still answers in time, while a
        # wedged backend (permits stranded by timed-out workers) makes
        # new reads fail at their own deadline instead of minting more
        # stuck threads
        t_start = time.monotonic()
        if not EventStore._point_read_sem.acquire(
                timeout=timeout_ms / 1000.0):
            self._timeout_counter().inc()
            raise TimeoutError(
                f"event lookup exceeded {timeout_ms} ms deadline: all "
                f"{self.POINT_READ_MAX_INFLIGHT} deadline-read workers "
                "are busy (backend wedged?)")
        done = threading.Event()
        result: list = []
        error: list = []

        def _run():
            try:
                result.append(_query())
            except Exception as e:  # surfaced below (if still awaited)
                error.append(e)
            finally:
                EventStore._point_read_sem.release()
                done.set()

        t = threading.Thread(target=_run, daemon=True,
                             name="pio-point-read")
        t.start()
        remaining = timeout_ms / 1000.0 - (time.monotonic() - t_start)
        if not done.wait(max(0.0, remaining)):
            # the worker keeps its permit until the backend answers;
            # its late result is dropped on the floor by design
            self._timeout_counter().inc()
            raise TimeoutError(
                f"event lookup exceeded {timeout_ms} ms deadline")
        if error:
            raise error[0]
        return result[0]


# Module-level default instances, mirroring the reference's singletons.
PEventStore = EventStore()
LEventStore = PEventStore
