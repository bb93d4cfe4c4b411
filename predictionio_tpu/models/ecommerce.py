"""E-commerce recommendation template: ALS + live business-rule filters.

Rebuilds `scala-parallel-ecommercerecommendation` (reference:
examples/scala-parallel-ecommercerecommendation/train-with-rate-event/src/
main/scala/ALSAlgorithm.scala — implicit ALS train :100-146; predict-time
live event-store reads with a 200 ms deadline for the user's seen items
:161-192 and the `constraint/unavailableItems` `$set` blacklist :195-215;
known-user scoring = dot(userFeature, productFeatures) with filters
:230-257; unknown users fall back to cosine similarity against their 10 most
recent viewed items :283-364).

The device path is one masked matmul top-k a route (known users: raw dot;
unknown users: cosine against their recent views) whose candidate mask is
composed ON THE DEVICE (ops/similarity.composed_top_k_batch_begin): the
item -> category array and the availability bitmap live there, a query
sends its category codes and its item lists (black list, seen items,
white list), a few KB. The business-rule reads stay host-side, each under
its 200 ms deadline, so a slow event store can never stall the device
(SURVEY hard part #4). The bitmap follows the `unavailableItems` `$set`:
every dispatch asks the store for the newest `$set`'s id first, and parses
the list and rebuilds the bitmap only when that id is new.
"""

from __future__ import annotations

import contextlib
import contextvars
import logging
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from predictionio_tpu.core import (DataSource, Engine, EngineFactory,
                                   EngineParams, FirstServing, P2LAlgorithm,
                                   Params, Preparator, SanityCheck)
from predictionio_tpu.data.bimap import EntityIdIxMap
from predictionio_tpu.data.store import LEventStore, PEventStore
from predictionio_tpu.models.common import (ItemScoreResult, RatingsData,
                                            resolve_ids,
                                            top_scores_to_result)
from predictionio_tpu.models.similarproduct import Item
from predictionio_tpu.ops.als import ALSConfig, als_train
from predictionio_tpu.ops.ratings import RatingsCOO, dedup_ratings
from predictionio_tpu.obs import TRACER
from predictionio_tpu.obs.metrics import get_registry
from predictionio_tpu.ops.similarity import (LISTED_OUT, LISTED_WHITE,
                                             ItemCategories, ItemFilterData,
                                             normalize_rows)

logger = logging.getLogger(__name__)

#: the live reads of one dispatch's queries run side by side (each is
#: I/O-bound under its own 200 ms deadline); one pool a process
_read_pool_lock = threading.Lock()
_read_pool: Optional[ThreadPoolExecutor] = None


def _reads() -> ThreadPoolExecutor:
    global _read_pool
    with _read_pool_lock:
        if _read_pool is None:
            _read_pool = ThreadPoolExecutor(
                max_workers=8, thread_name_prefix="pio-filter-read")
        return _read_pool


def _in_context(fn, *args):
    """`fn(*args)` for a pool thread, inside the caller's trace (a copy of
    its context: one Context is entered by one thread at a time)."""
    return (contextvars.copy_context().run, fn, *args)


def _counter(name: str, help_: str):
    return get_registry().counter(name, help_)


#: bounds of `pio_filter_seconds`: a live read is a millisecond or two and
#: fails at 200 ms; a re-set's parse is tens of milliseconds
_FILTER_BUCKETS = (0.00025, 0.0005, 0.001, 0.0015, 0.002, 0.003, 0.004,
                   0.006, 0.008, 0.012, 0.02, 0.035, 0.05, 0.1, 0.2, 0.5)


@contextlib.contextmanager
def _filter_stage(stage: str):
    """One piece of a dispatch's host-side filter work: the span
    `pio.filter.<stage>` and an observation of `pio_filter_seconds{stage}`
    (what a reader that wants every dispatch of a window takes: the
    tracer's ring holds the last 128 traces)."""
    t0 = time.perf_counter()
    try:
        with TRACER.region("filter." + stage) as span:
            yield span
    finally:
        get_registry().histogram(
            "pio_filter_seconds",
            "Host time of a dispatch's filter work by stage: seen_read "
            "(one per query, the user's seen events from the event store), "
            "constraint_read (one per dispatch: the newest unavailableItems "
            "`$set` asked for, parsed when it is new), lists (ids resolved, "
            "lists padded)", buckets=_FILTER_BUCKETS,
            labelnames=("stage",)).labels(stage=stage).observe(
                time.perf_counter() - t0)


@dataclass(frozen=True)
class RateEvent:
    user: str
    item: str
    rating: float
    t: int


@dataclass
class TrainingData(SanityCheck):
    """rate_events is columnar (RatingsData); plain RateEvent row lists
    are accepted and converted for hand-built fixtures."""
    users: Dict[str, dict]
    items: Dict[str, Item]
    rate_events: RatingsData

    def __post_init__(self):
        if isinstance(self.rate_events, (list, tuple)):
            self.rate_events = RatingsData.from_rows(self.rate_events)

    def sanity_check(self):
        if not len(self.rate_events):
            raise ValueError("rate_events is empty; check the data source")


@dataclass(frozen=True)
class Query:
    user: str
    num: int
    categories: Optional[Tuple[str, ...]] = None
    white_list: Optional[Tuple[str, ...]] = None
    black_list: Optional[Tuple[str, ...]] = None

    @staticmethod
    def from_dict(d: dict) -> "Query":
        def opt(key):
            v = d.get(key)
            return tuple(v) if v is not None else None
        return Query(user=str(d["user"]), num=int(d["num"]),
                     categories=opt("categories"),
                     white_list=opt("whiteList"),
                     black_list=opt("blackList"))


@dataclass
class PreparedData:
    td: TrainingData


@dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = "default"
    channel_name: Optional[str] = None
    rate_events: Tuple[str, ...] = ("rate", "buy")
    buy_rating: float = 4.0


class ECommerceDataSource(DataSource):
    PARAMS_CLASS = DataSourceParams

    def __init__(self, params=None):
        super().__init__(params or DataSourceParams())

    def read_training(self) -> TrainingData:
        app = self.params.app_name
        chan = self.params.channel_name
        users = {eid: dict(pm.fields) for eid, pm in
                 PEventStore.aggregate_properties(
                     app_name=app, channel_name=chan,
                     entity_type="user").items()}
        items = {}
        for eid, pm in PEventStore.aggregate_properties(
                app_name=app, channel_name=chan,
                entity_type="item").items():
            cats = pm.get_opt("categories", list)
            items[eid] = Item(tuple(cats) if cats is not None else None)
        # columnar ingest: flat arrays, no per-event Python objects
        rc = PEventStore.find_columnar(
            app_name=app, channel_name=chan, property_field="rating",
            entity_type="user", event_names=list(self.params.rate_events),
            target_entity_type="item")
        is_rate = rc["event"] == "rate"
        missing = is_rate & np.isnan(rc["prop"])
        if missing.any():
            raise ValueError(
                f"{int(missing.sum())} 'rate' event(s) lack the required "
                "'rating' property")
        vals = np.where(is_rate, rc["prop"],
                        np.float32(self.params.buy_rating)
                        ).astype(np.float32)
        rates = RatingsData(rc["entity_id"], rc["target_entity_id"],
                            vals, rc["t"])
        return TrainingData(users=users, items=items, rate_events=rates)


class ECommercePreparator(Preparator):
    def prepare(self, td: TrainingData) -> PreparedData:
        return PreparedData(td)


@dataclass(frozen=True)
class ECommAlgorithmParams(Params):
    app_name: str = "default"
    channel_name: Optional[str] = None  # serve-time reads use this channel
    unseen_only: bool = True
    seen_events: Tuple[str, ...] = ("buy", "view")
    rank: int = 10
    num_iterations: int = 20
    lam: float = 0.01
    alpha: float = 1.0
    seed: Optional[int] = None
    compute_dtype: Optional[str] = None  # None = bf16 on TPU, f32 on CPU
    # solver-call batching (ops/als.ALSConfig.sweep_chunk; 0 = auto)
    sweep_chunk: int = 0


@dataclass
class ECommerceModel:
    rank: int
    user_factors: np.ndarray               # [U, R]
    item_factors: np.ndarray               # [I, R]
    item_factors_normalized: np.ndarray    # [I, R]
    user_ix: EntityIdIxMap
    item_ix: EntityIdIxMap
    items: Dict[str, Item]
    # by dense index; models pickled before ISSUE 31 hold a list of
    # optional sets, which filter_data() converts
    item_categories: ItemCategories

    def filter_data(self) -> ItemFilterData:
        """This model's device-side filter data (category array,
        availability bitmap), made at first use; live state, so it is
        not pickled with the model."""
        fd = self.__dict__.get("_filter_data")
        if fd is None:
            fd = ItemFilterData(ItemCategories.from_sets(
                self.item_categories))
            self.__dict__["_filter_data"] = fd
        return fd

    def __getstate__(self):
        state = dict(self.__dict__)
        state.pop("_filter_data", None)
        return state


class ECommAlgorithm(P2LAlgorithm):
    PARAMS_CLASS = ECommAlgorithmParams
    QUERY_CLASS = Query
    #: an answer depends on event-store state read at predict time (seen
    #: items, the unavailable list), which no model version or query key
    #: names: the engine server keeps such answers out of its result cache
    LIVE_FILTERS = True

    def __init__(self, params=None):
        super().__init__(params or ECommAlgorithmParams())

    def train(self, pd: PreparedData) -> ECommerceModel:
        td = pd.td
        p = self.params
        if not len(td.rate_events):
            raise ValueError("No rate events to train on")
        rd = td.rate_events
        user_ix, ui = EntityIdIxMap.build_with_indices(rd.users)
        item_ix = EntityIdIxMap.build(list(td.items.keys()) +
                                      rd.items.tolist())
        ii = item_ix.to_indices_array(rd.items)
        # train-with-rate-event: duplicate ratings keep the latest value
        ui, ii, vals = dedup_ratings(ui, ii, rd.vals, rd.ts, "latest")
        coo = RatingsCOO(ui, ii, vals, len(user_ix), len(item_ix))
        from predictionio_tpu.ops.als import default_compute_dtype
        cfg = ALSConfig(rank=p.rank, iterations=p.num_iterations, lam=p.lam,
                        sweep_chunk=p.sweep_chunk,
                        implicit_prefs=True, alpha=p.alpha,
                        seed=p.seed if p.seed is not None else 0,
                        compute_dtype=p.compute_dtype
                        or default_compute_dtype())
        self.last_train_telemetry = {}
        model = als_train(coo, cfg,
                          telemetry=self.last_train_telemetry)
        with_cats = [(iid, item.categories) for iid, item in td.items.items()
                     if item.categories]
        item_categories = ItemCategories.from_pairs(
            len(item_ix),
            np.repeat(item_ix.to_indices([iid for iid, _ in with_cats]),
                      [len(cats) for _, cats in with_cats]),
            [c for _, cats in with_cats for c in cats])
        return ECommerceModel(
            rank=p.rank,
            user_factors=model.user_factors,
            item_factors=model.item_factors,
            item_factors_normalized=normalize_rows(model.item_factors),
            user_ix=user_ix, item_ix=item_ix, items=dict(td.items),
            item_categories=item_categories)

    # -- live business rules (ALSAlgorithm.scala:161-215) ------------------
    def _seen_items(self, user: str) -> List[str]:
        """The user's seen items, read now. Past its 200 ms deadline the
        read fails open as the template's does (no seen items, logged)
        and is counted (`pio_filter_seen_timeouts_total`)."""
        if not self.params.unseen_only:
            return []
        with _filter_stage("seen_read") as span:
            timed_out = False
            try:
                # the columnar read: only the target ids are wanted, and
                # a heavy user's thousand events decoded one by one into
                # Event objects cost 20 ms of interpreter time
                seen = LEventStore.find_columnar(
                    app_name=self.params.app_name,
                    channel_name=self.params.channel_name,
                    entity_type="user", entity_id=user,
                    event_names=list(self.params.seen_events),
                    target_entity_type="item", timeout_ms=200)
                return [t for t in seen["target_entity_id"].tolist() if t]
            except TimeoutError as e:
                timed_out = True
                _counter("pio_filter_seen_timeouts_total",
                         "Seen-item reads that passed their 200 ms "
                         "deadline: the query was answered without its "
                         "user's seen items excluded").inc()
                logger.error("Error when reading seen events: %s", e)
                return []
            except Exception as e:
                logger.error("Error when reading seen events: %s", e)
                return []
            finally:
                if span is not None:
                    span.attrs["timed_out"] = timed_out

    def _sync_unavailable(self, model: ECommerceModel,
                          filters: ItemFilterData) -> None:
        """Bring the availability bitmap up to the newest
        `constraint/unavailableItems` `$set` before a dispatch reads it.
        The store is asked for the newest `$set`'s id (200 ms deadline, as
        the template's read); the list (tens of thousands of ids) is read,
        parsed and resolved only when that id is new, once per `$set`.
        Where the read fails or passes its deadline the bitmap stays as it
        was (the template falls back to no list at all there, and the last
        list known is the closer answer): the dispatch is then answered
        under a list that may be stale, which is logged and counted
        (`pio_filter_constraint_failures_total`)."""
        def read(known_id):
            event_id, event = LEventStore.latest_event(
                app_name=self.params.app_name,
                channel_name=self.params.channel_name,
                entity_type="constraint", entity_id="unavailableItems",
                event_names=["$set"], known_id=known_id, timeout_ms=200)
            return event_id, lambda: resolve_ids(
                model.item_ix, event.properties.get_string_list("items"))

        with _filter_stage("constraint_read"):
            try:
                if filters.sync_unavailable(read):
                    _counter("pio_filter_constraint_reloads_total",
                             "Times the unavailableItems list was parsed "
                             "and the availability bitmap rebuilt: once "
                             "per `$set` seen, not once per dispatch").inc()
            except Exception as e:
                _counter("pio_filter_constraint_failures_total",
                         "Dispatches whose read of the newest "
                         "unavailableItems `$set` failed or passed its "
                         "200 ms deadline: answered under the last list "
                         "known").inc()
                logger.error("Error when reading unavailableItems: %s", e)

    def _recent_view_indices(self, model: ECommerceModel,
                             user: str) -> np.ndarray:
        """Dense indices of the user's 10 most recent viewed items
        (ALSAlgorithm.scala:283-364 fallback input)."""
        try:
            recent = LEventStore.find_by_entity(
                app_name=self.params.app_name,
                channel_name=self.params.channel_name, entity_type="user",
                entity_id=user, event_names=["view"],
                target_entity_type="item", limit=10, latest=True,
                timeout_ms=200)
            recent_items = {e.target_entity_id for e in recent
                            if e.target_entity_id}
        except Exception as e:
            logger.error("Error when reading recent events: %s", e)
            recent_items = set()
        r_ix = resolve_ids(model.item_ix, sorted(recent_items))
        if len(r_ix) == 0:
            logger.info("No productFeatures vector for recent items %s.",
                        recent_items)
        return r_ix

    def predict(self, model: ECommerceModel, query: Query
                ) -> ItemScoreResult:
        return self.batch_predict(model, [(0, query)])[0][1]

    # -- compile plane (ISSUE 9) -------------------------------------------
    def aot_warm_specs(self, model, batch_hint: int = 16):
        """(label, bucket-dims) rows of the composed-mask executable for
        `compile.aot.warm_models`: the micro-batcher's batch ladder times
        the list buckets a batch can fill. Both routes score against
        tables of one shape (`item_factors`, `item_factors_normalized`),
        so they share every executable."""
        from predictionio_tpu.obs import costmon
        from predictionio_tpu.ops.similarity import (
            composed_topk_warm_dims, register_composed_aot_specs)
        register_composed_aot_specs()
        n_items, rank = model.item_factors.shape
        c_max = model.filter_data().categories.ids.shape[1]
        return [(costmon.BATCH_PREDICT_COMPOSED, dims)
                for dims in composed_topk_warm_dims(n_items, rank,
                                                    batch_hint, c_max)]

    def batch_predict(self, model, queries):
        return self.batch_predict_begin(model, queries)()

    def batch_predict_begin(self, model, queries):
        """Two-phase batch predict (ISSUE 14 pipelined executor): the live
        reads, the lists and at most two device dispatches now (one
        composed-mask top-k for known users, raw dot scoring, and one for
        the new users' cosine fallback); the readback and the results in
        the returned ``finish()``. The candidate rule is the template's
        (ALSAlgorithm.scala:217-257): an item is a candidate when it is
        in the whiteList (if one is given), not in the blackList, not seen
        by the user, not unavailable, shares a category with the query
        (if it names any), and scores above 0."""
        from predictionio_tpu.ops.similarity import (
            composed_top_k_batch_begin, unpack_top_k_rows)
        out = {ix: ItemScoreResult(()) for ix, _ in queries}
        filters = model.filter_data()
        # before the reads, not beside them: the probe and the reads are
        # interpreter time more than waits, and side by side each read took
        # a millisecond longer while the dispatch took as long (PERF.md,
        # PR 31)
        self._sync_unavailable(model, filters)
        pool = _reads()
        seen_futs = {ix: pool.submit(*_in_context(self._seen_items, q.user))
                     for ix, q in queries}
        recent_futs = {ix: pool.submit(*_in_context(
                           self._recent_view_indices, model, q.user))
                       for ix, q in queries
                       if model.user_ix.get(q.user, -1) < 0}
        seen = {ix: f.result() for ix, f in seen_futs.items()}
        recent = {ix: f.result() for ix, f in recent_futs.items()}
        # route -> rows of (ix, query, vector [R], category codes, list)
        known, fallback = [], []
        with _filter_stage("lists"):
            for ix, q in queries:
                uix = model.user_ix.get(q.user, -1)
                if uix >= 0:
                    vec, rows = model.user_factors[int(uix)], known
                else:
                    logger.info("No userFeature found for user %s.", q.user)
                    if len(recent[ix]) == 0:
                        continue
                    vec = model.item_factors_normalized[recent[ix]].sum(
                        axis=0)
                    rows = fallback
                cats = (filters.categories.codes_of(q.categories)
                        if q.categories else np.zeros(0, np.int32))
                gone = np.unique(resolve_ids(
                    model.item_ix, list(q.black_list or ()) + seen[ix]))
                white = (np.unique(resolve_ids(model.item_ix, q.white_list))
                         if q.white_list is not None
                         else np.zeros(0, np.int32))
                listed = (np.concatenate([gone, white]),
                          np.concatenate([
                              np.full(gone.size, LISTED_OUT, np.int32),
                              np.full(white.size, LISTED_WHITE, np.int32)]))
                rows.append((ix, q, vec, cats, listed))
        fetches = []
        for rows, table in ((known, model.item_factors),
                            (fallback, model.item_factors_normalized)):
            if rows:
                fetches.append((rows, composed_top_k_batch_begin(
                    table, np.stack([r[2] for r in rows]), filters,
                    [r[3] for r in rows], [r[4] for r in rows],
                    [r[1].white_list is not None for r in rows],
                    max(r[1].num for r in rows))))

        def finish():
            for rows, fetch in fetches:
                scores, idx = fetch()
                for row, (ix, q, _, _, _) in enumerate(rows):
                    s_, i_ = unpack_top_k_rows(scores[row], idx[row], q.num)
                    out[ix] = top_scores_to_result(model.item_ix, s_, i_)
            return list(out.items())
        return finish


class ECommerceEngineFactory(EngineFactory):
    @classmethod
    def apply(cls) -> Engine:
        return Engine(
            {"": ECommerceDataSource},
            {"": ECommercePreparator},
            {"ecomm": ECommAlgorithm},
            {"": FirstServing})

    @classmethod
    def engine_params(cls, key: str = "") -> EngineParams:
        return EngineParams(
            data_source_params=("", DataSourceParams()),
            preparator_params=("", None),
            algorithm_params_list=[("ecomm", ECommAlgorithmParams())],
            serving_params=("", None))
