"""E-commerce recommendation template: ALS + live business-rule filters.

Rebuilds `scala-parallel-ecommercerecommendation` (reference:
examples/scala-parallel-ecommercerecommendation/train-with-rate-event/src/
main/scala/ALSAlgorithm.scala — implicit ALS train :100-146; predict-time
live event-store reads with a 200 ms deadline for the user's seen items
:161-192 and the `constraint/unavailableItems` `$set` blacklist :195-215;
known-user scoring = dot(userFeature, productFeatures) with filters
:230-257; unknown users fall back to cosine similarity against their 10 most
recent viewed items :283-364).

The device path mirrors the similarproduct template (masked matmul top-k);
the business-rule reads stay host-side and only mutate the candidate mask,
so a slow event store can never stall the device (SURVEY hard part #4).
"""

from __future__ import annotations

import logging
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
from predictionio_tpu.ops.similarity import (build_filter_mask, cosine_top_k,
                                             normalize_rows)

logger = logging.getLogger(__name__)


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
    item_categories: List[Optional[set]]


class ECommAlgorithm(P2LAlgorithm):
    PARAMS_CLASS = ECommAlgorithmParams
    QUERY_CLASS = Query

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
        item_categories = []
        for ix in range(len(item_ix)):
            item = td.items.get(item_ix.id_of(ix))
            item_categories.append(
                set(item.categories) if item and item.categories else None)
        return ECommerceModel(
            rank=p.rank,
            user_factors=model.user_factors,
            item_factors=model.item_factors,
            item_factors_normalized=normalize_rows(model.item_factors),
            user_ix=user_ix, item_ix=item_ix, items=dict(td.items),
            item_categories=item_categories)

    # -- live business rules (ALSAlgorithm.scala:161-215) ------------------
    def _seen_items(self, user: str) -> List[str]:
        if not self.params.unseen_only:
            return []
        try:
            events = LEventStore.find_by_entity(
                app_name=self.params.app_name,
                channel_name=self.params.channel_name, entity_type="user",
                entity_id=user, event_names=list(self.params.seen_events),
                target_entity_type="item", timeout_ms=200)
            return [e.target_entity_id for e in events
                    if e.target_entity_id]
        except Exception as e:
            logger.error("Error when reading seen events: %s", e)
            return []

    def _unavailable_items(self) -> List[str]:
        try:
            events = LEventStore.find_by_entity(
                app_name=self.params.app_name,
                channel_name=self.params.channel_name,
                entity_type="constraint",
                entity_id="unavailableItems", event_names=["$set"],
                limit=1, latest=True, timeout_ms=200)
            if events:
                return list(events[0].properties.get_string_list("items"))
        except Exception as e:
            logger.error("Error when reading unavailableItems: %s", e)
        return []

    def _build_mask(self, model: ECommerceModel, query: Query,
                    seen: List[str], unavailable: List[str]) -> np.ndarray:
        """Candidate mask shared by the single and batched paths: query
        blacklist + live seen-items + unavailableItems merged into the
        exclusion set (ALSAlgorithm.scala:217-257)."""
        black = list(query.black_list or ()) + seen + unavailable
        white = (resolve_ids(model.item_ix, query.white_list)
                 if query.white_list is not None else None)
        return build_filter_mask(
            len(model.item_ix),
            exclude=resolve_ids(model.item_ix, black),
            white_list=white,
            item_categories=model.item_categories,
            categories=set(query.categories) if query.categories else None)

    def predict(self, model: ECommerceModel, query: Query
                ) -> ItemScoreResult:
        mask = self._build_mask(model, query, self._seen_items(query.user),
                                self._unavailable_items())
        uix = model.user_ix.get(query.user, -1)
        if uix >= 0:
            # known user: raw dot-product scoring (ALSAlgorithm.scala:230-257)
            scores, idx = self._dot_topk(model, int(uix), query.num, mask)
            return top_scores_to_result(model.item_ix, scores, idx)
        logger.info("No userFeature found for user %s.", query.user)
        return self._predict_new_user(model, query, mask)

    @staticmethod
    def _dot_topk(model: ECommerceModel, uix: int, num: int,
                  mask: np.ndarray):
        from predictionio_tpu.ops.als import ALSModel, recommend_products
        als = ALSModel(model.user_factors, model.item_factors, model.rank)
        exclude = np.nonzero(~mask)[0]
        scores, idx = recommend_products(als, uix, num, exclude=exclude)
        keep = np.isfinite(scores) & (scores > 0)  # reference keeps score>0
        return scores[keep], idx[keep]

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

    def _predict_new_user(self, model: ECommerceModel, query: Query,
                          mask: np.ndarray) -> ItemScoreResult:
        """Recent-views cosine fallback (ALSAlgorithm.scala:283-364)."""
        r_ix = self._recent_view_indices(model, query.user)
        if len(r_ix) == 0:
            return ItemScoreResult(())
        query_vecs = model.item_factors_normalized[r_ix]
        scores, idx = cosine_top_k(model.item_factors_normalized, query_vecs,
                                   query.num, mask)
        return top_scores_to_result(model.item_ix, scores, idx)

    def batch_predict(self, model, queries):
        """Batched path (serving coalescer + eval): business-rule event
        reads stay host-side and only mutate candidate masks; the
        query-independent unavailableItems read happens once per batch,
        the per-user reads run concurrently (they are I/O-bound with a
        200 ms deadline each). The batch then needs at most two device
        calls — one masked-matmul top-k for known users (raw dot scoring)
        and one for new-user cosine fallbacks."""
        from concurrent.futures import ThreadPoolExecutor

        from predictionio_tpu.ops.similarity import (masked_top_k_batch,
                                                     unpack_top_k_rows)
        out = {ix: ItemScoreResult(()) for ix, _ in queries}
        unavailable = self._unavailable_items()
        known = []     # (ix, query, user_vec [R], mask [I])
        fallback = []  # (ix, query, qsum [R], mask [I])
        with ThreadPoolExecutor(max_workers=min(8, max(1, len(queries)))) \
                as pool:
            seen_futs = {ix: pool.submit(self._seen_items, q.user)
                         for ix, q in queries}
            recent_futs = {ix: pool.submit(self._recent_view_indices,
                                           model, q.user)
                           for ix, q in queries
                           if model.user_ix.get(q.user, -1) < 0}
            for ix, q in queries:
                mask = self._build_mask(model, q, seen_futs[ix].result(),
                                        unavailable)
                uix = model.user_ix.get(q.user, -1)
                if uix >= 0:
                    known.append((ix, q, model.user_factors[int(uix)], mask))
                    continue
                logger.info("No userFeature found for user %s.", q.user)
                recent = recent_futs[ix].result()
                if len(recent) == 0:
                    continue
                qsum = model.item_factors_normalized[recent].sum(axis=0)
                fallback.append((ix, q, qsum, mask))
        for rows, table in ((known, model.item_factors),
                            (fallback, model.item_factors_normalized)):
            if not rows:
                continue
            k_max = max(q.num for _, q, _, _ in rows)
            scores, idx = masked_top_k_batch(
                table, np.stack([r[2] for r in rows]),
                np.stack([r[3] for r in rows]), k_max)
            for row, (ix, q, _, _) in enumerate(rows):
                s, i = unpack_top_k_rows(scores[row], idx[row], q.num)
                out[ix] = top_scores_to_result(model.item_ix, s, i)
        return list(out.items())


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
