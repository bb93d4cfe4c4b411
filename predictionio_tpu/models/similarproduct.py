"""Similar-product engine template: implicit ALS + cosine similarity.

Rebuilds `scala-parallel-similarproduct` (reference:
examples/scala-parallel-similarproduct/multi/src/main/scala/
ALSAlgorithm.scala — `ALS.trainImplicit` over view-count "ratings" built by
`((u,i),1).reduceByKey(_+_)` :96-133; predict scores every item by summed
cosine similarity against the query items' factors with category/white/black
filters :146-190). The driver-side cosine scan becomes one jitted masked
matmul + top-k (ops.similarity).
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from predictionio_tpu.core import (DataSource, Engine, EngineFactory,
                                   EngineParams, FirstServing, P2LAlgorithm,
                                   Params, Preparator, SanityCheck)
from predictionio_tpu.core.persistence import PersistentModel
from predictionio_tpu.data.bimap import EntityIdIxMap
from predictionio_tpu.data.store import PEventStore
from predictionio_tpu.models.common import (ItemScoreResult, RatingsData,
                                            resolve_ids,
                                            top_scores_to_result)
from predictionio_tpu.ops.als import ALSConfig, als_train
from predictionio_tpu.ops.ratings import RatingsCOO, dedup_ratings
from predictionio_tpu.ops.similarity import (ItemCategories,
                                             build_filter_mask, cosine_top_k,
                                             item_cosine_similarities,
                                             normalize_rows)

logger = logging.getLogger(__name__)


@dataclass(frozen=True)
class Item:
    categories: Optional[Tuple[str, ...]] = None
    # full $set property bag (add-and-return-item-properties variant)
    properties: Optional[dict] = None


@dataclass(frozen=True)
class ViewEvent:
    user: str
    item: str
    t: int = 0


@dataclass(frozen=True)
class LikeEvent:
    """like/dislike event (multi variant: LikeAlgorithm.scala:15-76)."""
    user: str
    item: str
    like: bool
    t: int = 0


@dataclass
class TrainingData(SanityCheck):
    """view_events/like_events are columnar (RatingsData: like=+1,
    dislike=-1); plain ViewEvent/LikeEvent row lists are accepted and
    converted for hand-built fixtures."""
    users: Dict[str, dict]
    items: Dict[str, Item]
    view_events: RatingsData
    like_events: RatingsData = None  # filled when read_like_events on
    # True for an entity-filtered (fold-tick) read: users/items/events
    # cover ONLY the touched entities' complete histories — fold_in
    # merges item metadata with the deployed model's instead of
    # rebuilding it from this partial bag
    touched_only: bool = False

    def __post_init__(self):
        if isinstance(self.view_events, (list, tuple)):
            self.view_events = RatingsData(
                np.array([v.user for v in self.view_events], dtype=str),
                np.array([v.item for v in self.view_events], dtype=str),
                np.ones(len(self.view_events), dtype=np.float32),
                np.array([v.t for v in self.view_events], dtype=np.int64))
        if isinstance(self.like_events, (list, tuple)):
            self.like_events = RatingsData(
                np.array([e.user for e in self.like_events], dtype=str),
                np.array([e.item for e in self.like_events], dtype=str),
                np.array([1.0 if e.like else -1.0
                          for e in self.like_events], dtype=np.float32),
                np.array([e.t for e in self.like_events], dtype=np.int64))

    def sanity_check(self):
        if not len(self.view_events):
            raise ValueError("view_events is empty; check the data source")
        if not self.items:
            raise ValueError("items is empty; check the data source")


@dataclass(frozen=True)
class Query:
    items: Tuple[str, ...]
    num: int
    categories: Optional[Tuple[str, ...]] = None
    white_list: Optional[Tuple[str, ...]] = None
    black_list: Optional[Tuple[str, ...]] = None
    # filterbyyear variant (filterbyyear/Engine.scala:22,
    # ALSAlgorithm.scala:231): only items with year > recommendFromYear
    recommend_from_year: Optional[int] = None

    @staticmethod
    def from_dict(d: dict) -> "Query":
        def opt(key):
            v = d.get(key)
            return tuple(v) if v is not None else None
        rfy = d.get("recommendFromYear")
        return Query(items=tuple(d["items"]), num=int(d["num"]),
                     categories=opt("categories"),
                     white_list=opt("whiteList"),
                     black_list=opt("blackList"),
                     recommend_from_year=(int(rfy) if rfy is not None
                                          else None))


@dataclass
class PreparedData:
    td: TrainingData


@dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = "default"
    channel_name: Optional[str] = None
    # add-rateevent variant: treat rate events as views as well
    rate_as_view: bool = False
    # multi variant: also read like/dislike events for LikeAlgorithm
    read_like_events: bool = False


class SimilarProductDataSource(DataSource):
    """(multi/DataSource.scala readTraining: $set user, $set item with
    categories, view events, like/dislike events). The add-rateevent
    variant's rate-as-view mapping and the no-set-user variant (users are
    inferred from view events; $set user events are optional) are folded in
    as parameters."""
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
            items[eid] = Item(tuple(cats) if cats is not None else None,
                              properties=dict(pm.fields))
        view_names = ["view", "rate"] if self.params.rate_as_view \
            else ["view"]
        # columnar ingest: flat arrays, no per-event Python objects
        vc = PEventStore.find_columnar(
            app_name=app, channel_name=chan, entity_type="user",
            event_names=view_names, target_entity_type="item")
        views = RatingsData(vc["entity_id"], vc["target_entity_id"],
                            np.ones(len(vc["t"]), dtype=np.float32),
                            vc["t"])
        likes = None
        if self.params.read_like_events:
            lc = PEventStore.find_columnar(
                app_name=app, channel_name=chan, entity_type="user",
                event_names=["like", "dislike"],
                target_entity_type="item")
            likes = RatingsData(
                lc["entity_id"], lc["target_entity_id"],
                np.where(lc["event"] == "like", 1.0, -1.0
                         ).astype(np.float32), lc["t"])
        return TrainingData(users=users, items=items, view_events=views,
                            like_events=likes)

    def read_training_touched(self, touched_users,
                              touched_items) -> TrainingData:
        """Entity-filtered fold-tick read (see the recommendation
        template's read_training_touched): touched users' complete view
        histories + every view landing on a touched item through the
        backend pushdown, and per-entity property aggregation for the
        touched entities only."""
        app = self.params.app_name
        chan = self.params.channel_name
        tu = [str(u) for u in touched_users]
        ti = [str(i) for i in touched_items]
        users = {u: dict(pm.fields)
                 for u, pm in self._aggregate_for("user", tu).items()}
        items = {}
        for eid, pm in self._aggregate_for("item", ti).items():
            cats = pm.get_opt("categories", list)
            items[eid] = Item(tuple(cats) if cats is not None else None,
                              properties=dict(pm.fields))
        view_names = ["view", "rate"] if self.params.rate_as_view \
            else ["view"]
        vc = PEventStore.find_columnar_by_entities(
            app_name=app, channel_name=chan, entity_ids=tu,
            target_entity_ids=ti, entity_type="user",
            event_names=view_names, target_entity_type="item")
        views = RatingsData(vc["entity_id"], vc["target_entity_id"],
                            np.ones(len(vc["t"]), dtype=np.float32),
                            vc["t"])
        likes = None
        if self.params.read_like_events:
            lc = PEventStore.find_columnar_by_entities(
                app_name=app, channel_name=chan, entity_ids=tu,
                target_entity_ids=ti, entity_type="user",
                event_names=["like", "dislike"],
                target_entity_type="item")
            likes = RatingsData(
                lc["entity_id"], lc["target_entity_id"],
                np.where(lc["event"] == "like", 1.0, -1.0
                         ).astype(np.float32), lc["t"])
        return TrainingData(users=users, items=items, view_events=views,
                            like_events=likes, touched_only=True)

    def _aggregate_for(self, entity_type: str, entity_ids) -> dict:
        """Per-entity property aggregation for an id set: k indexed
        point reads instead of the corpus-wide $set scan; the
        app/channel names resolve ONCE, not per id."""
        from predictionio_tpu.data.aggregator import aggregate_properties
        from predictionio_tpu.data.storage.base import aggregate_event_names
        app_id, channel_id = PEventStore.resolve(
            self.params.app_name, self.params.channel_name)
        ev = PEventStore.events
        events = []
        for eid in entity_ids:
            events.extend(ev.find(
                app_id=app_id, channel_id=channel_id,
                entity_type=entity_type, entity_id=eid,
                event_names=list(aggregate_event_names())))
        return aggregate_properties(events)


class SimilarProductPreparator(Preparator):
    def prepare(self, td: TrainingData) -> PreparedData:
        return PreparedData(td)


@dataclass(frozen=True)
class ALSAlgorithmParams(Params):
    rank: int = 10
    num_iterations: int = 20
    lam: float = 0.01
    alpha: float = 1.0
    seed: Optional[int] = None
    compute_dtype: Optional[str] = None  # None = bf16 on TPU, f32 on CPU
    # add-and-return-item-properties variant: property keys copied onto
    # each ItemScore in the result JSON (missing -> null)
    return_properties: Tuple[str, ...] = ()
    # solver-call batching (ops/als.ALSConfig.sweep_chunk; 0 = auto)
    sweep_chunk: int = 0


@dataclass(kw_only=True)
class ItemMetadataModel:
    """Id maps + item metadata shared by every similarproduct model flavor
    (the ALSModel fields minus the factors)."""
    item_ix: EntityIdIxMap
    items: Dict[str, Item]
    # by dense index (ops/similarity.ItemCategories; models pickled
    # before ISSUE 31 hold a list of optional sets, which
    # build_filter_mask converts on the way in)
    item_categories: ItemCategories
    item_years: Optional[np.ndarray] = None  # float32, NaN = undated

    @staticmethod
    def derive_years(items: Dict[str, Item],
                     item_ix: EntityIdIxMap) -> np.ndarray:
        years = np.full(len(item_ix), np.nan, dtype=np.float32)
        for ix in range(len(item_ix)):
            item = items.get(item_ix.id_of(ix))
            y = (item.properties or {}).get("year") if item else None
            if y is not None:
                years[ix] = float(y)
        return years

    @classmethod
    def metadata_kwargs(cls, items: Dict[str, Item],
                        item_ix: EntityIdIxMap) -> dict:
        """Constructor kwargs for the shared fields, derived once from the
        training data's item bag."""
        item_categories = []
        for ix in range(len(item_ix)):
            item = items.get(item_ix.id_of(ix))
            item_categories.append(
                set(item.categories) if item and item.categories else None)
        return dict(item_ix=item_ix, items=dict(items),
                    item_categories=ItemCategories.from_sets(
                        item_categories),
                    item_years=cls.derive_years(items, item_ix))

    def properties_of(self, keys: Tuple[str, ...]):
        """ItemScore property passthrough (add-and-return-item-properties
        variant): requested keys always present, missing -> None/null."""
        if not keys:
            return None

        def get(ix: int):
            item = self.items.get(self.item_ix.id_of(ix))
            p = (item.properties if item and item.properties else {})
            return {k: p.get(k) for k in keys}
        return get


@dataclass(kw_only=True)
class SimilarProductModel(ItemMetadataModel):
    """productFeatures + id maps + item metadata (ALSAlgorithm.scala
    ALSModel)."""
    item_factors_normalized: np.ndarray   # [I, R] L2-normalized rows
    # online-update state (ISSUE 1): the serve path needs only the
    # normalized item table, but folding a fresh view into a deployed
    # model needs the raw factors AND the user side the implicit
    # normal equations solve against. Optional so old pickles load.
    item_factors_raw: Optional[np.ndarray] = None   # [I, R]
    user_factors: Optional[np.ndarray] = None       # [U, R]
    user_ix: Optional[EntityIdIxMap] = None


class ALSAlgorithm(P2LAlgorithm):
    PARAMS_CLASS = ALSAlgorithmParams
    QUERY_CLASS = Query

    def __init__(self, params=None):
        super().__init__(params or ALSAlgorithmParams())

    def _build_ratings(self, td: TrainingData
                       ) -> Tuple[EntityIdIxMap, EntityIdIxMap, RatingsCOO]:
        """((u,i),1).reduceByKey(_+_) — view counts. Item vocabulary covers
        all $set items (so unseen-in-views items still resolve), users only
        those with views."""
        if not len(td.view_events):
            raise ValueError("No view events to train on")
        views = td.view_events
        user_ix, ui = EntityIdIxMap.build_with_indices(views.users)
        item_ix = EntityIdIxMap.build(list(td.items.keys()) +
                                      views.items.tolist())
        ii = item_ix.to_indices_array(views.items)
        ui, ii, counts = dedup_ratings(ui, ii, views.vals, policy="sum")
        return user_ix, item_ix, RatingsCOO(ui, ii, counts,
                                            len(user_ix), len(item_ix))

    def train(self, pd: PreparedData) -> SimilarProductModel:
        td = pd.td
        p = self.params
        user_ix, item_ix, coo = self._build_ratings(td)
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
        return SimilarProductModel(
            item_factors_normalized=normalize_rows(model.item_factors),
            item_factors_raw=model.item_factors,
            user_factors=model.user_factors, user_ix=user_ix,
            **ItemMetadataModel.metadata_kwargs(td.items, item_ix))

    # -- online updates (ISSUE 1: predictionio_tpu/online) -----------------
    def _fold_users_present(self, td: TrainingData) -> set:
        """Users with event data — the only ones user-vocab growth may
        mint rows for (a $set-only user stays cold-start)."""
        if not len(td.view_events):
            return set()
        return set(np.unique(td.view_events.users).astype(str))

    def _fold_ratings(self, td: TrainingData, user_ix: EntityIdIxMap,
                      item_ix: EntityIdIxMap) -> RatingsCOO:
        """Fresh ratings against FIXED (grown) vocabularies — the fold-in
        analog of `_build_ratings`, which builds vocabularies itself and
        would shuffle the deployed dense indices."""
        views = td.view_events
        ui = user_ix.to_indices_array(views.users)
        ii = item_ix.to_indices_array(views.items)
        keep = (ui >= 0) & (ii >= 0)
        ui, ii, counts = dedup_ratings(ui[keep], ii[keep],
                                       views.vals[keep], policy="sum")
        return RatingsCOO(ui, ii, counts, len(user_ix), len(item_ix))

    def fold_in(self, model: SimilarProductModel, td: TrainingData,
                touched_users, touched_items, preparator_params=None
                ) -> Tuple[SimilarProductModel, dict]:
        """Implicit (Hu-Koren) fold-in: re-solve only the touched user and
        item rows of the view-count factorization and refresh the
        normalized serve table — a freshly $set + viewed item becomes
        similar-product-recommendable without a retrain. Models persisted
        before online support (no raw factor state) raise."""
        if model.item_factors_raw is None or model.user_factors is None \
                or model.user_ix is None:
            raise ValueError(
                "model lacks online-update state; retrain once with this "
                "build before attaching the delta scheduler")
        from predictionio_tpu.online.fold_in import (FoldInConfig,
                                                     fold_in_coo)
        from predictionio_tpu.ops.als import ALSModel, als_rmse, \
            default_compute_dtype
        p = self.params
        # users grow only with event data; items grow when viewed OR $set
        # (train's item vocabulary likewise covers all $set items)
        present_u = self._fold_users_present(td)
        user_ix, _ = model.user_ix.grow(
            u for u in map(str, touched_users) if u in present_u)
        item_ix, _ = model.item_ix.grow(str(i) for i in touched_items)
        coo = self._fold_ratings(td, user_ix, item_ix)
        tu = user_ix.to_indices([str(u) for u in touched_users])
        ti = item_ix.to_indices([str(i) for i in touched_items])
        cfg = FoldInConfig(
            lam=p.lam, alpha=p.alpha, implicit_prefs=True, sweeps=2,
            compute_dtype=p.compute_dtype or default_compute_dtype(),
            sweep_chunk=p.sweep_chunk)
        als = ALSModel(user_factors=model.user_factors,
                       item_factors=model.item_factors_raw,
                       rank=model.item_factors_raw.shape[1])
        new_als, stats = fold_in_coo(
            als, coo, tu[tu >= 0], ti[ti >= 0], cfg,
            resident_key=f"fold:{type(self).__name__}:{id(self)}")
        if stats.degenerate:
            # nothing solvable (ISSUE 5 satellite): the deployed model
            # object signals a clean no-op to the scheduler
            return model, {"algorithm": type(self).__name__,
                           "degenerate": True, "wallS": stats.wall_s}
        # an entity-filtered read carries only the touched items' $set
        # state: untouched items keep the deployed metadata (categories,
        # years) instead of being wiped by the partial bag
        items = ({**model.items, **td.items}
                 if getattr(td, "touched_only", False) else td.items)
        new_model = SimilarProductModel(
            item_factors_normalized=normalize_rows(new_als.item_factors),
            item_factors_raw=new_als.item_factors,
            user_factors=new_als.user_factors, user_ix=user_ix,
            **ItemMetadataModel.metadata_kwargs(items, item_ix))
        report = {
            "algorithm": type(self).__name__,
            "loss": als_rmse(new_als, coo),
            "userRows": stats.n_user_rows, "itemRows": stats.n_item_rows,
            "newUsers": stats.n_new_users, "newItems": stats.n_new_items,
            "wallS": stats.wall_s, "residentHit": stats.resident_hit,
            "sentinelRollback": stats.sentinel_rollback,
            "guardWallS": stats.guard_wall_s,
        }
        return new_model, report

    @staticmethod
    def _build_mask(model: SimilarProductModel, query: Query,
                    q_ix: np.ndarray) -> np.ndarray:
        """Candidate mask shared by the single and batched paths
        (isCandidateItem, ALSAlgorithm.scala:192+); query items excluded."""
        white = (resolve_ids(model.item_ix, query.white_list)
                 if query.white_list is not None else None)
        black = resolve_ids(model.item_ix, query.black_list or ())
        mask = build_filter_mask(
            len(model.item_ix),
            exclude=np.concatenate([q_ix, black]),
            white_list=white,
            item_categories=model.item_categories,
            categories=set(query.categories) if query.categories else None)
        if query.recommend_from_year is not None and \
                model.item_years is not None:
            # filterbyyear: dated items need year > recommendFromYear
            # (undated items pass)
            dated = ~np.isnan(model.item_years)
            mask &= ~(dated & (model.item_years <= query.recommend_from_year))
        return mask

    def predict(self, model: SimilarProductModel, query: Query
                ) -> ItemScoreResult:
        q_ix = resolve_ids(model.item_ix, query.items)
        if len(q_ix) == 0:
            logger.info("No productFeatures vector for query items %s.",
                        query.items)
            return ItemScoreResult(())
        query_vecs = model.item_factors_normalized[q_ix]
        mask = self._build_mask(model, query, q_ix)
        scores, idx = cosine_top_k(model.item_factors_normalized, query_vecs,
                                   query.num, mask)
        return top_scores_to_result(
            model.item_ix, scores, idx,
            properties_of=model.properties_of(self.params.return_properties))

    # -- compile plane (ISSUE 9) -------------------------------------------
    def aot_warm_specs(self, model, batch_hint: int = 16):
        """(label, bucket-dims) rows for the cosine serve executable —
        compiled at deploy / hot-swap / canary-stage time by
        ``compile.aot.warm_models`` so a fresh model's first query pays
        no XLA compile. Covers the micro-batcher's pow2 coalescing
        ladder; the gates golden-replay answers through the same
        bucketed executable."""
        from predictionio_tpu.compile import buckets as B
        from predictionio_tpu.obs import costmon
        from predictionio_tpu.ops.similarity import (masked_topk_dims,
                                                     register_aot_specs)
        table = model.item_factors_normalized
        register_aot_specs()
        batches = sorted({1} | {1 << e for e in range(
            1, B.bucket_batch(max(batch_hint, 1)).bit_length())})
        return [(costmon.BATCH_PREDICT_MASKED,
                 masked_topk_dims(table.shape[0], table.shape[1], b, 16,
                                  filter_positive=True))
                for b in batches]

    def batch_predict(self, model, queries):
        """Batched path (serving coalescer + eval): the cosine score is
        linear over query items, so each query collapses to one summed
        normalized vector and the whole batch is a single masked matmul +
        top-k device call (vs the reference's per-query driver scan),
        shape-bucketed and AOT-dispatched inside masked_top_k_batch."""
        return self.batch_predict_begin(model, queries)()

    def batch_predict_begin(self, model, queries):
        """Two-phase batch predict (ISSUE 14 pipelined executor):
        enqueue the masked cosine top-k now, defer the device->host
        readback + result building to the returned ``finish()`` —
        callable from the completion stage's thread."""
        from predictionio_tpu.ops.similarity import (
            masked_top_k_batch_begin, unpack_top_k_rows)
        out = {ix: ItemScoreResult(()) for ix, _ in queries}
        rows = []  # (ix, query, qsum [R], mask [I])
        for ix, q in queries:
            q_ix = resolve_ids(model.item_ix, q.items)
            if len(q_ix) == 0:
                logger.info("No productFeatures vector for query items %s.",
                            q.items)
                continue
            qsum = model.item_factors_normalized[q_ix].sum(axis=0)
            rows.append((ix, q, qsum, self._build_mask(model, q, q_ix)))
        fetch = None
        if rows:
            k_max = max(q.num for _, q, _, _ in rows)
            fetch = masked_top_k_batch_begin(
                model.item_factors_normalized,
                np.stack([r[2] for r in rows]),
                np.stack([r[3] for r in rows]), k_max)

        def finish():
            if fetch is not None:
                scores, idx = fetch()
                props_of = model.properties_of(
                    self.params.return_properties)
                for row, (ix, q, _, _) in enumerate(rows):
                    s, i = unpack_top_k_rows(scores[row], idx[row],
                                             q.num)
                    out[ix] = top_scores_to_result(
                        model.item_ix, s, i, properties_of=props_of)
            return list(out.items())
        return finish


class LikeAlgorithm(ALSAlgorithm):
    """Implicit ALS on like/dislike events (multi variant,
    LikeAlgorithm.scala:15-76): latest event per (user, item) wins — a user
    may like an item and change to dislike later — like maps to rating 1,
    dislike to -1 (a negative implicit signal: confidence with preference
    0). Serve path is the same cosine scan as ALSAlgorithm."""

    def _build_ratings(self, td: TrainingData
                       ) -> Tuple[EntityIdIxMap, EntityIdIxMap, RatingsCOO]:
        likes = td.like_events
        if likes is None or not len(likes):
            raise ValueError("No like/dislike events to train on "
                             "(set read_like_events on the data source)")
        user_ix, ui = EntityIdIxMap.build_with_indices(likes.users)
        item_ix = EntityIdIxMap.build(list(td.items.keys()) +
                                      likes.items.tolist())
        ii = item_ix.to_indices_array(likes.items)
        ui, ii, vals = dedup_ratings(ui, ii, likes.vals, likes.ts,
                                     policy="latest")
        return user_ix, item_ix, RatingsCOO(ui, ii, vals,
                                            len(user_ix), len(item_ix))

    def _fold_users_present(self, td: TrainingData) -> set:
        if td.like_events is None or not len(td.like_events):
            return set()
        return set(np.unique(td.like_events.users).astype(str))

    def _fold_ratings(self, td: TrainingData, user_ix: EntityIdIxMap,
                      item_ix: EntityIdIxMap) -> RatingsCOO:
        likes = td.like_events
        if likes is None or not len(likes):
            raise ValueError("No like/dislike events to fold in")
        ui = user_ix.to_indices_array(likes.users)
        ii = item_ix.to_indices_array(likes.items)
        keep = (ui >= 0) & (ii >= 0)
        ui, ii, vals = dedup_ratings(ui[keep], ii[keep], likes.vals[keep],
                                     likes.ts[keep], policy="latest")
        return RatingsCOO(ui, ii, vals, len(user_ix), len(item_ix))


@dataclass(frozen=True)
class DIMSUMAlgorithmParams(Params):
    """dimsum variant (DIMSUMAlgorithm.scala:23): `threshold` drops
    sub-threshold similarity entries. The TPU build computes the exact
    cosine (ops/similarity.item_cosine_similarities) rather than DIMSUM's
    shuffle-bounding sampling approximation."""
    threshold: float = 0.0
    return_properties: Tuple[str, ...] = ()


@dataclass(kw_only=True)
class DIMSUMModel(ItemMetadataModel, PersistentModel):
    """Precomputed item-item similarity rows + id maps
    (DIMSUMAlgorithm.scala DIMSUMModel). Implements the manual-persistence
    contract the variant demonstrates (IPersistentModel.save to
    /tmp/<id> -> here, <PIO_FS_BASEDIR>/dimsum/<instance_id>)."""
    similarities: np.ndarray              # [I, I] f32, zero diagonal

    @classmethod
    def _dir(cls, instance_id: str) -> str:
        import os
        from predictionio_tpu.data.storage.registry import base_dir
        return os.path.join(base_dir(), "dimsum", instance_id)

    def save(self, instance_id: str, params) -> bool:
        import os
        import pickle
        d = self._dir(instance_id)
        os.makedirs(d, exist_ok=True)
        np.save(os.path.join(d, "similarities.npy"), self.similarities)
        with open(os.path.join(d, "maps.pkl"), "wb") as f:
            pickle.dump({"item_ix": self.item_ix, "items": self.items,
                         "item_categories": self.item_categories,
                         "item_years": self.item_years}, f)
        return True

    @classmethod
    def load(cls, instance_id: str, params) -> "DIMSUMModel":
        import os
        import pickle
        d = cls._dir(instance_id)
        sims = np.load(os.path.join(d, "similarities.npy"))
        with open(os.path.join(d, "maps.pkl"), "rb") as f:
            maps = pickle.load(f)
        return cls(similarities=sims, **maps)


class DIMSUMAlgorithm(P2LAlgorithm):
    """dimsum variant (DIMSUMAlgorithm.scala:67-220): all-pairs item
    cosine similarity from binary view co-occurrence, precomputed at train
    time; predict sums the query items' similarity rows and applies the
    standard candidate filters. Serving is a host row-gather — the model
    IS the score table (the reference serves it from an RDD lookup)."""
    PARAMS_CLASS = DIMSUMAlgorithmParams
    QUERY_CLASS = Query

    def __init__(self, params=None):
        super().__init__(params or DIMSUMAlgorithmParams())

    def train(self, pd: PreparedData) -> DIMSUMModel:
        td = pd.td
        if not len(td.view_events):
            raise ValueError("No view events to train on")
        views = td.view_events
        user_ix, ui = EntityIdIxMap.build_with_indices(views.users)
        item_ix = EntityIdIxMap.build(list(td.items.keys()) +
                                      views.items.tolist())
        ii = item_ix.to_indices_array(views.items)
        sims = item_cosine_similarities(
            ui, ii, len(user_ix), len(item_ix),
            threshold=self.params.threshold)
        return DIMSUMModel(
            similarities=sims,
            **ItemMetadataModel.metadata_kwargs(td.items, item_ix))

    def predict(self, model: DIMSUMModel, query: Query) -> ItemScoreResult:
        q_ix = resolve_ids(model.item_ix, query.items)
        if len(q_ix) == 0:
            logger.info("No similarity row for query items %s.", query.items)
            return ItemScoreResult(())
        scores = model.similarities[q_ix].sum(axis=0)
        mask = ALSAlgorithm._build_mask(model, query, q_ix)
        scores = np.where(mask & (scores > 0), scores, -np.inf)
        k = min(query.num, len(scores))
        idx = np.argpartition(-scores, k - 1)[:k]
        idx = idx[np.argsort(-scores[idx], kind="stable")]
        keep = np.isfinite(scores[idx])
        return top_scores_to_result(
            model.item_ix, scores[idx][keep], idx[keep],
            properties_of=model.properties_of(self.params.return_properties))


class SimilarProductEngineFactory(EngineFactory):
    @classmethod
    def apply(cls) -> Engine:
        return Engine(
            {"": SimilarProductDataSource},
            {"": SimilarProductPreparator},
            {"als": ALSAlgorithm, "likealgo": LikeAlgorithm,
             "dimsum": DIMSUMAlgorithm},
            {"": FirstServing})

    @classmethod
    def engine_params(cls, key: str = "") -> EngineParams:
        return EngineParams(
            data_source_params=("", DataSourceParams()),
            preparator_params=("", None),
            algorithm_params_list=[("als", ALSAlgorithmParams())],
            serving_params=("", None))
