"""Recommendation engine template: explicit ALS on rate/buy events.

Rebuilds `scala-parallel-recommendation` (reference:
examples/scala-parallel-recommendation/custom-prepartor/src/main/scala/
ALSAlgorithm.scala:27-86 — MLlib `ALS.train` on rate/buy events, predict =
`model.recommendProducts`; DataSource.scala:20-46 reads rate/buy from the
event store, buy counts as rating 4.0; duplicate ratings keep the latest
event). The MLlib call becomes ops.als explicit training on the mesh.
"""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

from predictionio_tpu.core import (DataSource, Engine, EngineFactory,
                                   EngineParams, FirstServing, Metric,
                                   P2LAlgorithm, Params, Preparator,
                                   SanityCheck)
from predictionio_tpu.data.bimap import EntityIdIxMap
from predictionio_tpu.core.persistence import (PersistentModel,
                                               PersistentModelLoader)
from predictionio_tpu.data.store import PEventStore
from predictionio_tpu.models.common import (ItemScoreResult, RatingsData,
                                            top_scores_to_result)
from predictionio_tpu.ops.als import ALSConfig, ALSModel, als_train, \
    recommend_products
from predictionio_tpu.ops.ratings import RatingsCOO, dedup_ratings

logger = logging.getLogger(__name__)


# -- data shapes ------------------------------------------------------------

@dataclass(frozen=True)
class Rating:
    user: str
    item: str
    rating: float
    t: int = 0  # event-time millis (dedup tie-break)


@dataclass
class TrainingData(SanityCheck):
    """`ratings` is columnar (RatingsData); a plain list of Rating rows is
    accepted and converted, so hand-built fixtures keep working."""
    ratings: RatingsData
    items: Optional[dict] = None  # id -> property dict (read_items variants)
    # True when this payload came from an entity-filtered read
    # (read_training_touched): it holds ONLY the touched entities'
    # complete histories, not the corpus — valid fold-in input, never
    # valid retrain input
    touched_only: bool = False

    def __post_init__(self):
        if isinstance(self.ratings, (list, tuple)):
            self.ratings = RatingsData.from_rows(self.ratings)

    def sanity_check(self):
        if not len(self.ratings):
            raise ValueError("ratings is empty; check the data source")


@dataclass(frozen=True)
class Query:
    """Base query is (user, num); the custom-query and filter-by-category
    variants add creationYear and categories (custom-query/Engine.scala:6,
    filter-by-category/Engine.scala:6-10) — optional here, so the base wire
    format is unchanged."""
    user: str
    num: int
    categories: Optional[Tuple[str, ...]] = None
    creation_year: Optional[int] = None

    @staticmethod
    def from_dict(d: dict) -> "Query":
        cats = d.get("categories")
        return Query(user=str(d["user"]), num=int(d["num"]),
                     categories=tuple(cats) if cats is not None else None,
                     creation_year=(int(d["creationYear"])
                                    if d.get("creationYear") is not None
                                    else None))


@dataclass
class PreparedData:
    ratings_coo: RatingsCOO
    user_ix: EntityIdIxMap
    item_ix: EntityIdIxMap
    items: Optional[dict] = None  # id -> property dict


# -- DASE components --------------------------------------------------------

@dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = "default"
    channel_name: Optional[str] = None
    event_names: Tuple[str, ...] = ("rate", "buy")
    buy_rating: float = 4.0  # implicit rating assigned to buy events
    eval_k: Optional[int] = None    # enable k-fold read_eval when set
    eval_query_num: int = 10        # query.num used for eval queries
    # custom-query / filter-by-category variants: read $set item properties
    # (categories, creationYear, ...) for predict-time filters
    read_items: bool = False
    # bulk data plane (ISSUE 16): stream the training read through
    # chunked store cursors + double-buffered device staging instead of
    # one monolithic scan. None defers to PIO_DATAPLANE_STREAM; the
    # streamed read is exact-parity with the batch one (chunk-wise
    # _ratings_from_cols concat == global; the preparator's sorted
    # np.unique vocabulary is order-independent), so this is a
    # throughput knob, never a semantics knob.
    stream: Optional[bool] = None


@dataclass(frozen=True)
class ActualResult:
    """Ratings the test fold holds for the queried user (the template
    evaluation's ground truth)."""
    ratings: Tuple[Rating, ...]


class RecommendationDataSource(DataSource):
    PARAMS_CLASS = DataSourceParams

    def __init__(self, params=None):
        super().__init__(params or DataSourceParams())

    def _read_ratings(self) -> RatingsData:
        """Columnar ingest: one projected scan into flat numpy arrays
        (DataSource.scala:20-46 eventsRDD -> ratingsRDD, without 20M
        Python objects at ML-20M scale)."""
        p = self.params
        if self._stream_active():
            return self._read_ratings_streamed()
        cols = PEventStore.find_columnar(
            app_name=p.app_name, channel_name=p.channel_name,
            property_field="rating", entity_type="user",
            target_entity_type="item", event_names=list(p.event_names))
        return self._ratings_from_cols(cols, p)

    def _stream_active(self) -> bool:
        s = getattr(self.params, "stream", None)
        if s is not None:
            return bool(s)
        return os.environ.get("PIO_DATAPLANE_STREAM", "").lower() in (
            "1", "true", "yes", "on")

    def _read_ratings_streamed(self) -> RatingsData:
        """The same read through the bulk data plane: chunked store
        cursors decoded per chunk (overlapped with the reader thread)
        while the numeric training columns double-buffer onto the
        device. Chunk-wise ``_ratings_from_cols`` + concat is
        row-for-row identical to the monolithic scan — the chunk
        contract never splits a millisecond, and every conversion here
        is row-wise."""
        from predictionio_tpu.dataplane import (BulkLoadExecutor,
                                                StreamInterner)
        p = self.params
        users_in, items_in = StreamInterner(), StreamInterner()

        def decode(chunk):
            return self._ratings_from_cols(chunk, p)

        def encode(rd):
            # interned dense ids now; remap_to_sorted reconciles them
            # with the preparator's sorted vocabulary at finalize
            return {"user_ix": users_in.encode(rd.users),
                    "item_ix": items_in.encode(rd.items),
                    "vals": rd.vals, "t": rd.ts}

        result = BulkLoadExecutor().run(
            p.app_name, channel_name=p.channel_name,
            property_field="rating", decode=decode, encode=encode,
            entity_type="user", target_entity_type="item",
            event_names=list(p.event_names))
        st = result.stats
        logger.info(
            "streamed ratings read: %d rows / %d chunks, read %.2fs "
            "decode %.2fs h2d %.1f MB overlap %.0f%% compiles(steady) %d",
            st.rows, st.chunks, st.read_s, st.decode_s,
            st.h2d_bytes / 1e6, 100.0 * st.h2d_overlap_frac,
            st.steady_compiles)
        parts = result.decoded
        if not parts:
            return RatingsData(
                np.array([], dtype=str), np.array([], dtype=str),
                np.array([], dtype=np.float32),
                np.array([], dtype=np.int64))
        return RatingsData(
            np.concatenate([r.users for r in parts]),
            np.concatenate([r.items for r in parts]),
            np.concatenate([r.vals for r in parts]),
            np.concatenate([r.ts for r in parts]))

    @staticmethod
    def _ratings_from_cols(cols, p) -> RatingsData:
        is_rate = cols["event"] == "rate"
        missing = is_rate & np.isnan(cols["prop"])
        if missing.any():
            raise ValueError(
                f"{int(missing.sum())} 'rate' event(s) lack the required "
                f"'rating' property (first entity: "
                f"{cols['entity_id'][missing][0]!r})")
        vals = np.where(is_rate, cols["prop"],
                        np.float32(p.buy_rating)).astype(np.float32)
        return RatingsData(cols["entity_id"], cols["target_entity_id"],
                           vals, cols["t"])

    def _read_items(self) -> Optional[dict]:
        if not self.params.read_items:
            return None
        return {eid: dict(pm.fields) for eid, pm in
                PEventStore.aggregate_properties(
                    app_name=self.params.app_name,
                    channel_name=self.params.channel_name,
                    entity_type="item").items()}

    def read_training(self) -> TrainingData:
        return TrainingData(self._read_ratings(), items=self._read_items())

    def read_training_touched(self, touched_users,
                              touched_items) -> TrainingData:
        """Entity-filtered fold-tick read: only the touched users'
        complete rating histories plus every rating landing on a touched
        item — exactly the rows the touched-row least-squares solves
        consume (their dedup and per-entity regularizers see complete
        histories, so the folded factors match the full-scan path). Cost
        is O(touched histories) through each backend's pushdown
        (``find_columnar_by_entities``), not a corpus scan."""
        p = self.params
        cols = PEventStore.find_columnar_by_entities(
            app_name=p.app_name, channel_name=p.channel_name,
            entity_ids=[str(u) for u in touched_users],
            target_entity_ids=[str(i) for i in touched_items],
            property_field="rating", entity_type="user",
            target_entity_type="item", event_names=list(p.event_names))
        items = None
        if p.read_items:
            items = self._read_items_for([str(i) for i in touched_items])
        return TrainingData(self._ratings_from_cols(cols, p),
                            items=items, touched_only=True)

    def _read_items_for(self, item_ids) -> dict:
        """Aggregate $set/$unset/$delete for the given items only (k
        indexed point reads instead of the corpus-wide property scan;
        the app/channel names resolve ONCE, not per id)."""
        from predictionio_tpu.data.aggregator import aggregate_properties
        from predictionio_tpu.data.storage.base import aggregate_event_names
        app_id, channel_id = PEventStore.resolve(
            self.params.app_name, self.params.channel_name)
        ev = PEventStore.events
        events = []
        for iid in item_ids:
            events.extend(ev.find(
                app_id=app_id, channel_id=channel_id,
                entity_type="item", entity_id=iid,
                event_names=list(aggregate_event_names())))
        return {eid: dict(pm.fields)
                for eid, pm in aggregate_properties(events).items()}

    def read_eval(self):
        """k-fold split of rating events; one query per test-fold user with
        that user's held-out ratings as the actual (the recommendation
        template's Evaluation DataSource shape)."""
        p = self.params
        if not p.eval_k:
            return []
        ratings = self._read_ratings()
        row_ix = np.arange(len(ratings))
        folds = []
        for fold in range(p.eval_k):
            test_mask = (row_ix % p.eval_k) == fold
            train = ratings.select(~test_mask)
            by_user = {}
            for r in ratings.select(test_mask):
                by_user.setdefault(r.user, []).append(r)
            qa = [(Query(user=user, num=p.eval_query_num),
                   ActualResult(tuple(rs)))
                  for user, rs in sorted(by_user.items())]
            folds.append((TrainingData(train), None, qa))
        return folds


@dataclass(frozen=True)
class PreparatorParams(Params):
    dedup: str = "latest"
    # custom-prepartor variant (Preparator.scala:13-27): newline-separated
    # item ids excluded from training before the vocabulary is built.
    exclude_items_file: Optional[str] = None


class RecommendationPreparator(Preparator):
    """Builds the dense vocabulary + dedup'd COO (the BiMap.stringInt step
    of the reference's preparator/algorithm, done once host-side)."""
    PARAMS_CLASS = PreparatorParams

    def __init__(self, params=None):
        super().__init__(params or PreparatorParams())

    def prepare(self, td: TrainingData) -> PreparedData:
        rd = td.ratings
        if self.params.exclude_items_file:
            with open(self.params.exclude_items_file) as f:
                no_train = sorted({line.strip() for line in f
                                   if line.strip()})
            rd = rd.select(~np.isin(rd.items, no_train))
        # one np.unique pass per side builds the sorted vocabulary AND the
        # dense indices (no per-row dict probes)
        user_ix, ui = EntityIdIxMap.build_with_indices(rd.users)
        item_ix, ii = EntityIdIxMap.build_with_indices(rd.items)
        ui, ii, vals = dedup_ratings(ui, ii, rd.vals, rd.ts,
                                     self.params.dedup)
        coo = RatingsCOO(ui, ii, vals, len(user_ix), len(item_ix))
        return PreparedData(coo, user_ix, item_ix, items=td.items)


@dataclass(frozen=True)
class ALSAlgorithmParams(Params):
    rank: int = 10
    num_iterations: int = 10
    lam: float = 0.01
    seed: Optional[int] = None
    compute_dtype: Optional[str] = None  # None = bf16 on TPU, f32 on CPU
    # custom-query variant: property keys copied onto each ItemScore in the
    # result JSON (e.g. ("creationYear",)); requires data source read_items
    return_properties: Tuple[str, ...] = ()
    # solver-call batching (ops/als.ALSConfig.sweep_chunk; 0 = auto)
    sweep_chunk: int = 0
    # sharded online plane (ISSUE 12): 'model' trains, folds AND
    # serves the factor tables row-sharded over the mesh model axis
    # (ShardedTable handles end to end) — the configuration for
    # vocabularies whose table bytes exceed one device's budget.
    # 'replicated' (default) keeps the single-device-table layout.
    factor_sharding: str = "replicated"


@dataclass
class RecommendationModel:
    als: ALSModel
    user_ix: EntityIdIxMap
    item_ix: EntityIdIxMap
    # by dense item index; present when the data source read item properties
    item_properties: Optional[List[Optional[dict]]] = None
    # derived at train time so per-query masks are vectorized, not
    # O(n_items) Python loops on the serve path
    # ops/similarity.ItemCategories (a list of optional sets in models
    # pickled before ISSUE 31: build_filter_mask converts those)
    item_categories: Optional[object] = None
    item_years: Optional[np.ndarray] = None  # float32, NaN = undated

    @staticmethod
    def derive_filters(item_properties):
        if item_properties is None:
            return None, None
        from predictionio_tpu.ops.similarity import ItemCategories
        cats = ItemCategories.from_sets(
            [set(p["categories"]) if p and p.get("categories") else None
             for p in item_properties])
        years = np.array(
            [float(p["creationYear"])
             if p and p.get("creationYear") is not None else np.nan
             for p in item_properties], dtype=np.float32)
        return cats, years

    def properties_of(self, keys: Tuple[str, ...]):
        """ItemScore property passthrough: requested keys always present
        (missing -> None/null, the Option[Int] wire shape of
        custom-query/Engine.scala:12)."""
        if not keys or self.item_properties is None:
            return None
        props = self.item_properties

        def get(ix: int):
            p = props[ix] or {}
            return {k: p.get(k) for k in keys}
        return get

    def allowed_mask(self, query: Query) -> Optional[np.ndarray]:
        """Candidate mask for the filter variants; None = no filtering.
        categories: item must share a category (filter-by-category; empty
        list = no filter, as in the other templates); creationYear: undated
        items pass, dated items need year >= query's
        (custom-query/ALSAlgorithm.scala:141-148)."""
        from predictionio_tpu.ops.similarity import build_filter_mask
        want_cats = set(query.categories) if query.categories else None
        if want_cats is None and query.creation_year is None:
            return None
        n = len(self.item_ix)
        mask = build_filter_mask(
            n, item_categories=self.item_categories, categories=want_cats)
        if query.creation_year is not None and self.item_years is not None:
            dated = ~np.isnan(self.item_years)
            mask &= ~(dated & (self.item_years < query.creation_year))
        return mask


class ALSAlgorithm(P2LAlgorithm):
    """Explicit ALS (ALSAlgorithm.scala:27-86)."""
    PARAMS_CLASS = ALSAlgorithmParams
    QUERY_CLASS = Query

    def __init__(self, params=None):
        super().__init__(params or ALSAlgorithmParams())

    def train(self, pd: PreparedData) -> RecommendationModel:
        p = self.params
        if pd.ratings_coo.nnz == 0:
            raise ValueError("No ratings to train on")
        from predictionio_tpu.ops.als import default_compute_dtype
        sharded = getattr(p, "factor_sharding", "replicated") == "model"
        mesh = None
        if sharded:
            # the process-wide model mesh: fold ticks and server
            # threads resolve the same one for this shard count
            from predictionio_tpu.parallel.mesh import model_mesh
            import jax
            mesh = model_mesh(len(jax.devices()))
        cfg = ALSConfig(rank=p.rank, iterations=p.num_iterations, lam=p.lam,
                        sweep_chunk=p.sweep_chunk,
                        seed=p.seed if p.seed is not None else 0,
                        compute_dtype=p.compute_dtype
                        or default_compute_dtype(),
                        factor_sharding=("model" if sharded
                                         else "replicated"),
                        keep_sharded=sharded)
        # per-phase timing of the train that just ran (plan/upload/iters/
        # fetch) for the train report (workflow/core_workflow.py); the
        # hard syncs it adds are negligible next to a real train
        self.last_train_telemetry = {}
        model = als_train(pd.ratings_coo, cfg, mesh=mesh,
                          telemetry=self.last_train_telemetry)
        item_properties = None
        if pd.items is not None:
            item_properties = [pd.items.get(pd.item_ix.id_of(ix))
                               for ix in range(len(pd.item_ix))]
        cats, years = RecommendationModel.derive_filters(item_properties)
        return RecommendationModel(model, pd.user_ix, pd.item_ix,
                                   item_properties=item_properties,
                                   item_categories=cats, item_years=years)

    def predict(self, model: RecommendationModel, query: Query
                ) -> ItemScoreResult:
        uix = model.user_ix.get(query.user, -1)
        if uix < 0:
            logger.info("No prediction for unknown user %s.", query.user)
            return ItemScoreResult(())
        props_of = model.properties_of(self.params.return_properties)
        mask = model.allowed_mask(query)
        from predictionio_tpu.parallel.sharded_table import (is_sharded,
                                                             table_rows)
        if mask is None:
            if is_sharded(model.als.item_factors):
                # sharded single-query route: the same per-shard
                # top-k + merge executables the batched path runs
                from predictionio_tpu.ops.als import users_topk_serve
                from predictionio_tpu.ops.similarity import \
                    unpack_top_k_rows
                scores, idx = users_topk_serve(model.als, [int(uix)],
                                               query.num)
                s, i = unpack_top_k_rows(scores[0], idx[0], query.num)
                return top_scores_to_result(model.item_ix, s, i,
                                            properties_of=props_of)
            scores, idx = recommend_products(model.als, int(uix), query.num)
            return top_scores_to_result(model.item_ix, scores, idx,
                                        properties_of=props_of)
        # filtered path: ship the fixed-shape [I] bool mask, not a dense
        # exclude-index array whose length would recompile the kernel
        from predictionio_tpu.ops.similarity import (masked_top_k_batch,
                                                     unpack_top_k_rows)
        scores, idx = masked_top_k_batch(
            model.als.item_factors,
            table_rows(model.als.user_factors, [int(uix)]), mask[None],
            query.num, filter_positive=False)
        s, i = unpack_top_k_rows(scores[0], idx[0], query.num)
        return top_scores_to_result(model.item_ix, s, i,
                                    properties_of=props_of)

    # -- online updates (ISSUE 1: predictionio_tpu/online) -----------------
    def fold_in(self, model: RecommendationModel, td: TrainingData,
                touched_users, touched_items,
                preparator_params: Optional[PreparatorParams] = None
                ) -> Tuple[RecommendationModel, dict]:
        """Absorb fresh events without a retrain: grow the vocabularies
        with unseen touched entities (existing dense indices — and the
        deployed factor rows behind them — never move), then re-solve
        ONLY the touched user/item rows against the current data
        (online/fold_in.fold_in_coo; explicit ALS-WR normal equations,
        the same math `train` runs per sweep).

        ``td`` must be the CURRENT training data (the scheduler re-reads
        it through the data source): the touched rows' solves are
        least-squares over exactly what they are given, so a partial
        history would bias them toward the fresh slice.
        ``preparator_params`` replays the deployed Preparator's data
        policy (dedup mode, exclude_items_file) — the fold cannot run
        prepare() itself because prepare rebuilds vocabularies and would
        shuffle the deployed dense indices. Returns (new_model, report)
        where report carries the post-fold training loss the scheduler's
        drift gate consumes."""
        from predictionio_tpu.online.fold_in import (FoldInConfig,
                                                     fold_in_coo)
        from predictionio_tpu.ops.als import als_rmse
        p = self.params
        prep = preparator_params or PreparatorParams()
        rd = td.ratings
        if prep.exclude_items_file:
            with open(prep.exclude_items_file) as f:
                no_train = sorted({line.strip() for line in f
                                   if line.strip()})
            if no_train:
                rd = rd.select(~np.isin(rd.items, no_train))
                touched_items = [i for i in touched_items
                                 if str(i) not in set(no_train)]
        # grow only entities that actually have ratings: a property-only
        # $set for an unseen user/item must NOT mint a zero factor row
        # (an unknown user answers cold-start-empty, which is honest;
        # a zero row would answer all-zero scores)
        present_u = set(np.unique(rd.users).astype(str))
        present_i = set(np.unique(rd.items).astype(str))
        user_ix, _ = model.user_ix.grow(
            u for u in map(str, touched_users) if u in present_u)
        item_ix, _ = model.item_ix.grow(
            i for i in map(str, touched_items) if i in present_i)
        ui = user_ix.to_indices_array(rd.users)
        ii = item_ix.to_indices_array(rd.items)
        keep = (ui >= 0) & (ii >= 0)
        ui, ii, vals = dedup_ratings(ui[keep], ii[keep], rd.vals[keep],
                                     rd.ts[keep], prep.dedup)
        coo = RatingsCOO(ui, ii, vals, len(user_ix), len(item_ix))
        tu = user_ix.to_indices([str(u) for u in touched_users])
        ti = item_ix.to_indices([str(i) for i in touched_items])
        from predictionio_tpu.ops.als import default_compute_dtype
        from predictionio_tpu.parallel.sharded_table import is_sharded
        sharded = is_sharded(model.als.user_factors)
        cfg = FoldInConfig(
            lam=p.lam, sweeps=2,
            compute_dtype=p.compute_dtype or default_compute_dtype(),
            sweep_chunk=p.sweep_chunk,
            factor_sharding="model" if sharded else "replicated")
        # residency slot per deployed algorithm instance: consecutive
        # ticks through the same scheduler reuse the device tables and
        # upload only touched-row plans (fold_in_coo validates the slot
        # against the model's host arrays, so a swapped-out model misses)
        new_als, stats = fold_in_coo(
            model.als, coo, tu[tu >= 0], ti[ti >= 0], cfg,
            resident_key=f"fold:{type(self).__name__}:{id(self)}")
        if stats.degenerate:
            # nothing solvable this tick (ISSUE 5 satellite: touched
            # set emptied by filtering, or all-zero ratings): keep the
            # deployed model OBJECT so the scheduler can tell a no-op
            # from a publishable fold
            return model, {"algorithm": type(self).__name__,
                           "degenerate": True, "wallS": stats.wall_s}
        item_properties = model.item_properties
        if item_properties is not None and len(item_ix) > len(item_properties):
            # new items: carry fresh $set properties when the data source
            # read them, else None (no filter metadata yet)
            items = td.items or {}
            item_properties = list(item_properties) + [
                items.get(item_ix.id_of(ix))
                for ix in range(len(item_properties), len(item_ix))]
        cats, years = RecommendationModel.derive_filters(item_properties)
        new_model = RecommendationModel(
            new_als, user_ix, item_ix, item_properties=item_properties,
            item_categories=cats, item_years=years)
        report = {
            "algorithm": type(self).__name__,
            "loss": als_rmse(new_als, coo),
            "userRows": stats.n_user_rows, "itemRows": stats.n_item_rows,
            "newUsers": stats.n_new_users, "newItems": stats.n_new_items,
            "wallS": stats.wall_s, "residentHit": stats.resident_hit,
            "sentinelRollback": stats.sentinel_rollback,
            "guardWallS": stats.guard_wall_s,
        }
        if stats.sharded:
            report["sharding"] = {
                "layout": "model",
                "shards": new_als.user_factors.n_shards}
        return new_model, report

    # -- compile plane (ISSUE 9) -------------------------------------------
    def aot_warm_specs(self, model, batch_hint: int = 16):
        """(label, bucket-dims) rows for this model's serve executables
        — consumed by ``compile.aot.warm_models`` at deploy / hot-swap /
        canary-stage time so the FIRST query after a swap compiles
        nothing. Covers the micro-batcher's coalescing ladder (1..the
        configured window, pow2) and the gates golden-replay bucket
        (the probe answers through the same executable)."""
        from predictionio_tpu.compile import buckets as B
        from predictionio_tpu.obs import costmon
        from predictionio_tpu.ops.als import (batch_predict_dims,
                                              register_aot_specs)
        register_aot_specs()
        batches = sorted({1} | {1 << e for e in range(
            1, B.bucket_batch(max(batch_hint, 1)).bit_length())})
        return [(costmon.BATCH_PREDICT,
                 batch_predict_dims(model.als, b, 16))
                for b in batches]

    def batch_predict(self, model, queries):
        """Evaluation/serving path: one batched device top-k for all known
        users (vs the reference's per-query driver loop), through the
        compile plane — vocab/batch/k shape-buckets + AOT registry
        dispatch (ops.als.users_topk_serve), so a warmed server answers
        with zero trace and zero compile. Queries carrying category/year
        filters take a second batched call with per-query candidate
        masks."""
        return self.batch_predict_begin(model, queries)()

    def batch_predict_begin(self, model, queries):
        """Two-phase batch predict for the pipelined serving executor
        (ISSUE 14): partition + enqueue the device top-k NOW (async
        dispatch returns the moment the work is queued) and return
        ``finish() -> [(ix, result)]`` performing the deferred
        device->host readback and result building — the completion
        stage, callable from another thread, so window N's readback /
        serialization overlaps window N+1's formation and dispatch."""
        props_of = model.properties_of(self.params.return_properties)
        out = {ix: ItemScoreResult(()) for ix, _ in queries}
        plain, masked = [], []
        for ix, q in queries:
            uix = int(model.user_ix.get(q.user, -1))
            if uix < 0:
                logger.info("No prediction for unknown user %s.", q.user)
                continue
            mask = model.allowed_mask(q)
            (plain if mask is None else masked).append((ix, q, uix, mask))
        plain_fetch = masked_fetch = None
        if plain:
            from predictionio_tpu.ops.als import users_topk_serve_begin
            k_max = min(max(q.num for _, q, _, _ in plain),
                        model.als.n_items)
            # compile attribution (obs/costmon): a gates golden-query
            # replay keeps its gates_probe label; live serving books
            # under batch_predict
            from predictionio_tpu.obs import costmon
            with costmon.executable(costmon.BATCH_PREDICT,
                                    defer_to_outer=True):
                plain_fetch = users_topk_serve_begin(
                    model.als, [uix for _, _, uix, _ in plain], k_max)
        if masked:
            from predictionio_tpu.ops.similarity import \
                masked_top_k_batch_begin
            from predictionio_tpu.parallel.sharded_table import table_rows
            k_max = max(q.num for _, q, _, _ in masked)
            masked_fetch = masked_top_k_batch_begin(
                model.als.item_factors,
                table_rows(model.als.user_factors,
                           [uix for _, _, uix, _ in masked]),
                np.stack([mask for _, _, _, mask in masked]),
                k_max, filter_positive=False)

        def finish():
            from predictionio_tpu.ops.similarity import unpack_top_k_rows
            if plain_fetch is not None:
                scores, idx = plain_fetch()
                for row, (ix, q, _, _) in enumerate(plain):
                    # bucketed k may exceed n_items: padding slots carry
                    # -inf and are dropped here
                    s, i = unpack_top_k_rows(scores[row], idx[row],
                                             q.num)
                    out[ix] = top_scores_to_result(
                        model.item_ix, s, i, properties_of=props_of)
            if masked_fetch is not None:
                scores, idx = masked_fetch()
                for row, (ix, q, _, _) in enumerate(masked):
                    s, i = unpack_top_k_rows(scores[row], idx[row],
                                             q.num)
                    out[ix] = top_scores_to_result(
                        model.item_ix, s, i, properties_of=props_of)
            return list(out.items())
        return finish


class ShardedALSModelCheckpoint(PersistentModel, PersistentModelLoader):
    """Persistence mode 2 for the mesh model: factor tables checkpoint
    through orbax/tensorstore (each host writes its shards; restore
    re-shards on read) instead of being gathered into a pickle — the
    TPU-native replacement for the reference's 'persist the model RDD'
    pattern (controller/PersistentModel.scala:64; SURVEY §5
    checkpoint/resume). Only a manifest naming this loader is stored in
    MODELDATA."""

    def __init__(self, model: Optional[RecommendationModel] = None):
        self.model = model

    def save(self, instance_id: str, params) -> bool:
        import os
        from predictionio_tpu.parallel.sharded_table import is_sharded
        from predictionio_tpu.utils.checkpoint import (checkpoint_dir,
                                                       save_sharded)

        def _np(t):
            return t.to_numpy() if is_sharded(t) else t

        d = checkpoint_dir(instance_id)
        ok = save_sharded(
            os.path.join(d, "factors"),
            {"user_factors": _np(self.model.als.user_factors),
             "item_factors": _np(self.model.als.item_factors)})
        np.savez(os.path.join(d, "vocab.npz"),
                 users=np.asarray(self.model.user_ix._ids, dtype=str),
                 items=np.asarray(self.model.item_ix._ids, dtype=str))
        return ok

    def load(self, instance_id: str, params) -> "RecommendationModel":
        import os
        from predictionio_tpu.data.bimap import BiMap
        from predictionio_tpu.utils.checkpoint import (checkpoint_dir,
                                                       restore_sharded)
        d = checkpoint_dir(instance_id)
        arrays = restore_sharded(os.path.join(d, "factors"))
        with np.load(os.path.join(d, "vocab.npz")) as z:
            user_ix = EntityIdIxMap(BiMap(
                {str(u): i for i, u in enumerate(z["users"])}))
            item_ix = EntityIdIxMap(BiMap(
                {str(it): i for i, it in enumerate(z["items"])}))
        uf = np.asarray(arrays["user_factors"], dtype=np.float32)
        vf = np.asarray(arrays["item_factors"], dtype=np.float32)
        als = ALSModel(user_factors=uf, item_factors=vf,
                       rank=uf.shape[1])
        return RecommendationModel(als, user_ix, item_ix)


class MeshALSAlgorithm(ALSAlgorithm):
    """P-placement variant: factor tables are trained AND SERVED
    model-sharded across the mesh — nothing is ever replicated to one
    device, so catalogs larger than a single chip's HBM serve directly
    (reference: controller/PAlgorithm.scala:44-125 distributed-model
    lookup; enable with algorithm name 'als-mesh' in engine.json).
    Persistence: sharded checkpoint + manifest (ShardedALSModelCheckpoint)
    instead of the PAlgorithm retrain-on-deploy default."""
    placement = "mesh"

    def make_persistent_model(self, model: RecommendationModel):
        return ShardedALSModelCheckpoint(model)

    def train(self, pd: PreparedData) -> RecommendationModel:
        p = self.params
        if pd.ratings_coo.nnz == 0:
            raise ValueError("No ratings to train on")
        from predictionio_tpu.ops.als import default_compute_dtype
        cfg = ALSConfig(rank=p.rank, iterations=p.num_iterations, lam=p.lam,
                        sweep_chunk=p.sweep_chunk,
                        seed=p.seed if p.seed is not None else 0,
                        compute_dtype=p.compute_dtype
                        or default_compute_dtype(),
                        factor_sharding="model")
        self.last_train_telemetry = {}
        model = als_train(pd.ratings_coo, cfg,
                          telemetry=self.last_train_telemetry)
        item_properties = None
        if pd.items is not None:
            item_properties = [pd.items.get(pd.item_ix.id_of(ix))
                               for ix in range(len(pd.item_ix))]
        cats, years = RecommendationModel.derive_filters(item_properties)
        return RecommendationModel(model, pd.user_ix, pd.item_ix,
                                   item_properties=item_properties,
                                   item_categories=cats, item_years=years)

    def predict(self, model: RecommendationModel, query: Query
                ) -> ItemScoreResult:
        from predictionio_tpu.ops.als import recommend_products_sharded
        uix = model.user_ix.get(query.user, -1)
        if uix < 0:
            logger.info("No prediction for unknown user %s.", query.user)
            return ItemScoreResult(())
        scores, idx = recommend_products_sharded(
            model.als, int(uix), query.num,
            allowed_mask=model.allowed_mask(query))
        return top_scores_to_result(
            model.item_ix, scores, idx,
            properties_of=model.properties_of(
                self.params.return_properties))

    def batch_predict(self, model, queries):
        # sharded ranking is already a collective per query; map predict
        return [(ix, self.predict(model, q)) for ix, q in queries]

    def aot_warm_specs(self, model, batch_hint: int = 16):
        # the sharded serve path runs GSPMD collectives per query —
        # per-process AOT Compiled dispatch does not apply (and the
        # single-device batch_predict executable is never used here)
        return []


class PrecisionAtK(Metric):
    """Precision@K with a positive-rating threshold (the recommendation
    template's tuning metric). None (skipped) when a user has no positive
    actuals, matching OptionAverageMetric semantics."""

    def __init__(self, k: int = 10, rating_threshold: float = 2.0):
        self.k = k
        self.rating_threshold = rating_threshold

    def header(self) -> str:
        return f"PrecisionAtK(k={self.k}, threshold={self.rating_threshold})"

    def calculate(self, eval_data) -> float:
        vals = []
        for _, qpa in eval_data:
            for q, p, a in qpa:
                positives = {r.item for r in a.ratings
                             if r.rating >= self.rating_threshold}
                if not positives:
                    continue
                top = [s.item for s in p.item_scores[:self.k]]
                if not top:
                    vals.append(0.0)
                    continue
                hits = sum(1 for item in top if item in positives)
                vals.append(hits / min(self.k, len(top)))
        return float("nan") if not vals else float(np.mean(vals))


class RecommendationEngineFactory(EngineFactory):
    @classmethod
    def apply(cls) -> Engine:
        return Engine(
            {"": RecommendationDataSource},
            {"": RecommendationPreparator},
            {"als": ALSAlgorithm, "als-mesh": MeshALSAlgorithm},
            {"": FirstServing})

    @classmethod
    def engine_params(cls, key: str = "") -> EngineParams:
        return EngineParams(
            data_source_params=("", DataSourceParams()),
            preparator_params=("", PreparatorParams()),
            algorithm_params_list=[("als", ALSAlgorithmParams())],
            serving_params=("", None))
