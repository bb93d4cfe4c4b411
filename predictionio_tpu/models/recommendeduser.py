"""Recommended-user engine template: implicit ALS over follow events.

Rebuilds `scala-parallel-similarproduct/recommended-user` (reference:
examples/scala-parallel-similarproduct/recommended-user/src/main/scala/ —
DataSource.scala:30-85 reads `$set` user entities and `(user, follow,
followedUser)` events; ALSAlgorithm.scala:60-110 runs `ALS.trainImplicit`
over (user, followedUser, 1) triples; predict :110-165 scores every
followed user by summed cosine similarity of the query users' factors with
white/black-list filters, query users excluded, score > 0 kept).

The serve path is the same masked-matmul + on-device top-k as the
similarproduct template — the "item" table is the followed-user factor
table.
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
from predictionio_tpu.data.store import PEventStore
from predictionio_tpu.models.common import RatingsData, resolve_ids
from predictionio_tpu.ops.als import ALSConfig, als_train
from predictionio_tpu.ops.ratings import RatingsCOO, dedup_ratings
from predictionio_tpu.ops.similarity import (build_filter_mask, cosine_top_k,
                                             normalize_rows)

logger = logging.getLogger(__name__)


@dataclass(frozen=True)
class FollowEvent:
    user: str
    followed_user: str
    t: int = 0


@dataclass
class TrainingData(SanityCheck):
    """follow_events is columnar (RatingsData: users=follower,
    items=followed); FollowEvent row lists are accepted and converted."""
    users: Dict[str, dict]
    follow_events: RatingsData

    def __post_init__(self):
        if isinstance(self.follow_events, (list, tuple)):
            self.follow_events = RatingsData(
                np.array([e.user for e in self.follow_events], dtype=str),
                np.array([e.followed_user for e in self.follow_events],
                         dtype=str),
                np.ones(len(self.follow_events), dtype=np.float32),
                np.array([e.t for e in self.follow_events],
                         dtype=np.int64))

    def sanity_check(self):
        if not len(self.follow_events):
            raise ValueError("follow_events is empty; check the data source")


@dataclass(frozen=True)
class Query:
    """(Engine.scala:6-11: users list + num + white/black lists)"""
    users: Tuple[str, ...]
    num: int
    white_list: Optional[Tuple[str, ...]] = None
    black_list: Optional[Tuple[str, ...]] = None

    @staticmethod
    def from_dict(d: dict) -> "Query":
        def opt(key):
            v = d.get(key)
            return tuple(v) if v is not None else None
        return Query(users=tuple(d["users"]), num=int(d["num"]),
                     white_list=opt("whiteList"), black_list=opt("blackList"))


@dataclass(frozen=True)
class UserScore:
    user: str
    score: float


@dataclass(frozen=True)
class UserScoreResult:
    """PredictedResult of similarUserScores (ALSAlgorithm.scala:160-165)."""
    similar_user_scores: Tuple[UserScore, ...]

    def to_dict(self) -> dict:
        return {"similarUserScores": [{"user": s.user, "score": s.score}
                                      for s in self.similar_user_scores]}


@dataclass
class PreparedData:
    td: TrainingData


@dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = "default"
    channel_name: Optional[str] = None


class RecommendedUserDataSource(DataSource):
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
        # columnar ingest: flat arrays, no per-event Python objects
        fc = PEventStore.find_columnar(
            app_name=app, channel_name=chan, entity_type="user",
            event_names=["follow"], target_entity_type="user")
        follows = RatingsData(fc["entity_id"], fc["target_entity_id"],
                              np.ones(len(fc["t"]), dtype=np.float32),
                              fc["t"])
        return TrainingData(users=users, follow_events=follows)


class RecommendedUserPreparator(Preparator):
    def prepare(self, td: TrainingData) -> PreparedData:
        return PreparedData(td)


@dataclass(frozen=True)
class ALSAlgorithmParams(Params):
    rank: int = 10
    num_iterations: int = 20
    lam: float = 0.01
    seed: Optional[int] = None
    compute_dtype: Optional[str] = None  # None = bf16 on TPU, f32 on CPU
    # solver-call batching (ops/als.ALSConfig.sweep_chunk; 0 = auto)
    sweep_chunk: int = 0


@dataclass
class RecommendedUserModel:
    """similarUserFeatures + id map (ALSAlgorithm.scala ALSModel)."""
    followed_factors_normalized: np.ndarray   # [F, R] L2-normalized rows
    followed_ix: EntityIdIxMap


class RecommendedUserALSAlgorithm(P2LAlgorithm):
    PARAMS_CLASS = ALSAlgorithmParams
    QUERY_CLASS = Query

    def __init__(self, params=None):
        super().__init__(params or ALSAlgorithmParams())

    def train(self, pd: PreparedData) -> RecommendedUserModel:
        td = pd.td
        p = self.params
        if not len(td.follow_events):
            raise ValueError("No follow events to train on")
        fd = td.follow_events
        follower_ix, ui = EntityIdIxMap.build_with_indices(fd.users)
        followed_ix, ii = EntityIdIxMap.build_with_indices(fd.items)
        ui, ii, counts = dedup_ratings(ui, ii, fd.vals, policy="sum")
        coo = RatingsCOO(ui, ii, counts, len(follower_ix), len(followed_ix))
        from predictionio_tpu.ops.als import default_compute_dtype
        cfg = ALSConfig(rank=p.rank, iterations=p.num_iterations, lam=p.lam,
                        sweep_chunk=p.sweep_chunk,
                        implicit_prefs=True, alpha=1.0,
                        seed=p.seed if p.seed is not None else 0,
                        compute_dtype=p.compute_dtype
                        or default_compute_dtype())
        self.last_train_telemetry = {}
        model = als_train(coo, cfg,
                          telemetry=self.last_train_telemetry)
        return RecommendedUserModel(
            followed_factors_normalized=normalize_rows(model.item_factors),
            followed_ix=followed_ix)

    def _query_rows(self, model: RecommendedUserModel, query: Query
                    ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Resolve query users to factor rows + the candidate mask."""
        q_ix = resolve_ids(model.followed_ix, query.users)
        if len(q_ix) == 0:
            logger.info("No similarUserFeatures vector for query users %s.",
                        query.users)
            return q_ix, None
        white = (resolve_ids(model.followed_ix, query.white_list)
                 if query.white_list is not None else None)
        black = resolve_ids(model.followed_ix, query.black_list or ())
        mask = build_filter_mask(
            len(model.followed_ix),
            exclude=np.concatenate([q_ix, black]),  # query users excluded
            white_list=white)
        return q_ix, mask

    @staticmethod
    def _to_result(model: RecommendedUserModel, scores: np.ndarray,
                   idx: np.ndarray) -> UserScoreResult:
        return UserScoreResult(tuple(
            UserScore(model.followed_ix.id_of(int(i)), float(s))
            for s, i in zip(scores, idx)))

    def predict(self, model: RecommendedUserModel, query: Query
                ) -> UserScoreResult:
        q_ix, mask = self._query_rows(model, query)
        if mask is None:
            return UserScoreResult(())
        query_vecs = model.followed_factors_normalized[q_ix]
        scores, idx = cosine_top_k(model.followed_factors_normalized,
                                   query_vecs, query.num, mask)
        return self._to_result(model, scores, idx)

    def batch_predict(self, model, queries):
        """Batched path: summed normalized query vectors, one masked
        matmul + top-k device call for the batch."""
        from predictionio_tpu.ops.similarity import (masked_top_k_batch,
                                                     unpack_top_k_rows)
        out = {ix: UserScoreResult(()) for ix, _ in queries}
        rows = []
        for ix, q in queries:
            q_ix, mask = self._query_rows(model, q)
            if mask is None:
                continue
            qsum = model.followed_factors_normalized[q_ix].sum(axis=0)
            rows.append((ix, q, qsum, mask))
        if rows:
            k_max = max(q.num for _, q, _, _ in rows)
            scores, idx = masked_top_k_batch(
                model.followed_factors_normalized,
                np.stack([r[2] for r in rows]),
                np.stack([r[3] for r in rows]), k_max)
            for row, (ix, q, _, _) in enumerate(rows):
                s, i = unpack_top_k_rows(scores[row], idx[row], q.num)
                out[ix] = self._to_result(model, s, i)
        return list(out.items())


class RecommendedUserEngineFactory(EngineFactory):
    @classmethod
    def apply(cls) -> Engine:
        return Engine(
            {"": RecommendedUserDataSource},
            {"": RecommendedUserPreparator},
            {"als": RecommendedUserALSAlgorithm},
            {"": FirstServing})

    @classmethod
    def engine_params(cls, key: str = "") -> EngineParams:
        return EngineParams(
            data_source_params=("", DataSourceParams()),
            preparator_params=("", None),
            algorithm_params_list=[("als", ALSAlgorithmParams())],
            serving_params=("", None))
