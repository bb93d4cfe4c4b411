"""Classification engine template: naive bayes over aggregated user
properties.

Rebuilds `scala-parallel-classification` (reference:
examples/scala-parallel-classification/add-algorithm/src/main/scala/
NaiveBayesAlgorithm.scala:19-25 — MLlib NaiveBayes on `$set`-aggregated user
properties attr0/attr1/attr2 with label `plan`; DataSource.scala
readTraining uses aggregateProperties). Includes the template's evaluation
wiring (k-fold Accuracy, as in the quickstart's Evaluation.scala).
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.core import (AverageMetric, DataSource, Engine,
                                   EngineFactory, EngineParams, FirstServing,
                                   P2LAlgorithm, Params, Preparator,
                                   SanityCheck)
from predictionio_tpu.core.cross_validation import split_data
from predictionio_tpu.data.store import PEventStore
from predictionio_tpu.ops.forest import ForestModel, forest_train
from predictionio_tpu.ops.naive_bayes import (MultinomialNBModel,
                                              multinomial_nb_train)

logger = logging.getLogger(__name__)


@dataclass(frozen=True)
class LabeledPoint:
    label: float
    features: Tuple[float, ...]


@dataclass
class TrainingData(SanityCheck):
    """Columnar: `features` [n, F] float32 and `labels` [n] float64, one
    row a user, as the algorithms take them (no Python object a row)."""
    features: np.ndarray
    labels: np.ndarray

    @classmethod
    def from_points(cls, points: Sequence[LabeledPoint]) -> "TrainingData":
        return cls(np.array([p.features for p in points],
                            np.float32).reshape(len(points), -1),
                   np.array([p.label for p in points], np.float64))

    @property
    def labeled_points(self) -> List[LabeledPoint]:
        return [LabeledPoint(label=float(lab), features=tuple(
                    float(v) for v in row))
                for row, lab in zip(self.features, self.labels)]

    def sanity_check(self):
        if not len(self.labels):
            raise ValueError("labeled_points is empty; check the data source")


@dataclass(frozen=True)
class Query:
    attr0: float
    attr1: float
    attr2: float

    @staticmethod
    def from_dict(d: dict) -> "Query":
        return Query(attr0=float(d["attr0"]), attr1=float(d["attr1"]),
                     attr2=float(d["attr2"]))

    @property
    def features(self) -> np.ndarray:
        return np.array([self.attr0, self.attr1, self.attr2],
                        dtype=np.float32)


@dataclass(frozen=True)
class PredictedResult:
    label: float

    def to_dict(self):
        return {"label": self.label}


@dataclass(frozen=True)
class ActualResult:
    label: float


@dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = "default"
    channel_name: Optional[str] = None
    eval_k: Optional[int] = None  # enable k-fold read_eval when set


class ClassificationDataSource(DataSource):
    PARAMS_CLASS = DataSourceParams

    def __init__(self, params=None):
        super().__init__(params or DataSourceParams())

    def _read_columns(self) -> TrainingData:
        props = PEventStore.aggregate_properties(
            app_name=self.params.app_name,
            channel_name=self.params.channel_name, entity_type="user",
            required=["plan", "attr0", "attr1", "attr2"])
        X = np.empty((len(props), 3), np.float32)
        y = np.empty(len(props), np.float64)
        for row, (entity_id, pm) in enumerate(props.items()):
            try:
                y[row] = pm.get("plan", float)
                X[row] = (pm.get("attr0", float), pm.get("attr1", float),
                          pm.get("attr2", float))
            except Exception as e:
                logger.error("Cannot convert %s to LabeledPoint: %s",
                             entity_id, e)
                raise
        return TrainingData(X, y)

    def read_training(self) -> TrainingData:
        return self._read_columns()

    def read_eval(self):
        if not self.params.eval_k:
            return []
        points = self._read_columns().labeled_points
        return split_data(
            self.params.eval_k, points, None,
            training_data_creator=TrainingData.from_points,
            query_creator=lambda p: Query(*p.features),
            actual_creator=lambda p: ActualResult(p.label))


class ClassificationPreparator(Preparator):
    def prepare(self, td: TrainingData) -> TrainingData:
        return td


@dataclass(frozen=True)
class NaiveBayesAlgorithmParams(Params):
    lam: float = 1.0  # MLlib's lambda smoothing


class NaiveBayesAlgorithm(P2LAlgorithm):
    """(NaiveBayesAlgorithm.scala:19-25)"""
    PARAMS_CLASS = NaiveBayesAlgorithmParams
    QUERY_CLASS = Query

    def __init__(self, params=None):
        super().__init__(params or NaiveBayesAlgorithmParams())

    def train(self, td: TrainingData) -> MultinomialNBModel:
        return multinomial_nb_train(td.features, td.labels,
                                    lam=self.params.lam)

    def predict(self, model: MultinomialNBModel, query: Query
                ) -> PredictedResult:
        return PredictedResult(label=model.predict(query.features))

    def batch_predict(self, model, queries):
        if not queries:
            return []
        X = np.stack([q.features for _, q in queries])
        scores = model.pi[None, :] + X.astype(np.float64) @ model.theta.T
        labels = model.labels[np.argmax(scores, axis=1)]
        return [(ix, PredictedResult(label=float(lab)))
                for (ix, _), lab in zip(queries, labels)]


@dataclass(frozen=True)
class RandomForestAlgorithmParams(Params):
    """Knob-for-knob with the add-algorithm variant's
    RandomForestAlgorithmParams (RandomForestAlgorithm.scala:12-19)."""
    num_classes: int = 4
    num_trees: int = 10
    feature_subset_strategy: str = "auto"
    impurity: str = "gini"
    max_depth: int = 5
    max_bins: int = 32
    seed: int = 42


class RandomForestAlgorithm(P2LAlgorithm):
    """add-algorithm variant (RandomForestAlgorithm.scala:23-52): same
    P2L placement — cluster-scale train, host-resident model — with the
    level-synchronous TPU forest of ops/forest.py replacing MLlib's
    RandomForest.trainClassifier."""
    PARAMS_CLASS = RandomForestAlgorithmParams
    QUERY_CLASS = Query

    def __init__(self, params=None):
        super().__init__(params or RandomForestAlgorithmParams())

    def train(self, td: TrainingData) -> ForestModel:
        p = self.params
        return forest_train(
            td.features, td.labels, num_classes=p.num_classes,
            num_trees=p.num_trees,
            feature_subset_strategy=p.feature_subset_strategy,
            impurity=p.impurity, max_depth=p.max_depth,
            max_bins=p.max_bins, seed=p.seed)

    def predict(self, model: ForestModel, query: Query) -> PredictedResult:
        return PredictedResult(label=model.predict(query.features))

    def batch_predict(self, model, queries):
        if not queries:
            return []
        X = np.stack([q.features for _, q in queries]).astype(np.float32)
        labels = model.predict_batch(X)
        return [(ix, PredictedResult(label=float(lab)))
                for (ix, _), lab in zip(queries, labels)]


class Accuracy(AverageMetric):
    """(quickstart Evaluation.scala Accuracy metric)"""

    def calculate_one(self, query, predicted, actual) -> float:
        return 1.0 if predicted.label == actual.label else 0.0


class ClassificationEngineFactory(EngineFactory):
    @classmethod
    def apply(cls) -> Engine:
        return Engine(
            {"": ClassificationDataSource},
            {"": ClassificationPreparator},
            {"naive": NaiveBayesAlgorithm,
             "randomforest": RandomForestAlgorithm},
            {"": FirstServing})

    @classmethod
    def engine_params(cls, key: str = "") -> EngineParams:
        return EngineParams(
            data_source_params=("", DataSourceParams()),
            preparator_params=("", None),
            algorithm_params_list=[("naive", NaiveBayesAlgorithmParams())],
            serving_params=("", None))
