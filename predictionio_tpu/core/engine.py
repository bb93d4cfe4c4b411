"""The Engine: chains DASE components into train / eval pipelines.

Rebuilds the reference's ``Engine``
(reference: core/src/main/scala/io/prediction/controller/Engine.scala —
static train pipeline with sanity checks + stop-gates :621-708, eval
cross-product :726-816, params-from-JSON :353, prepareDeploy :196-265)
and ``WorkflowParams`` (workflow/WorkflowParams.scala:29-37).

TPU note: the pipeline itself is host-side control flow; all device work
happens inside component methods. `serialize_models` converts any jax.Array
leaves to host numpy before pickling (the Kryo analog), so models trained on
the mesh persist portably; mesh-resident (PAlgorithm) models instead use the
PersistentModel manifest path or retrain-on-deploy.
"""

from __future__ import annotations

import logging
import pickle
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple, Type

from predictionio_tpu.core.base import (Algorithm, DataSource, Doer,
                                        Preparator, Serving, run_sanity_check)
from predictionio_tpu.core.params import (EmptyParams, Params,
                                          params_from_dict, params_to_dict)
from predictionio_tpu.core.persistence import (RETRAIN, PersistentModel,
                                               PersistentModelManifest,
                                               load_persistent_model)

logger = logging.getLogger(__name__)


class StopAfterReadInterruption(Exception):
    pass


class StopAfterPrepareInterruption(Exception):
    pass


@dataclass(frozen=True)
class WorkflowParams:
    """(workflow/WorkflowParams.scala:29-37); sparkEnv becomes mesh config."""
    batch: str = ""
    verbose: int = 10
    save_model: bool = True
    skip_sanity_check: bool = False
    stop_after_read: bool = False
    stop_after_prepare: bool = False


@dataclass(frozen=True)
class EngineParams:
    """Named params for each DASE slot (controller/EngineParams.scala:32-80)."""
    data_source_params: Tuple[str, Any] = ("", EmptyParams())
    preparator_params: Tuple[str, Any] = ("", EmptyParams())
    algorithm_params_list: Sequence[Tuple[str, Any]] = field(
        default_factory=lambda: [("", EmptyParams())])
    serving_params: Tuple[str, Any] = ("", EmptyParams())


@dataclass
class TrainResult:
    models: List[Any]                # one per algorithm
    algorithms: List[Algorithm]      # the instances that trained them
    # wall seconds of the read / prepare / train stages of Engine.train
    # (empty for a prepare_deploy restore)
    stage_seconds: Dict[str, float] = field(default_factory=dict)


def _params_class_of(cls) -> Optional[Type[Params]]:
    return getattr(cls, "PARAMS_CLASS", None)


def _build_params(cls, raw: Optional[dict]):
    pc = _params_class_of(cls)
    if pc is not None:
        return params_from_dict(pc, raw)
    return raw if raw else EmptyParams()


class Engine:
    """An engine is class-maps for each DASE slot plus default params
    (controller/Engine.scala:154)."""

    def __init__(self,
                 data_source_class_map,
                 preparator_class_map,
                 algorithm_class_map,
                 serving_class_map):
        def as_map(x):
            return x if isinstance(x, dict) else {"": x}
        self.data_source_class_map: Dict[str, type] = as_map(data_source_class_map)
        self.preparator_class_map: Dict[str, type] = as_map(preparator_class_map)
        self.algorithm_class_map: Dict[str, type] = as_map(algorithm_class_map)
        self.serving_class_map: Dict[str, type] = as_map(serving_class_map)

    # -- component instantiation -------------------------------------------
    def _lookup(self, class_map: Dict[str, type], name: str, slot: str) -> type:
        if name not in class_map:
            raise KeyError(
                f"{slot} '{name}' not found; available: {sorted(class_map)}")
        return class_map[name]

    def make_data_source(self, ep: EngineParams) -> DataSource:
        name, params = ep.data_source_params
        return Doer.apply(self._lookup(self.data_source_class_map, name,
                                       "datasource"), params)

    def make_preparator(self, ep: EngineParams) -> Preparator:
        name, params = ep.preparator_params
        return Doer.apply(self._lookup(self.preparator_class_map, name,
                                       "preparator"), params)

    def make_algorithms(self, ep: EngineParams) -> List[Algorithm]:
        return [Doer.apply(self._lookup(self.algorithm_class_map, name,
                                        "algorithm"), params)
                for name, params in ep.algorithm_params_list]

    def make_serving(self, ep: EngineParams) -> Serving:
        name, params = ep.serving_params
        return Doer.apply(self._lookup(self.serving_class_map, name,
                                       "serving"), params)

    # -- train (Engine.scala:621-708) --------------------------------------
    def train(self, engine_params: EngineParams,
              workflow_params: WorkflowParams = WorkflowParams()) -> TrainResult:
        check = not workflow_params.skip_sanity_check
        stage_seconds = {}
        t0 = time.perf_counter()
        data_source = self.make_data_source(engine_params)
        td = data_source.read_training()
        run_sanity_check(td, check)
        stage_seconds["read"] = time.perf_counter() - t0
        if workflow_params.stop_after_read:
            raise StopAfterReadInterruption()

        t0 = time.perf_counter()
        preparator = self.make_preparator(engine_params)
        pd = preparator.prepare(td)
        run_sanity_check(pd, check)
        stage_seconds["prepare"] = time.perf_counter() - t0
        if workflow_params.stop_after_prepare:
            raise StopAfterPrepareInterruption()

        t0 = time.perf_counter()
        algorithms = self.make_algorithms(engine_params)
        models = []
        for i, algo in enumerate(algorithms):
            logger.info("Training algorithm %d/%d: %s",
                        i + 1, len(algorithms), type(algo).__name__)
            model = algo.train(pd)
            run_sanity_check(model, check)
            models.append(model)
        stage_seconds["train"] = time.perf_counter() - t0
        return TrainResult(models=models, algorithms=algorithms,
                           stage_seconds=stage_seconds)

    # -- eval (Engine.scala:726-816) ---------------------------------------
    def eval(self, engine_params: EngineParams,
             workflow_params: WorkflowParams = WorkflowParams()
             ) -> List[Tuple[Any, List[Tuple[Any, Any, Any]]]]:
        """Per eval-set: train on its training split, batch-predict every
        algorithm over the queries, serve, and join with actuals.
        Returns [(evalInfo, [(query, prediction, actual)])]."""
        data_source = self.make_data_source(engine_params)
        eval_sets = data_source.read_eval()
        serving = self.make_serving(engine_params)
        out = []
        for td, eval_info, qa in eval_sets:
            preparator = self.make_preparator(engine_params)
            pd = preparator.prepare(td)
            algorithms = self.make_algorithms(engine_params)
            models = [a.train(pd) for a in algorithms]
            qa_list = list(qa)
            indexed = [(ix, serving.supplement(q))
                       for ix, (q, _) in enumerate(qa_list)]
            # per-algo batch predict, joined by query index
            per_algo: List[Dict[int, Any]] = []
            for algo, model in zip(algorithms, models):
                per_algo.append(dict(algo.batch_predict(model, indexed)))
            qpa = []
            for ix, (q, a) in enumerate(qa_list):
                preds = [pa[ix] for pa in per_algo]
                qpa.append((q, serving.serve(q, preds), a))
            out.append((eval_info, qpa))
        return out

    def batch_eval(self, engine_params_list: Sequence[EngineParams],
                   workflow_params: WorkflowParams = WorkflowParams()):
        """(core/BaseEngine.scala:79) — evaluate many params settings."""
        return [(ep, self.eval(ep, workflow_params))
                for ep in engine_params_list]

    # -- persistence (Engine.scala:282, :196-265) --------------------------
    def make_serializable_models(self, train_result: TrainResult,
                                 instance_id: str,
                                 engine_params: EngineParams) -> List[Any]:
        """Per algorithm: model | PersistentModelManifest | RETRAIN."""
        out = []
        algo_params = list(engine_params.algorithm_params_list)
        for (name, params), algo, model in zip(
                algo_params, train_result.algorithms, train_result.models):
            decision = algo.make_persistent_model(model)
            if isinstance(decision, PersistentModel):
                if decision.save(instance_id, params):
                    out.append(PersistentModelManifest(
                        type(decision).loader_name()))
                else:
                    out.append(decision)
            else:
                out.append(decision)  # model object or RETRAIN
        return out

    def serialize_models(self, serializable_models: List[Any]) -> bytes:
        from predictionio_tpu.utils.arrays import to_host
        return pickle.dumps([to_host(m) for m in serializable_models],
                            protocol=pickle.HIGHEST_PROTOCOL)

    def deserialize_models(self, blob: bytes) -> List[Any]:
        return pickle.loads(blob)

    def prepare_deploy(self, engine_params: EngineParams,
                       persisted_models: List[Any], instance_id: str,
                       workflow_params: WorkflowParams = WorkflowParams()
                       ) -> TrainResult:
        """Restore models for serving (Engine.scala:196-265): manifests are
        loaded via their loader; RETRAIN models re-run the train pipeline."""
        algorithms = self.make_algorithms(engine_params)
        algo_params = list(engine_params.algorithm_params_list)
        needs_retrain = any(m is RETRAIN for m in persisted_models)
        retrained: Optional[TrainResult] = None
        if needs_retrain:
            logger.info("Some models request retrain-on-deploy; re-training")
            retrained = self.train(engine_params, workflow_params)
        models = []
        for i, m in enumerate(persisted_models):
            if m is RETRAIN:
                models.append(retrained.models[i])
            elif isinstance(m, PersistentModelManifest):
                models.append(load_persistent_model(
                    m, instance_id, algo_params[i][1]))
            else:
                models.append(m)
        return TrainResult(models=models, algorithms=algorithms)

    # -- engine.json params (Engine.scala:353 jValueToEngineParams) --------
    def json_to_engine_params(self, variant: dict) -> EngineParams:
        def one(slot_key: str, class_map: Dict[str, type]):
            block = variant.get(slot_key) or {}
            name = block.get("name", "")
            cls = self._lookup(class_map, name, slot_key)
            return (name, _build_params(cls, block.get("params")))

        ds = one("datasource", self.data_source_class_map)
        prep = one("preparator", self.preparator_class_map)
        serv = one("serving", self.serving_class_map)
        algo_blocks = variant.get("algorithms")
        if algo_blocks is None:
            algo_blocks = [{"name": "", "params": {}}]
        algos = []
        for block in algo_blocks:
            name = block.get("name", "")
            cls = self._lookup(self.algorithm_class_map, name, "algorithm")
            algos.append((name, _build_params(cls, block.get("params"))))
        return EngineParams(data_source_params=ds, preparator_params=prep,
                            algorithm_params_list=algos, serving_params=serv)

    def engine_params_to_json(self, ep: EngineParams) -> dict:
        def one(pair):
            name, params = pair
            return {"name": name, "params": params_to_dict(params)
                    if not isinstance(params, dict) else params}
        return {
            "datasource": one(ep.data_source_params),
            "preparator": one(ep.preparator_params),
            "algorithms": [one(p) for p in ep.algorithm_params_list],
            "serving": one(ep.serving_params),
        }


class SimpleEngine(Engine):
    """DataSource + single algorithm shortcut
    (controller/EngineParams.scala:127)."""

    def __init__(self, data_source_class, algorithm_class,
                 serving_class=None):
        from predictionio_tpu.core.base import (FirstServing,
                                                IdentityPreparator)
        super().__init__(data_source_class, IdentityPreparator,
                         algorithm_class, serving_class or FirstServing)


class EngineFactory:
    """Engine + default params provider (controller/EngineFactory.scala:28-33).
    Subclasses override apply(); registered under a dotted name used by
    engine.json's engineFactory field."""

    @classmethod
    def apply(cls) -> Engine:
        raise NotImplementedError

    @classmethod
    def engine_params(cls, key: str = "") -> EngineParams:
        """Programmatic engine parameters; `key` selects among named
        sets when a factory defines them (`pio train
        --engine-params-key`, EngineFactory.scala:33 — the reference's
        default likewise ignores the key and returns defaults)."""
        return EngineParams()
