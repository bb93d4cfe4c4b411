"""Core train/eval drivers with instance lifecycle records.

Rebuilds the reference's ``CoreWorkflow``
(reference: core/src/main/scala/io/prediction/workflow/CoreWorkflow.scala:
runTrain :42-99 — EngineInstance INIT -> train -> Kryo models ->
Models.insert -> status COMPLETED; runEvaluation :101-160 —
EvaluationInstance lifecycle with rendered results). Pickle of host-side
pytrees replaces Kryo; the SparkContext is replaced by the ambient device
mesh (parallel.mesh.current_mesh), created lazily by kernels.
"""

from __future__ import annotations

import datetime as _dt
import json
import logging
import traceback
from typing import Optional, Sequence

from predictionio_tpu.core.engine import (Engine, EngineParams,
                                          WorkflowParams)
from predictionio_tpu.core.evaluation import Evaluation, MetricEvaluator
from predictionio_tpu.data.storage.base import (EngineInstance,
                                                EvaluationInstance, Model)
from predictionio_tpu.data.storage.registry import Storage
from predictionio_tpu.obs import TRACER, get_registry, jaxmon
from predictionio_tpu.parallel.mesh import device_platform

logger = logging.getLogger(__name__)


def _now():
    return _dt.datetime.now(_dt.timezone.utc)


def _stage_hist():
    """Process-wide per-stage training timings (ISSUE 2): one labeled
    histogram instead of ad-hoc log lines, exposed on every /metrics
    through the registry parent chain."""
    return get_registry().histogram(
        "pio_train_stage_seconds",
        "Wall time of core-workflow stages, labeled by stage",
        buckets=(0.1, 0.5, 1.0, 5.0, 15.0, 60.0, 300.0, 1800.0),
        labelnames=("stage",))


def _timed_stage(hist, stage: str):
    """Context manager: one span + one histogram observation."""
    import contextlib
    import time

    @contextlib.contextmanager
    def cm():
        t0 = time.perf_counter()
        with TRACER.span(stage):
            yield
        hist.labels(stage=stage).observe(time.perf_counter() - t0)
    return cm()


def _train_report(device: dict, result) -> dict:
    """What ran where: the platform this process resolved, what each
    device holds and held at peak (where the backend reports it), the
    stage walls ``Engine.train`` measured, and — from algorithms that expose
    ``last_train_telemetry`` (the ALS family) — the solver / compute
    dtype ``auto`` resolved to plus the per-phase train walls."""
    report = {"platform": device["platform"],
              "device_kind": device["device_kind"],
              "device_count": device["n"],
              "devices": [dict(device=dev, **kinds) for dev, kinds
                          in sorted(jaxmon.device_memory().items())],
              "stages": {k: round(v, 3)
                         for k, v in result.stage_seconds.items()}}
    for algo in result.algorithms:
        tel = getattr(algo, "last_train_telemetry", None)
        if tel:
            report["algorithm"] = {
                k: (round(v, 4) if isinstance(v, float) else v)
                for k, v in tel.items()}
            break
    return report


def _report_env(report: dict) -> dict:
    """The slice of the train report persisted on the EngineInstance
    (``env`` is a str->str map): the deploy side reads it back into
    ``/stats.json`` so an operator can tell which device, solver and
    dtype produced the model being served."""
    env = {"platform": report["platform"],
           "device_kind": report["device_kind"],
           "device_count": str(report["device_count"])}
    algo = report.get("algorithm", {})
    for key in ("solver", "compute_dtype"):
        if key in algo:
            env[key] = str(algo[key])
    return env


def run_train(engine: Engine, engine_params: EngineParams,
              engine_id: str = "default", engine_version: str = "0",
              engine_variant: str = "default",
              engine_factory: str = "",
              env: Optional[dict] = None,
              workflow_params: WorkflowParams = WorkflowParams()) -> str:
    """Train and persist; returns the EngineInstance id
    (CoreWorkflow.runTrain)."""
    instances = Storage.get_meta_data_engine_instances()
    ep_json = engine.engine_params_to_json(engine_params)
    instance = EngineInstance(
        id="", status="INIT", start_time=_now(), end_time=_now(),
        engine_id=engine_id, engine_version=engine_version,
        engine_variant=engine_variant, engine_factory=engine_factory,
        batch=workflow_params.batch, env=env or {},
        data_source_params=json.dumps(ep_json.get("datasource", {})),
        preparator_params=json.dumps(ep_json.get("preparator", {})),
        algorithms_params=json.dumps(ep_json.get("algorithms", [])),
        serving_params=json.dumps(ep_json.get("serving", {})))
    # resolve the platform BEFORE the INIT record: a trainer that cannot
    # get the chip fails here, not after minutes of host-side read
    device = device_platform()
    instance_id = instances.insert(instance)
    instance = instances.get(instance_id)
    hist = _stage_hist()
    jaxmon.install()
    jaxmon.install_device_memory_gauge()
    from predictionio_tpu.obs.flight import FLIGHT
    FLIGHT.record("train_start", model_version=instance_id,
                  engine=engine_id)
    try:
        with TRACER.trace("train", instance=instance_id,
                          engine=engine_id):
            with _timed_stage(hist, "train"):
                result = engine.train(engine_params, workflow_params)
            report = _train_report(device, result)
            logger.info("Train report: %s", json.dumps(report))
            if workflow_params.save_model:
                with _timed_stage(hist, "serialize"):
                    serializable = engine.make_serializable_models(
                        result, instance_id, engine_params)
                    blob = engine.serialize_models(serializable)
                with _timed_stage(hist, "persist"):
                    Storage.get_model_data_models().insert(
                        Model(instance_id, blob))
            instances.update(instance.with_(
                status="COMPLETED", end_time=_now(),
                env={**instance.env, **_report_env(report)}))
        FLIGHT.record("train_end", model_version=instance_id,
                      status="COMPLETED")
        logger.info("Training completed: engine instance %s", instance_id)
        return instance_id
    except Exception:
        logger.error("Training failed:\n%s", traceback.format_exc())
        FLIGHT.record("train_end", model_version=instance_id,
                      status="ABORTED")
        instances.update(instance.with_(status="ABORTED", end_time=_now()))
        raise


def run_evaluation(engine: Engine, evaluation: Evaluation,
                   engine_params_list: Sequence[EngineParams],
                   evaluation_class: str = "",
                   engine_params_generator_class: str = "",
                   env: Optional[dict] = None,
                   output_path: Optional[str] = None,
                   workflow_params: WorkflowParams = WorkflowParams()) -> str:
    """Evaluate a params sweep and record results; returns the
    EvaluationInstance id (CoreWorkflow.runEvaluation)."""
    device_platform()
    dao = Storage.get_meta_data_evaluation_instances()
    instance = EvaluationInstance(
        status="INIT", start_time=_now(), end_time=_now(),
        evaluation_class=evaluation_class,
        engine_params_generator_class=engine_params_generator_class,
        batch=workflow_params.batch, env=env or {})
    instance_id = dao.insert(instance)
    instance = dao.get(instance_id)
    try:
        assert evaluation.metric is not None, "Evaluation.metric must be set"
        evaluator = MetricEvaluator(evaluation.metric,
                                    list(evaluation.metrics),
                                    output_path=output_path)
        with TRACER.trace("evaluation", instance=instance_id), \
                _timed_stage(_stage_hist(), "evaluate"):
            result = evaluator.evaluate_base(engine, engine_params_list,
                                             workflow_params)
        dao.update(instance.with_(
            status="EVALCOMPLETED", end_time=_now(),
            evaluator_results=result.one_liner(),
            evaluator_results_html=result.to_html(),
            evaluator_results_json=result.to_json(engine)))
        logger.info("Evaluation completed: %s", result.one_liner())
        return instance_id
    except Exception:
        logger.error("Evaluation failed:\n%s", traceback.format_exc())
        dao.update(instance.with_(status="ABORTED", end_time=_now()))
        raise
