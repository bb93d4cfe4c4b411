"""The names `benchmark/` takes from the program, held here as the program's
own tests: the benchmark's files may not be edited to follow a refactor, so
a rename, a dropped keyword or a moved argument has to fail on the CPU, in
seconds, before a chip run does. The list is read off

    grep -rn "predictionio_tpu" benchmark/

(jobs/als-train.py, jobs/ials-train.py, jobs/als-train-sharded.py,
jobs/http-queries.py, layer_metrics/cg_iters_run_pct.py, lib/account.py, scoped.py and
tests/test_offchip_compile.py, test_cg_iters.py, test_faults.py,
test_implicit.py), one case per name and per call shape; when the benchmark
starts to take another name, it gets a case here. Signatures are bound, never
called, except the two jits that `test_offchip_compile.py` lowers, which are
lowered here the same way at toy size."""

import dataclasses
import importlib
import inspect

import pytest


def binds(fn, *args, **kwargs):
    """`fn(*args, **kwargs)` is a legal call of fn's signature."""
    inspect.signature(fn).bind(*args, **kwargs)


def _mod(name):
    return importlib.import_module("predictionio_tpu." + name)


def _als():
    return _mod("ops.als")


# -- ALSConfig ---------------------------------------------------------------

#: the keywords jobs/als-train.py and jobs/ials-train.py construct it with
ALS_CONFIG_KEYWORDS = ["rank", "lam", "lambda_scaling", "implicit_prefs",
                       "factor_dtype", "compute_dtype", "solver",
                       "sweep_chunk", "work_budget", "bucket_ratio"]


@pytest.mark.parametrize("keyword", ALS_CONFIG_KEYWORDS)
def test_als_config_takes_the_keyword(keyword):
    als = _als()
    field = {f.name: f for f in dataclasses.fields(als.ALSConfig)}[keyword]
    cfg = als.ALSConfig(**{keyword: field.default})
    assert getattr(cfg, keyword) == field.default


# -- the sweep's and the serve path's call shapes ------------------------------

#: (who calls it so, the module under predictionio_tpu, the name, args,
#: kwargs); an argument's value is its role, nothing is called
CALLS = [
    ("als-train window", "ops.als", "_run_side",
     ("groups", "out", "counter", "cfg", None, "lam", "alpha"), {}),
    ("ials-train window", "ops.als", "_run_side",
     ("groups", "out", "counter", "cfg", "gram", "lam", "alpha"),
     {"side": "user"}),
    ("test_cg_iters", "ops.als", "_run_side",
     ("groups", "out", "counter", "cfg", None), {}),
    ("ials-train window", "ops.als", "_side_gram",
     ("cfg", "table", "n_live", "item"), {}),
    ("train set-up", "ops.als", "_upload_plan", ("mesh", "plan", "chunk"), {}),
    ("test_cg_iters", "ops.als", "_upload_plan", ("mesh", "plan"), {}),
    ("train set-up", "ops.als", "resolve_sweep_chunk", (0, 1), {}),
    ("train set-up", "ops.als", "default_compute_dtype", (), {}),
    # ials-train refuses a program whose Gram takes no count of live rows
    ("ials-train set-up", "ops.als", "_gram_eig_impl", ("table",),
     {"n_live": 1}),
    ("cg_iters_run_pct", "ops.als", "last_cg_iterations", (), {}),
    ("test_cg_iters", "ops.als", "_init_factors", (1, 1, 0, 1), {}),
    ("http-queries set-up", "ops.als", "ALSModel", ("U", "V", 200), {}),
    ("test_faults", "ops.als", "users_topk_serve_begin", (), None),
    ("train set-up", "ops.solve", "resolve_solver", ("auto", 1), {}),
    ("test_cg_iters", "ops.solve", "cg_solve_pallas", ("A", "b"),
     {"interpret": True}),
    # jobs/als-train-sharded.py: tables and plans placed for "model"
    ("sharded set-up", "ops.als", "sweep_solver", ("auto", "mesh", "model"),
     {}),
    ("sharded set-up", "ops.als", "batch_shards", ("mesh", "model"), {}),
    ("sharded set-up", "ops.als", "table_rows", (1, 4), {}),
    ("sharded set-up", "ops.als", "_upload_plan",
     ("mesh", "plan", "chunk", 200, "model"), {}),
    ("sharded set-up", "ops.als", "_gather_layout", ("mesh", 200, "model"),
     {}),
    ("sharded set-up", "ops.als", "sweep_shards", ("table", "groups"), {}),
    ("sharded set-up", "ops.als", "sweep_exchange",
     ("mesh", "groups", "out", "counter", "cfg"), {}),
    ("sharded window", "ops.als", "_run_side",
     ("groups", "out", "counter", "cfg", None, "lam", "alpha"),
     {"side": "user", "mesh": "mesh"}),
    ("sharded set-up", "parallel.mesh", "model_mesh", (4,), {}),
    ("train set-up", "ops.ratings", "plan_for_users", ("coo",),
     {"work_budget": 1, "batch_multiple": 1, "bucket_ratio": 1.125}),
    ("train set-up", "ops.ratings", "plan_for_items", ("coo",),
     {"work_budget": 1, "batch_multiple": 1, "bucket_ratio": 1.125}),
    ("train set-up", "ops.ratings", "RatingsCOO",
     ("user_idx", "item_idx", "value", 1, 1), {}),
    ("http-queries set-up", "models.recommendation", "ALSAlgorithmParams", (),
     {"rank": 200}),
    ("http-queries set-up", "models.recommendation", "RecommendationModel",
     ("als_model", "user_ix", "item_ix"), {}),
    ("http-queries warm-up", "models.recommendation", "Query", (),
     {"user": "0", "num": 10}),
]


@pytest.mark.parametrize(
    "module,name,args,kwargs",
    [pytest.param(m, n, a, k, id=f"{n}-{who.replace(' ', '_')}")
     for who, m, n, a, k in CALLS])
def test_the_benchmarks_call_binds(module, name, args, kwargs):
    fn = getattr(_mod(module), name)
    if kwargs is None:          # only replaced by name (monkeypatch)
        assert callable(fn)
    else:
        binds(fn, *args, **kwargs)


def test_resolvers_answer_for_this_backend():
    als, solve = _als(), _mod("ops.solve")
    assert als.default_compute_dtype() in ("float32", "bfloat16")
    assert solve.resolve_solver("auto", 1) in ("cholesky", "cg", "cg_pallas")
    assert als.resolve_sweep_chunk(0, 1) >= 1
    assert als.resolve_sweep_chunk(4, 1) == 4


def test_last_cg_iterations_is_none_or_a_pair():
    counted = _als().last_cg_iterations()
    assert counted is None or len(counted) == 2


def test_the_mesh_the_jobs_read():
    from predictionio_tpu.parallel import mesh as M
    mesh = M.current_mesh()
    assert mesh.n_devices >= 1 and mesh.data_parallelism >= 1
    assert callable(mesh.replicated) and callable(mesh.put_replicated)
    binds(M.make_mesh, devices=["d"])


def test_what_the_sharded_job_reads_of_the_program():
    """jobs/als-train-sharded.py: `factor_sharding` on the config, the
    model mesh's placement methods, and the shape of what `sweep_shards`
    and `sweep_exchange` answer."""
    import jax
    import numpy as np
    from predictionio_tpu.parallel import mesh as M
    als = _als()
    assert als.ALSConfig(factor_sharding="model").factor_sharding == "model"
    mesh = M.make_mesh(devices=jax.devices()[:4], model_parallelism=4)
    assert mesh.model_parallelism == 4 and mesh.n_devices == 4
    assert callable(mesh.model_sharded) and callable(mesh.put_model_sharded)
    table = mesh.put_model_sharded(np.zeros((8, 4), np.float32))
    groups = ((mesh.put_stacked(np.zeros((1, 8), np.int32),
                                als.plan_axes(mesh, "model")),),)
    assert als.sweep_shards(table, groups) == (4, 4)
    assert als.table_rows(20_980_000, 4) == 20_980_004
    # nothing is exchanged where the sweep is not the per-chip one
    assert als.sweep_exchange(mesh, groups, table, table,
                              als.ALSConfig()) == {}


def test_the_set_up_helpers_exist():
    from predictionio_tpu.compile import buckets
    from predictionio_tpu.compile.cache import enable_persistent_cache
    from predictionio_tpu.core import FirstServing
    from predictionio_tpu.data.bimap import BiMap, EntityIdIxMap
    from predictionio_tpu.ops import readback
    from predictionio_tpu.utils import device_cache
    binds(enable_persistent_cache)
    binds(device_cache.clear)
    binds(readback.pack_flag)
    assert buckets.bucket_rows(3) >= 3
    assert buckets.bucket_batch(3, floor=buckets.K_FLOOR) >= buckets.K_FLOOR
    ids = EntityIdIxMap(BiMap({"0": 0}))
    assert ids is not None and FirstServing is not None


# -- the two jits the benchmark's tests lower ----------------------------------

#: tests/test_offchip_compile.py passes all eight, by keyword
SWEEP_STATICS = dict(nratings_reg=True, implicit=False, rank=8,
                     compute_dtype="float32", solver="cg", dual_solve="auto",
                     solver_iters=None, dual_iters_cap=None)


def _sds():
    import jax
    return jax.ShapeDtypeStruct


@pytest.mark.parametrize("static", sorted(SWEEP_STATICS))
def test_solve_sweep_static_is_a_keyword_of_the_jit(static):
    """Each keyword is one the traced function takes; that the jit holds
    it static is what the lowering below shows (a traced None or string
    does not lower)."""
    params = inspect.signature(_als()._solve_sweep_impl).parameters
    assert params[static].kind is inspect.Parameter.KEYWORD_ONLY


def test_solve_sweep_lowers_as_the_benchmark_lowers_it():
    import jax.numpy as jnp
    sds, f32, i32 = _sds(), jnp.float32, jnp.int32
    groups = tuple(
        (sds((2, B), i32), sds((2, B, K), i32), sds((2, B, K), f32),
         sds((2, B, K), f32)) for B, K in ((16, 8), (8, 16)))
    lowered = _als()._solve_sweep.lower(
        sds((41, 8), f32), sds((31, 8), f32), None, groups, sds((), f32),
        sds((), f32), **SWEEP_STATICS)
    table, cg = lowered.out_info
    assert table.shape == (41, 8) and cg.shape == (2,)


def test_users_topk_b_packed_lowers_with_k_and_p():
    import jax.numpy as jnp
    sds, f32, i32 = _sds(), jnp.float32, jnp.int32
    lowered = _als()._users_topk_b_packed.lower(
        sds((64, 8), f32), sds((32, 8), f32), sds((4,), i32), sds((), i32),
        k=16, p=1)
    assert lowered.out_info is not None


# -- the serving account and the server ------------------------------------------

#: what lib/account.py and scoped.py index a record by
DISPATCH_READ = ["t_enqueue", "t_dequeue", "t_closed", "t_gate", "t_begin",
                 "t_ready", "t_done", "batch", "sync_s"]
REQUEST_READ = ["t_start", "t_enqueue", "t_result", "t_written",
                "dispatch_seq"]


@pytest.mark.parametrize(
    "fields_name,field",
    [("DISPATCH_FIELDS", f) for f in DISPATCH_READ]
    + [("REQUEST_FIELDS", f) for f in REQUEST_READ])
def test_account_record_carries_the_field(fields_name, field):
    from predictionio_tpu.obs import trace
    assert field in getattr(trace, fields_name)


def test_tracer_keeps_both_rings():
    from predictionio_tpu.obs import TRACER, trace
    assert (trace.DISPATCH, trace.REQUEST) == ("serve.dispatch",
                                               "serve.request")
    for kind in (trace.DISPATCH, trace.REQUEST):
        assert isinstance(TRACER.recent(kind, 1), list)


def test_engine_server_as_the_serve_job_builds_it():
    from predictionio_tpu.data.storage.base import EngineInstance
    from predictionio_tpu.serving import EngineServer, ServerConfig
    R = _mod("models.recommendation")
    binds(ServerConfig, ip="127.0.0.1", port=0, micro_batch=16,
          result_cache=False)
    binds(EngineServer, "config", engine="engine")
    binds(EngineServer._warm_aot, "self", "models", "bench", strict=True)
    binds(EngineInstance, id="bench", status="COMPLETED", start_time=0,
          end_time=0, engine_id="bench", engine_version="0",
          engine_variant="bench", engine_factory="recommendation")
    assert callable(R.RecommendationEngineFactory.apply)
    for method in ("batch_predict", "batch_predict_begin"):
        assert callable(getattr(R.ALSAlgorithm, method))
    for attr in ("start", "stop"):
        assert callable(getattr(EngineServer, attr))


# -- what jobs/http-queries-ecomm.py takes (ISSUE 31) ----------------------------

ECOMM_CALLS = [
    ("set-up", "models.ecommerce", "ECommerceModel", (),
     {"rank": 200, "user_factors": "U", "item_factors": "V",
      "item_factors_normalized": "Vn", "user_ix": "u", "item_ix": "i",
      "items": {}, "item_categories": "cats"}),
    ("set-up", "models.ecommerce", "ECommAlgorithmParams", (),
     {"app_name": "bench", "unseen_only": True,
      "seen_events": ("buy", "view"), "rank": 200}),
    ("warm-up", "models.ecommerce", "Query", (), {"user": "0", "num": 10}),
    ("set-up", "ops.similarity", "ItemCategories", ("ids", {"c0": 0}), {}),
    ("set-up", "ops.similarity", "normalize_rows", ("V",), {}),
    ("set-up", "data.storage.base", "App", (0, "bench"), {}),
    ("populate", "data.columnar", "ColumnarBatch",
     (3, "view", "user", ["0"], "item", ["1"], None, "2017-11-25T00:00:00Z"),
     {}),
    ("the writer", "data.event", "Event", (),
     {"event": "$set", "entity_type": "constraint",
      "entity_id": "unavailableItems", "properties": "DataMap"}),
]


@pytest.mark.parametrize(
    "module,name,args,kwargs",
    [pytest.param(m, n, a, k, id=f"{n}-ecomm_{who.replace(' ', '_')}")
     for who, m, n, a, k in ECOMM_CALLS])
def test_the_ecomm_jobs_call_binds(module, name, args, kwargs):
    binds(getattr(_mod(module), name), *args, **kwargs)


def test_ecomm_engine_as_the_serve_job_builds_it():
    from predictionio_tpu.serving import EngineServer
    E = _mod("models.ecommerce")
    assert callable(E.ECommerceEngineFactory.apply)
    for method in ("batch_predict", "batch_predict_begin",
                   "aot_warm_specs"):
        assert callable(getattr(E.ECommAlgorithm, method))
    assert E.ECommAlgorithm.LIVE_FILTERS is True
    binds(EngineServer._cache_usable, "self")
    # the model reads as a dataclass with the arrays the job hands it
    fields = {f.name for f in dataclasses.fields(E.ECommerceModel)}
    assert {"item_factors_normalized", "item_categories"} <= fields


def test_the_store_as_the_ecomm_job_opens_it(tmp_env, monkeypatch):
    """An app, a nativelog events DAO from the environment, the columnar
    write and the plain insert the writer uses."""
    from predictionio_tpu.data import Event
    from predictionio_tpu.data.columnar import ColumnarBatch
    from predictionio_tpu.data.datamap import DataMap
    from predictionio_tpu.data.storage import registry
    from predictionio_tpu.data.storage.base import App
    monkeypatch.setenv("PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE",
                       "NATIVELOG")
    monkeypatch.setenv("PIO_STORAGE_SOURCES_NATIVELOG_TYPE", "nativelog")
    monkeypatch.setenv("PIO_STORAGE_SOURCES_NATIVELOG_PATH",
                       str(tmp_env / "eventlog"))
    registry.clear_cache()
    app_id = registry.Storage.get_meta_data_apps().insert(App(0, "bench"))
    events = registry.Storage.get_events()
    events.init(app_id)
    events.insert_columnar(ColumnarBatch(
        2, "view", "user", ["0", "0"], "item", ["1", "2"], None,
        "2017-11-25T00:00:00.000Z"), app_id)
    events.insert(Event(event="$set", entity_type="constraint",
                        entity_id="unavailableItems",
                        properties=DataMap({"items": ["1"]})), app_id)
    from predictionio_tpu.data.store import LEventStore
    seen = LEventStore.find_columnar(
        "bench", entity_type="user", entity_id="0",
        event_names=["buy", "view"], target_entity_type="item",
        timeout_ms=200)
    assert sorted(seen["target_entity_id"].tolist()) == ["1", "2"]
    event_id, latest = LEventStore.latest_event(
        "bench", "constraint", "unavailableItems", event_names=["$set"])
    assert latest.event_id == event_id
    assert latest.properties.get_string_list("items") == ["1"]
    assert LEventStore.latest_event(
        "bench", "constraint", "unavailableItems", event_names=["$set"],
        known_id=event_id) == (event_id, None)


#: the counters the job reads as differences over the window, and the spans
#: it sums from the tracer's batch_predict traces
ECOMM_COUNTERS = ["pio_filter_h2d_bytes_total",
                  "pio_filter_seen_timeouts_total",
                  "pio_filter_constraint_reloads_total",
                  "pio_filter_constraint_failures_total"]


def test_the_filter_counters_and_spans_the_ecomm_job_reads(tmp_env):
    import numpy as np
    from predictionio_tpu.data.bimap import BiMap, EntityIdIxMap
    from predictionio_tpu.data.storage import registry
    from predictionio_tpu.data.storage.base import App
    from predictionio_tpu.obs import TRACER
    from predictionio_tpu.obs.metrics import get_registry
    E, S = _mod("models.ecommerce"), _mod("ops.similarity")
    app_id = registry.Storage.get_meta_data_apps().insert(App(0, "bench"))
    registry.Storage.get_events().init(app_id)
    ids = EntityIdIxMap(BiMap({str(i): i for i in range(8)}))
    V = np.abs(np.random.default_rng(0).standard_normal((8, 4))).astype(
        np.float32)
    model = E.ECommerceModel(
        rank=4, user_factors=V, item_factors=V,
        item_factors_normalized=S.normalize_rows(V), user_ix=ids,
        item_ix=ids, items={}, item_categories=S.ItemCategories(
            np.zeros((8, 1), np.int32), {"c0": 0}))
    algo = E.ECommAlgorithm(E.ECommAlgorithmParams(app_name="bench",
                                                   rank=4))
    with TRACER.trace("batch_predict"):
        algo.batch_predict(model, [(0, E.Query(user="1", num=3))])
    names = set()

    def walk(span):
        names.add(span["name"])
        for child in span.get("children", ()):
            walk(child)

    walk(TRACER.snapshot(limit=1, kind="batch_predict")[0]["root"])
    assert {"filter.seen_read", "filter.constraint_read",
            "filter.lists"} <= names
    assert get_registry().get(ECOMM_COUNTERS[0]).value > 0
    # every dispatch of a window, not the ring's last 128: the stages'
    # histogram, read as differences (sum, count, bucket_counts /
    # percentile_since)
    hist = get_registry().get("pio_filter_seconds")
    for stage in ("seen_read", "constraint_read", "lists"):
        child = hist.labels(stage=stage)
        assert child.count >= 1 and child.sum > 0
        assert child.percentile_since([0] * len(child.bucket_counts()),
                                      95) > 0
    # the others are registered when their event first happens (a read
    # past its deadline, a `$set` found); the job reads an absent counter
    # as 0, and tests/test_ecomm_filtered_reference.py makes both happen
    for name in ECOMM_COUNTERS[1:]:
        counter = get_registry().get(name)
        assert counter is None or counter.value >= 0


def test_the_stall_watch_names_the_ecomm_job_reads():
    """jobs/http-queries-ecomm.py: `server.stallwatch` (None without a
    batcher), its two totals, its worst tick and its reports' keys."""
    from predictionio_tpu.obs.stallwatch import StallWatch
    from predictionio_tpu.serving import EngineServer, ServerConfig
    R = _mod("models.recommendation")
    server = EngineServer(ServerConfig(ip="127.0.0.1", port=0,
                                       micro_batch=16),
                          engine=R.RecommendationEngineFactory.apply())
    watch = server.stallwatch
    assert isinstance(watch, StallWatch)
    assert (watch.n_stalls, watch.stall_s, watch.max_tick_late_s) == (0, 0.0,
                                                                      0.0)
    assert watch.reports() == []
    report = watch._report(0.0, 1.0, 0.5, 0.0, 0.0, {})
    assert {"at", "since", "stacks", "tick_late_s", "process_cpu_s",
            "machine_s", "waiting"} <= set(report)
    assert watch.reports()[0]["at"] == 1.0 and watch.n_stalls == 1
