"""ISSUE 37: the set-up account — one compile listener (obs/costmon) that
splits every compile request into its trace, lowering, compile and
cache-load seconds, the upload records of utils/device_cache and
ops/als._upload_plan, and what reads them: ``costmon.setup_account``
(``/stats.json``'s ``setup``, ``als_train``'s telemetry)."""

import threading
import time

import numpy as np
import pytest

from predictionio_tpu.obs import TRACER, costmon
from predictionio_tpu.obs.trace import (COMPILE, COMPILE_FIELDS, UPLOAD,
                                        UPLOAD_FIELDS)

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


def _records(kind, fields, t0):
    return [dict(zip(fields, r)) for r in TRACER.recent(kind) if r[0] >= t0]


def _compiles(t0, label=None):
    return [r for r in _records(COMPILE, COMPILE_FIELDS, t0)
            if label is None or r["executable"] == label]


@pytest.fixture()
def durations():
    """Every duration jax.monitoring reports while the test runs, in
    order (a listener of the test's own: jax has no way to take one
    off, so it stops listening when the test ends)."""
    from jax import monitoring
    seen, on = [], [True]
    costmon.install()

    def listen(name, secs, *a, **kw):
        if on[0]:
            seen.append((name, float(secs)))
    monitoring.register_event_duration_secs_listener(listen)
    yield seen
    on[0] = False


@pytest.fixture()
def disk_cache(tmp_path, monkeypatch, request):
    from predictionio_tpu.compile import cache as C
    monkeypatch.delenv("PIO_XLA_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    request.addfinalizer(C.disable_persistent_cache)
    if C.enable_persistent_cache(root=str(tmp_path)) is None:
        pytest.skip("persistent cache unavailable on this backend")


def test_a_miss_then_a_hit_split_into_jax_own_stages(durations, disk_cache):
    """A compile the cache misses, then the same program after the
    in-memory caches are cleared, which the cache answers: each request's
    stages are jax's own durations (the outer trace, the lowering, the
    backend less the retrieval, the retrieval), under its label."""
    import jax

    @jax.jit
    def f(x):
        return (jax.numpy.sin(x) * 3.0 - 1.0).sum()

    x = np.arange(29, dtype=np.float32)
    for label, outcome in (("probe_miss", costmon.COMPILED),
                           ("probe_hit", costmon.CACHE_LOADED)):
        jax.clear_caches()
        before = len(durations)
        t0 = time.perf_counter()
        with costmon.executable(label):
            f(x).block_until_ready()
        seen = durations[before:]
        (rec,) = _compiles(t0, label)
        assert rec["outcome"] == outcome
        assert rec["thread"] == threading.current_thread().name
        assert rec["t_start"] <= rec["t_end"]
        # the outer trace holds the nested jnp traces: the longest
        assert rec["trace_s"] == pytest.approx(
            max(s for n, s in seen if n == TRACE))
        (lower,) = [s for n, s in seen if n == LOWER]
        (backend,) = [s for n, s in seen if n == BACKEND]
        retrieval = sum(s for n, s in seen if n == RETRIEVAL)
        assert rec["lower_s"] == pytest.approx(lower)
        assert rec["compile_s"] + rec["cache_load_s"] == pytest.approx(
            backend)
        assert rec["cache_load_s"] == pytest.approx(
            retrieval if outcome == costmon.CACHE_LOADED else 0.0)
        assert costmon.compile_requests()[outcome][label] == 1
        by_stage = costmon.compile_stage_seconds()[label]
        assert set(by_stage) == set(costmon.STAGES)
        assert costmon.compile_seconds_by_executable()[label] == \
            pytest.approx(backend, abs=1e-4)


def test_compile_requests_agree_with_backend_compile_events(durations):
    """One request per backend compile, none counted twice (the listener
    it replaced counted a compile once per cache event); the count the
    benchmark's `window_compiles` reader takes from the same event."""
    import jax
    before = costmon.compile_totals()["requests"]
    n_before = len(durations)
    with costmon.executable("probe_count"):
        for n in (3, 5, 7):
            jax.jit(lambda v: v * 2.0 + 1.0)(np.ones(n, np.float32))
    backend = sum(1 for name, _ in durations[n_before:] if name == BACKEND)
    assert backend >= 3
    assert costmon.compile_totals()["requests"] - before == backend


def test_record_kinds_and_their_fields():
    from predictionio_tpu.obs import trace
    assert (trace.COMPILE, trace.UPLOAD) == ("setup.compile",
                                             "setup.upload")
    assert COMPILE_FIELDS == ("t_start", "t_end", "executable", "bucket",
                              "thread", "trace_s", "lower_s", "compile_s",
                              "cache_load_s", "outcome")
    assert UPLOAD_FIELDS == ("t_start", "t_put", "t_ready", "table",
                             "bytes", "host_prep_s")
    for kind in (COMPILE, UPLOAD):
        assert isinstance(TRACER.recent(kind, 1), list)


def test_aot_compile_record_carries_its_bucket():
    """The AOT registry's compile books under the spec's label with its
    shape bucket."""
    import jax
    from predictionio_tpu.compile.aot import AOTRegistry
    from predictionio_tpu.obs.metrics import MetricsRegistry

    def spec(b):
        return (jax.jit(lambda v: (v * 5.0).sum()),
                (jax.ShapeDtypeStruct((b, 3), np.float32),), {})

    reg = AOTRegistry(registry=MetricsRegistry())
    reg.register("probe_aot", spec)
    t0 = time.perf_counter()
    assert reg.ensure("probe_aot", {"b": 11}) is not None
    (rec,) = _compiles(t0, "probe_aot")
    assert rec["bucket"] == "b11"
    snap = reg.snapshot()
    assert snap["compileCount"] >= 1 and snap["compileSeconds"] > 0


def test_upload_ready_after_put_and_the_uploader_not_blocked():
    """The record's t_ready is stamped by the waiter once the arrays are
    ready; note_upload returns at once however long that takes."""
    from predictionio_tpu.utils import device_cache
    gate = threading.Event()

    class Slow:
        """An array whose transfer lands when the test says."""

        def block_until_ready(self):
            gate.wait(10.0)
            return self

    t0 = time.perf_counter()
    t_call = time.perf_counter()
    device_cache.note_upload([Slow()], "probe_slow", 123, t0, t_call, 0.0)
    assert time.perf_counter() - t_call < 0.5       # did not wait
    assert not [r for r in _records(UPLOAD, UPLOAD_FIELDS, t0)
                if r["table"] == "probe_slow"]
    time.sleep(0.05)
    t_open = time.perf_counter()
    gate.set()
    assert device_cache.settle_uploads(10.0)
    (rec,) = [r for r in _records(UPLOAD, UPLOAD_FIELDS, t0)
              if r["table"] == "probe_slow"]
    assert rec["t_ready"] >= t_open >= rec["t_put"] >= rec["t_start"]
    assert rec["bytes"] == 123


def test_cached_put_rows_records_its_upload():
    from predictionio_tpu.utils import device_cache
    table = np.ones((37, 4), np.float32)
    t0 = time.perf_counter()
    device_cache.cached_put_rows(table, 64, table="probe_rows")
    device_cache.cached_put_rows(table, 64, table="probe_rows")  # cached
    assert device_cache.settle_uploads(10.0)
    (rec,) = [r for r in _records(UPLOAD, UPLOAD_FIELDS, t0)
              if r["table"] == "probe_rows"]
    assert rec["bytes"] == 64 * 4 * 4
    assert rec["host_prep_s"] >= 0
    assert rec["t_start"] <= rec["t_put"] <= rec["t_ready"]


def test_setup_account_sums_the_records(request):
    """The derived block: per executable its stages and outcomes, per
    table its uploads; unlabelled compiles are not the program's."""
    TRACER.clear()
    request.addfinalizer(TRACER.clear)   # none of them outlives the test
    t0 = 1000.0
    TRACER.record(COMPILE, (t0, t0 + 2, "probe_a", "b1", "main",
                            0.5, 0.25, 1.0, 0.0, costmon.COMPILED))
    TRACER.record(COMPILE, (t0 + 1, t0 + 2, "probe_a", "b2", "main",
                            0.25, 0.25, 0.125, 0.5, costmon.CACHE_LOADED))
    TRACER.record(COMPILE, (t0, t0 + 1, costmon.UNLABELED, "", "main",
                            9.0, 9.0, 9.0, 0.0, costmon.COMPILED))
    TRACER.record(UPLOAD, (t0, t0 + 1, t0 + 3, "item", 100, 0.5))
    TRACER.record(UPLOAD, (t0 + 2, t0 + 2.5, t0 + 4, "item", 50, 0.25))
    acct = costmon.setup_account(since=t0)
    assert acct["executables"] == {"probe_a": {
        "trace_s": 0.75, "lower_s": 0.5, "compile_s": 1.125,
        "cache_load_s": 0.5, "compiled": 1, "cache_loaded": 1}}
    assert acct["tables"] == {"item": {"uploads": 2, "bytes": 150,
                                       "host_prep_s": 0.75, "ready_s": 5.0}}
    assert acct["total"]["upload_wall_s"] == 4.0
    assert acct["total"]["compiled"] == 1
    assert acct["total"]["upload_ready_s"] == 5.0


def test_stats_json_has_the_setup_block():
    from test_compile_plane import _real_server, _rec_model
    s = _real_server(_rec_model(40, 50), canary_fraction=0.0)
    s.handle_query_batch([{"user": "u1", "num": 3}])

    class _Req:
        params = {}
        headers = {}

    body = s._stats(_Req()).body
    setup = body["setup"]
    assert set(setup) == {"executables", "tables", "total"}
    assert {"user", "item"} <= set(setup["tables"])
    for t in setup["tables"].values():
        assert set(t) == {"uploads", "bytes", "host_prep_s", "ready_s"}
    assert costmon.UNLABELED not in setup["executables"]


# -- every compile of a set-up is the program's, and labelled ---------------

def _ratings(n_users=60, n_items=40, nnz=700, seed=5):
    from predictionio_tpu.ops.ratings import RatingsCOO
    rng = np.random.default_rng(seed)
    return RatingsCOO(rng.integers(0, n_users, nnz),
                      rng.integers(0, n_items, nnz),
                      rng.uniform(1, 5, nnz).astype(np.float32),
                      n_users, n_items)


def _assert_all_labelled(t0):
    stray = [r for r in _compiles(t0) if r["executable"] ==
             costmon.UNLABELED]
    assert not stray, stray


@pytest.mark.parametrize("kind", ["explicit", "implicit", "sharded_1x4"])
def test_train_leaves_no_compile_unlabelled(kind):
    import jax
    from predictionio_tpu.ops.als import ALSConfig, als_train
    from predictionio_tpu.parallel.mesh import make_mesh
    ratings = _ratings(seed=len(kind))
    kw = dict(rank=6, iterations=2, lam=0.1, seed=7, work_budget=256)
    if kind == "sharded_1x4":
        mesh = make_mesh(devices=jax.devices()[:4], model_parallelism=4)
        cfg = ALSConfig(factor_sharding="model", sentinel=False, **kw)
    else:
        mesh = make_mesh(devices=jax.devices()[:1])
        cfg = ALSConfig(implicit_prefs=kind == "implicit", alpha=2.0, **kw)
    t0 = time.perf_counter()
    tel = {}
    als_train(ratings, cfg, mesh, telemetry=tel)
    _assert_all_labelled(t0)
    assert {"user_plan", "item_plan"} <= set(tel["setup"]["tables"])


def test_deploy_and_first_dispatches_leave_no_compile_unlabelled(
        monkeypatch):
    """The serve job's set-up (benchmark/jobs/http-queries.py): the
    deploy-time AOT warm, then every batch bucket dispatched once, the
    tables reaching the device at the first."""
    monkeypatch.delenv("PIO_AOT_WARM", raising=False)
    from test_compile_plane import _real_server, _rec_model
    from predictionio_tpu.models import recommendation as R
    model = _rec_model(90, 70, seed=11)
    s = _real_server(model, canary_fraction=0.0)
    t0 = time.perf_counter()
    s._warm_aot(s.models, "probe", strict=True)
    assert s.last_aot_warm["compiled"] >= 1
    b = 1
    while b <= 8:
        s.algorithms[0].batch_predict(model, [
            (i, R.Query(user=f"u{i}", num=3)) for i in range(b)])
        b *= 2
    for t in threading.enumerate():
        if t.name.startswith("pio-aot-"):
            t.join()
    _assert_all_labelled(t0)
    assert {"user", "item"} <= {
        r["table"] for r in _records(UPLOAD, UPLOAD_FIELDS, t0)} | set(
        costmon.setup_account(since=t0)["tables"])


def test_status_telemetry_prints_the_setup_block(capsys):
    """`pio status --telemetry` reads the engine's `/stats.json` `setup`."""
    from predictionio_tpu.tools import cli
    TRACER.clear()
    try:
        TRACER.record(COMPILE, (5.0, 6.0, "batch_predict", "b4", "main",
                                0.5, 0.25, 2.0, 0.0, costmon.COMPILED))
        TRACER.record(UPLOAD, (5.0, 5.5, 7.0, "item", 4096, 0.125))
        cli._print_setup(costmon.setup_account())
    finally:
        TRACER.clear()
    out = capsys.readouterr().out
    assert "setup: trace=0.5s lower=0.25s compile=2.0s" in out
    assert "batch_predict: trace=0.5s" in out
    assert "table item: uploads=1 bytes=4096 hostPrep=0.125s ready=2.0s" \
        in out
