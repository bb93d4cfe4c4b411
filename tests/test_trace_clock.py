"""One clock for host and device (ISSUE 25): the program's spans as
profiler annotations, named stages inside the two device programs, and the
serving account's per-dispatch and per-request records."""

import datetime as dt
import gc
import glob
import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.obs import TRACER
from predictionio_tpu.obs.metrics import MetricsRegistry
from predictionio_tpu.obs.slowlog import build_waterfall
from predictionio_tpu.obs.trace import (DISPATCH, DISPATCH_FIELDS, REQUEST,
                                        REQUEST_FIELDS, Tracer)
from predictionio_tpu.serving.batcher import MicroBatcher


# -- A: spans on the profiler's clock ----------------------------------

@pytest.fixture(scope="module")
def session(tmp_path_factory):
    """One jax.profiler session over a trace with nested spans, a region
    with no trace around it, and a watched generation-2 collection: the
    host plane's `pio.*` events as {name: [(start_ns, end_ns, stats)]},
    and the Trace."""
    import jax
    from jax.profiler import ProfileData
    tracer = Tracer()
    d = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(d)
    try:
        with tracer.trace("query", route="q") as t:
            with tracer.span("outer", batch=3):
                with tracer.region("inner") as inner:
                    inner.attrs["late"] = 7
                    time.sleep(0.002)
        with tracer.region("loose", side="user") as loose:
            time.sleep(0.001)
        tracer.watch_gc(True)
        gc.collect()
        tracer.watch_gc(False)
    finally:
        jax.profiler.stop_trace()
    events = {}
    path, = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("pio."):
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    return {"events": events, "trace": t, "loose": loose}


def test_spans_land_in_the_host_plane_under_their_pio_names(session):
    ev = session["events"]
    assert {"pio.query", "pio.outer", "pio.inner", "pio.loose",
            "pio.gc.gen2"} <= set(ev)
    # attributes given at entry, and what the body learned, are the
    # event's stats
    assert ev["pio.outer"][0][2]["batch"] == 3
    assert ev["pio.inner"][0][2]["late"] == 7
    assert ev["pio.loose"][0][2]["side"] == "user"


def test_annotations_nest_as_the_span_tree_does(session):
    ev = session["events"]
    (q0, q1, _), = ev["pio.query"]
    (o0, o1, _), = ev["pio.outer"]
    (i0, i1, _), = ev["pio.inner"]
    assert q0 <= o0 <= i0 < i1 <= o1 <= q1
    assert i1 - i0 >= 2_000_000                  # the 2 ms it slept
    root = session["trace"].to_dict()["root"]
    outer, = root["children"]
    inner, = outer["children"]
    assert (root["name"], outer["name"], inner["name"]) == (
        "query", "outer", "inner")
    assert inner["attrs"] == {"late": 7}


def test_region_outside_a_trace_makes_no_span(session):
    assert session["loose"] is None
    tracer = Tracer()
    with tracer.region("loop") as s:
        assert s is None and tracer.current_trace() is None
    with tracer.span("nothing") as s:
        assert s is None
    assert tracer.snapshot() == []
    with tracer.trace("t") as t:
        with tracer.region("inside") as s:
            assert s is not None and s.name == "inside"
    assert [s.name for s in t.spans] == ["t", "inside"]


def test_an_exception_marks_the_span_and_passes():
    tracer = Tracer()
    with pytest.raises(KeyError):
        with tracer.trace("t") as t:
            with tracer.span("boom"):
                raise KeyError("k")
    assert t.spans[1].error.startswith("KeyError")
    assert t.spans[1].duration_s is not None
    assert tracer.current_trace() is None


def test_a_span_never_imports_jax():
    """The event server never needs JAX: with none loaded a span is the
    Span alone and a region nothing at all."""
    code = (
        "import sys\n"
        "import predictionio_tpu.utils.http\n"
        "from predictionio_tpu.obs.trace import Tracer\n"
        "t = Tracer()\n"
        "with t.trace('ingest') as tr:\n"
        "    with t.span('write') as s:\n"
        "        assert s is not None\n"
        "with t.region('loop') as r:\n"
        "    assert r is None\n"
        "assert [s.name for s in tr.spans] == ['ingest', 'write']\n"
        "assert 'jax' not in sys.modules, 'a span imported jax'\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], cwd=repo,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


# -- B: named stages inside the device programs ------------------------

def _sweep_text(solver: str, K: int, rank: int, platforms=None) -> str:
    import jax.numpy as jnp
    from predictionio_tpu.ops import als
    rng = np.random.default_rng(0)
    N, B = 2, 4
    group = (np.arange(N * B, dtype=np.int32).reshape(N, B),
             rng.integers(0, 50, (N, B, K)).astype(np.int32),
             rng.uniform(1, 5, (N, B, K)).astype(np.float32),
             np.ones((N, B, K), np.float32))
    traced = als._solve_sweep.trace(
        jnp.zeros((41, rank)), jnp.ones((51, rank)), None, (group,),
        np.float32(0.01), np.float32(1.0), nratings_reg=True,
        implicit=False, rank=rank, compute_dtype="float32", solver=solver,
        dual_solve="auto", solver_iters=None, dual_iters_cap=None)
    lowered = (traced.lower(lowering_platforms=platforms) if platforms
               else traced.lower())
    return lowered.as_text(debug_info=True)


_EVERY_SWEEP = {"pio.sweep.gather", "pio.sweep.gram", "pio.sweep.scatter"}


@pytest.mark.parametrize("solver,K,solve_scope", [
    ("cholesky", 16, "pio.sweep.solve.primal"),      # K >= rank: primal
    ("cholesky", 4, "pio.sweep.solve.dual"),         # K < rank: dual
    ("cg", 4, "pio.sweep.solve.jnp_cg"),
    ("cg", 16, "pio.sweep.solve.jnp_cg"),
])
def test_the_sweep_names_its_stages(solver, K, solve_scope):
    scopes = set(re.findall(r"pio\.[a-z_.]+", _sweep_text(solver, K, 8)))
    assert scopes == _EVERY_SWEEP | {solve_scope}


@pytest.mark.parametrize("K,scope,kernel", [
    (128, "pio.sweep.solve.primal", "pio_cg_primal_b16_n128"),
    (64, "pio.sweep.solve.dual", "pio_cg_dual_b16_n64"),
])
def test_the_pallas_solves_say_primal_or_dual_and_their_size(K, scope,
                                                             kernel):
    """Lowered for the TPU here, without one: the Mosaic kernel's name is
    what a device trace calls the custom call."""
    text = _sweep_text("cg_pallas", K, 128, platforms=("tpu",))
    assert scope in text and kernel in text


_EXCHANGE = {"pio.sweep.exchange.indices", "pio.sweep.exchange.rows",
             "pio.sweep.exchange.solved", "pio.sweep.gather.place"}


@pytest.mark.parametrize("K,solve_scope", [
    (16, "pio.sweep.solve.primal"), (4, "pio.sweep.solve.dual")])
def test_the_per_chip_sweep_names_its_exchanges(K, solve_scope):
    """The half-sweep over row-sharded tables: the one-chip stages, the
    three exchanges that cross the chips beside them, and the placing of
    the rows a chip received (`pio.sweep.gather` is the owners' gather)."""
    import jax
    import jax.numpy as jnp
    from predictionio_tpu.ops import als
    from predictionio_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(devices=jax.devices()[:4], model_parallelism=4)
    rng = np.random.default_rng(0)
    N, B, rank = 2, 8, 8
    rows = np.arange(N * B, dtype=np.int32).reshape(N, B)
    mask = np.ones((N, B, K), np.float32)
    place, send, _real, _room = als._route_group(
        rows, rng.integers(0, 50, (N, B, K)).astype(np.int32), mask, K,
        52 // 4, 4, 4, False)
    group = (rows, place, rng.uniform(1, 5, (N, B, K)).astype(np.float32),
             mask, send)
    text = als._solve_sweep_per_chip.trace(
        jnp.zeros((44, rank)), jnp.ones((52, rank)), None, (group,),
        np.float32(0.01), np.float32(1.0), nratings_reg=True,
        implicit=False, rank=rank, compute_dtype="float32",
        solver="cholesky", dual_solve="auto", solver_iters=None,
        dual_iters_cap=None, mesh=mesh.mesh, table_axis="model",
        batch_axes=("data", "model")).lower().as_text(debug_info=True)
    assert set(re.findall(r"pio\.[a-z_.]+", text)) == (
        _EVERY_SWEEP | _EXCHANGE | {solve_scope})


def test_the_full_grams_are_named():
    import jax
    import jax.numpy as jnp
    from predictionio_tpu.ops import als
    for fn, scopes in ((als._gram_impl, {"pio.sweep.gram_full.gram"}),
                       (als._gram_eig_impl, {"pio.sweep.gram_full.gram",
                                             "pio.sweep.gram_full.eigh"})):
        text = jax.jit(fn, static_argnames=("n_live",)).trace(
            jnp.ones((9, 4)), n_live=8).lower().as_text(debug_info=True)
        assert set(re.findall(r"pio\.[a-z_.]+", text)) == scopes


_SMW = {"pio.sweep.smw.project", "pio.sweep.smw.assemble",
        "pio.sweep.smw.back"}


@pytest.mark.parametrize("K,scopes", [
    # K < rank: the eig-SMW dual route, its three stages and the K x K solve
    (4, _EVERY_SWEEP | _SMW | {"pio.sweep.solve.dual"}),
    # K >= rank: the primal route; `pio.sweep.gram` holds its A and b
    (16, _EVERY_SWEEP | {"pio.sweep.solve.primal"}),
])
def test_the_implicit_sweep_names_its_stages(K, scopes):
    import jax.numpy as jnp
    from predictionio_tpu.ops import als
    rng = np.random.default_rng(0)
    N, B, rank = 2, 4, 8
    group = (np.arange(N * B, dtype=np.int32).reshape(N, B),
             rng.integers(0, 50, (N, B, K)).astype(np.int32),
             rng.integers(1, 4, (N, B, K)).astype(np.float32),
             np.ones((N, B, K), np.float32))
    gram = als._gram_eig(jnp.ones((51, rank)), n_live=50)
    text = als._solve_sweep.trace(
        jnp.zeros((41, rank)), jnp.ones((51, rank)), gram, (group,),
        np.float32(0.01), np.float32(1.0), nratings_reg=True,
        implicit=True, rank=rank, compute_dtype="float32",
        solver="cholesky", dual_solve="auto", solver_iters=None,
        dual_iters_cap=None).lower().as_text(debug_info=True)
    assert set(re.findall(r"pio\.[a-z_.]+", text)) == scopes


def test_implicit_training_puts_each_gram_under_a_host_region(monkeypatch):
    """`pio.train.gram` (attr `side`: whose table) around each Gram + eigh
    program, twice an iteration, before the half-sweep that reads it."""
    from predictionio_tpu.ops import als
    from predictionio_tpu.ops.ratings import RatingsCOO
    seen = []
    real = als.TRACER.region

    def region(name, **attrs):
        seen.append((name, attrs.get("side")))
        return real(name, **attrs)

    monkeypatch.setattr(als.TRACER, "region", region)
    rng = np.random.default_rng(1)
    coo = RatingsCOO(np.sort(rng.integers(0, 12, 60)).astype(np.int32),
                     rng.integers(0, 9, 60).astype(np.int32),
                     np.ones(60, np.float32), 12, 9)
    als.als_train(coo, als.ALSConfig(rank=4, iterations=2, sentinel=False,
                                     implicit_prefs=True))
    sweeps = [s for s in seen if s[0] in ("train.gram", "train.half_sweep")]
    assert sweeps == [("train.gram", "item"), ("train.half_sweep", "user"),
                      ("train.gram", "user"),
                      ("train.half_sweep", "item")] * 2
    seen.clear()
    als.als_train(coo, als.ALSConfig(rank=4, iterations=1, sentinel=False))
    assert not [s for s in seen if s[0] == "train.gram"]


def test_the_serve_kernel_names_its_stages():
    import jax.numpy as jnp
    from predictionio_tpu.ops import als
    text = als._users_topk_b_packed.trace(
        jnp.ones((64, 8)), jnp.ones((32, 8)), np.arange(4, dtype=np.int32),
        np.int32(30), k=4, p=1).lower().as_text(debug_info=True)
    assert set(re.findall(r"pio\.[a-z_.]+", text)) == {
        "pio.serve.user_rows", "pio.serve.score", "pio.serve.topk",
        "pio.serve.pack"}


def test_the_composed_mask_kernel_names_its_stages():
    """The masked executables carry the other serve kernel's scope names
    (benchmark/scoped.py reduces both alike), with `pio.serve.mask` for the
    mask composed on the device (ISSUE 31)."""
    import jax.numpy as jnp
    from predictionio_tpu.ops import similarity as S
    i32 = np.int32
    text = S._composed_masked_topk_packed.trace(
        jnp.ones((2, 8)), jnp.ones((64, 8)), np.zeros((64, 1), i32),
        np.zeros(2, np.uint32), i32(60), np.full((2, 4), -2, i32),
        np.zeros(16, i32), np.full(16, 64, i32), np.zeros(16, i32),
        np.zeros(2, bool), k=4, p=1).lower().as_text(debug_info=True)
    assert set(re.findall(r"pio\.[a-z_.]+", text)) == {
        "pio.serve.mask", "pio.serve.score", "pio.serve.topk",
        "pio.serve.pack"}
    text = S._batched_masked_topk_packed.trace(
        jnp.ones((2, 8)), jnp.ones((64, 8)), np.ones((2, 64), bool),
        k=4, filter_positive=True, p=1).lower().as_text(debug_info=True)
    assert set(re.findall(r"pio\.[a-z_.]+", text)) == {
        "pio.serve.score", "pio.serve.topk", "pio.serve.pack"}


@pytest.mark.parametrize("name", ["filter.seen_read",
                                  "filter.constraint_read", "filter.lists"])
def test_the_filter_spans_are_regions_of_the_one_tracer(name, monkeypatch):
    """Host side of the live filters: `pio.filter.*` regions entered by
    models/ecommerce.py through obs/trace.py's TRACER, so they lie on the
    profiler's clock with every other span."""
    import inspect
    from predictionio_tpu.models import ecommerce as E
    from predictionio_tpu.obs import TRACER
    assert E.TRACER is TRACER
    stage = name.split(".", 1)[1]
    assert f'_filter_stage("{stage}")' in inspect.getsource(E.ECommAlgorithm)
    # the one way in: a region of that name, and the stage's histogram
    from predictionio_tpu.obs.metrics import get_registry
    with TRACER.trace("probe") as root:
        with E._filter_stage(stage) as span:
            assert span.name == name
    assert root is not None
    hist = get_registry().get("pio_filter_seconds")
    assert hist.labels(stage=stage).count >= 1


def test_scopes_leave_the_answers_as_they_were():
    """named_scope is metadata: the packed serve kernel ranks as the
    exact-size reference does."""
    import jax.numpy as jnp
    from predictionio_tpu.ops import als, readback
    rng = np.random.default_rng(3)
    U = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
    V = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
    ix = np.arange(4, dtype=np.int32)
    packed = als._users_topk_b_packed(U, V, ix, np.int32(30), k=4, p=2)
    scores, idx = readback.unpack_host(np.asarray(packed), 2)
    ref_scores, ref_idx = als._users_topk(U, V[:30], ix, k=4)
    assert (idx == np.asarray(ref_idx)).all()
    np.testing.assert_allclose(scores, np.asarray(ref_scores), rtol=1e-6)


# -- the batch's user rows, read row by row (PR 26) ---------------------

_ROWS_U, _ROWS_LIVE, _ROWS_RANK = 16384, 16000, 13


def _rows_tables(n_u=_ROWS_U, live=_ROWS_LIVE, n_i=96, rank=_ROWS_RANK):
    """A user table at its row bucket (zero rows past the live ones), as
    the serve path uploads it, at a rank that is no multiple of 8."""
    import jax.numpy as jnp
    rng = np.random.default_rng(26)
    U = np.zeros((n_u, rank), np.float32)
    U[:live] = rng.standard_normal((live, rank))
    V = rng.standard_normal((n_i, rank)).astype(np.float32)
    return jnp.asarray(U), jnp.asarray(V)


def _rows_batch(b, live=_ROWS_LIVE):
    """What users_topk_serve_begin sends at bucket b: the table's last
    live row, a row asked for twice, and zero slots as padding."""
    ix = np.zeros(b, np.int32)
    asked = [live - 1, 3, 3, 17, 1024, live - 129, 5, 640, 3, 127, 128, 1]
    n = max(1, b - b // 4)
    ix[:n] = asked[:n]
    return ix


def _assert_ranks_as_the_reference(variant, U, V, ix, n_items, k=8):
    """Either serve executable against the exact-size reference: ids
    equal, scores to 1e-6."""
    from predictionio_tpu.ops import als, readback
    if variant == "packed":
        packed = als._users_topk_b_packed(U, V, ix, np.int32(n_items),
                                          k=k, p=readback.PACK_EXACT)
        scores, idx = readback.unpack_host(np.asarray(packed),
                                           readback.PACK_EXACT)
    else:
        scores, idx = als._users_topk_b(U, V, ix, np.int32(n_items), k=k)
    ref_scores, ref_idx = als._users_topk(U, V[:n_items], ix, k=k)
    assert (np.asarray(idx) == np.asarray(ref_idx)).all()
    np.testing.assert_allclose(np.asarray(scores), np.asarray(ref_scores),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("variant", ["packed", "unpacked"])
@pytest.mark.parametrize("n_u,live,b,row_by_row", [
    (_ROWS_U, _ROWS_LIVE, 1, True), (_ROWS_U, _ROWS_LIVE, 2, True),
    (_ROWS_U, _ROWS_LIVE, 4, True), (_ROWS_U, _ROWS_LIVE, 8, True),
    (_ROWS_U, _ROWS_LIVE, 16, True),
    # the other side of _batch_rows' shape test: 16 reads of a 64-row
    # table are no cheaper than the table, so it gathers
    (64, 60, 16, False)])
def test_the_serve_executables_rank_as_the_reference(n_u, live, b,
                                                     row_by_row, variant):
    from predictionio_tpu.ops import als
    assert (b * als._ROWS_PER_READ <= n_u) == row_by_row
    U, V = _rows_tables(n_u=n_u, live=live)
    _assert_ranks_as_the_reference(variant, U, V, _rows_batch(b, live) % live,
                                   n_items=90)


@pytest.mark.parametrize("n_rows,gathers", [(16384, False), (64, True)])
def test_batch_rows_treats_a_bad_index_as_indexing_does(n_rows, gathers):
    """A negative index wraps once and then clamps, a too-large one
    clamps: x[ixs]'s own treatment, on both sides of the shape test, and
    the program holds a gather only on the far side."""
    import jax
    import jax.numpy as jnp
    from predictionio_tpu.ops import als
    table = jnp.asarray(np.random.default_rng(1).standard_normal(
        (n_rows, _ROWS_RANK)), jnp.float32)
    ix = np.array([0, -1, -n_rows, -n_rows - 1, n_rows - 1, n_rows,
                   1 << 30, -(1 << 30), 7, 7, 0, 0, 0, 0, 0, 0], np.int32)
    got = jax.jit(als._batch_rows)(table, ix)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(table[ix]))
    text = str(jax.make_jaxpr(als._batch_rows)(table, ix))
    assert ("gather" in text) == gathers
    assert ("dynamic_slice" in text) != gathers


# -- C: the serving account --------------------------------------------

def _field(rec, name, fields=DISPATCH_FIELDS):
    return rec[fields.index(name)]


def _pipelined_batcher(metrics=None, hold_s: float = 0.0, **kw):
    def begin(queries):
        def finish():
            if hold_s:
                time.sleep(hold_s)
            return [q * 2 for q in queries]
        return finish
    return MicroBatcher(lambda qs: [q * 2 for q in qs], max_batch=4,
                        max_wait_ms=0.5, metrics=metrics,
                        process_batch_begin=begin, inflight=2, **kw)


def test_dispatch_records_are_monotone_and_gate_plus_begin_is_dispatch():
    TRACER.clear()
    reg = MetricsRegistry()
    b = _pipelined_batcher(metrics=reg, hold_s=0.002, tenant="acct-a")
    try:
        threads = [threading.Thread(
            target=lambda i=i: [b.submit(i) for _ in range(20)])
            for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        b.stop()
    recs = TRACER.recent(DISPATCH)
    assert len(recs) == b.n_batches > 0
    order = ("t_enqueue", "t_dequeue", "t_closed", "t_gate", "t_begin",
             "t_pickup", "t_ready", "t_done")
    for r in recs:
        assert len(r) == len(DISPATCH_FIELDS)
        stamps = [_field(r, f) for f in order]
        assert stamps == sorted(stamps), dict(zip(DISPATCH_FIELDS, r))
        assert 1 <= _field(r, "batch") <= _field(r, "bucket") <= 4
        assert _field(r, "sync_s") == 0.0 and _field(r, "tenant") == "acct-a"
    assert sum(_field(r, "batch") for r in recs) == 160
    seqs = [_field(r, "seq") for r in recs]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    # the histogram's new stages split the old one: gate + begin == dispatch
    h = reg.get("pio_serve_stage_seconds")
    stage = {s: h.labels(stage=s) for s in ("dispatch", "gate", "begin",
                                            "turnaround")}
    assert (stage["gate"].count == stage["begin"].count
            == stage["dispatch"].count == stage["turnaround"].count
            == len(recs))
    assert stage["gate"].sum + stage["begin"].sum == pytest.approx(
        stage["dispatch"].sum, rel=1e-9)
    # two slots, eight clients: some window waited at the gate, and the
    # histogram read what the records hold
    assert b.n_pipeline_stalls > 0
    assert sum(_field(r, "t_gate") - _field(r, "t_closed")
               for r in recs) == pytest.approx(stage["gate"].sum, rel=1e-9)
    # a tuple of floats, ints and the tenant: nothing the collector tracks
    gc.collect()
    assert not gc.is_tracked(recs[0])


def test_the_dispatch_ring_stays_within_its_bound_under_10k_dispatches():
    TRACER.clear()
    b = _pipelined_batcher()
    try:
        for i in range(10_000):
            assert b.submit(i) == 2 * i
    finally:
        b.stop()
    assert b.n_batches == 10_000
    recs = TRACER.recent(DISPATCH)
    assert len(recs) == 4096
    assert [_field(r, "seq") for r in recs] == list(
        range(_field(recs[0], "seq"), _field(recs[0], "seq") + 4096))
    assert len(TRACER.recent(DISPATCH, 10)) == 10
    assert TRACER.recent(DISPATCH, 10)[-1] == recs[-1]
    assert TRACER.dispatch_record(_field(recs[7], "seq")) == recs[7]
    assert TRACER.dispatch_record(_field(recs[0], "seq") - 1) is None


def test_the_synchronous_batcher_keeps_the_account_too():
    TRACER.clear()
    b = MicroBatcher(lambda qs: [q + 1 for q in qs], max_batch=4)
    try:
        assert b.submit(1) == 2
    finally:
        b.stop()
    rec, = TRACER.recent(DISPATCH)
    stamps = [_field(rec, f) for f in DISPATCH_FIELDS[1:9]]
    assert stamps == sorted(stamps)
    assert _field(rec, "t_gate") == _field(rec, "t_closed")


def _mini_server(result_cache: bool = False):
    from predictionio_tpu.core import FirstServing
    from predictionio_tpu.data.bimap import BiMap, EntityIdIxMap
    from predictionio_tpu.data.storage.base import EngineInstance
    from predictionio_tpu.models import recommendation as R
    from predictionio_tpu.ops.als import ALSModel
    from predictionio_tpu.serving import EngineServer, ServerConfig
    rng = np.random.default_rng(7)
    als = ALSModel(rng.standard_normal((30, 6)).astype(np.float32),
                   rng.standard_normal((20, 6)).astype(np.float32), 6)
    model = R.RecommendationModel(
        als, EntityIdIxMap(BiMap({f"u{i}": i for i in range(30)})),
        EntityIdIxMap(BiMap({f"i{i}": i for i in range(20)})))
    s = EngineServer(ServerConfig(ip="127.0.0.1", port=0, micro_batch=16,
                                  result_cache=result_cache))
    now = dt.datetime.now(dt.timezone.utc)
    s.engine_instance = EngineInstance(
        id="acct", status="COMPLETED", start_time=now, end_time=now,
        engine_id="acct", engine_version="0", engine_variant="acct",
        engine_factory="recommendation")
    s.algorithms = [R.ALSAlgorithm(R.ALSAlgorithmParams(rank=6))]
    s.models = [model]
    s.serving = FirstServing()
    return s


def _post(port, path, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.status, json.loads(r.read())


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=30) as r:
        return json.loads(r.read())


def test_a_request_joins_to_its_dispatch_by_sequence_number():
    TRACER.clear()
    s = _mini_server(result_cache=True)
    s.start()
    try:
        port = s.config.port
        for u in ("u1", "u2", "u3", "u1"):          # the last: a cache hit
            assert _post(port, "/queries.json",
                         {"user": u, "num": 3})[0] == 200
        _get(port, "/stats.json")                   # no query: no record
    finally:
        s.stop()
    # the rings outlive the server that wrote them
    requests = TRACER.recent(REQUEST)
    dispatches = {_field(r, "seq"): r for r in TRACER.recent(DISPATCH)}
    assert len(requests) == 4 and len(dispatches) == 3
    for r in requests[:3]:
        assert len(r) == len(REQUEST_FIELDS)
        t_start, t_enq, t_res, t_written, seq, tenant = r
        d = dispatches[seq]
        assert t_start <= t_enq <= t_res <= t_written
        # the request is a member of that dispatch: enqueued before its
        # batch closed, answered when its results were set
        assert (_field(d, "t_enqueue") <= t_enq
                <= _field(d, "t_closed"))
        assert _field(d, "t_ready") <= t_res <= _field(d, "t_done")
        assert tenant is None
    t_start, t_enq, t_res, t_written, seq, _ = requests[3]
    assert seq == -1 and t_enq == t_res == 0.0 and t_start <= t_written


def test_slow_waterfalls_take_gate_and_turnaround_from_the_account():
    tracer = Tracer()
    with tracer.trace("query") as qt:
        with tracer.span("batch_wait"):
            pass
    with tracer.trace("batch_predict", formationMs=0.5,
                      completionWaitMs=1.0) as bt:
        for name in ("supplement", "predict", "readback", "post_process"):
            with tracer.span(name):
                pass
    rec = dict.fromkeys(DISPATCH_FIELDS, 0.0)
    rec.update(t_closed=1.0, t_gate=1.004, t_begin=1.005, t_ready=1.030)
    stages = build_waterfall(qt, bt, serialize_s=0.0,
                             dispatch=tuple(rec[f] for f in DISPATCH_FIELDS))
    names = [s["stage"] for s in stages]
    assert names == ["queue_wait", "batch_formation", "gate", "supplement",
                     "dispatch", "turnaround", "completion_wait",
                     "readback", "post_process", "serialize"]
    ms = {s["stage"]: s["ms"] for s in stages}
    assert ms["gate"] == pytest.approx(4.0) and ms["turnaround"] == \
        pytest.approx(25.0)
    # without a record the waterfall is what it was
    assert [s["stage"] for s in build_waterfall(qt, bt)] == [
        n for n in names if n not in ("gate", "turnaround", "serialize")]


def test_the_sampled_sync_is_a_span_and_is_noted_for_the_account():
    from predictionio_tpu.obs import costmon
    st = costmon._device_state("acct_probe")
    st.every = 2
    before = costmon.thread_sync_s()
    with TRACER.trace("acct_probe") as t:
        t.discard = True
        for _ in range(4):
            costmon.device_timed("acct_probe", lambda: np.zeros(3))
    assert [s.name for s in t.spans].count("device_sync") == 2
    assert costmon.thread_sync_s() >= before
