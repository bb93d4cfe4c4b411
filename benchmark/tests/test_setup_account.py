"""The four readers of the program's set-up account on a hand-made ring:
only the program's labelled compiles count, a serve cell's set-up ends at its
window's first dispatch, and a program that keeps no such rings (the parent)
reads nothing."""

import importlib.util
import os

import pytest

from predictionio_tpu.obs import TRACER
from predictionio_tpu.obs import trace as program_trace
from predictionio_tpu.obs.trace import (COMPILE, COMPILE_FIELDS, DISPATCH,
                                        DISPATCH_FIELDS, UPLOAD, UPLOAD_FIELDS)

from benchmark.lib import setup_account

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = ("setup_trace_lower_s", "setup_xla_compile_s",
           "setup_cache_load_s", "setup_h2d_s")


def reader(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def compile_rec(t, label, trace_s, lower_s, compile_s, load_s, outcome):
    return (t, t + trace_s + lower_s + compile_s + load_s, label, "",
            "MainThread", trace_s, lower_s, compile_s, load_s, outcome)


def upload_rec(t, table, ready_after, nbytes=1000, prep=0.5):
    return (t, t + prep, t + ready_after, table, nbytes, prep)


def dispatch_rec(seq, t):
    rec = dict.fromkeys(DISPATCH_FIELDS, 0)
    rec.update(seq=seq, t_enqueue=t, t_dequeue=t, t_closed=t, t_gate=t,
               t_begin=t, t_pickup=t, t_ready=t + 0.01, t_done=t + 0.02,
               batch=1, bucket=1, tenant=None)
    return tuple(rec[f] for f in DISPATCH_FIELDS)


@pytest.fixture()
def ring():
    """Set-up from t=10 to t=60: two labelled compiles (one the cache
    missed, one it loaded), the benchmark's own unlabelled one, two
    uploads; then, from t=100, what happens in a serve cell's window: a
    promotion compile and a swap's upload."""
    TRACER.clear()
    TRACER.record(COMPILE, compile_rec(10.0, "batch_predict", 1.0, 2.0,
                                       30.0, 0.0, "compiled"))
    TRACER.record(COMPILE, compile_rec(20.0, "als_sweep", 0.5, 0.25, 0.125,
                                       4.0, "cache_loaded"))
    TRACER.record(COMPILE, compile_rec(25.0, "unlabeled", 9.0, 9.0, 9.0,
                                       0.0, "compiled"))
    TRACER.record(UPLOAD, upload_rec(30.0, "user", 6.0))
    TRACER.record(UPLOAD, upload_rec(32.0, "item", 2.0))
    TRACER.record(COMPILE, compile_rec(101.0, "batch_predict", 7.0, 7.0,
                                       7.0, 0.0, "compiled"))
    TRACER.record(UPLOAD, upload_rec(102.0, "item", 50.0))
    for i in range(4):
        TRACER.record(DISPATCH, dispatch_rec(i + 1, 100.0 + 0.5 * i))
    yield
    TRACER.clear()


def train_ctx():
    return {"window": {"iterations": 5, "wall_s": 40.0}}


def serve_ctx():
    return {"window": {"dispatches": 4, "attempted": 4, "wall_s": 2.0}}


def test_train_cell_reads_every_labelled_record(ring):
    got = {name: reader(name)(train_ctx()) for name in READERS}
    assert got["setup_trace_lower_s"] == pytest.approx(1 + 2 + 0.5 + 0.25
                                                       + 7 + 7)
    assert got["setup_xla_compile_s"] == pytest.approx(30 + 7)
    assert got["setup_cache_load_s"] == pytest.approx(4.0)
    assert got["setup_h2d_s"] == pytest.approx(6 + 2 + 50)


def test_serve_cell_stops_at_the_windows_first_dispatch(ring):
    got = {name: reader(name)(serve_ctx()) for name in READERS}
    assert got == pytest.approx({
        "setup_trace_lower_s": 3.75, "setup_xla_compile_s": 30.0,
        "setup_cache_load_s": 4.0, "setup_h2d_s": 8.0})


def test_unlabelled_compiles_are_not_set_ups(ring):
    labels = {r["executable"] for r in setup_account.compiles(train_ctx())}
    assert labels == {"batch_predict", "als_sweep"}


def test_a_program_without_the_account_reads_nothing(ring, monkeypatch):
    monkeypatch.delattr(program_trace, "COMPILE_FIELDS")
    monkeypatch.delattr(program_trace, "UPLOAD_FIELDS")
    for name in READERS:
        assert reader(name)(train_ctx()) is None
        assert reader(name)(serve_ctx()) is None


def test_field_names_are_the_programs():
    assert {"t_start", "executable", "trace_s", "lower_s", "compile_s",
            "cache_load_s", "outcome"} <= set(COMPILE_FIELDS)
    assert {"t_start", "t_ready"} <= set(UPLOAD_FIELDS)


def test_union_of_upload_spans():
    assert setup_account.union_seconds(
        [(0.0, 2.0), (1.0, 3.0), (5.0, 6.0)]) == 4.0
