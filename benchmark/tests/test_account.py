"""Each reader of the program's serving account on a hand-made ring: the
window is the newest records, a ring that spans more than the slice (or a
program that keeps none) reads nothing."""

import importlib.util
import os

import pytest

from predictionio_tpu.obs import TRACER
from predictionio_tpu.obs.trace import (DISPATCH, DISPATCH_FIELDS, REQUEST,
                                        REQUEST_FIELDS)

from benchmark.lib import account

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reader(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def dispatch(seq, t, gate_s=0.004, turnaround_s=0.030, sync_s=0.0):
    """One dispatch whose first member was enqueued at `t`: 2 ms in the
    queue, 1 ms to form, the gate, 1 ms in begin, turnaround, 1 ms post."""
    rec = dict.fromkeys(DISPATCH_FIELDS, 0)
    t_closed = t + 0.003
    t_begin = t_closed + gate_s + 0.001
    rec.update(seq=seq, t_enqueue=t, t_dequeue=t + 0.002, t_closed=t_closed,
               t_gate=t_closed + gate_s, t_begin=t_begin,
               t_pickup=t_begin + 0.010, t_ready=t_begin + turnaround_s,
               t_done=t_begin + turnaround_s + 0.001, batch=3, bucket=4,
               sync_s=sync_s, tenant=None)
    return tuple(rec[f] for f in DISPATCH_FIELDS)


def request(t, server_s, seq):
    rec = dict(t_start=t, t_enqueue=t + 0.001, t_result=t + server_s - 0.001,
               t_written=t + server_s, dispatch_seq=seq, tenant=None)
    return tuple(rec[f] for f in REQUEST_FIELDS)


@pytest.fixture()
def ctx():
    """Warm traffic long before (it must not be read), then a 2 s window:
    four dispatches, eight requests of 10..80 ms inside the server."""
    TRACER.clear()
    TRACER.record(DISPATCH, dispatch(1, 10.0, gate_s=9.0))
    TRACER.record(REQUEST, request(10.0, 5.0, 1))
    for i in range(4):
        TRACER.record(DISPATCH, dispatch(
            2 + i, 100.0 + 0.5 * i, gate_s=0.004 * (i + 1),
            turnaround_s=0.030 + 0.010 * i,
            sync_s=0.020 if i == 0 else 0.0))
    for i in range(8):
        TRACER.record(REQUEST, request(100.0 + 0.2 * i, 0.010 * (i + 1),
                                       2 + i // 2))
    yield {"window": {"wall_s": 2.0, "dispatches": 4, "attempted": 8,
                      "query_p50_ms": 43.5},
           "trace": {"modules": {
               "jit__users_topk_b_packed": {"count": 3, "seconds": 0.060},
               "jit__users_topk_b": {"count": 1, "seconds": 0.020},
               "jit_other": {"count": 9, "seconds": 9.0}}}}
    TRACER.clear()


def test_gate_turnaround_and_device_queue(ctx):
    assert reader("serve_gate_wait_ms")(ctx) == pytest.approx(10.0)
    assert reader("serve_turnaround_ms")(ctx) == pytest.approx(45.0)
    # 80 ms of device time in 4 runs of the top-k executables: 20 ms each
    assert account.device_ms_per_dispatch(ctx) == pytest.approx(20.0)
    assert reader("serve_device_queue_ms")(ctx) == pytest.approx(25.0)


def test_sync_held_is_the_windows_sync_seconds_over_the_slice(ctx):
    assert reader("serve_sync_held_pct")(ctx) == pytest.approx(1.0)


def test_server_and_outside_add_up_to_the_slices_median(ctx):
    inside = reader("serve_request_server_ms_p50")(ctx)
    outside = reader("serve_outside_server_ms_p50")(ctx)
    assert inside == pytest.approx(40.0)          # 4th of 10..80 ms
    assert inside + outside == pytest.approx(ctx["window"]["query_p50_ms"])


def test_the_stage_reader_takes_the_new_entries_from_the_jobs_stages(ctx):
    ctx["window"]["stage_ms"] = {"dispatch": 1.5, "readback": 0.4}
    read = reader("serve_stage_ms")
    assert read(dict(ctx, metric="serve_stage_ms.dispatch")) == 1.5
    assert read(dict(ctx, metric="serve_stage_ms.readback")) == 0.4


NEW = ("serve_gate_wait_ms", "serve_turnaround_ms", "serve_device_queue_ms",
       "serve_sync_held_pct", "serve_request_server_ms_p50",
       "serve_outside_server_ms_p50")


@pytest.mark.parametrize("name", NEW)
def test_records_that_span_more_than_the_slice_read_nothing(ctx, name):
    # one more dispatch and request than the window had: the warm traffic's
    ctx["window"].update(dispatches=5, attempted=9)
    assert reader(name)(ctx) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_rings_reads_nothing(ctx, name, monkeypatch):
    """The commit before the rings: TRACER has no `recent`."""
    monkeypatch.delattr(type(TRACER), "recent")
    assert reader(name)(ctx) is None


def test_fewer_records_than_the_window_had_read_nothing(ctx):
    ctx["window"].update(dispatches=50)
    assert reader("serve_gate_wait_ms")(ctx) is None


def test_every_new_entry_is_in_the_benchmark_and_has_a_reader():
    import json
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW + ("serve_stage_ms.dispatch", "serve_stage_ms.readback"):
        assert entries[name]["workloads"] == [
            "rec-amazonbooks14-r200.serve-uniform"]
    from benchmark.lib.spec import Spec
    for name in NEW:
        assert callable(Spec().reader(name))
