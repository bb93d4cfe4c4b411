"""The reduction from a trace to device seconds by the program's own stage
names: on a small hand-made trace in the shape `scopes.load` gives, on a
sample recorded on the chip in a traced run of the serve cell from an empty
compile cache (`scopes.sample`, through scoped.py --sample), and the reader
of the profiler's file against jax's own on a trace made here."""

import json
import os

import pytest

from benchmark.lib import scopes, trace

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "trace_sample.scoped.json")

J = "jit(_users_topk_b_packed)/"
PLANES = [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ("jit__users_topk_b_packed(1)", 1.0, 1.0, "")]},
        {"name": "XLA Ops", "events": [
            # the compiler's own conversion of a parameter: no scope, read
            # by the gather
            ("%copy = bf16[64,8] copy(f32[64,8] %user_factors.1)",
             1.0, 0.6, "user_factors:"),
            ("%fusion = bf16[4,8] fusion(bf16[64,8] %copy, s32[4] %p.2)",
             1.6, 0.1, J + "pio.serve.user_rows/gather:"),
            ("%convolution_select_fusion = f32[4,32] fusion(bf16[4,8] "
             "%fusion)", 1.7, 0.2, J + "pio.serve.score/br,ir->bi/dot:"),
            ("%custom-call = (f32[4,4]) custom-call(f32[4,32] "
             "%convolution_select_fusion)", 1.9, 0.05,
             J + "pio.serve.topk/top_k:"),
            ("%orphan = f32[] add(f32[] %a, f32[] %b)", 1.95, 0.05, ""),
            # a loop's own event, beside its body's: never counted
            ("%while.3 = (s32[]) while(%tuple.1)", 3.0, 0.5, ""),
            ("%pio_cg_dual_b64_n176.3 = f32[64,176] custom-call(f32[64,176,"
             "176] %g)", 3.0, 0.3,
             "jit(_solve_sweep_impl)/while/body/closed_call/"
             "pio.sweep.solve.dual/pio_cg_dual_b64_n176/pallas_call:"),
            ("%pio_cg_primal_b64_n200.1 = f32[64,200] custom-call(f32[64,200,"
             "200] %h)", 3.3, 0.2,
             "jit(_solve_sweep_impl)/while/body/closed_call/"
             "pio.sweep.solve.primal/pio_cg_primal_b64_n200/pallas_call:"),
        ]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [
            ("pio.batch.begin", 2.2, 0.4, ""),
            ("pio.device_sync", 2.3, 0.15, ""),
            ("DevicePut", 2.35, 0.01, "")]},
        {"name": "python3", "events": [("pio.batch.post", 0.5, 0.3, "")]}]},
]


def test_seconds_by_scope_inherited_and_unscoped():
    r = scopes.reduce(PLANES)
    assert r["by_scope"] == pytest.approx({
        "pio.sweep.solve.dual": 0.3, "pio.serve.score": 0.2,
        "pio.sweep.solve.primal": 0.2, "pio.serve.user_rows": 0.1,
        "pio.serve.topk": 0.05})
    # the copy carries no scope: counted under the stage that reads it,
    # and apart
    assert r["inherited"] == pytest.approx({"pio.serve.user_rows": 0.6})
    assert r["unscoped"] == pytest.approx({"orphan": 0.05})
    assert r["op_s"] == pytest.approx(1.5)        # the loop's event left out
    assert r["scoped_pct"] == pytest.approx(100 * 1.45 / 1.5)
    assert r["busy_s"] == pytest.approx(1.5)      # 1.0-2.0 and 3.0-3.5


def test_two_programs_that_name_their_instructions_alike():
    """Every batch bucket is a program of its own and calls its operations
    `%copy`, `%fusion`: which stage reads an unscoped one is settled inside
    the program whose run it lies in."""
    device = PLANES[0]
    other = {"name": device["name"], "lines": [
        {"name": "XLA Modules", "events": device["lines"][0]["events"] + [
            ("jit__users_topk_b_packed(2)", 5.0, 1.0, "")]},
        {"name": "XLA Ops", "events": device["lines"][1]["events"] + [
            # here `%copy` is the scalar the scoring reads, and `%fusion`
            # what is left of a TopK rewrite, with no scope and no reader
            ("%copy = s32[] copy(s32[] %n_items.1)", 5.0, 0.1, ""),
            ("%iota_compare_fusion = pred[32] fusion(s32[] %copy)",
             5.1, 0.2, J + "pio.serve.score/lt:"),
            ("%fusion = (f32[4,4]) fusion(f32[4,32] %reshape.1)",
             5.3, 0.3, "")]}]}
    r = scopes.reduce([other, PLANES[1]])
    assert r["inherited"] == pytest.approx({"pio.serve.user_rows": 0.6,
                                            "pio.serve.score": 0.1})
    assert r["by_scope"]["pio.serve.score"] == pytest.approx(0.4)
    assert r["unscoped"] == pytest.approx({"orphan": 0.05, "fusion": 0.3})


def test_pallas_solves_split_into_primal_and_dual():
    r = scopes.reduce(PLANES)
    assert r["pallas"] == pytest.approx({"dual": 0.3, "primal": 0.2})
    assert set(r["pallas_kernels"]) == {"pio_cg_dual_b64_n176",
                                        "pio_cg_primal_b64_n200"}


def test_host_spans_and_idle_named_by_the_innermost_pio_span_only():
    r = scopes.reduce(PLANES)
    assert r["host_spans"]["pio.batch.begin"] == {
        "count": 1, "seconds": pytest.approx(0.4)}
    assert "DevicePut" not in r["host_spans"]
    # the one gap, 2.0-3.0, has its middle under begin alone (the sync
    # ended at 2.45; XLA's own DevicePut is no name of ours)
    assert r["idle_by_span"] == pytest.approx({"pio.batch.begin": 1.0})
    quiet = [PLANES[0], {"name": "/host:CPU", "lines": []}]
    assert scopes.reduce(quiet)["idle_by_span"] == pytest.approx(
        {scopes.NO_SPAN: 1.0})


def test_scope_of_takes_the_innermost():
    assert scopes.scope_of(
        "jit(f)/pio.sweep.solve.dual/pio.sweep.solve.jnp_cg/while/dot:"
    ) == "pio.sweep.solve.jnp_cg"
    assert scopes.scope_of("jit(f)/while/body/add:") is None
    assert scopes.scope_of("") is None


def test_no_device_plane_reads_nothing():
    r = scopes.reduce([PLANES[1]])
    assert r["op_s"] == 0.0 and r["by_scope"] == {}
    assert set(r["host_spans"]) == {"pio.batch.begin", "pio.device_sync",
                                    "pio.batch.post"}


def test_the_files_reader_agrees_with_jaxs_own(tmp_path):
    """`scopes.load` reads the profiler's file itself, for the stats that
    jax's reader does not show: names, starts and durations have to come
    out as `trace.load` gives them."""
    import jax
    import jax.numpy as jnp
    from predictionio_tpu.obs import TRACER

    @jax.jit
    def f(x):
        with jax.named_scope("pio.sweep.gather"):
            return (x * 2).sum()

    f(jnp.ones(100)).block_until_ready()
    trace.start(str(tmp_path))
    with TRACER.region("batch.begin", batch=3):
        f(jnp.ones(100)).block_until_ready()
    trace.stop()

    def flat(planes, width):
        return sorted((p["name"], ln["name"]) + tuple(e[:3])
                      for p in planes
                      if p["name"].startswith(("/device:", "/host:CPU"))
                      for ln in p["lines"] for e in ln["events"])
    mine, theirs = flat(scopes.load(str(tmp_path)), 4), flat(
        trace.load(str(tmp_path)), 3)
    assert len(mine) == len(theirs) > 0
    for a, b in zip(mine, theirs):
        assert a[:3] == b[:3]
        assert a[3] == pytest.approx(b[3], abs=1e-9)
        assert a[4] == pytest.approx(b[4], abs=1e-9)
    assert any(e[2] == "pio.batch.begin" for e in mine)


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded sample")
def test_recorded_serve_sample_reduces():
    with open(RECORDED) as f:
        planes = json.load(f)
    r = scopes.reduce(planes)
    # three runs of the batch-2 executable: its stages are there under their
    # own names, and the compiler's conversion of the user table (most of
    # its time) is inherited by the gather that reads it
    assert {"pio.serve.user_rows", "pio.serve.score",
            "pio.serve.pack"} <= set(r["by_scope"])
    assert r["inherited"]["pio.serve.user_rows"] > 0.6 * r["op_s"]
    # what this executable's TopK rewrite (reshape, custom call, sort) is
    # left with carries no scope: reported by kind, never guessed
    assert "custom-call TopK" in r["unscoped"]
    assert r["scoped_pct"] > 95.0
    assert 0 < r["busy_s"] <= r["op_s"] * 1.001
    assert {"pio.batch.begin", "pio.batch.post", "pio.readback.wait",
            "pio.http.request", "pio.query"} <= set(r["host_spans"])
    assert all(k.startswith("pio.") or k == scopes.NO_SPAN
               for k in r["idle_by_span"])
