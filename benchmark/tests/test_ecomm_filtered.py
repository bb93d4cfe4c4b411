"""The e-commerce serve cell's files at a tiny size on the CPU: the draw, the
counts, the readers, and one run of the job through the harness from a tree
to which a tiny configuration, its mix, its cell and its limits are ADDED as
files (the harness finds the job, the load generator's parent, the reference
and the readers by name)."""

import json
import os
import shutil

import numpy as np
import pytest

import tinytree
from benchmark import prove, run
from benchmark.lib import counts_masked, datagen_ecomm
from benchmark.lib.spec import Spec

REPO = tinytree.REPO
CELL = "tiny-ecomm.serve-filtered-tiny"
BIG = "ecomm-taobao-ub-r200-served"


def _config(name=BIG):
    with open(os.path.join(REPO, "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)


def _mix():
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "serve-filtered.json")) as f:
        return json.load(f)


def tiny_config():
    c = _config()
    c.update(name="tiny-ecomm", n_users=1600, n_items=3000, n_categories=40,
             rank=16, store_user_stride=4)
    c["assumed"] = dict(c["assumed"], seen_mean=12.0, user_degree_cap=60,
                        item_popularity_offset=8.0,
                        unavailable_replaced=0.5)
    return c


def tiny_mix():
    m = _mix()
    m.update(rate_qps=60.0, check_requests=64, check_after_reset=16,
             connections=8, visitors=32, whitelist_items=40,
             blacklist_items=5, warm_seconds=0.5)
    return m


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("ecomm"))
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    b = os.path.join(root, "benchmark")
    with open(os.path.join(b, "configs", "tiny-ecomm.json"), "w") as f:
        json.dump(tiny_config(), f)
    with open(os.path.join(b, "traffic", "serve-filtered-tiny.json"),
              "w") as f:
        json.dump(tiny_mix(), f)
    limits = dict.fromkeys(
        ["dot_rank_gap_max", "dot_score_err_max", "cos_rank_gap_max",
         "cos_score_err_max"], 2e-2)
    limits.update(dict.fromkeys(
        ["filter_violations", "seen_timeouts", "constraint_failures",
         "malformed", "unanswered", "failed_requests"], 0))
    with open(os.path.join(b, "limits", CELL + ".json"), "w") as f:
        json.dump(limits, f)
    bench["configs"].append({"name": "tiny-ecomm", "source": "test",
                             "file": "benchmark/configs/tiny-ecomm.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-ecomm",
                               "traffic": "serve-filtered-tiny", "chips": 1,
                               "why": "test"})
    big = BIG + ".serve-filtered"
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if big in m.get("workloads", []):
                m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


# -- the benchmark's entries -----------------------------------------------

def test_the_cell_and_its_metrics_are_declared():
    spec = Spec(REPO)
    cell = spec.cell(BIG + ".serve-filtered")
    assert cell["chips"] == 1 and cell["traffic"]["job"] == \
        "http-queries-ecomm"
    # judged on the rate alone. Its median and its 95th percentile are
    # per-layer readings (`query_p50_ms.filtered`, `query_p95_ms.filtered`):
    # over sets of six seeds neither spreads by less than half the
    # end-to-end metric's bound (PERF.md sections 4 and 6)
    assert {m["name"] for m in spec.metrics_of(cell["name"],
                                               "end_to_end")} == {
        "queries_per_s", "setup_s"}
    per_layer = [m["name"] for m in spec.metrics_of(cell["name"],
                                                    "per_layer")]
    assert per_layer == [
        "masked_topk_roofline", "serve_mfu.filtered",
        "device_idle_pct.serve_filtered", "window_compiles.serve_filtered",
        "serve_avg_batch.filtered", "filter_host_ms", "seen_read_ms_p95",
        "filter_h2d_bytes", "query_p50_ms.filtered", "query_p95_ms.filtered",
        "query_p99_ms.filtered",
        "serve_stall_ms.filtered", "loadgen_late_ms_p95.filtered",
        "serve_gc_pause_pct.filtered", "serve_sync_held_pct.filtered",
        "serve_gate_wait_ms.filtered", "serve_turnaround_ms.filtered",
        "serve_request_server_ms_p50.filtered",
        "serve_stage_ms_filtered.formation",
        "serve_stage_ms_filtered.dispatch",
        "serve_stage_ms_filtered.completion",
        "serve_stage_ms_filtered.readback"]
    moved = {m["moves"] for m in spec.metrics_of(cell["name"], "per_layer")}
    assert moved == {"queries_per_s"}
    for name in per_layer:
        assert callable(spec.reader(name))
    assert set(cell["limits"]) >= {"filter_violations", "seen_timeouts",
                                   "constraint_failures",
                                   "dot_rank_gap_max", "cos_score_err_max"}
    mix = cell["traffic"]
    assert abs(sum(mix["kinds"].values()) - 1.0) < 1e-9
    assert mix["rate_qps"] == pytest.approx(
        mix["rate_share_of_knee"] * mix["knee_qps"])


def test_the_configuration_is_the_published_counts():
    c, train = _config(), _config("ecomm-taobao-ub-r200")
    for key in ("n_users", "n_items", "n_categories", "rank"):
        assert c[key] == train[key]
    assert c["n_views"] == train["n_events"] and c["n_buys"] == train["n_buy"]
    assert c["reduced"] == ["store_user_stride"]
    assert datagen_ecomm.store_users(c).size == 61750


# -- the draw ----------------------------------------------------------------

def test_category_sizes_are_the_configurations():
    sizes = datagen_ecomm.category_sizes(_config())
    assert sizes.size == 9439 and sizes.sum() == 4162024
    assert sizes.min() >= 1 and sizes.max() <= 0.08 * 4162024
    assert (np.diff(sizes) <= 0).all()


def test_the_draw_is_the_seeds():
    c, mix = tiny_config(), tiny_mix()
    pop = datagen_ecomm.Popularity(c, 5)
    cat = datagen_ecomm.item_categories(c, 5)
    assert np.bincount(cat, minlength=40).tolist() == \
        datagen_ecomm.category_sizes(c).tolist()
    u, i, bought = datagen_ecomm.seen_pairs(c, 5, pop)
    assert (np.diff(u) >= 0).all() and set(u.tolist()) <= set(
        datagen_ecomm.store_users(c).tolist())
    assert np.unique(u.astype(np.int64) * 3000 + i).size == u.size
    assert abs(u.size / 400 - 12.0) < 1.5
    again = datagen_ecomm.seen_pairs(c, 5, datagen_ecomm.Popularity(c, 5))
    assert (again[1] == i).all()
    other = datagen_ecomm.seen_pairs(c, 6, datagen_ecomm.Popularity(c, 6))
    assert other[1].size != i.size or (other[1] != i).any()
    reqs = datagen_ecomm.requests(c, mix, 5, 200, 0, cat, pop)
    kinds = [q["kind"] for q in reqs]
    assert [kinds.count(k) for k in datagen_ecomm.KINDS] == \
        [60, 80, 20, 20, 10, 10]
    for q in reqs:
        d = datagen_ecomm.body(q, 10)
        if q["kind"] == "campaign":
            assert len(d["whiteList"]) == 40
            assert len(set(cat[q["white"]].tolist())) == 1
        if q["kind"] == "new_visitor":
            assert d["user"].startswith("v")
        if q["kind"] == "multi_category":
            assert len(d["categories"]) == 3


def test_a_reset_replaces_its_share_uniformly():
    c = tiny_config()
    v = datagen_ecomm.unavailable_versions(c, 5, 3)
    for old, new in zip(v, v[1:]):
        assert old.size == new.size == 30
        assert np.unique(new).size == 30 and new.max() < 3000
        assert np.setdiff1d(new, old).size == 15
    assert [x.tolist() for x in v] == [
        x.tolist() for x in datagen_ecomm.unavailable_versions(c, 5, 3)]
    # the full-size list: 1% of the catalogue, 2% of it a re-set
    big = datagen_ecomm.unavailable_versions(_config(), 5, 1)
    assert big[0].size == 41620
    assert np.setdiff1d(big[1], big[0]).size == 832


def test_a_reset_probe_asks_for_what_just_sold_out():
    c = tiny_config()
    v = datagen_ecomm.unavailable_versions(c, 5, 2)
    probes = datagen_ecomm.reset_probes(c, 5, 2, 3, v[1], v[2])
    assert len(probes) == 3
    for q in probes:
        assert q["kind"] == "reset_probe" and q["user"] % 4 == 0
        assert q["white"] == np.setdiff1d(v[2], v[1]).tolist()
        assert set(datagen_ecomm.body(q, 10)) == {"user", "num",
                                                  "whiteList"}
    assert datagen_ecomm.reset_probes(c, 5, 2, 0, v[1], v[2]) == []


# -- counts and readers ------------------------------------------------------

def test_counts_come_from_the_configuration_alone():
    assert counts_masked.query_flops(4162024, 200) == 2.0 * 4162024 * 200
    one = counts_masked.dispatch_bytes(4162024, 200, 16, 1, 68.0)
    assert one == pytest.approx(4162024 * (800 + 4 + 0.125)
                                + 16 * (800 + 4 * 68.0))
    assert one < (1 << 22) * 200 * 4 * 1.01


def test_the_roofline_reader_reads_the_modules_named_masked_topk():
    read = Spec(REPO).reader("masked_topk_roofline")
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    work = {"n_items": 4162024, "rank": 200, "category_slots": 1,
            "listed_per_query": 68.0, "factor_bytes": 4,
            "query_flops": 2.0 * 4162024 * 200}
    ctx = {"peaks": peaks, "work": work,
           "window": {"avg_batch": 8.0, "dispatches": 100},
           "trace": {"modules": {
               "jit__composed_masked_topk_packed": {"count": 110,
                                                    "seconds": 0.88},
               "jit_other": {"count": 5, "seconds": 1.0}}}}
    share = read(ctx)
    assert 40.0 < share < 60.0       # ~4.1 ms of bytes over 8 ms
    # the parent has no such module, and a train cell's work no such key
    assert read(dict(ctx, trace={"modules": {"jit_other": {
        "count": 1, "seconds": 1.0}}})) is None
    assert read(dict(ctx, work={"n_items": 1, "rank": 1})) is None
    for name in ("filter_host_ms", "seen_read_ms_p95", "filter_h2d_bytes",
                 "serve_stall_ms.filtered", "query_p95_ms.filtered",
                 "query_p50_ms.filtered",
                 "serve_stage_ms_filtered.formation"):
        assert Spec(REPO).reader(name)({"window": {},
                                       "metric": name}) is None
    window = {"stage_ms": {"formation": 1.5}, "query_p95_ms": 27.0,
              "query_p50_ms": 14.0, "stall_ms": 0.0}
    for name, want in (("serve_stage_ms_filtered.formation", 1.5),
                       ("query_p95_ms.filtered", 27.0),
                       ("query_p50_ms.filtered", 14.0),
                       ("serve_stall_ms.filtered", 0.0)):
        assert Spec(REPO).reader(name)({"window": window,
                                       "metric": name}) == want


# -- one run of the job ------------------------------------------------------

@pytest.fixture(scope="module")
def proved(tree):
    prove.REPO = run.REPO = tree
    spec = Spec(tree)
    return prove.prove_seed(
        spec, CELL, 3000000019, 4.0, traced=True, need_chip=False,
        controls=("float8_e4m3fn", "fault:category_ignored",
                  "fault:bitmap_behind"))


def test_the_run_is_correct_and_reports_the_cells_metrics(proved):
    assert proved["correct"], proved["compared"]
    n = proved["numbers"]
    assert n["filter_violations"] == 0 and n["seen_timeouts"] == 0
    # 64 of the window's requests and two probes behind each of 3 re-sets
    assert n["answers"] == 70 and n["probes"] == 6
    assert n["after_reset"] >= 16 + 6 and n["constraint_failures"] == 0
    assert n["dot_answers"] + n["cos_answers"] == 70 and n["cos_answers"]
    w = proved["window"]
    assert w["resets"] == 3 and w["constraint_reloads"] == 3
    assert w["reset_probes"] == 6
    # every dispatch of the window, from the program's histogram; and what
    # the window shows of a freeze
    assert w["seen_read_ms_p50"] <= w["seen_read_ms_p95"]
    assert w["stalls"] == 0 and w["stall_ms"] == 0.0
    assert w["tick_late_max_ms"] < 400.0
    assert "stall_reports" not in proved["spans"]
    assert 0 < w["completion_gap_max_ms"] < 400.0
    assert 0 < w["filter_h2d_bytes_per_dispatch"] < 64 * 1024
    assert w["filter_host_ms"] > 0 and w["seen_read_ms_p95"] > 0
    assert proved["spans"]["populate_events_per_s"] > 0
    assert proved["spans"]["result_cache_used"] is False
    got = set(proved["metrics"])
    assert {"serve_avg_batch.filtered", "window_compiles.serve_filtered",
            "filter_host_ms", "seen_read_ms_p95", "filter_h2d_bytes",
            "query_p50_ms.filtered", "query_p95_ms.filtered",
            "query_p99_ms.filtered",
            "serve_stall_ms.filtered", "loadgen_late_ms_p95.filtered",
            "serve_gc_pause_pct.filtered", "serve_sync_held_pct.filtered",
            "serve_gate_wait_ms.filtered", "serve_turnaround_ms.filtered",
            "serve_request_server_ms_p50.filtered",
            "serve_stage_ms_filtered.formation",
            "serve_stage_ms_filtered.readback"} <= got
    assert not {"query_p95_ms", "query_p50_ms"} & got


def test_the_control_and_both_planted_faults_fail(proved):
    assert not proved["control_correct:float8_e4m3fn"]
    low = proved["control:float8_e4m3fn"]
    assert low["filter_violations"] == 0
    assert low["dot_rank_gap_max"] > proved["numbers"]["dot_rank_gap_max"]
    for fault in ("fault:category_ignored", "fault:bitmap_behind"):
        assert not proved["control_correct:" + fault]
        assert proved["control:" + fault]["filter_violations"] > 0
    # a bitmap one re-set behind answers every probe with ten of the ids
    # that had just sold out
    assert proved["control:fault:bitmap_behind"]["filter_violations"] >= 60
