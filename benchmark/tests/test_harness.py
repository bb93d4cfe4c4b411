"""One run of each job kind at a tiny size, driven from files ADDED beside
the benchmark's own (a configuration, a traffic mix, two cells, limits and
a per-layer metric): the harness finds each by name and needs no edit."""

import json
import os
import subprocess
import sys

import pytest

import tinytree
from benchmark import run
from benchmark.lib.spec import Spec

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tinytree.build(str(tmp_path_factory.mktemp("tree")))
    # a per-layer metric of a later PR: a reader of its own and an entry
    b = os.path.join(root, "benchmark")
    with open(os.path.join(b, "layer_metrics", "added_metric.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx['window']['attempted']\n")
    with open(os.path.join(b, "layer_metrics", "silent_metric.py"), "w") as f:
        f.write("def read(ctx):\n    return None\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name in ("added_metric", "silent_metric"):
        bench["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "test",
            "moves": "train_ratings_per_s", "workloads": ["tiny-r32.train"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def last_line(capsys, root, workload, trace, seconds="1.5", seed="3000000019"):
    run.REPO = root
    rc = run.main(["--workload", workload, "--seed", seed, "--seconds",
                   seconds, "--trace", str(trace)], need_chip=False)
    out, err = capsys.readouterr()
    assert rc == 0
    lines = out.strip().splitlines()
    # the set-up's spans stand on a line before the last, never in it
    spans = json.loads(lines[-2])
    assert spans["workload"] == workload and "reference_s" in spans["spans"]
    return json.loads(lines[-1]), err


@pytest.mark.parametrize("workload,metrics", [
    ("tiny-r32.train", {"train_ratings_per_s", "setup_s"}),
    ("tiny-r32.serve-tiny", {"query_p95_ms", "query_p50_ms",
                             "queries_per_s", "setup_s"}),
])
def test_last_line_is_the_contracts(tree, capsys, workload, metrics):
    line, err = last_line(capsys, tree, workload, 0)
    assert list(line) == CONTRACT_KEYS + ["compared"]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == metrics
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"      # stamped, never a chip
    # every number compared stands beside its limit, and on standard error
    assert all(set(c) == {"value", "limit"} for c in line["compared"].values())
    assert err.strip().splitlines()[-1] == "correct = true"
    for name in line["compared"]:
        assert f"compared {name} = " in err


def test_traced_run_reads_the_added_metric_and_leaves_out_a_silent_one(
        tree, capsys):
    line, _ = last_line(capsys, tree, "tiny-r32.train", 1)
    assert line["metrics"]["added_metric"]["value"] == line["attempted"]
    assert "silent_metric" not in line["metrics"]
    assert "plan_s" in line["metrics"]
    # no device plane on the CPU: shares of a roofline are left out, never 0
    assert "als_sweep_roofline" not in line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert list(line) == CONTRACT_KEYS + ["breakdown", "compared"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    # the window goes on from the state set-up left, with its programs
    assert line["metrics"]["window_compiles.train"]["value"] == 0


def test_traced_serve_run_reads_the_tail_and_the_collector(tree, capsys):
    line, _ = last_line(capsys, tree, "tiny-r32.serve-tiny", 1)
    m = line["metrics"]
    assert m["query_p99_ms"]["value"] > 0
    assert 0 <= m["serve_gc_pause_pct"]["value"] < 100
    assert m["window_compiles.serve"]["value"] == 0
    # `<quantity>.<suffix>` is read by <quantity>.py, which takes the stage
    # from the metric's own name
    assert (m["serve_stage_ms.formation"]["value"]
            != m["serve_stage_ms.completion"]["value"])


def test_same_seed_same_inputs(tree):
    from benchmark.lib import datagen
    cfg = Spec(tree).cell("tiny-r32.train")["config"]
    a, b = datagen.ratings(cfg, 2**31 + 7), datagen.ratings(cfg, 2**31 + 7)
    c = datagen.ratings(cfg, 2**31 + 8)
    assert all((x == y).all() for x, y in zip(a, b))
    assert not (a[1] == c[1]).all()
    # the degree sequences, and so the plan's shapes, belong to the
    # configuration: every seed has the same
    import numpy as np
    for x, y in ((a[0], c[0]), (a[1], c[1])):
        assert (np.sort(np.bincount(x)) == np.sort(np.bincount(y))).all()
    assert len(set(zip(a[0].tolist(), a[1].tolist()))) == a[0].size


def test_no_chip_no_result(tree, capsys):
    run.REPO = tree
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "tiny-r32.train", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out.strip() == ""


def test_bare_directory_gives_no_result(tree):
    """BENCHMARK.json and the benchmark's own files alone: no program, so
    no result and another exit code than 0."""
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny-r32.train",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tree,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
        capture_output=True, text=True)
    assert p.returncode != 0 and p.stdout.strip() == ""
