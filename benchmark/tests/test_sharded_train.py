"""The four-chip train cell's own files at a tiny size on a forced
four-device CPU mesh: one run of its job through run.py (in a process of its
own, which is the only way to a four-device backend from a test session that
has one), the faults `correct` has to catch, and the two readers the cell
brought, on recorded samples. Control flow and comparisons, never a device
number."""

import json
import os
import subprocess
import sys

import pytest

import tinytree
from benchmark.lib.spec import Spec, load_module

CELL = "tiny-sharded.train-sharded"
LIMITS = {"user_err_p50": 1e-3, "user_err_max": 1e-2,
          "item_err_p50": 1e-3, "item_err_max": 1e-2,
          "item_end_err_p50": 1e-3, "item_end_err_max": 1e-2,
          "nonfinite_rows_at_end": 0}

# what the child prints: one traced run, then the control and the planted
# fault read on the same collected rows
CHILD = """
import json, sys
sys.path[:0] = [{repo!r}, {tests!r}]
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)
from benchmark import prove
from benchmark.lib.spec import Spec
r = prove.prove_seed(Spec({tree!r}), {cell!r}, 2**31 + 33, 0.5, traced=True,
                     controls=("float8_e4m3fn", "fault:half"),
                     need_chip=False)
print("RESULT " + json.dumps({{k: r[k] for k in r if k != "detail"}}))
"""


def tiny_config() -> dict:
    """The configuration's own file with its counts cut, rank 32 and
    float32 operands (the CPU has no bfloat16 unit); every other key as it
    stands."""
    with open(os.path.join(tinytree.REPO, "benchmark", "configs",
                           "rec-amazon14-all-r200.json")) as f:
        c = json.load(f)
    c.update(tinytree.TINY_SIZES, name="tiny-sharded", source="test",
             rank=32, compute_dtype="float32")
    c["assumed"] = dict(c["assumed"], **tinytree.TINY_ASSUMED)
    return c


@pytest.fixture(scope="module")
def result(tmp_path_factory):
    root = tinytree.build(str(tmp_path_factory.mktemp("tree")))
    b = os.path.join(root, "benchmark")
    with open(os.path.join(b, "configs", "tiny-sharded.json"), "w") as f:
        json.dump(tiny_config(), f)
    with open(os.path.join(b, "limits", CELL + ".json"), "w") as f:
        json.dump(LIMITS, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-sharded", "source": "test", "reduced": [],
        "file": "benchmark/configs/tiny-sharded.json", "why": "test"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-sharded", "traffic": "train-sharded",
        "chips": 4, "why": "test"})
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if any(w.endswith(".train-sharded")
                   for w in m.get("workloads", [])):
                m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    child = CHILD.format(repo=tinytree.REPO, tree=root, cell=CELL,
                         tests=os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PIO_XLA_CACHE="off",
               TPU_LOG_DIR="disabled")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run([sys.executable, "-c", child], env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-4000:]
    line = [ln for ln in done.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_the_cell_runs_divided_over_four_devices_and_is_correct(result):
    assert result["correct"] is True, result["compared"]
    spans = result["spans"]
    assert spans["table_shards"] == 4 and spans["batch_shards"] == 4
    # rows of every shard of both tables are in the sample
    for side in ("user", "item"):
        assert len(spans["sample_rows_by_shard"][side]) == 4
        assert min(spans["sample_rows_by_shard"][side]) > 0
    assert result["window"]["iterations"] >= 1
    assert result["window"]["chips"] == 4
    assert result["device"]["count"] == 4


def test_what_the_half_sweeps_exchange_is_among_the_window_numbers(result):
    exchanged = result["spans"]["exchange_bytes"]
    assert set(exchanged) == {"user", "item"}
    sent = sum(side["sent"] for side in exchanged.values())
    assert sent > 0
    assert result["window"]["exchange_sent_bytes_per_iteration"] == sent
    assert result["metrics"]["sweep_exchange_bytes"]["value"] == sent
    # traced on the CPU: no device plane, so nothing of the trace to read,
    # and the readers that need none still report
    assert result["metrics"]["window_compiles.train_sharded"]["value"] == 0
    assert result["metrics"]["plan_s.train_sharded"]["value"] > 0


def test_the_control_and_the_planted_fault_fail(result):
    assert result["control_correct:float8_e4m3fn"] is False
    assert result["control_correct:fault:half"] is False
    for name in ("user", "item", "item_end"):
        assert (result["control:fault:half"][name + "_err_max"]
                > LIMITS[name + "_err_max"])


# -- the readers, on recorded samples ---------------------------------------

def _reader(name):
    return Spec(tinytree.REPO).reader(name)


def test_sharded_sweep_mfu_divides_by_every_chip():
    ctx = {"window": {"wall_s": 20.0, "iterations": 4, "chips": 4},
           "work": {"iteration_flops": 197e12}, "peaks": {"flops_per_s": 197e12}}
    # one chip-second of operations in a 5 s iteration on four chips
    assert _reader("sharded_sweep_mfu")(ctx) == pytest.approx(100.0 / 20.0)
    one = load_module(os.path.join(tinytree.REPO, "benchmark", "layer_metrics",
                                   "als_sweep_mfu.py"), "one_chip_mfu").read
    assert one(ctx) == pytest.approx(4 * 100.0 / 20.0)
    assert _reader("sharded_sweep_mfu")(dict(ctx, peaks=None)) is None
    assert _reader("sharded_sweep_mfu")(
        dict(ctx, window={"wall_s": 20.0, "iterations": 4})) is None


def test_sweep_exchange_bytes_reads_the_jobs_window_number():
    read = _reader("sweep_exchange_bytes")
    assert read({"window": {"exchange_sent_bytes_per_iteration": 3.5e9}}) \
        == 3.5e9
    # a program that keeps no count: the line leaves the metric out
    assert read({"window": {"iterations": 3}}) is None
