"""One run of one cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Sets up (data and weights from the seed, plan, upload, compile or cache
load, warm-up of the cell's own shapes), measures for --seconds, compares
what the timed path produced with the configuration's plain reference, and
prints one JSON object as the last line of standard output: the contract's
keys and no others. The set-up's spans, and what else the window read, go on a
line before it.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()            # process start, to within the import

import argparse                      # noqa: E402
import json                          # noqa: E402
import math                          # noqa: E402
import os                            # noqa: E402
import shutil                        # noqa: E402
import sys                           # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import compare, device, trace  # noqa: E402
from benchmark.lib.peaks import peaks_for         # noqa: E402
from benchmark.lib.spec import Spec               # noqa: E402


def measure(spec: Spec, cell: dict, seed: int, seconds: float, traced: bool,
            need_chip: bool = True, t0: float | None = None) -> dict:
    """Set-up, the window, and what the job set aside of the timed path's
    output; when this returns the program's state is freed and the peak
    memory read. `need_chip=False` skips the look for a chip (the
    harness's own tests)."""
    t0 = time.perf_counter() if t0 is None else t0
    device.prepare_environment(spec.repo)
    info = device.require_chip(cell["chips"]) if need_chip \
        else device.device_info()
    compiles = device.CompileCounter()
    spans: dict = {}
    job = spec.job(cell).Job(cell, seed, spans)
    trace_dir = os.path.join(spec.repo, ".bench_work", "trace")
    try:
        job.setup()
        setup_s = time.perf_counter() - t0
        before = compiles.snapshot()
        if traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace.start(trace_dir)
            try:
                window = job.window(min(
                    seconds, cell["traffic"]["traced_slice_seconds"]))
            finally:
                trace.stop()
        else:
            window = job.window(seconds)
        after = compiles.snapshot()
        info["memory_peak_bytes"] = device.memory_peak_bytes()
        collected = job.collect()
    finally:
        job.close()
    window["setup_s"] = setup_s
    return {"job": job, "seed": seed, "window": window,
            "collected": collected, "info": info, "spans": spans,
            "compiles_in_window": after[0] - before[0],
            "trace_dir": trace_dir if traced else None}


def judge(spec: Spec, cell: dict, m: dict):
    """The numbers of the comparison with the configuration's plain
    reference, and the reference (prove.py reads the control from it)."""
    reference = spec.reference(cell)
    t_ref = time.perf_counter()
    numbers = m["job"].compare(m["collected"], reference)
    m["spans"]["reference_s"] = time.perf_counter() - t_ref
    return numbers, reference


def finish(spec: Spec, cell: dict, m: dict, numbers: dict) -> dict:
    """All that one run found (`last_line` picks the contract's keys out of
    it): the verdict, and the cell's end-to-end metrics or, traced, its
    per-layer ones, each from a reader of its own."""
    job, window, info = m["job"], m["window"], m["info"]
    correct, compared = compare.decide(numbers, cell["limits"])
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": window["failed"], "metrics": {}, "device": info,
              "workload": cell["name"], "seed": m["seed"],
              "spans": {**m["spans"], **job.resolved}, "numbers": numbers,
              "detail": window.pop("detail", None),
              # every plain number the window read, end to end or not
              "window": {k: v for k, v in window.items()
                         if isinstance(v, (int, float))}}
    if m["trace_dir"]:
        reduced = trace.reduce(trace.load(m["trace_dir"]),
                               window_s=window["wall_s"])
        shutil.rmtree(m["trace_dir"], ignore_errors=True)
        info["busy_s"], info["window_s"] = (reduced["busy_s"],
                                            reduced["window_s"])
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        result["modules"] = reduced["modules"]
        ctx = {"cell": cell, "config": cell["config"], "window": window,
               "spans": m["spans"], "trace": reduced, "work": job.work(),
               "peaks": (peaks_for(info["kind"])
                         if info["platform"] == "tpu" else None),
               "compiles_in_window": m["compiles_in_window"]}
        for metric in spec.metrics_of(cell["name"], "per_layer"):
            value = spec.reader(metric["name"])(
                dict(ctx, metric=metric["name"]))
            if value is not None:
                result["metrics"][metric["name"]] = {
                    "value": value, "unit": metric["unit"]}
    else:
        for metric in spec.metrics_of(cell["name"], "end_to_end"):
            result["metrics"][metric["name"]] = {
                "value": window[metric["name"]], "unit": metric["unit"]}
    job.release()
    result["compared"] = compared
    return result


def run_cell(spec: Spec, workload: str, seed: int, seconds: float,
             traced: bool, need_chip: bool = True,
             t0: float | None = None) -> dict:
    cell = spec.cell(workload)
    m = measure(spec, cell, seed, seconds, traced, need_chip, t0)
    numbers, _reference = judge(spec, cell, m)
    return finish(spec, cell, m, numbers)


def last_line(result: dict) -> dict:
    """The contract's keys, the traced run's breakdown, and each number
    compared beside its limit under a key of its own, which comes last."""
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    if "breakdown" in result:
        keys.append("breakdown")
    return _finite({k: result[k] for k in keys + ["compared"]})


def _finite(x):
    """JSON has no NaN or Infinity: a number that is not finite prints as
    null."""
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


def main(argv=None, need_chip: bool = True) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    result = run_cell(Spec(REPO), a.workload, a.seed, a.seconds,
                      bool(a.trace), need_chip=need_chip, t0=_T0)
    print(json.dumps(_finite({
        "workload": a.workload, "seed": a.seed, "spans": result["spans"],
        "window": result["window"], "numbers": result["numbers"],
        "modules": result.get("modules")})), flush=True)
    compare.report(result["compared"], result["correct"])
    print(json.dumps(last_line(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
