"""Many seeds of one cell in one process, the first few with the control's
readings (and a planted fault's) beside the program's and the harness's own
verdict on each: what the limits in benchmark/limits/ were set from.

    python3 benchmark/prove.py --workload <name> --seeds 11,12,... --seconds 10

One line per seed goes to chiprun_out/prove.<workload>.jsonl: every number
compared, `correct`, and for each control its numbers (`control:<name>`) and
whether they pass the cell's limits (`control_correct:<name>`, which has to
read false), the set-up's spans; the seed's per-request latencies and
collector pauses, where the job has them, go to
chiprun_out/prove.<workload>.<seed>.detail.json. `--seconds` takes one
length or one per seed. A benchmark run never comes here: the control is the
reference put in the program's place at a lower precision, and it is no part
of a run."""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import run                      # noqa: E402
from benchmark.lib import compare, trace       # noqa: E402
from benchmark.lib.spec import Spec            # noqa: E402


def prove_seed(spec: Spec, workload: str, seed: int, seconds: float,
               traced: bool = False, controls: tuple = (),
               need_chip: bool = True, trace_sample: str | None = None
               ) -> dict:
    """One run as run.run_cell makes it, with each control's numbers and
    the verdict of compare.decide on them beside the program's.
    `trace_sample` names a file for a small sample of a traced run's events
    (the tests' recorded trace)."""
    cell = spec.cell(workload)
    m = run.measure(spec, cell, seed, seconds, traced, need_chip)
    numbers, reference = run.judge(spec, cell, m)
    read = {name: m["job"].compare(m["collected"], reference, name)
            for name in controls}
    if traced and trace_sample:
        with open(trace_sample, "w") as f:
            json.dump(trace.sample(trace.load(m["trace_dir"])), f)
    result = run.finish(spec, cell, m, numbers)
    for name, control_numbers in read.items():
        result["control:" + name] = control_numbers
        result["control_correct:" + name], _ = compare.decide(
            control_numbers, cell["limits"])
    return result


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", default="5",
                   help="one window length, or one per seed with commas")
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--control", default="config",
                   help="'config': the configuration's control_precision; "
                        "'' for none; or names with commas, 'fault:half' "
                        "among them for a train cell")
    p.add_argument("--control-seeds", type=int, default=3,
                   help="the control is read on the first so many seeds")
    a = p.parse_args()
    spec = Spec(REPO)
    control = (spec.cell(a.workload)["config"]["control_precision"]
               if a.control == "config" else a.control)
    controls = tuple(filter(None, control.split(",")))
    seeds = [int(s) for s in a.seeds.split(",")]
    seconds = [float(s) for s in a.seconds.split(",")]
    if len(seconds) == 1:
        seconds *= len(seeds)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    stem = os.path.join(REPO, "chiprun_out", f"prove.{a.workload}.")
    out = stem + "jsonl"
    for n, (seed, length) in enumerate(zip(seeds, seconds, strict=True)):
        t0 = time.perf_counter()
        result = prove_seed(
            spec, a.workload, seed, length, bool(a.trace),
            controls if n < a.control_seeds else (),
            trace_sample=os.path.join(
                REPO, "chiprun_out", f"trace_sample.{a.workload}.json"))
        result["whole_s"] = time.perf_counter() - t0
        result["seconds"] = length
        detail = result.pop("detail")
        if detail:
            with open(stem + f"{seed}.detail.json", "w") as f:
                json.dump(run._finite(detail), f)
        line = json.dumps(run._finite(result))
        with open(out, "a") as f:
            f.write(line + "\n")
        print(line, flush=True)
        del result
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
