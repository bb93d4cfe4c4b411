"""Find a serve cell's knee, once, on the chip: one server, set up once, and
the cell's mix offered at a ladder of rates for a few seconds each.

    python3 benchmark/sweep.py --workload <name> --seed 1 --rates 50,100,200 --seconds 8

One line per step goes to chiprun_out/sweep.<workload>.jsonl, and the step's
per-request latencies and collector pauses to
chiprun_out/sweep.<workload>.<seed>.<step>-<rate>.detail.json. (A rate given
several times is as many windows of one process: how far a reading moves
from window to window where nothing but the draw of users differs.) The knee is the highest rate
with every request answered, no backlog growing (the second half's median
latency no worse than the first's by half) and the generator's lateness small
against the median. The traffic file states the knee and the cell's
`rate_qps`, a fixed share of it, written there by hand (PERF.md records the
sweep and why that share)."""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import device                # noqa: E402
from benchmark.lib.spec import Spec             # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=8.0)
    a = p.parse_args()
    spec = Spec(REPO)
    device.prepare_environment(REPO)
    cell = spec.cell(a.workload)
    info = device.require_chip(cell["chips"])
    spans: dict = {}
    job = spec.job(cell).Job(cell, a.seed, spans)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    stem = os.path.join(REPO, "chiprun_out", f"sweep.{a.workload}.")
    out = stem + "jsonl"
    try:
        job.setup()
        for step, rate in enumerate(float(r) for r in a.rates.split(",")):
            job.mix = dict(job.mix, rate_qps=rate)
            # another draw of users at every rate: a repeat would be
            # answered by the result cache
            w = job.window(a.seconds, salt=10 + step)
            detail = w.pop("detail")
            with open(stem + f"{a.seed}.{step}-{rate:g}.detail.json",
                      "w") as f:
                json.dump(detail, f)
            lat = np.array([np.inf if x is None else x
                            for x in detail["latency_s"]])
            half = len(lat) // 2
            w.update(rate_qps=rate, seed=a.seed, step=step, spans=spans,
                     device=info,
                     memory_peak_bytes=device.memory_peak_bytes(),
                     p50_first_half_ms=1e3 * float(np.median(lat[:half])),
                     p50_second_half_ms=1e3 * float(np.median(lat[half:])))
            line = json.dumps(w)
            with open(out, "a") as f:
                f.write(line + "\n")
            print(line, flush=True)
    finally:
        job.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
