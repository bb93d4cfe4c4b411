"""A builder's tool: one traced run of a cell, reduced by the program's own
stage names before the trace is deleted.

    JAX_COMPILATION_CACHE_DIR=<an empty directory> \\
    python3 benchmark/scoped.py --workload <name> --seed <n>

`run.py`'s steps with one between them: measure -> lib/scopes on
m["trace_dir"] -> judge -> finish (which deletes the trace). One line goes to
chiprun_out/scoped.<workload>.jsonl: device seconds by `pio.*` scope, the
Pallas solves split into primal and dual, the program's host spans, the idle
gaps by `pio.*` span, the run's per-layer metrics and breakdown and, for a
serve cell, the account of one query from the program's serving account
(lib/account.py). `--sample` writes a small sample of the trace (the tests'
recorded one).

The compile cache has to start empty: the persistent cache's key leaves an
executable's debug information out, so one that a commit without the scopes
compiled is loaded without the names (PERF.md section 3)."""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import run                          # noqa: E402
from benchmark.lib import account, scopes          # noqa: E402
from benchmark.lib.spec import Spec                # noqa: E402


def query_account(window: dict, modules: dict) -> dict | None:
    """Where one query's time goes, in milliseconds, as means over the
    window's dispatches: its stages in order, their sum beside the first
    enqueue -> done they should add up to, and the requests' medians."""
    ctx = {"window": window, "trace": {"modules": modules}}
    d = account.window_dispatches(ctx)
    r = account.window_requests(ctx)
    if not d or not r:
        return None
    stages = {name + "_ms": account.mean_ms(d, later, earlier)
              for name, later, earlier in account.DISPATCH_PARTS}
    out = dict(stages, sum_ms=sum(stages.values()),
               enqueue_to_done_ms=account.mean_ms(d, "t_done", "t_enqueue"),
               completion_wait_ms=account.mean_ms(d, "t_pickup", "t_begin"),
               device_ms=account.device_ms_per_dispatch(ctx),
               sync_ms_when_sampled=None, dispatches=len(d),
               mean_batch=sum(x["batch"] for x in d) / len(d))
    synced = [x["sync_s"] for x in d if x["sync_s"] > 0]
    if synced:
        out["sync_ms_when_sampled"] = 1e3 * sum(synced) / len(synced)
        out["synced_dispatches"] = len(synced)
    batched = [x for x in r if x["dispatch_seq"] >= 0]
    out["server_ms_p50"] = account.server_ms_p50(ctx)
    for name, later, earlier in account.REQUEST_PARTS:
        out[name + "_ms_p50"] = 1e3 * account.percentile(
            [x[later] - x[earlier] for x in batched], 50)
    out["requests"], out["unbatched"] = len(r), len(r) - len(batched)
    return out


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=40.0,
                   help="cut to the cell's traced slice, as run.py cuts it")
    p.add_argument("--sample", default=None)
    a = p.parse_args()
    spec = Spec(REPO)
    cell = spec.cell(a.workload)
    m = run.measure(spec, cell, a.seed, a.seconds, traced=True)
    planes = scopes.load(m["trace_dir"])
    reduced = scopes.reduce(planes)
    if a.sample:
        with open(a.sample, "w") as f:
            json.dump(scopes.sample(planes), f)
    del planes
    numbers, _reference = run.judge(spec, cell, m)
    result = run.finish(spec, cell, m, numbers)
    line = run._finite({
        "workload": a.workload, "seed": a.seed, "correct": result["correct"],
        "device": result["device"], "scopes": reduced,
        "account": query_account(result["window"],
                                 result.get("modules") or {}),
        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        "breakdown": result["breakdown"], "modules": result.get("modules"),
        "window": result["window"], "spans": result["spans"]})
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out",
                           f"scoped.{a.workload}.jsonl"), "a") as f:
        f.write(json.dumps(line) + "\n")
    print(json.dumps(line), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
