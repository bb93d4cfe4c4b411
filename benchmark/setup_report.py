"""Builder's tool (PR 37): one run of a cell as run.py makes it, then the
program's set-up account of that run in full, cut as the `setup_*` readers cut
it (benchmark/lib/setup_account.py): per executable label its trace, lowering,
compile and cache-load seconds and its outcomes, per table its uploads, bytes,
host preparation and seconds to ready, the uploads' union wall, and the job's
own set-up spans beside them.

    python3 benchmark/setup_report.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1> [--out DIR] [--tag TAG]

Prints run.py's two lines, then one more, {"setup_report": ...}; with --out it
also writes that to DIR/<workload>-<seed>-<tag>.json. On a program that keeps
no account (the commit before PR 37) the account is null.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()            # process start, as run.py takes it

import argparse                      # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import sys                           # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import run                        # noqa: E402
from benchmark.lib import setup_account          # noqa: E402
from benchmark.lib.spec import Spec              # noqa: E402

STAGES = ("trace_s", "lower_s", "compile_s", "cache_load_s")


def account(window: dict) -> dict | None:
    """The set-up's records, summed per executable and per table."""
    try:
        from predictionio_tpu.utils.device_cache import settle_uploads
        settle_uploads()
    except ImportError:
        return None
    ctx = {"window": window}
    compiles = setup_account.compiles(ctx)
    uploads = setup_account.uploads(ctx)
    if compiles is None or uploads is None:
        return None
    execs: dict = {}
    for r in compiles:
        e = execs.setdefault(r["executable"], dict.fromkeys(STAGES, 0.0))
        for st in STAGES:
            e[st] += r[st]
        e[r["outcome"]] = e.get(r["outcome"], 0) + 1
    tables: dict = {}
    for r in uploads:
        t = tables.setdefault(r["table"], {"uploads": 0, "bytes": 0,
                                           "host_prep_s": 0.0,
                                           "put_s": 0.0, "ready_s": 0.0})
        t["uploads"] += 1
        t["bytes"] += r["bytes"]
        t["host_prep_s"] += r["host_prep_s"]
        t["put_s"] += r["t_put"] - r["t_start"] - r["host_prep_s"]
        t["ready_s"] += r["t_ready"] - r["t_start"]
    total = {st: sum(e[st] for e in execs.values()) for st in STAGES}
    total["compile_s_of_misses"] = sum(
        r["compile_s"] for r in compiles if r["outcome"] == "compiled")
    total["requests"] = len(compiles)
    total["upload_bytes"] = sum(t["bytes"] for t in tables.values())
    total["upload_ready_s"] = sum(t["ready_s"] for t in tables.values())
    total["upload_wall_s"] = setup_account.union_seconds(
        (r["t_start"], r["t_ready"]) for r in uploads)
    total["compile_wall_s"] = setup_account.union_seconds(
        (r["t_start"], r["t_end"]) for r in compiles)
    return {"executables": execs, "tables": tables, "total": total}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--out")
    p.add_argument("--tag", default="run")
    a = p.parse_args(argv)
    result = run.run_cell(Spec(REPO), a.workload, a.seed, a.seconds,
                          bool(a.trace), t0=_T0)
    print(json.dumps(run._finite({
        "workload": a.workload, "seed": a.seed, "spans": result["spans"],
        "window": result["window"], "numbers": result["numbers"]})),
        flush=True)
    print(json.dumps(run.last_line(result)), flush=True)
    report = run._finite({
        "workload": a.workload, "seed": a.seed, "tag": a.tag,
        "correct": result["correct"],
        "setup_s": result["window"].get("setup_s"),
        "spans": result["spans"],
        "metrics": {k: m["value"] for k, m in result["metrics"].items()},
        "account": account(result["window"])})
    print(json.dumps({"setup_report": report}), flush=True)
    if a.out:
        os.makedirs(a.out, exist_ok=True)
        with open(os.path.join(
                a.out, f"{a.workload}-{a.seed}-{a.tag}.json"), "w") as f:
            json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
