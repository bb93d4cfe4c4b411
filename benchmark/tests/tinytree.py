"""A copy of the benchmark in a directory of the test's own, with a tiny
configuration, its cells and their limits ADDED as files and entries: what
a later PR does, and what the harness must need no edit for."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_SIZES = {"n_users": 6000, "n_items": 2000, "n_ratings": 80000,
              "work_budget": 1 << 14}
TINY_ASSUMED = {"user_degree_cap": 500, "item_degree_cap": 2500,
                "item_popularity_offset": 8.0}


def tiny_config(name: str = "tiny-r32", base: str | None = None,
                rank: int = 32) -> dict:
    """A configuration cut to a size the CPU holds: the `base`
    configuration's own file with its counts cut (every other key as it
    stands), or a rank-`rank` one of the same shape."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           (base or "rec-amazonbooks14-r200") + ".json")) as f:
        c = json.load(f)
    c.update(TINY_SIZES, name=name, source="test",
             compute_dtype="float32")
    c["assumed"] = dict(c["assumed"], **TINY_ASSUMED)
    c.setdefault("serve", {"micro_batch": 16, "num": 10,
                           "result_cache": True})
    if base is None:
        c["rank"] = rank
    return c


TRAIN_LIMITS = {"user_err_p50": 1e-3, "user_err_max": 1e-2,
                "item_err_p50": 1e-3, "item_err_max": 1e-2,
                "item_end_err_p50": 1e-3, "item_end_err_max": 1e-2,
                "nonfinite_rows_at_end": 0}
SERVE_LIMITS = {"rank_gap_max": 2e-2, "score_err_max": 2e-2,
                "malformed": 0, "unanswered": 0,
                "failed_requests": 0}


def build(root: str, base: str | None = None) -> str:
    """`root`/BENCHMARK.json and `root`/benchmark, with the configuration
    tiny-r32 (or `base` cut to size, still under the name tiny-r32), a
    traffic mix, two cells and their limits added. Returns `root`."""
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    b = os.path.join(root, "benchmark")
    with open(os.path.join(b, "configs", "tiny-r32.json"), "w") as f:
        json.dump(tiny_config(base=base), f)
    with open(os.path.join(b, "traffic", "serve-tiny.json"), "w") as f:
        with open(os.path.join(b, "traffic", "serve-uniform.json")) as g:
            mix = json.load(g)
        mix.update(rate_qps=150.0, check_requests=48, connections=16)
        json.dump(mix, f)
    bench["configs"].append({"name": "tiny-r32", "source": "test",
                             "file": "benchmark/configs/tiny-r32.json",
                             "reduced": [], "why": "test"})
    for traffic, limits in (("train", TRAIN_LIMITS),
                            ("serve-tiny", SERVE_LIMITS)):
        name = "tiny-r32." + traffic
        bench["workloads"].append({"name": name, "config": "tiny-r32",
                                   "traffic": traffic, "chips": 1,
                                   "why": "test"})
        with open(os.path.join(b, "limits", name + ".json"), "w") as f:
            json.dump(limits, f)
        kind = "train" if traffic == "train" else "serve-uniform"
        for group in ("end_to_end", "per_layer"):
            for m in bench[group]:
                if any(w.endswith("." + kind) for w in m.get("workloads", [])):
                    m["workloads"].append(name)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
