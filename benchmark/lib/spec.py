"""Cells, configurations, traffic mixes, limits and per-layer readers, each
found by its name in BENCHMARK.json: a later PR adds files and entries and
edits none. A configuration's file names its plain reference (`reference`,
a path under benchmark/), so configurations of one model share one copy; a
metric `<quantity>.<suffix>` with no reader of its own is read by
`<quantity>`'s."""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Spec:
    def __init__(self, repo: str = REPO):
        self.repo = repo
        self.bench_dir = os.path.join(repo, "benchmark")
        self.benchmark = _read_json(os.path.join(repo, "BENCHMARK.json"))

    def cell(self, name: str) -> dict:
        """One workload entry with its configuration, traffic mix and
        limits loaded."""
        for w in self.benchmark["workloads"]:
            if w["name"] == name:
                break
        else:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        for c in self.benchmark["configs"]:
            if c["name"] == w["config"]:
                break
        else:
            raise KeyError(f"workload {name!r} names configuration "
                           f"{w['config']!r}, which BENCHMARK.json lacks")
        config = _read_json(os.path.join(self.repo, c["file"]))
        return {
            "name": name, "chips": int(w["chips"]), "config": config,
            "reference_path": os.path.join(self.bench_dir,
                                           config["reference"]),
            "traffic": _read_json(os.path.join(
                self.bench_dir, "traffic", w["traffic"] + ".json")),
            "limits": _read_json(os.path.join(
                self.bench_dir, "limits", name + ".json")),
        }

    def reference(self, cell: dict):
        return load_module(cell["reference_path"],
                           "reference_" + cell["config"]["name"])

    def job(self, cell: dict):
        kind = cell["traffic"]["job"]
        return load_module(os.path.join(self.bench_dir, "jobs",
                                        kind + ".py"), "job_" + kind)

    def metrics_of(self, cell_name: str, group: str) -> list[dict]:
        """The `end_to_end` or `per_layer` metrics this cell reports: those
        that list it, and those with no list whose `moves` (or, end to end,
        the metric itself) the cell reports."""
        end = self.benchmark["end_to_end"]
        mine = {m["name"] for m in end
                if cell_name in m.get("workloads", [cell_name])}
        if group == "end_to_end":
            return [m for m in end if m["name"] in mine]
        return [m for m in self.benchmark["per_layer"]
                if (cell_name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]

    def reader(self, metric_name: str):
        """The per-layer metric's reader, read(ctx) -> number or None:
        layer_metrics/<name>.py, or for `<quantity>.<suffix>` without one,
        layer_metrics/<quantity>.py (ctx["metric"] holds the whole name)."""
        for stem in (metric_name, metric_name.split(".", 1)[0]):
            path = os.path.join(self.bench_dir, "layer_metrics",
                                stem + ".py")
            if os.path.exists(path):
                return load_module(path, "layer_metric_" + stem).read
        raise FileNotFoundError(
            f"no reader for the per-layer metric {metric_name!r} under "
            f"{os.path.join(self.bench_dir, 'layer_metrics')}")
