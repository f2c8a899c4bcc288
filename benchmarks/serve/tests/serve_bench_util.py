"""Helpers of the benchmark's tests: paths, and a checkout built in a
temporary directory from BENCHMARK.json and the benchmark's files, to which
a test adds a configuration, a traffic mix or a metric as new files and
entries only."""

from __future__ import annotations

import json
import os
import shutil
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(os.path.dirname(BENCH))
DATA = os.path.join(TESTS, "data")

for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


def checkout(tmp: str) -> str:
    """A copy of BENCHMARK.json and the benchmark's directory under `tmp`."""
    root = os.path.join(tmp, "checkout")
    shutil.copytree(BENCH, os.path.join(root, "benchmarks", "serve"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests", ".jax_cache"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


def add_cell(root: str, name: str, *, config: str, traffic: str, limit: float,
             metrics=(), config_file: str | None = None, traffic_file: str | None = None,
             loop_metrics: bool = True) -> None:
    """Add a configuration, a traffic mix and a cell as new files and new
    entries of BENCHMARK.json; existing files are left as they are."""
    bench = os.path.join(root, "benchmarks", "serve")
    if config_file:
        shutil.copy(config_file, os.path.join(bench, "configs", config + ".json"))
    if traffic_file:
        shutil.copy(traffic_file, os.path.join(bench, "traffic", traffic + ".json"))
    with open(os.path.join(bench, "limits", name + ".json"), "w") as f:
        json.dump({"mean_logit_gap": limit}, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        b = json.load(f)
    if config not in {c["name"] for c in b["configs"]}:
        b["configs"].append({"name": config, "source": "test",
                             "file": f"benchmarks/serve/configs/{config}.json",
                             "reduced": [], "why": "test"})
    b["workloads"].append({"name": name, "config": config, "traffic": traffic,
                           "chips": 1, "why": "test"})
    if loop_metrics:
        for m in b["end_to_end"] + b["per_layer"]:
            if "workloads" in m:
                m["workloads"].append(name)
    for m in metrics:
        group = "per_layer" if "layer" in m else "end_to_end"
        b[group].append({**m, "workloads": [name]})
    with open(path, "w") as f:
        json.dump(b, f, indent=1)


def tiny_checkout(tmp: str, name: str = "tiny.closed", traffic_file: str | None = None,
                  limit: float = 0.5, metrics=()) -> str:
    root = checkout(tmp)
    add_cell(root, name, config="tiny", traffic=name.split(".", 1)[1], limit=limit,
             config_file=os.path.join(DATA, "tiny.json"),
             traffic_file=traffic_file or os.path.join(DATA, "tiny_traffic.json"),
             metrics=metrics)
    return root
