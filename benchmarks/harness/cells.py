"""Finding a cell's files by the names BENCHMARK.json gives them.

A later PR adds a configuration, a traffic mix, a cell or a per-layer
metric by adding files and entries; nothing in this module (or anywhere in
the harness) lists them. `root` is the checkout: the directory that holds
BENCHMARK.json.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Optional

HARNESS_DIR = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HARNESS_DIR)
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def bench_dir(root: str, bench: dict) -> str:
    """The first of `paths`: the directory with traffic/ and
    layer_metrics/ in it."""
    return os.path.join(root, bench["paths"][0])


def resolve(name: str, root: str = ROOT) -> dict:
    """Everything one cell is made of: its `workloads` entry, its
    configuration (entry and file), its traffic file, and the metrics of
    BENCHMARK.json that apply to it."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    traffic_path = os.path.join(bench_dir(root, bench), "traffic",
                                cell["traffic"] + ".json")

    def applies(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return {
        "name": name, "root": root, "bench_dir": bench_dir(root, bench),
        "cell": cell, "config_entry": entry,
        "config": load_json(os.path.join(root, entry["file"])),
        "traffic": load_json(traffic_path),
        "traffic_path": traffic_path,
        "run_seconds": bench["run_seconds"],
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def load_reader(bench_directory: str, metric: str) -> Callable:
    """`read(run)` of layer_metrics/<metric>.py, found by the metric's
    name. The file is loaded by path: metric names hold dots."""
    path = os.path.join(bench_directory, "layer_metrics", metric + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"per-layer metric {metric!r} has no "
                                f"reader at {path}")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_layer_metrics(resolved: dict, run: dict) -> dict:
    """{name: {"value", "unit"}} for every per-layer metric of the cell
    whose reader found something to read; a reader that returns None is
    left out of the line."""
    out = {}
    for m in resolved["per_layer"]:
        value: Optional[float] = load_reader(
            resolved["bench_dir"], m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
