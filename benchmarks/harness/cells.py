"""Finding a cell's files by the names BENCHMARK.json gives them.

A later PR adds a configuration, its reference, a traffic mix, a cell or
a per-layer metric by adding files and entries; nothing in this module (or
anywhere in the harness) lists them. `root` is the checkout: the directory
that holds BENCHMARK.json.
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


def _exec_file(path: str):
    """A Python file as a module, loaded by its path: metric names hold
    dots, and a reference is wherever its configuration says."""
    spec = importlib.util.spec_from_file_location(
        "bench_file_" + "".join(c if c.isalnum() else "_"
                                for c in os.path.basename(path)), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_module(root: str, rel_path: str, needs: tuple):
    """The Python file at `rel_path` of the checkout, loaded by path, with
    every function of `needs` in it; SystemExit names what is missing."""
    path = os.path.join(root, rel_path)
    if not os.path.isfile(path):
        raise SystemExit(f"{rel_path}: no such file in the checkout")
    mod = _exec_file(path)
    missing = [f for f in needs if not callable(getattr(mod, f, None))]
    if missing:
        raise SystemExit(f"{rel_path}: has no function {missing}")
    return mod


# what a configuration's `reference` has to hold for each section
REFERENCE_ENTRY = {"serve": "serve_logits", "train": "train_loss"}


def load_reference(root: str, config: dict, config_file: str):
    """The plain reference a configuration's file names under `reference`
    (a path inside the checkout): `serve_logits(params, ids, config)` for
    a configuration that is served, `train_loss(params, ids, labels,
    config, rows)` for one that is trained. No key, no default."""
    if not config.get("reference"):
        raise SystemExit(
            f"{config_file}: no \"reference\" key — a configuration names "
            f"the file of its plain reference (benchmarks/references/…)")
    return load_module(root, config["reference"], tuple(
        entry for section, entry in REFERENCE_ENTRY.items()
        if section in config))


def resolve(name: str, root: str = ROOT) -> dict:
    """Everything one cell is made of: its `workloads` entry, its
    configuration (entry and file), the reference that file names, its
    traffic file, and the metrics of BENCHMARK.json that apply to it."""
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

    config = load_json(os.path.join(root, entry["file"]))
    return {
        "name": name, "root": root, "bench_dir": bench_dir(root, bench),
        "cell": cell, "config_entry": entry, "config": config,
        "reference": load_reference(root, config, entry["file"]),
        "traffic": load_json(traffic_path),
        "traffic_path": traffic_path,
        "run_seconds": bench["run_seconds"],
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def load_reader(bench_directory: str, metric: str) -> Callable:
    """`read(run)` of layer_metrics/<metric>.py, found by the metric's
    name."""
    path = os.path.join(bench_directory, "layer_metrics", metric + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"per-layer metric {metric!r} has no "
                                f"reader at {path}")
    return _exec_file(path).read


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
