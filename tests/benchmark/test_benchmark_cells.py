"""BENCHMARK.json against its contract, and the harness being driven by
data: a configuration, its reference, a traffic mix, a cell and a
per-layer metric added as NEW files (and new entries) are found without
editing any file that is there; a configuration that names no reference,
or one without the entry its sections need, is refused by the key's name.
And the command's refusal to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(REPO, "benchmarks"), REPO]

from harness import cells  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = cells.load_benchmark(REPO)
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def applies(metric, cell):
    return cell in metric.get("workloads", [cell])


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks", "tests/benchmark"]
    assert BENCH["command"][1].startswith("benchmarks/")
    assert isinstance(BENCH["run_seconds"], int) and \
        1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10
    # 2 + 14 runs a cell at the full 24 cells must fit the check's time
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_whys():
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    assert len(CELLS) == len(set(CELLS))
    for m in METRICS:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for c in BENCH["configs"]:
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/")
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= \
        max(1, len(CELLS) // 4)


def test_end_to_end_metrics():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_one_more_and_a_layer_metric(cell):
    e2e = [m["name"] for m in BENCH["end_to_end"] if applies(m, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(applies(m, cell) for m in BENCH["per_layer"])


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_and_moves_a_reported_metric(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert 1 <= len(metric["layer"]) <= 200
    reader = cells.load_reader(os.path.join(REPO, "benchmarks"),
                               metric["name"])
    assert callable(reader)
    moved = next(m for m in BENCH["end_to_end"]
                 if m["name"] == metric["moves"])
    for cell in CELLS:
        if applies(metric, cell):
            assert applies(moved, cell), (metric["name"], cell)
    # a reader that finds nothing to read returns nothing
    empty = {"setup": {"build_s": 1.0, "cache_misses": 0}, "spans": [],
             "trace": None}
    if not metric["name"].startswith(("setup.", "compile_cache.")):
        assert reader(empty) is None


def test_every_entry_has_its_reader_file():
    have = {f[:-3] for f in os.listdir(
        os.path.join(REPO, "benchmarks", "layer_metrics"))
        if f.endswith(".py")}
    assert {m["name"] for m in BENCH["per_layer"]} <= have


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    res = cells.resolve(cell, REPO)
    assert res["traffic"]["kind"] in ("train", "serve")
    assert res["config"][res["traffic"]["kind"]]["preset"] == \
        res["cell"]["config"]
    assert res["config"]["reduced"] == res["config_entry"]["reduced"]
    # the architecture is the configuration file's: its reference, the
    # sizes the drivers draw from, each section's tolerance and its reason
    assert res["reference"].__file__ == os.path.join(
        REPO, "benchmarks", "references", "gpt.py")
    arch = res["config"]["architecture"]
    assert arch["vocab_size"] > 0 and arch["max_seq_len"] > 0
    for section, limit in (("train", "loss_rtol"),
                           ("serve", "logit_tol_std")):
        if section in res["config"]:
            check = res["config"][section]["check"]
            assert 0 < check[limit] < 1 and len(check["why"]) > 40
            assert callable(getattr(
                res["reference"], cells.REFERENCE_ENTRY[section]))
    assert [m["name"] for m in res["end_to_end"]].count("setup_s") == 1
    with pytest.raises(SystemExit):
        cells.resolve("no-such.cell", REPO)


def test_preset_sizes_are_the_config_files():
    from paddle_tpu.models import PRESETS

    for c in BENCH["configs"]:
        arch = cells.load_json(os.path.join(REPO, c["file"]))["architecture"]
        p = PRESETS[c["name"]]
        assert (arch["num_layers"], arch["hidden_size"], arch["num_heads"],
                arch["ffn_hidden"], arch["vocab_size"],
                arch["max_seq_len"]) == (
            p.num_layers, p.hidden_size, p.num_heads, p.ffn_hidden,
            p.vocab_size, p.max_seq_len)
        assert arch["head_size"] * arch["num_heads"] == arch["hidden_size"]


def test_new_files_are_found_without_editing_any(tmp_path):
    """A later PR's additions: files of its own, entries of its own."""
    root = str(tmp_path)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {}
    for d, _, files in os.walk(os.path.join(root, "benchmarks")):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()

    bdir = os.path.join(root, "benchmarks")
    with open(os.path.join(bdir, "references", "gpt_again.py"), "w") as fh:
        fh.write("def serve_logits(params, ids, config):\n    return 'mine'\n")
    with open(os.path.join(bdir, "configs", "gpt3-small.json"), "w") as fh:
        json.dump({"reference": "benchmarks/references/gpt_again.py",
                   "architecture": {"vocab_size": 6288, "max_seq_len": 1024},
                   "reduced": ["vocab_size"],
                   "serve": {"preset": "gpt3-small",
                             "check": {"logit_tol_std": 1e-2, "why": "w"}}},
                  fh)
    with open(os.path.join(bdir, "traffic", "serve-chat.json"), "w") as fh:
        json.dump({"kind": "serve", "loop": "open", "rate_per_s": 2.0,
                   "prompt_tokens": {"dist": "loguniform", "min": 32,
                                     "max": 512},
                   "output_tokens": {"dist": "loguniform", "min": 32,
                                     "max": 256}}, fh)
    with open(os.path.join(bdir, "layer_metrics",
                           "engine.queue_wait_ms_p50.py"), "w") as fh:
        fh.write("def read(run):\n    return run.get('queue_wait')\n")
    bench = cells.load_benchmark(root)
    bench["configs"].append({"name": "gpt3-small", "source": "x",
                             "file": "benchmarks/configs/gpt3-small.json",
                             "reduced": [], "why": "y"})
    bench["workloads"].append({"name": "gpt3-small.serve-chat",
                               "config": "gpt3-small",
                               "traffic": "serve-chat", "chips": 1,
                               "why": "z"})
    for m in bench["end_to_end"]:
        if m["name"] == "itl_ms_p95":
            m["workloads"].append("gpt3-small.serve-chat")
    bench["per_layer"].append({
        "name": "engine.queue_wait_ms_p50", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "serving engine",
        "moves": "itl_ms_p95", "workloads": ["gpt3-small.serve-chat"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)

    res = cells.resolve("gpt3-small.serve-chat", root)
    assert res["traffic"]["rate_per_s"] == 2.0
    assert res["config"]["serve"]["preset"] == "gpt3-small"
    # its own reference, and only the entry a served configuration needs
    assert res["reference"].serve_logits(None, None, None) == "mine"
    assert not hasattr(res["reference"], "train_loss")
    assert {m["name"] for m in res["end_to_end"]} == {
        "itl_ms_p95", "setup_s"}
    run = {"setup": {"build_s": 2.5, "cache_misses": 0}, "spans": [],
           "trace": None, "queue_wait": 12.0}
    got = cells.read_layer_metrics(res, run)
    assert got == {
        "setup.build_s": {"value": 2.5, "unit": "s"},
        "compile_cache.setup_misses": {"value": 0.0, "unit": "count"},
        "engine.queue_wait_ms_p50": {"value": 12.0, "unit": "ms"}}
    # the generator reads the new mix as it is
    from harness.traffic import Mix

    mix = Mix(res["traffic"], 1, res["config"]["architecture"]["vocab_size"])
    assert 32 <= mix.lengths(0)[0] <= 512
    assert max(mix.payload(0)["input_ids"]) < 6288
    for path, data in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == data, path


@pytest.mark.parametrize("config, reference_source, named", [
    ({"serve": {}}, None, '"reference"'),
    ({"reference": "", "serve": {}}, None, '"reference"'),
    ({"reference": "benchmarks/references/gone.py", "serve": {}}, None,
     "benchmarks/references/gone.py"),
    ({"reference": "benchmarks/references/half.py", "serve": {}},
     "def train_loss(*a):\n    return 0.0\n", "serve_logits"),
    ({"reference": "benchmarks/references/half.py", "train": {}},
     "def serve_logits(*a):\n    return 0.0\n", "train_loss"),
    ({"reference": "benchmarks/references/half.py", "train": {},
      "serve": {}}, "serve_logits = 3\n", "serve_logits"),
], ids=["no-key", "empty-key", "no-file", "no-serve_logits",
        "no-train_loss", "not-a-function"])
def test_a_configuration_without_its_reference_is_refused_by_name(
        tmp_path, config, reference_source, named):
    """No reference is an error, never a default: the message names the
    key, the file or the entry that is missing."""
    root = str(tmp_path)
    if reference_source is not None:
        os.makedirs(os.path.join(root, "benchmarks", "references"))
        with open(os.path.join(root, config["reference"]), "w") as fh:
            fh.write(reference_source)
    with pytest.raises(SystemExit) as refused:
        cells.load_reference(root, config, "benchmarks/configs/new.json")
    assert named in str(refused.value)


def test_a_cell_whose_configuration_names_no_reference_does_not_resolve(
        tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = os.path.join(root, "benchmarks", "configs", "gpt3-medium.json")
    config = cells.load_json(path)
    del config["reference"]
    with open(path, "w") as fh:
        json.dump(config, fh)
    with pytest.raises(SystemExit, match='gpt3-medium.json: no "reference"'):
        cells.resolve("gpt3-medium.train", root)


def _run_cell(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "run.py"),
         "--workload", "gpt3-medium.train", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_command_refuses_a_machine_without_a_tpu():
    r = _run_cell(REPO)
    assert r.returncode != 0 and r.stdout == ""
    assert "needs a TPU" in r.stderr


def test_command_refuses_a_directory_with_the_benchmark_alone(tmp_path):
    """Only BENCHMARK.json and the files under `paths`: no program."""
    root = str(tmp_path)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(REPO, p), os.path.join(root, p),
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_cell(root)
    assert r.returncode != 0 and r.stdout == ""
