"""CPU rehearsal of each driver at a tiny size (gpt3-tiny), calling the
drivers' own functions on cells that exist only as new files in a
temporary checkout. The command itself refuses a machine without a TPU
(test_benchmark_cells.py); here the drivers are handed jax's CPU device.
No device number is asserted: a CPU run says nothing of a chip."""
import json
import os
import shutil
import sys
import time

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(REPO, "benchmarks"), REPO]

from harness import (cells, common, serve_driver,  # noqa: E402
                     train_driver)

LENGTHS = {"prompt_tokens": {"dist": "uniform", "min": 4, "max": 100},
           "output_tokens": {"dist": "uniform", "min": 4, "max": 24},
           "sampling": {"temperature": 0.8, "top_p": 0.95}, "pool": 16}
TRAFFIC = {
    "tiny-train": {"kind": "train", "seq": 64, "batch": 4, "ring": 4},
    "tiny-closed": dict(LENGTHS, kind="serve", loop="closed", clients=6,
                        ramp_s=0.5, max_requests=2000),
    "tiny-open": dict(LENGTHS, kind="serve", loop="open", rate_per_s=25.0,
                      ramp_s=0.5),
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout with three more cells, added as files and entries."""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bdir = os.path.join(root, "benchmarks")
    with open(os.path.join(bdir, "configs", "gpt3-tiny.json"), "w") as fh:
        json.dump({"reduced": [], "train": {"preset": "gpt3-tiny",
                                            "mesh": None},
                   "serve": {"preset": "gpt3-tiny",
                             "engine": {"max_new_tokens_cap": 64}}}, fh)
    bench = cells.load_benchmark(root)
    bench["configs"].append({"name": "gpt3-tiny", "source": "x",
                             "file": "benchmarks/configs/gpt3-tiny.json",
                             "reduced": [], "why": "y"})
    for name, traffic in TRAFFIC.items():
        with open(os.path.join(bdir, "traffic", name + ".json"), "w") as fh:
            json.dump(traffic, fh)
        cell = "gpt3-tiny." + name
        bench["workloads"].append({"name": cell, "config": "gpt3-tiny",
                                   "traffic": name, "chips": 1, "why": "z"})
        like = {"tiny-train": "gpt3-medium.train",
                "tiny-closed": "gpt3-medium.serve-decode",
                "tiny-open": "gpt3-medium.serve-longprompt"}[name]
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return root


def _drive(driver, root, cell, trace, tmp_path, seconds=2.0):
    res = cells.resolve(cell, root)
    out = driver.run(res, 3_000_000_019, seconds, trace,
                     time.monotonic(), jax.devices()[:1],
                     str(tmp_path / "trace"))
    run = dict(out["run"], peaks={"bf16_flops": 197e12},
               window_s=out["window_s"])
    return res, out, cells.read_layer_metrics(res, run)


@pytest.mark.parametrize("trace", [False, True])
def test_train_driver_rehearsal(root, trace, tmp_path):
    res, out, layer = _drive(train_driver, root, "gpt3-tiny.tiny-train",
                             trace, tmp_path)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 3
    assert out["end_to_end"]["train_tokens_per_s_per_chip"] > 0
    assert out["setup_s"] > 0 and 0 < out["window_s"] < 4.0
    assert out["device"]["count"] == 1
    want = {"setup.build_s", "compile_cache.setup_misses", "train_step.mfu"}
    if trace:
        want.add("train_step.step_ms_p50")
        # the CPU's trace holds no TPU plane: nothing is reduced, and the
        # device-trace metrics are left out rather than written as zeros
        assert out["run"]["trace"] is None
    assert set(layer) == want


def test_serve_driver_closed_loop_rehearsal(root, tmp_path):
    res, out, layer = _drive(serve_driver, root, "gpt3-tiny.tiny-closed",
                             False, tmp_path)
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] >= 4 + 6
    assert set(out["end_to_end"]) == {"serve_tokens_per_s", "itl_ms_p95"}
    assert all(v > 0 for v in out["end_to_end"].values())
    serve = out["run"]["serve"]
    assert serve["w1"] - serve["w0"] == pytest.approx(2.0)
    # a closed loop: no client has two requests in flight
    by_client = {}
    for r in serve["samples"]:
        by_client.setdefault(r["i"] % 6, []).append(r)
    for reqs in by_client.values():
        reqs.sort(key=lambda r: r["i"])
        for a, b in zip(reqs, reqs[1:]):
            assert not a["tokens"] or b["sent"] >= a["tokens"][-1]
    assert 1.0 <= layer["engine.rows_per_step"]["value"] <= 8.0
    assert "engine.decode_step_ms_p50" not in layer      # spans are off


def test_serve_driver_open_loop_rehearsal(root, tmp_path, monkeypatch):
    from paddle_tpu.observability import trace as tracer

    monkeypatch.setattr(tracer, "_ENABLED", True)   # what FLAGS_trace_dir does
    try:
        res, out, layer = _drive(serve_driver, root, "gpt3-tiny.tiny-open",
                                 True, tmp_path)
    finally:
        tracer.reset()
    assert out["correct"] and out["failed"] == 0
    assert "itl_ms_p95" in out["end_to_end"] and \
        set(out["end_to_end"]) <= {"itl_ms_p95", "ttft_ms_p90"}
    serve = out["run"]["serve"]
    due = [r["due"] for r in serve["samples"]]
    # arrivals keep their schedule whatever the server does: about 25/s
    # over ramp + window, none sent before it was due, most just after
    assert 40 <= len(due) <= 85
    late = sorted(r["sent"] - r["due"] for r in serve["samples"])
    assert late[0] >= 0 and late[len(late) // 2] < 0.1
    assert out["run"]["trace"] is None
    assert {"engine.decode_step_ms_p50", "engine.prefill_ms_p50",
            "http_front.ttft_overhead_ms_p50"} <= set(layer)
    # the TTFT tail is somewhere in the cell's lines: bounded or recorded
    assert "ttft_ms_p90" in out["end_to_end"] or \
        layer["engine.ttft_ms_p90"]["value"] > 0
    assert layer["engine.prefill_ms_p50"]["value"] > 0
    assert abs(layer["http_front.ttft_overhead_ms_p50"]["value"]) < 200


def test_seeded_weights_come_from_the_seed_and_the_patch_is_undone():
    import bench
    import numpy as np

    import paddle_tpu as paddle

    real = paddle.seed

    def first_weight(seed):
        with common.seeded_weights(seed):
            step, *_ = bench.build_train_step("gpt3-tiny", 2, 16)
        return np.asarray(step.state()[0]["qkv_w"], np.float32)

    a, b, c = first_weight(5), first_weight(5), first_weight(3_000_000_019)
    assert paddle.seed is real
    assert (a == b).all() and (a != c).any()


def test_device_dict_takes_the_fuller_reading():
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

        def __init__(self, peak, now):
            self._st = {"peak_bytes_in_use": peak, "bytes_in_use": now}

        def memory_stats(self):
            return self._st

    d = common.device_dict([Dev(5, 4), Dev(6, 3)], program_bytes=10)
    assert d == {"platform": "tpu", "kind": "TPU v5 lite", "count": 2,
                 "memory_peak_bytes": 14}
    assert common.device_dict([Dev(50, 4)])["memory_peak_bytes"] == 50


def test_run_ahead_loop_ends_near_its_budget():
    """run_steps with a fake step: the window ends within a step of the
    budget and counts whole steps only."""
    class Loss:
        def __init__(self, ready_at):
            self.ready_at = ready_at

        def numpy(self):
            time.sleep(max(0.0, self.ready_at - time.monotonic()))
            return 1.0

    state = {"free_at": time.monotonic()}

    def step(ids, labels):           # a device that takes 50 ms a step
        state["free_at"] = max(state["free_at"], time.monotonic()) + 0.05
        return Loss(state["free_at"])

    losses, elapsed = train_driver.run_steps(step, [(0, 0)], 0, 1.0, 0.05)
    assert 0.9 <= elapsed <= 1.1
    assert len(losses) == pytest.approx(elapsed / 0.05, abs=1.5)
