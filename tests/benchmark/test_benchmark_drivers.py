"""CPU rehearsal of each driver at a tiny size (gpt3-tiny), calling the
drivers' own functions on cells that exist only as new files in a
temporary checkout. The command itself refuses a machine without a TPU
(test_benchmark_cells.py); here the drivers are handed jax's CPU device.
No device number is asserted: a CPU run says nothing of a chip.

The seam a new architecture comes in by: a configuration that names
ANOTHER reference file (written apart from gpt.py), holds a SLICE of the
preset's vocabulary and states its own tolerances runs through both
drivers with no existing file changed; the same reference made wrong on
purpose reads `correct: false`."""
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
from harness.traffic import Mix  # noqa: E402

LENGTHS = {"prompt_tokens": {"dist": "uniform", "min": 4, "max": 100},
           "output_tokens": {"dist": "uniform", "min": 4, "max": 24},
           "sampling": {"temperature": 0.8, "top_p": 0.95}, "pool": 16}
TINY = {"num_layers": 2, "hidden_size": 128, "num_heads": 8,
        "head_size": 16, "ffn_hidden": 512, "vocab_size": 1024,
        "max_seq_len": 256, "layer_norm_eps": 1e-5, "n_params": 560_000}
CHECKS = {"train": {"loss_rtol": 5e-5, "why": "as the GPT files'"},
          "serve": {"logit_tol_std": 2e-3, "why": "as the GPT files'"}}

# A second reference for the GPT block, written apart from gpt.py: one
# head at a time over slices of the fused projection, an additive mask
# from position indices, jax's own gelu, the whole batch at once. PEEK = 1
# lets every position see the one after it: wrong on purpose.
OTHER_REFERENCE = '''
import jax
import jax.numpy as jnp
import numpy as np

PEEK = 0
NAMES = {"wte.weight": "wte", "wpe.weight": "wpe", "ln_f.weight": "lnf_w",
         "ln_f.bias": "lnf_b"}
CALLS = []          # (entry, largest id it was handed)


def _ln(x, w, b, eps):
    x = x - x.mean(-1, keepdims=True)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w + b


def _forward(p, ids, config):
    arch = config["architecture"]
    heads, eps = arch["num_heads"], arch["layer_norm_eps"]
    p = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
    ids = jnp.asarray(ids, jnp.int32)
    n = ids.shape[1]
    pos = jnp.arange(n)
    bias = jnp.where(pos[None, :] <= pos[:, None] + PEEK, 0.0, -1e30)
    with jax.default_matmul_precision("highest"):
        h = p["wte"][ids] + p["wpe"][:n]
        d = h.shape[-1]
        hd = d // heads
        for l in range(p["qkv_w"].shape[0]):
            y = _ln(h, p["ln1_w"][l], p["ln1_b"][l], eps)
            qkv = y @ p["qkv_w"][l] + p["qkv_b"][l]
            out = []
            for i in range(heads):
                q, k, v = (qkv[..., j * d + i * hd:j * d + (i + 1) * hd]
                           for j in range(3))
                w = jax.nn.softmax(
                    q @ jnp.swapaxes(k, -1, -2) / hd ** 0.5 + bias, -1)
                out.append(w @ v)
            h = h + jnp.concatenate(out, -1) @ p["out_w"][l] + p["out_b"][l]
            y = _ln(h, p["ln2_w"][l], p["ln2_b"][l], eps)
            y = jax.nn.gelu(y @ p["fc1_w"][l] + p["fc1_b"][l],
                            approximate=True)
            h = h + y @ p["fc2_w"][l] + p["fc2_b"][l]
        return _ln(h, p["lnf_w"], p["lnf_b"], eps) @ p["wte"].T


def serve_logits(params, ids, config):
    CALLS.append(("serve_logits", int(np.max(ids))))
    return _forward(params, ids, config)


def train_loss(params, ids, labels, config, rows=4):
    CALLS.append(("train_loss", int(np.max(ids))))
    logits = _forward({NAMES.get(k, k): v for k, v in params.items()},
                      ids, config)
    picked = jnp.take_along_axis(
        logits, jnp.asarray(labels, jnp.int32)[..., None], -1)[..., 0]
    return float((jax.nn.logsumexp(logits, -1) - picked).mean())
'''

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
    BEFORE[root] = _files(bdir)
    for name, peek in (("other", 0), ("other_peek", 1)):
        with open(os.path.join(bdir, "references", name + ".py"), "w") as fh:
            fh.write(OTHER_REFERENCE.replace("PEEK = 0", f"PEEK = {peek}"))

    def config(reference, vocab, checks):
        return {"reference": f"benchmarks/references/{reference}.py",
                "architecture": dict(TINY, vocab_size=vocab),
                "reduced": ["vocab_size"] if vocab != 1024 else [],
                "train": {"preset": "gpt3-tiny", "mesh": None,
                          "check": checks["train"]},
                "serve": {"preset": "gpt3-tiny",
                          "engine": {"max_new_tokens_cap": 64},
                          "program_memory": "benchmarks/engine_probes/gpt.py",
                          "check": checks["serve"]}}

    own = {"train": {"loss_rtol": 1e-4, "why": "this file's own"},
           "serve": {"logit_tol_std": 3e-3, "why": "this file's own"}}
    configs = {"gpt3-tiny": config("gpt", 1024, CHECKS),
               # half of the preset's vocabulary is held here
               "gpt3-tiny-half": config("other", 512, own),
               "gpt3-tiny-wrong": config("other_peek", 512, own)}
    bench = cells.load_benchmark(root)
    for name, traffic in TRAFFIC.items():
        with open(os.path.join(bdir, "traffic", name + ".json"), "w") as fh:
            json.dump(traffic, fh)
    for cname, cfg in configs.items():
        with open(os.path.join(bdir, "configs", cname + ".json"), "w") as fh:
            json.dump(cfg, fh)
        bench["configs"].append({"name": cname, "source": "x",
                                 "file": f"benchmarks/configs/{cname}.json",
                                 "reduced": cfg["reduced"], "why": "y"})
        for name in TRAFFIC:
            cell = f"{cname}.{name}"
            bench["workloads"].append({"name": cell, "config": cname,
                                       "traffic": name, "chips": 1,
                                       "why": "z"})
            like = {"tiny-train": "gpt3-medium.train",
                    "tiny-closed": "gpt3-medium.serve-decode",
                    "tiny-open": "gpt3-medium.serve-longprompt"}[name]
            for m in bench["end_to_end"] + bench["per_layer"]:
                if like in m.get("workloads", []):
                    m["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return root


BEFORE: dict = {}       # checkout -> {path: bytes} of benchmarks/ as copied


def _files(directory):
    out = {}
    for d, _, files in os.walk(directory):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.join(d, f)] = fh.read()
    return out


def _no_existing_file_changed(root):
    now = _files(os.path.join(root, "benchmarks"))
    for path, data in BEFORE[root].items():
        assert now.get(path) == data, path


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


class Sent:
    """What the drivers handed on as token ids: the vocabulary they gave
    the load generator, every greedy-check prompt, every ring batch."""

    def __init__(self, monkeypatch):
        self.loadgen_vocab, self.greedy_ids, self.ring_ids = [], [], []
        real_gen, real_post, real_ring = (
            serve_driver.LoadGen, serve_driver.post_generate,
            train_driver.make_ring)

        def gen(url, res, seed, vocab, *args, **kw):
            self.loadgen_vocab.append(vocab)
            return real_gen(url, res, seed, vocab, *args, **kw)

        def post(url, payload):
            self.greedy_ids.append(max(payload["input_ids"]))
            return real_post(url, payload)

        def ring(seed, vocab, *args):
            made = real_ring(seed, vocab, *args)
            self.ring_ids.append(max(int(x.max()) for x, _ in made))
            return made

        monkeypatch.setattr(serve_driver, "LoadGen", gen)
        monkeypatch.setattr(serve_driver, "post_generate", post)
        monkeypatch.setattr(train_driver, "make_ring", ring)


def test_another_reference_and_a_sliced_vocabulary_through_the_train_driver(
        root, tmp_path, monkeypatch):
    sent = Sent(monkeypatch)
    res, out, _ = _drive(train_driver, root, "gpt3-tiny-half.tiny-train",
                         False, tmp_path, seconds=1.0)
    assert res["reference"].__file__.endswith("references/other.py")
    # the named file decided `correct`, at the tolerance of its own file
    assert res["reference"].CALLS[0][0] == "train_loss"
    value, limit = out["compared"]["first_loss_rel_diff"]
    assert out["correct"] and value <= limit == 1e-4
    # half of the ids exist here: none that was sent reaches the slice's end
    assert sent.ring_ids and max(sent.ring_ids) < 512
    assert res["reference"].CALLS[0][1] < 512
    _no_existing_file_changed(root)


def test_another_reference_and_a_sliced_vocabulary_through_the_serve_driver(
        root, tmp_path, monkeypatch):
    sent = Sent(monkeypatch)
    res, out, _ = _drive(serve_driver, root, "gpt3-tiny-half.tiny-closed",
                         False, tmp_path, seconds=1.0)
    assert res["reference"].__file__.endswith("references/other.py")
    assert [c[0] for c in res["reference"].CALLS] == ["serve_logits"]
    value, limit = out["compared"]["greedy_gap_over_std"]
    assert out["correct"] and value <= limit == 3e-3
    assert sent.loadgen_vocab == [512]
    assert len(sent.greedy_ids) == 4 and max(sent.greedy_ids) < 512
    # what the load generator builds from that vocabulary
    mix = Mix(res["traffic"], 3_000_000_019, sent.loadgen_vocab[0])
    assert max(max(mix.payload(i)["input_ids"]) for i in range(64)) < 512
    assert out["device"]["memory_peak_bytes"] > 0
    _no_existing_file_changed(root)


@pytest.mark.parametrize("driver, traffic, number", [
    (train_driver, "tiny-train", "first_loss_rel_diff"),
    (serve_driver, "tiny-closed", "greedy_gap_over_std")])
def test_a_reference_made_wrong_on_purpose_reads_not_correct(
        root, tmp_path, driver, traffic, number):
    """The same second reference with an off-by-one mask (every position
    sees the next): the system is right, so the verdict must be false,
    and by the number that compares the two."""
    _, out, _ = _drive(driver, root, "gpt3-tiny-wrong." + traffic, False,
                       tmp_path, seconds=1.0)
    value, limit = out["compared"][number]
    assert value > limit and not out["correct"]
    assert out["failed"] >= 1 or driver is train_driver
    others = {k: v for k, v in out["compared"].items() if k != number}
    assert all(v <= lim for v, lim in others.values())


def _main(root, cell, monkeypatch, capsys):
    """run.py's own main on a cell of the temporary checkout, its look
    for a chip skipped -> (exit code, the result line, standard error)."""
    import run as bench_run
    from harness import peaks

    monkeypatch.setattr(bench_run, "ROOT", root)
    monkeypatch.setattr(common, "require_chips",
                        lambda n: jax.devices()[:n])
    monkeypatch.setattr(peaks, "device_peaks",
                        lambda kind: peaks.DEVICE_PEAKS["TPU v5 lite"])
    capsys.readouterr()
    rc = bench_run.main(["--workload", cell, "--seed", "3000000019",
                         "--seconds", "1", "--trace", "0"])
    got = capsys.readouterr()
    return rc, json.loads(got.out.strip().splitlines()[-1]), got.err


def test_result_line_ends_with_each_number_compared_beside_its_limit(
        root, monkeypatch, capsys):
    rc, line, err = _main(root, "gpt3-tiny.tiny-closed", monkeypatch,
                          capsys)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert set(line["metrics"]) == {"serve_tokens_per_s", "itl_ms_p95",
                                    "setup_s"}
    gap = line["compared"]["greedy_gap_over_std"]
    assert gap["limit"] == 2e-3 and 0 <= gap["value"] <= gap["limit"]
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    # and as standard error's last lines
    last = err.strip().splitlines()[-len(line["compared"]):]
    assert [ln.split(":")[0] for ln in last] == [
        "compared " + k for k in line["compared"]]
    assert "(limit 0.002)" in last[0]


def test_a_token_altered_where_it_is_produced_reads_not_correct(
        root, monkeypatch, capsys):
    """The timed path broken underneath: every program of the engine
    emits the token after the one it chose."""
    from paddle_tpu.inference.serving import generate

    real = generate._sample_token

    def off_by_one(logits, *args):
        return (real(logits, *args) + 1) % logits.shape[-1]

    monkeypatch.setattr(generate, "_sample_token", off_by_one)
    rc, line, err = _main(root, "gpt3-tiny.tiny-closed", monkeypatch,
                          capsys)
    assert rc == 0 and line["correct"] is False and line["failed"] >= 4
    gap = line["compared"]["greedy_gap_over_std"]
    assert gap["value"] > 100 * gap["limit"]
    assert f"compared greedy_gap_over_std: {gap['value']!r}" in err


def test_program_memory_probe_reads_the_decode_programs_temporaries(root):
    """`serve.program_memory` names the file that lowers this engine's
    largest decode program: its reading is what the driver adds to the
    bytes in use, and equals a direct memory_analysis of that program."""
    from paddle_tpu.inference.serve import build_generator

    res = cells.resolve("gpt3-tiny.tiny-closed", root)
    engine = build_generator("gpt3-tiny", max_new_tokens_cap=64)
    try:
        got = serve_driver.program_temp_bytes(res, engine)
    finally:
        engine.shutdown(drain=False)
    assert isinstance(got, int) and got > 0
    bad = dict(res, config={"serve": {"program_memory":
                                      "benchmarks/engine_probes/none.py"}})
    with pytest.raises(SystemExit, match="engine_probes/none.py"):
        serve_driver.program_temp_bytes(bad, engine)


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
