"""The serving cells: `inference.serve.build_generator(preset)` behind
`ServingHTTPServer` in this process — what `python -m
paddle_tpu.inference.serve --generate PRESET --http PORT` serves — under
load from a child process (loadgen.py) that never imports jax.

What differs by architecture comes from the configuration's file: the
reference (`reference`, loaded by cells.resolve), the sizes the traffic and
the check draw from (`architecture.vocab_size`, the vocabulary HELD, and
`architecture.max_seq_len`), the check's tolerance with its reason
(`serve.check`), and the file that knows how to read the decode program's
temporaries off this engine (`serve.program_memory`).

Order: build, warm up (the engine's own inventory), the correctness check
on four greedy requests, then the child offers `ramp_s` seconds of the
cell's traffic before the window and `--seconds` of it inside. At the
window's end the engine is stopped without draining: a request cut there
is neither attempted nor failed.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np

from . import cells, common, end_to_end, stats, trace_reduce
from .traffic import Mix

HARNESS_DIR = os.path.dirname(os.path.abspath(__file__))
TRACE_S = 3.0          # profiled part of a traced window


def post_generate(url: str, payload: dict) -> dict:
    req = urllib.request.Request(
        url + "/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as r:
        return json.loads(r.read())


def greedy_check(url: str, params: dict, res: dict, mix: Mix,
                 seed: int) -> dict:
    """Four greedy requests of the cell's lengths, sent together; the
    configuration's reference runs teacher-forced over prompt + answer,
    and at every answered position the emitted token's reference logit
    must lie within `serve.check.logit_tol_std` standard deviations of
    the reference's largest. Tokens are not compared as such: with random
    weights the largest logit changes on rounding."""
    import jax.numpy as jnp

    config = res["config"]
    vocab = int(config["architecture"]["vocab_size"])
    max_seq_len = int(config["architecture"]["max_seq_len"])
    tol = float(config["serve"]["check"]["logit_tol_std"])
    picks = [0, mix.pool // 3, 2 * mix.pool // 3, mix.pool - 1]
    rng = np.random.default_rng(seed)
    reqs = []
    for a, b in zip(picks, reversed(picks)):
        n_prompt, n_out = mix.prompt_pool[a], mix.output_pool[b]
        reqs.append({"input_ids": [int(t) for t in rng.integers(
            0, vocab, n_prompt)], "max_new_tokens": n_out})
    answers: list = [None] * len(reqs)

    def ask(i):
        answers[i] = post_generate(url, reqs[i])["tokens"]

    threads = [threading.Thread(target=ask, args=(i,)) for i in
               range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    if any(a is None for a in answers):
        raise SystemExit("serve_driver: a greedy check request failed")

    longest = max(len(r["input_ids"]) + len(a)
                  for r, a in zip(reqs, answers))
    s_ref = min(-(-longest // 128) * 128, max_seq_len)
    ids = np.zeros((len(reqs), s_ref), np.int32)
    nxt = np.zeros((len(reqs), s_ref), np.int32)
    answered = np.zeros((len(reqs), s_ref), bool)
    for i, (r, a) in enumerate(zip(reqs, answers)):
        seq = r["input_ids"] + a
        p = len(r["input_ids"])
        ids[i, :len(seq)] = seq
        # position p-1+j holds the logits that chose answer token j
        nxt[i, p - 1:p - 1 + len(a)] = a
        answered[i, p - 1:p - 1 + len(a)] = True
    lg = res["reference"].serve_logits(params, ids, config)
    top = np.asarray(lg.max(-1))
    got = np.asarray(jnp.take_along_axis(
        lg, jnp.asarray(nxt)[..., None], -1)[..., 0])
    std = float(lg.std())
    gap = float(((top - got) * answered).max()) / std
    return {
        "requests": len(reqs),
        "returned": [len(a) for a in answers],
        "asked": [r["max_new_tokens"] for r in reqs],
        "prompt_lens": [len(r["input_ids"]) for r in reqs],
        "positions": int(answered.sum()),
        "argmax_agree": int(((top == got) & answered).sum()),
        "max_gap_over_std": gap, "logit_std": std, "tolerance": tol,
        "ok": gap <= tol
        and [len(a) for a in answers] == [r["max_new_tokens"] for r in reqs],
    }


class LoadGen:
    """The child process: started early (it builds its request bodies
    while the parent finishes set-up), released with the start time."""

    def __init__(self, url: str, res: dict, seed: int, vocab: int,
                 ramp: float, seconds: float, out_path: str,
                 rate: float | None = None):
        self.out_path = out_path
        cmd = [sys.executable, "-S", os.path.join(HARNESS_DIR, "loadgen.py"),
               "--url", url, "--traffic", res["traffic_path"],
               "--seed", str(seed), "--vocab", str(vocab),
               "--ramp", str(ramp), "--seconds", str(seconds),
               "--out", out_path]
        if rate is not None:
            cmd += ["--rate", str(rate)]
        self.proc = subprocess.Popen(cmd, stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, text=True)
        if self.proc.stdout.readline().strip() != "ready":
            self.stop()
            raise SystemExit("serve_driver: the load generator did not "
                             "come up")

    def release(self, t0: float) -> None:
        self.proc.stdin.write(f"{t0!r}\n")
        self.proc.stdin.flush()

    def finish(self, timeout: float) -> tuple:
        """Wait for the child -> (samples, its summary line)."""
        try:
            out, _ = self.proc.communicate(timeout=timeout)
        finally:
            self.stop()
        with open(self.out_path) as fh:
            samples = [json.loads(line) for line in fh]
        return samples, json.loads(out.strip().splitlines()[-1])

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()


def sleep_until(t: float) -> None:
    d = t - time.monotonic()
    if d > 0:
        time.sleep(d)


def build(res: dict, seed: int, t_proc0: float):
    """-> (engine, server, url, build_s): the engine as --generate builds
    it, warmed and started, behind the HTTP front."""
    from paddle_tpu.inference.serve import build_generator
    from paddle_tpu.inference.serving import ServingHTTPServer

    cfg = res["config"]["serve"]
    with common.seeded_weights(seed):
        engine = build_generator(cfg["preset"], **cfg.get("engine", {}))
    # the constructor warms the inventory up before it returns; what came
    # before that is the build
    build_s = time.monotonic() - t_proc0 \
        - engine.warmup_report["time_s"]
    srv = ServingHTTPServer(None, generator=engine).start()
    return engine, srv, f"http://127.0.0.1:{srv.port}", build_s


def program_temp_bytes(res: dict, engine) -> int:
    """Temporaries of the engine's largest decode program, which the
    allocator's peak leaves out: read by the file the configuration names
    under `serve.program_memory`, since the program's signature and pools
    are the architecture's."""
    probe = cells.load_module(
        res["root"], res["config"]["serve"]["program_memory"],
        ("program_temp_bytes",))
    return int(probe.program_temp_bytes(engine))


def run(res: dict, seed: int, seconds: float, trace: bool,
        t_proc0: float, devices, trace_dir: str) -> dict:
    """t_proc0: time.monotonic() when the process started; set-up is
    counted from it."""
    from paddle_tpu.core import compile_cache as cc
    from paddle_tpu.observability import trace as tracer

    traffic = res["traffic"]
    vocab = int(res["config"]["architecture"]["vocab_size"])
    ramp = float(traffic.get("ramp_s", 0.0))
    engine, srv, url, build_s = build(res, seed, t_proc0)
    gen = None
    try:
        out_dir = os.path.join(res["root"], ".bench_tmp")
        os.makedirs(out_dir, exist_ok=True)
        gen = LoadGen(url, res, seed, vocab, ramp, seconds,
                      os.path.join(out_dir, f"samples-{res['name']}.jsonl"))
        check = greedy_check(url, engine._params, res,
                             Mix(traffic, seed, vocab), seed)
        temp_bytes = program_temp_bytes(res, engine)
        common.log(phase="setup", build_s=build_s,
                   warmup=engine.warmup_report, greedy_check=check,
                   program_temp_bytes=temp_bytes,
                   cache=cc.stats())
        setup_misses = int(cc.stats()["misses"])

        t0 = time.monotonic() + 0.2
        gen.release(t0)
        w0, w1 = t0 + ramp, t0 + ramp + seconds
        sleep_until(w0)
        setup_s = time.monotonic() - t_proc0
        snap0, lookups0 = engine.metrics.snapshot(), common.lookups(cc)
        reduced = None
        if trace:
            import jax

            sleep_until(w0 + (seconds - TRACE_S) / 2)
            common.start_profile(trace_dir)
            try:
                time.sleep(min(TRACE_S, seconds / 2))
            finally:
                jax.profiler.stop_trace()
        sleep_until(w1)
        snap1 = engine.metrics.snapshot()
        in_window_lookups = common.lookups(cc) - lookups0
        spans = tracer.spans() if tracer.enabled() else []
        # the window is over: stop without draining, which ends every
        # stream the child still reads
        time.sleep(0.2)
        srv.stop(drain=False)
        samples, child = gen.finish(timeout=60)
        # while the weights and the pool are still on the device
        device = common.device_dict(devices, temp_bytes)
    finally:
        if gen is not None:
            gen.stop()
        srv.stop(drain=False)
    if trace:
        reduced = trace_reduce.reduce_file(
            trace_reduce.find_xplane(trace_dir))

    ended = [r for r in samples if not r["cut"]]
    bad = [r for r in ended if r["error"] or r["status"] != 200
           or not r["done"] or len(r["tokens"]) != r["asked"]]
    bad += [r for r in samples if r["cut"] and r["error"]]
    late = [(r["sent"] - r["due"]) * 1e3 for r in samples
            if r["sent"] is not None]
    e2e = {name: end_to_end.METRICS[name](samples, w0, w1)
           for name in (m["name"] for m in res["end_to_end"])
           if name in end_to_end.METRICS}
    # each number the verdict rests on, beside its limit
    compared = {
        "greedy_gap_over_std": (check["max_gap_over_std"],
                                check["tolerance"]),
        "greedy_answers_short": (sum(a != b for a, b in zip(
            check["returned"], check["asked"])), 0),
        "requests_not_whole": (len(bad), 0),
        "compiles_in_window": (in_window_lookups, 0),
        "shed_or_failed": (snap1["shed_total"] - snap0["shed_total"]
                           + snap1["failed_total"] - snap0["failed_total"],
                           0)}
    common.log(phase="window", child=child, requests=len(samples),
               ended=len(ended), cut=len(samples) - len(ended),
               bad=[{k: r[k] for k in ("i", "status", "error", "asked")}
                    for r in bad[:5]],
               generator_lateness_ms={"p50": stats.percentile(late, 50),
                                      "max": max(late, default=None)},
               in_window_lookups=in_window_lookups, compared=compared,
               engine_window={k: snap1[k] - snap0[k] for k in (
                   "steps_total", "step_rows_total",
                   "step_padded_rows_total", "prefills_total",
                   "tokens_out_total", "completed_total")})
    return {
        "correct": common.verdict(compared), "compared": compared,
        "attempted": len(ended) + check["requests"],
        "failed": len(bad) + (0 if check["ok"] else check["requests"]),
        "setup_s": setup_s, "window_s": seconds,
        "device": device,
        "end_to_end": e2e,
        "run": {
            "setup": {"build_s": build_s, "cache_misses": setup_misses},
            "serve": {"samples": samples, "w0": w0, "w1": w1,
                      "snap0": snap0, "snap1": snap1},
            "trace": reduced,
            "spans": [s for s in spans
                      if w0 * 1e6 <= s["ts"] <= w1 * 1e6],
        },
    }
