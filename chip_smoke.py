"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --multichip  # one host with four

One process drives the repo's two tiers through the entry points users
call, at the full published width of "gpt3-medium" (24 layers, d 1024,
16 heads, seq 1024, vocab 50304; seeded random weights):

  train  bench.build_train_step (scan model, bf16 O2, AdamW with f32
         master weights, fused LM-head CE) — a compile step and six more
         through the compiled TrainStep, each ending in a host read of
         the loss;
  serve  inference.serve.build_generator (what `--generate PRESET`
         serves) behind ServingHTTPServer, over HTTP: greedy, seeded-
         sampled and streamed /generate requests, prompts across four
         prefill buckets, five in flight together.

--multichip runs only what exists across chips, and what it is compared
with: the dp2 x tp2 ZeRO-1 step against the one-device step on the same
batch, and four one-device engine replicas answering a request each.

Every line of stdout is one JSON object: a line per phase with what it
observed (times and rates are smoke observations, not a benchmark), and
LAST, only when every phase passed,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the device as jax reports it. Any failed check or exception ends the
run with a non-zero exit code and without that line — as does a machine
where jax finds no TPU: there is no fallback to another platform. The
phases take their size as arguments so tests/test_chip_smoke.py can
rehearse the same code on the CPU at a tiny size.
"""
from __future__ import annotations

import argparse
import gc
import json
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor


def emit(phase: str, **observed) -> None:
    print(json.dumps({"phase": phase, **observed}), flush=True)


def require(cond, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def run_steps(step, ids, labels, n: int):
    """n calls of the train step, each drained by a host read of the
    loss -> (losses, seconds per call)."""
    losses, secs = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        losses.append(float(step(ids, labels).numpy()))
        secs.append(time.perf_counter() - t0)
    return losses, secs


# ------------------------------------------------------------------ train --
def train_phase(preset: str, batch: int, seq: int, steps: int = 6) -> dict:
    import jax
    import numpy as np

    import bench
    from paddle_tpu.core import compile_cache as cc

    t0 = time.perf_counter()
    step, ids, labels, n_params = bench.build_train_step(preset, batch, seq)
    build_s = time.perf_counter() - t0
    losses, secs = run_steps(step, ids, labels, 1 + steps)
    # the program that just ran, as the compiler emitted it (on the CPU
    # a donated program stays off the persistent cache)
    with cc.donated_cpu_guard():
        compiled = step.lowered(ids, labels).compile()
    mem = compiled.memory_analysis()
    steady = sorted(secs[1:])
    out = {
        "preset": preset, "batch": batch, "seq": seq, "n_params": n_params,
        "build_s": round(build_s, 2), "compile_step_s": round(secs[0], 2),
        "step_ms": [round(s * 1e3, 1) for s in secs[1:]],
        "smoke_tokens_per_s": round(
            batch * seq / steady[len(steady) // 2], 1),
        "losses": [round(x, 4) for x in losses],
        "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
        "program_argument_bytes": int(mem.argument_size_in_bytes),
        "program_temp_bytes": int(mem.temp_size_in_bytes),
        "peak_bytes_in_use": (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use"),
        "persistent_cache": step.compile_report,
    }
    emit("train", **out)
    require(np.isfinite(losses).all(), f"non-finite loss in {losses}")
    require(all(b < a for a, b in zip(losses, losses[1:])),
            f"loss not decreasing on the fixed batch: {losses}")
    return out


# ------------------------------------------------------------------ serve --
def generate(url: str, stream: bool = False, **payload) -> list:
    """POST /generate -> the generated token ids."""
    req = urllib.request.Request(
        url + "/generate",
        data=json.dumps(dict(payload, stream=stream)).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as r:
        if not stream:
            return json.loads(r.read())["tokens"]
        tokens, done = [], False
        for line in r:
            obj = json.loads(line)
            if "error" in obj:
                raise RuntimeError(f"stream failed: {obj}")
            if "token" in obj:
                tokens.append(obj["token"])
            done = done or bool(obj.get("done"))
        require(done, "stream ended without its done line")
        return tokens


def generate_together(url: str, requests: list) -> list:
    """Send `requests` (kwargs of generate) at once, one thread each;
    -> their token lists in order. A request that failed raises here."""
    with ThreadPoolExecutor(len(requests), "smoke-req") as pool:
        return list(pool.map(lambda kw: generate(url, **kw), requests))


def random_prompt(rng, preset: str, n: int) -> list:
    from paddle_tpu.models import PRESETS

    return [int(t) for t in rng.randint(0, PRESETS[preset].vocab_size, n)]


def serve_phase(preset: str, prompt_lens, new_tokens: int) -> dict:
    """prompt_lens: four prompt lengths, each in its own prefill bucket."""
    import numpy as np

    from paddle_tpu.core import compile_cache as cc
    from paddle_tpu.inference.serve import build_generator
    from paddle_tpu.inference.serving import ServingHTTPServer

    t0 = time.perf_counter()
    engine = build_generator(preset)      # default flags; warms inventory
    warm_s = time.perf_counter() - t0
    srv = ServingHTTPServer(None, generator=engine).start()
    url = f"http://127.0.0.1:{srv.port}"
    rng = np.random.RandomState(0)
    a, b, c, d = (random_prompt(rng, preset, n) for n in prompt_lens)
    sampled = dict(input_ids=c, max_new_tokens=new_tokens, seed=7,
                   temperature=0.8, top_k=40)
    try:
        with cc.measure() as compiles:
            first = generate(url, input_ids=a, max_new_tokens=new_tokens)
            t0 = time.perf_counter()
            wave = generate_together(url, [
                dict(input_ids=b, max_new_tokens=new_tokens),
                dict(input_ids=d, max_new_tokens=new_tokens),
                dict(sampled),
                dict(sampled, stream=True),
                dict(input_ids=a, max_new_tokens=new_tokens, stream=True),
            ])
            wave_s = time.perf_counter() - t0
            # same request, same conditions (alone) as `first`, after the
            # wave rewrote the donated pool many times over
            again = generate(url, input_ids=a, max_new_tokens=new_tokens)
        snap = engine.metrics.snapshot()
    finally:
        srv.stop(drain=True)
    warm = engine.warmup_report
    out = {
        "preset": preset, "donate": engine._donate,
        "warmup_s": round(warm_s, 2), "executables": warm["executables"],
        "prefill_buckets": warm["prefill_buckets"],
        "decode_batch_buckets": warm["decode_batch_buckets"],
        "kv_pool_bytes": warm["kv_pool_bytes"],
        "warmup_persistent_hits": warm["persistent_hits"],
        "warmup_persistent_misses": warm["persistent_misses"],
        "prompt_lens": list(prompt_lens), "new_tokens": new_tokens,
        "tokens_returned": [len(t) for t in [first, again, *wave]],
        "workload_compile_misses": compiles["misses"],
        "max_slot_occupancy": snap["max_slot_occupancy"],
        "failed_total": snap["failed_total"],
        "stream_equals_json_for_one_seed": wave[2] == wave[3],
        "repeat_equals_first": again == first,
        "wave_s": round(wave_s, 2),
        "smoke_wave_tokens_per_s": round(
            len(wave) * new_tokens / wave_s, 1),
        "ttft_ms": snap["ttft_ms"],
    }
    emit("serve", **out)
    require(set(out["tokens_returned"]) == {new_tokens},
            f"asked {new_tokens} tokens of every request")
    require(out["stream_equals_json_for_one_seed"],
            "streamed and non-streamed outputs differ for one seed")
    require(out["repeat_equals_first"],
            "an identical greedy request returned other tokens the second "
            "time (donated-pool aliasing?)")
    require(compiles["enabled"] and compiles["misses"] == 0,
            f"compiles after warm-up: {compiles}")
    require(snap["max_slot_occupancy"] >= 4 and snap["failed_total"] == 0,
            f"wanted >=4 rows decoding together and no failure: "
            f"{snap['occupancy_hist']}")
    return out


# -------------------------------------------------------------- multichip --
def multichip_train_phase(preset: str, batch: int, seq: int, devices,
                          steps: int = 3) -> dict:
    """The dp x tp2 ZeRO-1 step over `devices` against the one-device
    step: same seed, same batch, losses compared at bf16 tolerance."""
    import numpy as np

    import bench

    step, ids, labels, _ = bench.build_train_step(preset, batch, seq)
    single, _ = run_steps(step, ids, labels, 1 + steps)
    del step
    gc.collect()

    mesh = bench.dp_tp_mesh(devices, tp=2)
    with mesh:
        step, ids, labels, _ = bench.build_train_step(preset, batch, seq,
                                                      mesh=mesh)
        sharded, secs = run_steps(step, ids, labels, 1 + steps)
    # code that has only met virtual CPU devices may leave everything on
    # device 0: every parameter must live on every mesh device, in shards
    # of the shape its spec says
    mesh_ids = {int(d.id) for d in mesh.devices.flat}
    misplaced, tp_split = [], 0
    for name, v in step._params.items():
        want = v.sharding.shard_shape(v.shape)
        tp_split += want != v.shape
        if {int(s.device.id) for s in v.addressable_shards} != mesh_ids \
                or any(s.data.shape != want for s in v.addressable_shards):
            misplaced.append(name)
    out = {
        "preset": preset, "batch": batch, "seq": seq,
        "mesh": dict(mesh.shape), "device_ids": sorted(mesh_ids),
        "losses_one_device": [round(x, 4) for x in single],
        "losses_sharded": [round(x, 4) for x in sharded],
        "max_rel_diff": float(np.max(
            np.abs(np.subtract(sharded, single)) / np.abs(single))),
        "params": len(step._params), "params_split_over_tp": int(tp_split),
        "params_misplaced": misplaced,
        "compile_step_s": round(secs[0], 2),
        "step_ms": [round(s * 1e3, 1) for s in secs[1:]],
    }
    emit("multichip_train", **out)
    require(np.isfinite(sharded).all() and sharded[-1] < sharded[0],
            f"sharded losses {sharded}")
    require(np.allclose(sharded, single, rtol=2e-2),
            f"sharded losses {sharded} != one-device losses {single}")
    require(not misplaced and tp_split > 0,
            f"parameters not sharded as their spec says: {misplaced}, "
            f"{tp_split} split over tp")
    return out


def multichip_serve_phase(preset: str, replicas: int,
                          new_tokens: int) -> dict:
    """`replicas` one-device engine replicas, one request each: a replica
    has one slot, so requests that arrive together spread over them. One
    prefill bucket and a short context: every replica compiles its own
    programs, and four chips are charged while it does."""
    import numpy as np

    from paddle_tpu.inference.serve import build_generator
    from paddle_tpu.inference.serving import ServingHTTPServer

    engine = build_generator(preset, replicas=replicas, slots=1,
                             max_context=64, prompt_boundaries=[16])
    srv = ServingHTTPServer(None, generator=engine).start()
    url = f"http://127.0.0.1:{srv.port}"
    rng = np.random.RandomState(1)
    requests = [dict(input_ids=random_prompt(rng, preset, 12),
                     max_new_tokens=new_tokens) for _ in range(replicas)]
    answers, waves = [], 0
    try:
        # a replica whose thread wakes late can miss a wave to a faster
        # neighbour; one that works cannot miss five
        while waves < 5:
            answers += generate_together(url, requests)
            waves += 1
            rows = engine.replica_states()
            if all(r["batches"] > 0 for r in rows):
                break
    finally:
        srv.stop(drain=True)
    out = {"preset": preset, "waves": waves,
           "tokens_returned": [len(t) for t in answers],
           "replicas": [{k: r[k] for k in ("rid", "device", "batches")}
                        for r in rows]}
    emit("multichip_serve", **out)
    require(set(out["tokens_returned"]) == {new_tokens},
            f"asked {new_tokens} tokens of every request")
    require(len({r["device"] for r in rows}) == replicas
            and all(r["batches"] > 0 for r in rows),
            f"wanted {replicas} replicas, each on its own device, each "
            f"having served: {rows}")
    return out


# ------------------------------------------------------------------- main --
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run only the four-chip phases")
    args = ap.parse_args()

    import bench
    from paddle_tpu.core import compile_cache as cc

    devs = bench.require_tpu()
    emit("start", device=bench.device_dict(devs),
         compile_cache_dir=cc.stats()["dir"])
    if args.multichip:
        require(len(devs) >= 4, f"--multichip needs 4 chips, have {len(devs)}")
        multichip_train_phase(bench.PRESET, bench.BATCH, bench.SEQ, devs[:4])
        gc.collect()
        multichip_serve_phase(bench.PRESET, replicas=4, new_tokens=32)
    else:
        train = train_phase(bench.PRESET, bench.BATCH, bench.SEQ)
        require(train["tpu_custom_call"],
                "no tpu_custom_call in the compiled step: attention did "
                "not take the flash kernel")
        require(train["peak_bytes_in_use"],
                "the device reports no peak_bytes_in_use")
        gc.collect()
        serve = serve_phase(bench.PRESET, prompt_lens=(5, 24, 100, 300),
                            new_tokens=64)
        require(serve["donate"], "engine buffer donation is off on the TPU")
    emit("cache", **{k: cc.stats().get(k)
                     for k in ("dir", "hits", "misses", "entries")})
    print(json.dumps({"ok": True, "device": bench.device_dict(devs)}),
          flush=True)


if __name__ == "__main__":
    main()
