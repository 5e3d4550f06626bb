#!/usr/bin/env python
"""Load generator for the serving HTTP front-end.

Closed-loop (``--mode closed``): C worker threads each fire sequential
requests back-to-back — measures saturated throughput and the batching
it induces. Open-loop (``--mode open``): requests arrive on a Poisson
clock at ``--rate`` rps regardless of completions — measures latency
under a fixed offered load (the honest tail-latency number; closed-loop
self-throttles around slow responses).

Emits one BENCH-style JSON line (and ``--save PATH`` writes the same
object): throughput, latency percentiles, batch-occupancy histogram and
the engine's serving metrics snapshot.

By default spins up an in-process engine+server on a tiny generated
model (CPU-safe, the ci.sh smoke path); point --url at a running
``python -m paddle_tpu.inference.serve <prefix> --engine --http PORT``
to bench a real deployment over the wire.
"""
from __future__ import annotations

import argparse
import base64
import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _percentile(sorted_vals, p):
    if not sorted_vals:
        return 0.0
    i = min(int(p * (len(sorted_vals) - 1) + 0.5), len(sorted_vals) - 1)
    return sorted_vals[i]


# ===================================================================
# generation mode (--generate): token throughput + TTFT through the
# chunked /generate endpoint, vs a sequential per-request baseline
# ===================================================================
def gen_workload(n, seed=7, vocab=256, prompt_range=(4, 25),
                 out_range=(12, 33), shared_prefix=0):
    """Deterministic mixed-length workload: n (prompt_ids, max_new)
    pairs — the same list feeds the concurrent and the sequential pass
    so their outputs are comparable token-for-token. ``shared_prefix``
    prepends the SAME `shared_prefix`-token head to every prompt (the
    shared-system-prompt shape the prefix cache exists for)."""
    rng = np.random.RandomState(seed)
    head = rng.randint(0, vocab, size=shared_prefix).tolist() \
        if shared_prefix else []
    out = []
    for _ in range(n):
        plen = int(rng.randint(*prompt_range))
        mnew = int(rng.randint(*out_range))
        out.append((head + rng.randint(0, vocab, size=plen).tolist(),
                    mnew))
    return out


class GenClient:
    """One streaming /generate client: records TTFT (first chunk on
    the wire — the honest client-side number), per-request latency and
    the generated tokens (for the batched-vs-sequential parity check)."""

    def __init__(self, url, sample=None):
        self.url = url.rstrip("/") + "/generate"
        self.sample = sample
        self.results = []
        self.errors = 0

    def fire(self, idx, prompt, max_new):
        obj = {"input_ids": prompt, "max_new_tokens": max_new,
               "stream": True}
        if self.sample:
            obj.update(self.sample)
        body = json.dumps(obj).encode()
        req = urllib.request.Request(
            self.url, data=body,
            headers={"Content-Type": "application/json"})
        t0 = time.perf_counter()
        ttft = None
        toks = []
        try:
            with urllib.request.urlopen(req, timeout=300) as r:
                for line in r:
                    obj = json.loads(line)
                    if "token" in obj:
                        if ttft is None:
                            ttft = time.perf_counter() - t0
                        toks.append(obj["token"])
                    elif "error" in obj:
                        raise RuntimeError(obj["error"])
            self.results.append({"idx": idx, "tokens": toks, "ttft": ttft,
                                 "latency": time.perf_counter() - t0})
        except Exception:  # noqa: BLE001 — count, keep loading
            self.errors += 1


def run_generation(url, work, concurrency, sample=None):
    """Closed-loop: `concurrency` workers drain the shared work list.
    concurrency=1 IS the sequential per-request-decode baseline (one
    request in flight -> every decode step runs at batch bucket 1)."""
    clients = [GenClient(url, sample=sample) for _ in range(concurrency)]
    nxt = [0]
    lock = threading.Lock()

    def worker(c):
        while True:
            with lock:
                i = nxt[0]
                if i >= len(work):
                    return
                nxt[0] += 1
            prompt, max_new = work[i]
            c.fire(i, prompt, max_new)

    threads = [threading.Thread(target=worker, args=(c,),
                                name=f"bench-gen-{i}")
               for i, c in enumerate(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    results = [r for c in clients for r in c.results]
    errors = sum(c.errors for c in clients)
    tokens = sum(len(r["tokens"]) for r in results)
    return {
        "wall_s": wall,
        "errors": errors,
        "completed": len(results),
        "tokens": tokens,
        "tokens_per_s": tokens / wall if wall else 0.0,
        "ttft_sorted": sorted(r["ttft"] for r in results
                              if r["ttft"] is not None),
        "latency_sorted": sorted(r["latency"] for r in results),
        "by_idx": {r["idx"]: r["tokens"] for r in results},
    }


def _spec_gate(model, base_url, vocab, retries=2, kv_dtype="f32",
               quantize_weights=False):
    """Smoke gate: speculative decode must beat plain sequential decode
    by >=1.5x tokens/s on a decode-heavy workload, with BITWISE-equal
    outputs. The draft IS the target (self-draft): every greedy
    proposal verifies, so the verdict measures the machinery — k
    tokens per propose+verify dispatch pair instead of one per decode
    dispatch — not draft-quality luck."""
    from paddle_tpu.core import compile_cache as _cc
    from paddle_tpu.inference.serving import (GenerativeEngine,
                                              ServingHTTPServer)

    work = gen_workload(10, seed=9, vocab=vocab, prompt_range=(4, 17),
                        out_range=(48, 65))
    eng = GenerativeEngine(model, slots=4, max_context=128,
                           max_new_tokens_cap=64, draft=model,
                           spec_tokens=6, kv_dtype=kv_dtype,
                           quantize_weights=quantize_weights)
    srv = ServingHTTPServer(None, generator=eng).start()
    spec_url = f"http://127.0.0.1:{srv.port}"
    misses = 0
    try:
        for attempt in range(retries + 1):
            with _cc.measure() as d:
                base = run_generation(base_url, work, 1)
                spec = run_generation(spec_url, work, 1)
            misses += d["misses"]
            speedup = spec["tokens_per_s"] / base["tokens_per_s"] \
                if base["tokens_per_s"] else 0.0
            parity = (spec["by_idx"] == base["by_idx"]
                      and len(spec["by_idx"]) == len(work))
            errors = base["errors"] + spec["errors"]
            ok = parity and errors == 0 and speedup >= 1.5
            if ok or not parity or errors:
                break  # a determinism/error failure will not retry away
            print(f"# serve_bench spec gate: pass {attempt + 1} speedup "
                  f"{speedup:.2f}x < 1.5, retrying", file=sys.stderr)
        snap = eng.metrics.snapshot()
    finally:
        srv.stop()
    return {
        "ok": ok,
        "speedup": round(speedup, 3),
        "greedy_parity": parity,
        "errors": errors,
        "tokens_per_s": round(spec["tokens_per_s"], 2),
        "baseline_tokens_per_s": round(base["tokens_per_s"], 2),
        "spec_accept_rate": snap.get("spec_accept_rate"),
        "spec_steps_total": snap.get("spec_steps_total"),
        "workload_compile_misses": misses,
    }


def _prefix_gate(vocab, retries=2):
    """Smoke gate: with a shared 256-token system prompt, a warm prefix
    cache must cut client-observed TTFT p50 to <=0.5x cold. One engine
    serves both sides of the verdict: the cold pass uses DISTINCT
    256+token prompts (every request misses, full bucket-512 prefill —
    and churns the LRU, since the workload outnumbers the cache rows),
    the warm pass replays a shared-prefix workload whose head an admit
    pass already cached (tail-only prefill). 512-token prompts on this
    model make prefill the dominant TTFT term, so the ratio measures
    the cache, not HTTP/decode-dispatch overhead. Token parity is
    checked hit-vs-miss: the admit pass (request 0 is a miss) must
    match the all-hits replay bitwise."""
    from paddle_tpu.core import compile_cache as _cc
    from paddle_tpu.inference.serving import (GenerativeEngine,
                                              ServingHTTPServer)
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    import paddle_tpu as paddle

    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=vocab, hidden_size=64, num_layers=2, num_heads=4,
        max_seq_len=512, dropout=0.0))
    model.eval()
    eng = GenerativeEngine(model, slots=4, max_context=512,
                           max_new_tokens_cap=16,
                           prompt_boundaries=[8, 16, 32, 256, 512],
                           prefix_cache_slots=2)
    srv = ServingHTTPServer(None, generator=eng).start()
    url = f"http://127.0.0.1:{srv.port}"
    shared = gen_workload(8, seed=13, vocab=vocab, prompt_range=(4, 25),
                          out_range=(8, 13), shared_prefix=256)
    distinct = gen_workload(8, seed=17, vocab=vocab,
                            prompt_range=(260, 282), out_range=(8, 13))
    misses = 0
    try:
        with _cc.measure() as d:
            admit = run_generation(url, shared, 1)  # seeds the cache
        misses += d["misses"]
        for attempt in range(retries + 1):
            with _cc.measure() as d:
                cold = run_generation(url, distinct, 1)
                warm = run_generation(url, shared, 1)
            misses += d["misses"]
            p50_cold = _percentile(cold["ttft_sorted"], 0.50)
            p50_warm = _percentile(warm["ttft_sorted"], 0.50)
            ratio = p50_warm / p50_cold if p50_cold else 1.0
            parity = (warm["by_idx"] == admit["by_idx"]
                      and len(warm["by_idx"]) == len(shared))
            errors = admit["errors"] + cold["errors"] + warm["errors"]
            ok = parity and errors == 0 and ratio <= 0.5
            if ok or not parity or errors:
                break
            print(f"# serve_bench prefix gate: pass {attempt + 1} TTFT "
                  f"ratio {ratio:.2f} > 0.5, retrying", file=sys.stderr)
        snap = eng.metrics.snapshot()
    finally:
        srv.stop()
    return {
        "ok": ok,
        "ttft_ratio": round(ratio, 3),
        "parity": parity,
        "errors": errors,
        "ttft_ms_warm_p50": round(p50_warm * 1e3, 3),
        "ttft_ms_cold_p50": round(p50_cold * 1e3, 3),
        "prefix_hits": snap.get("prefix_hits_total"),
        "prefix_evictions": snap.get("prefix_evictions_total"),
        "prefix_tokens_reused": snap.get("prefix_tokens_reused_total"),
        "workload_compile_misses": misses,
    }


def _quant_gate(vocab):
    """Quantized-serving gate (DESIGN.md "Quantized serving"). Three
    engines on the same seeded weights: the f32 reference at S slots
    sets the byte budget, an int8-pool engine at 2S slots must FIT that
    budget (allocator-exact ``kv_pool_bytes``, which mirrors ``alloc``
    to the byte) and serve a concurrent workload over the doubled slots
    with errors==0 and zero fresh compiles after admission warmup, and
    an int8-pool S-slot engine must bill half the bytes per slot. The
    parity half of the verdict is deliberately two-tier: the kv-only
    int8 engine must match float greedy output near-exactly on this
    tiny preset (the pool round-trip is the only error source), while
    the full tier (weights int8 too) must keep every FIRST token exact
    (prefill attends in-program f32 K/V) and the full sequences within
    the documented drift tolerance. No retries: every check here is
    deterministic — a failure is a real regression, not CI noise."""
    from paddle_tpu.core import compile_cache as _cc
    from paddle_tpu.inference.serving import (GenerativeEngine,
                                              ServingHTTPServer)
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    import paddle_tpu as paddle

    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=vocab, hidden_size=64, num_layers=2, num_heads=4,
        max_seq_len=128, dropout=0.0))
    model.eval()
    S = 4
    kw = dict(max_context=128, max_new_tokens_cap=32)
    f32 = GenerativeEngine(model, slots=S, **kw)
    budget = f32.kv_pool_bytes()
    dense = GenerativeEngine(model, slots=2 * S, kv_dtype="int8", **kw)
    i8 = GenerativeEngine(model, slots=S, kv_dtype="int8", **kw)
    i8w = GenerativeEngine(model, slots=S, kv_dtype="int8",
                           quantize_weights=True, **kw)
    srvs = [ServingHTTPServer(None, generator=e).start()
            for e in (f32, dense, i8, i8w)]
    urls = [f"http://127.0.0.1:{s.port}" for s in srvs]
    work = gen_workload(12, seed=21, vocab=vocab, out_range=(8, 17))
    try:
        half_per_slot = i8.kv_pool_bytes() * 2 <= budget
        double_slots = dense.kv_pool_bytes() <= budget
        with _cc.measure() as d:
            ref = run_generation(urls[0], work, 1)
            # the doubled-slot engine takes the CONCURRENT pass: all
            # 2S slots live at once, proving the density is usable,
            # not just billable
            out_d = run_generation(urls[1], work, 2 * S + 2)
            out_kv = run_generation(urls[2], work, 1)
            out_w = run_generation(urls[3], work, 1)
        misses = d["misses"]
        errors = (ref["errors"] + out_d["errors"] + out_kv["errors"]
                  + out_w["errors"])

        def frac(a, b):
            # mean per-request fraction of token positions that agree
            # (workload guarantees non-empty outputs per request)
            if set(a) != set(b) or not a:
                return 0.0
            per = [float(np.mean([x == y
                                  for x, y in zip(a[i], b[i])]))
                   for i in a]
            return float(np.mean(per))

        frac_kv = frac(ref["by_idx"], out_kv["by_idx"])
        frac_dense = frac(ref["by_idx"], out_d["by_idx"])
        frac_w = frac(ref["by_idx"], out_w["by_idx"])
        first_w = all(ref["by_idx"][i][:1] == out_w["by_idx"][i][:1]
                      for i in ref["by_idx"]) if ref["by_idx"] else False
        occupancy = dense.metrics.snapshot()["max_slot_occupancy"]
        ok = (half_per_slot and double_slots and errors == 0
              and misses == 0 and occupancy > S
              and frac_kv >= 0.95 and frac_dense >= 0.95
              and first_w and frac_w >= 0.6)
    finally:
        for s in srvs:
            s.stop()
    return {
        "ok": ok,
        "f32_pool_bytes": budget,
        "int8_pool_bytes": i8.kv_pool_bytes(),
        "int8_2x_slots_pool_bytes": dense.kv_pool_bytes(),
        "half_bytes_per_slot": half_per_slot,
        "double_slots_in_budget": double_slots,
        "max_slot_occupancy_2x": occupancy,
        "errors": errors,
        "parity_frac_kv_int8": round(frac_kv, 4),
        "parity_frac_kv_int8_2x": round(frac_dense, 4),
        "parity_frac_full_int8": round(frac_w, 4),
        "first_token_exact_full_int8": first_w,
        "workload_compile_misses": misses,
    }


def quant_gate_main(args):
    """--quant-gate entry: the quantized-serving density + parity gate
    standalone (the cheap CI wiring — no spec/prefix/throughput passes
    riding along)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    gate = _quant_gate(args.vocab)
    result = {
        "metric": "quantized_serving_gate",
        "value": gate["int8_2x_slots_pool_bytes"],
        "unit": "bytes",
        "mode": "quant-gate",
        "quant_gate": gate,
    }
    print(json.dumps(result))
    if args.save:
        with open(args.save, "w") as f:
            json.dump(result, f, indent=1)
    if not gate["ok"]:
        print(f"# serve_bench quant gate FAILED: {gate}", file=sys.stderr)
        return 1 if args.smoke else 0
    print(f"# serve_bench quant gate OK: 2x slots in "
          f"{gate['int8_2x_slots_pool_bytes']} <= "
          f"{gate['f32_pool_bytes']} bytes (occupancy "
          f"{gate['max_slot_occupancy_2x']}), kv-int8 parity "
          f"{gate['parity_frac_kv_int8']:.3f}, full-int8 parity "
          f"{gate['parity_frac_full_int8']:.3f} (first tokens exact), "
          f"0 workload compiles", file=sys.stderr)
    return 0


def generation_main(args):
    """--generate entry: concurrent pass (in-flight batching) vs
    sequential baseline over the same workload; BENCH JSON + smoke
    verdict (>=2x aggregate tokens/s AND token-identical outputs,
    plus the speculative >=1.5x and prefix-cache TTFT <=0.5x gates
    on the in-process engine)."""
    srv = None
    engine = None
    model = None
    url = args.url
    vocab = args.vocab
    if url is None:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import paddle_tpu as paddle
        from paddle_tpu.inference.serving import (GenerativeEngine,
                                                  ServingHTTPServer)
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

        paddle.seed(0)
        cfg = GPTConfig(vocab_size=vocab, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=128, dropout=0.0)
        model = GPTForCausalLM(cfg)
        model.eval()
        draft_model = None
        if args.draft == "self":
            draft_model = model
        elif args.draft == "tiny":
            paddle.seed(1)
            draft_model = GPTForCausalLM(GPTConfig(
                vocab_size=vocab, hidden_size=32, num_layers=1,
                num_heads=2, max_seq_len=128, dropout=0.0))
            draft_model.eval()
        engine = GenerativeEngine(model, slots=args.slots,
                                  max_context=128,
                                  max_new_tokens_cap=64,
                                  draft=draft_model,
                                  spec_tokens=args.spec_tokens,
                                  prefix_cache_slots=args.prefix_cache,
                                  kv_dtype=args.kv_dtype,
                                  quantize_weights=args.quantize_weights)
        srv = ServingHTTPServer(None, generator=engine).start()
        url = f"http://127.0.0.1:{srv.port}"
        print(f"# serve_bench --generate: in-process server on {url} "
              f"(warmup {engine.warmup_report})", file=sys.stderr)

    def _measured(fn):
        # workload passes must hit only programs the engine warmed at
        # admission time — a fresh compile mid-workload is a warmup
        # inventory hole, and --smoke reds on it
        if engine is None:
            return fn(), 0
        from paddle_tpu.core import compile_cache as _cc
        with _cc.measure() as d:
            out = fn()
        return out, d["misses"]

    work = gen_workload(args.requests, vocab=vocab,
                        shared_prefix=args.shared_prefix)
    (conc, m1) = _measured(
        lambda: run_generation(url, work, args.concurrency,
                               sample=args.sample))
    (seq, m2) = _measured(
        lambda: run_generation(url, work, 1, sample=args.sample))
    workload_misses = m1 + m2

    def verdict(c, s):
        sp = c["tokens_per_s"] / s["tokens_per_s"] \
            if s["tokens_per_s"] else 0.0
        par = (c["by_idx"] == s["by_idx"]
               and len(c["by_idx"]) == len(work))
        return sp, par

    speedup, parity = verdict(conc, seq)
    for attempt in range(2):
        if not (args.smoke and parity and speedup < 2.0
                and conc["errors"] == seq["errors"] == 0):
            break
        # retry bursts (predict smoke's rule, twice here because the
        # measured windows are sub-second): a noisy scheduling window
        # on a loaded shared host must not red an unrelated PR — and
        # the saved artifact describes the pass the verdict was
        # judged on
        print(f"# serve_bench generate: pass {attempt + 1} speedup "
              f"{speedup:.2f}x < 2.0, retrying", file=sys.stderr)
        (conc, m1) = _measured(
            lambda: run_generation(url, work, args.concurrency,
                                   sample=args.sample))
        (seq, m2) = _measured(
            lambda: run_generation(url, work, 1, sample=args.sample))
        workload_misses += m1 + m2
        speedup, parity = verdict(conc, seq)

    # the speculative and prefix-cache gates need the in-process model
    # (each spins its own engine); against an external --url there is
    # nothing to build, so they stay None and the smoke skips them
    spec_gate = prefix_gate = None
    if args.smoke and model is not None:
        spec_gate = _spec_gate(model, url, vocab,
                               kv_dtype=args.kv_dtype,
                               quantize_weights=args.quantize_weights)
        workload_misses += spec_gate.pop("workload_compile_misses")
        prefix_gate = _prefix_gate(vocab)
        workload_misses += prefix_gate.pop("workload_compile_misses")

    snap = engine.metrics.snapshot() if engine is not None else None
    result = {
        "metric": "generate_tokens_per_s",
        "value": round(conc["tokens_per_s"], 2),
        "unit": "tokens/s",
        "mode": "generate-closed",
        "requests": len(work),
        "completed": conc["completed"],
        "errors": conc["errors"] + seq["errors"],
        "wall_s": round(conc["wall_s"], 3),
        "concurrency": args.concurrency,
        "tokens": conc["tokens"],
        "ttft_ms": {
            "p50": round(_percentile(conc["ttft_sorted"], 0.50) * 1e3, 3),
            "p95": round(_percentile(conc["ttft_sorted"], 0.95) * 1e3, 3),
        },
        "latency_ms": {
            "p50": round(_percentile(conc["latency_sorted"], 0.50)
                         * 1e3, 3),
            "p95": round(_percentile(conc["latency_sorted"], 0.95)
                         * 1e3, 3),
        },
        "sequential_tokens_per_s": round(seq["tokens_per_s"], 2),
        "inflight_speedup": round(speedup, 3),
        "greedy_parity": parity,
        "sample": args.sample,
        "shared_prefix": args.shared_prefix,
        "draft": args.draft,
        "kv_dtype": args.kv_dtype,
        "quantize_weights": args.quantize_weights,
        "workload_compile_misses": workload_misses,
        "spec_gate": spec_gate,
        "prefix_gate": prefix_gate,
        "generation": snap,
    }
    print(json.dumps(result))
    if args.save:
        with open(args.save, "w") as f:
            json.dump(result, f, indent=1)

    rc = 0
    if args.smoke:
        occ = (snap or {}).get("max_slot_occupancy", 0)
        # occupancy is only observable on the in-process engine; against
        # an external --url there is no snapshot to assert on
        occ_ok = occ > 1 if engine is not None else True
        gates_ok = ((spec_gate is None or spec_gate["ok"])
                    and (prefix_gate is None or prefix_gate["ok"]))
        ok = (result["errors"] == 0
              and conc["completed"] == len(work)
              and seq["completed"] == len(work)
              and parity
              and speedup >= 2.0
              and occ_ok
              and workload_misses == 0
              and gates_ok)
        if not ok:
            print(f"# serve_bench generate smoke FAILED: "
                  f"errors={result['errors']} "
                  f"completed={conc['completed']}/{len(work)} "
                  f"parity={parity} speedup={speedup:.2f} "
                  f"occupancy={occ} "
                  f"workload_misses={workload_misses} "
                  f"spec_gate={spec_gate} prefix_gate={prefix_gate}",
                  file=sys.stderr)
            rc = 1
        else:
            extra = ""
            if spec_gate is not None:
                extra = (f", speculative {spec_gate['speedup']:.2f}x, "
                         f"prefix TTFT {prefix_gate['ttft_ratio']:.2f}x "
                         f"cold")
            print(f"# serve_bench generate smoke OK: {conc['tokens']} "
                  f"tokens, {result['value']} tok/s batched vs "
                  f"{result['sequential_tokens_per_s']} sequential "
                  f"({speedup:.2f}x, occupancy {occ}, outputs "
                  f"token-identical{extra})", file=sys.stderr)
    if srv is not None:
        srv.stop()
    return rc


# ===================================================================
# recsys mode (--recsys): batched sparse-embedding lookups + pushes
# through the fabric front door's /embed endpoints, vs a sequential
# per-key baseline — the embedding tier's standing throughput gate
# ===================================================================
def recsys_workload(n_batches, batch_keys, n_keys, push_frac=0.1,
                    seed=11):
    """Deterministic zipf-distributed op list: the recsys shape (a few
    hot keys dominate, a long cold tail) with a read/write mix. Each op
    is ("lookup"|"push", [keys...]); the same list feeds the batched
    and the per-key pass so the verdict compares like for like."""
    rng = np.random.RandomState(seed)
    ops = []
    for _ in range(n_batches):
        keys = (rng.zipf(1.3, size=batch_keys) % n_keys).tolist()
        kind = "push" if rng.rand() < push_frac else "lookup"
        ops.append((kind, keys))
    return ops


class EmbedClient:
    """One /embed client: fires batched lookups/pushes, records
    latency + keys served, verifies row dim on every answer."""

    def __init__(self, url, table, dim):
        self.base = url.rstrip("/")
        self.table = table
        self.dim = dim
        self.latencies = []
        self.keys_done = 0
        self.errors = 0

    def fire(self, kind, keys):
        if kind == "push":
            path, obj = "/embed/push", {
                "table": self.table, "keys": keys,
                "deltas": [[0.01] * self.dim] * len(keys),
                "op": "grad", "lr": 0.1}
        else:
            path, obj = "/embed/lookup", {"table": self.table,
                                          "keys": keys}
        body = json.dumps(obj).encode()
        req = urllib.request.Request(
            self.base + path, data=body,
            headers={"Content-Type": "application/json"})
        t0 = time.perf_counter()
        try:
            with urllib.request.urlopen(req, timeout=60) as r:
                ans = json.loads(r.read())
            if kind == "lookup":
                rows = ans.get("rows") or []
                if len(rows) != len(keys) or \
                        any(len(row) != self.dim for row in rows):
                    raise RuntimeError(f"bad lookup answer: "
                                       f"{len(rows)} rows")
            self.latencies.append(time.perf_counter() - t0)
            self.keys_done += len(keys)
        except Exception:  # noqa: BLE001 — count, keep loading
            self.errors += 1


def run_embed(url, ops, concurrency, table, dim):
    """Closed-loop: `concurrency` workers drain the shared op list."""
    clients = [EmbedClient(url, table, dim) for _ in range(concurrency)]
    nxt = [0]
    lock = threading.Lock()

    def worker(c):
        while True:
            with lock:
                i = nxt[0]
                if i >= len(ops):
                    return
                nxt[0] += 1
            kind, keys = ops[i]
            c.fire(kind, keys)

    threads = [threading.Thread(target=worker, args=(c,),
                                name=f"bench-embed-{i}")
               for i, c in enumerate(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    keys_done = sum(c.keys_done for c in clients)
    return {
        "wall_s": wall,
        "errors": sum(c.errors for c in clients),
        "completed": sum(len(c.latencies) for c in clients),
        "keys": keys_done,
        "keys_per_s": keys_done / wall if wall else 0.0,
        "latency_sorted": sorted(x for c in clients
                                 for x in c.latencies),
    }


def recsys_main(args):
    """--recsys entry: an in-process 2-shard embedding fleet behind a
    real fabric front door (or --url at a running door), zipf batched
    lookups + pushes vs the SAME keys one per request. --smoke asserts
    errors==0 and batched >= 2x sequential keys/s."""
    table, dim = "bench", args.dim
    world = None
    url = args.url
    if url is None:
        import tempfile

        from paddle_tpu.distributed.store import TCPStore
        from paddle_tpu.inference.embedding import (EmbeddingRouter,
                                                    EmbeddingShardServer,
                                                    ShardAgent)
        from paddle_tpu.inference.fabric import (FabricHTTPServer,
                                                 FabricRouter,
                                                 MembershipView)
        from paddle_tpu.testing.multihost import free_port, poll_until

        port = free_port()
        store = TCPStore("127.0.0.1", port, is_master=True)
        shards, agents = [], []
        for i in range(args.shards):
            sh = EmbeddingShardServer(
                tempfile.mkdtemp(prefix=f"embed_bench{i}_"),
                tables={table: dim}, cache_rows=args.cache_rows).start()
            agents.append(ShardAgent(sh, store,
                                     host_id=f"bench-shard{i}").start())
            shards.append(sh)
        view = MembershipView(store, lease_s=3.0).start()
        poll_until(lambda: len(view.alive("embed")) == len(shards),
                   timeout=10.0)
        door = FabricHTTPServer(
            FabricRouter(view),
            embed_router=EmbeddingRouter(view, store=store)).start()
        url = f"http://{door.host}:{door.port}"
        world = (store, shards, agents, door)
        print(f"# serve_bench --recsys: in-process {len(shards)}-shard "
              f"fleet behind {url}", file=sys.stderr)

    ops = recsys_workload(args.batches, args.batch_keys, args.n_keys,
                          push_frac=args.push_frac)
    per_key = [(kind, [k]) for kind, keys in ops for k in keys]
    batched = run_embed(url, ops, args.concurrency, table, dim)
    seq = run_embed(url, per_key, args.concurrency, table, dim)
    speedup = batched["keys_per_s"] / seq["keys_per_s"] \
        if seq["keys_per_s"] else 0.0
    for attempt in range(2):
        if not (args.smoke and speedup < 2.0
                and batched["errors"] == seq["errors"] == 0):
            break
        # retry bursts (the generate smoke's rule): scheduling noise
        # on a loaded CI host must not red an unrelated PR
        print(f"# serve_bench recsys: pass {attempt + 1} speedup "
              f"{speedup:.2f}x < 2.0, retrying", file=sys.stderr)
        batched = run_embed(url, ops, args.concurrency, table, dim)
        seq = run_embed(url, per_key, args.concurrency, table, dim)
        speedup = batched["keys_per_s"] / seq["keys_per_s"] \
            if seq["keys_per_s"] else 0.0

    shard_stats = None
    if world is not None:
        shard_stats = [sh.stats()["metrics"] for sh in world[1]]
    result = {
        "metric": "embed_lookup_keys_per_s",
        "value": round(batched["keys_per_s"], 2),
        "unit": "keys/s",
        "mode": "recsys-closed",
        "ops": len(ops),
        "completed": batched["completed"],
        "errors": batched["errors"] + seq["errors"],
        "wall_s": round(batched["wall_s"], 3),
        "concurrency": args.concurrency,
        "keys": batched["keys"],
        "zipf_keys": args.n_keys,
        "push_frac": args.push_frac,
        "latency_ms": {
            "p50": round(_percentile(batched["latency_sorted"], 0.50)
                         * 1e3, 3),
            "p95": round(_percentile(batched["latency_sorted"], 0.95)
                         * 1e3, 3),
        },
        "sequential_keys_per_s": round(seq["keys_per_s"], 2),
        "batch_speedup": round(speedup, 3),
        "shards": shard_stats,
    }
    print(json.dumps(result))
    if args.save:
        with open(args.save, "w") as f:
            json.dump(result, f, indent=1)

    rc = 0
    if args.smoke:
        ok = (result["errors"] == 0
              and batched["completed"] == len(ops)
              and seq["completed"] == len(per_key)
              and speedup >= 2.0)
        if not ok:
            print(f"# serve_bench recsys smoke FAILED: "
                  f"errors={result['errors']} "
                  f"completed={batched['completed']}/{len(ops)} "
                  f"speedup={speedup:.2f}", file=sys.stderr)
            rc = 1
        else:
            print(f"# serve_bench recsys smoke OK: {batched['keys']} "
                  f"keys at {result['value']} keys/s batched vs "
                  f"{result['sequential_keys_per_s']} per-key "
                  f"({speedup:.2f}x)", file=sys.stderr)
    if world is not None:
        store, shards, agents, door = world
        door.stop()
        for a, sh in zip(agents, shards):
            a.leave()
            sh.stop()
        store.stop()
    return rc


def disagg_main(args):
    """--disagg entry: an in-process disaggregated fleet — one prefill
    host plus two decode hosts, identically seeded engines — behind a
    real fabric front door. Every stream prefills on the prefill pool
    and moves to a decode host over the live KV handoff; --smoke
    asserts errors==0, token parity against a single reference engine,
    at least one stream actually rode the disagg path, ZERO fresh
    compiles mid-workload (the handoff program families are warmup
    inventory, not lazy compiles), and the int8 handoff wire costing
    <= 0.55x the f32 wire at the same capacity class."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import paddle_tpu as paddle
    from paddle_tpu.core import compile_cache as _cc
    from paddle_tpu.distributed.store import TCPStore
    from paddle_tpu.inference.fabric import (FabricHTTPServer,
                                             FabricRouter, HostAgent,
                                             MembershipView)
    from paddle_tpu.inference.fabric import handoff as _handoff
    from paddle_tpu.inference.serving import (GenerativeEngine,
                                              ServingHTTPServer)
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.testing.multihost import free_port, poll_until

    vocab = args.vocab

    def build(kv_dtype="f32"):
        paddle.seed(0)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=vocab, hidden_size=64, num_layers=2,
            num_heads=4, max_seq_len=128, dropout=0.0))
        model.eval()
        return GenerativeEngine(model, slots=args.slots,
                                max_context=128,
                                max_new_tokens_cap=64,
                                kv_dtype=kv_dtype)

    ref = build()
    ref_srv = ServingHTTPServer(None, generator=ref).start()
    ref_url = f"http://127.0.0.1:{ref_srv.port}"

    store = TCPStore("127.0.0.1", free_port(), is_master=True)
    hosts = []
    for hid, pools in (("bench-pf", ("prefill",)),
                       ("bench-dc0", ("decode",)),
                       ("bench-dc1", ("decode",))):
        eng = build()
        srv = ServingHTTPServer(None, generator=eng, admin=True).start()
        agent = HostAgent(srv, store, host_id=hid, heartbeat_s=0.25,
                          pools=pools).start()
        hosts.append((hid, eng, srv, agent))
    view = MembershipView(store, lease_s=3.0).start()
    poll_until(lambda: len(view.alive("prefill")) == 1
               and len(view.alive("decode")) == 2, timeout=10.0)
    router = FabricRouter(view)
    door = FabricHTTPServer(router).start()
    url = f"http://{door.host}:{door.port}"
    print(f"# serve_bench --disagg: 1 prefill + 2 decode hosts behind "
          f"{url}", file=sys.stderr)

    work = gen_workload(args.requests, seed=23, vocab=vocab)
    try:
        with _cc.measure() as d:
            base = run_generation(ref_url, work, 1, sample=args.sample)
            out = run_generation(url, work, args.concurrency,
                                 sample=args.sample)
        misses = d["misses"]
        snap = router.metrics.snapshot()
        handoffs = snap["prefill_handoffs_total"]
        parity = (out["by_idx"] == base["by_idx"]
                  and len(out["by_idx"]) == len(work))
        errors = out["errors"] + base["errors"]

        # wire-density check: export the SAME prompt's live KV state
        # from an f32 and an int8 engine at the same capacity class
        # and compare payload bytes (the int8 row ships int8 data plus
        # one f32 scale per (row, layer) — well under 0.55x)
        probe = work[0][0]
        raw32 = _handoff.from_b64(
            ref.submit(probe, max_new_tokens=8,
                       prefill_only=True).result(60)["handoff"])
        i8 = build(kv_dtype="int8")
        raw8 = _handoff.from_b64(
            i8.submit(probe, max_new_tokens=8,
                      prefill_only=True).result(60)["handoff"])
        ratio = len(raw8) / len(raw32) if raw32 else 1.0

        ok = (errors == 0 and parity
              and out["completed"] == len(work)
              and handoffs > 0 and misses == 0 and ratio <= 0.55)
        result = {
            "metric": "disagg_tokens_per_s",
            "value": round(out["tokens_per_s"], 2),
            "unit": "tokens/s",
            "mode": "disagg",
            "requests": len(work),
            "completed": out["completed"],
            "errors": errors,
            "concurrency": args.concurrency,
            "parity": parity,
            "prefill_handoffs": handoffs,
            "streams_resumed": snap["streams_resumed_total"],
            "streams_migrated": snap["streams_migrated_total"],
            "workload_compile_misses": misses,
            "handoff_wire_bytes_f32": len(raw32),
            "handoff_wire_bytes_int8": len(raw8),
            "handoff_wire_ratio": round(ratio, 3),
            "latency_ms": {
                "p50": round(_percentile(out["latency_sorted"], 0.50)
                             * 1e3, 3),
                "p95": round(_percentile(out["latency_sorted"], 0.95)
                             * 1e3, 3),
            },
        }
    finally:
        door.stop()
        for _hid, _eng, _srv, agent in hosts:
            agent.leave()
        ref_srv.stop()
        store.stop()
    print(json.dumps(result))
    if args.save:
        with open(args.save, "w") as f:
            json.dump(result, f, indent=1)
    rc = 0
    if args.smoke:
        if not ok:
            print(f"# serve_bench disagg smoke FAILED: errors={errors} "
                  f"completed={out['completed']}/{len(work)} "
                  f"parity={parity} handoffs={handoffs} "
                  f"misses={misses} wire_ratio={ratio:.3f}",
                  file=sys.stderr)
            rc = 1
        else:
            print(f"# serve_bench disagg smoke OK: {len(work)} streams "
                  f"({handoffs} disagg handoffs) token-identical at "
                  f"{result['value']} tok/s, 0 workload compiles, "
                  f"int8 wire {ratio:.3f}x f32", file=sys.stderr)
    return rc


class Client:
    """One /predict JSON client; records per-request latency."""

    def __init__(self, url, feature_dim, rows=1):
        self.url = url.rstrip("/") + "/predict"
        self.dim = feature_dim
        self.rows = rows
        self.latencies = []
        self.errors = 0

    def fire(self, rng):
        x = rng.randn(self.rows, self.dim).astype("float32")
        body = json.dumps({"inputs": [{
            "b64": base64.b64encode(x.tobytes()).decode(),
            "dtype": "float32", "shape": list(x.shape)}]}).encode()
        req = urllib.request.Request(
            self.url, data=body,
            headers={"Content-Type": "application/json"})
        t0 = time.perf_counter()
        try:
            with urllib.request.urlopen(req, timeout=60) as r:
                r.read()
            self.latencies.append(time.perf_counter() - t0)
        except Exception:  # noqa: BLE001 — count, keep loading
            self.errors += 1


def closed_loop(url, dim, concurrency, requests_per_worker, rows):
    clients = [Client(url, dim, rows) for _ in range(concurrency)]

    def work(c, seed):
        rng = np.random.RandomState(seed)
        for _ in range(requests_per_worker):
            c.fire(rng)

    threads = [threading.Thread(target=work, args=(c, i),
                                name=f"bench-closed-{i}")
               for i, c in enumerate(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    lat = sorted(x for c in clients for x in c.latencies)
    errors = sum(c.errors for c in clients)
    return wall, lat, errors


def open_loop(url, dim, rate, duration_s, rows, max_inflight=256):
    """Poisson arrivals at `rate` rps for `duration_s`. `rate` may be a
    float or a callable of elapsed-seconds (the --ramp overload
    profile: offered load climbs while the run progresses, which is
    what an autoscaler must answer)."""
    lock = threading.Lock()
    lat, errors = [], [0]
    threads = []
    arrival_rng = np.random.RandomState(1)
    rate_fn = rate if callable(rate) else (lambda _t: rate)

    def one(seed):
        c = Client(url, dim, rows)
        c.fire(np.random.RandomState(seed))
        with lock:
            lat.extend(c.latencies)
            errors[0] += c.errors

    t0 = time.perf_counter()
    t_next = t0
    i = 0
    while time.perf_counter() - t0 < duration_s:
        now = time.perf_counter()
        if now < t_next:
            time.sleep(min(t_next - now, 0.005))
            continue
        r = max(1e-3, float(rate_fn(now - t0)))
        t_next += arrival_rng.exponential(1.0 / r)
        threads = [t for t in threads if t.is_alive()]
        if len(threads) >= max_inflight:
            errors[0] += 1  # offered load beyond client capacity
            continue
        th = threading.Thread(target=one, args=(i,), name=f"bench-open-{i}")
        th.start()
        threads.append(th)
        i += 1
    for th in threads:
        th.join(60)
    wall = time.perf_counter() - t0
    return wall, sorted(lat), errors[0]


def ramp_rate(r0: float, r1: float, duration_s: float):
    """Linear offered-load ramp r0 -> r1 rps over the run."""
    def fn(t):
        frac = min(max(t / duration_s, 0.0), 1.0) if duration_s else 1.0
        return r0 + (r1 - r0) * frac

    return fn


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--url", default=None,
                    help="bench a running server (default: spin up an "
                         "in-process engine+server on a tiny model)")
    ap.add_argument("--mode", choices=("closed", "open"), default="closed")
    ap.add_argument("--concurrency", type=int, default=8,
                    help="closed-loop worker threads")
    ap.add_argument("--requests", type=int, default=25,
                    help="closed-loop requests per worker")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="open-loop arrival rate (rps)")
    ap.add_argument("--ramp", default=None, metavar="R0:R1",
                    help="open-loop overload profile: ramp the arrival "
                         "rate linearly R0 -> R1 rps over --duration "
                         "(implies --mode open); the load shape an "
                         "autoscaler is judged against")
    ap.add_argument("--duration", type=float, default=5.0,
                    help="open-loop duration (s)")
    ap.add_argument("--rows", type=int, default=1,
                    help="rows per request")
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--max-batch-size", type=int, default=8)
    ap.add_argument("--batch-timeout-ms", type=float, default=2.0)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--save", default=None, help="write the JSON artifact")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: small fixed load + sanity asserts")
    ap.add_argument("--generate", action="store_true",
                    help="generation mode: token throughput + TTFT "
                         "through the chunked /generate endpoint, with "
                         "a sequential per-request-decode baseline "
                         "(--smoke asserts >=2x aggregate tokens/s and "
                         "token-identical greedy outputs)")
    ap.add_argument("--slots", type=int, default=8,
                    help="generation mode: decode-batch capacity of the "
                         "in-process engine")
    ap.add_argument("--sample", default=None, metavar="T,K,P,SEED",
                    help="generation mode: send temperature/top_k/top_p/"
                         "seed on every request (seeded sampling is "
                         "deterministic, so the parity verdicts still "
                         "hold)")
    ap.add_argument("--draft", choices=("self", "tiny"), default=None,
                    help="generation mode: speculative decode on the "
                         "in-process engine — 'self' drafts with the "
                         "target itself (every greedy proposal "
                         "verifies; isolates the dispatch-fusion win), "
                         "'tiny' with a 1-layer model at the same "
                         "vocab")
    ap.add_argument("--spec-tokens", type=int, default=4,
                    help="generation mode: tokens per speculative "
                         "burst (with --draft)")
    ap.add_argument("--prefix-cache", type=int, default=0,
                    metavar="SLOTS",
                    help="generation mode: prefix-cache slots on the "
                         "in-process engine (pair with --shared-prefix)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    metavar="TOKENS",
                    help="generation mode: prepend the same N-token "
                         "head to every prompt (the shared-system-"
                         "prompt workload the prefix cache serves)")
    ap.add_argument("--kv-dtype", choices=("f32", "int8"), default="f32",
                    help="generation mode: KV-pool precision of the "
                         "in-process engine (int8 = quantized pool, "
                         "half the bytes per slot)")
    ap.add_argument("--quantize-weights", action="store_true",
                    help="generation mode: weight-only int8 on the "
                         "in-process engine")
    ap.add_argument("--quant-gate", action="store_true",
                    help="run ONLY the quantized-serving gate: the int8 "
                         "pool must fit >=2x the f32 engine's decode "
                         "slots in the same byte budget (allocator-"
                         "exact nbytes), serve over the doubled slots "
                         "with errors==0 and zero fresh compiles, and "
                         "hold greedy parity vs the float engine "
                         "(--smoke makes the verdict the exit code)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated-serving mode: 1 prefill + 2 "
                         "decode hosts behind an in-process fabric "
                         "door; streams prefill on one pool and decode "
                         "on the other via the live KV handoff "
                         "(--smoke asserts errors==0, token parity vs "
                         "a reference engine, zero fresh compiles "
                         "mid-workload, and int8 handoff wire bytes "
                         "<= 0.55x f32 at the same capacity class)")
    ap.add_argument("--recsys", action="store_true",
                    help="recsys mode: zipf batched sparse-embedding "
                         "lookups + pushes through the fabric front "
                         "door's /embed endpoints, vs a sequential "
                         "per-key baseline (--smoke asserts errors==0 "
                         "and >=2x batched keys/s)")
    ap.add_argument("--shards", type=int, default=2,
                    help="recsys mode: in-process shard hosts")
    ap.add_argument("--batches", type=int, default=30,
                    help="recsys mode: batched ops in the workload")
    ap.add_argument("--batch-keys", type=int, default=64,
                    help="recsys mode: keys per batched op")
    ap.add_argument("--n-keys", type=int, default=5000,
                    help="recsys mode: key-space size the zipf draw "
                         "folds into")
    ap.add_argument("--push-frac", type=float, default=0.1,
                    help="recsys mode: fraction of ops that are pushes")
    ap.add_argument("--cache-rows", type=int, default=4096,
                    help="recsys mode: DiskRowStore hot-cache rows per "
                         "shard table")
    ap.add_argument("--vocab", type=int, default=256,
                    help="generation mode: vocab size the workload "
                         "samples prompt token ids from — must match "
                         "the served model when pointing --url at an "
                         "external server")
    args = ap.parse_args(argv)
    if args.sample is not None:
        try:
            t, k, p, s = args.sample.split(",")
            args.sample = {"temperature": float(t), "top_k": int(k),
                           "top_p": float(p), "seed": int(s)}
        except ValueError:
            ap.error(f"--sample wants T,K,P,SEED, got {args.sample!r}")
    if args.quant_gate:
        return quant_gate_main(args)
    if args.disagg:
        if args.smoke:
            # a dozen mixed-length streams at modest depth: enough that
            # both decode hosts serve imports concurrently, small
            # enough to stay sub-30s on CI; concurrency stays below
            # the prefill host's slot count so the disagg first leg is
            # never shed (handoffs>0 must hold deterministically)
            args.concurrency, args.requests = 3, 12
        return disagg_main(args)
    if args.recsys:
        if args.smoke:
            # small fixed load: ~20 batched ops x 64 keys keeps both
            # passes sub-10s on CI while the per-key baseline still
            # pays the per-request overhead the 2x verdict is about
            args.concurrency, args.batches, args.batch_keys = 8, 20, 64
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        return recsys_main(args)
    if args.generate:
        if args.smoke:
            # enough in-flight depth and enough requests that the full
            # occupancy window (not the ramp/drain tails) dominates the
            # measurement — the 2x verdict is about steady state. 64
            # requests keep each timed pass long enough that OS
            # scheduling noise on small CI hosts stays in the noise;
            # concurrency 2 above the default 8 slots keeps a small
            # standing queue so freed slots refill instantly instead of
            # idling through a client's turnaround gap (measured: the
            # margin over 2x roughly doubles), while staying below the
            # client-thread count where bench-side GIL contention in
            # this single-process harness throttles the scheduler
            args.concurrency, args.requests = 10, 64
        return generation_main(args)
    if args.smoke:
        args.concurrency, args.requests = 6, 10
        args.mode = "closed"
        # a wide coalescing window keeps the occupancy>1 assertion
        # honest on slow shared CI hosts where 2ms can serialize clients
        args.batch_timeout_ms = max(args.batch_timeout_ms, 50.0)

    srv = None
    engine = None
    url = args.url
    if url is None:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn
        from paddle_tpu import jit
        from paddle_tpu.inference.serving import (ServingEngine,
                                                  ServingHTTPServer)
        from paddle_tpu.static import InputSpec

        paddle.seed(0)
        model = nn.Sequential(nn.Linear(args.dim, 64), nn.GELU(),
                              nn.Linear(64, 8))
        model.eval()
        prefix = os.path.join("/tmp", "serve_bench_model", "m")
        jit.save(model, prefix,
                 input_spec=[InputSpec([None, args.dim], "float32")])
        engine = ServingEngine(prefix,
                               max_batch_size=args.max_batch_size,
                               batch_timeout_ms=args.batch_timeout_ms,
                               replicas=args.replicas)
        srv = ServingHTTPServer(engine).start()
        url = f"http://127.0.0.1:{srv.port}"
        print(f"# serve_bench: in-process server on {url} "
              f"(warmup {engine.warmup_report})", file=sys.stderr)

    mode = args.mode
    if args.ramp is not None:
        mode = "ramp"
        try:
            r0, r1 = (float(x) for x in args.ramp.split(":"))
        except ValueError:
            ap.error(f"--ramp wants R0:R1 rps, got {args.ramp!r}")
    if mode == "closed":
        wall, lat, errors = closed_loop(url, args.dim, args.concurrency,
                                        args.requests, args.rows)
        offered = None
        n = args.concurrency * args.requests
    elif mode == "ramp":
        wall, lat, errors = open_loop(url, args.dim,
                                      ramp_rate(r0, r1, args.duration),
                                      args.duration, args.rows)
        offered = [r0, r1]
        n = len(lat) + errors
    else:
        wall, lat, errors = open_loop(url, args.dim, args.rate,
                                      args.duration, args.rows)
        offered = args.rate
        n = len(lat) + errors

    if args.smoke and engine is not None and \
            engine.metrics.max_occupancy() <= 1:
        # one retry burst BEFORE the artifact is assembled: a fully
        # serialized first pass (cold code paths on a loaded host) must
        # not red an unrelated PR — and the saved BENCH line must
        # describe the load the verdict was judged on
        wall2, lat2, errors2 = closed_loop(url, args.dim,
                                           args.concurrency,
                                           args.requests, args.rows)
        wall, lat, errors = wall + wall2, sorted(lat + lat2), \
            errors + errors2
        n += args.concurrency * args.requests

    metrics_snapshot = None
    metrics_text = None
    if engine is not None:
        metrics_snapshot = engine.metrics.snapshot()
    else:
        # remote target: no snapshot API, attach the Prometheus text so
        # the artifact still carries occupancy/bucket evidence
        try:
            with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
                metrics_text = r.read().decode()
        except Exception:  # noqa: BLE001
            pass

    result = {
        "metric": "serving_throughput_rps",
        "value": round(len(lat) / wall, 2) if wall else 0.0,
        "unit": "req/s",
        "mode": mode,
        "requests": n,
        "completed": len(lat),
        "errors": errors,
        "wall_s": round(wall, 3),
        "offered_rps": offered,
        "concurrency": args.concurrency if mode == "closed" else None,
        "rows_per_request": args.rows,
        "latency_ms": {
            "p50": round(_percentile(lat, 0.50) * 1e3, 3),
            "p95": round(_percentile(lat, 0.95) * 1e3, 3),
            "p99": round(_percentile(lat, 0.99) * 1e3, 3),
        },
        "serving": metrics_snapshot,
    }
    if metrics_text is not None:
        result["metrics_text"] = metrics_text
    print(json.dumps(result))
    if args.save:
        with open(args.save, "w") as f:
            json.dump(result, f, indent=1)

    rc = 0
    if args.smoke:
        snap = metrics_snapshot or {}
        ok = (errors == 0 and len(lat) == n
              and snap.get("max_batch_occupancy", 0) > 1
              and snap.get("batches_total", 0) < n)
        if not ok:
            print(f"# serve_bench smoke FAILED: errors={errors} "
                  f"completed={len(lat)}/{n} occupancy="
                  f"{snap.get('max_batch_occupancy')} "
                  f"batches={snap.get('batches_total')}", file=sys.stderr)
            rc = 1
        else:
            print(f"# serve_bench smoke OK: {len(lat)} requests in "
                  f"{snap.get('batches_total')} batches (max occupancy "
                  f"{snap.get('max_batch_occupancy')})", file=sys.stderr)

    if srv is not None:
        srv.stop()
    return rc


if __name__ == "__main__":
    sys.exit(main())
