"""Quantized serving tier (quantization/kv + inference/serving/generate):
int8 KV-cache pool and weight-only int8 replicas — all on the CPU
backend.

Parity contract under quantization: the kv-only int8 engine's FIRST
emitted token is EXACT vs float (prefill attention runs on in-program
full-precision K/V; only the stored rows are quantized), full sequences
match within tolerance (exactly on these tiny presets), and everything
that was exact AMONG float paths stays exact AMONG quantized paths —
batched == sequential == streaming == HTTP, spec-on == spec-off (every
pass writes its new positions into the pool before it attends, so a
verify pass reads what plain decode would), and chaos
requeue replays reproduce the original tokens. Density is asserted on
allocator-real buffer nbytes, not arithmetic."""
import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _cpu_env import cpu_subprocess_env  # noqa: E402
from _spec_draft import noisy_draft  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.inference.serving import (GenerativeEngine,  # noqa: E402
                                          ServingHTTPServer)
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM  # noqa: E402
from paddle_tpu.quantization import kv as kvq  # noqa: E402
from paddle_tpu.testing import chaos  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SAMP = {"temperature": 0.8, "top_k": 50, "top_p": 0.9, "seed": 42}


@pytest.fixture(scope="module", autouse=True)
def _lockcheck_module():
    from paddle_tpu.testing import lockcheck, racecheck

    lockcheck.install()
    racecheck.install(ignore_site_parts=(os.sep + "tests" + os.sep,))
    try:
        yield
        lockcheck.assert_clean()
        racecheck.assert_clean()
    finally:
        racecheck.uninstall()
        lockcheck.uninstall()


@pytest.fixture(autouse=True)
def _chaos_reset():
    chaos.reset()
    yield
    chaos.reset()


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=64, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


@pytest.fixture(scope="module")
def draft_model(tiny_model):
    """A genuinely DIFFERENT draft that agrees sometimes: its proposals
    are accepted about half the time, so both the accept path and the
    reject fallback actually run."""
    return noisy_draft(tiny_model)


def make_engine(model, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("max_context", 64)
    kw.setdefault("max_new_tokens_cap", 16)
    return GenerativeEngine(model, **kw)


@pytest.fixture(scope="module")
def f32_engine(tiny_model):
    eng = make_engine(tiny_model)
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def int8_engine(tiny_model):
    eng = make_engine(tiny_model, kv_dtype="int8")
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def int8w_engine(tiny_model):
    eng = make_engine(tiny_model, kv_dtype="int8", quantize_weights=True)
    yield eng
    eng.shutdown()


def mixed_prompts(n, seed=1, vocab=256, lo=3, hi=30):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, size=int(l))
            for l in rng.randint(lo, hi, size=n)]


def shared_prefix_prompts(n, prefix_len=16, seed=2, vocab=256,
                          lo=3, hi=12):
    rng = np.random.RandomState(seed)
    head = rng.randint(0, vocab, size=prefix_len)
    return [np.concatenate([head, rng.randint(0, vocab, size=int(l))])
            for l in rng.randint(lo, hi, size=n)]


def match_frac(a, b):
    """Mean fraction of aligned token positions that agree."""
    per = [np.mean([x == y for x, y in zip(s, t)])
           for s, t in zip(a, b)]
    return float(np.mean(per))


# ===================================================================
# quantization/kv primitives
# ===================================================================
class TestKVPrimitives:
    def test_quantize_absmax_round_trip(self):
        from paddle_tpu.quantization import quantize_absmax

        rng = np.random.RandomState(0)
        w = rng.randn(4, 8, 8).astype(np.float32)
        q, s = quantize_absmax(w)
        assert q.dtype == np.int8 and np.isscalar(s)
        assert np.max(np.abs(q.astype(np.float32) * s - w)) <= s
        qa, sa = quantize_absmax(w, axis=(1, 2))
        assert sa.shape == (4, 1, 1)
        # per-slice scales bound the per-slice error tighter
        err = np.abs(qa.astype(np.float32) * sa - w)
        assert np.all(err.max(axis=(1, 2), keepdims=True) <= sa)

    def test_store_read_round_trip_error_bounded(self):
        import jax

        rng = np.random.RandomState(1)
        shape = (3, 2, 16, 4 * 8)                      # rows L cap H*Dh
        dev = jax.devices()[0]
        buf = kvq.alloc(shape, dev, "int8")
        ks = rng.randn(2, 16, 4, 8).astype(np.float32)
        buf = kvq.store_block(buf, np.int32(1), ks)
        for layer in range(2):
            got = np.asarray(kvq.read_layer(
                buf, np.asarray([1], np.int32), layer))[0]
            s = float(np.asarray(buf.scale)[1, layer])
            assert np.max(np.abs(got - ks[layer].reshape(16, 32))) <= s
            # untouched rows stay zero
            other = kvq.read_layer(buf, np.asarray([0], np.int32), layer)
            assert np.all(np.asarray(other) == 0.0)

    def test_write_then_read_is_the_quant_round_trip_bitwise(self):
        """THE spec-parity lemma: a position written into the pool
        (quantize with the row's scale) and read back (dequantize with
        it) is bitwise quant(x, s) * s, whichever program wrote it — a
        verify pass attending its own block reads what plain decode's
        next step would."""
        import jax
        import jax.numpy as jnp

        rng = np.random.RandomState(2)
        buf = kvq.alloc((3, 2, 8, 32), jax.devices()[0], "int8")
        s = np.abs(rng.randn(3, 2)).astype(np.float32) + 0.01
        buf = buf._replace(scale=jnp.asarray(s))
        x = rng.randn(2, 32).astype(np.float32) * 3
        wslot = np.asarray([2, 0], np.int32)
        wpos = np.asarray([5, 7], np.int32)
        buf = kvq.write_layer(buf, 1, wslot, wpos, jnp.asarray(x))
        back = np.asarray(kvq.read_layer(buf, wslot, 1))
        for i in range(2):
            sc = s[wslot[i], 1]
            want = (np.asarray(kvq.quant(jnp.asarray(x[i]),
                                         jnp.asarray(sc)))
                    .astype(np.int8).astype(np.float32) * sc)
            assert np.array_equal(back[i, wpos[i]], want)
        # the other layer and the other positions were not touched
        assert np.all(np.asarray(buf.data)[:, 0] == 0)
        assert np.count_nonzero(np.asarray(buf.data)[:, 1]) <= 2 * 32

    def test_zero_block_does_not_divide_by_zero(self):
        import jax

        dev = jax.devices()[0]
        buf = kvq.alloc((2, 1, 8, 2 * 4), dev, "int8")
        buf = kvq.store_block(buf, np.int32(0),
                              np.zeros((1, 8, 2, 4), np.float32))
        rows = kvq.read_layer(buf, np.asarray([0], np.int32), 0)
        assert np.all(np.isfinite(np.asarray(rows)))
        assert np.all(np.asarray(buf.scale) > 0.0)

    def test_dequant_params_identity_for_float_dict(self):
        p = {"wte": np.ones((4, 2), np.float32)}
        assert kvq.dequant_params(p) is p

    def test_quantize_stacked_params_layout(self):
        rng = np.random.RandomState(3)
        params = {
            "wte": rng.randn(16, 8).astype(np.float32),
            "qkv_w": rng.randn(2, 8, 24).astype(np.float32),
            "lm_head": rng.randn(8, 16).astype(np.float32),
            "qkv_b": rng.randn(2, 24).astype(np.float32),
        }
        q = kvq.quantize_stacked_params(params)
        assert "qkv_w" not in q and "lm_head" not in q
        assert np.asarray(q["qkv_w__q"]).dtype == np.int8
        assert np.asarray(q["qkv_w__s"]).shape == (2, 1, 1)  # per layer
        assert np.asarray(q["lm_head__s"]).shape == ()       # per tensor
        assert "wte" in q and "qkv_b" in q                   # untouched
        back = kvq.dequant_params(q)
        assert not any(k.endswith(("__q", "__s")) for k in back)
        w = np.asarray(back["qkv_w"])
        s = np.asarray(q["qkv_w__s"])
        assert np.max(np.abs(w - params["qkv_w"])) <= float(s.max())


# ===================================================================
# density: asserted on real allocated buffers, not arithmetic
# ===================================================================
class TestDensity:
    def test_int8_pool_halves_buffer_nbytes(self, f32_engine,
                                            int8_engine):
        import jax

        dev = jax.devices()[0]
        for eng_a, eng_b in ((f32_engine, int8_engine),):
            for cap in eng_a._caps:
                a = eng_a._alloc_class(cap, dev)
                b = eng_b._alloc_class(cap, dev)
                assert b.buf_k.nbytes * 2 <= a.buf_k.nbytes
                assert b.buf_v.nbytes * 2 <= a.buf_v.nbytes
        # the billing helper matches the allocator to the byte
        total = 0
        for cap in int8_engine._caps:
            cs = int8_engine._alloc_class(cap, dev)
            total += cs.buf_k.nbytes + cs.buf_v.nbytes
        assert total == int8_engine.kv_pool_bytes()
        assert int8_engine.kv_pool_bytes() * 2 <= \
            f32_engine.kv_pool_bytes()

    def test_double_slots_fit_f32_budget(self, tiny_model, f32_engine):
        eng = make_engine(tiny_model, slots=8, kv_dtype="int8")
        try:
            assert eng.kv_pool_bytes() <= f32_engine.kv_pool_bytes()
        finally:
            eng.shutdown()

    def test_pool_bytes_on_metrics_bus(self, int8_engine):
        snap = int8_engine.metrics.snapshot()
        assert snap["kv_pool"]["pool_bytes"] == \
            int8_engine.kv_pool_bytes()
        assert snap["quant_kv_enabled"] == 1
        assert snap["quant_weights_enabled"] == 0
        text = int8_engine.metrics.prometheus_text()
        assert "paddle_generate_kv_pool_bytes" in text
        assert "paddle_generate_quant_kv_enabled 1" in text
        assert "paddle_generate_quant_weights_enabled 0" in text


# ===================================================================
# greedy parity vs float, on every path
# ===================================================================
class TestGreedyParity:
    def test_kv_int8_greedy_matches_float(self, f32_engine,
                                          int8_engine):
        prompts = mixed_prompts(6, seed=5)
        ref = [f32_engine.generate(p, 12, timeout=60)["tokens"]
               for p in prompts]
        out = [int8_engine.generate(p, 12, timeout=60)["tokens"]
               for p in prompts]
        # first token exact: prefill attends in-program f32 K/V
        assert all(a[0] == b[0] for a, b in zip(ref, out))
        # full sequences within tolerance (exact on this tiny preset)
        assert match_frac(ref, out) >= 0.9

    def test_weight_int8_greedy_within_tolerance(self, f32_engine,
                                                 int8w_engine):
        prompts = mixed_prompts(6, seed=5)
        ref = [f32_engine.generate(p, 12, timeout=60)["tokens"]
               for p in prompts]
        out = [int8w_engine.generate(p, 12, timeout=60)["tokens"]
               for p in prompts]
        assert all(a[0] == b[0] for a, b in zip(ref, out))
        assert match_frac(ref, out) >= 0.6

    def test_weight_int8_untied_head_within_tolerance(self):
        """An UNTIED head is one of the int8 weights (`lm_head__q`): every
        body — the prefill and the pool pass's callers — must put its
        logits through the dequantized head, not fall back on `wte`."""
        paddle.seed(3)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            max_seq_len=64, dropout=0.0, tie_embeddings=False))
        model.eval()
        f32 = make_engine(model)
        int8w = make_engine(model, quantize_weights=True)
        try:
            prompts = mixed_prompts(6, seed=5)
            ref = [f32.generate(p, 12, timeout=60)["tokens"]
                   for p in prompts]
            out = [int8w.generate(p, 12, timeout=60)["tokens"]
                   for p in prompts]
        finally:
            f32.shutdown()
            int8w.shutdown()
        assert all(a[0] == b[0] for a, b in zip(ref, out))
        assert match_frac(ref, out) >= 0.6

    def test_all_paths_token_identical_among_quantized(self,
                                                       int8w_engine):
        """Whatever the quantized outputs ARE, every serving path must
        agree on them exactly: batched, sequential, streaming, HTTP."""
        eng = int8w_engine
        srv = ServingHTTPServer(None, generator=eng).start()
        try:
            prompts = mixed_prompts(4, seed=11)
            seq = [eng.generate(p, 8, timeout=60, **SAMP)["tokens"]
                   for p in prompts]
            handles = [eng.submit(p, 8, **SAMP) for p in prompts]
            assert [h.result(60)["tokens"] for h in handles] == seq
            assert [list(eng.stream(p, 8, **SAMP))
                    for p in prompts] == seq
            url = f"http://127.0.0.1:{srv.port}/generate"
            http = []
            for p in prompts:
                body = json.dumps(dict(
                    SAMP, input_ids=[int(x) for x in p],
                    max_new_tokens=8)).encode()
                req = urllib.request.Request(
                    url, data=body,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=60) as r:
                    http.append(json.loads(r.read())["tokens"])
            assert http == seq
        finally:
            srv.stop()


# ===================================================================
# speculative decode + chaos under the int8 pool
# ===================================================================
class TestSpecAndChaos:
    def test_spec_on_bitwise_spec_off_int8(self, tiny_model,
                                           draft_model, int8_engine):
        spec = make_engine(tiny_model, kv_dtype="int8",
                           draft=draft_model, spec_tokens=3)
        try:
            prompts = mixed_prompts(6, seed=5)
            ref_g = [int8_engine.generate(p, 12, timeout=60)["tokens"]
                     for p in prompts]
            out_g = [spec.generate(p, 12, timeout=60)["tokens"]
                     for p in prompts]
            assert out_g == ref_g
            ref_s = [int8_engine.generate(p, 10, timeout=60,
                                          **SAMP)["tokens"]
                     for p in prompts]
            out_s = [spec.generate(p, 10, timeout=60, **SAMP)["tokens"]
                     for p in prompts]
            assert out_s == ref_s
            snap = spec.metrics.snapshot()
            assert snap["spec_steps_total"] > 0
            assert snap["spec_accept_rate"] > 0.0
        finally:
            spec.shutdown()

    def test_chaos_requeue_replays_with_int8_pool(self, tiny_model):
        eng = make_engine(tiny_model, slots=2, kv_dtype="int8")
        try:
            prompts = mixed_prompts(3, seed=8)
            ref = [eng.generate(p, 9, timeout=60, **SAMP)["tokens"]
                   for p in prompts[:2]]
            ref.append(eng.generate(prompts[2], 9, timeout=60)["tokens"])
            chaos.add_rule("serving.decode_step", "raise_n", 1)
            handles = [eng.submit(p, 9, **SAMP) for p in prompts[:2]]
            handles.append(eng.submit(prompts[2], 9))
            streams = [list(h) for h in handles]
            assert streams == ref
            assert eng.metrics.requeues_total >= 1
            assert eng.metrics.failed_total == 0
        finally:
            chaos.reset()
            eng.shutdown()


# ===================================================================
# prefix cache over quantized rows
# ===================================================================
class TestPrefixCacheInt8:
    def test_hit_parity_within_tolerance(self, tiny_model):
        """A cache hit extends a quantized row with the CACHED prefix's
        scale (clip semantics), while a cold engine re-prefills and
        re-scales — outputs agree within tolerance, and the cache-on
        engine stays exactly self-consistent across its own paths."""
        pc = make_engine(tiny_model, kv_dtype="int8",
                         prefix_cache_slots=2)
        cold = make_engine(tiny_model, kv_dtype="int8")
        try:
            prompts = shared_prefix_prompts(6)
            ref = [cold.generate(p, 8, timeout=60)["tokens"]
                   for p in prompts]
            out = [pc.generate(p, 8, timeout=60)["tokens"]
                   for p in prompts]
            assert pc.metrics.snapshot()["prefix_hits_total"] >= 1
            assert match_frac(ref, out) >= 0.7
            s1 = [pc.generate(p, 8, timeout=60, **SAMP)["tokens"]
                  for p in prompts]
            s2 = [list(pc.stream(p, 8, **SAMP)) for p in prompts]
            assert s1 == s2
        finally:
            pc.shutdown()
            cold.shutdown()


# ===================================================================
# warm-restart: persistent compile cache + bitwise outputs, int8 pool
# ===================================================================
class TestWarmRestartInt8:
    def test_int8_restart_zero_persistent_misses(self, tmp_path):
        """The compile-discipline acceptance for the kv_dtype program
        family: a warm FLAGS_compile_cache_dir restart serves a sampled
        + speculative + prefix-cached workload on the int8 pool with
        persistent_misses == 0 and outputs bitwise identical across
        the restart."""
        env = cpu_subprocess_env(
            FLAGS_compile_cache_dir=str(tmp_path / "cc"))

        def run():
            out = subprocess.run(
                [sys.executable, "-c", _QUANT_CHILD],
                capture_output=True, text=True, timeout=600, cwd=REPO,
                env=env)
            assert out.returncode == 0, out.stdout + out.stderr
            return json.loads(out.stdout.strip().splitlines()[-1])

        r1 = run()
        assert r1["warm"]["kv_dtype"] == "int8"
        assert r1["warm"]["quantize_weights"] is True
        assert r1["warm"]["persistent_cache_enabled"]
        assert r1["warm"]["persistent_misses"] > 0   # cold dir compiles
        assert r1["work_misses"] == 0                # workload: nothing
        r2 = run()
        assert r2["warm"]["persistent_misses"] == 0, r2["warm"]
        assert r2["warm"]["persistent_hits"] > 0
        assert r2["work_misses"] == 0
        assert r1["outs"] == r2["outs"]              # bitwise restart


_QUANT_CHILD = """
import json
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.core import compile_cache as cc
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.inference.serving import GenerativeEngine

paddle.seed(0)
cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                num_heads=4, max_seq_len=64, dropout=0.0)
model = GPTForCausalLM(cfg)
model.eval()
paddle.seed(1)
draft = GPTForCausalLM(GPTConfig(vocab_size=256, hidden_size=32,
                                 num_layers=1, num_heads=2,
                                 max_seq_len=64, dropout=0.0))
draft.eval()
eng = GenerativeEngine(model, slots=2, max_context=64,
                       max_new_tokens_cap=8, draft=draft, spec_tokens=3,
                       prefix_cache_slots=2, kv_dtype="int8",
                       quantize_weights=True)
rng = np.random.RandomState(3)
head = rng.randint(0, 256, size=16)
samp = dict(temperature=0.8, top_k=50, top_p=0.9, seed=42)
with cc.measure() as work:
    hs = []
    for i, l in enumerate(rng.randint(2, 10, size=6)):
        p = np.concatenate([head, rng.randint(0, 256, size=int(l))])
        hs.append(eng.submit(p, 6, **(samp if i % 2 else {})))
    outs = [h.result(120)["tokens"] for h in hs]
eng.shutdown()
print(json.dumps({"warm": eng.warmup_report,
                  "work_misses": work["misses"], "outs": outs}))
"""


# ===================================================================
# engine surface / validation
# ===================================================================
class TestSurface:
    def test_bad_kv_dtype_rejected(self, tiny_model):
        with pytest.raises(ValueError, match="kv_dtype"):
            make_engine(tiny_model, kv_dtype="int4")

    def test_reports_carry_quant_fields(self, int8w_engine):
        assert int8w_engine.warmup_report["kv_dtype"] == "int8"
        assert int8w_engine.warmup_report["quantize_weights"] is True
        assert int8w_engine.warmup_report["kv_pool_bytes"] > 0
        h = int8w_engine.health()
        assert h["kv_dtype"] == "int8" and h["quantize_weights"] is True
        rep = int8w_engine.program_report()
        assert rep["kv_dtype"] == "int8"
        assert any("kv=int8" in p for p in rep["programs"])

    def test_f32_engine_unaffected(self, f32_engine):
        snap = f32_engine.metrics.snapshot()
        assert snap["quant_kv_enabled"] == 0
        rep = f32_engine.program_report()
        assert not any("kv=" in p for p in rep["programs"])


# ===================================================================
# satellite: PTQ zero-absmax fallback (quantization/__init__)
# ===================================================================
class TestPTQZeroAbsmaxFallback:
    def test_zero_calibration_falls_back_to_dynamic(self):
        import paddle_tpu.nn as nn
        from paddle_tpu import quantization as q

        paddle.seed(0)
        model = nn.Sequential(nn.Linear(8, 4))
        ptq = q.PTQ()
        ptq.quantize(model)
        # calibrate with ONLY zeros: the observer's absmax stays 0.0
        model(paddle.to_tensor(np.zeros((2, 8), np.float32)))
        q._WARNED_ZERO_ABSMAX = False
        with pytest.warns(RuntimeWarning, match="dynamic"):
            ptq.convert(model)
        lin = model[0]
        assert isinstance(lin, q.QuantizedLinear)
        # dynamic fallback: no baked activation scale, and a real
        # activation is NOT saturated — output tracks the float layer
        assert lin._act_scale is None
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(2, 8).astype(np.float32))
        out = np.asarray(lin(x).numpy())
        assert np.all(np.isfinite(out)) and np.any(out != 0.0)

    def test_nonzero_calibration_still_bakes_static_scale(self):
        import paddle_tpu.nn as nn
        from paddle_tpu import quantization as q

        paddle.seed(0)
        model = nn.Sequential(nn.Linear(8, 4))
        ptq = q.PTQ()
        ptq.quantize(model)
        model(paddle.to_tensor(
            np.random.RandomState(1).randn(4, 8).astype(np.float32)))
        ptq.convert(model)
        assert model[0]._act_scale is not None
