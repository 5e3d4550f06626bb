"""Generation beyond greedy (inference/serving/generate): seeded
sampling, speculative multi-token decode and prefix-cache reuse — all
on the CPU backend.

Determinism notes: seeded sampling is DETERMINISTIC — the per-row PRNG
key is split once per emitted token inside the compiled programs, so
the same (prompt, sampling params, seed) yields token-identical output
on every path (batched, sequential, streaming, HTTP) and across
restarts. Speculative decode consumes the key chain at the same
one-split-per-token rate, so spec-on output is bitwise-equal to
spec-off output under greedy AND seeded sampling. The tests assert
exact equality throughout, never closeness.
"""
import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _cpu_env import cpu_subprocess_env  # noqa: E402
from _spec_draft import noisy_draft  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.core import compile_cache as cc  # noqa: E402
from paddle_tpu.inference.serving import (GenerativeEngine,  # noqa: E402
                                          ServingError, ServingHTTPServer)
from paddle_tpu.inference.serving.lifecycle import \
    validate_sampling  # noqa: E402
from paddle_tpu.models.gpt import (PRESETS, GPTConfig,  # noqa: E402
                                   GPTForCausalLM)
from paddle_tpu.testing import chaos  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one sampling config used across the parity matrix: hot enough that a
# different seed visibly diverges, filtered enough to exercise both
# top-k and the top-p nucleus cut
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
def plain_engine(tiny_model):
    eng = make_engine(tiny_model)
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def spec_engine(tiny_model, draft_model):
    eng = make_engine(tiny_model, draft=draft_model, spec_tokens=3)
    yield eng
    eng.shutdown()


def mixed_prompts(n, seed=1, vocab=256, lo=3, hi=30):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, size=int(l))
            for l in rng.randint(lo, hi, size=n)]


def shared_prefix_prompts(n, prefix_len=16, seed=2, vocab=256,
                          lo=3, hi=12):
    """Prompts sharing the same `prefix_len`-token head (the shared
    system prompt), each with a distinct random tail."""
    rng = np.random.RandomState(seed)
    head = rng.randint(0, vocab, size=prefix_len)
    return [np.concatenate([head, rng.randint(0, vocab, size=int(l))])
            for l in rng.randint(lo, hi, size=n)]


class TestValidateSampling:
    def test_defaults_and_passthrough(self):
        assert validate_sampling({}) == {
            "temperature": None, "top_k": None, "top_p": None,
            "seed": None}
        out = validate_sampling({"temperature": 0.8, "top_k": 50,
                                 "top_p": 0.9, "seed": 42,
                                 "input_ids": [1, 2]})
        assert out == {"temperature": 0.8, "top_k": 50, "top_p": 0.9,
                       "seed": 42}
        # boundary values are legal
        validate_sampling({"temperature": 0.0, "top_k": 1,
                           "top_p": 1.0, "seed": 0})
        validate_sampling({"seed": -1})          # any int seeds the key

    @pytest.mark.parametrize("bad", [
        {"temperature": -0.1}, {"temperature": "hot"},
        {"temperature": True},
        {"top_k": 0}, {"top_k": -3}, {"top_k": 1.5}, {"top_k": True},
        {"top_p": 0.0}, {"top_p": 1.2}, {"top_p": -0.5},
        {"top_p": "all"}, {"top_p": False},
        {"seed": 1.5}, {"seed": "abc"}, {"seed": True},
    ])
    def test_rejects_are_400(self, bad):
        with pytest.raises(ServingError) as e:
            validate_sampling(bad)
        assert e.value.status == 400

    def test_engine_submit_rejects_before_enqueue(self, plain_engine):
        eng = plain_engine
        before = eng.metrics.snapshot()["queue_depth"]
        with pytest.raises(ServingError) as e:
            eng.submit([1, 2, 3], 4, temperature=-1.0)
        assert e.value.status == 400
        with pytest.raises(ServingError) as e:
            eng.submit([1, 2, 3], 4, top_k=0)
        assert e.value.status == 400
        # nothing was enqueued for the rejected requests
        assert eng.metrics.snapshot()["queue_depth"] == before


class TestSeededSamplingParity:
    def test_four_paths_token_identical(self, tiny_model):
        """THE sampling acceptance: the same (prompt, params, seed)
        yields identical tokens on the sequential, batched, streaming
        and HTTP paths — the key chain advances once per emitted token
        regardless of how requests are scheduled. (Own engine: the
        HTTP server's stop() shuts its generator down.)"""
        eng = make_engine(tiny_model)
        srv = ServingHTTPServer(None, generator=eng).start()
        try:
            prompts = mixed_prompts(4)
            seq = [eng.generate(p, 8, timeout=60, **SAMP)["tokens"]
                   for p in prompts]
            handles = [eng.submit(p, 8, **SAMP) for p in prompts]
            batched = [h.result(60)["tokens"] for h in handles]
            assert batched == seq
            streamed = [list(eng.stream(p, 8, **SAMP)) for p in prompts]
            assert streamed == seq
            url = f"http://127.0.0.1:{srv.port}/generate"
            http = []
            for p in prompts:
                body = json.dumps(dict(SAMP, input_ids=[int(x) for x in p],
                                       max_new_tokens=8)).encode()
                req = urllib.request.Request(
                    url, data=body,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=60) as r:
                    http.append(json.loads(r.read())["tokens"])
            assert http == seq
        finally:
            srv.stop(drain=False)

    def test_seed_changes_output_temperature_zero_is_greedy(
            self, plain_engine):
        eng = plain_engine
        prompt = mixed_prompts(1, seed=3)[0]
        a = eng.generate(prompt, 12, timeout=60, **SAMP)["tokens"]
        b = eng.generate(prompt, 12, timeout=60,
                         **dict(SAMP, seed=43))["tokens"]
        assert a != b                     # a different seed diverges
        greedy = eng.generate(prompt, 12, timeout=60)["tokens"]
        # temperature 0 forces argmax no matter the other knobs/seed
        frozen = eng.generate(prompt, 12, timeout=60, temperature=0.0,
                              top_k=5, top_p=0.5, seed=7)["tokens"]
        assert frozen == greedy

    def test_sampling_stays_in_top_k(self, plain_engine):
        """top_k=1 degenerates to greedy even at high temperature —
        the cheapest end-to-end proof the filter is applied."""
        eng = plain_engine
        prompt = mixed_prompts(1, seed=4)[0]
        greedy = eng.generate(prompt, 10, timeout=60)["tokens"]
        k1 = eng.generate(prompt, 10, timeout=60, temperature=5.0,
                          top_k=1, seed=9)["tokens"]
        assert k1 == greedy


class TestDeviceHeldKeys:
    """The key chain runs through keys the device holds from step to
    step (PR 32): the host reads each step's keys one step late and
    stages them again only when the row set changes."""

    @pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
    def test_staggered_mixed_batch_matches_one_at_a_time(self, tiny_model,
                                                         kv_dtype):
        """Sampled rows (fixed seeds) mixed with greedy ones, eight
        requests through three slots with answers of 3 to 14 tokens —
        admitted and finishing on different steps, so rows are restaged
        around every survivor — give, each, the tokens it gives alone."""
        eng = make_engine(tiny_model, slots=3, kv_dtype=kv_dtype)
        try:
            prompts = mixed_prompts(8, seed=31)
            asks = [3, 14, 7, 9, 4, 12, 5, 10]
            samps = [dict(SAMP, seed=100 + i) if i % 3 else {}
                     for i in range(8)]
            alone = [eng.generate(p, n, timeout=60, **sp)["tokens"]
                     for p, n, sp in zip(prompts, asks, samps)]
            handles = [eng.submit(p, n, **sp)
                       for p, n, sp in zip(prompts, asks, samps)]
            assert [h.result(60)["tokens"] for h in handles] == alone
            snap = eng.metrics.snapshot()
            assert snap["max_slot_occupancy"] == 3
            assert snap["steps_ahead_total"] > 0
            # every emitted token is a prefill's or a real row's of a step
            assert snap["tokens_out_total"] == \
                snap["prefills_total"] + snap["step_rows_total"]
        finally:
            eng.shutdown()

    def test_sampled_row_migrated_mid_stream_continues_identically(
            self, tiny_model, plain_engine):
        """A sampled stream taken out of the loop mid-decode (a migrating
        drain) with a step launched ahead of what its client has seen:
        the export carries the state of the last step READ and settled,
        and the importer continues the same chain — head + tail is the
        uninterrupted sequence."""
        import threading

        from paddle_tpu.inference.fabric import handoff

        prompt = mixed_prompts(1, seed=33)[0]
        want = plain_engine.generate(prompt, 14, timeout=60,
                                     **SAMP)["tokens"]
        eng = make_engine(tiny_model, slots=2)
        try:
            chaos.add_rule("serving.decode_step", "delay", 0.02)
            h = eng.submit(prompt, 14, **SAMP)
            head, payload, dt = [], [], None
            for kind, val in h.events():
                if kind == "tok":
                    head.append(int(val))
                    if len(head) == 4:
                        assert eng.metrics.steps_ahead_total >= 1
                        dt = threading.Thread(
                            target=eng.shutdown, name="test-migrate",
                            kwargs={"drain": True, "migrate": True})
                        dt.start()
                elif kind == "handoff":
                    payload.append(val)
            dt.join(60)
            chaos.reset()
            assert payload and payload[0]["streamed"] == len(head)
            raw = handoff.from_b64(payload[0]["handoff"])
            meta, arrays = handoff.decode(raw)
            # the state that left is the state the client has seen
            assert meta["tokens"] == head == want[:len(head)]
            assert meta["length"] == len(prompt) + len(head) - 1
            tail = list(plain_engine.import_handoff(raw))
            assert head + tail == want, (head, tail, want)
        finally:
            chaos.reset()
            eng.shutdown(drain=False)


class TestSpeculative:
    def test_greedy_bitwise_equal_with_spec_on(self, plain_engine,
                                               spec_engine):
        """THE speculative acceptance: with a different-weight draft,
        greedy output is BITWISE identical to the non-speculative
        engine — rejected proposals fall back to the target's own
        token, so speculation is invisible in the tokens."""
        prompts = mixed_prompts(6, seed=5)
        ref = [plain_engine.generate(p, 12, timeout=60)["tokens"]
               for p in prompts]
        seq = [spec_engine.generate(p, 12, timeout=60)["tokens"]
               for p in prompts]
        assert seq == ref
        handles = [spec_engine.submit(p, 12) for p in prompts]
        assert [h.result(60)["tokens"] for h in handles] == ref
        snap = spec_engine.metrics.snapshot()
        assert snap["spec_steps_total"] > 0
        assert snap["spec_proposed_total"] > 0
        # a different-weight draft must neither always agree nor never
        assert 0.0 < snap["spec_accept_rate"] < 1.0

    def test_sampling_bitwise_equal_with_spec_on(self, plain_engine,
                                                 spec_engine):
        """Seeded sampling through the verify path: the key chain
        advances once per emitted token whether the token came from an
        accepted proposal or the rejection fallback, so spec-on
        sampled output equals spec-off sampled output."""
        prompts = mixed_prompts(4, seed=6)
        ref = [plain_engine.generate(p, 10, timeout=60, **SAMP)["tokens"]
               for p in prompts]
        out = [spec_engine.generate(p, 10, timeout=60, **SAMP)["tokens"]
               for p in prompts]
        assert out == ref

    def test_self_draft_accepts_everything(self, tiny_model):
        """Draft == target: every greedy proposal must verify (the
        accept rule's sanity anchor) and each burst emits k tokens."""
        eng = make_engine(tiny_model, slots=2, draft=tiny_model,
                          spec_tokens=4)
        try:
            out = eng.generate(mixed_prompts(1, seed=7)[0], 12,
                               timeout=60)
            assert len(out["tokens"]) == 12
            snap = eng.metrics.snapshot()
            assert snap["spec_accept_rate"] == 1.0
            # 12 tokens in ceil(12/4)=3 bursts, not 12 decode steps
            assert snap["spec_steps_total"] == 3
        finally:
            eng.shutdown()

    def test_draft_contract_validation(self, tiny_model):
        paddle.seed(2)
        wrong_vocab = GPTForCausalLM(GPTConfig(
            vocab_size=128, hidden_size=32, num_layers=1, num_heads=2,
            max_seq_len=64, dropout=0.0))
        wrong_vocab.eval()
        with pytest.raises(ValueError, match="vocab"):
            make_engine(tiny_model, draft=wrong_vocab)
        paddle.seed(2)
        short_ctx = GPTForCausalLM(GPTConfig(
            vocab_size=256, hidden_size=32, num_layers=1, num_heads=2,
            max_seq_len=32, dropout=0.0))
        short_ctx.eval()
        with pytest.raises(ValueError, match="max_seq_len"):
            make_engine(tiny_model, draft=short_ctx)
        with pytest.raises(ValueError, match="spec_tokens"):
            make_engine(tiny_model, draft=tiny_model, spec_tokens=1)

    def test_chaos_raise_mid_burst_requeues_without_duplicates(
            self, tiny_model, draft_model):
        """A raise mid-speculative-burst follows the requeue ladder:
        rows re-prefill WITH their replayed key chain and regenerate
        the same tokens; tokens streamed before the fault are not
        re-emitted. Greedy and seeded-sampled rows ride the same
        incident."""
        eng = make_engine(tiny_model, draft=draft_model, spec_tokens=3)
        try:
            prompts = mixed_prompts(3, seed=8)
            ref = [eng.generate(p, 9, timeout=60, **SAMP)["tokens"]
                   for p in prompts[:2]]
            ref.append(eng.generate(prompts[2], 9,
                                    timeout=60)["tokens"])
            # second decode burst raises: the first burst's tokens are
            # already on the streams when the fault lands (one fault —
            # two consecutive faults on the same in-flight request is
            # the engine's deliberate hard-fail, covered elsewhere)
            chaos.add_rule("serving.decode_step", "raise_n", 1)
            handles = [eng.submit(p, 9, **SAMP) for p in prompts[:2]]
            handles.append(eng.submit(prompts[2], 9))
            streams = [list(h) for h in handles]
            assert streams == ref              # no dups, no holes
            assert eng.metrics.requeues_total >= 1
            assert eng.metrics.failed_total == 0
        finally:
            chaos.reset()
            eng.shutdown()


class TestPrefixCache:
    def test_hit_parity_and_metrics(self, tiny_model, plain_engine):
        """Prompts sharing a 16-token head: the first admits, the rest
        hit and prefill only their tail — outputs bitwise-equal to the
        cache-less engine, under greedy AND seeded sampling."""
        eng = make_engine(tiny_model, prefix_cache_slots=2)
        try:
            prompts = shared_prefix_prompts(5)
            ref = [plain_engine.generate(p, 8, timeout=60)["tokens"]
                   for p in prompts]
            out = [eng.generate(p, 8, timeout=60)["tokens"]
                   for p in prompts]
            assert out == ref
            snap = eng.metrics.snapshot()
            assert snap["prefix_misses_total"] >= 1
            assert snap["prefix_hits_total"] >= 4
            assert snap["prefix_tokens_reused_total"] >= 4 * 16
            assert snap["prefix_hit_rate"] > 0.5
            sref = [plain_engine.generate(p, 8, timeout=60,
                                          **SAMP)["tokens"]
                    for p in prompts]
            sout = [eng.generate(p, 8, timeout=60, **SAMP)["tokens"]
                    for p in prompts]
            assert sout == sref
        finally:
            eng.shutdown()

    def test_lru_eviction_bounded(self, tiny_model):
        """More distinct prefixes than cache rows: the LRU evicts, the
        eviction counter moves, and every output stays correct."""
        eng = make_engine(tiny_model, prefix_cache_slots=1)
        try:
            groups = [shared_prefix_prompts(2, seed=s) for s in (3, 4)]
            ref = {}
            for g in groups:
                for i, p in enumerate(g):
                    ref[id(p)] = eng.generate(p, 6,
                                              timeout=60)["tokens"]
            # alternate prefixes: each group's head evicts the other's
            for _ in range(2):
                for g in groups:
                    for p in g:
                        assert eng.generate(p, 6, timeout=60)["tokens"] \
                            == ref[id(p)]
            snap = eng.metrics.snapshot()
            assert snap["prefix_evictions_total"] >= 1
            assert snap["kv_pool"]["slots_used"] == 0
        finally:
            eng.shutdown()

    def test_batched_prefix_workload_matches_sequential(self,
                                                        tiny_model):
        eng = make_engine(tiny_model, prefix_cache_slots=2)
        try:
            prompts = shared_prefix_prompts(6, seed=5)
            seq = [eng.generate(p, 8, timeout=60)["tokens"]
                   for p in prompts]
            handles = [eng.submit(p, 8) for p in prompts]
            assert [h.result(60)["tokens"] for h in handles] == seq
        finally:
            eng.shutdown()


class TestHTTPAndFleetValidation:
    def test_http_generate_400_before_enqueue(self, tiny_model):
        eng = make_engine(tiny_model)
        srv = ServingHTTPServer(None, generator=eng).start()
        try:
            sub = eng.metrics.snapshot()["requests_total"]
            for bad in ({"temperature": -1.0}, {"top_k": 0},
                        {"top_p": 2.0}, {"seed": "abc"}):
                body = json.dumps(dict(bad, input_ids=[1, 2, 3],
                                       max_new_tokens=4)).encode()
                req = urllib.request.Request(
                    f"http://127.0.0.1:{srv.port}/generate", data=body,
                    headers={"Content-Type": "application/json"})
                with pytest.raises(urllib.error.HTTPError) as e:
                    urllib.request.urlopen(req, timeout=60)
                assert e.value.code == 400, bad
            # the rejects happened before any request touched the queue
            assert eng.metrics.snapshot()["requests_total"] == sub
        finally:
            srv.stop(drain=False)

    def test_fleet_client_rejects_without_network(self):
        """The client-side mirror: a malformed request never leaves
        the process — the (unreachable) door is never contacted, so no
        HopError and no retry storm."""
        from paddle_tpu.inference.fabric import FleetClient

        fc = FleetClient(["127.0.0.1:9"], timeout_s=0.2)
        status, body = fc.generate({"input_ids": [1, 2],
                                    "temperature": -0.5})
        assert status == 400 and "temperature" in body["error"]
        lines = list(fc.stream_generate({"input_ids": [1, 2],
                                         "top_p": 0.0}))
        assert len(lines) == 1
        assert lines[0]["status"] == 400
        assert fc.counters_snapshot()["door_retries"] == 0


class TestDraftPresetAndCLI:
    def test_tiny_draft_preset_pairs_with_gpt3_tiny(self):
        d, t = PRESETS["tiny-draft"], PRESETS["gpt3-tiny"]
        assert d.vocab_size == t.vocab_size
        assert d.max_seq_len >= t.max_seq_len
        from paddle_tpu.inference.serving.generate import stack_gpt_params

        paddle.seed(0)
        model = GPTForCausalLM(d)
        model.eval()
        params, cfg = stack_gpt_params(model)
        assert cfg.num_layers == 1 and cfg.vocab_size == 1024

    def test_preset_pair_generates(self):
        """`--generate gpt3-tiny --draft tiny-draft` wiring at the
        engine layer: the preset pair builds a speculative engine whose
        greedy output matches the target model's own reference loop."""
        paddle.seed(0)
        target = GPTForCausalLM(PRESETS["gpt3-tiny"])
        target.eval()
        paddle.seed(0)
        draft = GPTForCausalLM(PRESETS["tiny-draft"])
        draft.eval()
        eng = GenerativeEngine(target, slots=2, max_context=32,
                               max_new_tokens_cap=8, draft=draft,
                               spec_tokens=3)
        try:
            prompt = mixed_prompts(1, seed=9, vocab=1024, lo=4,
                                   hi=10)[0]
            out = eng.generate(prompt, 6, timeout=120)["tokens"]
            ids = paddle.to_tensor(
                np.asarray(prompt)[None].astype("int64"))
            ref = target.generate(ids, max_new_tokens=6)
            assert out == list(np.asarray(ref.numpy())[0, len(prompt):])
            assert eng.metrics.snapshot()["spec_steps_total"] > 0
        finally:
            eng.shutdown()

    def test_serve_cli_rejects_unknown_draft(self):
        from paddle_tpu.inference.serve import main as serve_main

        with pytest.raises(SystemExit):
            serve_main(["--generate", "gpt3-tiny", "--draft", "nope",
                        "--http", "0"])


class TestWarmRestart:
    def test_beyond_greedy_restart_zero_persistent_misses(self,
                                                          tmp_path):
        """THE compile-discipline acceptance for the new program
        families (decode-with-sampling, dprefill/dpropose/verify,
        extend, pcopy): a warm restart serves a sampled + speculative +
        prefix-cached workload with persistent_misses == 0, outputs
        bitwise identical across the restart."""
        env = cpu_subprocess_env(
            FLAGS_compile_cache_dir=str(tmp_path / "cc"))

        def run():
            out = subprocess.run(
                [sys.executable, "-c", _BEYOND_CHILD],
                capture_output=True, text=True, timeout=600, cwd=REPO,
                env=env)
            assert out.returncode == 0, out.stdout + out.stderr
            return json.loads(out.stdout.strip().splitlines()[-1])

        r1 = run()
        assert r1["warm"]["persistent_cache_enabled"]
        assert r1["warm"]["persistent_misses"] > 0   # cold dir compiles
        assert r1["work_misses"] == 0                # workload: nothing
        r2 = run()
        assert r2["warm"]["persistent_misses"] == 0, r2["warm"]
        assert r2["warm"]["persistent_hits"] > 0
        assert r2["work_misses"] == 0
        assert r1["outs"] == r2["outs"]              # bitwise restart


_BEYOND_CHILD = """
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
                       prefix_cache_slots=2)
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


@pytest.mark.slow
class TestSoakBeyondGreedy:
    def test_mixed_sampling_spec_prefix_soak(self, tiny_model,
                                             draft_model):
        """Sustained mixed load on the full stack at once: greedy and
        seeded-sampled requests, speculative bursts, shared-prefix
        hits and LRU churn — batched output matches the sequential
        reference exactly and the pool drains clean."""
        eng = make_engine(tiny_model, draft=draft_model, spec_tokens=3,
                          prefix_cache_slots=2)
        try:
            rng = np.random.RandomState(21)
            prompts = (shared_prefix_prompts(10, seed=6) +
                       shared_prefix_prompts(10, prefix_len=8, seed=7) +
                       mixed_prompts(10, seed=8))
            kwargs = [dict(SAMP, seed=int(rng.randint(0, 1000)))
                      if rng.rand() < 0.5 else {} for _ in prompts]
            lens = rng.randint(2, 16, size=len(prompts))
            ref = [eng.generate(p, int(m), timeout=120, **kw)["tokens"]
                   for p, m, kw in zip(prompts, lens, kwargs)]
            handles = [eng.submit(p, int(m), **kw)
                       for p, m, kw in zip(prompts, lens, kwargs)]
            out = [h.result(120)["tokens"] for h in handles]
            assert out == ref
            snap = eng.metrics.snapshot()
            assert snap["failed_total"] == 0
            assert snap["spec_steps_total"] > 0
            assert snap["prefix_hits_total"] > 0
            assert snap["kv_pool"]["slots_used"] == 0
        finally:
            eng.shutdown()
