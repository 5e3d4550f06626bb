"""Continuous-batching generative serving (inference/serving/generate):
prefill/decode split, bucketed KV slot pool, in-flight batching,
streaming, compile-shape discipline and the elastic/chaos ladder — all
on the CPU backend.

Determinism notes: greedy decode is deterministic, so every path
(batched, sequential, streaming, post-requeue regeneration) must
produce token-IDENTICAL output — the tests assert exact equality, not
closeness. Chaos rules are scoped to (replica, generation) so a revive
replacement runs clean (the PR-9 pattern).
"""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _cpu_env import cpu_subprocess_env  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.core import compile_cache as cc  # noqa: E402
from paddle_tpu.inference.serving import (GenerativeEngine,  # noqa: E402
                                          ServingError, ServingHTTPServer)
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM  # noqa: E402
from paddle_tpu.testing import chaos  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def _lockcheck_module():
    """Lock-order race detection across the WHOLE module: every lock
    the generation scheduler creates (engine cv, stream queues, metrics,
    program memo) is shimmed; any acquisition-order cycle recorded by
    ANY test fails here — matching the serving/fault-tolerance modules
    (ISSUE 8 acceptance, carried forward)."""
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


def make_engine(model, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("max_context", 64)
    kw.setdefault("max_new_tokens_cap", 16)
    return GenerativeEngine(model, **kw)


@pytest.fixture(scope="module")
def shared_engine(tiny_model):
    eng = make_engine(tiny_model)
    yield eng
    eng.shutdown()


def mixed_prompts(n, seed=1, vocab=256, lo=3, hi=30):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, size=int(l))
            for l in rng.randint(lo, hi, size=n)]


class TestGreedyParity:
    def test_streaming_nonstreaming_and_batch1_identical(self,
                                                         shared_engine):
        """THE acceptance invariant: greedy outputs are token-identical
        between the sequential (decode bucket 1) path, the in-flight
        batched path, and the streaming delivery of the same request —
        and match the model's own reference generate()."""
        eng = shared_engine
        prompts = mixed_prompts(6)
        # sequential: one request in flight -> every decode step is
        # batch bucket 1
        seq = [eng.generate(p, 10, timeout=60)["tokens"] for p in prompts]
        # concurrent: all six in flight -> the scheduler batches rows
        handles = [eng.submit(p, 10) for p in prompts]
        conc = [h.result(60)["tokens"] for h in handles]
        assert conc == seq
        assert eng.metrics.max_occupancy() > 1
        # streaming delivers the same tokens in order
        streamed = list(eng.stream(prompts[0], 10))
        assert streamed == seq[0]
        # reference: the model's own cached-attention generate loop
        model_out = eng_model_generate(prompts[0], 10)
        assert list(model_out) == seq[0]

    def test_eos_retires_early(self, shared_engine):
        eng = shared_engine
        prompt = mixed_prompts(1, seed=5)[0]
        full = eng.generate(prompt, 10, timeout=60)["tokens"]
        assert len(full) == 10
        # pick a token at its FIRST occurrence (greedy tiny models
        # repeat tokens; an eos that also appears earlier would
        # legitimately retire the row there)
        k = next(i for i in range(1, 10) if full[i] not in full[:i])
        out = eng.generate(prompt, 10, eos_token_id=full[k],
                           timeout=60)
        assert out["tokens"] == full[:k + 1]
        assert out["finish_reason"] == "eos"

    def test_max_new_tokens_cap_and_clamp(self, shared_engine):
        eng = shared_engine
        prompt = mixed_prompts(1, seed=6)[0]
        out = eng.generate(prompt, 9999, timeout=60)
        # server-side cap (16) and the slot-capacity clamp both bound it
        assert out["n_tokens"] <= 16
        assert out["finish_reason"] == "length"


def eng_model_generate(prompt, max_new):
    """Reference greedy tokens from the model the engine was built
    from, via its own cached-attention generate loop — rebuilt from
    the same seed (cheap for the tiny config)."""
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=64, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    ids = paddle.to_tensor(np.asarray(prompt)[None].astype("int64"))
    out = model.generate(ids, max_new_tokens=max_new)
    return np.asarray(out.numpy())[0, len(prompt):]


class TestValidation:
    def test_rejects(self, shared_engine):
        eng = shared_engine
        with pytest.raises(ServingError) as e:
            eng.submit([])
        assert e.value.status == 400
        with pytest.raises(ServingError) as e:
            eng.submit([999999])          # out of vocab
        assert e.value.status == 400
        with pytest.raises(ServingError) as e:
            eng.submit(list(range(1, 70)))  # beyond usable context
        assert e.value.status == 400
        with pytest.raises(ServingError) as e:
            eng.submit([1, 2, 3], max_new_tokens=0)  # zero tokens asked
        assert e.value.status == 400

    def test_queue_shed_503_with_retry_after(self, tiny_model):
        eng = make_engine(tiny_model, max_queue_depth=2,
                          auto_start=False)
        try:
            for _ in range(2):
                eng.submit([1, 2, 3], 4)
            with pytest.raises(ServingError) as e:
                eng.submit([1, 2, 3], 4)
            assert e.value.status == 503
            assert e.value.retry_after is not None
            assert eng.metrics.shed_total == 1
        finally:
            eng.start()
            eng.shutdown()


class TestScheduler:
    def test_in_flight_admission_slot_reuse(self, tiny_model):
        """More requests than slots: rows retire, slots return to the
        free list, queued requests admit into them mid-flight — all
        complete, and the pool never grows."""
        eng = make_engine(tiny_model, slots=2)
        try:
            prompts = mixed_prompts(8, seed=2)
            ref = [eng.generate(p, 6, timeout=60)["tokens"]
                   for p in prompts]
            handles = [eng.submit(p, 6) for p in prompts]
            out = [h.result(60)["tokens"] for h in handles]
            assert out == ref
            snap = eng.metrics.snapshot()
            assert snap["max_slot_occupancy"] == 2      # capacity bound
            assert snap["completed_total"] == 16
            assert snap["kv_pool"]["slots_total"] == 2
        finally:
            eng.shutdown()

    def test_admission_skips_saturated_class(self, tiny_model):
        """Multi-class pools: a long request at the queue head whose
        capacity class is full must NOT block short requests that fit a
        class with free slots — FIFO holds per class, not globally."""
        from paddle_tpu.inference.serving.generate import _ClassState
        from paddle_tpu.inference.serving.lifecycle import ReplicaSlot

        eng = make_engine(tiny_model, slots=1, max_context=64,
                          kv_slot_buckets=[32, 64], auto_start=False)
        try:
            eng.submit(list(range(1, 30)), 16)   # 29+16=45 -> 64-class
            eng.submit([1, 2, 3], 8)             # 3+8=11  -> 32-class
            w = ReplicaSlot(99, None)
            state = {32: _ClassState(32, 1, None, None),
                     64: _ClassState(64, 1, None, None)}
            state[64].free = []                  # 64-class saturated
            with eng._cv:
                admitted = eng._admit_locked(w, w.generation, state)
            assert [int(r.prompt.size) for r, _, _ in admitted] == [3]
            assert len(eng._queue) == 1          # long head still queued
            assert int(eng._queue[0].prompt.size) == 29
        finally:
            eng.shutdown(drain=False)

    def test_drain_shutdown_completes_inflight(self, tiny_model):
        eng = make_engine(tiny_model)
        handles = [eng.submit(p, 8) for p in mixed_prompts(4, seed=3)]
        eng.shutdown(drain=True)
        for h in handles:
            assert len(h.result(1)["tokens"]) == 8
        with pytest.raises(ServingError):
            eng.submit([1, 2], 4)

    def test_kv_utilization_gauge_live(self, tiny_model):
        """Mid-flight the pool gauge reports held slots/positions."""
        eng = make_engine(tiny_model, auto_start=False)
        try:
            handles = [eng.submit(p, 16)
                       for p in mixed_prompts(4, seed=4)]
            seen = {"util": 0.0, "slots": 0}

            def watch():
                t0 = time.monotonic()
                while time.monotonic() - t0 < 30 and \
                        not all(h.future.done() for h in handles):
                    kv = eng.metrics.snapshot()["kv_pool"]
                    seen["util"] = max(seen["util"], kv["utilization"])
                    seen["slots"] = max(seen["slots"], kv["slots_used"])

            t = threading.Thread(target=watch, name="kv-watch")
            t.start()
            eng.start()
            for h in handles:
                h.result(60)
            t.join(35)
            assert seen["slots"] >= 2
            assert seen["util"] > 0.0
        finally:
            eng.shutdown()


class TestProgramInventory:
    def test_workload_compiles_only_the_two_families(self, tiny_model):
        """Compile-shape discipline: after warmup, a full mixed-length
        concurrent workload triggers ZERO persistent-cache lookups —
        everything runs on the warmed prefill bucket ladder + one
        decode-step program per batch bucket (plus the per-class
        kvget/kvput KV-handoff pair, warmed so a mid-workload
        export/import never compiles)."""
        eng = make_engine(tiny_model)
        try:
            with cc.measure() as work:
                handles = [eng.submit(p, 8)
                           for p in mixed_prompts(8, seed=7)]
                for h in handles:
                    h.result(60)
            assert work["misses"] == 0, work
            rep = eng.program_report()
            expect = {f"prefill[cap=64,b={b}]"
                      for b in (8, 16, 32, 64)} | \
                     {f"decode[cap=64,b={b}]" for b in (1, 2, 4)} | \
                     {"kvget[cap=64,b=1]", "kvput[cap=64,b=1]"}
            assert set(rep["programs"]) == expect, rep
        finally:
            eng.shutdown()

    def test_warm_restart_serves_with_zero_persistent_misses(
            self, tmp_path):
        """THE acceptance: cold process populates the compile-cache
        dir; a warm restart serves the same generation workload with
        persistent_misses == 0 (warmup AND workload), outputs bitwise
        identical."""
        env = cpu_subprocess_env(
            FLAGS_compile_cache_dir=str(tmp_path / "cc"))

        def run():
            out = subprocess.run(
                [sys.executable, "-c", _GEN_CHILD], capture_output=True,
                text=True, timeout=300, cwd=REPO, env=env)
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


_GEN_CHILD = """
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
eng = GenerativeEngine(model, slots=4, max_context=64,
                       max_new_tokens_cap=16)
rng = np.random.RandomState(3)
with cc.measure() as work:
    hs = [eng.submit(rng.randint(0, 256, size=int(l)), 8)
          for l in rng.randint(3, 30, size=6)]
    outs = [h.result(60)["tokens"] for h in hs]
eng.shutdown()
print(json.dumps({"warm": eng.warmup_report,
                  "work_misses": work["misses"], "outs": outs}))
"""


class TestElasticity:
    def test_add_replica_warm_before_admission(self, tiny_model):
        # the added replica lands on a second device, whose executables a
        # cold persistent cache has never held: build them once first, as
        # an earlier life of the server would have (test_autoscale.py's
        # twin does the same), so the verdict does not hang on what an
        # untracked cache directory held when the run began
        make_engine(tiny_model, replicas=2).shutdown()
        eng = make_engine(tiny_model)
        try:
            report = eng.add_replica()
            # device 0 was warmed at engine construction: the new
            # worker's warm pass must be pure cache hits in-process —
            # zero persistent misses, admitted only after
            assert report["persistent_misses"] == 0
            assert report["admitted_after_warmup"]
            assert len(eng._active()) == 2
            out = eng.remove_replica(report["rid"], drain=True)
            assert out["drained"]
        finally:
            eng.shutdown()

    def test_drain_under_live_traffic_loses_nothing(self, tiny_model):
        eng = make_engine(tiny_model, replicas=2)
        try:
            prompts = mixed_prompts(6, seed=8)
            ref = [eng.generate(p, 8, timeout=60)["tokens"]
                   for p in prompts]
            handles = [eng.submit(p, 8) for p in prompts]
            rid = eng._active()[0].rid
            out = eng.remove_replica(rid, drain=True, timeout=60)
            assert out["drained"]
            assert [h.result(60)["tokens"] for h in handles] == ref
            assert eng.metrics.failed_total == 0
        finally:
            eng.shutdown()

    def test_decode_raise_requeues_then_reprefills(self, tiny_model):
        """A raise mid-decode follows the requeue ladder: the in-flight
        sequences re-prefill and regenerate to the SAME tokens, with
        already-streamed tokens suppressed (no duplicates on the
        stream)."""
        eng = make_engine(tiny_model)
        try:
            prompts = mixed_prompts(3, seed=9)
            ref = [eng.generate(p, 8, timeout=60)["tokens"]
                   for p in prompts]
            chaos.add_rule("serving.decode_step", "raise_n", 1)
            handles = [eng.submit(p, 8) for p in prompts]
            streams = [list(h) for h in handles]
            assert streams == ref                 # no dups, no holes
            assert eng.metrics.requeues_total >= 1
            assert eng.metrics.failed_total == 0
        finally:
            chaos.reset()
            eng.shutdown()

    def test_repeated_raise_bounds_at_503(self, tiny_model):
        eng = make_engine(tiny_model)
        try:
            chaos.add_rule("serving.decode_step", "raise")  # every step
            h = eng.submit(mixed_prompts(1, seed=10)[0], 8)
            with pytest.raises(ServingError) as e:
                h.result(60)
            assert e.value.status == 503
            assert "replaced twice" in e.value.message or \
                "in flight" in e.value.message
        finally:
            chaos.reset()
            eng.shutdown()

    def test_hang_revive_no_corruption_no_reemission(self, tiny_model):
        """The chaos acceptance: a hang mid-decode on ONE worker is
        revived (PR-9 ladder); its requests re-prefill and complete
        token-identically; the OTHER worker's in-flight sequences are
        untouched; no stream sees a duplicate token."""
        eng = make_engine(tiny_model, replicas=2)
        try:
            prompts = mixed_prompts(6, seed=11)
            ref = [eng.generate(p, 8, timeout=60)["tokens"]
                   for p in prompts]
            w0 = eng._workers[0]
            chaos.add_rule(
                "serving.decode_step", "delay", 8.0,
                match={"replica": w0.rid, "generation": w0.generation})
            collected = [[] for _ in prompts]
            handles = [eng.submit(p, 8) for p in prompts]

            def consume(i, h):
                for tok in h:
                    collected[i].append(tok)

            threads = [threading.Thread(target=consume, args=(i, h),
                                        name=f"consume-{i}")
                       for i, h in enumerate(handles)]
            for t in threads:
                t.start()
            # wait until the chaos delay has the worker wedged
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                rows = {r["rid"]: r for r in eng.replica_states()}
                if rows[w0.rid]["busy_s"] > 0.3:
                    break
                time.sleep(0.02)
            eng.revive_replica(w0.rid)
            for t in threads:
                t.join(60)
            assert collected == ref    # exact: no dup, no corruption
            assert eng.metrics.failed_total == 0
        finally:
            chaos.reset()
            eng.shutdown()


class TestLaunchAhead:
    """Plain decode keeps its step-to-step state on the device and
    launches step n+1 before it reads step n (PR 32): what the host may
    no longer do between two steps, and what a late-learned end, a raise
    and a hang must still leave intact."""

    @staticmethod
    def _fixed_pair(model, max_new, monkeypatch):
        """Two requests admitted in one pass, ending on one step: the row
        set never changes while they decode. -> (snapshot, device_puts of
        the worker thread, the Prometheus text)."""
        import jax

        eng = make_engine(model, slots=2, max_new_tokens_cap=32,
                          auto_start=False)
        puts = []
        real = jax.device_put

        def counting(x, *a, **k):
            # the worker's own puts by array (a restage puts its seven as
            # one list), its two zeroed pools left out
            if threading.current_thread().name.startswith(
                    "generate-worker"):
                puts.extend(np.shape(leaf) for leaf in jax.tree.leaves(x)
                            if np.ndim(leaf) <= 2)
            return real(x, *a, **k)

        monkeypatch.setattr(jax, "device_put", counting)
        try:
            handles = [eng.submit(p, max_new)
                       for p in mixed_prompts(2, seed=21)]
            eng.start()
            for h in handles:
                assert len(h.result(60)["tokens"]) == max_new
            return (eng.metrics.snapshot(), puts,
                    eng.metrics.prometheus_text())
        finally:
            monkeypatch.setattr(jax, "device_put", real)
            eng.shutdown()

    def test_fixed_row_set_is_launched_ahead_and_stages_nothing(
            self, tiny_model, monkeypatch):
        short, puts_short, _ = self._fixed_pair(tiny_model, 6,
                                                monkeypatch)
        snap, puts, text = self._fixed_pair(tiny_model, 24, monkeypatch)
        n = snap["steps_total"]
        assert n == 23 and snap["step_rows_total"] == 2 * n
        # every step but the first was launched with its predecessor
        # still unread
        assert snap["steps_ahead_total"] >= n - 2
        assert snap["launch_ahead_share"] >= round((n - 2) / n, 4)
        assert (f"paddle_generate_launch_ahead_share "
                f"{snap['launch_ahead_share']}") in text
        assert (f"paddle_generate_steps_ahead_total "
                f"{snap['steps_ahead_total']}") in text
        # the host staged two prefills (seven arrays each) and ONE set of
        # seven row arrays, however many steps followed: 18 more steps
        # put nothing
        assert short["steps_total"] == 5
        assert len(puts) == len(puts_short) == 2 * 7 + 7
        assert puts == puts_short

    def test_eos_learned_a_step_late_emits_nothing_after_it(
            self, tiny_model):
        """The host reads a row's EOS with the next step already on the
        queue: that step's token for the row is discarded, the step counts
        as no row, and the slot's next owner — prefilled behind the
        overshoot — decodes what a fresh engine gives it."""
        pa, pb = mixed_prompts(2, seed=5)
        fresh = make_engine(tiny_model, slots=1)
        try:
            full = fresh.generate(pa, 10, timeout=60)["tokens"]
            want_b = fresh.generate(pb, 9, timeout=60)["tokens"]
        finally:
            fresh.shutdown()
        k = next(i for i in range(2, 10) if full[i] not in full[:i])
        eng = make_engine(tiny_model, slots=1, auto_start=False)
        try:
            ha = eng.submit(pa, 10, eos_token_id=full[k])
            hb = eng.submit(pb, 9)          # waits for A's slot
            eng.start()
            events = list(ha.events())
            assert [v for kind, v in events if kind == "tok"] \
                == full[:k + 1]
            assert events[-1][0] == "done"
            assert events[-1][1]["finish_reason"] == "eos"
            assert hb.result(60)["tokens"] == want_b
            snap = eng.metrics.snapshot()
            # A ran k real steps (its first token is the prefill's), B 8;
            # A's overshoot step held no real row: not a step
            assert snap["steps_total"] == k + 8
            assert snap["step_rows_total"] == k + 8
            assert snap["tokens_out_total"] == k + 1 + 9
        finally:
            eng.shutdown()

    def test_raise_with_a_step_in_flight_requeues_without_dup_or_loss(
            self, tiny_model):
        eng = make_engine(tiny_model, max_new_tokens_cap=32)
        try:
            prompts = mixed_prompts(3, seed=9)
            ref = [eng.generate(p, 24, timeout=60)["tokens"]
                   for p in prompts]
            # slow steps, so the raise is armed mid-stream: by a row's
            # third token the loop is steady and every pass meets the
            # injection point with a launched step unread
            chaos.add_rule("serving.decode_step", "delay", 0.01)
            handles = [eng.submit(p, 24) for p in prompts]
            streams = [[] for _ in prompts]
            for tok in handles[0]:
                streams[0].append(tok)
                if len(streams[0]) == 3:
                    assert eng.metrics.steps_ahead_total >= 1
                    chaos.add_rule("serving.decode_step", "raise_n", 1)
            streams[1:] = [list(h) for h in handles[1:]]
            assert streams == ref                 # no dups, no holes
            assert eng.metrics.requeues_total >= 1
            assert eng.metrics.failed_total == 0
        finally:
            chaos.reset()
            eng.shutdown()

    def test_hang_with_a_step_in_flight_revives_without_dup_or_loss(
            self, tiny_model):
        eng = make_engine(tiny_model, max_new_tokens_cap=32)
        try:
            prompts = mixed_prompts(3, seed=12)
            ref = [eng.generate(p, 24, timeout=60)["tokens"]
                   for p in prompts]
            w0 = eng._workers[0]
            gen0 = w0.generation
            chaos.add_rule("serving.decode_step", "delay", 0.01)
            handles = [eng.submit(p, 24) for p in prompts]
            collected = [[] for _ in prompts]

            def consume(i, h):
                for tok in h:
                    collected[i].append(tok)
                    if i == 0 and len(collected[0]) == 3:
                        # wedge THIS incarnation at its next pass, a
                        # launched step still unread behind it
                        chaos.add_rule(
                            "serving.decode_step", "delay", 8.0,
                            match={"replica": w0.rid,
                                   "generation": gen0})

            threads = [threading.Thread(target=consume, args=(i, h),
                                        name=f"consume-{i}")
                       for i, h in enumerate(handles)]
            for t in threads:
                t.start()
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                rows = {r["rid"]: r for r in eng.replica_states()}
                if rows[w0.rid]["busy_s"] > 0.3:
                    break
                time.sleep(0.02)
            assert eng.metrics.steps_ahead_total >= 1
            eng.revive_replica(w0.rid)
            for t in threads:
                t.join(60)
            assert collected == ref    # exact: no dup, no corruption
            assert eng.metrics.failed_total == 0
        finally:
            chaos.reset()
            eng.shutdown()


class TestAutoscaleIntegration:
    def test_health_watchdog_revives_hung_decode_worker(self,
                                                       tiny_model):
        """The PR-9 controllers drive the generation engine through
        the SAME replica contract: a chaos-hung decode worker trips
        the watchdog's busy deadline, is revived in place, and every
        generation completes token-identically."""
        from paddle_tpu.autoscale import HealthWatchdog

        eng = make_engine(tiny_model, replicas=2)
        try:
            # more rows than one worker has slots, and every worker's
            # first incarnation hangs at its first decode step: whichever
            # worker admits first hangs holding at most its 4 slots, and
            # the other is then the only one that can take the rest — so
            # each CERTAINLY hangs holding a row (a rule on worker 0 alone
            # never fires when worker 1 wakes first and takes them all)
            prompts = mixed_prompts(8, seed=20)
            ref = [eng.generate(p, 8, timeout=60)["tokens"]
                   for p in prompts]
            for w in eng._workers:
                chaos.add_rule(
                    "serving.decode_step", "delay", 8.0,
                    match={"replica": w.rid, "generation": w.generation})
            wd = HealthWatchdog(eng, exec_deadline_s=0.3,
                                beat_deadline_s=30.0, backoff_s=0.1)
            handles = [eng.submit(p, 8) for p in prompts]
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline and \
                    wd.counters["watchdog_revives"] < 2:
                wd.poll_once()
                time.sleep(0.05)
            assert wd.counters["watchdog_revives"] == 2, \
                "watchdog never fired on a hung worker"
            assert [h.result(60)["tokens"] for h in handles] == ref
            assert eng.metrics.failed_total == 0
        finally:
            chaos.reset()
            eng.shutdown()

    def test_autoscaler_signals_and_headroom_stretch(self, tiny_model):
        """ReplicaAutoscaler reads the generation engine's signals
        unmodified, and its headroom hook stretches the breaker's
        queue bound (degrade order scale -> queue -> shed)."""
        from paddle_tpu.autoscale import ReplicaAutoscaler
        from paddle_tpu.autoscale.policy import ScalingPolicy

        eng = make_engine(tiny_model, max_queue_depth=2,
                          overload_queue_factor=2.0, auto_start=False)
        try:
            auto = ReplicaAutoscaler(
                eng, policy=ScalingPolicy(min_replicas=1,
                                          max_replicas=3))
            sig = auto._signals()
            assert sig["replicas"] == 1 and sig["queue_depth"] == 0
            # with headroom, the bound stretches 2 -> 4: four queued
            # requests, zero shed
            for _ in range(4):
                eng.submit([1, 2, 3], 2)
            assert eng.metrics.shed_total == 0
            with pytest.raises(ServingError):
                eng.submit([1, 2, 3], 2)   # 5th: stretched bound hit
            auto.close()
            # headroom unhooked: the plain bound (2) applies again
            assert eng._queue_bound() == 2
        finally:
            eng.start()
            eng.shutdown()


class TestHTTP:
    def test_generate_stream_json_health_metrics(self, tiny_model):
        eng = make_engine(tiny_model)
        srv = ServingHTTPServer(None, generator=eng).start()
        try:
            url = f"http://127.0.0.1:{srv.port}"
            prompt = [int(x) for x in mixed_prompts(1, seed=12)[0]]
            body = json.dumps({"input_ids": prompt,
                               "max_new_tokens": 6}).encode()
            req = urllib.request.Request(
                url + "/generate", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as r:
                ns = json.loads(r.read())
            assert len(ns["tokens"]) == 6
            assert ns["ttft_ms"] is not None
            body = json.dumps({"input_ids": prompt, "max_new_tokens": 6,
                               "stream": True}).encode()
            req = urllib.request.Request(
                url + "/generate", data=body,
                headers={"Content-Type": "application/json"})
            toks, final = [], None
            with urllib.request.urlopen(req, timeout=60) as r:
                assert r.headers.get("Content-Type") == \
                    "application/x-ndjson"
                for line in r:
                    obj = json.loads(line)
                    if obj.get("done"):
                        final = obj
                    elif "token" in obj:
                        toks.append(obj["token"])
            assert toks == ns["tokens"]           # stream == JSON mode
            assert final["n_tokens"] == 6
            with urllib.request.urlopen(url + "/healthz",
                                        timeout=10) as r:
                assert json.loads(r.read())["status"] == "ok"
            with urllib.request.urlopen(url + "/metrics",
                                        timeout=10) as r:
                text = r.read().decode()
            assert "paddle_generate_tokens_total" in text
            assert "paddle_generate_ttft_seconds" in text
        finally:
            srv.stop()

    def test_bad_request_is_400_and_no_generator_404(self, tiny_model,
                                                     tmp_path):
        import paddle_tpu.nn as nn
        from paddle_tpu import jit
        from paddle_tpu.inference.serving import ServingEngine
        from paddle_tpu.static import InputSpec

        paddle.seed(0)
        mlp = nn.Sequential(nn.Linear(8, 4))
        mlp.eval()
        prefix = str(tmp_path / "m")
        jit.save(mlp, prefix,
                 input_spec=[InputSpec([None, 8], "float32")])
        pred = ServingEngine(prefix, max_batch_size=4, replicas=1)
        gen = make_engine(tiny_model)
        srv = ServingHTTPServer(pred, generator=gen).start()
        try:
            url = f"http://127.0.0.1:{srv.port}"
            # both fronts on one server
            body = json.dumps({"inputs": [
                np.zeros((1, 8), np.float32).tolist()]}).encode()
            req = urllib.request.Request(
                url + "/predict", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as r:
                assert r.status == 200
            body = json.dumps({"input_ids": [1, 2],
                               "max_new_tokens": 2}).encode()
            req = urllib.request.Request(
                url + "/generate", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as r:
                assert len(json.loads(r.read())["tokens"]) == 2
            # malformed generate body -> 400
            req = urllib.request.Request(
                url + "/generate", data=b"{}",
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req, timeout=60)
            assert e.value.code == 400
        finally:
            srv.stop()


class TestObservability:
    def test_span_chain_and_summary_provider(self, tiny_model,
                                             tmp_path):
        from paddle_tpu.observability import trace

        eng = make_engine(tiny_model)
        paddle.set_flags({"FLAGS_trace_dir": str(tmp_path)})
        try:
            before = len(trace.spans())
            out = eng.generate(mixed_prompts(1, seed=13)[0], 4,
                               timeout=60)
            assert len(out["tokens"]) == 4
            evs = trace.spans()[before:]
            names = {e["name"] for e in evs}
            assert {"generate.enqueue", "generate.prefill",
                    "generate.decode_step", "generate.token",
                    "generate.finish"} <= names
            # the whole request is ONE trace across client + worker
            # threads
            enq = [e for e in evs if e["name"] == "generate.enqueue"][-1]
            tid = enq["args"]["trace"]
            chain = [e for e in evs if e["args"].get("trace") == tid]
            assert {e["name"] for e in chain} >= {
                "generate.enqueue", "generate.prefill", "generate.token"}
            assert len({e["tid"] for e in chain}) >= 2
        finally:
            paddle.set_flags({"FLAGS_trace_dir": ""})
            eng.shutdown()
        # the bus digest carries the generation section
        import paddle_tpu.profiler as prof

        with prof.profiler_guard(timer_only=True) as p:
            pass
        d = p.summary_dict()
        assert "generative" in d
        assert d["generative"]["tokens_out_total"] >= 4


@pytest.mark.slow
class TestSoak:
    def test_capacity_churn_soak(self, tiny_model):
        """Sustained mixed load with more requests than slots, random
        lengths and EOS retirements: everything completes, outputs
        match the sequential reference, nothing leaks."""
        eng = make_engine(tiny_model, slots=4)
        try:
            prompts = mixed_prompts(40, seed=14)
            lens = np.random.RandomState(15).randint(2, 16, size=40)
            ref = [eng.generate(p, int(m), timeout=120)["tokens"]
                   for p, m in zip(prompts, lens)]
            handles = [eng.submit(p, int(m))
                       for p, m in zip(prompts, lens)]
            out = [h.result(120)["tokens"] for h in handles]
            assert out == ref
            snap = eng.metrics.snapshot()
            assert snap["failed_total"] == 0
            assert snap["kv_pool"]["slots_used"] == 0   # all freed
        finally:
            eng.shutdown()
