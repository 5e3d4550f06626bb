"""models/brumby.py through the serving engine, on the CPU at `brumby-tiny`
(seeded): the engine's prefill (attention form) then decode (recurrent form
over the state) against the plain reference's full forward pass
(benchmarks/references/brumby.py, which imports nothing of paddle_tpu) in
LOGITS; the two forms against each other over 1,000+ positions under a SET
slow decay, where a bfloat16 state and a dropped carry each read over the
tolerance; the kernel in interpret mode against its `jax.numpy` twin; the
state through slot reuse, padded buckets and a requeue; every engine
feature the model refuses, by name; and the LFM2 programs' lowered text,
which this model's arrival must not have moved (the GPT programs' stand in
tests/test_lfm2.py).
"""
import copy
import hashlib
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import GenerativeEngine, ServingError
from paddle_tpu.models import brumby, lfm2
from paddle_tpu.observability import trace
from paddle_tpu.ops.pallas import retention_step as rs
from paddle_tpu.quantization import kv as kvq
from paddle_tpu.testing import chaos

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(REPO,
                                                                     path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmarks/references/brumby.py", "brumby_reference")


def tiny(dtype="float32", **kw):
    cfg = copy.copy(brumby.PRESETS["brumby-tiny"])
    cfg.dtype = dtype
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


def ref_config(cfg):
    """What the reference reads of a configuration's file."""
    return {"architecture": {
        "num_hidden_layers": cfg.num_hidden_layers,
        "num_attention_heads": cfg.num_attention_heads,
        "num_key_value_heads": cfg.num_key_value_heads,
        "head_dim": cfg.head_dim, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta}}


def params_of(cfg, seed=3):
    paddle.seed(seed)
    return brumby.init_params(cfg)


def through_the_state(cfg, p, prompt, answer, bucket, slot=1, rows=3,
                      dirty=False, after_step=None):
    """Logits at every answered position: a prefill of the prompt padded to
    `bucket` into `slot`, then one pass a token — the programs' bodies,
    called as the engine calls them, the pools None. `after_step` (a
    planted fault) is applied to the state after every pass."""
    passes = cfg.serving_passes()
    rec = jnp.zeros(passes.state_shape(rows), jnp.float32)
    if dirty:           # what an earlier owner of the slot left behind
        rec = rec + 5
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :len(prompt)] = prompt
    h, _, _, rec = jax.jit(passes.prefill)(
        p, None, None, rec, jnp.int32(slot), jnp.asarray(ids),
        jnp.int32(len(prompt)))
    out = [passes.head(p, h)]
    step = jax.jit(passes.pool_pass, static_argnums=(7,))
    scratch = rows - 1
    for j, tok in enumerate(answer[:-1]):
        # the row beside a padding row, as a decode bucket has them
        h, _, _, rec, aux = step(
            p, None, None, rec, jnp.asarray([slot, scratch], jnp.int32),
            jnp.asarray([tok, 0], jnp.int32),
            jnp.asarray([len(prompt) + j, 0], jnp.int32), scratch)
        assert aux is None
        if after_step is not None:
            rec = after_step(rec)
        out.append(passes.head(p, h[0]))
    return np.asarray(jnp.stack(out), np.float32), rec


def reference_logits(cfg, p, prompt, answer, precision=None):
    ids = np.asarray(list(prompt) + list(answer[:-1]), np.int32)[None]
    lg = ref.serve_logits(p, ids, ref_config(cfg), precision=precision)
    return np.asarray(lg, np.float32)[0, len(prompt) - 1:]


PROMPT = np.random.RandomState(5).randint(0, 512, 21)
ANSWER = np.random.RandomState(6).randint(0, 512, 9)


# ------------------------------------------- the engine against the reference
@pytest.mark.parametrize("bucket", [32, 64], ids=["bucket32", "bucket64"])
def test_prefill_then_decode_agrees_with_the_full_forward_pass_f32(bucket):
    """float32, tightly: the attention form over the padded prompt, the
    state after its last real position, and the recurrent steps over it
    change the order of sums and nothing else."""
    cfg = tiny("float32")
    p = params_of(cfg)
    got, _ = through_the_state(cfg, p, PROMPT, ANSWER, bucket)
    want = reference_logits(cfg, p, PROMPT, ANSWER)
    assert got.shape == want.shape == (len(ANSWER), cfg.vocab_size)
    np.testing.assert_allclose(got, want, atol=1e-4 * want.std(), rtol=0)


# between what bfloat16 reads and what the precision below it reads, in
# standard deviations of the logits (both are printed by the test)
BF16_LOGIT_TOL = 0.06


def test_bfloat16_is_within_a_tolerance_that_float8_fails():
    """bfloat16 weights and operands over a float32 state: within
    BF16_LOGIT_TOL of the float32 reference over the same (bfloat16-held)
    weights at every answered position; the reference computed one
    precision down is not."""
    cfg = tiny("bfloat16")
    p = params_of(cfg)
    got, _ = through_the_state(cfg, p, PROMPT, ANSWER, 32)
    want = reference_logits(cfg, p, PROMPT, ANSWER)
    below = reference_logits(cfg, p, PROMPT, ANSWER, precision="float8")
    std = want.std()
    read = np.abs(got - want).max() / std
    control = np.abs(below - want).max() / std
    print("bfloat16", read, "float8", control)
    assert read < BF16_LOGIT_TOL < control
    # and bfloat16 operands in the reference read what the program reads
    same = reference_logits(cfg, p, PROMPT, ANSWER, precision="bfloat16")
    assert np.abs(same - want).max() / std < BF16_LOGIT_TOL


@pytest.mark.parametrize("fault", ["bfloat16_state", "dropped_carry"])
def test_a_fault_in_the_state_reads_over_the_float32_tolerance(fault):
    """The float32 comparison with a fault planted in the program: the
    state rounded to bfloat16 after every step, or the carry dropped (the
    state nought after every step, so that a step sees its own token
    alone)."""
    cfg = tiny("float32")
    p = params_of(cfg)
    want = reference_logits(cfg, p, PROMPT, ANSWER)
    plant = {"bfloat16_state": lambda rec: rec.astype(jnp.bfloat16).astype(
        jnp.float32), "dropped_carry": jnp.zeros_like}[fault]
    got, _ = through_the_state(cfg, p, PROMPT, ANSWER, 32, after_step=plant)
    read = np.abs(got - want).max() / want.std()
    print(fault, read)
    assert read > 10 * 1e-4


def test_engine_serves_what_the_reference_puts_first():
    """Through `build_generator`, the worker loop and the HTTP-less front:
    every served greedy token is the reference's own first choice,
    teacher-forced over prompt + answer (the benchmark's comparison); the
    engine says what cache is there."""
    from paddle_tpu.inference.serve import build_generator, generate_presets

    assert generate_presets()["brumby-tiny"] is brumby
    assert generate_presets()["lfm2-tiny"] is lfm2
    assert "gpt3-tiny" in generate_presets()
    eng = build_generator("brumby-tiny", slots=4, max_new_tokens_cap=16)
    try:
        cfg, p = eng._cfg, eng._params
        assert cfg is brumby.PRESETS["brumby-tiny"]
        assert p["0.q_w"].dtype == jnp.bfloat16
        assert p["0.g_w"].dtype == jnp.float32
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, cfg.vocab_size, n)
                   for n in (5, 17, 1, 9, 40, 2)]
        handles = [eng.submit(q, 12) for q in prompts]
        busy = eng.metrics.snapshot()["kv_pool"]
        answers = [h.result(300)["tokens"] for h in handles]
        snap = eng.metrics.snapshot()
        report = eng.program_report()
        text = eng.metrics.prometheus_text()
        cache_bytes = eng.kv_pool_bytes()
        memory = eng.program_memory()
    finally:
        eng.shutdown()
    worst = 0.0
    for prompt, answer in zip(prompts, answers):
        assert len(answer) == 12
        lg = reference_logits(cfg, p, prompt, answer)
        gaps = lg.max(-1) - lg[np.arange(len(answer)), answer]
        worst = max(worst, float(gaps.max() / lg.std()))
    assert worst < BF16_LOGIT_TOL
    # no K/V pool: the state is the cache, float32, one row a slot + scratch
    R = rs.state_rows(cfg.head_dim)
    assert report["cache"] == {"model": "brumby", "kv_pool": {},
                               "state": [5, 2, 2, R, 16]}
    assert report["state_dtype"] == "f32" and report["kv_read"] == {}
    assert cache_bytes == 4 * 5 * 2 * 2 * R * 16
    assert memory["temp_bytes"] >= 0 and memory["argument_bytes"] > 0
    assert all(name.startswith(("prefill[", "decode["))
               for name in report["programs"])
    # the steps moved each real row's state, read and written, and no K/V
    assert snap["state_bytes_moved_total"] == \
        snap["step_rows_total"] * 2 * 4 * 2 * 2 * R * 16
    assert snap["kv_positions_read_total"] == 0
    assert snap["kv_positions_capacity_total"] == 0
    assert "paddle_generate_state_bytes_moved_total" in text
    # the gauge reports slots, not 0/0
    assert busy["positions_total"] == 0 and busy["slots_total"] == 4
    assert busy["utilization"] == busy["slots_used"] / 4
    assert snap["kv_pool"]["pool_bytes"] == cache_bytes


def test_the_seed_reaches_the_initialisers():
    cfg = tiny("bfloat16")
    a, b, c = params_of(cfg, 1), params_of(cfg, 1), params_of(cfg, 2)
    assert set(a) == set(brumby.param_shapes(cfg))
    for name, (shape, kind) in brumby.param_shapes(cfg).items():
        assert a[name].shape == shape
        assert a[name].dtype == (jnp.float32 if kind == "gate"
                                 else jnp.bfloat16)
        assert bool((a[name] == b[name]).all())
        assert not bool((a[name] == c[name]).all())
    assert sum(int(np.prod(v.shape)) for v in a.values()) == \
        brumby.n_params(cfg)
    big = brumby.PRESETS["brumby-14b-base"]
    assert brumby.n_params(big) == 4_198_652_928
    assert big.serving_passes().state_shape(17) == (17, 8, 8, 8832, 128)
    with pytest.raises(ValueError, match="multiple of the K/V heads"):
        brumby.BrumbyConfig(num_attention_heads=5, num_key_value_heads=2)


# ---------------------------------------------- the two forms of the retention
def test_phi_is_the_second_power_of_the_score():
    rng = np.random.RandomState(0)
    for Dh in (16, 128):
        q = jnp.asarray(rng.standard_normal((3, Dh)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((3, Dh)), jnp.float32)
        got = (rs.phi_q(q) * rs.phi_k(k)).sum(-1)
        np.testing.assert_allclose(got, (q * k).sum(-1) ** 2 / Dh,
                                   rtol=1e-4)
        assert rs.phi_q(q).shape == (3, rs.product_rows(Dh))
    assert rs.product_rows(128) == 8704 and rs.state_rows(128) == 8832
    assert rs.state_block_plan(128, 5).rows == 8832
    assert rs.state_block_plan(16, 2) is None       # the twin serves it
    assert rs.state_block_plan(256, 5) is None      # no room in VMEM


SLOW = -1.0 / 512       # log g: a state that remembers ~500 tokens
LONG = 1040


def _long_sequence(seed=0, S=LONG, Hkv=2, G=2, Dh=16):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.standard_normal((S, Hkv * G, Dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((S, Hkv, Dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((S, Hkv, Dh)), jnp.float32)
    return q, k, v, jnp.full((S, Hkv), SLOW, jnp.float32)


def _recurrent(q, k, v, log_g, after_step=lambda t, st: st):
    """The recurrent form a position at a time: advance, read."""
    S, H, Dh = q.shape
    Hkv = k.shape[1]

    def step(st, xs):
        t, q_t, k_t, v_t, lg = xs
        st = rs.advance(st, k_t, v_t, jnp.exp(lg))
        y = rs.read(st, q_t.reshape(Hkv, H // Hkv, Dh))
        return after_step(t, st), y.reshape(H, Dh)

    st0 = jnp.zeros((Hkv, rs.state_rows(Dh), Dh), jnp.float32)
    st, ys = jax.lax.scan(step, st0, (jnp.arange(S), q, k, v, log_g))
    return ys, st


# the largest difference between the two forms' outputs, over the
# outputs' standard deviation: float32 rounding reads far under it; the
# faults planted below read far over it (all are printed)
FORMS_TOL = 1e-3


@pytest.mark.parametrize("fault", [None, "bfloat16_state", "dropped_carry",
                                   "decay_ignored"])
def test_recurrent_form_equals_attention_form_over_a_long_sequence(fault):
    """1,040 positions under a decay of exp(-1/512) a position, so that
    the state at the end still holds the sequence's start: the recurrent
    form (what decode runs) equals the attention form (what prefill and
    the reference run); with the state held in bfloat16, the carry dropped
    half way, or the decay ignored, it does not."""
    q, k, v, log_g = _long_sequence()
    want = brumby.retention_seq(q, k, v, log_g)
    # the reference's own attention form says the same
    theirs = ref.power_retention(q[None], k[None], v[None], log_g[None])[0]
    np.testing.assert_allclose(theirs, want, atol=1e-4)
    after = {
        None: lambda t, st: st,
        "bfloat16_state": lambda t, st: st.astype(jnp.bfloat16).astype(
            jnp.float32),
        "dropped_carry": lambda t, st: jnp.where(t == LONG // 2, 0.0, st),
        "decay_ignored": lambda t, st: st,
    }[fault]
    got, _ = _recurrent(q, k, v, log_g * (fault != "decay_ignored"), after)
    read = float(jnp.abs(got - want).max() / want.std())
    print(fault, read)
    if fault is None:
        assert read < FORMS_TOL
    else:
        assert read > 3 * FORMS_TOL


@pytest.mark.parametrize("length", [1, 7, 300, LONG])
def test_state_after_a_padded_prompt_is_the_state_the_steps_reach(length):
    """`state_after` at the last REAL position equals `length` recurrent
    steps, whatever lies in the padding behind it."""
    q, k, v, log_g = _long_sequence(seed=1)
    want = _recurrent(q[:length], k[:length], v[:length], log_g[:length])[1]
    got = brumby.state_after(k, v, log_g, jnp.int32(length))
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-4 * float(jnp.abs(want).max()))


# ------------------------------------------------------------------ the kernel
@pytest.mark.parametrize("slots", [(1, 0, 3), (3, 2, 0), (3, 3, 3)],
                         ids=["real_first", "padding_first", "all_padding"])
def test_retention_step_in_interpret_mode_against_its_twin(slots):
    """The Pallas kernel (interpret mode) against the `jax.numpy` step at
    the published head size, two query heads a K/V head: the outputs, the
    state advanced in place at the rows' slots and this layer, and nothing
    else touched — a padding row (the scratch slot, 3) moves nothing."""
    Dh, G, Hkv, L, rows, scratch = 128, 2, 1, 2, 4, 3
    rng = np.random.RandomState(0)
    b = len(slots)
    # a state as steps leave it: a few tokens in every slot
    state = jnp.zeros((rows, L, Hkv, rs.state_rows(Dh), Dh), jnp.float32)
    for _ in range(3):
        state = rs.advance(
            state, jnp.asarray(rng.standard_normal((rows, L, Hkv, Dh)),
                               jnp.float32),
            jnp.asarray(rng.standard_normal((rows, L, Hkv, Dh)),
                        jnp.float32),
            jnp.asarray(rng.uniform(0.2, 0.9, (rows, L, Hkv)), jnp.float32))
    q = jnp.asarray(rng.standard_normal((b, Hkv, G, Dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, Hkv, Dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, Hkv, Dh)), jnp.float32)
    g = jnp.asarray(rng.uniform(0.1, 0.9, (b, Hkv)), jnp.float32)
    at = jnp.asarray(slots, jnp.int32)
    y_twin, s_twin = rs.retention_step_twin(state, 1, at, q, k, v, g,
                                            scratch=scratch)
    y, s = rs.retention_step(state, 1, at, q, k, v, g, scratch=scratch,
                             interpret=True)
    scale = float(jnp.abs(y_twin).max()) or 1.0
    np.testing.assert_allclose(y, y_twin, atol=2e-4 * scale)
    np.testing.assert_allclose(s, s_twin, atol=1e-5)
    real = [x for x in slots if x != scratch]
    for row in range(rows):
        np.testing.assert_array_equal(s[row, 0], state[row, 0])
        if row not in real:
            np.testing.assert_array_equal(s[row, 1], state[row, 1])
        else:
            assert not np.array_equal(s[row, 1], state[row, 1])
    for i, x in enumerate(slots):
        if x == scratch:
            assert not np.asarray(y[i]).any()
        else:       # the twin's step is the two forms' own arithmetic
            want = rs.read(rs.advance(state[x, 1], k[i], v[i], g[i]), q[i])
            np.testing.assert_allclose(y[i], want, atol=2e-4 * scale)


# --------------------------------------------------- the state through a slot
def test_padded_prefill_keeps_the_state_of_the_last_real_positions():
    """The same prompt in buckets of 32 and 64, into a clean slot and into
    one an earlier request left dirty: one state, and the logits that
    follow; no other slot touched."""
    cfg = tiny("float32")
    p = params_of(cfg)
    a, ra = through_the_state(cfg, p, PROMPT, ANSWER[:3], 32)
    b, rb = through_the_state(cfg, p, PROMPT, ANSWER[:3], 64)
    c, rc = through_the_state(cfg, p, PROMPT, ANSWER[:3], 32, dirty=True)
    np.testing.assert_allclose(ra[1], rb[1], rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(ra[1], rc[1])
    np.testing.assert_allclose(a, b, atol=1e-4)
    np.testing.assert_array_equal(a, c)
    assert float(jnp.abs(ra[1]).max()) > 0
    # the other slots, the scratch row among them, were not touched
    np.testing.assert_array_equal(ra[0], 0)
    np.testing.assert_array_equal(ra[2], 0)
    np.testing.assert_array_equal(rc[0], 5)
    np.testing.assert_array_equal(rc[2], 5)


def _serve(cfg, p, prompts, new=10, **kw):
    eng = GenerativeEngine(params=(p, cfg), warmup=False,
                           max_new_tokens_cap=new, **kw)
    try:
        return [eng.generate(q, new, timeout=300)["tokens"]
                for q in prompts], eng.metrics.snapshot()
    finally:
        eng.shutdown()


def test_a_reused_slot_starts_clean_through_the_engine():
    """One slot, two requests one after the other: the second's answer is
    what a fresh engine gives it."""
    cfg = tiny("float32")
    p = params_of(cfg)
    rng = np.random.RandomState(2)
    first, second = rng.randint(0, 512, 30), rng.randint(0, 512, 7)
    assert _serve(cfg, p, [first, second], slots=1)[0][1] == \
        _serve(cfg, p, [second], slots=1)[0][0]


def test_a_requeue_rebuilds_the_state_from_the_prompt():
    """A raise mid-decode drops the worker's state and requeues its rows:
    the replay re-prefills, and the streams carry the same tokens once."""
    cfg = tiny("float32")
    p = params_of(cfg)
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, 512, n) for n in (6, 19, 11)]
    eng = GenerativeEngine(params=(p, cfg), slots=4, warmup=False,
                           max_new_tokens_cap=10)
    try:
        want = [eng.generate(q, 10, timeout=300)["tokens"] for q in prompts]
        chaos.add_rule("serving.decode_step", "raise_n", 1)
        streams = [list(h) for h in [eng.submit(q, 10) for q in prompts]]
        assert streams == want
        assert eng.metrics.requeues_total >= 1
        assert eng.metrics.failed_total == 0
    finally:
        chaos.reset()
        eng.shutdown()


# --------------------------------------------------------------- refusals
@pytest.mark.parametrize("feature, kw", [
    ("prefix_cache_slots", {"prefix_cache_slots": 2}),
    ("draft", {"draft_params": "a draft"}),
    ("kv_dtype=int8", {"kv_dtype": "int8"}),
    ("quantize_weights", {"quantize_weights": True}),
])
def test_what_cannot_carry_the_state_is_refused_by_name(feature, kw):
    cfg = tiny("float32")
    with pytest.raises(ValueError) as e:
        GenerativeEngine(params=({}, cfg), warmup=False, auto_start=False,
                         **kw)
    assert "brumby" in str(e.value) and feature in str(e.value)


def test_the_handoff_plane_is_refused_at_the_call():
    cfg = tiny("float32")
    eng = GenerativeEngine(params=(params_of(cfg), cfg), slots=2,
                           warmup=True, auto_start=False)
    try:
        assert not any(name.startswith(("kvget", "kvput", "pcopy"))
                       for name in eng.program_report()["programs"])
        for call in (lambda: eng.submit([1, 2], 4, prefill_only=True),
                     lambda: eng.import_handoff(b"whatever"),
                     lambda: eng.shutdown(drain=True, migrate=True)):
            with pytest.raises(ServingError) as e:
                call()
            assert e.value.status == 409
            assert "brumby" in str(e.value) and "handoff" in str(e.value)
        assert set(brumby.ServingPasses.refuses) == \
            set(lfm2.ServingPasses.refuses)
    finally:
        eng.shutdown(drain=False)


# ------------------------------------------------------ spans and scopes
def test_scopes_are_noted_by_program_and_the_step_says_its_state_bytes(
        tmp_path):
    cfg = tiny("float32")
    p = params_of(cfg)
    paddle.set_flags({"FLAGS_trace_dir": str(tmp_path)})
    trace.reset()
    try:
        eng = GenerativeEngine(params=(p, cfg), slots=2,
                               max_new_tokens_cap=8)
        try:
            eng.generate([3, 4, 5], 6, timeout=300)
        finally:
            eng.shutdown()
        by_program = trace.op_scopes_by_program()
        spans = trace.spans()
    finally:
        paddle.set_flags({"FLAGS_trace_dir": ""})
        trace.reset()
    assert {"jit_brumby_decode_c128_b1", "jit_brumby_decode_c128_b2",
            "jit_brumby_prefill_c128_b8"} <= set(by_program)
    for program, names in by_program.items():
        scopes = " ".join(names.values())
        assert program.startswith("jit_brumby_")
        assert ("retention.step" if "_decode_" in program
                else "retention.prefill") in scopes, program
        assert "generate.sample" in scopes
    moved = [s["args"]["state_bytes"] for s in spans
             if s["name"] == "generate.decode_step"]
    row = 2 * 4 * int(np.prod(cfg.serving_passes().state_shape(1)))
    assert moved and all(m == row for m in moved)
    assert all(s["args"]["kv_read"] == 0 for s in spans
               if s["name"] == "generate.decode_step")


# ------------------------------------ the programs, lowered for their chips
def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _row_args(b):
    i32, f32 = np.int32, np.float32
    return (_sds((b,), i32), _sds((b,), i32), _sds((b,), i32),
            _sds((b,), f32), _sds((b,), i32), _sds((b,), f32),
            _sds((b, 2), np.uint32))


def test_the_decode_program_lowers_for_a_tpu_with_no_gather_of_the_state():
    """Lowered for a TPU (the host has none: nothing compiles or runs), the
    decode program at the published head size takes the kernel — one
    `retention_step` for all layers, the layer its argument — and nothing
    gathers, scatters or slices an array of the state's shape; lowered for
    the CPU it takes the twin."""
    cfg = brumby.BrumbyConfig(
        vocab_size=512, hidden_size=64, intermediate_size=160,
        num_hidden_layers=2, num_attention_heads=5, num_key_value_heads=1,
        head_dim=128, max_seq_len=128)
    eng = GenerativeEngine(params=({}, cfg), slots=4, warmup=False,
                           auto_start=False, donate=True)
    try:
        params = {n: _sds(shape, jnp.float32 if kind == "gate"
                          else jnp.bfloat16)
                  for n, (shape, kind) in brumby.param_shapes(cfg).items()}
        cap, b = eng._caps[-1], eng._batch_buckets[-1]
        pool_k, pool_v, rec = eng._cache_avals(cap)
        assert pool_k is None and pool_v is None
        assert rec.shape == (5, 2, 1, 8832, 128) and rec.dtype == jnp.float32
        traced = eng._program("decode", cap, b).trace(
            params, None, None, *_row_args(b), rec)
        tpu = traced.lower(lowering_platforms=("tpu",)).as_text()
        cpu = traced.lower(lowering_platforms=("cpu",)).as_text()
    finally:
        eng.shutdown(drain=False)
    state = "x".join(str(d) for d in rec.shape) + "xf32"
    # the kernel is traced and lowered ONCE, and called once a layer
    assert tpu.count("@tpu_custom_call") == 1
    assert tpu.count("call @retention_step(") == cfg.num_hidden_layers
    assert "jit_brumby_decode_c128_b4" in tpu
    on_state = [line for line in tpu.splitlines() if state in line]
    assert on_state
    moved = [line for line in on_state if re.search(
        r"gather|scatter|dynamic_slice|dynamic_update_slice|"
        r"stablehlo\.slice|concatenate", line)]
    assert not moved, moved[:3]
    assert "tpu_custom_call" not in cpu and "scatter" in cpu
    assert cpu.count("call @retention_step_twin(") == cfg.num_hidden_layers


# sha256 of the lowered text of the engine's LFM2 programs at lfm2-tiny
# (4 slots, pools and state donated), on the CPU with this container's jax,
# taken on the parent of the PR that brought models/brumby.py: an engine
# with no K/V pool, a state of the model's own type and a new counter went
# through the same bodies and moved neither.
LFM2_LOWERED = {
    "decode":
        "68359fa08c20d5863923fbb0c292d8b5b3ea18f26093005ff22c35cebb671449",
    "prefill":
        "d7330234d3f9e78818c76364e05c07dfb476a8600d03c7b566c6b4bb48fbdc36",
}


@pytest.mark.parametrize("kind", sorted(LFM2_LOWERED))
def test_lfm2_programs_lower_to_the_parents_text(kind):
    cfg = lfm2.PRESETS["lfm2-tiny"]
    eng = GenerativeEngine(params=({}, cfg), slots=4, warmup=False,
                           auto_start=False, donate=True)
    try:
        params = {n: _sds(shape, jnp.float32 if what == "bias"
                          else jnp.bfloat16)
                  for n, (shape, what) in lfm2.param_shapes(cfg).items()}
        cap, b, S = eng._caps[-1], eng._batch_buckets[-1], 16
        pool = kvq.aval(eng._pool_shape(cap), "bf16")
        rec = kvq.aval(eng._state_shape(), "bf16")
        i32, f32 = np.int32, np.float32
        args = _row_args(b) if kind == "decode" else (
            _sds((), i32), _sds((1, S), i32), _sds((), i32), _sds((), f32),
            _sds((), i32), _sds((), f32), _sds((2,), np.uint32))
        text = eng._program(kind, cap, S if kind == "prefill" else b).lower(
            params, pool, pool, *args, rec).as_text()
    finally:
        eng.shutdown(drain=False)
    assert f"jit_lfm2_{kind}_c128_b" in text
    assert hashlib.sha256(text.encode()).hexdigest() == LFM2_LOWERED[kind]
