"""models/mistral4.py through the serving engine, on the CPU at
`mistral4-tiny` (seeded): the engine's prefill (the expanded form) then
decode (the absorbed form over ONE latent pool) against the plain
reference's full forward pass (benchmarks/references/mistral4.py, which
imports nothing of paddle_tpu) in LOGITS — tightly in float32, looser in
bfloat16 where the precision below fails; the absorbed form against the
expanded one at ragged positions; the held experts' shares; YaRN and the
attention factor at the published numbers; the `mla_decode` kernel in
interpret mode against its twin; the held-only expert counters and the
attended positions; a planted router fault the comparison sees; every
engine feature the model refuses, by name.
"""
import copy
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import GenerativeEngine, ServingError
from paddle_tpu.models import mistral4
from paddle_tpu.observability import trace
from paddle_tpu.ops.pallas import mla_decode as mla

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(REPO,
                                                                     path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmarks/references/mistral4.py", "mistral4_reference")


def tiny(dtype="float32", **kw):
    cfg = copy.copy(mistral4.PRESETS["mistral4-tiny"])
    cfg.dtype = dtype
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


def ref_config(cfg):
    """What the reference reads of a configuration's file."""
    return {"architecture": {
        "num_hidden_layers": cfg.num_hidden_layers,
        "num_attention_heads": cfg.num_attention_heads,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "experts_held": list(cfg.experts_held), "norm_topk_prob": True,
        "routed_scaling_factor": 1, "rms_norm_eps": cfg.norm_eps,
        "rope_parameters": {
            "rope_theta": cfg.rope_theta, "factor": cfg.rope_factor,
            "original_max_position_embeddings":
                cfg.original_max_position_embeddings,
            "beta_fast": cfg.beta_fast, "beta_slow": cfg.beta_slow,
            "mscale": 1, "mscale_all_dim": cfg.mscale_all_dim,
            "llama_4_scaling_beta": cfg.llama_4_scaling_beta}}}


def params_of(cfg, seed=3):
    paddle.seed(seed)
    return mistral4.init_params(cfg)


CAP = 256


def empty_pool(cfg, rows=3):
    return jnp.zeros((rows, cfg.num_hidden_layers, CAP, cfg.latent_width),
                     jnp.dtype(cfg.dtype))


def through_the_pool(cfg, p, prompt, answer, bucket, slot=1, rows=3):
    """Logits at every answered position: a prefill of the prompt padded to
    `bucket` into `slot` of one latent pool, then one pass a token beside a
    padding row — the programs' bodies, called as the engine calls them,
    no V pool."""
    passes = cfg.serving_passes()
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :len(prompt)] = prompt
    h, pool, none, _ = jax.jit(passes.prefill)(
        p, empty_pool(cfg, rows), None, None, jnp.int32(slot),
        jnp.asarray(ids), jnp.int32(len(prompt)))
    assert none is None
    out = [passes.head(p, h)]
    step = jax.jit(passes.pool_pass, static_argnums=(7,))
    scratch = rows - 1
    for j, tok in enumerate(answer[:-1]):
        h, pool, none, _, aux = step(
            p, pool, None, None, jnp.asarray([slot, scratch], jnp.int32),
            jnp.asarray([tok, 0], jnp.int32),
            jnp.asarray([len(prompt) + j, 0], jnp.int32), scratch)
        assert none is None and aux is not None
        out.append(passes.head(p, h[0]))
    return np.asarray(jnp.stack(out), np.float32), pool


def reference_logits(cfg, p, prompt, answer, precision=None):
    ids = np.asarray(list(prompt) + list(answer[:-1]), np.int32)[None]
    lg = ref.serve_logits(p, ids, ref_config(cfg), precision=precision)
    return np.asarray(lg, np.float32)[0, len(prompt) - 1:]


PROMPT = np.random.RandomState(5).randint(0, 512, 21)
ANSWER = np.random.RandomState(6).randint(0, 512, 9)
# past the tiny configuration's original length (64): the llama-4 factor
LONG = np.random.RandomState(7).randint(0, 512, 70)
F32_TOL = 1e-4          # in standard deviations of the logits


# ------------------------------------------- the engine against the reference
@pytest.mark.parametrize("prompt, bucket", [(PROMPT, 32), (PROMPT, 64),
                                            (LONG, 128)],
                         ids=["bucket32", "bucket64", "past_original"])
def test_prefill_then_decode_agrees_with_the_full_forward_pass_f32(prompt,
                                                                   bucket):
    """float32, tightly: the expanded form over the padded prompt, the
    latent rows it stores, and the absorbed steps over them change the
    order of sums and nothing else (read: ~1e-6 of the logits' deviation)."""
    cfg = tiny("float32")
    p = params_of(cfg)
    got, _ = through_the_pool(cfg, p, prompt, ANSWER, bucket)
    want = reference_logits(cfg, p, prompt, ANSWER)
    assert got.shape == want.shape == (len(ANSWER), cfg.vocab_size)
    np.testing.assert_allclose(got, want, atol=F32_TOL * want.std(), rtol=0)


# between what bfloat16 reads and what the precision below it reads, in
# standard deviations of the logits (both are printed by the test)
BF16_LOGIT_TOL = 0.06


def test_bfloat16_is_within_a_tolerance_that_float8_fails():
    """bfloat16 weights, pool and operands: within BF16_LOGIT_TOL of the
    float32 reference over the same (bfloat16-held) weights at every
    answered position; the reference computed one precision down is not,
    and the reference with bfloat16 operands (what the program does) is."""
    cfg = tiny("bfloat16")
    p = params_of(cfg)
    got, _ = through_the_pool(cfg, p, PROMPT, ANSWER, 32)
    want = reference_logits(cfg, p, PROMPT, ANSWER)
    below = reference_logits(cfg, p, PROMPT, ANSWER, precision="float8")
    same = reference_logits(cfg, p, PROMPT, ANSWER, precision="bfloat16")
    std = want.std()
    read = np.abs(got - want).max() / std
    control = np.abs(below - want).max() / std
    print("bfloat16", read, "float8", control)
    assert read < BF16_LOGIT_TOL < control
    assert np.abs(same - want).max() / std < BF16_LOGIT_TOL


def test_absorbed_form_equals_expanded_form_at_ragged_positions():
    """Three prompts of different lengths prefilled (expanded) into three
    slots, then ONE absorbed pass of the three rows at their own positions
    beside a padding row: each row's logits are the expanded form's at
    that position (the reference over its own prompt and token)."""
    cfg = tiny("float32")
    p = params_of(cfg)
    passes = cfg.serving_passes()
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, 512, n) for n in (3, 40, 17)]
    nxt = rng.randint(0, 512, 3)
    pool = empty_pool(cfg, rows=5)
    prefill = jax.jit(passes.prefill)
    for slot, prompt in enumerate(prompts):
        ids = np.zeros((1, 64), np.int32)
        ids[0, :len(prompt)] = prompt
        _, pool, _, _ = prefill(p, pool, None, None, jnp.int32(slot),
                                jnp.asarray(ids), jnp.int32(len(prompt)))
    scratch = 4
    h, _, _, _, _ = jax.jit(passes.pool_pass, static_argnums=(7,))(
        p, pool, None, None, jnp.asarray([2, scratch, 0, 1], jnp.int32),
        jnp.asarray([nxt[2], 0, nxt[0], nxt[1]], jnp.int32),
        jnp.asarray([17, 0, 3, 40], jnp.int32), scratch)
    got = np.asarray(passes.head(p, h), np.float32)[[2, 3, 0]]
    for row, (prompt, tok) in enumerate(zip(prompts, nxt)):
        want = reference_logits(cfg, p, prompt, [tok, 0])[1]
        np.testing.assert_allclose(got[row], want, atol=F32_TOL * want.std())


@pytest.mark.parametrize("fault", ["sigmoid_router", "no_attention_factor",
                                   "no_shared_expert"])
def test_a_fault_planted_in_the_program_fails_the_comparison(monkeypatch,
                                                             fault):
    """The float32 comparison with a fault planted in the program: the
    router's softmax replaced by a sigmoid (the other family's scoring),
    the attention factor left at 1/sqrt(128) without m², the shared expert
    left out. Each reads far over the float32 tolerance."""
    cfg = tiny("float32")
    p = params_of(cfg)
    want = reference_logits(cfg, p, PROMPT, ANSWER)
    if fault == "sigmoid_router":
        def route(x, gate_w, cfg):
            s = jax.nn.sigmoid(jnp.dot(
                x.astype(jnp.float32), gate_w.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            w, sel = jax.lax.top_k(s, cfg.num_experts_per_tok)
            return sel.astype(jnp.int32), w / w.sum(-1, keepdims=True)
        monkeypatch.setattr(mistral4, "route", route)
    elif fault == "no_attention_factor":
        monkeypatch.setattr(mistral4, "attention_factor", lambda cfg: 1.0
                            / math.sqrt(cfg.qk_nope_head_dim
                                        + cfg.qk_rope_head_dim))
    else:
        monkeypatch.setattr(mistral4, "dense_ffn",
                            lambda x, p, i: jnp.zeros_like(x))
    got, _ = through_the_pool(cfg, p, PROMPT, ANSWER, 32)
    read = np.abs(got - want).max() / want.std()
    print(fault, read)
    assert read > 10 * F32_TOL


# -------------------------------------------------------------- the experts
@pytest.mark.parametrize("decode", [True, False], ids=["batched", "grouped"])
@pytest.mark.parametrize("holders", [2, 4, 8])
def test_the_holders_shares_add_up_to_the_uncut_layer(holders, decode):
    """Each of `holders` chips holds 8/holders of the routed
    experts, routes over all 8 and computes its own experts' part beside
    the shared expert; the routed parts add up to the uncut layer's, the
    shared expert counted once — in the decode step's batched layout and
    in the prefill's grouped one, each against the reference's."""
    cfg = tiny("float32")
    p = params_of(cfg)
    i = 1
    rng = np.random.RandomState(4)
    h = jnp.asarray(rng.standard_normal((40, cfg.hidden_size)), jnp.float32)
    names = ("ffn_norm", "gate_w", "e_w1", "e_w3", "e_w2", "w1", "w3", "w2")
    z = ref.sizes(ref_config(cfg))
    n = rms_norm_of(cfg, p, i, h)
    whole = ref.expert_layer(n, {k: p[f"{i}.{k}"] for k in names}, z)
    shared = ref.swiglu(n, p[f"{i}.w1"], p[f"{i}.w3"], p[f"{i}.w2"])
    count = cfg.n_routed_experts // holders
    total = shared
    for j in range(holders):
        held = tiny("float32", experts_held=(j * count, count))
        part = dict(p)
        for name in ("e_w1", "e_w3", "e_w2"):
            part[f"{i}.{name}"] = p[f"{i}.{name}"][j * count:(j + 1) * count]
        out, counts = held.serving_passes()._ffn(h, part, i, decode)
        assert counts.shape == (count,)
        total = total + (out - shared)
        zj = ref.sizes(ref_config(held))
        same = ref.expert_layer(n, {k: part[f"{i}.{k}"] for k in names}, zj)
        np.testing.assert_allclose(out, same, atol=2e-5)
    np.testing.assert_allclose(total, whole, atol=5e-5)


def rms_norm_of(cfg, p, i, h):
    return mistral4.rms_norm(h, p[f"{i}.ffn_norm"], cfg.norm_eps)


def test_grouped_experts_take_passes_past_one_block():
    """A prefill's assignments sorted by held expert, in passes of a few
    rows: runs longer than a pass, empty experts, the last pass padded —
    the same as the batched layout."""
    cfg = tiny("float32", experts_held=(2, 4))
    rng = np.random.RandomState(9)
    T, D, F = 50, cfg.hidden_size, cfg.moe_intermediate_size
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    w1, w3 = (jnp.asarray(rng.standard_normal((4, D, F)) * 0.1, jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(rng.standard_normal((4, F, D)) * 0.1, jnp.float32)
    sel = jnp.asarray(np.stack([rng.choice(8, 2, replace=False)
                                for _ in range(T)]), jnp.int32)
    sel = sel.at[:, 0].set(jnp.where(jnp.arange(T) < 30, 3, sel[:, 0]))
    sel = sel.at[:30, 1].set(jnp.where(sel[:30, 1] == 3, 7, sel[:30, 1]))
    w = jnp.asarray(rng.uniform(0.1, 1.0, (T, 2)), jnp.float32)
    got = mistral4.grouped_experts(x, w1, w3, w2, sel, w, cfg, block=8)
    want = mistral4.experts(x, w1, w3, w2, sel, w, cfg)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


# ------------------------------------------------------ rotation and factors
def test_yarn_and_the_attention_factor_at_the_published_numbers():
    """The published rope_parameters (64 rotary dims, θ 1e4, factor 128,
    original 8192, β 32 / 1): the correction dims are 12 and 25 — below 12
    the frequency is θ^(-2i/64), from 25 on it is that over 128, a linear
    blend between — and σ = (1 + 0.1 ln 128)² / √128."""
    cfg = mistral4.PRESETS["mistral-small-4-119b"]
    low = math.floor(64 * math.log(8192 / (32 * 2 * math.pi))
                     / (2 * math.log(1e4)))
    high = math.ceil(64 * math.log(8192 / (1 * 2 * math.pi))
                     / (2 * math.log(1e4)))
    assert (low, high) == (12, 25)
    base = np.array([1e4 ** (-2 * i / 64) for i in range(32)])
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    want = base * (1 - ramp) + base / 128 * ramp
    np.testing.assert_allclose(mistral4.yarn_inv_freq(cfg), want, rtol=1e-6)
    np.testing.assert_allclose(ref.yarn_frequencies(64, 1e4, 128, 8192, 32, 1),
                               want, rtol=1e-6)
    m2 = (1 + 0.1 * math.log(128)) ** 2
    assert abs(m2 - 2.20583) < 1e-5
    assert mistral4.attention_factor(cfg) == pytest.approx(m2 / math.sqrt(128))


def test_the_llama4_factor_past_the_original_length():
    cfg = tiny("float32")           # original length 64
    pos = jnp.asarray([0, 63, 64, 127, 128, 200])
    got = mistral4.llama4_scale(pos, cfg)
    want = [1, 1, 1 + 0.1 * math.log(2), 1 + 0.1 * math.log(2),
            1 + 0.1 * math.log(3), 1 + 0.1 * math.log(4)]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    big = mistral4.PRESETS["mistral-small-4-119b"]
    assert float(mistral4.llama4_scale(jnp.asarray(8191), big)) == 1.0


def test_rotation_is_pairwise_and_keeps_dot_products_by_offset():
    """Pairs (2i, 2i+1) rotate together; the scores depend on the offset
    between two positions alone."""
    inv = jnp.asarray([1.0, 0.1], jnp.float32)
    x = jnp.asarray([1.0, 0.0, 0.0, 1.0], jnp.float32)
    got = mistral4.rope(x[None], jnp.asarray([2]), inv)[0]
    np.testing.assert_allclose(got, [math.cos(2), -math.sin(0.2),
                                     math.sin(2), math.cos(0.2)], atol=1e-6)
    rng = np.random.RandomState(0)
    q, k = (jnp.asarray(rng.standard_normal(4), jnp.float32)
            for _ in range(2))

    def score(a, b):
        return float(mistral4.rope(q[None], jnp.asarray([a]), inv)[0]
                     @ mistral4.rope(k[None], jnp.asarray([b]), inv)[0])

    assert score(7, 3) == pytest.approx(score(104, 100), rel=1e-4)


# ------------------------------------------------------------------ the kernel
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["4-byte", "2-byte"])
@pytest.mark.parametrize("slots", [(1, 3, 0, 4), (4, 2, 4, 0), (4, 4, 4, 4)],
                         ids=["padding_inside", "padding_first",
                              "all_padding"])
def test_mla_decode_in_interpret_mode_against_its_twin(slots, dtype):
    """Interpret mode: 8 heads over a latent of 128 and a rotary key padded
    to 256 lanes, blocks of 16 positions, rows of ragged lengths (a row at
    its first position, one at a block's edge, one at the cap's end), NaN
    in every position past a row's length (it must not reach the output);
    a padding row (the scratch slot, 4) computes nothing and reads nought."""
    rows, L, cap, W, C, H, scratch = 5, 2, 64, 256, 128, 8, 4
    rng = np.random.RandomState(0)
    pool = rng.standard_normal((rows, L, cap, W)).astype(np.float32)
    pool[..., C + 64:] = 0
    lengths = np.asarray([0, 15, 63, 40], np.int32)
    for s, n in zip(slots, lengths):
        if s != scratch:
            pool[s, 1, n + 1:] = np.nan
    pool = jnp.asarray(pool, dtype)
    q = jnp.asarray(rng.standard_normal((4, H, W)), dtype)
    q = q.at[..., C + 64:].set(0)
    at = jnp.asarray(slots, jnp.int32)
    kw = dict(value_width=C, sm_scale=0.2, scratch=scratch)
    kern = mla.mla_decode(q, pool, 1, at, jnp.asarray(lengths),
                          interpret=True, block=16, **kw)
    twin = mla.mla_decode_twin(q, pool, 1, at, jnp.asarray(lengths), **kw)
    assert kern.shape == (4, H, C) and kern.dtype == jnp.float32
    assert not np.isnan(np.asarray(kern)).any()
    assert not np.isnan(np.asarray(twin)).any()
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(kern, twin, atol=tol, rtol=tol)
    for i, s in enumerate(slots):
        if s == scratch:
            assert not np.asarray(kern[i]).any()
    # by hand, a row at its first position: its own latent, every head
    if slots[0] != scratch:
        np.testing.assert_allclose(
            kern[0], np.broadcast_to(np.asarray(pool[slots[0], 1, 0, :C],
                                                np.float32), (H, C)),
            atol=tol, rtol=tol)


def test_latent_block_plan_serves_the_cells_pool():
    plan = mla.latent_block_plan(8192, 32, 384, 256, 2)
    assert plan.block == 1024 and plan.positions_read(0, 8192) == 1024
    assert plan.positions_read(3000, 8192) == 3072
    assert plan.positions_read(9000, 8192) == 8192
    # a row or a latent that is no whole number of lane tiles
    assert mla.latent_block_plan(8192, 32, 320, 256, 2) is None
    assert mla.latent_block_plan(8192, 32, 384, 192, 2) is None
    assert mla.latent_block_plan(12, 32, 384, 256, 2) is None
    cfg = mistral4.PRESETS["mistral-small-4-119b"]
    assert cfg.latent_width == 384
    assert cfg.serving_passes().read_plan(8192) == plan


# ------------------------------------------------------- through the engine
def test_engine_serves_what_the_reference_puts_first():
    """Through `build_generator`, the worker loop and the HTTP-less front:
    every served greedy token is the reference's own first choice,
    teacher-forced over prompt + answer (the benchmark's comparison); the
    engine says what cache is there — one latent pool, no V pool."""
    from paddle_tpu.inference.serve import build_generator, generate_presets

    assert generate_presets()["mistral4-tiny"] is mistral4
    assert generate_presets()["mistral-small-4-119b"] is mistral4
    eng = build_generator("mistral4-tiny", slots=4, max_new_tokens_cap=16)
    try:
        cfg, p = eng._cfg, eng._params
        assert cfg is mistral4.PRESETS["mistral4-tiny"]
        assert p["0.kv_b"].dtype == jnp.bfloat16
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, cfg.vocab_size, n)
                   for n in (5, 17, 1, 9, 40, 2)]
        handles = [eng.submit(q, 12) for q in prompts]
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
    shape = [5, 2, 256, 128]
    assert report["cache"] == {"model": "mistral4", "kv_pool": {},
                               "latent_pool": {256: shape}, "state": None}
    assert cache_bytes == 2 * int(np.prod(shape))
    assert snap["kv_pool"]["pool_bytes"] == cache_bytes
    assert memory["temp_bytes"] >= 0 and memory["argument_bytes"] > 0
    assert all(name.startswith(("prefill[", "decode[", "join["))
               for name in report["programs"])
    # the expert counters are the HELD experts' (here all 8)
    assert len(snap["moe_expert_tokens"]) == 8
    assert snap["moe_assignments_total"] == sum(snap["moe_expert_tokens"])
    assert "paddle_generate_attended_positions_total" in text


def _serve(cfg, p, prompts, new, **kw):
    eng = GenerativeEngine(params=(p, cfg), warmup=False,
                           max_new_tokens_cap=new, **kw)
    try:
        return [eng.generate(q, new, timeout=300)["tokens"]
                for q in prompts], eng.metrics.snapshot()
    finally:
        eng.shutdown()


def test_the_counters_are_the_held_experts_and_the_attended_positions():
    """One request at a time: a step's real row attends every position up
    to its own, whatever the read copies; a holder of 4 of the 8 experts
    counts the assignments to ITS experts and the distinct ones of them a
    layer-step hits, never more than it holds."""
    cfg = tiny("float32", experts_held=(2, 4))
    p = params_of(cfg)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 512, n) for n in (7, 30)]
    new = 6
    _, snap = _serve(cfg, p, prompts, new, slots=1)
    # steps 1 .. new-1 of a row attend positions 0 .. P + j, j = 0 .. new-2
    want = sum(P + j + 1 for P in (7, 30) for j in range(new - 1))
    assert snap["attended_positions_total"] == want
    layers, k = cfg.num_hidden_layers, cfg.num_experts_per_tok
    assert len(snap["moe_expert_tokens"]) == 4
    assert snap["moe_layer_steps_total"] == snap["steps_total"] * layers
    assert 0 < snap["moe_assignments_total"] <= \
        snap["step_rows_total"] * k * layers
    assert snap["moe_distinct_experts_total"] <= \
        min(4, k) * snap["moe_layer_steps_total"]


def test_a_steps_counts_are_its_held_share_of_the_routing():
    """One pass of two real rows beside padding: the per-expert counts are
    the held columns of the routing's one-hot, over the real rows alone."""
    cfg = tiny("float32", experts_held=(4, 4))
    p = params_of(cfg)
    passes = cfg.serving_passes()
    scratch = 3
    slots = jnp.asarray([0, 2, scratch, scratch], jnp.int32)
    _, _, _, _, (per_expert, distinct) = passes.pool_pass(
        p, empty_pool(cfg, 4), None, None, slots,
        jnp.asarray([1, 2, 0, 0], jnp.int32),
        jnp.asarray([0, 3, 0, 0], jnp.int32), scratch)
    all_held = tiny("float32")
    assert per_expert.shape == (4,)
    assert int(per_expert.sum()) <= 2 * cfg.num_experts_per_tok \
        * cfg.num_hidden_layers
    assert int(distinct) <= 4 * cfg.num_hidden_layers
    # the same routing counted by a holder of all 8: ours are its columns
    sel = mistral4.route(jnp.ones((2, cfg.hidden_size)), p["0.gate_w"],
                         all_held)[0]
    np.testing.assert_array_equal(
        mistral4.held_counts(sel, cfg),
        mistral4.held_counts(sel, all_held)[4:])


def test_a_reused_slot_starts_clean_through_the_engine():
    cfg = tiny("float32")
    p = params_of(cfg)
    rng = np.random.RandomState(2)
    first, second = rng.randint(0, 512, 30), rng.randint(0, 512, 7)
    assert _serve(cfg, p, [first, second], 8, slots=1)[0][1] == \
        _serve(cfg, p, [second], 8, slots=1)[0][0]


# --------------------------------------------------------------- refusals
@pytest.mark.parametrize("feature, kw", [
    ("prefix_cache_slots", {"prefix_cache_slots": 2}),
    ("draft", {"draft_params": "a draft"}),
    ("kv_dtype=int8", {"kv_dtype": "int8"}),
    ("quantize_weights", {"quantize_weights": True}),
])
def test_what_the_latent_pool_cannot_take_is_refused_by_name(feature, kw):
    cfg = tiny("float32")
    with pytest.raises(ValueError) as e:
        GenerativeEngine(params=({}, cfg), warmup=False, auto_start=False,
                         **kw)
    assert "mistral4" in str(e.value) and feature in str(e.value)


def test_the_handoff_plane_is_refused_at_the_call():
    cfg = tiny("float32")
    eng = GenerativeEngine(params=(params_of(cfg), cfg), slots=2,
                           warmup=True, auto_start=False)
    try:
        assert not any(name.startswith(("kvget", "kvput", "pcopy"))
                       for name in eng.program_report()["programs"])
        for call in (lambda: eng.submit([1, 2], 4, prefill_only=True),
                     lambda: eng.import_handoff(b"whatever")):
            with pytest.raises(ServingError) as e:
                call()
            assert e.value.status == 409
            assert "mistral4" in str(e.value) and "handoff" in str(e.value)
    finally:
        eng.shutdown(drain=False)


# ------------------------------------------------------ spans and scopes
def test_scopes_are_noted_by_program_and_the_step_says_what_it_attends(
        tmp_path):
    cfg = tiny("float32")
    p = params_of(cfg)
    paddle.set_flags({"FLAGS_trace_dir": str(tmp_path)})
    trace.reset()
    try:
        eng = GenerativeEngine(params=(p, cfg), slots=2,
                               max_new_tokens_cap=8,
                               prompt_boundaries=[8, 16])
        try:
            eng.generate([3, 4, 5], 6, timeout=300)
        finally:
            eng.shutdown()
        by_program = trace.op_scopes_by_program()
        spans = trace.spans()
    finally:
        paddle.set_flags({"FLAGS_trace_dir": ""})
        trace.reset()
    assert {"jit_mistral4_decode_c256_b1", "jit_mistral4_decode_c256_b2",
            "jit_mistral4_prefill_c256_b8"} <= set(by_program)
    for program, names in by_program.items():
        scopes = " ".join(names.values())
        assert program.startswith("jit_mistral4_")
        for scope in ("mla.attention", "moe.route", "moe.experts",
                      "moe.shared"):
            assert (scope in scopes) == ("_join_" not in program), \
                (program, scope)
    attended = [s["args"]["attended"] for s in spans
                if s["name"] == "generate.decode_step"]
    assert attended == list(range(4, 4 + len(attended)))


def test_the_seed_reaches_the_initialisers_and_the_counts_are_published():
    cfg = tiny("bfloat16")
    a, b, c = params_of(cfg, 1), params_of(cfg, 1), params_of(cfg, 2)
    assert set(a) == set(mistral4.param_shapes(cfg))
    for name, (shape, _) in mistral4.param_shapes(cfg).items():
        assert a[name].shape == shape and a[name].dtype == jnp.bfloat16
        assert bool((a[name] == b[name]).all())
        assert not bool((a[name] == c[name]).all())
    big = mistral4.PRESETS["mistral-small-4-119b"]
    layer = sum(int(np.prod(s)) for n, (s, _) in
                mistral4.param_shapes(big).items() if n.startswith("0."))
    expert = 3 * 4096 * 2048
    assert layer == 456_402_176 == 53_748_992 + 16 * expert
    assert mistral4.n_params(big) == 8 * layer + 2 * 16384 * 4096 + 4096 \
        == 3_785_439_232
    # the published model: 36 layers of 128 experts, the whole vocabulary
    assert 36 * (53_748_992 + 128 * expert) + 2 * 131072 * 4096 + 4096 \
        == 118_972_826_624
    with pytest.raises(ValueError, match="one shared expert"):
        mistral4.Mistral4Config(n_shared_experts=2)
