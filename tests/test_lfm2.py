"""models/lfm2.py through the serving engine, on the CPU at `lfm2-tiny`
(seeded): the engine's prefill-then-decode through both kinds of cache
against the plain reference's full forward pass (benchmarks/references/
lfm2.py) in LOGITS; the parts of the block one by one; the cache by layer
type; the expert layer's shares; the grouped-query decode kernel against
its twin; every engine feature the model refuses, by name; and the GPT
programs' lowered text, which this model's arrival must not have moved.
"""
import copy
import hashlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import GenerativeEngine, ServingError
from paddle_tpu.inference.serving import generate as gen
from paddle_tpu.models import lfm2
from paddle_tpu.observability import trace
from paddle_tpu.ops.pallas import decode_attention as da
from paddle_tpu.quantization import kv as kvq

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(REPO,
                                                                     path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmarks/references/lfm2.py", "lfm2_reference")


def tiny(dtype="float32", **kw):
    cfg = copy.copy(lfm2.PRESETS["lfm2-tiny"])
    cfg.dtype = dtype
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


def ref_config(cfg):
    """What the reference reads of a configuration's file."""
    return {"architecture": {
        "layer_types": list(cfg.layer_types),
        "num_dense_layers": cfg.num_dense_layers,
        "num_attention_heads": cfg.num_attention_heads,
        "num_key_value_heads": cfg.num_key_value_heads,
        "norm_eps": cfg.norm_eps, "num_experts": cfg.num_experts,
        "rope_parameters": {"rope_theta": cfg.rope_theta},
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "norm_topk_prob": cfg.norm_topk_prob,
        "experts_held": list(cfg.experts_held)}}


def params_of(cfg, seed=3):
    paddle.seed(seed)
    return lfm2.init_params(cfg)


def cache_of(passes, rows, cap, dtype):
    cfg = passes.cfg
    pool = jnp.zeros((rows, passes.kv_layers, cap,
                      passes.kv_heads * cfg.head_dim), dtype)
    return pool, pool, jnp.zeros(passes.state_shape(rows), dtype)


def through_the_cache(cfg, p, prompt, answer, bucket, cap=64, slot=1,
                      rows=3, dirty=False):
    """Logits at every answered position: a prefill of the prompt padded
    to `bucket` into `slot`, then one pass a token — the programs' bodies,
    called as the engine calls them."""
    passes = cfg.serving_passes()
    dtype = jnp.dtype(cfg.dtype)
    buf_k, buf_v, rec = cache_of(passes, rows, cap, dtype)
    if dirty:           # what an earlier owner of the slot left behind
        buf_k, buf_v, rec = buf_k + 3, buf_v - 2, rec + 5
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :len(prompt)] = prompt
    h, buf_k, buf_v, rec = jax.jit(passes.prefill)(
        p, buf_k, buf_v, rec, jnp.int32(slot), jnp.asarray(ids),
        jnp.int32(len(prompt)))
    out = [passes.head(p, h)]
    step = jax.jit(passes.pool_pass, static_argnums=(7,))
    scratch = rows - 1
    for j, tok in enumerate(answer[:-1]):
        # the row beside a padding row, as a decode bucket has them
        h, buf_k, buf_v, rec, _ = step(
            p, buf_k, buf_v, rec, jnp.asarray([slot, scratch], jnp.int32),
            jnp.asarray([tok, 0], jnp.int32),
            jnp.asarray([len(prompt) + j, 0], jnp.int32), scratch)
        out.append(passes.head(p, h[0]))
    return np.asarray(jnp.stack(out), np.float32), (buf_k, buf_v, rec)


def reference_logits(cfg, p, prompt, answer, precision=None):
    ids = np.asarray(list(prompt) + list(answer[:-1]), np.int32)[None]
    lg = ref.serve_logits(p, ids, ref_config(cfg), precision=precision)
    return np.asarray(lg, np.float32)[0, len(prompt) - 1:]


PROMPT = np.random.RandomState(5).randint(0, 512, 21)
ANSWER = np.random.RandomState(6).randint(0, 512, 9)


# ------------------------------------------- the engine against the reference
@pytest.mark.parametrize("bucket", [32, 64], ids=["bucket32", "bucket64"])
def test_prefill_then_decode_agrees_with_the_full_forward_pass_f32(bucket):
    """float32, tightly: the cache (K/V rows and conv state) and the padded
    bucket, one that just holds the prompt and one twice its size, change
    the order of sums and nothing else."""
    cfg = tiny("float32")
    p = params_of(cfg)
    got, _ = through_the_cache(cfg, p, PROMPT, ANSWER, bucket)
    want = reference_logits(cfg, p, PROMPT, ANSWER)
    assert got.shape == want.shape == (len(ANSWER), cfg.vocab_size)
    np.testing.assert_allclose(got, want, atol=2e-5 * want.std(), rtol=0)


# between what bfloat16 reads and what the precision below it reads, in
# standard deviations of the logits (both are printed by the test)
BF16_LOGIT_TOL = 0.06


def test_bfloat16_is_within_a_tolerance_that_float8_fails():
    """bfloat16 weights, pool and state, bfloat16 operands: within
    BF16_LOGIT_TOL of the float32 reference over the same (bfloat16-held)
    weights at every answered position; the reference computed one
    precision down — float8 operands — is not."""
    cfg = tiny("bfloat16")
    p = params_of(cfg)
    got, _ = through_the_cache(cfg, p, PROMPT, ANSWER, 32)
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


def test_engine_serves_what_the_reference_puts_first():
    """Through `build_generator`, the worker loop and the HTTP-less front:
    every served greedy token is the reference's own first choice,
    teacher-forced over prompt + answer (the benchmark's comparison)."""
    from paddle_tpu.inference.serve import build_generator, generate_presets

    assert generate_presets()["lfm2-tiny"] is lfm2
    assert "gpt3-tiny" in generate_presets()
    eng = build_generator("lfm2-tiny", slots=4, max_new_tokens_cap=16)
    try:
        cfg, p = eng._cfg, eng._params
        assert cfg is lfm2.PRESETS["lfm2-tiny"]
        assert p["0.conv_in"].dtype == jnp.bfloat16
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, cfg.vocab_size, n)
                   for n in (5, 17, 1, 9, 40, 2)]
        answers = [h.result(300)["tokens"]
                   for h in [eng.submit(q, 12) for q in prompts]]
        snap = eng.metrics.snapshot()
        report = eng.program_report()
        text = eng.metrics.prometheus_text()
        pool_bytes = eng.kv_pool_bytes()
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
    # per-expert counts: every real row of every step, k experts in each
    # of the expert layers
    layers, k = len(cfg.expert_layers), cfg.num_experts_per_tok
    assert snap["moe_assignments_total"] == \
        snap["step_rows_total"] * k * layers
    assert sum(snap["moe_expert_tokens"]) == snap["moe_assignments_total"]
    assert len(snap["moe_expert_tokens"]) == cfg.num_experts
    assert snap["moe_layer_steps_total"] == snap["steps_total"] * layers
    assert 0 < snap["moe_distinct_experts_total"] <= \
        snap["moe_layer_steps_total"] * cfg.num_experts
    assert "paddle_generate_moe_assignments_total" in text
    assert 'paddle_generate_moe_expert_tokens_total{expert="7"}' in text
    # both kinds of cache, said
    assert report["cache"] == {
        "model": "lfm2", "kv_pool": {128: [5, 1, 128, 32]},
        "state": [5, 4, 2, 64]}
    assert report["kv_dtype"] == "bf16"
    assert pool_bytes == 2 * (2 * 5 * 128 * 32) + 2 * (5 * 4 * 2 * 64)
    assert memory["temp_bytes"] >= 0 and memory["argument_bytes"] > 0
    assert all(name.startswith(("prefill[", "decode["))
               for name in report["programs"])


def test_the_seed_reaches_the_initialisers():
    """`paddle.seed`'s state is where every array is drawn from — what the
    benchmark's --seed swaps — and nothing is float32 but the bias."""
    cfg = tiny("bfloat16")
    a, b, c = params_of(cfg, 1), params_of(cfg, 1), params_of(cfg, 2)
    assert set(a) == set(lfm2.param_shapes(cfg))
    for name, (shape, kind) in lfm2.param_shapes(cfg).items():
        assert a[name].shape == shape
        assert a[name].dtype == (jnp.float32 if kind == "bias"
                                 else jnp.bfloat16)
        assert bool((a[name] == b[name]).all())
        assert not bool((a[name] == c[name]).all())
    assert sum(int(np.prod(v.shape)) for v in a.values()) == \
        lfm2.n_params(cfg)
    big = lfm2.PRESETS["lfm2-24b-a2b"]
    assert lfm2.n_params(big) == 5_177_950_976
    assert lfm2.n_params(big, active=True) == 648_102_656


# --------------------------------------------------- the short convolution
def test_conv_step_by_step_equals_the_whole_sequence():
    rng = np.random.RandomState(1)
    z = jnp.asarray(rng.standard_normal((11, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((8, 3)), jnp.float32)
    whole = lfm2.short_conv_seq(z, w)
    # by hand: c_t = w0 z_{t-2} + w1 z_{t-1} + w2 z_t, z_{<0} = 0
    zn, wn = np.asarray(z), np.asarray(w)
    for t in range(11):
        by_hand = sum(wn[:, j] * zn[t - 2 + j] for j in range(3)
                      if t - 2 + j >= 0)
        np.testing.assert_allclose(whole[t], by_hand, atol=1e-6)
    state = jnp.zeros((1, 2, 8), jnp.float32)
    for t in range(11):
        c, state = lfm2.short_conv_step(z[t][None], state, w)
        np.testing.assert_allclose(c[0], whole[t], atol=1e-6)
        np.testing.assert_array_equal(state[0, 1], z[t])
    for length in (1, 2, 7, 11):
        want = np.zeros((2, 8), np.float32)
        for j, at in enumerate((length - 2, length - 1)):
            if at >= 0:
                want[j] = zn[at]
        np.testing.assert_array_equal(
            lfm2.state_after(z, jnp.int32(length), 3), want)


def test_padded_prefill_keeps_the_state_of_the_last_real_positions():
    """The same prompt in buckets of 32 and 64, into a clean slot and into
    one an earlier request left dirty: one state, one K/V prefix, and the
    logits that follow."""
    cfg = tiny("float32")
    p = params_of(cfg)
    a, (ka, _, ra) = through_the_cache(cfg, p, PROMPT, ANSWER[:3], 32)
    b, (kb, _, rb) = through_the_cache(cfg, p, PROMPT, ANSWER[:3], 64)
    c, (kc, _, rc) = through_the_cache(cfg, p, PROMPT, ANSWER[:3], 32,
                                       dirty=True)
    n = len(PROMPT) + 2
    np.testing.assert_allclose(ra[1], rb[1], atol=1e-6)
    np.testing.assert_array_equal(ra[1], rc[1])
    np.testing.assert_allclose(ka[1, :, :n], kb[1, :, :n], atol=1e-6)
    np.testing.assert_array_equal(ka[1, :, :n], kc[1, :, :n])
    np.testing.assert_allclose(a, b, atol=1e-5)
    np.testing.assert_array_equal(a, c)
    assert float(jnp.abs(ra[1]).max()) > 0
    # the other slots were not touched (the dirty ones kept their dirt)
    np.testing.assert_array_equal(ra[0], 0)
    np.testing.assert_array_equal(rc[0], 5)


def test_a_reused_slot_starts_clean_through_the_engine():
    """One slot, two requests one after the other: the second's answer is
    what a fresh engine gives it."""
    cfg = tiny("float32")
    p = params_of(cfg)
    rng = np.random.RandomState(2)
    first, second = rng.randint(0, 512, 30), rng.randint(0, 512, 7)

    def serve(prompts):
        eng = GenerativeEngine(params=(p, cfg), slots=1, warmup=False,
                               max_new_tokens_cap=10)
        try:
            return [eng.generate(q, 10, timeout=300)["tokens"]
                    for q in prompts]
        finally:
            eng.shutdown()

    assert serve([first, second])[1] == serve([second])[0]


def test_a_row_past_its_class_cap_lands_in_scratch():
    cfg = tiny("float32")
    p = params_of(cfg)
    passes = cfg.serving_passes()
    cap, rows = 16, 3
    buf_k, buf_v, rec = cache_of(passes, rows, cap, jnp.float32)
    buf_k, buf_v, rec = buf_k + 1.0, buf_v + 1.0, rec + 1.0
    _, k2, v2, rec2, _ = passes.pool_pass(
        p, buf_k, buf_v, rec, jnp.asarray([0], jnp.int32),
        jnp.asarray([7], jnp.int32), jnp.asarray([cap], jnp.int32), 2)
    for before, after in ((buf_k, k2), (buf_v, v2), (rec, rec2)):
        np.testing.assert_array_equal(after[:2], before[:2])
        assert not np.array_equal(after[2], before[2])


# ------------------------------------------------------------ the experts
def test_the_bias_chooses_and_never_weighs():
    cfg = tiny("float32", num_experts=16, num_experts_per_tok=4)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.standard_normal((256, cfg.hidden_size)), jnp.float32)
    gate = jnp.asarray(rng.standard_normal((cfg.hidden_size, 16)) * 0.02
                       * np.sqrt(cfg.hidden_size) / 8, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(16) * lfm2.BIAS_STD, jnp.float32)
    sel0, w0 = lfm2.route(x, gate, jnp.zeros(16), cfg)
    sel1, w1 = lfm2.route(x, gate, bias, cfg)
    moved = np.mean([set(a) != set(b) for a, b in
                     zip(np.asarray(sel0).tolist(),
                         np.asarray(sel1).tolist())])
    print("share of tokens whose chosen set the bias changed:", moved)
    assert 0.05 < moved < 0.95
    # the weights are the chosen experts' own scores, normalised: the bias
    # is nowhere in them
    s = np.asarray(jax.nn.sigmoid(x @ gate))
    picked = np.take_along_axis(s, np.asarray(sel1), -1)
    np.testing.assert_allclose(
        w1, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-5)
    # by hand, a sort: the k largest of score + bias
    by_hand = np.argsort(-(s + np.asarray(bias)), -1, kind="stable")[:, :4]
    assert [set(r) for r in by_hand.tolist()] == \
        [set(r) for r in np.asarray(sel1).tolist()]


@pytest.mark.parametrize("tokens", [6, 40], ids=["6tokens", "40tokens"])
@pytest.mark.parametrize("holders", [1, 2, 4, 8])
def test_the_holders_shares_add_up_to_the_uncut_layer(holders, tokens):
    """The guide's §4: each of `holders` chips holds 8/holders of the
    experts, routes over all 8 and computes its own experts' part; the
    parts add up to what the uncut reference gives for the whole layer,
    at a decode step's few rows and at a prefill's many."""
    cfg = tiny("float32")
    p = params_of(cfg)
    i = cfg.expert_layers[0]
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.standard_normal((tokens, cfg.hidden_size)),
                    jnp.float32)
    names = ("gate_w", "expert_bias", "e_w1", "e_w3", "e_w2")
    gate_w, bias, w1, w3, w2 = (p[f"{i}.{n}"] for n in names)
    whole = ref.expert_ffn(x[None], gate_w, bias, w1, w3, w2,
                           top_k=cfg.num_experts_per_tok, scale=1.0,
                           norm=True)[0]
    n = cfg.num_experts // holders
    total, counted = 0.0, 0
    for j in range(holders):
        held = tiny("float32", experts_held=(j * n, n))
        part = dict(p)
        for name in ("e_w1", "e_w3", "e_w2"):
            part[f"{i}.{name}"] = p[f"{i}.{name}"][j * n:(j + 1) * n]
        out, counts = lfm2.expert_ffn(x, part, i, held)
        total = total + out
        counted = counts            # every holder counts the whole routing
        # the reference, given the same share, gives the same part
        same = ref.expert_ffn(
            x[None], gate_w, bias, *(part[f"{i}.{m}"] for m in
                                     ("e_w1", "e_w3", "e_w2")),
            top_k=cfg.num_experts_per_tok, scale=1.0, norm=True,
            first=j * n)[0]
        np.testing.assert_allclose(out, same, atol=2e-6)
    np.testing.assert_allclose(total, whole, atol=5e-6)
    assert int(counted.sum()) == tokens * cfg.num_experts_per_tok


def test_a_steps_counts_are_its_real_rows_times_k_times_expert_layers():
    cfg = tiny("float32")
    p = params_of(cfg)
    passes = cfg.serving_passes()
    buf_k, buf_v, rec = cache_of(passes, 4, 16, jnp.float32)
    scratch = 3
    slots = jnp.asarray([0, 2, scratch, scratch], jnp.int32)
    _, _, _, _, (per_expert, distinct) = passes.pool_pass(
        p, buf_k, buf_v, rec, slots, jnp.asarray([1, 2, 0, 0], jnp.int32),
        jnp.asarray([0, 3, 0, 0], jnp.int32), scratch)
    layers, k = len(cfg.expert_layers), cfg.num_experts_per_tok
    assert per_expert.shape == (cfg.num_experts,)
    assert int(per_expert.sum()) == 2 * k * layers
    assert k * 1 <= int(distinct) <= min(2 * k, cfg.num_experts) * layers


# ------------------------------------------ the grouped-query decode kernel
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["4-byte", "2-byte"])
def test_decode_attn_with_grouped_heads_against_its_twin(dtype):
    """Interpret mode: 8 query heads on 2 K/V heads of 64 (fold [8, 128]),
    rows of staggered lengths over blocks of 16, repeats of one slot."""
    H, Hkv, Dh, cap, L = 8, 2, 64, 64, 3
    rng = np.random.RandomState(7)
    pool_k = jnp.asarray(rng.standard_normal((5, L, cap, Hkv * Dh)), dtype)
    pool_v = jnp.asarray(rng.standard_normal((5, L, cap, Hkv * Dh)), dtype)
    q = jnp.asarray(rng.standard_normal((4, 1, H, Dh)), jnp.float32)
    slots = jnp.asarray([3, 0, 4, 4], jnp.int32)
    pos = jnp.asarray([[0], [15], [16], [63]], jnp.int32)
    plan = da.DecodePlan(16, 0)
    kern = da.decode_attention(
        q.reshape(4, H * Dh), pool_k, pool_v, 1, slots, pos[:, 0],
        num_heads=H, interpret=True, plan=plan)
    twin = gen._gather_read(q, pool_k, pool_v, 1, slots, pos)[:, 0]
    assert kern.dtype == twin.dtype == jnp.float32
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(kern, twin, atol=tol, rtol=tol)
    # by hand, one row: softmax over its own K/V head's positions
    k = np.asarray(pool_k[3, 1, :1], np.float32).reshape(1, Hkv, Dh)
    v = np.asarray(pool_v[3, 1, :1], np.float32).reshape(1, Hkv, Dh)
    for head in range(H):
        np.testing.assert_allclose(
            np.asarray(kern[0]).reshape(H, Dh)[head], v[0, head // 4],
            atol=tol, rtol=tol)
    assert k.shape == v.shape


def test_block_plan_serves_grouped_queries_and_a_two_byte_pool():
    plan = da.block_plan(1024, 8, 64, 2, 32)         # the cell's geometry
    assert plan is not None and plan.block == 1024
    assert gen.kernel_plan(1024, 8, 64, "bf16", 32) == plan
    assert gen.kernel_plan(1024, 8, 64, "int8", 32) is None
    # as before for as many K/V heads as query heads
    assert da.block_plan(1024, 16, 64, 4) == da.block_plan(1024, 16, 64, 4,
                                                           16)
    assert da.block_plan(1024, 16, 64, 4).block == 256
    # query heads that do not divide, groups over K/V heads that do not
    # fill sublane tiles: the gather serves them
    assert da.block_plan(1024, 8, 64, 2, 12) is None
    assert da.block_plan(1024, 2, 64, 2, 8) is None


def test_a_bfloat16_pool_through_the_kv_helpers():
    pool = kvq.alloc((3, 2, 8, 128), jax.devices()[0], "bf16")
    assert pool.dtype == jnp.bfloat16
    assert kvq.pool_nbytes((3, 2, 8, 128), "bf16") == pool.nbytes
    assert kvq.aval((3, 2, 8, 128), "bf16").dtype == jnp.bfloat16
    assert kvq.capacity(pool) == 8 and kvq.row_width(pool) == 128
    vals = jnp.full((2, 128), 1.00390625, jnp.float32)   # rounds in bf16
    out = kvq.write_layer(pool, 1, jnp.asarray([0, 2]), jnp.asarray([5, 0]),
                          vals)
    assert out.dtype == jnp.bfloat16 and float(out[0, 1, 5, 0]) == 1.0
    block = jnp.ones((2, 4, 2, 64), jnp.float32)
    assert kvq.store_block(pool, jnp.int32(1), block).dtype == jnp.bfloat16


# --------------------------------------------------------------- refusals
@pytest.mark.parametrize("feature, kw", [
    ("prefix_cache_slots", {"prefix_cache_slots": 2}),
    ("draft", {"draft_params": "a draft"}),
    ("kv_dtype=int8", {"kv_dtype": "int8"}),
    ("quantize_weights", {"quantize_weights": True}),
])
def test_what_cannot_carry_the_conv_state_is_refused_by_name(feature, kw):
    cfg = tiny("float32")
    with pytest.raises(ValueError) as e:
        GenerativeEngine(params=({}, cfg), warmup=False, auto_start=False,
                         **kw)
    assert "lfm2" in str(e.value) and feature in str(e.value)


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
            assert "lfm2" in str(e.value) and "handoff" in str(e.value)
        assert eng.metrics.snapshot()["rejected_total"] == 3
        with pytest.raises(ValueError, match="kv_dtype must be 'f32'"):
            GenerativeEngine(params=({}, cfg), warmup=False,
                             auto_start=False, kv_dtype="bf16")
    finally:
        eng.shutdown(drain=False)


# ------------------------------------------------------ spans and scopes
def test_scopes_are_noted_by_program_and_the_step_says_its_experts(
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
    assert {"jit_lfm2_decode_c128_b1", "jit_lfm2_decode_c128_b2",
            "jit_lfm2_prefill_c128_b8"} <= set(by_program)
    for program, names in by_program.items():
        scopes = " ".join(names.values())
        assert program.startswith("jit_lfm2_")
        for scope in ("moe.route", "moe.experts", "lfm2.short_conv"):
            assert scope in scopes, (program, scope)
    hits = [s["args"]["experts_hit"] for s in spans
            if s["name"] == "generate.decode_step"
            and "experts_hit" in s.get("args", {})]
    layers, k = len(cfg.expert_layers), cfg.num_experts_per_tok
    assert hits and all(k <= h <= k * layers for h in hits)
    assert trace.op_scopes_by_program() == {}


# ------------------------------------- the GPT programs, where they were
# sha256 of the lowered text of the engine's GPT programs at gpt3-tiny
# (4 slots, pools donated), on the CPU with this container's jax: PR 33
# put a model-supplied step, a state beside the K/V rows and grouped
# queries through the same bodies, and moved none of these. PR 34 moved
# all eight, as it set out to (the sampling head every body ends in: a
# search over values in the sort's place), and they are pinned again as
# that PR lowers them.
GPT_LOWERED = {
    ("decode", "f32"):
        "1c971dfd0830cd6449870b597e8516e512425abfb6ea521ab694699ee02babe4",
    ("prefill", "f32"):
        "f4920ff7d217c0c1037a980df2893d6d7f6dc7682ed503714bf194debfadb077",
    ("extend", "f32"):
        "76658609e8ef97947b5e0d584874586b200376b27617f0252e9610682a1023e6",
    ("verify", "f32"):
        "61707d76a9dec8984dc172b511d4376a840ec9d5795d504a51cf481463dda7b8",
    ("decode", "int8"):
        "a32b7ac11fdaf9faeeefd2046f619e3e88d917929090b4bd4d922ae0318d9b50",
    ("prefill", "int8"):
        "7db11911e704a6e28f393e4a001c04f2378ddc7d3a65dc32a76e57598692f21d",
    ("extend", "int8"):
        "26964344064c2f60e4041f8a957c0a7bc6f925125535b9d27ee5e9913710026e",
    ("verify", "int8"):
        "714d4555f6a821996cbc7f6028021ec1c8fc5fe2828b5c1d998bde9b7220fe9d",
}


@pytest.fixture(scope="module")
def gpt_engines():
    from paddle_tpu.inference.serving.generate import stack_gpt_params
    from paddle_tpu.models import PRESETS, GPTForCausalLM

    engines = {}
    for kv in ("f32", "int8"):
        paddle.seed(0)
        model = GPTForCausalLM(PRESETS["gpt3-tiny"])
        model.eval()
        engines[kv] = GenerativeEngine(
            params=stack_gpt_params(model), slots=4, warmup=False,
            auto_start=False, kv_dtype=kv, donate=True)
    yield engines
    for eng in engines.values():
        eng.shutdown(drain=False)


@pytest.mark.parametrize("kind, kv", sorted(GPT_LOWERED))
def test_gpt_programs_lower_to_the_parents_text(gpt_engines, kind, kv):
    eng = gpt_engines[kv]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    cap, b, S, k = eng._caps[-1], eng._batch_buckets[-1], 16, 4
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), eng._params)
    pool = kvq.aval(eng._pool_shape(cap), kv)
    i32, f32 = np.int32, np.float32
    row = (sds((), i32), sds((1, S), i32))
    sampling = (sds((), f32), sds((), i32), sds((), f32), sds((2,), np.uint32))
    rows = (sds((b,), f32), sds((b,), i32), sds((b,), f32),
            sds((b, 2), np.uint32))
    args = {
        "decode": (sds((b,), i32), sds((b,), i32), sds((b,), i32), *rows),
        "prefill": (*row, sds((), i32), *sampling),
        "extend": (*row, sds((), i32), sds((), i32), *sampling),
        "verify": (sds((b,), i32), sds((b, k), i32), sds((b,), i32), *rows),
    }[kind]
    bucket = S if kind in ("prefill", "extend") else b
    text = eng._program(kind, cap, bucket, k if kind == "verify" else 1).lower(
        params, pool, pool, *args).as_text()
    # PR 37 gave the GPT programs names (`GPTPasses.program_prefix`): the
    # name is ALL that moved — with `jit__unknown` in its place the text
    # hashes to what it hashed to while the programs had none
    name = f"jit_gpt_{kind}_c{cap}_b{bucket}" + \
        (f"_k{k}" if kind == "verify" else "")
    assert name in text and "jit__unknown" not in text
    assert hashlib.sha256(text.replace(name, "jit__unknown").encode()
                          ).hexdigest() == GPT_LOWERED[kind, kv]
