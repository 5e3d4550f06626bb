"""Decode attention over the K/V pool in place (ops/pallas/decode_attention)
and the engine's decode pass that uses it — on the CPU: the kernel in
interpret mode, the engine's programs with their plain-XLA twin and, steered
from the tests, with the interpreted kernel in the twin's place.

What a chip alone can show (Mosaic accepts the kernel, no pool-sized copy or
temporary is left in the compiled program) is in tests/test_chip_compile.py.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference.serving import GenerativeEngine
from paddle_tpu.inference.serving import generate as gen
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.ops.pallas import decode_attention as da

ROWS, LAYERS, CAP, H, DH = 6, 2, 64, 2, 64
W = H * DH
BLOCK = 16
SCRATCH = ROWS - 1


def reference(q, pk, pv, layer, slots, lengths):
    """A plain masked softmax over gathered rows, in float64."""
    out = []
    for i, s in enumerate(slots):
        n = min(int(lengths[i]), CAP - 1) + 1
        k = pk[s, layer, :n].reshape(n, H, DH).astype(np.float64)
        v = pv[s, layer, :n].reshape(n, H, DH).astype(np.float64)
        sc = np.einsum("hd,mhd->hm", q[i].reshape(H, DH), k) / np.sqrt(DH)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out.append(np.einsum("hm,mhd->hd", p, v).reshape(W))
    return np.stack(out)


# slots permuted and repeated, the scratch row among them; a row that
# shares a slot shares its length (the NaN fill is by slot)
SLOTS = np.array([3, 0, SCRATCH, 3, 1, SCRATCH], np.int32)
LENGTH_CASES = {
    "zero": 0, "one": 1, "under_edge": BLOCK - 1, "at_edge": BLOCK,
    "over_edge": BLOCK + 1, "last": CAP - 1, "past_cap": CAP + 5,
}


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("case", list(LENGTH_CASES) + ["mixed"])
def test_kernel_reads_in_place_up_to_each_rows_length(case, layer):
    """Against the plain softmax, rows x lengths around a block's edges;
    and with the pool filled with NaN past each row's length the output is
    finite and the same — nothing past `length` reaches it."""
    rng = np.random.RandomState(7)
    pk = rng.randn(ROWS, LAYERS, CAP, W).astype(np.float32)
    pv = rng.randn(ROWS, LAYERS, CAP, W).astype(np.float32)
    q = rng.randn(len(SLOTS), W).astype(np.float32)
    if case == "mixed":
        by_slot = {3: BLOCK - 1, 0: CAP - 1, SCRATCH: 0, 1: 2 * BLOCK}
        lengths = np.array([by_slot[s] for s in SLOTS], np.int32)
    else:
        lengths = np.full(len(SLOTS), LENGTH_CASES[case], np.int32)
    want = reference(q, pk, pv, layer, SLOTS, lengths)
    plan = da.DecodePlan(BLOCK, 0)

    def run(k, v):
        return np.asarray(da.decode_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.int32(layer), jnp.asarray(SLOTS), jnp.asarray(lengths),
            num_heads=H, interpret=True, plan=plan))

    got = run(pk, pv)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    nan_k, nan_v = pk.copy(), pv.copy()
    for s, n in zip(SLOTS, lengths):
        nan_k[s, :, min(int(n), CAP - 1) + 1:] = np.nan
        nan_v[s, :, min(int(n), CAP - 1) + 1:] = np.nan
    # the other layer and the rows no query names: NaN throughout
    nan_k[:, 1 - layer] = nan_v[:, 1 - layer] = np.nan
    nan_k[[2, 4]] = nan_v[[2, 4]] = np.nan
    got_nan = run(nan_k, nan_v)
    assert np.all(np.isfinite(got_nan))
    np.testing.assert_array_equal(got_nan, got)


@pytest.mark.parametrize("cap,heads,head_dim,itemsize,block", [
    (1024, 16, 64, 4, 256),     # gpt3-medium's pool: 1 MiB a K block
    (1024, 16, 64, 2, 512),     # the same in bf16
    (2048, 32, 64, 4, 128),     # gpt3-1.3b's heads at twice the context
    (1024, 16, 128, 4, 128),    # heads 128 wide
    (1024, 12, 64, 4, 256),     # 768 lanes: six whole tiles
    (64, 2, 64, 4, 64),         # a tiny pool is one block
    (24, 2, 64, 4, 8),          # the block divides the capacity
    (1024, 4, 16, 4, None),     # folded heads under one lane tile
    (100, 2, 64, 4, None),      # capacity not whole sublane tiles
    (1024, 256, 64, 4, None),   # the accumulator alone is over the count
])
def test_block_plan(cap, heads, head_dim, itemsize, block):
    """The plan's arithmetic: the block from the shape under the bytes a
    DMA should move, the VMEM count that goes with it, and what a row of a
    given length then reads."""
    plan = da.block_plan(cap, heads, head_dim, itemsize)
    if block is None:
        assert plan is None
        return
    width = heads * head_dim
    assert plan.block == block and cap % block == 0
    assert block * width * itemsize <= da._BLOCK_BYTES
    kv = 2 * 2 * block * width * itemsize
    acc = -(-heads // 8) * 8 * (width + 2 * 128) * 4
    assert plan.vmem_bytes == kv + 2 * 2 * 8 * width * 4 + acc
    assert plan.vmem_bytes <= da._VMEM_BUDGET < da._VMEM_LIMIT
    assert plan.positions_read(0, cap) == block
    assert plan.positions_read(block - 1, cap) == block
    assert plan.positions_read(block, cap) == min(2 * block, cap)
    assert plan.positions_read(cap + 7, cap) == cap


def test_pool_attention_takes_the_gather_where_the_kernel_does_not_serve():
    """One entry, two reads: several queries a row, an int8 pool and a
    shape without a plan take the gather whatever the platform — the
    kernel is never traced for them."""
    from paddle_tpu.quantization import kv as kvq

    def boom(*a, **k):
        raise AssertionError("the kernel was traced")

    rng = np.random.RandomState(0)
    dev = jax.devices()[0]
    slots = jnp.asarray([1, 0], jnp.int32)
    old = gen._kernel_read
    gen._kernel_read = boom
    try:
        for kv_dtype, Q, heads in (("f32", 3, H), ("int8", 1, H),
                                   ("f32", 1, 4)):
            dh = W // heads if heads == H else 16      # 4 x 16: 64 lanes
            buf = kvq.alloc((3, LAYERS, CAP, heads * dh), dev, kv_dtype)
            q = jnp.asarray(rng.randn(2, Q, heads, dh).astype(np.float32))
            pos = jnp.asarray(rng.randint(0, CAP, size=(2, Q)), jnp.int32)
            out = gen.pool_attention(q, buf, buf, 1, slots, pos)
            assert out.shape == (2, Q, heads * dh)
    finally:
        gen._kernel_read = old


def test_twin_and_kernel_agree_through_pool_attention():
    """The engine's two reads behind their one entry, on one pool."""
    rng = np.random.RandomState(3)
    pk = jnp.asarray(rng.randn(ROWS, LAYERS, CAP, W).astype(np.float32))
    pv = jnp.asarray(rng.randn(ROWS, LAYERS, CAP, W).astype(np.float32))
    q = jnp.asarray(rng.randn(4, 1, H, DH).astype(np.float32))
    slots = jnp.asarray([2, 5, 0, 5], jnp.int32)
    pos = jnp.asarray([[0], [17], [63], [70]], jnp.int32)
    twin = gen._gather_read(q, pk, pv, 1, slots, pos)
    kern = gen._kernel_read(q, pk, pv, 1, slots, pos, interpret=True)
    assert kern.shape == twin.shape == (4, 1, W)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(twin),
                               rtol=2e-5, atol=2e-6)


# ===================================================================
# the engine's decode pass over the pool in place
# ===================================================================
def _model(layers=2, context=CAP):
    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig(vocab_size=256, hidden_size=W,
                                 num_layers=layers, num_heads=H,
                                 max_seq_len=context, dropout=0.0))
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _model()


def full_forward_greedy(model, prompt, n_new):
    """Greedy tokens with no cache at all: the whole sequence through the
    model at every step, padded to the context — attention is causal, so
    the logits at the last real position are those of the unpadded
    sequence, and the eager model compiles its ops for ONE length (a
    fresh length at each of the 72 steps cost 83 s of a cold 103 s)."""
    ids = [int(t) for t in prompt]
    for _ in range(n_new):
        padded = np.zeros((1, CAP), "int64")
        padded[0, :len(ids)] = ids
        logits = model(paddle.to_tensor(padded))
        ids.append(int(np.argmax(
            np.asarray(logits.numpy())[0, len(ids) - 1])))
    return ids[len(prompt):]


@pytest.fixture
def kernel_live(monkeypatch):
    """Steer the engine's programs onto the interpreted kernel, as a TPU
    lowering would take the compiled one, in blocks of 16 positions."""
    monkeypatch.setattr(da, "_BLOCK_BYTES", BLOCK * W * 4)
    monkeypatch.setattr(
        gen, "_on_tpu",
        lambda kernel, twin, *a: kernel(*a, interpret=True))
    monkeypatch.setattr(gen, "_lowers_for_tpu", lambda device: True)


# rows of staggered lengths: under, at and over the edges at 16 and 32,
# every one carried over an edge by its 12 new tokens
PROMPT_LENGTHS = (5, 14, 15, 16, 22, 30)


def _prompts():
    rng = np.random.RandomState(11)
    return [rng.randint(0, 256, size=n) for n in PROMPT_LENGTHS]


@pytest.mark.parametrize("read", ["gather", "kernel"])
def test_engine_decodes_what_a_full_forward_gives(model, read, request):
    """Token for token, batched rows of staggered lengths that cross a
    block's edge while decoding, against a forward with no cache — with
    the twin's read (what a CPU lowers) and with the kernel's."""
    if read == "kernel":
        request.getfixturevalue("kernel_live")
    eng = GenerativeEngine(model, slots=4, max_context=CAP,
                           max_new_tokens_cap=16)
    try:
        handles = [eng.submit(p, 12) for p in _prompts()]
        got = [h.result(120)["tokens"] for h in handles]
        report = eng.program_report()
        snap = eng.metrics.snapshot()
    finally:
        eng.shutdown()
    assert got == [full_forward_greedy(model, p, 12) for p in _prompts()]
    # every decode program names its read; no other family attends rows
    assert set(report["kv_read"]) == {
        p for p in report["programs"] if p.startswith("decode[")}
    assert set(report["kv_read"].values()) == {read}
    assert snap["max_slot_occupancy"] > 1
    if read == "gather":
        assert snap["kv_read_share"] == 1.0
    else:
        assert 0.25 <= snap["kv_read_share"] < 0.75


def test_kv_read_share_of_a_scripted_run(model, kernel_live):
    """One request alone, so the steps are known: a prompt of 14 leaves
    the pending token at position 14, and 6 decode steps read positions up
    to 14 … 19 — one block of 16 twice (14, 15), two blocks four times."""
    eng = GenerativeEngine(model, slots=2, max_context=CAP,
                           max_new_tokens_cap=16)
    try:
        out = eng.generate(_prompts()[1], max_new_tokens=7)
        snap = eng.metrics.snapshot()
        text = eng.metrics.prometheus_text()
    finally:
        eng.shutdown()
    assert out["n_tokens"] == 7 and snap["steps_total"] == 6
    assert snap["kv_positions_read_total"] == 2 * 16 + 4 * 32
    assert snap["kv_positions_capacity_total"] == 6 * CAP
    assert snap["kv_read_share"] == round(160 / (6 * CAP), 4)
    assert "paddle_generate_kv_positions_read_total 160" in text
    assert f"paddle_generate_kv_read_share {snap['kv_read_share']}" in text


def test_decode_step_span_carries_kv_read(model, tmp_path):
    from paddle_tpu.observability import trace

    eng = GenerativeEngine(model, slots=2, max_context=CAP,
                           max_new_tokens_cap=16)
    paddle.set_flags({"FLAGS_trace_dir": str(tmp_path)})
    try:
        before = len(trace.spans())
        eng.generate(_prompts()[0], max_new_tokens=3)
        steps = [e for e in trace.spans()[before:]
                 if e["name"] == "generate.decode_step"]
    finally:
        paddle.set_flags({"FLAGS_trace_dir": ""})
        eng.shutdown()
    assert len(steps) == 2
    for e in steps:
        assert e["args"]["kv_read"] == e["args"]["rows"] * e["args"]["cap"]


def test_decode_program_holds_no_temporary_of_a_pool_rows_size():
    """In place, shown on the CPU: with the pools donated the decode
    program's temporaries are smaller than ONE pool row (the program that
    gathered, transposed and re-set the rows held five copies of all of
    them; what is left is a layer's weights and a layer of the rows), and
    its outputs alias the pools."""
    layers, cap = 8, 512
    eng = GenerativeEngine(_model(layers, cap), slots=2, max_context=cap,
                           warmup=False, auto_start=False, donate=True)
    try:
        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

        b = eng._batch_buckets[-1]
        params = {n: sds(v.shape, v.dtype) for n, v in eng._params.items()}
        pool = sds(eng._pool_shape(cap), np.float32)
        compiled = eng._program("decode", cap, b).lower(
            params, pool, pool, sds((b,), np.int32), sds((b,), np.int32),
            sds((b,), np.int32), sds((b,), np.float32),
            sds((b,), np.int32), sds((b,), np.float32),
            sds((b, 2), np.uint32)).compile()
    finally:
        eng.shutdown(drain=False)
    mem = compiled.memory_analysis()
    row = layers * cap * W * 4
    assert eng._pool_shape(cap) == (3, layers, cap, W)
    assert mem.temp_size_in_bytes < row
    assert mem.alias_size_in_bytes >= 2 * 3 * row
