"""Pallas flash-attention kernel vs the XLA einsum reference (interpret mode
on CPU — the fake-TPU CI pattern; tests/test_chip_compile.py asks the TPU
compiler, chip_smoke.py runs the kernel).
Reference role: paddle/phi/kernels/gpu/flash_attn_kernel.cu (+grad).
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import flash_attention, flash_attention_supported


def _ref_attn(q, k, v, causal):
    d = q.shape[-1]
    s = 1.0 / math.sqrt(d)
    qh, kh, vh = [jnp.swapaxes(x, 1, 2) for x in (q, k, v)]
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * s
    if causal:
        L = logits.shape[-1]
        logits = jnp.where(jnp.tril(jnp.ones((L, L), bool)), logits,
                           -jnp.inf)
    p = jax.nn.softmax(logits, -1)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vh), 1, 2)


def _grad_errors(f1, f2, args):
    """Max error of each gradient over that gradient's max."""
    g1 = jax.grad(f1, (0, 1, 2))(*args)
    g2 = jax.grad(f2, (0, 1, 2))(*args)
    return [float(jnp.abs(a.astype(jnp.float32) - b).max())
            / (float(jnp.abs(b).max()) + 1e-9) for a, b in zip(g1, g2)]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("impl", ["bf16", "f32"])
def test_flash_matches_reference_fwd_bwd(causal, impl):
    """Both operand choices (FLAGS_flash_dot_impl) must be exact against
    the einsum reference on f32 inputs — 'f32' only casts blocks."""
    rng = np.random.RandomState(0)
    B, L, H, D = 2, 256, 2, 64
    q, k, v = [jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
               for _ in range(3)]
    out = flash_attention(q, k, v, causal=causal, interpret=True,
                          impl=impl)
    ref = _ref_attn(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    f1 = lambda q, k, v: (flash_attention(  # noqa: E731
        q, k, v, causal=causal, interpret=True, impl=impl) ** 2).sum()
    f2 = lambda q, k, v: (_ref_attn(q, k, v, causal) ** 2).sum()  # noqa: E731
    assert max(_grad_errors(f1, f2, (q, k, v))) < 2e-4


# (name, B, L, H, D, explicit plan or None for the shape's own)
_SCHEDULES = [
    # one square of 1,024 in 256 x 256 tiles: what both train cells get
    ("cell", 1, 1024, 2, 64, None),
    # 2 x 2 squares on the grid, scratch accumulators: a square below the
    # diagonal (unmasked), two on it, one above it (skipped)
    ("squares", 1, 2048, 1, 64, None),
    # the same at a small size, 2 batch-heads a step, and a scale that is
    # no power of two (head_dim 128: the score tile is scaled, not q)
    ("grid-small", 1, 512, 4, 128, (256, 128, 128, 2)),
    # tile_q != tile_k: a masked tile holds rows with no column left
    ("uneven", 1, 384, 2, 64, (384, 128, 384, 1)),
]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("case", _SCHEDULES, ids=[c[0] for c in _SCHEDULES])
def test_flash_schedule_matches_reference(case, causal):
    """Forward, log-sum-exp and gradients of the kernels under a tile
    plan against the einsum reference, f32 inputs."""
    from paddle_tpu.ops.pallas.flash_attention import (
        TilePlan, _bwd, _fwd, tile_plan)

    _, B, L, H, D, plan = case
    plan = TilePlan(*plan) if plan else tile_plan(L, D, 4, causal)
    if case[0] == "cell":       # the cells run bf16: their plan, f32 data
        plan = tile_plan(L, D, 2, causal)
        assert (plan.block, plan.tile_q, plan.tile_k) == (1024, 256, 256)
    rng = np.random.RandomState(0)
    q, k, v, g = [jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
                  for _ in range(4)]
    scale = 1.0 / math.sqrt(D)

    def to_bh(x):
        return jnp.swapaxes(x, 1, 2).reshape(B * H, L, D)

    def from_bh(x):
        return jnp.swapaxes(x.reshape(B, H, L, D), 1, 2)

    out, lse = _fwd(to_bh(q), to_bh(k), to_bh(v), scale, causal, True,
                    "bf16", plan)
    np.testing.assert_allclose(np.asarray(from_bh(out)),
                               np.asarray(_ref_attn(q, k, v, causal)),
                               atol=2e-5)
    logits = jnp.einsum("bqd,bkd->bqk", to_bh(q), to_bh(k)) * scale
    if causal:
        logits = jnp.where(jnp.tril(jnp.ones((L, L), bool)), logits,
                           -jnp.inf)
    np.testing.assert_allclose(np.asarray(lse[:, 0]),
                               np.asarray(jax.nn.logsumexp(logits, -1)),
                               atol=2e-5)
    grads = _bwd(scale, causal, True, "bf16",
                 (to_bh(q), to_bh(k), to_bh(v), out, lse), to_bh(g), plan)
    want = jax.grad(lambda *a: (_ref_attn(*a, causal) * g).sum(),
                    (0, 1, 2))(q, k, v)
    for got, ref in zip(grads, want):
        err = float(jnp.abs(from_bh(got) - ref).max())
        assert err / float(jnp.abs(ref).max()) < 2e-4


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bf16_against_f32_reference(causal):
    """bf16 operands, as the cells run them, against the f32 reference on
    the same (bf16-rounded) values. The kernel rounds p and dS to bf16 for
    the MXU (2^-9 relative) and its output and gradients to bf16: 2e-2 of
    the output's max and 3e-2 of each gradient's max hold with room (read
    here: 4e-3 and 1e-2)."""
    rng = np.random.RandomState(0)
    q, k, v = [jnp.asarray(rng.randn(1, 512, 2, 64), jnp.bfloat16)
               for _ in range(3)]
    q32, k32, v32 = [x.astype(jnp.float32) for x in (q, k, v)]
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = _ref_attn(q32, k32, v32, causal)
    assert out.dtype == jnp.bfloat16
    assert float(jnp.abs(out.astype(jnp.float32) - ref).max()) \
        < 2e-2 * float(jnp.abs(ref).max())

    f1 = lambda q, k, v: (flash_attention(  # noqa: E731
        q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
        v.astype(jnp.bfloat16), causal=causal,
        interpret=True).astype(jnp.float32) ** 2).sum()
    f2 = lambda q, k, v: (_ref_attn(q, k, v, causal) ** 2).sum()  # noqa: E731
    assert max(_grad_errors(f1, f2, (q32, k32, v32))) < 3e-2


@pytest.mark.parametrize("itemsize", [2, 4])
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("seq", [128, 256, 1024, 2048])
def test_tile_plan(seq, head_dim, itemsize):
    """The plan divides the sequence, fits the VMEM budget it states and
    takes fewer grid steps than the 128 x 128 schedule it replaced (one
    step per batch-head and 128 rows) at both cells' per-shard
    batch-heads: 64 x 16 and 16 x 16."""
    import importlib

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    plan = fa.tile_plan(seq, head_dim, itemsize, True)
    assert plan == fa.tile_plan(seq, head_dim, itemsize, False)
    assert seq % plan.block == 0 and plan.block % plan.tile_q == 0 \
        and plan.block % plan.tile_k == 0
    assert plan.tile_q % 128 == 0 and plan.tile_k % 128 == 0
    assert plan.block * itemsize <= fa._MAX_BLOCK_BYTES
    assert 1 <= plan.bh <= fa._MAX_BH
    assert fa._step_vmem_bytes(seq, head_dim, itemsize, plan.block,
                               plan.bh) <= fa._VMEM_BUDGET < fa._VMEM_LIMIT
    for batch_heads in (1024, 256):
        assert batch_heads % plan.bh_per_step(batch_heads) == 0
        steps = (batch_heads // plan.bh_per_step(batch_heads)
                 * (seq // plan.block) ** 2)
        assert steps < batch_heads * (seq // 128)
    if seq * itemsize <= fa._MAX_TILE_BYTES:    # short: one tile, no loop
        assert plan.block == plan.tile_q == plan.tile_k == seq


def test_dot_impl_resolves_in_process():
    """FLAGS_flash_dot_impl: 'auto' is 'bf16' — decided in this process,
    no probe child, no file — a named strategy is itself, anything else
    raises (the retired 'nn'/'nn2' too)."""
    from paddle_tpu.core.flags import flag, set_flags
    from paddle_tpu.ops.pallas.flash_attention import _resolve_dot_impl

    assert flag("flash_dot_impl") == "auto" and _resolve_dot_impl() == "bf16"
    try:
        for impl in ("bf16", "f32"):
            set_flags({"FLAGS_flash_dot_impl": impl})
            assert _resolve_dot_impl() == impl
        for impl in ("fp8", "nn", "nn2"):
            set_flags({"FLAGS_flash_dot_impl": impl})
            with pytest.raises(ValueError, match="auto|bf16|f32"):
                _resolve_dot_impl()
    finally:
        set_flags({"FLAGS_flash_dot_impl": "auto"})


def test_supported_gate():
    """The gate is the plan: multiples of 128 (or one short tile of a
    multiple of 8 rows), heads up to 256 wide."""
    assert flash_attention_supported((2, 256, 4, 64), jnp.bfloat16, True)
    assert flash_attention_supported((2, 1152, 4, 128), jnp.float32, False)
    assert flash_attention_supported((2, 64, 4, 16), jnp.float32, True)
    assert not flash_attention_supported((2, 200, 4, 64), jnp.bfloat16, True)
    assert not flash_attention_supported((2, 100, 4, 64), jnp.bfloat16, True)
    assert not flash_attention_supported((2, 256, 4, 512), jnp.bfloat16,
                                         True)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("impl", ["bf16", "f32"])
def test_mosaic_tpu_lowering(causal, dtype, impl):
    """Cross-lower the kernels for the TPU target on the CPU host.
    jax.export only LOWERS (jaxpr -> Mosaic MLIR in a custom call); it
    never asks the TPU compiler, which is what refused these kernels for
    their dot precision — tests/test_chip_compile.py does that. What this
    guards is the lowering itself, e.g. the x64 pitfall: the package enables
    jax_enable_x64, so stray Python int/float literals in kernel bodies
    become 64-bit constants Mosaic cannot lower (infinite recursion in
    convert_element_type)."""
    rng = np.random.RandomState(0)
    q, k, v = [jnp.asarray(rng.randn(1, 256, 2, 64), dtype)
               for _ in range(3)]

    def f(q, k, v):
        return flash_attention(q, k, v, causal=causal, impl=impl)

    def g(q, k, v):
        return jax.grad(
            lambda *a: f(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    jax.export.export(jax.jit(f), platforms=["tpu"])(q, k, v)
    jax.export.export(jax.jit(g), platforms=["tpu"])(q, k, v)


def test_bench_train_step_mosaic_lowering():
    """Cross-lower the FULL bench program — tiny GPT with the Pallas flash
    path live (seq 256, head_dim 64 passes the gate), chunked fused
    LM-head CE, fused AdamW update — for the TPU target. This is the
    whole-step analog of the kernel-level lowering guard: a Mosaic or
    GSPMD regression anywhere in the bench path fails here, no chip
    needed."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.nn.functional_more import fused_linear_cross_entropy

    from paddle_tpu.core.flags import set_flags

    set_flags({"FLAGS_force_flash_attention": True})
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                    num_heads=2, max_seq_len=256, dropout=0.0)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.train()
    optimizer = opt.AdamW(1e-4, parameters=model.parameters())

    def loss_fn(m, ids, labels):
        h = m.gpt(ids)
        return fused_linear_cross_entropy(h, m.gpt.wte.weight, labels,
                                          transpose_y=True, chunk=128)

    step = TrainStep(model, optimizer, loss_fn)
    if step._step_fn is None:
        step._build()
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (1, 256)), jnp.int64)
    lr = jnp.asarray(1e-4, jnp.float32)
    si = jnp.asarray(1, jnp.int32)
    from paddle_tpu.core import rng as _rng

    key = _rng.next_key()
    try:
        exported = jax.export.export(step._step_fn, platforms=["tpu"])(
            step._params, step._buffers, step._opt_state, lr, si, key,
            (ids, ids))
    finally:
        from paddle_tpu.core.flags import set_flags as _sf

        _sf({"FLAGS_force_flash_attention": False})
    text = exported.mlir_module()
    # the flash kernel really is in the program (not the einsum fallback)
    assert "tpu_custom_call" in text or "custom_call" in text


def test_scan_gpt_parity_and_mosaic_lowering():
    """GPTForCausalLMScan (scan-over-layers, the compile-time lever):
    exact forward/train parity with the unrolled model, much smaller
    program, and the WHOLE scan train step — flash kernel inside the
    lax.scan body + fused CE — cross-lowers for the TPU target."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as opt
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTForCausalLMScan)
    from paddle_tpu.nn.functional_more import fused_linear_cross_entropy

    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=4,
                    num_heads=4, max_seq_len=32, dropout=0.0)
    paddle.seed(0)
    m = GPTForCausalLM(cfg)
    m.eval()
    ms = GPTForCausalLMScan.from_unrolled(m)
    ms.eval()
    ids = paddle.to_tensor(np.random.RandomState(0)
                           .randint(0, 128, (2, 16)).astype("int64"))
    np.testing.assert_allclose(m(ids).numpy(), ms(ids).numpy(),
                               rtol=2e-5, atol=2e-5)

    def loss_fn(model, i, l):
        lg = model(i)
        return F.cross_entropy(lg.reshape([-1, cfg.vocab_size]),
                               l.reshape([-1]))

    X = np.random.RandomState(1).randint(0, 128, (4, 16)).astype("int64")
    Y = np.roll(X, -1, 1)
    s1 = TrainStep(m, opt.AdamW(1e-3, parameters=m.parameters()), loss_fn)
    l1 = [float(s1(X, Y).numpy()) for _ in range(3)]
    s2 = TrainStep(ms, opt.AdamW(1e-3, parameters=ms.parameters()),
                   loss_fn)
    l2 = [float(s2(X, Y).numpy()) for _ in range(3)]
    np.testing.assert_allclose(l1, l2, rtol=1e-4)
    # program shrinks (at real depth the ratio approaches 1/L)
    assert s2.lower_hlo(X, Y).count("\n") < \
        s1.lower_hlo(X, Y).count("\n") * 0.6

    # Mosaic cross-lowering of the bench-shaped scan step: flash inside
    # the scan body (seq 256 / head_dim 64 passes the gate) + fused CE
    scfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                     num_heads=2, max_seq_len=256, dropout=0.0)
    paddle.seed(0)
    bm = GPTForCausalLMScan(scfg)
    bm.remat = True
    bm.train()

    def bench_loss(model, i, l):
        return fused_linear_cross_entropy(model.hidden(i),
                                          model.wte.weight, l,
                                          transpose_y=True, chunk=128)

    step = TrainStep(bm, opt.AdamW(1e-4, parameters=bm.parameters()),
                     bench_loss)
    step._build()
    bids = jnp.asarray(np.random.RandomState(0).randint(
        0, scfg.vocab_size, (1, 256)), jnp.int64)
    from paddle_tpu.core import rng as _rng

    set_flags({"FLAGS_force_flash_attention": True})
    try:
        exported = jax.export.export(step._step_fn, platforms=["tpu"])(
            step._params, step._buffers, step._opt_state,
            jnp.asarray(1e-4, jnp.float32), jnp.asarray(1, jnp.int32),
            _rng.next_key(), (bids, bids))
    finally:
        set_flags({"FLAGS_force_flash_attention": False})
    assert "custom_call" in exported.mlir_module()
