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


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("impl", ["bf16", "nn", "nn2", "f32"])
def test_flash_matches_reference_fwd_bwd(causal, impl):
    """Every dot strategy (FLAGS_flash_dot_impl) must be exact against
    the einsum reference — 'nn' restructures every dot into canonical NN
    form (pre-transposed K/V + in-kernel transposes), 'nn2' additionally
    avoids in-kernel transposes (Q^T/dO^T in, dK^T/dV^T out), 'f32'
    casts blocks; same math all four ways."""
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
    g1 = jax.grad(f1, (0, 1, 2))(q, k, v)
    g2 = jax.grad(f2, (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        scale = float(jnp.abs(b).max()) + 1e-9
        assert float(jnp.abs(a - b).max()) / scale < 2e-4


def test_dot_impl_resolves_in_process():
    """FLAGS_flash_dot_impl: 'auto' is 'bf16' — decided in this process,
    no probe child, no file — a named strategy is itself, anything else
    raises."""
    from paddle_tpu.core.flags import flag, set_flags
    from paddle_tpu.ops.pallas.flash_attention import _resolve_dot_impl

    assert flag("flash_dot_impl") == "auto" and _resolve_dot_impl() == "bf16"
    try:
        for impl in ("bf16", "nn", "nn2", "f32"):
            set_flags({"FLAGS_flash_dot_impl": impl})
            assert _resolve_dot_impl() == impl
        set_flags({"FLAGS_flash_dot_impl": "fp8"})
        with pytest.raises(ValueError, match="auto|bf16|nn|nn2|f32"):
            _resolve_dot_impl()
    finally:
        set_flags({"FLAGS_flash_dot_impl": "auto"})


def test_supported_gate():
    assert flash_attention_supported((2, 256, 4, 64), 64, True)
    assert not flash_attention_supported((2, 200, 4, 64), 64, True)
    assert not flash_attention_supported((2, 256, 4, 512), 512, True)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("impl", ["bf16", "nn", "nn2", "f32"])
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
