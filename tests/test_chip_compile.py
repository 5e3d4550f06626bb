"""Compile the main path's programs for a TPU v5e that is described, not
attached (the TPU compiler is installed with jax; the third rehearsal of
.claude/skills/verify/SKILL.md). Interpret mode and jax.export cannot show
what this does: a kernel Mosaic refuses, a program that does not fit HBM.
Nothing runs — a compile that passes is not a chip run.

All such tests live in THIS file, and the topology is described inside a
fixture: one process at a time may hold the TPU library, so only the
xdist worker that is handed this file may load it, and only once a test
of the file has started (never at import, in a skipif or in parametrize
arguments — every worker imports every test file).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.pallas import flash_attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever says "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """An executable compiled for the described chip is written to the
    persistent cache but cannot be read back without a chip (the next
    compile warns and recompiles): keep these compiles off it."""
    from paddle_tpu.core import compile_cache

    with compile_cache.suspend_if():
        yield


def _compile_flash(one_chip, shape, causal, impl, dtype=jnp.bfloat16):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=causal, impl=impl)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    for fn in (fwd, bwd):
        text = jax.jit(fn).lower(x, x, x).compile().as_text()
        assert "tpu_custom_call" in text


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("impl", [None, "f32"])
def test_flash_compiles_at_bench_width(one_chip, no_persistent_cache,
                                       causal, impl):
    """fwd and bwd at gpt3-medium.train's own shape, batch 64 and all —
    a plan that Mosaic refuses for VMEM or alignment fails here, not on
    the chip; impl=None resolves FLAGS_flash_dot_impl=auto (bf16 operands)
    in-process, "f32" is the other operand choice.
    Before the kernel stated its own precision, the package-wide "highest"
    default made Mosaic refuse every bf16 strategy here ("Bad lhs
    type")."""
    _compile_flash(one_chip, (64, 1024, 16, 64), causal, impl)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_compiles_at_1p3b_shard(one_chip, no_persistent_cache,
                                      causal):
    """[16, 1024, 16, 64]: what one chip of gpt3-1.3b.train-dp2tp2 runs
    (batch 32 over dp 2, 32 heads over tp 2), under auto."""
    _compile_flash(one_chip, (16, 1024, 16, 64), causal, None)


@pytest.mark.parametrize("shape,dtype", [
    ((8, 1024, 8, 128), jnp.bfloat16),     # head_dim 128: narrower k tiles
    ((4, 2048, 8, 64), jnp.bfloat16),      # 2 x 2 squares, scratch
    ((8, 128, 8, 64), jnp.bfloat16),       # one tile
    ((4, 1024, 8, 64), jnp.float32),       # f32 operands: halved tiles
], ids=["dh128", "seq2048", "seq128", "f32"])
def test_flash_compiles_at_other_shapes(one_chip, no_persistent_cache,
                                        shape, dtype):
    """The plan's other branches, causal, under auto."""
    _compile_flash(one_chip, shape, True, None, dtype)


def test_flash_runs_per_shard_on_a_2x2_mesh(topo, no_persistent_cache):
    """GSPMD cannot partition a Pallas call: with dp/tp-sharded operands
    the bare kernel does not even lower, and under shard_map — what
    GPTForCausalLMScan.shard_attention does — each chip runs it on its
    own (8/dp)*(16/tp) = 32 batch-heads, nothing gathered around it."""
    import functools

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed.collective import shard_map

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "tp"))
    spec = P("dp", None, "tp", None)
    x = jax.ShapeDtypeStruct((8, 1024, 16, 64), jnp.bfloat16,
                             sharding=NamedSharding(mesh, spec))
    flash = functools.partial(flash_attention, causal=True)
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(flash).lower(x, x, x)
    text = jax.jit(shard_map(flash, mesh, in_specs=(spec,) * 3,
                             out_specs=spec, check=False)
                   ).lower(x, x, x).compile().as_text()
    assert "tpu_custom_call" in text and "bf16[32,1024,64]" in text
    assert "all-gather" not in text


def test_engine_decode_compiles_at_gpt3_medium(one_chip,
                                               no_persistent_cache):
    """The engine's largest decode program (batch bucket = the default
    slots) at gpt3-medium with the pool the default flags give, pools
    donated as on a chip. Shapes only: the stacked params are a one-layer
    model's, re-declared at depth 24. The decode pass works on the pool
    where it lies: the lowering for the described chip takes the Pallas
    kernel (the host's default backend is a CPU), the output pools alias
    the inputs, and nothing of a pool's or of the gathered rows' size is
    copied or held as a temporary (8.11 GiB before the pass was in
    place)."""
    import re

    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import GenerativeEngine
    from paddle_tpu.inference.serving.generate import stack_gpt_params
    from paddle_tpu.models import PRESETS, GPTConfig, GPTForCausalLM

    cfg = PRESETS["gpt3-medium"]
    paddle.seed(0)
    one_layer, _ = stack_gpt_params(GPTForCausalLM(GPTConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_layers=1, num_heads=cfg.num_heads,
        max_seq_len=cfg.max_seq_len)))
    eng = GenerativeEngine(params=(one_layer, cfg), warmup=False,
                           auto_start=False, donate=True)
    try:
        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        params = {
            n: sds(v.shape if n in ("wte", "wpe", "lnf_w", "lnf_b")
                   else (cfg.num_layers,) + v.shape[1:], v.dtype)
            for n, v in one_layer.items()}
        b = eng._batch_buckets[-1]
        cap = eng._caps[-1]
        pool_shape = eng._pool_shape(cap)
        pool = sds(pool_shape, jnp.float32)
        compiled = eng._program("decode", cap, b).lower(
            params, pool, pool, sds((b,), jnp.int32), sds((b,), jnp.int32),
            sds((b,), jnp.int32), sds((b,), jnp.float32),
            sds((b,), jnp.int32), sds((b,), jnp.float32),
            sds((b, 2), jnp.uint32)).compile()
    finally:
        eng.shutdown(drain=False)
    mem = compiled.memory_analysis()
    assert (b, cap) == (8, 1024)
    # params (1.4 GB f32) + both pools fit one chip's 15.75 GiB with room
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12 * 2**30
    assert mem.temp_size_in_bytes < 2**30
    pool_bytes = 4 * int(np.prod(pool_shape))
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "decode_attn" in text

    def dims(shape):
        return ",".join(str(d) for d in shape)

    # a pool, the rows a step decodes gathered whole, or a layer of them
    for shape in (pool_shape, (b,) + pool_shape[1:],
                  (pool_shape[1], b) + pool_shape[2:]):
        copies = re.findall(
            r"= f32\[" + dims(shape) + r"\]\S* copy\(", text)
        assert not copies, copies
