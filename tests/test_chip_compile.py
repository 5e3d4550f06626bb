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


@pytest.mark.parametrize("kind, bucket", [("decode", 32), ("prefill", 128)])
def test_lfm2_programs_compile_for_a_v5e_at_the_cells_size(
        one_chip, no_persistent_cache, kind, bucket):
    """`lfm2-24b-a2b` as the benchmark's cell serves it (32 slots, one class
    of 1,024): the decode program of all slots and the prefill of the
    longest prompt bucket of the traffic, from shapes alone. The decode
    program takes the grouped-query kernel over the bfloat16 pool (fold
    [32, 512]); the pools and the conv state alias their inputs; 10.4 GB of
    bfloat16 weights, the cache and the temporaries fit one chip; no
    expert matrix is copied (a layout the matmuls cannot read in place
    would cost its bytes again, every step)."""
    import re

    from paddle_tpu.inference.serving import GenerativeEngine
    from paddle_tpu.models import lfm2

    cfg = lfm2.PRESETS["lfm2-24b-a2b"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {n: sds(shape, jnp.float32 if what == "bias" else jnp.bfloat16)
              for n, (shape, what) in lfm2.param_shapes(cfg).items()}
    eng = GenerativeEngine(params=({}, cfg), slots=32, warmup=False,
                           auto_start=False, donate=True)
    try:
        cap = eng._caps[-1]
        pool = sds(eng._pool_shape(cap), jnp.bfloat16)
        rec = sds(eng._state_shape(), jnp.bfloat16)
        assert pool.shape == (33, 2, 1024, 512)
        assert rec.shape == (33, 7, 2, 2048)
        if kind == "decode":
            b = bucket
            rows = (sds((b,), jnp.int32), sds((b,), jnp.int32),
                    sds((b,), jnp.int32), sds((b,), jnp.float32),
                    sds((b,), jnp.int32), sds((b,), jnp.float32),
                    sds((b, 2), jnp.uint32))
        else:
            rows = (sds((), jnp.int32), sds((1, bucket), jnp.int32),
                    sds((), jnp.int32), sds((), jnp.float32),
                    sds((), jnp.int32), sds((), jnp.float32),
                    sds((2,), jnp.uint32))
        compiled = eng._program(kind, cap, bucket).lower(
            params, pool, pool, *rows, rec).compile()
    finally:
        eng.shutdown(drain=False)
    mem = compiled.memory_analysis()
    n_bytes = 2 * lfm2.n_params(cfg)
    assert mem.argument_size_in_bytes > n_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 14 * 2**30
    cache_bytes = 2 * 2 * int(np.prod(pool.shape)) \
        + 2 * int(np.prod(rec.shape))
    assert mem.alias_size_in_bytes >= cache_bytes
    text = compiled.as_text()
    assert f"jit_lfm2_{kind}_c1024_b{bucket}" in text
    assert "moe.experts" in text and "lfm2.short_conv" in text \
        and "moe.route" in text
    if kind == "decode":
        assert "tpu_custom_call" in text and "decode_attn" in text
    copies = re.findall(r"= bf16\[64,(?:2048,1536|1536,2048)\]\S* copy\(",
                        text)
    assert not copies, copies
    print(kind, bucket, "temp", mem.temp_size_in_bytes, "args",
          mem.argument_size_in_bytes, "alias", mem.alias_size_in_bytes)


@pytest.mark.parametrize("kind, bucket", [("decode", 16), ("prefill", 128)])
def test_brumby_programs_compile_for_a_v5e_at_the_cells_size(
        one_chip, no_persistent_cache, kind, bucket):
    """`brumby-14b-base` as the benchmark's cell serves it (16 slots, one
    class of 1,024 positions, NO K/V pool): the decode program of all slots
    and the prefill of the traffic's longest prompt bucket, from shapes
    alone. The decode program takes the `retention_step` kernel, one a
    layer, over the float32 state where it lies: the state (4.92 GB)
    aliases its input, and the program's temporaries hold nothing of its
    size — under 100 MB, where one state-sized copy would not fit the chip
    beside 8.4 GB of weights."""
    from paddle_tpu.inference.serving import GenerativeEngine
    from paddle_tpu.models import brumby

    cfg = brumby.PRESETS["brumby-14b-base"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {n: sds(shape, jnp.float32 if what == "gate" else jnp.bfloat16)
              for n, (shape, what) in brumby.param_shapes(cfg).items()}
    eng = GenerativeEngine(params=({}, cfg), slots=16, warmup=False,
                           auto_start=False, donate=True)
    try:
        cap = eng._caps[-1]
        rec = sds(eng._state_shape(), jnp.float32)
        assert rec.shape == (17, 8, 8, 8832, 128)
        assert eng._cache_avals(cap)[:2] == (None, None)
        if kind == "decode":
            b = bucket
            rows = (sds((b,), jnp.int32), sds((b,), jnp.int32),
                    sds((b,), jnp.int32), sds((b,), jnp.float32),
                    sds((b,), jnp.int32), sds((b,), jnp.float32),
                    sds((b, 2), jnp.uint32))
        else:
            rows = (sds((), jnp.int32), sds((1, bucket), jnp.int32),
                    sds((), jnp.int32), sds((), jnp.float32),
                    sds((), jnp.int32), sds((), jnp.float32),
                    sds((2,), jnp.uint32))
        compiled = eng._program(kind, cap, bucket).lower(
            params, None, None, *rows, rec).compile()
        state_bytes = eng.kv_pool_bytes()
    finally:
        eng.shutdown(drain=False)
    mem = compiled.memory_analysis()
    assert state_bytes == 4 * int(np.prod(rec.shape)) == 4_919_918_592
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.argument_size_in_bytes > 2 * brumby.n_params(cfg) + state_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 0.85 * 16.9e9
    text = compiled.as_text()
    assert f"jit_brumby_{kind}_c1024_b{bucket}" in text
    if kind == "decode":
        assert mem.temp_size_in_bytes < 100e6
        assert "tpu_custom_call" in text and "retention_step" in text \
            and "retention.step" in text
    else:
        assert mem.temp_size_in_bytes < 2**30
        assert "retention.prefill" in text
    print(kind, bucket, "temp", mem.temp_size_in_bytes, "args",
          mem.argument_size_in_bytes, "alias", mem.alias_size_in_bytes)


# --------------------------------------------- the train step's remat plan --
# a v5e's `bytes_limit` as the chip's runtime reports it (memory_stats on
# the cells' own runs): what TrainStep's plan reads there as the device's
# memory, since a described device reports none
V5E_BYTES_LIMIT = 16_909_334_528


def _abstract_train_step(topo, monkeypatch, preset, batch, seq, sharded):
    """`bench.build_train_step`'s program at `preset`'s full shapes for
    the described chip(s), nothing allocated: the step is built at toy
    widths on this host's CPU devices, then traced over the full shapes
    (every size the model reads comes off its arguments; the two it
    reads off its config are set) with the described devices in the
    mesh's place. -> (lower(names) -> the step lowered with the block
    keeping `names`, the model's candidates)."""
    import contextlib

    import bench
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.models import PRESETS, GPTConfig, gpt
    from paddle_tpu.profiler.stats.flops import DEVICE_PEAKS

    full = PRESETS[preset]
    toy = GPTConfig(vocab_size=256, hidden_size=2 * full.num_heads,
                    num_layers=1, num_heads=full.num_heads,
                    ffn_hidden=4 * full.num_heads, max_seq_len=seq)
    monkeypatch.setitem(PRESETS, "_toy", toy)
    host_mesh = bench.dp_tp_mesh(jax.devices()[:4], tp=2) if sharded \
        else None
    with host_mesh or contextlib.nullcontext():
        step, _, _, _ = bench.build_train_step("_toy", batch, seq,
                                               mesh=host_mesh)
    model = step.model
    if sharded:
        mesh = Mesh(np.array(topo.devices).reshape(2, 2), bench.MESH_AXES)
        step.mesh = mesh
        model._attn_shard = (mesh, model._attn_shard[1])

        def place(spec):
            return NamedSharding(mesh, spec)
    else:
        mesh = None

        def place(spec):
            return SingleDeviceSharding(topo.devices[0])

    grown = {toy.hidden_size: full.hidden_size,
             3 * toy.hidden_size: 3 * full.hidden_size,
             toy.ffn_hidden: full.ffn_hidden,
             toy.vocab_size: full.vocab_size}

    def full_shape(name, shape):
        layers = [full.num_layers] if name in gpt.LAYER_PARAMS else []
        return tuple(layers + [grown.get(d, d)
                               for d in shape[len(layers):]])

    specs = step._param_specs or {}
    params = {n: jax.ShapeDtypeStruct(full_shape(n, v.shape), v.dtype,
                                      sharding=place(specs.get(n, P())))
              for n, v in step._params.items()}
    opt_specs = step._opt_specs[0] if step._opt_specs else {}
    opt = ({n: {k: jax.ShapeDtypeStruct(
        full_shape(n, v.shape) if v.shape == step._params[n].shape
        else v.shape, v.dtype,
        sharding=place(opt_specs.get(n, {}).get(k, P())))
        for k, v in state.items()}
        for n, state in step._opt_state[0].items()},)
    buffers = {n: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                       sharding=place(P()))
               for n, v in step._buffers.items()}
    batch_specs = step._batch_sharding or (P(), P())
    ids = tuple(jax.ShapeDtypeStruct((batch, seq), jnp.int64,
                                     sharding=place(s))
                for s in batch_specs)
    monkeypatch.setattr(model.cfg, "hidden_size", full.hidden_size)
    monkeypatch.setattr(model.cfg, "num_layers", full.num_layers)

    def lower(names):
        model.remat_save = tuple(names)
        step._build()
        return step._step_fn.lower(
            params, buffers, opt, jax.ShapeDtypeStruct((), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jax.random.key(0).dtype), ids)

    return lower, model.remat_candidates(
        (batch, seq), mesh, step._param_specs, batch_specs[0],
        DEVICE_PEAKS["TPU v5 lite"])


@pytest.fixture
def flash_on_this_host():
    import paddle_tpu as paddle

    paddle.set_flags({"FLAGS_force_flash_attention": True})
    yield
    paddle.set_flags({"FLAGS_force_flash_attention": False})


@pytest.mark.parametrize("preset, batch, sharded, kept", [
    ("gpt3-1.3b", 32, True, ["gpt.attn_proj", "gpt.qkv"]),
    ("gpt3-medium", 64, False, []),
], ids=["1.3b-dp2tp2", "medium"])
def test_train_step_remat_plan_by_the_chips_own_compile(
        topo, no_persistent_cache, flash_on_this_host, monkeypatch, preset,
        batch, sharded, kept):
    """Both train cells' whole steps, 24 layers, compiled for the
    described chip(s) under the plan `fit_remat_plan` reaches from the
    compiler's reports, as TrainStep does on the chip. 1.3B over dp2 x
    tp2, 16 x 1,024 tokens a dp shard: the attention projection AFTER its
    all-reduce and the QKV product fit 14.75 GiB (13.4 needed; 9.6
    without), the backward's while body holds two all-reduces of the
    residual where the policy-free one holds three (the forward's two
    stay), and the temporaries grow by what `remat_saved` counts, within
    a tenth. gpt3-medium at batch 64 on one chip has 0.5 GiB to spare:
    an empty plan, no second compile."""
    from _hlo_text import residual_reduces
    from paddle_tpu.jit import remat_plan as rp
    from paddle_tpu.jit import train_step as ts

    lower, candidates = _abstract_train_step(
        topo, monkeypatch, preset, batch, 1024, sharded)
    compiled = {}

    def need_of(names):
        compiled[names] = lower(names).compile()
        return ts._need_bytes(compiled[names].memory_analysis())

    limit = V5E_BYTES_LIMIT - rp.SPARE_BYTES
    saved = rp.fit_remat_plan(candidates, limit, need_of)
    assert saved["names"] == kept
    assert list(compiled) == ([(), tuple(kept)] if kept else [()])
    assert saved["need_bytes"] <= limit
    bare = compiled[()]
    if not kept:
        assert 0 <= saved["spare_bytes"] < min(c.bytes for c in candidates)
        assert "tpu_custom_call" in bare.as_text()
        return
    chosen = compiled[tuple(kept)]
    residual = r"bf16\[16,1024,2048\]"
    assert residual_reduces(bare.as_text(), residual) == [2, 3]
    assert residual_reduces(chosen.as_text(), residual) == [2, 2]
    grown = chosen.memory_analysis().temp_size_in_bytes \
        - bare.memory_analysis().temp_size_in_bytes
    assert saved["bytes"] == 24 * (64 + 96) * 2**20
    assert abs(grown - saved["bytes"]) < 0.1 * saved["bytes"]
    assert 13 * 2**30 < saved["need_bytes"] < 14.75 * 2**30
