"""AOT-scale validation of the BASELINE.md north-star configs WITHOUT a
chip (round-3 verdict task 2): the real model sizes — gpt3-1.3b DP8 +
ZeRO-1 and a gpt3-6.7b TP4 pipeline stage — must compile through GSPMD on
virtual meshes, and the planner's HBM estimate must fit a v4 chip budget.

Params are abstract (jax.ShapeDtypeStruct) so nothing is materialized:
`jit(step).lower(...).compile()` exercises tracing + SPMD partitioning +
XLA compilation at the true tensor shapes (tied-embedding sharding, scan
over 24/32 real layers, 50304 vocab) where toy shapes hide bugs.

Reference scale-model fixture: test/auto_parallel/get_gpt_model.py.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.models.gpt import (PRESETS, _gpt_scan_blocks_p,
                                   layer_stack)
from paddle_tpu.nn.functional_more import fused_linear_cross_entropy

V4_HBM_GB = 32.0  # TPU v4 per-chip HBM (BASELINE.md runs on v4-32)


def _scan_param_shapes(cfg, dtype, first_stage=True, layers=None):
    """Abstract param pytree of GPTForCausalLMScan (models/gpt.py:295)."""
    L = layers if layers is not None else cfg.num_layers
    D, F = cfg.hidden_size, cfg.ffn_hidden
    sd = lambda shape: jax.ShapeDtypeStruct(shape, dtype)  # noqa: E731
    p = {
        "ln1_w": sd((L, D)), "ln1_b": sd((L, D)),
        "qkv_w": sd((L, D, 3 * D)), "qkv_b": sd((L, 3 * D)),
        "out_w": sd((L, D, D)), "out_b": sd((L, D)),
        "ln2_w": sd((L, D)), "ln2_b": sd((L, D)),
        "fc1_w": sd((L, D, F)), "fc1_b": sd((L, F)),
        "fc2_w": sd((L, F, D)), "fc2_b": sd((L, D)),
    }
    if first_stage:
        p["wte"] = sd((cfg.vocab_size, D))
        p["wpe"] = sd((cfg.max_seq_len, D))
        p["lnf_w"] = sd((D,))
        p["lnf_b"] = sd((D,))
    return p


def _hidden(params, ids, cfg, remat=True):
    """Embedding + scan-over-layers + final LN, the bench model's hidden
    path on a raw param dict."""
    x = jnp.take(params["wte"], ids, axis=0) + \
        params["wpe"][None, : ids.shape[1]]
    h = _gpt_scan_blocks_p._pure_fn(
        x, *layer_stack(params),
        num_heads=cfg.num_heads, eps=cfg.layer_norm_eps, remat=remat)
    mu = h.mean(-1, keepdims=True)
    var = ((h - mu) ** 2).mean(-1, keepdims=True)
    return (h - mu) / jnp.sqrt(var + cfg.layer_norm_eps) \
        * params["lnf_w"] + params["lnf_b"]


def _adamw(params, master, m, v, grads, lr=1e-4):
    """The compiled-step optimizer math (mirrors jit/train_step.py's
    fused fwd+bwd+AdamW program: bf16 params, f32 master + moments)."""
    b1, b2, eps, wd = 0.9, 0.95, 1e-8, 0.01
    new_m = jax.tree.map(lambda mm, g: b1 * mm + (1 - b1) * g.astype(
        jnp.float32), m, grads)
    new_v = jax.tree.map(lambda vv, g: b2 * vv + (1 - b2) * jnp.square(
        g.astype(jnp.float32)), v, grads)
    new_master = jax.tree.map(
        lambda p, mm, vv: (p - lr * (mm / (jnp.sqrt(vv) + eps) + wd * p)),
        master, new_m, new_v)
    new_params = jax.tree.map(lambda mp, p: mp.astype(p.dtype),
                              new_master, params)
    return new_params, new_master, new_m, new_v


def _zero1_spec(shape, dp, axis="dp"):
    """Shard the largest dp-divisible dim (TrainStep's zspec rule,
    jit/train_step.py:157)."""
    entries = [None] * len(shape)
    for i in sorted(range(len(shape)), key=lambda i: -shape[i]):
        if shape[i] % dp == 0 and shape[i] >= dp:
            entries[i] = axis
            return P(*entries)
    return P(*entries)


@pytest.fixture(scope="module")
def mesh8():
    devs = jax.devices()
    assert len(devs) >= 8, "conftest must force 8 virtual CPU devices"
    return Mesh(np.array(devs[:8]), ("dp",))


class TestGPT13BDataParallel:
    """gpt3-1.3b DP8 + ZeRO-1: the BASELINE.md flagship row."""

    def test_step_compiles_and_fits_hbm(self, mesh8):
        cfg = PRESETS["gpt3-1.3b"]
        batch, seq = 8, 1024
        dp = 8

        params = _scan_param_shapes(cfg, jnp.bfloat16)
        master = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params)
        m = master
        v = master
        ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        labels = jax.ShapeDtypeStruct((batch, seq), jnp.int32)

        def step(params, master, m, v, ids, labels):
            def loss_fn(p):
                h = _hidden(p, ids, cfg)
                out = fused_linear_cross_entropy(
                    h, p["wte"], labels, transpose_y=True, chunk=2048)
                return getattr(out, "_data", out)  # Tensor -> raw array

            loss, grads = jax.value_and_grad(loss_fn)(params)
            new_p, new_master, new_m, new_v = _adamw(params, master, m, v,
                                                     grads)
            return loss, new_p, new_master, new_m, new_v

        rep = NamedSharding(mesh8, P())
        p_sh = jax.tree.map(lambda _: rep, params)
        # ZeRO-1: optimizer state (master + moments) dp-sharded; GSPMD
        # emits reduce-scatter(grads)/all-gather(params) from the specs
        z_sh = jax.tree.map(
            lambda s: NamedSharding(mesh8, _zero1_spec(s.shape, dp)),
            master)
        b_sh = NamedSharding(mesh8, P("dp"))

        jitted = jax.jit(
            step,
            in_shardings=(p_sh, z_sh, z_sh, z_sh, b_sh, b_sh),
            out_shardings=(NamedSharding(mesh8, P()), p_sh, z_sh, z_sh,
                           z_sh),
            donate_argnums=(0, 1, 2, 3))
        compiled = jitted.lower(params, master, m, v, ids, labels).compile()
        assert compiled is not None
        # tied embedding [50304, 2048] must have survived SPMD at real
        # vocab: the head matmul and the embedding lookup share it
        text = compiled.as_text()
        assert "50304" in text

    def test_planner_hbm_within_v4_budget(self):
        from paddle_tpu.distributed.planner import (
            ClusterSpec, ModelSpec, Planner)

        cfg = PRESETS["gpt3-1.3b"]
        model = ModelSpec.from_gpt_config(cfg, global_batch=64)
        cluster = ClusterSpec(num_devices=8, hbm_bytes=V4_HBM_GB * 1e9)
        planner = Planner(cluster)
        plans = planner.search(model, top_k=50)
        assert plans, "no feasible plan for gpt3-1.3b on 8x32GB"
        dp8 = [p for p in plans if p.dp == 8 and p.tp == 1 and p.pp == 1]
        assert dp8, f"DP8 not feasible: {[str(p) for p in plans]}"
        assert dp8[0].est_hbm_gb <= V4_HBM_GB


class TestGPT67BStagePrograms:
    """gpt3-6.7b TP4 x PP4: one pipeline stage (8 of 32 layers) compiled
    under Megatron TP sharding on a 4-device mesh — the per-stage program
    the fleet executor would run on each v4-32 stage group."""

    def test_middle_stage_tp4_compiles(self):
        cfg = PRESETS["gpt3-6.7b"]
        stage_layers = cfg.num_layers // 4  # pp=4
        batch, seq = 8, 1024

        devs = jax.devices()[:4]
        mesh = Mesh(np.array(devs), ("tp",))

        params = _scan_param_shapes(cfg, jnp.bfloat16, first_stage=False,
                                    layers=stage_layers)
        x = jax.ShapeDtypeStruct((batch, seq, cfg.hidden_size),
                                 jnp.bfloat16)
        g = x

        def stage_fwd(params, x):
            return _gpt_scan_blocks_p._pure_fn(
                x, *layer_stack(params),
                num_heads=cfg.num_heads, eps=cfg.layer_norm_eps,
                remat=True)

        def stage_fwd_bwd(params, x, g):
            y, vjp = jax.vjp(lambda p, xx: stage_fwd(p, xx), params, x)
            gp, gx = vjp(g)
            return y, gp, gx

        # Megatron TP over the stacked [L, in, out] weights
        # (distributed/mp_layers.py layout): qkv/fc1 column-parallel,
        # out/fc2 row-parallel, norms/biases replicated
        tp_specs = {
            "qkv_w": P(None, None, "tp"), "qkv_b": P(None, "tp"),
            "out_w": P(None, "tp", None), "out_b": P(None, None),
            "fc1_w": P(None, None, "tp"), "fc1_b": P(None, "tp"),
            "fc2_w": P(None, "tp", None), "fc2_b": P(None, None),
            "ln1_w": P(None, None), "ln1_b": P(None, None),
            "ln2_w": P(None, None), "ln2_b": P(None, None),
        }
        p_sh = {k: NamedSharding(mesh, tp_specs[k]) for k in params}
        x_sh = NamedSharding(mesh, P())

        jitted = jax.jit(stage_fwd_bwd,
                         in_shardings=(p_sh, x_sh, x_sh),
                         out_shardings=(x_sh, p_sh, x_sh))
        compiled = jitted.lower(params, x, g).compile()
        assert compiled is not None
        text = compiled.as_text()
        # TP must actually partition: collectives present at 6.7b scale
        assert ("all-reduce" in text or "reduce-scatter" in text
                or "all-gather" in text or "collective-permute" in text)

    def test_planner_hbm_within_v4_budget(self):
        from paddle_tpu.distributed.planner import (
            ClusterSpec, ModelSpec, Planner)

        cfg = PRESETS["gpt3-6.7b"]
        model = ModelSpec.from_gpt_config(cfg, global_batch=64)
        # v4-32: 32 chips, 32 GB each (BASELINE.md hybrid row)
        cluster = ClusterSpec(num_devices=32, hbm_bytes=V4_HBM_GB * 1e9)
        planner = Planner(cluster)
        plans = planner.search(model, top_k=100)
        hybrid = [p for p in plans if p.tp == 4 and p.pp == 4]
        assert hybrid, \
            f"TP4xPP4 not feasible for 6.7b: {[str(p) for p in plans]}"
        assert hybrid[0].est_hbm_gb <= V4_HBM_GB


class TestGPT67BShardedDecode:
    """Serving path at scale: one KV-cached decode step of gpt3-6.7b
    under Megatron TP8 — params column/row-sharded, caches head-sharded —
    must compile through GSPMD at the real shapes (4096 hidden, 32
    layers, 50304 vocab). Complements tests/test_sharded_decode.py
    (which EXECUTES token-parity at tiny scale)."""

    def test_decode_step_tp8_compiles(self):
        cfg = PRESETS["gpt3-6.7b"]
        mesh = Mesh(np.array(jax.devices()[:8]), ("tp",))
        B, maxlen = 8, 1024
        L, D = cfg.num_layers, cfg.hidden_size
        H, Dh = cfg.num_heads, cfg.hidden_size // cfg.num_heads
        F = cfg.ffn_hidden
        bf = jnp.bfloat16
        sd = lambda s, dt=bf: jax.ShapeDtypeStruct(s, dt)  # noqa: E731

        params = _scan_param_shapes(cfg, bf)
        kc = sd((L, B, maxlen, H, Dh))
        vc = sd((L, B, maxlen, H, Dh))
        tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)

        def ln(x, w, b, eps=cfg.layer_norm_eps):
            xf = x.astype(jnp.float32)
            mu = xf.mean(-1, keepdims=True)
            var = ((xf - mu) ** 2).mean(-1, keepdims=True)
            return ((xf - mu) / jnp.sqrt(var + eps)).astype(x.dtype) \
                * w + b

        def step(params, kc, vc, tok, pos):
            x = jnp.take(params["wte"], tok, axis=0) \
                + jax.lax.dynamic_slice_in_dim(params["wpe"], pos, 1,
                                               axis=0)[None]

            def body(x, layer):
                (l1w, l1b, qkvw, qkvb, ow, ob, l2w, l2b,
                 f1w, f1b, f2w, f2b, k_l, v_l) = layer
                h = ln(x, l1w, l1b)
                qkv = jnp.einsum("bqd,de->bqe", h, qkvw) + qkvb
                q, k, v = (qkv.reshape(B, 1, 3, H, Dh)[:, :, i]
                           for i in range(3))
                z = jnp.int32(0)
                k_l = jax.lax.dynamic_update_slice(k_l, k, (z, pos, z, z))
                v_l = jax.lax.dynamic_update_slice(v_l, v, (z, pos, z, z))
                s = jnp.einsum("bqhd,bkhd->bhqk", q, k_l,
                               preferred_element_type=jnp.float32) \
                    / np.sqrt(Dh)
                mask = jnp.arange(maxlen)[None, None, None, :] <= pos
                s = jnp.where(mask, s, jnp.float32(-1e30))
                p = jax.nn.softmax(s, axis=-1).astype(bf)
                o = jnp.einsum("bhqk,bkhd->bqhd", p, v_l)
                x = x + jnp.einsum("bqe,ed->bqd",
                                   o.reshape(B, 1, D), ow) + ob
                h2 = ln(x, l2w, l2b)
                y = jax.nn.gelu(jnp.einsum("bqd,df->bqf", h2, f1w) + f1b)
                x = x + jnp.einsum("bqf,fd->bqd", y, f2w) + f2b
                return x, (k_l, v_l)

            layers = (params["ln1_w"], params["ln1_b"], params["qkv_w"],
                      params["qkv_b"], params["out_w"], params["out_b"],
                      params["ln2_w"], params["ln2_b"], params["fc1_w"],
                      params["fc1_b"], params["fc2_w"], params["fc2_b"],
                      kc, vc)
            x, (nkc, nvc) = jax.lax.scan(body, x, layers)
            h = ln(x, params["lnf_w"], params["lnf_b"])
            logits = jnp.einsum("bqd,vd->bqv", h, params["wte"],
                                preferred_element_type=jnp.float32)
            return jnp.argmax(logits[:, -1], axis=-1), nkc, nvc

        tp = lambda *spec: NamedSharding(mesh, P(*spec))  # noqa: E731
        p_sh = dict(
            ln1_w=tp(), ln1_b=tp(), ln2_w=tp(), ln2_b=tp(),
            qkv_w=tp(None, None, "tp"), qkv_b=tp(None, "tp"),
            out_w=tp(None, "tp", None), out_b=tp(),
            fc1_w=tp(None, None, "tp"), fc1_b=tp(None, "tp"),
            fc2_w=tp(None, "tp", None), fc2_b=tp(),
            wte=tp("tp", None), wpe=tp(), lnf_w=tp(), lnf_b=tp())
        c_sh = tp(None, None, None, "tp", None)  # caches head-sharded
        compiled = jax.jit(
            step,
            in_shardings=(p_sh, c_sh, c_sh, tp(), tp()),
            out_shardings=(tp(), c_sh, c_sh),
            donate_argnums=(1, 2),
        ).lower(params, kc, vc, tok, pos).compile()
        assert compiled is not None
        assert "50304" in compiled.as_text()  # real-vocab head survived


class TestScanFlashHeadDim128:
    """scan + flash attention at head-dim 128 (gpt3-1.3b uses 64; 6.7b
    uses 128) — Mosaic cross-lowering of the exact kernel shapes."""

    def test_flash_headdim128_mosaic_lowering(self):
        from paddle_tpu.ops.pallas.flash_attention import flash_attention

        B, L, H, D = 2, 1024, 4, 128
        q = jnp.zeros((B, L, H, D), jnp.bfloat16)

        def f(q, k, v):
            return flash_attention(q, k, v, causal=True, interpret=False)

        def g(q, k, v):
            out = flash_attention(q, k, v, causal=True, interpret=False)
            return jax.grad(
                lambda a, b, c: f(a, b, c).astype(jnp.float32).sum(),
                argnums=(0, 1, 2))(q, k, v), out

        exported = jax.export.export(jax.jit(g), platforms=["tpu"])(
            q, q, q)
        assert "tpu_custom_call" in exported.mlir_module()


class TestScanZero1TrainStepExecutes:
    """Tier-1 smoke for the multichip dry-run's SCALE tier (ISSUE 9
    satellite): a TrainStep over GPTForCausalLMScan with ZeRO-1 on a
    dp x tp mesh must EXECUTE, not just compile. Regression guard for
    the s64/s32 HLO-verifier failure: the package's jax_enable_x64
    makes the scan loop counter s64, and letting GSPMD propagate the
    dp-sharded ZeRO moment layout into the backward scan accumulator
    made the partitioner emit s32 bounds checks against it
    (train_step now pins ZeRO-1 grads to the param layout)."""

    def test_tiny_scan_zero1_dp_tp_step(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn
        import paddle_tpu.optimizer as opt
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models import (GPTConfig, GPTForCausalLMScan,
                                       gpt_scan_shard_fn)

        devs = jax.devices()
        assert len(devs) >= 4
        mesh = Mesh(np.array(devs[:4]).reshape(2, 2), ("dp", "tp"))
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=4, ffn_hidden=64, max_seq_len=64,
                        dropout=0.0)
        paddle.seed(0)
        model = GPTForCausalLMScan(cfg)
        model.train()
        o = opt.AdamW(1e-3, parameters=model.parameters())
        lossf = nn.CrossEntropyLoss()

        def loss_fn(m, ids, labels):
            logits = m(ids)
            return lossf(logits.reshape([-1, cfg.vocab_size]),
                         labels.reshape([-1]))

        with mesh:
            step = TrainStep(model, o, loss_fn, mesh=mesh,
                             shard_fn=gpt_scan_shard_fn(("dp", "tp")),
                             zero_stage=1, dp_axis="dp",
                             batch_sharding=(P("dp", None),
                                             P("dp", None)))
            ids = np.random.RandomState(0).randint(
                0, cfg.vocab_size, (2, 32)).astype("int64")
            l1 = float(step(ids, np.roll(ids, -1, 1)).numpy())
            l2 = float(step(ids, np.roll(ids, -1, 1)).numpy())
        assert np.isfinite(l1) and np.isfinite(l2) and l2 < l1

    def test_tiny_scan_zero1_accumulation_step(self):
        """Same guarantee on the GRADIENT-ACCUMULATION path: acc_step
        pins the ZeRO-1 accumulator to the param layout too (the
        monolithic-step fix alone leaves the micro-batch program open
        to the same s64/s32 partitioner failure)."""
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn
        import paddle_tpu.optimizer as opt
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models import (GPTConfig, GPTForCausalLMScan,
                                       gpt_scan_shard_fn)

        devs = jax.devices()
        mesh = Mesh(np.array(devs[:4]).reshape(2, 2), ("dp", "tp"))
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=4, ffn_hidden=64, max_seq_len=64,
                        dropout=0.0)
        paddle.seed(0)
        model = GPTForCausalLMScan(cfg)
        model.train()
        o = opt.AdamW(1e-3, parameters=model.parameters())
        lossf = nn.CrossEntropyLoss()

        def loss_fn(m, ids, labels):
            logits = m(ids)
            return lossf(logits.reshape([-1, cfg.vocab_size]),
                         labels.reshape([-1]))

        with mesh:
            step = TrainStep(model, o, loss_fn, mesh=mesh,
                             shard_fn=gpt_scan_shard_fn(("dp", "tp")),
                             zero_stage=1, dp_axis="dp",
                             accumulate_steps=2,
                             batch_sharding=(P("dp", None),
                                             P("dp", None)))
            ids = np.random.RandomState(0).randint(
                0, cfg.vocab_size, (2, 32)).astype("int64")
            labels = np.roll(ids, -1, 1)
            for _ in range(2):  # one full accumulation window
                loss = step(ids, labels)
        assert np.isfinite(float(loss.numpy()))
