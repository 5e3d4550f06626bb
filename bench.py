"""GPT causal-LM training step on the TPU, and one timed measurement of it.

`build_train_step` is the program the repo's chip entry points share:
chip_smoke.py's train and --multichip phases, __graft_entry__'s scale
tier on virtual devices, and (ROADMAP S1) the training cells. Model:
"gpt3-medium" (hidden 1024, 24 layers, 16 heads, seq 1024, 354.9M
params) as a scan-over-layers stack, bf16 params via amp.decorate O2,
AdamW with f32 master weights, chunked fused LM-head cross-entropy, the
compiled TrainStep (fused fwd+bwd+update, donated state).

    python bench.py

prints ONE JSON line that names the device it ran on, and exits non-zero
— reason on stderr, nothing on stdout — where jax finds no TPU. One
process, no CPU fallback, no cached payload, no retries.

vs_baseline: BASELINE.json's north star is >=70% of A100+NCCL tokens/sec/
device. The reference repo publishes no absolute numbers (BASELINE.md), so
the A100 anchor is computed from the standard transformer cost model
(6*N FLOPs/token) at 50% MFU on A100 312 TFLOPs bf16:
    a100_tokens_per_sec = 312e12 * 0.5 / (6 * N_params)
vs_baseline = value / (0.7 * a100_tokens_per_sec)  -> 1.0 means we hit the
70%-of-A100 target on this chip.
"""
from __future__ import annotations

import json
import sys
import time

# The one-chip training configuration. Whole-step compiles for a described
# v5e (PERF.md "HBM arithmetic", GiB of 15.75): batch 8 without remat needs
# 19.30 and is refused; remat per scan iteration brings the same batch to
# 6.56, batch 4 without remat to 12.79. Remat at batch 8 keeps the
# 8x1024-token step and leaves room for whatever else the process holds on
# the device.
PRESET, BATCH, SEQ = "gpt3-medium", 8, 1024
MESH_AXES = ("dp", "tp")


def require_tpu():
    """jax's devices, or SystemExit where the first one is not a TPU —
    the chip entry points never fall back to another platform."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"{sys.argv[0]}: needs a TPU, jax found "
            f"{devs[0].platform}:{devs[0].device_kind} x{len(devs)}")
    return devs


def device_dict(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def dp_tp_mesh(devices, tp: int):
    """(dp, tp) mesh over `devices` — the layout __graft_entry__'s dry run
    rehearses on virtual devices and chip_smoke.py --multichip runs."""
    import numpy as np
    from jax.sharding import Mesh

    devices = np.asarray(devices)
    return Mesh(devices.reshape(devices.size // tp, tp), MESH_AXES)


def build_train_step(preset: str = PRESET, batch: int = BATCH,
                     seq: int = SEQ, mesh=None):
    """Model + compiled TrainStep + one fixed batch, seeded.

    With `mesh` (a dp_tp_mesh) the step is the sharded one: Megatron
    column/row-parallel stacked weights over "tp", ZeRO-1 moments and the
    batch over "dp", attention running per (dp, tp) shard.
    Returns (step, ids, labels, n_params).
    """
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import amp
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import (PRESETS, GPTForCausalLM,
                                   GPTForCausalLMScan, gpt_scan_shard_fn)
    from paddle_tpu.nn.functional_more import fused_linear_cross_entropy

    cfg = PRESETS[preset]
    paddle.seed(0)
    # scan-over-layers: ONE traced block body instead of num_layers
    # copies — ~L-fold smaller program, proportionally faster compile
    model = GPTForCausalLMScan.from_unrolled(GPTForCausalLM(cfg))
    model.remat = True
    model.train()
    # bf16 params (O2); AdamW keeps fp32 master weights + moments
    model = amp.decorate(model, level="O2", dtype="bfloat16")
    optimizer = opt.AdamW(1e-4, parameters=model.parameters(),
                          weight_decay=0.01, multi_precision=True)

    def loss_fn(m, ids, labels):
        # chunked fused LM-head+CE never materializes the [B*L, vocab]
        # logits (824 MB bf16 at this scale): the head matmul runs per
        # token-chunk with f32 accumulation and remats in backward
        return fused_linear_cross_entropy(
            m.hidden(ids), m.wte.weight, labels, transpose_y=True,
            chunk=2048)

    sharded = {}
    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        model.shard_attention(mesh, MESH_AXES)
        sharded = dict(mesh=mesh, shard_fn=gpt_scan_shard_fn(MESH_AXES),
                       zero_stage=1, dp_axis=MESH_AXES[0],
                       batch_sharding=(P(MESH_AXES[0], None),) * 2)
    step = TrainStep(model, optimizer, loss_fn, **sharded)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int64")
    labels = np.roll(ids, -1, axis=1)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    return step, ids, labels, n_params


def measure(iters: int = 20) -> dict:
    from paddle_tpu.profiler.stats import device_peaks

    devs = require_tpu()
    peak = device_peaks(devs[0].device_kind)["bf16_flops"]
    step, ids, labels, n_params = build_train_step()
    batch, seq = ids.shape

    # compile + warmup; the host read of the loss drains the step
    t0 = time.perf_counter()
    float(step(ids, labels).numpy())
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(ids, labels)
    final_loss = float(loss.numpy())
    dt = time.perf_counter() - t0

    tokens_per_sec = batch * seq * iters / dt
    a100_tps = 312e12 * 0.5 / (6 * n_params)
    return {
        "metric": "gpt350m_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tokens_per_sec / (0.7 * a100_tps), 4),
        "device": device_dict(devs),
        # model FLOPs (6*N per token; recompute not counted) over the
        # table's bf16 peak for this device_kind
        "mfu": round(6 * n_params * tokens_per_sec / peak, 4),
        "config": {"preset": PRESET, "batch": batch, "seq": seq,
                   "remat": True, "n_params": n_params},
        "iters": iters,
        "step_ms": round(dt / iters * 1e3, 2),
        "compile_s": round(compile_s, 1),
        "final_loss": round(final_loss, 4),
        "peak_hbm_bytes": devs[0].memory_stats()["peak_bytes_in_use"],
    }


if __name__ == "__main__":
    print(json.dumps(measure()))
