"""Calibrate the planner's cost-model constants from MEASURED step times
(round-3 verdict task 7; reference analog:
python/paddle/distributed/auto_parallel/cost_model.py:25 profiled-table
mode vs the modeled defaults).

Runs a sweep of (dp, tp[, zero]) plans of a tiny GPT as REAL compiled
steps on whatever mesh this host offers (the 8-virtual-device CPU mesh in
CI; one TPU host), fits ClusterSpec's (mfu_guess,
ici_bandwidth, dcn_bandwidth) by non-negative least squares over the cost
model's own terms (planner.calibrate), and writes the fitted spec to
tools/planner_cluster.json, which Planner picks up via
ClusterSpec? -> load_calibrated().

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/calibrate_planner.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CAL_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "planner_cluster.json")


def sweep_plans(n_devices: int):
    """The measured sweep: every (dp, tp) factorization of the mesh plus
    a ZeRO-1 variant of the all-dp plan."""
    from paddle_tpu.distributed.planner import Plan

    plans = []
    tp = 1
    while tp <= n_devices:
        plans.append(Plan(dp=n_devices // tp, tp=tp, pp=1))
        tp *= 2
    if n_devices > 1:
        plans.append(Plan(dp=n_devices, tp=1, pp=1, zero_stage=1))
    return plans


def measure_plan(plan, cfg, global_batch: int, iters: int = 8):
    """Median wall time (s) of one compiled train step under the plan's
    mesh factorization."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import gpt_shard_fn

    devs = np.array(jax.devices()[:plan.dp * plan.tp])
    mesh = Mesh(devs.reshape(plan.dp, plan.tp), ("dp", "tp"))
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.train()
    optimizer = opt.AdamW(1e-4, parameters=model.parameters())

    def loss_fn(m, ids, labels):
        return m.loss(ids, labels)

    step = TrainStep(model, optimizer, loss_fn, mesh=mesh,
                     shard_fn=gpt_shard_fn(("dp", "tp")),
                     zero_stage=plan.zero_stage,
                     batch_sharding=(P("dp"), P("dp")))
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size,
                      (global_batch, cfg.max_seq_len)).astype("int64")
    labels = np.roll(ids, -1, axis=1)
    loss = step(ids, labels)
    float(loss.numpy())  # compile + warmup drain
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        loss = step(ids, labels)
        float(loss.numpy())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def run_sweep(cfg=None, global_batch: int = 8, iters: int = 8):
    """[(Plan, measured_seconds)] over this host's devices."""
    import jax

    from paddle_tpu.models import PRESETS

    cfg = cfg or PRESETS["gpt3-tiny"]
    n = len(jax.devices())
    out = []
    for plan in sweep_plans(n):
        t = measure_plan(plan, cfg, global_batch, iters)
        print(f"# measured dp={plan.dp} tp={plan.tp} "
              f"zero={plan.zero_stage}: {t * 1e3:.1f} ms", file=sys.stderr)
        out.append((plan, t))
    return out, cfg, n


def load_calibrated(path: str = CAL_PATH):
    """ClusterSpec from a saved calibration, or None. (Planner() also
    consults this file by default — planner.load_calibrated_cluster.)"""
    from paddle_tpu.distributed.planner import load_calibrated_cluster

    return load_calibrated_cluster(path)


def main():
    import dataclasses

    import jax

    from paddle_tpu.distributed.planner import (ClusterSpec, ModelSpec,
                                                calibrate)
    from paddle_tpu.models import PRESETS

    samples, cfg, n = run_sweep()
    model = ModelSpec.from_gpt_config(cfg, global_batch=8)
    prior = ClusterSpec(num_devices=n)
    fitted = calibrate(samples, prior, model)
    payload = dataclasses.asdict(fitted)
    from stamp import stamp

    meta = {
        "backend": jax.default_backend(),
        "sweep": [{"dp": p.dp, "tp": p.tp, "zero": p.zero_stage,
                   "measured_ms": round(t * 1e3, 2)}
                  for p, t in samples],
        **stamp(),
    }
    from paddle_tpu.distributed.checkpoint import atomic_write_json

    atomic_write_json(CAL_PATH, payload, indent=1)
    # provenance alongside (the spec file itself must stay pure
    # ClusterSpec kwargs for load_calibrated_cluster)
    atomic_write_json(CAL_PATH.replace(".json", "_meta.json"), meta,
                      indent=1)
    print(json.dumps({"fitted": payload, "meta": meta}))


if __name__ == "__main__":
    main()
