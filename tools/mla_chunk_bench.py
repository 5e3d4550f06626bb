"""Time the `mla_decode` kernel alone at the latent cell's shapes, by plan.

128 rows of 32 heads over a pool `[129, 8, 8192, 384]` bfloat16 (the
`mistral-small-4-119b.serve-longcontext-128` cell's), each row's position
drawn as that cell's traffic draws it (a prompt of 1,024–2,048 tokens, an
answer of 2,048–5,120, the row somewhere in its answer). One program runs
the kernel once a layer, eight layers, as a decode step does. A line a
plan (positions a block of products takes, positions a copy of a row's
last block reads, copies in flight) gives the time a layer-call, the share
of the least time (the attended positions' 320 values at the HBM peak, as
the benchmark's `mla.decode_kernel.roofline_share` counts it), the
positions copied over those attended, and the largest gap to the
`jax.numpy` twin.

    python tools/mla_chunk_bench.py --blocks 1024,2048 --chunks 128,256 \
        --in-flight 2,3

Needs a TPU. Against a tree whose kernel has no chunk (`_CHUNK_BYTES`
absent) it times that tree's own plan alone.
"""
from __future__ import annotations

import argparse
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops.pallas import mla_decode as mla

ROWS, HEADS, WIDTH, LATENT, ROPE = 128, 32, 384, 256, 64
LAYERS, CAP, SLOTS = 8, 8192, 129
HBM_BYTES_PER_S = 819e9          # one v5e (Google Cloud, "TPU v5e")


def positions(seed: int) -> np.ndarray:
    rng = np.random.RandomState(seed)
    prompt = rng.randint(1024, 2049, ROWS)
    answer = rng.randint(2048, 5121, ROWS)
    return np.minimum(prompt + (rng.random_sample(ROWS) * answer)
                      .astype(np.int64), CAP - 1).astype(np.int32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="1024")
    ap.add_argument("--chunks", default="128,256,512,1024")
    ap.add_argument("--in-flight", default="1,2,3",
                    help="copies the kernel keeps out at once")
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--reps", type=int, default=30)
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    lanes = jnp.arange(WIDTH) < LATENT + ROPE

    @jax.jit
    def make_pool(key):
        # 16 slots drawn, tiled over the 129: the draw's bits at the
        # whole pool's size would not fit beside it
        part = jax.random.normal(key, (16, LAYERS, CAP, WIDTH), jnp.bfloat16)
        return jnp.where(lanes, jnp.tile(part, (-(-SLOTS // 16), 1, 1, 1))
                         [:SLOTS], 0).astype(jnp.bfloat16)

    pool = make_pool(k1)
    q = jnp.where(lanes, jax.random.normal(
        k2, (ROWS, HEADS, WIDTH), jnp.bfloat16), 0).astype(jnp.bfloat16)
    slots = jnp.asarray(np.random.RandomState(0).permutation(ROWS),
                        jnp.int32)
    kw = dict(value_width=LATENT, sm_scale=0.195, scratch=SLOTS - 1)
    chunked = hasattr(mla, "_CHUNK_BYTES")
    grid = [(int(b), int(c), int(d)) for b in a.blocks.split(",")
            for c in a.chunks.split(",") for d in a.in_flight.split(",")
            if int(c) <= int(b)] if chunked else [(0, 0, 0)]

    @jax.jit
    def step(q, pool, slots, lengths):
        out = jnp.zeros((ROWS, HEADS, LATENT), jnp.float32)
        for layer in range(LAYERS):
            out = out + mla.mla_decode(q, pool, layer, slots, lengths, **kw)
        return out

    for block, chunk, depth in grid:
        if chunk:
            mla._BLOCK_BYTES = block * WIDTH * 2
            mla._CHUNK_BYTES = chunk * WIDTH * 2
            mla._IN_FLIGHT = depth
        mla.mla_decode.clear_cache()
        step.clear_cache()
        plan = mla.latent_block_plan(CAP, HEADS, WIDTH, LATENT, 2)
        per_call, shares, gaps = [], [], []
        copied = attended = 0
        for seed in (int(s) for s in a.seeds.split(",")):
            lengths = jnp.asarray(positions(seed))
            step(q, pool, slots, lengths).block_until_ready()
            times = []
            for _ in range(a.reps):
                t = time.perf_counter()
                step(q, pool, slots, lengths).block_until_ready()
                times.append(time.perf_counter() - t)
            call = statistics.median(times) / LAYERS
            att = int((np.asarray(lengths) + 1).sum())
            per_call.append(call)
            shares.append(att * (LATENT + ROPE) * 2 / HBM_BYTES_PER_S / call)
            attended += att
            copied += sum(plan.positions_read(int(x), CAP)
                          for x in np.asarray(lengths))
            kern = mla.mla_decode(q, pool, 3, slots, lengths, **kw)
            twin = mla.mla_decode_twin(q, pool, 3, slots, lengths, **kw)
            gaps.append(float(jnp.max(jnp.abs(kern - twin))))
        print(json.dumps({
            "block": plan.block, "chunk": getattr(plan, "chunk", None),
            "in_flight": depth or None,
            "ms_per_layer_call": [round(x * 1e3, 4) for x in per_call],
            "roofline_share": [round(x, 4) for x in shares],
            "copied_over_attended": round(copied / attended, 4),
            "max_abs_gap_to_twin": max(gaps),
            "device": dev.device_kind}), flush=True)


if __name__ == "__main__":
    main()
