"""Latent attention's decode work, and where a trace and the counters show
it: the bytes and operations an absorbed-form decode step has to spend on
the latent pool, computed from the configuration's sizes and the engine's
counter of attended positions — the same work whatever implements the read
(a kernel over padded lanes or whole blocks, a gather, plain XLA) — and the
`mla_decode` kernels' self time on the lowest device.

Kept beside flops.py so that a PR that claims a gain cannot change what is
divided by the time.

**The least work.** A real row whose new token sits at position t attends
t + 1 positions of its latent rows in every layer: each of them is read
once — the latent and the rotary key, `kv_lora_rank + qk_rope_head_dim`
values in the pool's type — and each head takes a score over that width
and a weighted sum over the latent, `H (W + C)` multiply-adds. Lanes the
implementation pads the row to, and blocks it reads past a row's position,
count against it.

Against a program that has no such kernel or counter (the parent of the PR
that brought them) every function here finds nothing and returns None;
none raises.
"""
from __future__ import annotations

import re
from typing import Optional

from . import moe_work
from . import trace_reduce as tr

KERNEL = "mla_decode"
ITEMSIZE = {"bfloat16": 2, "float32": 4}


def latent_width(arch: dict) -> int:
    """Values of one latent row: the latent and the shared rotary key."""
    return int(arch["kv_lora_rank"]) + int(arch["qk_rope_head_dim"])


def decode_work(positions: float, arch: dict) -> dict:
    """The least work of the latent reads of one decode step whose real
    rows attend `positions` positions in all (summed over the rows), over
    every layer."""
    layers = int(arch["num_hidden_layers"])
    width = latent_width(arch)
    heads = int(arch["num_attention_heads"])
    latent = int(arch["kv_lora_rank"])
    return {"bytes": positions * layers * width
            * ITEMSIZE[arch.get("dtype", "bfloat16")],
            "flops": positions * layers * heads * (width + latent) * 2.0}


def kernel_seconds(view: dict) -> Optional[dict]:
    """{"events", "seconds", "busy_s", "decode_steps"} of the lowest
    device: the `mla_decode.N` operations (the name the program's
    `pallas_call` carries) and their self time, beside all self time and
    the decode programs' events on the `XLA Modules` line; None where the
    trace holds no such operation."""
    d = min(view["ops"])
    events, seconds, busy = 0, 0.0, 0.0
    for text, _start, _end, _op_name, self_ns in view["ops"][d]:
        busy += self_ns / 1e9
        if re.sub(r"\.\d+$", "", tr.short_name(text)) == KERNEL:
            events += 1
            seconds += self_ns / 1e9
    if not events:
        return None
    steps = sum(1 for name, _, _ in view["modules"].get(d, [])
                if "_decode_" in moe_work.program_of(name))
    return {"events": events, "seconds": seconds, "busy_s": busy,
            "decode_steps": steps}


def counters_delta(run: dict) -> Optional[dict]:
    """The window's counters (snapshot after less snapshot before) ->
    {"attended", "steps", "rows"}; None for a run that is not served, a
    program without the counter, or a window in which no step attended."""
    serve = run.get("serve")
    if not serve or "attended_positions_total" not in serve["snap1"]:
        return None
    a, b = serve["snap0"], serve["snap1"]
    attended = b["attended_positions_total"] \
        - a.get("attended_positions_total", 0)
    steps = b["steps_total"] - a["steps_total"]
    if attended <= 0 or steps <= 0:
        return None
    return {"attended": attended, "steps": steps,
            "rows": b["step_rows_total"] - a["step_rows_total"]}
