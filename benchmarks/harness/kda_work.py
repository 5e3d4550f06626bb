"""Kimi delta attention's work, and where a trace and the counters show it:
the bytes a decode step of the delta rule has to move, computed from the
configuration's sizes — the same work whatever implements it (a kernel over
a padded or tiled state, plain XLA) — the `kda_step` kernels' events and
self time on the lowest device, and the engine's step counters over the
window.

Kept beside flops.py so that a PR that claims a gain cannot change what is
divided by the time.

**The least work of a decode step.** A row's state in one KDA layer is a
float32 matrix `d_k x d_v` a head. A step reads and writes all of it once
for each REAL row and layer and nothing else of any size: its least time is
those bytes at the HBM peak. Per element: the decay, the correction's read
`S'ᵀk`, the rank-one update and the query's read, a multiply-add each but
the decay — seven operations. The convolutions' windows and the
projections are not the delta rule's and are left out.

Against a program that has no such kernel or counter (the parent of the PR
that brought them) every function here finds nothing and returns None;
none raises.
"""
from __future__ import annotations

import re
from typing import Optional

from . import trace_reduce as tr

KERNEL = "kda_step"
STATE_ITEMSIZE = 4          # float32, by the model's numerics


def state_elements_per_row_layer(arch: dict) -> int:
    """Elements of one row's state in one KDA layer: a `d_k x d_v` matrix
    a head."""
    return int(arch["num_attention_heads"]) * int(arch["head_dim"]) \
        * int(arch["v_head_dim"])


def step_work(rows: float, arch: dict) -> dict:
    """The least work of ONE KDA layer on one decode step of `rows` real
    rows: each row's state read and written once, seven operations an
    element."""
    elements = rows * state_elements_per_row_layer(arch)
    return {"flops": elements * 7.0,
            "bytes": elements * 2.0 * STATE_ITEMSIZE}


def kernel_seconds(view: dict) -> Optional[dict]:
    """{"events", "seconds", "busy_s"} of the lowest device: the
    `kda_step.N` operations (the name the program's `pallas_call` carries)
    and their self time, beside all self time; None where the trace holds
    no such operation."""
    d = min(view["ops"])
    events, seconds, busy = 0, 0.0, 0.0
    for text, _start, _end, _op_name, self_ns in view["ops"][d]:
        busy += self_ns / 1e9
        if re.sub(r"\.\d+$", "", tr.short_name(text)) == KERNEL:
            events += 1
            seconds += self_ns / 1e9
    if not events:
        return None
    return {"events": events, "seconds": seconds, "busy_s": busy}


def counters_delta(run: dict) -> Optional[dict]:
    """The window's step counters (snapshot after less snapshot before) ->
    {"rows", "steps"}; None for a run that is not served or a window with
    no decode step."""
    serve = run.get("serve")
    if not serve or "step_rows_total" not in serve["snap1"]:
        return None
    a, b = serve["snap0"], serve["snap1"]
    steps = b["steps_total"] - a["steps_total"]
    if steps <= 0:
        return None
    return {"rows": b["step_rows_total"] - a["step_rows_total"],
            "steps": steps}
