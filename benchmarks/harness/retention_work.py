"""The retention state's work, and where a trace and the counters show it:
the bytes a decode step of power retention has to move, computed from the
configuration's sizes — the same work whatever implements the step (a
kernel over a tiled or padded state, plain XLA) — the `retention_step`
kernels' events and self time on the lowest device, and the engine's
state counter over the window.

Kept beside flops.py so that a PR that claims a gain cannot change what is
divided by the time.

**The least work.** A row's state in one layer is, a K/V head, the second
symmetric power of a key against a value: `D = Dh (Dh + 1) / 2` products
(8,256 for Dh = 128) times `Dh` values, and the normaliser's `D` — float32.
A decode step reads and writes all of it once for each REAL row and layer
and nothing else of any size, so its least time is those bytes at the HBM
peak. A layout that tiles or pads the state for the lanes moves more; that
is the implementation's cost, and `retention.state_bytes_per_token` (the
engine's own counter) shows it.

Against a program that has no such kernel or counter (the parent of the PR
that brought them) every function here finds nothing and returns None;
none raises.
"""
from __future__ import annotations

import re
from typing import Optional

from . import trace_reduce as tr

KERNEL = "retention_step"
STATE_ITEMSIZE = 4          # float32, by the model's numerics


def state_elements_per_row_layer(arch: dict) -> int:
    """Elements of one row's state in one layer: for each K/V head the
    symmetric second power of a key, `Dh (Dh + 1) / 2`, against `Dh`
    values and the one normaliser."""
    dh = int(arch["head_dim"])
    return int(arch["num_key_value_heads"]) * (dh * (dh + 1) // 2) * (dh + 1)


def step_work(rows: float, arch: dict) -> dict:
    """The least work of ONE retention layer on one decode step of `rows`
    real rows: each row's state read and written once; per element of it a
    decay, the token's product added, and a multiply-add a query head
    (G = H / Hkv of them) for the read."""
    elements = rows * state_elements_per_row_layer(arch)
    groups = int(arch["num_attention_heads"]) \
        // int(arch["num_key_value_heads"])
    return {"flops": elements * (3.0 + 2.0 * groups),
            "bytes": elements * 2.0 * STATE_ITEMSIZE}


def kernel_seconds(view: dict) -> Optional[dict]:
    """{"events", "seconds", "busy_s"} of the lowest device: the
    `retention_step.N` operations (the name the program's `pallas_call`
    carries) and their self time, beside all self time; None where the
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
    return {"events": events, "seconds": seconds, "busy_s": busy}


def counters_delta(run: dict) -> Optional[dict]:
    """The window's counters (snapshot after less snapshot before) ->
    {"state_bytes", "tokens", "rows", "steps"}; None for a run that is not
    served, a program without the state counter, or a window in which no
    step moved any state."""
    serve = run.get("serve")
    if not serve or "state_bytes_moved_total" not in serve["snap1"]:
        return None
    a, b = serve["snap0"], serve["snap1"]
    moved = b["state_bytes_moved_total"] - a.get("state_bytes_moved_total", 0)
    if moved <= 0:
        return None
    return {"state_bytes": moved,
            "tokens": b["tokens_out_total"] - a["tokens_out_total"],
            "rows": b["step_rows_total"] - a["step_rows_total"],
            "steps": b["steps_total"] - a["steps_total"]}
