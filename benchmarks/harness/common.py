"""What both drivers need around the system under test."""
from __future__ import annotations

import contextlib
import json
import sys


def log(**fields) -> None:
    """An earlier line of stdout: free-form, one JSON object."""
    print(json.dumps(fields), flush=True)


def verdict(compared: dict) -> bool:
    """`correct`: every number compared, {name: (value, limit)}, is within
    its limit. A value that is not a number is not within it."""
    return all(value <= limit for value, limit in compared.values())


def require_chips(n: int):
    """jax's first n devices, or SystemExit (nothing on stdout) where jax
    finds no TPU or fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"{sys.argv[0]}: needs a TPU, jax found "
            f"{devs[0].platform}:{devs[0].device_kind} x{len(devs)}")
    if len(devs) < n:
        raise SystemExit(f"{sys.argv[0]}: the cell needs {n} chips, jax "
                         f"found {len(devs)}")
    return devs[:n]


@contextlib.contextmanager
def seeded_weights(seed: int):
    """The entry points (bench.build_train_step, serve.build_generator)
    seed their demo weights with a fixed `paddle.seed(0)` and take no seed
    of their own; the benchmark's weights come from --seed. While the
    entry point runs, its call of `paddle.seed` seeds with ours. (PERF.md
    §7: a `seed=` argument on the entry points makes this unnecessary.)"""
    import paddle_tpu as paddle

    real = paddle.seed
    paddle.seed = lambda _fixed: real(int(seed) % (2 ** 31))
    try:
        yield
    finally:
        paddle.seed = real


def device_dict(devices, program_bytes: int = 0) -> dict:
    """The result line's `device`. memory_peak_bytes is the fullest chip's
    peak: the allocator's `peak_bytes_in_use`, or — where that counter
    leaves a running program's temporaries out, as it does on this
    runtime (PERF.md §2) — what stays resident plus the largest program's
    temporaries (`program_bytes`, from the compile's memory_analysis)."""
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        log(memory_stats={k: st.get(k) for k in (
            "bytes_in_use", "peak_bytes_in_use", "bytes_limit")},
            device=str(d), program_temp_bytes=int(program_bytes))
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)),
                   int(st.get("bytes_in_use", 0)) + int(program_bytes))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def start_profile(trace_dir: str) -> None:
    """Start jax's profiler into an emptied `trace_dir`, with the Python
    tracer off: it would log every call of the engine's worker thread,
    slow the host it measures and swell the file. Device operations and
    the runtime's own host events stay."""
    import shutil

    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def lookups(cc) -> int:
    """Persistent-cache lookups so far: every fresh jit compile makes one
    (hit or miss); a program already in memory makes none."""
    st = cc.stats()
    return int(st["hits"]) + int(st["misses"])
