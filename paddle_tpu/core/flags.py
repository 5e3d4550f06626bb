"""Runtime flag registry.

Analog of the reference's gflags-based exported flags
(`paddle/phi/core/flags.cc`, `paddle.set_flags/get_flags` at
`python/paddle/fluid/framework.py:7506`). Flags are settable from the
environment (`FLAGS_*`) at import time and from `set_flags` at runtime.
"""
from __future__ import annotations

import os
from typing import Any, Dict

_REGISTRY: Dict[str, Any] = {}
_DOC: Dict[str, str] = {}


def define_flag(name: str, default, doc: str = ""):
    """Register a flag; env var FLAGS_<name> overrides the default."""
    val = default
    env = os.environ.get("FLAGS_" + name)
    if env is not None:
        if isinstance(default, bool):
            val = env.lower() in ("1", "true", "yes", "on")
        elif isinstance(default, int):
            val = int(env)
        elif isinstance(default, float):
            val = float(env)
        else:
            val = env
    _REGISTRY[name] = val
    _DOC[name] = doc
    return val


def get_flags(names):
    if isinstance(names, str):
        names = [names]
    out = {}
    for n in names:
        key = n[6:] if n.startswith("FLAGS_") else n
        if key not in _REGISTRY:
            raise KeyError(f"unknown flag {n}")
        out[n] = _REGISTRY[key]
    return out


# bumped on every set_flags: compiled-program caches that bake flag
# values into their trace (core/dispatch.py eager-op jit + vjp caches)
# include this in their keys, so toggling a flag at runtime retraces
# instead of silently reusing a program specialized on the old value
_EPOCH = 0


def flags_epoch() -> int:
    return _EPOCH


def set_flags(flags: Dict[str, Any]):
    global _EPOCH
    # validate EVERY key before mutating anything: a partially-applied
    # call that raises mid-way would change flag values without bumping
    # the epoch — exactly the silent-stale-cache bug the epoch prevents
    resolved = {}
    for n, v in flags.items():
        key = n[6:] if n.startswith("FLAGS_") else n
        if key not in _REGISTRY:
            raise KeyError(f"unknown flag {n}")
        resolved[key] = v
    changed = False
    cache_dir_changed = False
    trace_dir_changed = False
    chaos_changed = False
    for key, v in resolved.items():
        if _REGISTRY[key] != v:
            _REGISTRY[key] = v
            changed = True
            if key == "compile_cache_dir":
                cache_dir_changed = True
            elif key in ("trace_dir", "trace_buffer_spans"):
                trace_dir_changed = True
            elif key in ("chaos_spec", "chaos_seed"):
                chaos_changed = True
    if changed:
        # no-op re-sets must NOT invalidate the compiled-program caches
        # (a per-step set_flags of an unchanged value would otherwise
        # force a full retrace every step)
        _EPOCH += 1
    if cache_dir_changed:
        # the persistent compile cache is wired at import; a runtime
        # change must re-point (or disable) jax's cache, not just the
        # registry value
        from . import compile_cache

        compile_cache.reconfigure(_REGISTRY["compile_cache_dir"])
    if trace_dir_changed:
        # the span tracer latches its enabled bit at import for a
        # zero-cost disabled path; a runtime flip must re-latch it
        from ..observability import trace

        trace.reconfigure(_REGISTRY["trace_dir"])
    if chaos_changed:
        # the chaos harness parses its rule set once (import for
        # env-armed workers, configure() for tests); a runtime spec/seed
        # change must re-arm it — configure() re-reads both flags
        from ..testing import chaos

        chaos.configure()


def flag(name: str):
    return _REGISTRY[name]


# --- Core flags (subset of the reference's ~89 exported flags that are
# meaningful on TPU/XLA; allocator-fraction style flags are handled by XLA
# itself). ---
define_flag("check_nan_inf", False, "check outputs of every op for nan/inf")
define_flag("use_flash_attention", True,
            "use the Pallas flash-attention kernel on TPU when shapes allow")
define_flag("force_flash_attention", False,
            "take the flash path even on a CPU backend (for jax.export "
            "cross-lowering tests; the kernel cannot EXECUTE on CPU)")
define_flag("attention_chunk", 256,
            "query-chunk size for the pure-XLA chunked attention "
            "fallback (used when the Pallas flash kernel is unavailable "
            "and seq >= 1024): lax.scan over query blocks with per-chunk "
            "remat bounds attention HBM traffic at [B,H,chunk,L] instead "
            "of the full [L,L] score tensor; 0 disables (plain einsum)")
define_flag("flash_dot_impl", "auto",
            "operands of the matmuls inside the flash kernels: 'bf16' "
            "feeds storage-dtype operands straight into the MXU, 'f32' "
            "casts blocks to f32 before the dots (~4x slower MXU rate), "
            "'auto' is bf16")
define_flag("dataloader_fork_workers", False,
            "DataLoader num_workers>0 uses forked worker PROCESSES (numpy-"
            "only datasets; forking after jax backend init is unsafe for "
            "datasets that touch device arrays) instead of threads")
define_flag("eager_op_jit", True, "jit-compile eager per-op executions")
define_flag("eager_jit_cache_size", 8192, "max cached compiled op programs")
define_flag("compile_cache_dir",
            os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), ".jax_cache"),
            "persistent XLA compilation-cache directory (jax "
            "jax_compilation_cache_dir): compiled per-op plan executables "
            "and TrainStep programs survive process restarts; default is "
            "one fixed path inside the checkout; empty string disables; "
            "JAX_COMPILATION_CACHE_DIR, where set, wins "
            "(core/compile_cache.py). DONATED programs are kept off the "
            "cache on the CPU backend (jaxlib serialization corrupts "
            "their aliasing — core/compile_cache.suspend_if)")
define_flag("compile_cache_min_compile_secs", 0.0,
            "only persist programs whose compile took at least this many "
            "seconds (0.0 persists everything, including the "
            "millisecond-scale eager per-op executables)")
define_flag("benchmark", False, "block on every op for accurate timing")
define_flag("serving_max_batch_size", 8,
            "serving engine: max ROWS coalesced into one executed batch "
            "(batch buckets are pow2 up to this, each AOT-compiled once)")
define_flag("serving_batch_timeout_ms", 2.0,
            "serving engine: max time the dynamic batcher holds the first "
            "request of a batch open waiting for batchmates")
define_flag("serving_max_queue_depth", 64,
            "serving engine circuit breaker: queue depth beyond which new "
            "requests are shed with 503 + Retry-After instead of growing "
            "the queue unboundedly")
define_flag("serving_default_deadline_ms", 0.0,
            "serving engine: default per-request deadline (0 = none); "
            "requests still queued past their deadline fail 503")
define_flag("generate_slots", 8,
            "generative serving: decode-batch capacity per worker (KV "
            "pool slots per class; decode batch buckets are pow2 up to "
            "this, each AOT-compiled once)")
define_flag("generate_max_new_tokens", 128,
            "generative serving: server-side cap on tokens generated per "
            "request (requests asking for more are clamped; also the "
            "default when a request does not specify max_new_tokens)")
define_flag("seed", 0, "global random seed")
define_flag("chaos_spec", "",
            "deterministic fault-injection spec (testing/chaos.py): "
            "';'-separated rules 'site:action[:arg]', e.g. "
            "'store.get:raise:0.5;ckpt.write:kill_after:3;step:nan:7'. "
            "Empty disables all injection (zero overhead)")
define_flag("chaos_seed", 0,
            "seed for probabilistic chaos rules — the same (spec, seed) "
            "fires the same faults at the same hit counts, so a CI "
            "failure replays exactly")
define_flag("store_retry_attempts", 3,
            "TCPStore client ops: bounded retries (with exponential "
            "backoff + jitter, total time capped by the op timeout) on "
            "transient connect/reset errors before the failure "
            "propagates; 1 disables retry. Non-idempotent add never "
            "retries at all (a reset after the send leaves 'applied?' "
            "unknowable — a replay could double-count); the initial "
            "connect in the constructor is retried for every op. "
            "ReplicatedStore member clients pin attempts=1: the replica "
            "layer is the retry there")
define_flag("skip_nan_steps", False,
            "graceful numeric degradation: the compiled train step keeps "
            "the previous params/opt-state/buffers when loss or grads "
            "are non-finite (the skipped update is counted in "
            "TrainStep.bad_step_count) instead of raising; the finite "
            "check runs on f32-cast grads so bf16/AMP overflow is "
            "caught post-cast")
define_flag("use_bf16_matmul_precision", "default",
            "jax matmul precision: default|high|highest")
define_flag("trace_dir", "",
            "unified tracing (observability.trace): directory for the "
            "merged chrome-trace/Perfetto JSON written by "
            "observability.trace.export(). Non-empty ENABLES the span "
            "tracer — serving requests and training steps get explicit "
            "trace ids propagated across thread boundaries (batcher, "
            "replica workers, the async checkpoint writer). Empty "
            "disables it: every instrumentation site then costs one "
            "module-attribute check and allocates nothing")
define_flag("trace_buffer_spans", 262144,
            "span tracer ring capacity; the oldest spans are evicted "
            "beyond this (evictions counted in trace.stats())")
define_flag("metrics_dir", "",
            "metrics bus (observability.bus) file output: per-step "
            "scalar series appended to <dir>/metrics.jsonl and a "
            "Prometheus textfile rewritten at <dir>/metrics.prom on "
            "every flush — the training-side analog of the serving "
            "/metrics endpoint. Empty disables file output (the "
            "in-memory series still records when a consumer asks)")
