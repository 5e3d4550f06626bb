"""Persistent (on-disk) XLA compilation cache wiring.

The role the reference fills with its kernel .so ahead-of-time build:
compiled artifacts must survive process restarts. Every jax
compilation — eager per-op plan executables (core/dispatch fast path),
TrainStep programs, the serving engines' program inventory — is written
through jax's persistent compilation cache, so a cold process against a
warm cache deserializes executables instead of re-running XLA.

Where the cache lives, highest first:
  1. ``JAX_COMPILATION_CACHE_DIR`` — placed from outside; jax reads it
     itself and this module sets no other directory;
  2. ``FLAGS_compile_cache_dir`` — the explicit override (``""``
     disables);
  3. its default, ``<checkout>/.jax_cache`` — one fixed path (the
     directory is part of the cache key, so a path that moves never
     hits).

Process-level hit/miss counters come from jax.monitoring's
``/jax/compilation_cache/*`` events and surface in
``profiler.summary_dict()["dispatch_cache"]["persistent"]`` and the
eager-bench JSON artifact.
"""
from __future__ import annotations

import contextlib
import os

_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
_STATS = {"enabled": False, "dir": None, "hits": 0, "misses": 0}
_LISTENER_INSTALLED = False


def _reset_jax_memo() -> None:
    """jax memoizes its is-cache-used verdict after the first compile
    (compilation_cache._cache_checked): any change to the enable flag or
    the directory is ignored until that memo is reset."""
    from jax._src import compilation_cache as _jcc

    _jcc.reset_cache()


@contextlib.contextmanager
def suspend_if(cond: bool = True):
    """Temporarily divert compiles away from the persistent cache.

    jaxlib's CPU (thunk-runtime) executable serialization mishandles
    buffer DONATION: a donated program compiled through the on-disk
    cache corrupts its input/output aliasing (measured here: ~50%
    segfault on the Engine save→load→fit flow, and wrong parameter
    updates after a crashed process left a torn entry). Donated-program
    compiles on the CPU backend therefore run under this guard
    (jit/train_step.py, distributed/pipeline.py); pure programs — the
    eager per-op plan executables, EvalStep — are unaffected and stay
    cached.

    Consults jax's ACTUAL cache state, not only this module's wiring:
    the user may have enabled the cache directly
    (JAX_COMPILATION_CACHE_DIR / jax.config) with
    FLAGS_compile_cache_dir unset — donated CPU programs must stay off
    it either way."""
    import jax

    if not (cond and jax.config.jax_compilation_cache_dir
            and jax.config.jax_enable_compilation_cache):
        yield
        return
    jax.config.update("jax_enable_compilation_cache", False)
    _reset_jax_memo()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        _reset_jax_memo()


def donated_cpu_guard(donated: bool = True):
    """suspend_if(donated and running on the CPU backend) — the unsafe
    combination documented on suspend_if."""
    import jax

    return suspend_if(donated and jax.default_backend() == "cpu")


def _on_event(event, **kwargs):
    if event == "/jax/compilation_cache/cache_hits":
        _STATS["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _STATS["misses"] += 1


def setup(path: str | None = None) -> bool:
    """Wire jax's persistent compilation cache (see the module docstring
    for where it lives) and install the hit/miss counter listener.
    Returns True when the cache is active; an unwritable directory
    degrades to in-memory compilation only."""
    global _LISTENER_INSTALLED
    from .flags import flag

    if path is None:
        path = flag("compile_cache_dir")
    if not path:
        return False
    import jax

    env_dir = os.environ.get(_ENV_DIR)
    if env_dir:
        path = env_dir
    else:
        path = os.path.expanduser(str(path))
        try:
            os.makedirs(path, exist_ok=True)
        except OSError:
            return False
        jax.config.update("jax_compilation_cache_dir", path)
    # persist every entry: per-op plan executables compile in
    # milliseconds but re-dispatching a cold eager process pays them
    # by the hundred; the min-compile-time gate would skip them all
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(flag("compile_cache_min_compile_secs")))
    if not _LISTENER_INSTALLED:
        from jax import monitoring

        monitoring.register_event_listener(_on_event)
        _LISTENER_INSTALLED = True
    _STATS["enabled"] = True
    _STATS["dir"] = path
    return True


def reconfigure(path: str | None) -> bool:
    """Apply a RUNTIME FLAGS_compile_cache_dir change (called from
    flags.set_flags): empty/None disables the cache, a new path
    redirects it (unless JAX_COMPILATION_CACHE_DIR placed it)."""
    import jax

    if path:
        ok = setup(path)
    else:
        jax.config.update("jax_compilation_cache_dir", None)
        _STATS["enabled"] = False
        _STATS["dir"] = None
        ok = False
    _reset_jax_memo()
    return ok


def _plan_path(key: str):
    import hashlib

    d = _STATS["dir"]
    if not (_STATS["enabled"] and d):
        return None
    return os.path.join(
        d, hashlib.sha256(key.encode()).hexdigest() + "-plan.json")


def plan_lookup(key: str):
    """What `plan_store` kept under `key` beside the cache's entries, or
    None: a decision that cost compiles to reach (TrainStep's remat
    plan, keyed by the lowered text it was reached for), so that a warm
    start compiles only the program it chose."""
    import json

    path = _plan_path(key)
    if path is None:
        return None
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def plan_store(key: str, plan) -> None:
    """Keep `plan` (JSON) under `key` for `plan_lookup`; nothing is kept
    where the cache is off or its directory cannot be written."""
    import json

    path = _plan_path(key)
    if path is None:
        return
    try:
        with open(path + ".tmp", "w") as f:
            json.dump(plan, f)
        os.replace(path + ".tmp", path)
    except OSError:
        pass


@contextlib.contextmanager
def measure():
    """Count persistent-cache hits/misses across a code region.

    Yields a dict that is filled in on exit with {hits, misses,
    enabled}: the delta of THIS process's persistent-cache lookups while
    the region ran. The serving engine wraps its warmup with this so a
    warm restart can prove "first request = deserialization, zero fresh
    compiles" (misses == 0, hits > 0)."""
    pre = dict(_STATS)
    out = {}
    try:
        yield out
    finally:
        out["hits"] = _STATS["hits"] - pre["hits"]
        out["misses"] = _STATS["misses"] - pre["misses"]
        out["enabled"] = _STATS["enabled"]


def stats() -> dict:
    """{enabled, dir, hits, misses, entries, bytes} — hits/misses are
    THIS process's persistent-cache lookups (a warm restart shows
    hits>0, misses==0 for already-seen programs); entries/bytes are the
    on-disk cache size shared across processes."""
    out = dict(_STATS)
    d = out.get("dir")
    if out["enabled"] and d and os.path.isdir(d):
        try:
            names = [f for f in os.listdir(d) if f.endswith("-cache")]
            out["entries"] = len(names)
            out["bytes"] = sum(
                os.path.getsize(os.path.join(d, f)) for f in names)
        except OSError:
            pass
    return out
