"""Clean CPU environment for test subprocesses.

A CPU-only child must not inherit the parent's device selection or
launcher identity: JAX_PLATFORMS / XLA_FLAGS / PADDLE_* are scrubbed and
JAX_PLATFORMS=cpu is forced, with the repo on PYTHONPATH.
"""
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cpu_subprocess_env(**extra):
    """os.environ minus device/launcher settings, plus JAX_PLATFORMS=cpu +
    repo on PYTHONPATH. Keyword args override. A child given its own
    FLAGS_compile_cache_dir must really get it, so the variable that
    outranks the flag (JAX_COMPILATION_CACHE_DIR) is dropped with it."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PADDLE_", "XLA_FLAGS", "JAX_PLATFORM"))}
    if "FLAGS_compile_cache_dir" in extra:
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    parts = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    if REPO not in parts:
        parts.insert(0, REPO)
    env["PYTHONPATH"] = os.pathsep.join(parts)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env
