"""Persistent compile-cache misses during set-up (`compile_cache.stats()`
when the window opens): every one is a fresh XLA compile. 0 on a run that
found its checkout's cache warm. Moves setup_s."""


def read(run):
    return run["setup"]["cache_misses"]
