"""What a GPT-family `GenerativeEngine` needs said about it before its
decode program's temporaries can be read: the program's signature (two
K/V pools of the class's shape in float32, then seven per-row arrays) and
the engine's private members that hold its classes, buckets and programs.

A configuration names this file under `serve.program_memory`; the serve
driver calls `program_temp_bytes(engine)`. It stands in for a public
`engine.program_memory()` (PERF.md §7): an engine that has one needs a
file of one line here.
"""
import jax
import numpy as np


def program_temp_bytes(engine) -> int:
    """Temporaries of the engine's largest decode program (all slots), by
    the compile's memory_analysis: the allocator's peak leaves them out."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    cap, b = engine._caps[-1], engine._batch_buckets[-1]
    params = {n: sds(v.shape, v.dtype) for n, v in engine._params.items()}
    pool = sds(engine._pool_shape(cap), np.float32)
    lowered = engine._program("decode", cap, b).lower(
        params, pool, pool, sds((b,), np.int32), sds((b,), np.int32),
        sds((b,), np.int32), sds((b,), np.float32), sds((b,), np.int32),
        sds((b,), np.float32), sds((b, 2), np.uint32))
    return int(lowered.compile().memory_analysis().temp_size_in_bytes)
