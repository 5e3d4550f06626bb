"""90th percentile of the engine's `generate.queue_wait` spans that begin
inside the window: enqueue to the admission that gives the request a
slot, on the host clock (emitted by `GenerativeEngine._worker_loop` when
the request leaves the queue). With eight slots nine-tenths busy this
wait, not the prefill, is most of a late first token. None where the
program emits no such span. Moves itl_ms_p95's cell: the prefills that
stall decoding rows are what the queue waits behind."""
from harness import stats


def read(run):
    waits = [s["dur"] / 1e3 for s in run["spans"]
             if s["name"] == "generate.queue_wait"]
    return stats.percentile(waits, 90) if waits else None
