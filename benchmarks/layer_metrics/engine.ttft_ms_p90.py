"""90th percentile of the time from a request's DUE time to its first
streamed token, at the client, over the requests due inside the window.
At 0.8 of the throughput knee eight slots are nine-tenths busy, so this
tail is a queueing tail: it swings from run to run (PERF.md §4) and is
recorded here, not bounded. Moves itl_ms_p95's cell: the same prefills
that stall decoding rows make the queue."""
from harness import end_to_end


def read(run):
    serve = run.get("serve")
    if not serve:
        return None
    return end_to_end.ttft_ms_p90(serve["samples"], serve["w0"], serve["w1"])
