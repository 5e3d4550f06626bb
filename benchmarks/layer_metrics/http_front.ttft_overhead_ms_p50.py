"""What the HTTP front and the loopback add to the time to first token:
the clients' median TTFT (from the due time) less the median of the
engine's own `ttft_ms` (enqueue to first token, from each stream's last
line), over the requests due inside the window. Moves ttft_ms_p90."""
from harness import end_to_end, stats


def read(run):
    serve = run.get("serve")
    if not serve:
        return None
    w0, w1 = serve["w0"], serve["w1"]
    client = stats.percentile(
        end_to_end.ttft_ms(serve["samples"], w0, w1), 50)
    engine = stats.percentile(
        [r["engine_ttft_ms"] for r in serve["samples"]
         if w0 <= r["due"] <= w1 and r["engine_ttft_ms"] is not None], 50)
    if client is None or engine is None:
        return None
    return client - engine
