"""Median duration of the engine's `generate.prefill` spans inside the
window (one request's prompt through the batch-1 prefill program). Moves
ttft_ms_p90."""
from harness import stats


def read(run):
    return stats.percentile(
        [s["dur"] / 1e3 for s in run["spans"]
         if s["name"] == "generate.prefill"], 50)
