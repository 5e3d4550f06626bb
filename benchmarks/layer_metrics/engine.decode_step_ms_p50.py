"""Median duration of the engine's `generate.decode_step` spans inside
the window (host clock around one batched decode step, its device wait
included). Moves itl_ms_p95."""
from harness import stats


def read(run):
    return stats.percentile(
        [s["dur"] / 1e3 for s in run["spans"]
         if s["name"] == "generate.decode_step"], 50)
