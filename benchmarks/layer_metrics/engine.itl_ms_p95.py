"""The engine's own inter-token gap, p95: the `gap_ms` of the window's
`generate.emit` spans that carry one — the time since the class's previous
step was read, taken where the worker hands a row its next token — each
counted once for every row the read emitted to (`rows`: every live row of
a step gets its token at that read). The client's `itl_ms_p95` is this
plus what the HTTP front and the stream's thread add. None against a
program whose emit spans carry no gap. Moves itl_ms_p95."""
from harness import stats


def read(run):
    return stats.percentile(
        [s["args"]["gap_ms"] for s in run["spans"]
         if s["name"] == "generate.emit" and "gap_ms" in s["args"]
         for _ in range(int(s["args"].get("rows", 1)))], 95)
