"""Bytes of retention state the window's decode steps read and wrote, for
each token that reached a client: the engine's own counter
(`state_bytes_moved_total`, what the step as implemented moves of its own
layout — real rows only where padding rows move nothing) over
`tokens_out_total`, snapshot after less snapshot before. The least is
`retention_work.step_work(1, arch)["bytes"]` a layer. None against a
program without the counter and for a model that keeps no state. Moves
serve_tokens_per_s."""
from harness import retention_work


def read(run):
    counted = retention_work.counters_delta(run)
    if counted is None or not counted["tokens"]:
        return None
    return counted["state_bytes"] / counted["tokens"]
