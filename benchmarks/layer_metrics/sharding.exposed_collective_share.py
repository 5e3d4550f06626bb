"""The part of the collectives' time in which no other operation ran on
that device, over the traced window: communication the step waits for.
Moves train_tokens_per_s_per_chip."""
from harness import trace_reduce


def read(run):
    trace = run.get("trace")
    if not trace:
        return None
    dev = trace_reduce.lowest_device(trace)
    return dev["exposed_collective_s"] / trace["window_s"]
