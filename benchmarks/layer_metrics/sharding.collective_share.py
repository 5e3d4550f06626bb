"""Share of the traced window in which a collective operation (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all) ran on the
lowest-numbered device. Moves train_tokens_per_s_per_chip."""
from harness import trace_reduce


def read(run):
    trace = run.get("trace")
    if not trace:
        return None
    dev = trace_reduce.lowest_device(trace)
    return dev["collective_s"] / trace["window_s"]
