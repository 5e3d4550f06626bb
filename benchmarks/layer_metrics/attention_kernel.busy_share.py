"""Share of the device's busy time spent in `custom-call` operations, on
the lowest-numbered device of the trace. The flash-attention kernel
(forward and its backward kernels) is the train step's only Pallas call,
so this is the kernel's share. Moves train_tokens_per_s_per_chip."""
from harness import trace_reduce


def read(run):
    trace = run.get("trace")
    if not trace:
        return None
    dev = trace_reduce.lowest_device(trace)
    return dev["by_class"].get("custom-call", 0.0) / dev["busy_s"]
