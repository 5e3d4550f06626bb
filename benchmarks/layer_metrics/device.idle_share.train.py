"""Device idle share: 1 - (union of the intervals in which any operation
ran on the device) / traced window, averaged over the chips used."""
from harness import trace_reduce


def read(run):
    return trace_reduce.idle_share(run["trace"]) if run.get("trace") \
        else None
