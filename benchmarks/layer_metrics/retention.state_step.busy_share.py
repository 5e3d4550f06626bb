"""Share of the lowest device's busy (self) time spent in the retention
layers' decode step: the self time of the `retention_step.N` operations
(the kernel that decays a row's state, adds the token's product and reads
it with the query heads, in place) over all self time. None without a
trace and against a program without the kernel. Moves serve_tokens_per_s."""
from harness import host_spans, retention_work


def read(run):
    view = host_spans.load(run)
    if view is None:
        return None
    took = retention_work.kernel_seconds(view)
    if took is None or not took["busy_s"]:
        return None
    host_spans.note(run, "retention_step.json", **took)
    return took["seconds"] / took["busy_s"]
