"""Share of the lowest device's busy (self) time spent in the KDA layers'
decode step: the self time of the `kda_step.N` operations (the kernel that
decays a row's states, corrects them by the delta rule and reads them with
the query, in place) over all self time. None without a trace and against
a program without the kernel. Moves serve_tokens_per_s."""
from harness import host_spans, kda_work


def read(run):
    view = host_spans.load(run)
    if view is None:
        return None
    took = kda_work.kernel_seconds(view)
    if took is None or not took["busy_s"]:
        return None
    host_spans.note(run, "kda_step.json", **took)
    return took["seconds"] / took["busy_s"]
