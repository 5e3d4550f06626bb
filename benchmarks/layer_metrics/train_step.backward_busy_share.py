"""Share of the lowest device's busy (self) time spent in the backward
pass proper: the transposed pass less the recomputed forward, its
collectives with it
(harness/host_spans.py reads the scopes). The five classes — forward,
backward, recompute, optimizer, other — sum to 1; the whole split goes to
an earlier line and to busy_by_scope.json. None without a trace and where
no operation carries a train scope. Moves train_tokens_per_s_per_chip."""
from harness import host_spans


def read(run):
    return host_spans.scope_share(run, "backward")
