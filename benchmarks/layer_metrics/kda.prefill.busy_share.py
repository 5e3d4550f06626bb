"""Share of the lowest device's busy (self) time spent in the KDA layers'
chunked prefill: the operations traced under
`jax.named_scope("kda.prefill")` (the pairwise decays, the triangular
solve, the chunks' reads and the state carried between them), in every
program of the engine that keeps a map from instruction to scope
(harness/moe_work.py joins them by program). None without a trace and
against a program without the scope. Moves serve_tokens_per_s."""
from harness import moe_work


def read(run):
    return moe_work.scope_busy_share(run, "kda.prefill")
