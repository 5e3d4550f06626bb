"""Share of the lowest device's busy (self) time spent in the latent
attention sublayer: the operations traced under
`jax.named_scope("mla.attention")` (the projections, the absorption, the
`mla_decode` kernel or the prefill's attention, `W_UV`, `W_o`), in every
program of the engine that keeps a map from instruction to scope
(harness/moe_work.py joins them by program). None without a trace and
against a program without the scope. Moves serve_tokens_per_s."""
from harness import moe_work


def read(run):
    return moe_work.scope_busy_share(run, "mla.attention")
