"""The flash-attention kernels' share of their roofline on the lowest
device: the least time the calls seen could take on this chip over the
self time of the kernels' events. The kernels are found by the names the
program gave them (`flash_fwd`, `flash_bwd_dkv`); a forward call — the
remat'd one too — needs `flops.flash_attention_fwd`, a backward pass (one
fused kernel) `flops.flash_attention_bwd`; shapes are one shard's (batch
over dp, heads over tp). The least time
is `flops.roofline_seconds` on the benchmark's own peaks. None without a
trace and where no event carries a kernel's name. Moves
train_tokens_per_s_per_chip."""
from harness import flops, host_spans


def read(run):
    view = host_spans.load(run)
    if view is None:
        return None
    arch, traffic = run["config"]["architecture"], run["traffic"]
    tp = int((run["config"]["train"].get("mesh") or {}).get("tp", 1))
    dp = int(run["cell"]["chips"]) // tp
    shape = dict(batch=int(traffic["batch"]) // dp,
                 heads=int(arch["num_heads"]) // tp,
                 seq_q=int(traffic["seq"]), seq_k=int(traffic["seq"]),
                 head_dim=int(arch["head_size"]), causal=True, itemsize=2)
    kernels = host_spans.kernel_events(view)
    roof = host_spans.flash_roofline(
        kernels,
        flops.roofline_seconds(flops.flash_attention_fwd(**shape),
                               run["peaks"]),
        flops.roofline_seconds(flops.flash_attention_bwd(**shape),
                               run["peaks"]))
    if roof is None:
        return None
    host_spans.note(run, "flash_kernels.json", flash_kernels=kernels,
                    flash_roofline=roof, shard_shape=shape)
    return roof["share"]
