"""The absorbed decode kernel's share of its roofline on the lowest device:
the least time the chip could take for the latent reads that the decode
steps' REAL rows required, over the self time of the `mla_decode.N`
operations.

The least time of one step is
`flops.roofline_seconds(mla_work.decode_work(positions, arch))`: the
positions the real rows attend (the engine's `attended_positions_total`
over the window, a step's share of it), each latent row — the latent and
the rotary key, unpadded — read once a layer at the HBM peak, or each
head's score and weighted sum at the bf16 peak, whichever is longer. The
steps seen are the decode programs' events in the trace. Padding lanes and
blocks read past a row's position count against the kernel. None without
a trace, without the counter or against a program without the kernel.
Moves serve_tokens_per_s."""
from harness import flops, host_spans, mla_work


def read(run):
    view = host_spans.load(run)
    counted = mla_work.counters_delta(run)
    if view is None or counted is None:
        return None
    took = mla_work.kernel_seconds(view)
    if took is None or not took["seconds"] or not took["decode_steps"]:
        return None
    per_step = flops.roofline_seconds(mla_work.decode_work(
        counted["attended"] / counted["steps"],
        run["config"]["architecture"]), run["peaks"])
    least = per_step * took["decode_steps"]
    host_spans.note(run, "mla_roofline.json",
                    positions_a_step=counted["attended"] / counted["steps"],
                    decode_steps_seen=took["decode_steps"],
                    kernel_events=took["events"],
                    least_s_a_step=per_step, least_s=least,
                    took_s=took["seconds"])
    return least / took["seconds"]
