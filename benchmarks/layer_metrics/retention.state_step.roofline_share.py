"""The retention step's share of its roofline on the lowest device: the
least time the chip could take for the work that the steps' REAL rows
required, over the self time of the `retention_step.N` operations.

The least time of one layer on one step is
`flops.roofline_seconds(retention_work.step_work(rows, arch))`: each real
row's state — the bare symmetric second power, `Dh (Dh + 1) / 2` products
by `Dh + 1` float32 a K/V head — read and written once at the HBM peak,
whatever implements the step and however it lays the state out. The rows
a step are the engine's counters over the window (`step_rows_total /
steps_total`: padding rows move nothing and count for nothing); the layer
steps seen are the kernel's events in the trace, one a layer and step.
None without a trace, without the counters or against a program without
the kernel. Moves serve_tokens_per_s."""
from harness import flops, host_spans, retention_work


def read(run):
    view = host_spans.load(run)
    counted = retention_work.counters_delta(run)
    if view is None or counted is None or not counted["steps"]:
        return None
    took = retention_work.kernel_seconds(view)
    if took is None or not took["seconds"]:
        return None
    rows = counted["rows"] / counted["steps"]
    per_layer_step = flops.roofline_seconds(retention_work.step_work(
        rows, run["config"]["architecture"]), run["peaks"])
    least = per_layer_step * took["events"]
    host_spans.note(run, "retention_roofline.json", rows_a_step=rows,
                    layer_steps_seen=took["events"],
                    least_s_a_layer_step=per_layer_step, least_s=least,
                    took_s=took["seconds"])
    return least / took["seconds"]
