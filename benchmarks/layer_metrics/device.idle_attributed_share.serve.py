"""Of the lowest device's idle time inside the profiled window, the share
that lies under one of the program's spans (each instant goes to the
innermost span that covers it; the spans are on the profiler's clock,
harness/host_spans.py). The split by span name, what lies on either side
of the unattributed pieces, and how many of the device's programs lie
wholly inside the `generate.decode_step` or `generate.prefill` span that
ran them (do the two clocks agree?), go to an earlier line and to
idle_by_span.json. None
without a trace, and where the profile holds no program span. Moves
itl_ms_p95."""
from harness import host_spans


def read(run):
    view = host_spans.load(run)
    if view is None or not view["spans"]:
        return None
    split = host_spans.idle_by_span(view)
    host_spans.note(
        run, "idle_by_span.json", idle_by_span=split,
        programs_inside_spans=host_spans.modules_inside(
            view, ("generate.decode_step", "generate.prefill")))
    return split["attributed_share"]
