"""Median time of one decode program on the device: the events of the
lowest device's `XLA Modules` line whose program's name holds `_decode_`
(`jit_gpt_decode_c1024_b8`, `jit_lfm2_decode_c1024_b32`, …), each from its
start to its end on the device's own clock. The host's span around a step
(`engine.decode_step_ms_p50`) ends before its program since steps are
launched ahead; this is the program itself. None without a profile and
against a program that names none of its programs (`jit__unknown`). Moves
itl_ms_p95."""
from harness import host_spans, moe_work, stats


def read(run, part="_decode_"):
    view = host_spans.load(run)
    if view is None:
        return None
    return stats.percentile(
        [(end - start) / 1e6
         for name, start, end in view["modules"].get(min(view["ops"]), [])
         if part in moe_work.program_of(name)], 50)
