"""Median time of one prefill program on the device: the events of the
lowest device's `XLA Modules` line whose program's name holds `_prefill_`
(`jit_gpt_prefill_c1024_b512`, `jit_lfm2_prefill_c1024_b128`, …; a draft's
`_dprefill_` is not one), read as `engine.decode_program_ms_p50` reads the
decode programs. The host's span around a prefill (`engine.prefill_ms_p50`)
also holds its `device_put`s, the wait behind the step queued before it
and the blocking read. None without a profile and against a program that
names none of its programs (`jit__unknown`). Moves itl_ms_p95."""
import os

from harness import cells

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    return cells.load_reader(BENCH_DIR, "engine.decode_program_ms_p50")(
        run, "_prefill_")
