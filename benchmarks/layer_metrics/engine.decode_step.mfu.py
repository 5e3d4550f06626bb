"""The whole decode step's share of the chip's bf16 peak: 2·N model FLOPs
(`flops.serve_model_flops_per_token`: N of the configuration's file,
attention's own not counted) for each REAL row of a step over the step's
time and the peak of the benchmark's own table. Rows a step and the
step's time are the readings of `engine.rows_per_step` (the engine's
counters over the window) and `engine.decode_step_ms_p50` (the median
`generate.decode_step` span: host clock around one batched step, its
device wait included), taken by those readers themselves. A later change
that takes a kernel off the decode path leaves that kernel's roofline
silent; this share still bounds what it can claim. None where either
reader finds nothing. Moves serve_tokens_per_s."""
import os

from harness import cells, flops

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    rows = cells.load_reader(BENCH_DIR, "engine.rows_per_step")(run)
    step_ms = cells.load_reader(BENCH_DIR, "engine.decode_step_ms_p50")(run)
    if not rows or not step_ms:
        return None
    return flops.decode_step_mfu(
        rows, step_ms / 1e3, run["config"]["architecture"],
        run["peaks"]["bf16_flops"])
