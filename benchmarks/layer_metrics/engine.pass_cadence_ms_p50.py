"""Median gap between two reads of a class's decode steps where the worker
did nothing else between them (`generate.emit` spans whose `cause` is
`steady`: the step was launched from the last one's outputs): the cadence
at which a row gets its tokens while the row set stands. It cannot be
under the decode program's own time on the device
(`engine.decode_program_ms_p50`) and lies under window / steps, which has
the admissions in it. The arithmetic is `engine.admission_gap_ms_p50`'s,
over another cause. None against a program whose emit spans carry no
cause. Moves serve_tokens_per_s."""
import os

from harness import cells

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(run):
    return cells.load_reader(BENCH_DIR, "engine.admission_gap_ms_p50")(
        run, "steady")
