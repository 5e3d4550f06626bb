"""Median gap between two reads of a class's decode steps where at least
one prefill or import ran between them (`generate.emit` spans whose
`cause` is `admission`): what a decoding row waits across an admission.
None against a program whose emit spans carry no cause, and in a window
that admitted nothing while a row decoded. Moves itl_ms_p95."""
from harness import stats


def read(run, cause="admission"):
    return stats.percentile(
        [s["args"]["gap_ms"] for s in run["spans"]
         if s["name"] == "generate.emit"
         and s["args"].get("cause") == cause], 50)
