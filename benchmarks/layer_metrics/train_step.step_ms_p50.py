"""Median host-clock time of one train step, each drained by a host read
of its loss, over the drained steps of the traced run. Moves
train_tokens_per_s_per_chip."""
from harness import stats


def read(run):
    return stats.percentile(run.get("train", {}).get("step_ms", []), 50)
