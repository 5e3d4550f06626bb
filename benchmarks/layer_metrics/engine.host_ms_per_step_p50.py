"""Median host time of one pass of the engine's worker loop that ran a
decode step, the device wait left out: `generate.admit` +
`generate.decode_step.stage` + `generate.decode_step.launch` +
`generate.emit` of the pass (spans of one pass of one replica carry the
same `iter` and `rid`). It is the time the device has nothing to run
between two steps, seen from the host. None where the program emits no
such spans. Moves itl_ms_p95."""
from harness import stats

HOST_PHASES = ("generate.admit", "generate.decode_step.stage",
               "generate.decode_step.launch", "generate.emit")


def read(run):
    per_pass, stepped = {}, set()
    for s in run["spans"]:
        if s["name"] not in HOST_PHASES:
            continue
        key = (s["args"].get("rid"), s["args"].get("iter"))
        per_pass[key] = per_pass.get(key, 0.0) + s["dur"] / 1e3
        if s["name"] == "generate.decode_step.launch":
            stepped.add(key)
    return stats.percentile([per_pass[k] for k in stepped], 50) \
        if stepped else None
