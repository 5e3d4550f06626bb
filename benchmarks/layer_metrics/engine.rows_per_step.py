"""Real rows per decode step over the window: the engine's own counters,
`step_rows_total / steps_total`, snapshot after less snapshot before.
Moves serve_tokens_per_s."""


def read(run):
    serve = run.get("serve")
    if not serve:
        return None
    steps = serve["snap1"]["steps_total"] - serve["snap0"]["steps_total"]
    rows = serve["snap1"]["step_rows_total"] \
        - serve["snap0"]["step_rows_total"]
    return rows / steps if steps else None
