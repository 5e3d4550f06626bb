"""Of the tokens the window's decode steps handed to their rows after a
gap, the share whose gap held an admission: the engine's counter
`step_gap_tokens_admission_total` over the three causes' sum, snapshot
after less snapshot before. Over 0.05 the p95 gap IS an admission. None
for a run that is not served, against a program without the counters, and
for a window with no gap counted. Moves itl_ms_p95."""
CAUSES = ("steady", "rowset", "admission")


def read(run):
    serve = run.get("serve")
    if not serve or "step_gap_tokens_admission_total" not in serve["snap1"]:
        return None
    tokens = {c: serve["snap1"][f"step_gap_tokens_{c}_total"]
              - serve["snap0"][f"step_gap_tokens_{c}_total"] for c in CAUSES}
    total = sum(tokens.values())
    return tokens["admission"] / total if total else None
