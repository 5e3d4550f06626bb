"""Provenance stamp for measurement artifacts.

Every stored measurement (eager_bench_last.json, planner_cluster_meta.json)
embeds the git SHA + UTC timestamp of the MEASUREMENT, so a payload read
later is self-identifying: nothing ties a number to code unless the
artifact says which commit it measured.
"""
from __future__ import annotations

import os
import subprocess
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def git_sha() -> str:
    """HEAD SHA of the repo at measurement time ('unknown' outside git)."""
    try:
        out = subprocess.run(["git", "rev-parse", "HEAD"], cwd=_REPO,
                             capture_output=True, text=True, timeout=10)
        if out.returncode == 0:
            return out.stdout.strip()
    except (OSError, subprocess.TimeoutExpired):
        pass
    return "unknown"


def stamp() -> dict:
    """{"git_sha": ..., "measured_at": ISO-8601 UTC} for embedding."""
    return {"git_sha": git_sha(),
            "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime())}


def is_ancestor(sha: str) -> bool | None:
    """Is ``sha`` an ancestor of (or equal to) current HEAD?

    Returns None when it cannot be determined (unknown sha, git absent).
    """
    if not sha or sha == "unknown":
        return None
    try:
        out = subprocess.run(["git", "merge-base", "--is-ancestor",
                              sha, "HEAD"], cwd=_REPO,
                             capture_output=True, timeout=10)
    except (OSError, subprocess.TimeoutExpired):
        return None
    if out.returncode == 0:
        return True
    if out.returncode == 1:
        return False
    return None  # e.g. sha not present in this clone
