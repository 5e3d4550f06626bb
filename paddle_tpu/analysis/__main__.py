"""CLI: ``python -m paddle_tpu.analysis [--ci] [--json] [paths...]``.

Exit codes: 0 = clean (or --ci with only baselined findings),
1 = findings (--ci: NEW findings), 2 = usage error.

``--json`` prints one machine-readable document (schema version 1) so
CI and editors consume findings without scraping text; exit codes are
unchanged. Full-tree scans ride a parse cache keyed on (path, mtime,
size) under ``~/.cache/paddle_tpu`` (override: PADDLE_ANALYSIS_CACHE_DIR;
disable: --no-cache) — back-to-back ``--ci`` runs skip re-parsing
unchanged modules; the cache self-invalidates when the checker set
changes.
"""
from __future__ import annotations

import argparse
import json
import sys

from . import (CHECKERS, last_cache_stats, load_baseline, new_findings,
               run, write_baseline)


def _finding_json(f) -> dict:
    return {"path": f.path, "line": f.line, "checker": f.checker,
            "message": f.message, "hint": f.hint, "key": f.key()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.analysis",
        description="framework-aware invariant lints (see "
                    "DESIGN.md 'Static analysis & lock checking')")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to scan (default: paddle_tpu/ and "
                         "tools/ under the repo root)")
    ap.add_argument("--ci", action="store_true",
                    help="gate mode: fail only on findings NOT in "
                         "analysis/baseline.json")
    ap.add_argument("--write-baseline", action="store_true",
                    help="absorb all current findings into the baseline "
                         "file (pre-existing debt only — fix new ones)")
    ap.add_argument("--strict-baseline", action="store_true",
                    help="with --ci: fail (exit 1) on STALE baseline "
                         "entries instead of warning — baseline rot "
                         "cannot accumulate silently; refresh with "
                         "--write-baseline after fixing the debt")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output (schema v1: "
                         "path/line/checker/message/hint/key per "
                         "finding); exit codes unchanged")
    ap.add_argument("--no-cache", action="store_true",
                    help="re-parse every module instead of reusing the "
                         "(path, mtime, size)-keyed findings cache")
    ap.add_argument("--list-checkers", action="store_true")
    args = ap.parse_args(argv)

    if args.list_checkers:
        for cls in CHECKERS:
            print(f"{cls.name:24} {cls.doc}")
        return 0

    # the cache only serves full default-tree scans: a path-scoped run
    # would poison entries with a partial view of nothing (entries are
    # per-file) but gains little — keep the logic trivially safe
    use_cache = not args.paths and not args.no_cache
    findings = run(args.paths or None, use_cache=use_cache)

    def emit_json(extra: dict) -> None:
        doc = {
            "version": 1,
            "checkers": [c.name for c in CHECKERS],
            "count": len(findings),
            "findings": [_finding_json(f) for f in findings],
            "cache": dict(last_cache_stats) if use_cache else None,
        }
        doc.update(extra)
        json.dump(doc, sys.stdout, indent=1)
        sys.stdout.write("\n")

    if args.write_baseline:
        if args.paths:
            # a partial scan would overwrite the WHOLE baseline with
            # only these paths' findings, silently resurrecting every
            # other suppressed site as NEW on the next --ci run
            print("--write-baseline regenerates the whole file and "
                  "must scan the default tree; drop the explicit paths",
                  file=sys.stderr)
            return 2
        write_baseline(findings)
        print(f"baseline: wrote {len(findings)} suppression(s)")
        return 0

    if args.ci:
        baseline = load_baseline()
        fresh = new_findings(findings, baseline)
        # staleness is only decidable on a FULL scan: a path-scoped run
        # simply didn't visit the other baselined sites
        stale = (set(baseline) - {f.key() for f in findings}
                 if not args.paths else set())
        if args.json:
            ok = not fresh and not (stale and args.strict_baseline)
            emit_json({"mode": "ci", "ok": ok,
                       "new": [_finding_json(f) for f in fresh],
                       "baselined": len(findings) - len(fresh),
                       "stale_baseline": sorted(stale)})
            return 0 if ok else 1
        for f in fresh:
            print(f.render())
        strict_stale = bool(stale) and args.strict_baseline
        if stale:
            # a stale entry is debt that was FIXED but never pruned: it
            # keeps a suppression key alive that a future regression at
            # the same line-hash would silently hide under. --strict-
            # baseline (wired into tools/ci.sh) makes that rot a
            # failure instead of a warning.
            for key in sorted(stale):
                entry = baseline[key]
                print(f"stale baseline entry: {entry.get('path')}:"
                      f"{entry.get('line')} [{entry.get('checker')}] "
                      f"(key {key})", file=sys.stderr)
            if not args.strict_baseline:
                print(f"note: {len(stale)} stale baseline entries — "
                      f"refresh with --write-baseline", file=sys.stderr)
        n_old = len(findings) - len(fresh)
        if fresh or strict_stale:
            # BOTH failure causes always print: a strict-stale message
            # alone would hide concurrent NEW findings, and its prune
            # advice would absorb them into the baseline. Pruning is
            # only safe once the tree is otherwise clean.
            parts = []
            if fresh:
                parts.append(f"{len(fresh)} NEW finding(s) "
                             f"({n_old} baselined)")
            if strict_stale:
                parts.append(
                    f"{len(stale)} STALE baseline entry(ies) under "
                    f"--strict-baseline"
                    + ("" if fresh else
                       " — prune with --write-baseline"))
            print(f"\nanalysis: {' + '.join(parts)} across "
                  f"{len(CHECKERS)} checkers — FAIL")
            if fresh and strict_stale:
                print("fix the NEW findings before pruning the stale "
                      "entries: --write-baseline absorbs everything it "
                      "sees", file=sys.stderr)
            return 1
        print(f"analysis: clean ({n_old} baselined finding(s), "
              f"{len(CHECKERS)} checkers)")
        return 0

    if args.json:
        emit_json({"mode": "scan", "ok": not findings})
        return 1 if findings else 0
    for f in findings:
        print(f.render())
    print(f"\nanalysis: {len(findings)} finding(s) across "
          f"{len(CHECKERS)} checkers")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
