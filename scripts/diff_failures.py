#!/usr/bin/env python
"""Strict-subset gate for the tier-1 failure set (ISSUE 8 satellite).

Every PR so far has diffed its tier-1 failure list against the previous
baseline BY HAND to prove "zero new failures, N pre-existing fixed". This
script automates that contract: the committed manifest
``tests/known_failures.txt`` is the documented failure set of the current
environment baseline (one pytest node id per line, ``#`` comments allowed),
and a run's failures must be a SUBSET of it — any *new* failure fails the
gate even when the raw counts still satisfy the TDT_TIER1_MIN_PASS /
TDT_TIER1_MAX_FAIL floors (counts can mask a swap: one fixed, one newly
broken).

Usage::

    scripts/diff_failures.py <pytest-log> [manifest] [--update]

- ``<pytest-log>``: a ``pytest -q`` capture (run_tier1.sh passes
  its tier-1 log); failures are the ``FAILED <nodeid>[ - reason]`` lines.
- ``manifest``: defaults to ``tests/known_failures.txt`` next to this repo.
- ``--update``: rewrite the manifest to exactly this run's failure set
  and PRINT the node ids removed/added relative to the old manifest (a
  silent shrink makes review diffs hard to audit). Use after deliberately
  fixing failures, then commit the shrunk file; growing the manifest
  should always be a reviewed, explained change.

Exit codes: 0 = subset (prints the fixed set, if any); 1 = new failures
(prints them); 2 = usage/IO error.

The manifest describes the ONE installed toolchain (jax 0.9.0; regenerated
in PR 23 from the driver's command). Shrink it with ``--update`` after
fixing failures.
"""

from __future__ import annotations

import os
import re
import sys

_FAIL_RE = re.compile(r"^(?:FAILED|ERROR) +(\S+)")


def parse_failures(log_path: str) -> set[str]:
    """Node ids of every FAILED/ERROR summary line in a pytest -q log."""
    out: set[str] = set()
    with open(log_path, errors="replace") as f:
        for line in f:
            m = _FAIL_RE.match(line.strip())
            if m:
                # "FAILED tests/x.py::t - reason" -> "tests/x.py::t"
                out.add(m.group(1).rstrip("-").rstrip())
    return out


def load_manifest(path: str) -> set[str]:
    if not os.path.exists(path):
        return set()
    with open(path) as f:
        return {
            ln.strip() for ln in f
            if ln.strip() and not ln.strip().startswith("#")
        }


def write_manifest(path: str, failures: set[str]) -> None:
    with open(path, "w") as f:
        for node in sorted(failures):
            f.write(node + "\n")


def main(argv: list[str]) -> int:
    args = [a for a in argv if a != "--update"]
    update = "--update" in argv
    if not args:
        print(__doc__, file=sys.stderr)
        return 2
    log_path = args[0]
    default_manifest = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tests", "known_failures.txt",
    )
    manifest_path = args[1] if len(args) > 1 else default_manifest
    if not os.path.exists(log_path):
        print(f"diff_failures: no such log: {log_path}", file=sys.stderr)
        return 2
    current = parse_failures(log_path)
    known = load_manifest(manifest_path)

    if update:
        write_manifest(manifest_path, current)
        print(
            f"diff_failures: manifest rewritten with {len(current)} "
            f"failure(s) (was {len(known)})"
        )
        # a silent shrink makes review diffs hard to audit: name exactly
        # which node ids left (and, for a reviewed growth, which arrived)
        removed = sorted(known - current)
        added = sorted(current - known)
        if removed:
            print(f"  removed {len(removed)} node id(s):")
            for node in removed:
                print(f"    - {node}")
        if added:
            print(f"  added {len(added)} node id(s) (growing the manifest "
                  f"should be a reviewed, explained change):")
            for node in added:
                print(f"    + {node}")
        return 0

    new = sorted(current - known)
    fixed = sorted(known - current)
    print(
        f"diff_failures: {len(current)} failed now, {len(known)} in "
        f"manifest, {len(new)} new, {len(fixed)} fixed"
    )
    if fixed:
        print("  fixed (shrink the manifest with --update when deliberate):")
        for node in fixed:
            print(f"    {node}")
    if new:
        print("  NEW failures (not in tests/known_failures.txt):")
        for node in new:
            print(f"    {node}")
        print("diff_failures: FAIL — the failure set is not a subset")
        return 1
    print("diff_failures: PASS — strict subset of the known set")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
