#!/usr/bin/env python
"""Summarize an obs chrome trace for chip logs (ISSUE 9 satellite).

Reads the artifact ``obs.export_chrome_trace(PATH)``
writes (a Perfetto-loadable chrome trace whose span events carry op-entry
ladder rungs and whose instant events on the ``device wait telemetry``
process carry per-(family, site, kind) spin histograms) and prints two
tables a chip session pastes straight into its log:

- top-N wait sites by total observed spin count (where the fused
  pipelines actually stall on the success path), and
- top-N slowest spans (which op entries / serving phases cost the time),
  with their ladder rung when recorded.

Since ISSUE 15, ``--incidents DIR`` folds that directory's black-box
post-mortem bundles (``obs/blackbox.py``) into a third table — trigger
kind, family, engine-clock time, whether a burn-rate alert was firing
when the flip landed, and the attributed culprit PEs — so ONE command
answers "where did the run stall *and* what broke"
(``scripts/postmortem.py`` renders any single bundle in full).

Dependency-free stdlib CLI::

    python scripts/trace_summary.py docs/chip_logs/obs_trace.json [-n 15]
    python scripts/trace_summary.py obs.json --incidents bundles/
    python scripts/trace_summary.py --incidents bundles/   # bundles only
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys


def load_events(path: str) -> list[dict]:
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict):
        events = doc.get("traceEvents")
    else:
        events = doc  # bare-array chrome traces are legal too
    if not isinstance(events, list):
        raise SystemExit(
            f"trace_summary: {path!r} has no traceEvents list — not a "
            f"chrome trace?"
        )
    return [e for e in events if isinstance(e, dict)]


def wait_rows(events: list[dict]) -> list[dict]:
    rows = []
    for e in events:
        args = e.get("args") or {}
        if e.get("cat") == "wait_telemetry" and "total_spins" in args:
            rows.append({
                "name": e.get("name", "?"),
                "calls": args.get("calls", 0),
                "total_spins": args.get("total_spins", 0),
                "max_spins": args.get("max_spins", 0),
                "mean_spins": args.get("mean_spins", 0),
                "label": args.get("label", ""),
            })
    rows.sort(key=lambda r: (-r["total_spins"], r["name"]))
    return rows


def span_rows(events: list[dict]) -> list[dict]:
    rows = []
    for e in events:
        if e.get("ph") != "X":
            continue
        args = e.get("args") or {}
        rows.append({
            "name": e.get("name", "?"),
            "dur_ms": float(e.get("dur", 0.0)) / 1e3,
            "rung": args.get("rung", ""),
            "label": args.get("label", ""),
        })
    rows.sort(key=lambda r: (-r["dur_ms"], r["name"]))
    return rows


def incident_rows(paths: list[str]) -> list[dict]:
    """One row per post-mortem bundle: what fired, when, whether an
    alert led it, and the attributed culprit PEs."""
    rows = []
    for path in paths:
        try:
            with open(path) as f:
                b = json.load(f)
        except (OSError, ValueError):
            continue
        trig = b.get("trigger") or {}
        firing = [
            key for key, row in sorted(
                ((b.get("alerts") or {}).get("rules") or {}).items()
            )
            if row.get("state") == "firing"
        ]
        attribution = b.get("attribution") or {}
        peers = attribution.get("peers") or {}
        bits = [
            f"pe{pe}:{row.get('state')}"
            for pe, row in sorted(peers.items(), key=lambda kv: int(kv[0]))
        ]
        # scoped namespaces (ISSUE 17): owned-scope culprits render as
        # pe{N}@{owner} so a fleet bundle names the replica too
        for owner, sc in sorted((attribution.get("scopes") or {}).items()):
            bits.extend(
                f"pe{pe}@{owner}:{row.get('state')}"
                for pe, row in sorted((sc.get("peers") or {}).items(),
                                      key=lambda kv: int(kv[0]))
            )
        culprits = ",".join(bits)
        rows.append({
            "bundle": os.path.basename(path),
            "kind": trig.get("kind", "?"),
            "family": trig.get("family", "?"),
            "clock_s": trig.get("clock_s", ""),
            "alerts_firing": ";".join(firing) or "-",
            "culprits": culprits or "-",
            "reason": (trig.get("reason") or "")[:60],
        })
    # clock_s may be missing on a truncated/foreign bundle (shown as "");
    # never let str-vs-float comparison take the whole summary down
    rows.sort(key=lambda r: (
        not isinstance(r["clock_s"], (int, float)),
        r["clock_s"] if isinstance(r["clock_s"], (int, float)) else 0.0,
        r["bundle"],
    ))
    return rows


def _table(rows: list[dict], cols: list[tuple[str, str]], n: int) -> str:
    if not rows:
        return "  (none)"
    widths = {
        key: max(len(title), *(len(str(r[key])) for r in rows[:n]))
        for key, title in cols
    }
    head = "  " + "  ".join(t.ljust(widths[k]) for k, t in cols)
    sep = "  " + "  ".join("-" * widths[k] for k, _ in cols)
    body = [
        "  " + "  ".join(str(r[k]).ljust(widths[k]) for k, _ in cols)
        for r in rows[:n]
    ]
    return "\n".join([head, sep, *body])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", nargs="?",
                    help="obs chrome-trace JSON path (optional when "
                         "--incidents is given)")
    ap.add_argument("-n", type=int, default=10, help="rows per table")
    ap.add_argument("--incidents", metavar="DIR",
                    help="fold DIR's black-box incident bundles into the "
                         "summary (ISSUE 15)")
    args = ap.parse_args(argv)
    if args.trace is None and args.incidents is None:
        ap.error("need a trace path and/or --incidents DIR")

    if args.incidents is not None:
        paths = sorted(glob.glob(
            os.path.join(args.incidents, "incident_*.json")
        ))
        incidents = incident_rows(paths)
        print(f"== incidents ({len(incidents)} bundle(s) in "
              f"{args.incidents}) ==")
        print(_table(incidents, [
            ("clock_s", "clock_s"), ("kind", "kind"), ("family", "family"),
            ("alerts_firing", "alerts_firing"), ("culprits", "culprits"),
            ("reason", "reason"),
        ], max(args.n, len(incidents))))
        if args.trace is None:
            return 0
        print()

    events = load_events(args.trace)
    waits = wait_rows(events)
    spans = span_rows(events)

    print(f"== top {args.n} wait sites by total spins "
          f"({len(waits)} site(s) recorded) ==")
    print(_table(waits, [
        ("name", "wait site"), ("calls", "calls"),
        ("total_spins", "total_spins"), ("mean_spins", "mean_spins"),
        ("max_spins", "max_spins"), ("label", "label"),
    ], args.n))
    print()
    print(f"== top {args.n} slowest spans ({len(spans)} span(s)) ==")
    print(_table(spans, [
        ("name", "span"), ("dur_ms", "dur_ms"), ("rung", "rung"),
        ("label", "label"),
    ], args.n))
    overflow = [e for e in events
                if "overflow_sites" in (e.get("args") or {})]
    if overflow:
        print()
        print("!! telemetry window overflow (waits past the per-kernel "
              "slot window — raise obs.telemetry.TELEM_SLOTS to see them):")
        for e in overflow:
            print(f"  {e.get('name')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
