#!/usr/bin/env python3
"""The spread of a cell's runs, as the builder's contract reads it: for
each metric and each set of runs, the distance between the first and the
third quartile (``statistics.quantiles(values, n=4)``) as a share of the
median; the bound follows from the wider of the two sets' spreads.

    python3 perfbench/tools/spread.py runs.jsonl

``runs.jsonl`` holds one line per run: ``{"set": 1, "seed": n, "result":
<the run's last line>}``.
"""

from __future__ import annotations

import json
import statistics
import sys


def spread(values: list[float]) -> float:
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def trimmed(values: list[float]) -> list[float]:
    """Without the run farthest from the median (the driver's reading of
    tightness forgives one far-off run a set)."""
    m = statistics.median(values)
    far = max(values, key=lambda v: abs(v - m))
    out = list(values)
    out.remove(far)
    return out


def main() -> int:
    rows = [json.loads(line) for line in open(sys.argv[1]) if line.strip()]
    sets = sorted({r["set"] for r in rows})
    names = sorted({m for r in rows for m in r["result"]["metrics"]})
    print(f"{len(rows)} runs, sets {sets}, correct: "
          f"{sum(r['result']['correct'] for r in rows)} of {len(rows)}")
    for name in names:
        per_set = {
            s: [r["result"]["metrics"][name]["value"] for r in rows
                if r["set"] == s and name in r["result"]["metrics"]]
            for s in sets
        }
        # setup_s: each side's first run compiles and is recorded apart
        if name == "setup_s":
            per_set = {s: v[1:] for s, v in per_set.items()}
        line = [f"{name}:"]
        spreads, kept = [], []
        for s, v in per_set.items():
            if len(v) < 2:
                continue
            spreads.append(spread(v))
            kept.append(spread(trimmed(v)))
            line.append(f"set {s} median {statistics.median(v):.6g} "
                        f"spread {100 * spreads[-1]:.3f}% "
                        f"(trimmed {100 * spread(trimmed(v)):.3f}%, "
                        f"min {min(v):.6g}, max {max(v):.6g});")
        allv = [x for v in per_set.values() for x in v]
        if spreads:
            # the driver's two readings: too tight if the mean of the sets'
            # trimmed spreads is over half the bound, too loose if the bound
            # is over eight times the widest spread
            line.append(f"widest {100 * max(spreads):.3f}%, all runs "
                        f"{100 * spread(allv):.3f}%, five times the widest "
                        f"{100 * 5 * max(spreads):.2f}%, mean of the trimmed "
                        f"{100 * sum(kept) / len(kept):.3f}%")
        if len(per_set) == 2:
            a, b = (statistics.median(v) for v in per_set.values())
            line.append(f"second median {100 * (b - a) / a:+.3f}% of the first")
        print(" ".join(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
