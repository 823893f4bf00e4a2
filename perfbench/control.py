#!/usr/bin/env python3
"""Where the limits of ``correct`` come from, and the proof that they
hold: sound readings of the program and readings of the lower-precision
control, over many seeds in ONE process (one set-up; the weights are made
anew for every seed under the same compiled engine), at the cell's own
size and load over a short window. The benchmark's own runs never run
the control.

For every seed it prints the numbers ``run.py`` compares (``max_gap``,
``mean_gap`` of the served tokens under the plain reference) and, for the
first ``--control-seeds`` of them, the same two numbers for the tokens
that the W8A8 int8 twin of the reference puts first at the same
positions. Both go through ``correct.verdict`` under the configuration's
limits: every sound seed has to come out correct and every control seed
not correct, or the exit code is 1.

    python3 perfbench/control.py --workload <cell> --seeds 1,2,3 --seconds 15
    python3 perfbench/control.py --workload <cell> --replay chiprun_out/<log>

``--replay`` judges the ``CONTROL`` lines of an earlier call's output
again under the limits as committed; it needs no chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

from harness import cells, correct, traffic  # noqa: E402


def verdicts(row: dict, limits: dict) -> dict:
    """The program's readings of one seed and, where it has them, the
    control's put in the program's place, each through the comparison
    that decides ``correct``."""
    base = {"failed": row.get("failed", 0),
            "health_flips": row.get("health_flips", 0),
            "tokens_compared": row["tokens_compared"]}
    ok, _ = correct.verdict(dict(base, max_gap=row["max_gap"],
                                 mean_gap=row["mean_gap"]), limits)
    out = {"seed": row.get("seed"), "sound_correct": ok}
    if "control_max_gap" in row:
        # the control is the reference itself: nothing of it fails or flips
        ok, numbers = correct.verdict(
            {"failed": 0, "health_flips": 0,
             "tokens_compared": row["tokens_compared"],
             "max_gap": row["control_max_gap"],
             "mean_gap": row["control_mean_gap"]}, limits)
        out["control_correct"] = ok
        out["control_over_limit"] = sorted(
            k for k, (v, lim) in numbers.items() if not v <= lim)
    return out


def summarize(rows: list, limits: dict) -> int:
    """Print each seed's verdicts and the readings the limits stand
    between; 0 only if every sound seed passed and every control failed."""
    judged = [verdicts(r, limits) for r in rows]
    for v in judged:
        print("CONTROL-VERDICT " + json.dumps(v), flush=True)
    low = [r for r in rows if "control_max_gap" in r]
    print("CONTROL-SUMMARY " + json.dumps({
        "seeds": len(rows), "control_seeds": len(low), "limits": limits,
        "sound_max_gap_largest": max(r["max_gap"] for r in rows),
        "sound_mean_gap_largest": max(r["mean_gap"] for r in rows),
        "control_max_gap_smallest": min(
            (r["control_max_gap"] for r in low), default=None),
        "control_mean_gap_smallest": min(
            (r["control_mean_gap"] for r in low), default=None),
        "sound_correct": sum(v["sound_correct"] for v in judged),
        "control_correct": sum(v.get("control_correct", False) for v in judged),
    }), flush=True)
    bad = [v for v in judged
           if not v["sound_correct"] or v.get("control_correct", False)]
    return 1 if bad or not low else 0


def main(argv=None, devices=None, bench=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds")
    ap.add_argument("--replay")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    cell = cells.Cell(bench or cells.benchmark(), args.workload)
    config = cell.config
    if args.replay:
        with open(args.replay) as f:
            rows = [json.loads(line[len("CONTROL "):]) for line in f
                    if line.startswith("CONTROL {")]
        return summarize(rows, config["limits"])
    import run as bench_run

    used = (devices or bench_run.require_chips)(cell)
    reference, adapter, spec = bench_run.open_cell(cell)
    seeds = [int(x) for x in args.seeds.split(",")]
    dims = correct.shape(spec, int(spec.get("check_requests", 4)))
    system = None
    rows = []
    for k, seed in enumerate(seeds):
        reqs = traffic.generate(spec, config["sizes"]["vocab"], seed, args.seconds)
        if system is None:
            system = adapter.System(config, reference, used, seed)
            system.warm(reqs)
            bench_run.log("warmed")
        else:
            system.reseed(seed)
        reqs = [traffic.Req(f"c{seed}_{r.uid}", r.t_s, r.prompt, r.n_out)
                for r in reqs]
        records, _ = system.serve(reqs)
        picked = correct.sample(records, seed, dims[0])
        row = correct.judge(
            reference, config["sizes"], seed, picked,
            {r.uid: r.prompt for r in reqs}, dims, used,
            control=k < args.control_seeds)
        row.update(seed=seed, failed=sum(not r.ok for r in records),
                   health_flips=system.health_flips(), requests=len(records))
        rows.append(row)
        print("CONTROL " + json.dumps(row), flush=True)
    return summarize(rows, config["limits"])


if __name__ == "__main__":
    sys.exit(main())
