#!/usr/bin/env python3
"""Find a cell's rate ONCE, on the chip: one process, one set-up, the
cell's traffic at several rates in turn (open loop: ``rate_rps``;
backlog: ``backlog_tokens_per_s``). The knee is the highest rate at which
the queue does not grow over the window: the last request finishes soon
after the last arrival, and the TTFT tail stays near its unloaded value.
The number goes into the traffic file by hand, with this table in
``PERF.md``; the benchmark's own runs never search.

    python3 perfbench/tools/sweep.py --workload <cell> --rates 0.8,1.2,1.6 --seconds 30
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(HERE), HERE]

import run as bench_run  # noqa: E402
from harness import cells, stats, traffic  # noqa: E402


def main(argv=None, devices=None, bench=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = cells.Cell(bench or cells.benchmark(), args.workload)
    used = (devices or bench_run.require_chips)(cell)
    config = cell.config
    reference, adapter, spec = bench_run.open_cell(cell)
    knob = "backlog_tokens_per_s" if spec["process"] == "backlog" else "rate_rps"
    rates = [float(x) for x in args.rates.split(",")]
    trial = [traffic.generate(dict(spec, **{knob: r}), config["sizes"]["vocab"],
                              args.seed + i, args.seconds)
             for i, r in enumerate(rates)]
    system = adapter.System(config, reference, used, args.seed)
    system.warm([r for reqs in trial for r in reqs])
    bench_run.log("warmed")
    for rate, reqs in zip(rates, trial):
        reqs = [traffic.Req(f"s{rate}_{r.uid}", r.t_s, r.prompt, r.n_out)
                for r in reqs]
        records, t_open = system.serve(reqs)
        ttft = stats.ttft_ms(records)
        last_due = max(r.t_due for r in records) - t_open
        last_done = max(r.t_finished for r in records if r.ok) - t_open
        row = {
            knob: rate, "requests": len(records),
            "failed": sum(not r.ok for r in records),
            "ttft_p50_ms": stats.percentile(ttft, 50),
            "ttft_p90_ms": stats.percentile(ttft, 90),
            "ttft_max_ms": max(ttft),
            "tpot_mean_ms": stats.tpot_mean_ms(records),
            "tokens_per_s": stats.tokens_per_s(records, t_open),
            "queue_wait_mean_ms": stats.mean(
                [(r.t_admitted - r.t_due) * 1e3 for r in records if r.ok]),
            "last_due_s": last_due, "drain_after_last_due_s": last_done - last_due,
        }
        print("SWEEP " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
