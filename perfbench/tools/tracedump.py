#!/usr/bin/env python3
"""Look at a raw trace by hand: planes, lines, the names that take most
time on each line, and one sample event's stats per name. With ``--cut``
it also writes a small window of it in the harness's plain form, for the
recorded trace that the reduction is checked on.

    python3 perfbench/tools/tracedump.py --workload <cell> --seed 1 --seconds 45 [--cut out.json --cut-s 1.5]
    python3 perfbench/tools/tracedump.py .perfbench/trace/<cell>

With ``--workload`` it first makes a traced run of the cell and keeps the
raw trace (``run.py`` itself deletes it once it is reduced).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(HERE), HERE]

from harness import trace as tr  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir", nargs="?")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--grep", help="also list every name matching this")
    ap.add_argument("--cut")
    ap.add_argument("--cut-s", type=float, default=1.5)
    ap.add_argument("--cut-around", default="^jit_fn",
                    help="the cut starts 0.3 s before the first program "
                         "execution whose name matches")
    args = ap.parse_args()
    if args.workload:
        import run as bench_run
        from harness import cells

        cell = cells.Cell(cells.benchmark(), args.workload)
        result = bench_run.run_cell(
            cell, args.seed, args.seconds, True, bench_run.require_chips(cell),
            keep_trace=True)
        print(json.dumps(result))
        args.trace_dir = os.path.join(bench_run.OUT_DIR, "trace", cell.name)
    from jax.profiler import ProfileData

    path = tr.find_xplane(args.trace_dir)
    print(f"{path}: {os.path.getsize(path) / 1e6:.1f} MB")
    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            total, count, sample = {}, {}, {}
            n = 0
            for e in line.events:
                n += 1
                total[e.name] = total.get(e.name, 0) + e.duration_ns
                count[e.name] = count.get(e.name, 0) + 1
                sample.setdefault(e.name, e)
            print(f"  line {line.name!r}: {n} events, {len(total)} names")
            if not plane.name.startswith("/device") and "perfbench" not in " ".join(total):
                continue
            ranked = sorted(total, key=lambda k: -total[k])
            shown = ranked[: args.top] + [
                n for n in ranked[args.top:]
                if args.grep and re.search(args.grep, n[:200])]
            for name in shown:
                stats = {k: (str(v)[:80]) for k, v in sample[name].stats}
                print(f"    {total[name] / 1e9:9.4f} s x{count[name]:<6} "
                      f"{name[:90]!r} {json.dumps(stats)[:400]}")
    if args.cut:
        t = tr.load_xplane(path)
        w0, w1 = t.window()
        mods = t.line(t.device_planes()[0], tr.MODULES).within(w0, w1)
        hit = mods.matching(args.cut_around)
        c0 = int(hit.start[0] - 0.3e9) if len(hit) else int((w0 + w1) // 2)
        c1 = int(c0 + args.cut_s * 1e9)
        cut = {}
        for plane, lines in t.planes.items():
            for line, ev in lines.items():
                keep = ev.select((ev.end > c0) & (ev.start < c1))
                used = sorted(set(keep.idx.tolist()))
                remap = {k: i for i, k in enumerate(used)}
                if len(keep):
                    cut.setdefault(plane, {})[line] = {
                        "names": [ev.names[k] for k in used],
                        "idx": [remap[k] for k in keep.idx.tolist()],
                        "start": (keep.start - c0).tolist(),
                        "dur": keep.dur.tolist(),
                    }
        with open(args.cut, "w") as f:
            json.dump(cut, f, separators=(",", ":"))
        print(f"cut [{(c0 - w0) / 1e9:.3f}, {(c1 - w0) / 1e9:.3f}) s of the "
              f"window into {args.cut}: {os.path.getsize(args.cut) / 1e3:.0f} kB")
    return 0


if __name__ == "__main__":
    sys.exit(main())
