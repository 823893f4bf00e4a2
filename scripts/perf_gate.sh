#!/usr/bin/env bash
# Perf gate (ISSUE 3 satellite): run `bench.py --metric <m>` for the
# ring-op metric families and FAIL if any emitted `vs_baseline` drops
# below its floor in BASELINE.json's "perf_floors" table.
#
# Opt-in; needs the chip:
#   - without a TPU the gate FAILS (exit 2): a gate that passes because
#     nothing was measured is no gate. The parent never touches JAX (one
#     process per chip) — each `bench.py --metric` child finds the chip
#     or fails on its own. Set TDT_PERF_GATE_FORCE=1 with
#     TDT_BENCH_PLATFORM=cpu for CI plumbing checks (ratios meaningless).
#   - wire into CI via `TDT_PERF_GATE=1 scripts/run_tier1.sh` (the tier-1
#     driver runs it as an opt-in stage after the chaos smoke).
#
# Knobs:
#   TDT_PERF_GATE_METRICS  space-separated bench metric names
#                          (default: the perf_floors keys in BASELINE.json)
#   TDT_PERF_GATE_FORCE=1  allow bench's CPU plumbing mode
set -uo pipefail
cd "$(dirname "$0")/.."

python - "$@" <<'EOF'
import json
import os
import subprocess
import sys

with open("BASELINE.json") as f:
    baseline = json.load(f)
floors = {
    k: float(v)
    for k, v in baseline.get("perf_floors", {}).items()
    if not k.startswith("_")
}
if not floors:
    print("perf gate: no perf_floors in BASELINE.json — nothing to gate")
    sys.exit(0)

# suffix floors ("<family>_overlap_efficiency", "<family>_chunked") scope
# specific LINES of a family's run (see the per-line routing below) — they
# are not bench metric families themselves and must not be enumerated as
# `bench.py --metric` targets
_SUFFIXES = ("_overlap_efficiency", "_chunked")
families = sorted(k for k in floors if not k.endswith(_SUFFIXES))
metrics = os.environ.get("TDT_PERF_GATE_METRICS", "").split() or families

if (os.environ.get("TDT_BENCH_PLATFORM") == "cpu"
        and os.environ.get("TDT_PERF_GATE_FORCE", "0") != "1"):
    print("perf gate: TDT_BENCH_PLATFORM=cpu gates nothing real — set "
          "TDT_PERF_GATE_FORCE=1 for a plumbing check", file=sys.stderr)
    sys.exit(2)

failures, missing = [], []
for name in metrics:
    floor = floors.get(name)
    if floor is None:
        print(f"perf gate: {name}: no floor in BASELINE.json — skipped")
        continue
    print(f"== perf gate: bench.py --metric {name} (floor {floor}) ==",
          flush=True)
    try:
        proc = subprocess.run(
            [sys.executable, "bench.py", "--metric", name],
            capture_output=True, text=True,
            timeout=int(os.environ.get("TDT_BENCH_METRIC_TIMEOUT", "1500")),
        )
    except subprocess.TimeoutExpired as e:
        # a wedged device call must fail THIS metric with a clean verdict,
        # not crash the gate and discard the other metrics' results
        sys.stdout.write((e.stdout or b"").decode("utf-8", "replace")
                         if isinstance(e.stdout, bytes) else (e.stdout or ""))
        failures.append(f"{name}: bench timed out after {e.timeout:.0f}s")
        continue
    sys.stdout.write(proc.stdout)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr)
        failures.append(f"{name}: bench exited {proc.returncode}")
        continue
    lines = []
    for line in proc.stdout.splitlines():
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and "vs_baseline" in rec:
            lines.append(rec)
    if not lines:
        missing.append(name)
        continue
    gated = 0
    for rec in lines:
        # floors are scoped to the family that was RUN (no name-prefix
        # matching: "moe_w8" lines must never be gated by the "moe"
        # floor). Overlap-efficiency lines carry a differently-defined
        # ratio (serial/fused) than the pair-timed ratio the family floor
        # is calibrated against, so they gate only through an explicit
        # "<family>_overlap_efficiency" floor and are otherwise
        # informational. Chunked-schedule A/B lines (ISSUE 4) likewise
        # gate only through an explicit "<family>_chunked" floor: they
        # time a forced experimental schedule with no baseline reading
        # yet, and must not fail the gate while the shipped chunk=1
        # default holds its own floor.
        if "overlap_efficiency" in rec["metric"]:
            line_floor = floors.get(f"{name}_overlap_efficiency")
        elif "_chunked" in rec["metric"]:
            line_floor = floors.get(f"{name}_chunked")
        else:
            line_floor = floor
        if line_floor is None:
            print(f"  {rec['metric']}: vs_baseline={rec['vs_baseline']} "
                  "(no floor — informational)")
            continue
        gated += 1
        vs = float(rec["vs_baseline"])
        verdict = "ok" if vs >= line_floor else "BELOW FLOOR"
        print(f"  {rec['metric']}: vs_baseline={vs} (floor {line_floor}) "
              f"{verdict}")
        if vs < line_floor:
            failures.append(
                f"{rec['metric']}: vs_baseline {vs} < floor {line_floor}"
            )
    if not gated:
        missing.append(name)

if missing:
    failures.extend(f"{name}: emitted no metric lines" for name in missing)
if failures:
    print("perf gate: FAIL")
    for f in failures:
        print(f"  - {f}")
    sys.exit(1)
print("perf gate: PASS")
EOF
