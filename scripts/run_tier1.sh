#!/usr/bin/env bash
# The ONE tier-1 gate: builders and CI run this same script, so "tests
# pass" means the same thing everywhere (the tier-1 phase is the driver's
# own command, /root/TESTS_LAST_RUN.json `commands`).
#
# Three phases:
#   1. the full tier-1 suite (everything not marked `slow`, 1470 s budget,
#      six xdist workers with --dist loadfile, CPU backend, 8 virtual
#      devices via tests/conftest.py — the tests/
#      glob picks up tests/test_serving.py and the ISSUE 15
#      tests/test_flight_recorder.py automatically);
#   2. the static protocol lint (scripts/protocol_lint.py --quick,
#      ISSUE 10): every fused family's signal graph proved
#      credit-balanced and deadlock-free from a recorded trace — needs no
#      interpreter, so a schedule/emitter change that unbalances a slot
#      fails here (TDT_SKIP_PROTOCOL_LINT=1 to skip);
#   3. a fast `chaos`-marker smoke subset (resilience + elastic layers,
#      incl. the elastic SERVING arcs of tests/test_serving.py) — a
#      focused re-run of the cells most likely to regress silently,
#      cheap enough to eyeball on every PR.
#
# Prints PASSED/FAILED counts per phase (record them in CHANGES.md) and
# exits non-zero if either phase fails.
#
# Gate semantics: the tier-1 phase must exit 0. While the manifest of
# DOCUMENTED failures (tests/known_failures.txt, regenerated in PR 23 for
# the one installed jax 0.9.0) is not empty, the
# acceptance bar is "no worse than seed": set TDT_TIER1_MIN_PASS=<N> /
# TDT_TIER1_MAX_FAIL=<M> to gate on counts instead of the raw exit code
# (the chaos smoke must always exit 0 either way). Independent of the
# count floors, the failure SET must be a subset of the committed
# tests/known_failures.txt manifest (scripts/diff_failures.py): counts
# can mask a one-fixed-one-broken swap, the subset check cannot. Skip it
# (e.g. when running a filtered subset via extra pytest args) with
# TDT_SKIP_FAILURE_DIFF=1.
#
# Usage: scripts/run_tier1.sh [extra pytest args for the tier-1 phase]
set -uo pipefail
cd "$(dirname "$0")/.."

count() { # count <word> <log>: occurrences of "N <word>" in the summary
    grep -aoE "[0-9]+ $1" "$2" | tail -1 | grep -oE '[0-9]+' || echo 0
}

# logs of this run: a fresh directory under TMPDIR, so two checkouts on
# one machine never meet (nothing outside it is written)
logdir=$(mktemp -d "${TMPDIR:-/tmp}/tdt_tier1.XXXXXX")
t1_log=$logdir/tier1.log
chaos_log=$logdir/chaos.log
echo "logs: $logdir"

echo "== tier-1 (ROADMAP verify) =="
# the driver's own command (/root/TESTS_LAST_RUN.json): six xdist workers,
# one FILE per worker at a time, 1470 s. The driver also exports
# ALLOW_MULTIPLE_LIBTPU_LOAD=1 for its run; the repo does not set it —
# tests/test_chip_compile.py loads libtpu from one worker only.
timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p xdist -n 6 --dist loadfile \
    -p no:randomly "$@" 2>&1 | tee "$t1_log"
t1_rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$t1_log" | tr -cd . | wc -c)"

# failure-set strict-subset gate (ISSUE 8 satellite): any NEW tier-1
# failure fails the gate even when the count floors still pass
diff_rc=0
if [ "${TDT_SKIP_FAILURE_DIFF:-0}" != "1" ] && [ "$#" -eq 0 ]; then
    echo
    echo "== failure-set diff (tests/known_failures.txt) =="
    python scripts/diff_failures.py "$t1_log"
    diff_rc=$?
fi

# static protocol lint (ISSUE 10): prove every fused family's signal
# graph credit-balanced and deadlock-free at trace time — no interpreter
# needed. Quick posture (worlds
# {2,4}; same protocol generators, less wall time — chaos_matrix.sh runs
# the full {2,4,8} sweep). Skip with TDT_SKIP_PROTOCOL_LINT=1.
lint_rc=0
if [ "${TDT_SKIP_PROTOCOL_LINT:-0}" != "1" ]; then
    echo
    echo "== static protocol lint (scripts/protocol_lint.py --quick) =="
    timeout -k 10 420 env JAX_PLATFORMS=cpu \
        python scripts/protocol_lint.py --quick || lint_rc=$?
fi

echo
echo "== chaos smoke (resilience + elastic) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'chaos and not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee "$chaos_log"
chaos_rc=${PIPESTATUS[0]}

echo
echo "== tier-1 summary =="
printf '  tier-1:      rc=%s  %s passed / %s failed / %s skipped\n' \
    "$t1_rc" "$(count passed "$t1_log")" "$(count failed "$t1_log")" \
    "$(count skipped "$t1_log")"
printf '  chaos smoke: rc=%s  %s passed / %s failed / %s skipped\n' \
    "$chaos_rc" "$(count passed "$chaos_log")" \
    "$(count failed "$chaos_log")" "$(count skipped "$chaos_log")"
printf '  protocol lint: rc=%s\n' "$lint_rc"

t1_ok=0
if [ "$t1_rc" -ne 0 ]; then
    t1_ok=1
    # count-based gate for environments with documented seed failures
    if [ -n "${TDT_TIER1_MIN_PASS:-}" ]; then
        passed=$(count passed "$t1_log")
        failed=$(count failed "$t1_log")
        if [ "$passed" -ge "$TDT_TIER1_MIN_PASS" ] \
            && [ "$failed" -le "${TDT_TIER1_MAX_FAIL:-$failed}" ]; then
            echo "  tier-1 rc=$t1_rc but counts meet the baseline floor" \
                "(>= $TDT_TIER1_MIN_PASS passed," \
                "<= ${TDT_TIER1_MAX_FAIL:-any} failed)"
            t1_ok=0
        fi
    fi
fi
if [ "$t1_ok" -ne 0 ] || [ "$chaos_rc" -ne 0 ] \
    || [ "$diff_rc" -ne 0 ] || [ "$lint_rc" -ne 0 ]; then
    echo "tier-1 gate: FAIL"
    exit 1
fi
echo "tier-1 gate: PASS"
