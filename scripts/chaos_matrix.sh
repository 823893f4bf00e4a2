#!/usr/bin/env bash
# Run the FULL resilience fault-injection matrix standalone
# (tests/test_chaos.py + tests/test_elastic.py + the chunk-signal cells
# of tests/test_chunked.py and tests/test_chunked_a2a.py + the ragged
# chunk-fault cells of tests/test_ragged.py + the emitter cells of
# tests/test_emitter.py + the serving-engine cells
# of tests/test_serving.py, docs/resilience.md): every kernel family ×
# drop/dup/delay signal + straggler PE, the ring and a2a/MoE chunk-fault
# cells (ISSUE 3/4), the ragged-pipeline cells (ISSUE 5: ragged tail
# blocks must add no droppable signal edge), the emitter cells (ISSUE 7:
# a dropped/dup'd chunk signal under the w8 ragged chunked pipeline must
# name only pre-existing diagnostic kinds or stay exact — the w8 scale
# DMAs add no signal edges), the forced-compile-failure
# degradation cases, the elastic arcs
# (retry/quarantine/shrink/readmit), and the elastic SERVING arcs
# (ISSUE 6: persistent straggler mid-serving → quarantine → the engine
# shrinks to the serviceable world and keeps serving with prefix replay
# → probation re-admit regrows it — zero lost requests, tokens
# byte-identical to the uninterrupted run), including the cells marked
# `slow` that tier-1 skips.
#
# The serving arc is HOST-LEVEL (FakeClock + fabricated watchdog records
# through the production engine paths) and runs everywhere; live-fault
# arcs remain interpreter-gated as before.
#
# The live injection cells need the Mosaic TPU interpreter (jax >= 0.6);
# on older jax lines they skip and the degradation + host-arc tiers
# still run.
#
# Since ISSUE 8 the matrix also covers the DATA-INTEGRITY cells
# (tests/test_integrity.py): payload-corruption kinds
# (bitflip/torn_chunk/stale_read/nan_inject) × detection tier (per-chunk
# canary, host output guards), the detect → retry → golden-fallback →
# quarantine ladder with bit-exact fallback output, the train-step
# skip-step containment, and the serving poison-quarantine cell (one
# NaN-logit request typed-rejected, survivors byte-identical) plus the
# stop(drain=True)-vs-persistent-straggler drain race. The host-tier
# integrity cells run everywhere; live payload injection is
# interpreter-gated like every other injection cell.
#
# Since ISSUE 9 the matrix also covers the OBSERVABILITY cells
# (tests/test_obs.py): an armed obs layer (spans + device wait
# telemetry) must be observation-only — clean armed runs bit-exact to
# disarmed ones, chaos under an armed obs layer names only pre-existing
# diagnostic kinds, and the interpreter-gated straggler cell proves
# end-to-end attribution (an injected straggler shifts the victim wait
# site's spin histogram on the chunked ring pipeline).
#
# Since ISSUE 10 the matrix also runs the STATIC protocol lint
# (scripts/protocol_lint.py, full sweep): every tune-space tuple of all
# seven kernel families at worlds {2, 4, 8} proved credit-balanced and
# deadlock-free from the captured signal graph alone, plus the
# seeded-defect harness (analysis/defects.py — dropped wait, dropped or
# extra signal, swapped chunk issue order, missing drain, each flagged
# with a slot/site-named diagnosis). Unlike every other tier here it
# needs NO interpreter, so this coverage is identical on every jax line.
# Skip with TDT_SKIP_PROTOCOL_LINT=1.
#
# Since ISSUE 11 the matrix also covers the OVERLOAD cells
# (tests/test_overload.py): deadline-expiry shedding, priority shed
# order, per-class retry-budget exhaustion, brownout-ladder hysteresis
# on a FakeClock, the disarmed-byte-identity pin, and the QUICK CHAOS
# SOAK cell — one seeded multi-fault campaign (flash-crowd bursts ×
# persistent straggler × payload corruption) through resilience/soak.py
# with its invariants (no lost request, no deadlock, balanced
# accounting, bit-identical seeded replay). The full 20-campaign soak is
# scripts/chaos_soak.py / `pytest -m soak` (soak implies slow).
#
# Since ISSUE 13 the matrix also covers the DISAGGREGATED-SERVING cells
# (tests/test_disagg.py, tests/test_disagg_soak.py): a corrupted/dropped KV chunk mid-handoff must
# walk the guard ladder (bounded re-send → whole-sequence re-stream →
# decode-local cold re-prefill) with the culprit PE struck and the
# request finishing byte-identically to unified cold prefill; a
# prefill-pool straggler shrinks the POOL mid-stream; a prefill-pool
# timeout storm collapses the topology to the unified engine with zero
# lost requests; and the quick disagg soak campaign replays
# bit-identically (resilience/soak.py SoakSpec.disagg; the full set
# rides scripts/chaos_soak.py). The static lint also proves the new
# kv_stream kernel family (ops/kv_stream.py) at worlds {2, 4, 8}.
#
# Since ISSUE 12 the matrix also covers the PREFIX-CACHE cells
# (tests/test_prefix_cache_chaos.py): a poisoned SHARED prefix page must
# strike every reader of the chain (evicted for a cold re-prefill,
# byte-identical regeneration, no request lost), and the quick
# shared-prefix soak campaign composes the strike with the straggler /
# corruption rebuild arcs over burst traffic (resilience/soak.py
# SoakSpec.shared_prefix; the full set rides scripts/chaos_soak.py).
#
# Since ISSUE 14 the matrix also covers the SCHEDULE-SYNTHESIZER cells
# (tests/test_synth.py): seeded emitter-bug mutations on SYNTHESIZED
# span-policy schedules (window/interleave/torus2d) must be flagged by
# slot/site while the clean twin stays silent — the static defect twins
# of the synthesized families, held to the hand-written standard. The
# full lint below re-proves the whole standing registry
# (triton_dist_tpu/synth/admitted.py) at worlds {2, 4, 8} on every run.
#
# Since ISSUE 15 the matrix also covers the FLIGHT-RECORDER cells
# (tests/test_flight_recorder.py): the chaos-marked quick soak must
# write exactly ONE post-mortem bundle per health-flipping event
# (resilience/soak.py check_blackbox_invariant) with byte-identical
# bundles + metrics exports across seeded replays, and the burn-rate
# alert must fire BEFORE the brownout ladder reaches shed_all_batch.
#
# Since ISSUE 16 the matrix also covers the FLEET cells
# (tests/test_fleet.py): a replica killed mid-burst (typed step death
# out of its decode pool) must have every queued + in-flight request
# re-offered to the survivors with the ORIGINAL arrival/deadline
# anchors and token streams byte-identical to an unkilled run (greedy
# AND seeded-sampled); graceful drain and crash must produce equivalent
# terminal censuses; and the quick fleet soak campaign (replica death ×
# corrupt handoff × overload, resilience/soak.py SoakSpec.fleet)
# replays bit-identically (the full set rides scripts/chaos_soak.py).
#
# Since ISSUE 17 the matrix also covers the RECOVERY-PLANE cells
# (tests/test_recovery.py, tests/test_recovery_soak.py): the elastic-ON fleet with per-replica
# ElasticScope namespaces must keep strikes inside their replica
# (pe{N}@r{i} health families only), regrow a quarantined decode pool
# by probation mid-serve, un-collapse a collapsed prefill pool after a
# clean probation window, and resurrect a dead replica (probe rounds →
# fresh engine → cold trie + affinity ramp) that then serves again —
# with the quick recovery soak campaign
# (resilience/soak.py SoakSpec.fleet_recovery_spec) replaying
# bit-identically.
#
# Since ISSUE 18 the matrix also covers the RANGED-PREFILL cells
# (tests/test_pipelined_admission.py, tests/test_disagg_soak.py): the pipelined disagg handoff — decode
# admission at FIRST-page-landed while the tail streams — must keep the
# transfer-span decomposition exact with tokens byte-identical, and a
# corrupt KV chunk injected mid-pipelined-handoff must walk the guard
# ladder with zero lost requests and a bit-identical seeded replay
# (resilience/soak.py SoakSpec.disagg(pipelined_handoff=True); the full
# set rides scripts/chaos_soak.py).
#
# Since ISSUE 19 the matrix also covers the FP8 cells
# (tests/test_fp8.py): the brownout3 rung — a two-stage precision
# downshift (w8 then fp8) driven through the rebuild+replay machinery —
# must climb AND revert with zero lost requests and a bit-identical
# seeded replay, and a corrupt KV chunk on the fp8 handoff wire must
# walk the same guard ladder as int8 (the wire format changes the
# payload bytes, never the integrity protocol). The static lint also
# proves the fp8 tune tuples (the w8 twins' exact slot structure) at
# worlds {2, 4, 8}.
#
# Since ISSUE 20 the matrix also covers the SPECULATIVE-SERVING cells
# (tests/test_spec_soak.py): a corrupted draft token injected
# mid-round must be REJECTED by the batched verify pass with the token
# stream byte-identical to a non-speculative run, and the quick
# speculative soak campaign — self-draft speculation × scheduled draft
# corruption × a straggler shrink + prefix replay mid-speculation —
# must come up green (the quick cell, on a world of two since PR 44) with
# a bit-identical seeded replay (the slow cell, on four)
# (resilience/soak.py SoakSpec.speculative; the full set rides
# scripts/chaos_soak.py).
#
# Every cell runs under a wall-clock budget (TDT_CELL_TIMEOUT_S,
# default 600 s; conftest.py delivers it as a SIGALRM inside the cell):
# a hung cell reports as one named FAILED row — and so fails the exit
# code — instead of stalling the whole matrix.
#
# Per-cell failures propagate into the exit code (CI gates on it), and a
# pass/fail summary table is printed after the run.
#
# Usage: scripts/chaos_matrix.sh [--quick] [extra pytest args]
#
# --quick: the bounded tier-1 subset — chaos cells not marked slow, over
# the corruption + serving + elastic files only (the cells most likely to
# regress silently; run_tier1.sh's chaos smoke covers the same marker
# over all of tests/, this flag is the focused standalone form).
set -euo pipefail
cd "$(dirname "$0")/.."

log="$(mktemp "${TMPDIR:-/tmp}/chaos_matrix.XXXXXX.log")"
trap 'rm -f "$log"' EXIT

files="tests/test_chaos.py tests/test_elastic.py \
    tests/test_chunked.py tests/test_chunked_a2a.py tests/test_ragged.py \
    tests/test_emitter.py tests/test_serving.py tests/test_integrity.py \
    tests/test_obs.py tests/test_analysis.py tests/test_overload.py \
    tests/test_prefix_cache_chaos.py tests/test_prefix_cache_soak.py \
    tests/test_disagg.py tests/test_disagg_soak.py tests/test_synth.py \
    tests/test_flight_recorder.py tests/test_fleet.py \
    tests/test_recovery.py tests/test_recovery_soak.py \
    tests/test_pipelined_admission.py \
    tests/test_fp8.py tests/test_spec_serving.py tests/test_spec_soak.py"
marker="chaos"
lint_args=""
if [ "${1:-}" = "--quick" ]; then
    shift
    files="tests/test_integrity.py tests/test_serving.py \
        tests/test_elastic.py tests/test_overload.py \
        tests/test_prefix_cache_chaos.py tests/test_prefix_cache_soak.py \
        tests/test_disagg.py tests/test_disagg_soak.py \
        tests/test_synth.py tests/test_flight_recorder.py \
        tests/test_fleet.py tests/test_recovery.py \
        tests/test_recovery_soak.py \
        tests/test_pipelined_admission.py tests/test_fp8.py \
        tests/test_spec_serving.py tests/test_spec_soak.py"
    marker="chaos and not slow"
    # keep the quick posture bounded: worlds {2,4} (the full {2,4,8}
    # sweep is the default standalone run's job)
    lint_args="--quick"
fi

# one hung cell must not stall the matrix: conftest.py turns this budget
# into a SIGALRM TimeoutError inside the cell (named FAILED row, exit
# code propagates). Override or set to 0 to disable.
: "${TDT_CELL_TIMEOUT_S:=600}"
export TDT_CELL_TIMEOUT_S

# -v so every cell prints its own PASSED/FAILED/SKIPPED line for the
# summary; the pytest exit code is captured, not exec'd away, so the
# table still prints when cells fail.
set +e
# shellcheck disable=SC2086 — $files is a deliberate word-split list
env JAX_PLATFORMS=cpu python -m pytest $files \
    -m "$marker" -v -rs -p no:cacheprovider -p no:xdist -p no:randomly "$@" \
    2>&1 | tee "$log"
rc=${PIPESTATUS[0]}
set -e

echo
echo "== chaos matrix summary =="
# one row per cell: "tests/test_chaos.py::test_chaos_matrix[drop-ag] PASSED"
awk '
    / (PASSED|FAILED|ERROR|SKIPPED|XFAIL|XPASS)/ && /::/ {
        split($1, path, "::"); cell = path[2];
        for (i = 2; i <= NF; i++)
            if ($i ~ /^(PASSED|FAILED|ERROR|SKIPPED|XFAIL|XPASS)$/) verdict = $i;
        printf "  %-72s %s\n", cell, verdict;
        n[verdict]++;
    }
    END {
        printf "  %d passed, %d failed, %d errors, %d skipped\n",
            n["PASSED"], n["FAILED"], n["ERROR"], n["SKIPPED"];
    }
' "$log"

lint_rc=0
if [ "${TDT_SKIP_PROTOCOL_LINT:-0}" != "1" ]; then
    echo
    echo "== static protocol lint (full sweep + defect harness) =="
    # shellcheck disable=SC2086 — $lint_args is a deliberate flag list
    env JAX_PLATFORMS=cpu timeout -k 10 900 python scripts/protocol_lint.py \
        $lint_args || lint_rc=$?
fi

failed=$(grep -cE ' (FAILED|ERROR)$| (FAILED|ERROR) ' "$log" || true)
if [ "$rc" -ne 0 ] || [ "$failed" -gt 0 ] || [ "$lint_rc" -ne 0 ]; then
    echo "chaos matrix: FAIL (pytest rc=$rc, failing cells=$failed," \
        "protocol lint rc=$lint_rc)"
    exit 1
fi
echo "chaos matrix: PASS"
