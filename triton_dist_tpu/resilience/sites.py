"""THE wait-site numbering table (ISSUE 10 satellite).

One module owns the constants that three layers previously agreed on only
by convention:

- ``resilience/records.py`` — the watchdog's diagnostic records name a
  wait by ``(site, kind)``;
- ``resilience/watchdog.py`` — ``KernelDiagScope.next_wait_site`` hands
  out the trace-time site ordinals those records carry;
- ``obs/telemetry.py`` — the wait-telemetry buffer keys its per-site spin
  histograms by the SAME ordinals and kinds, in a ``TELEM_SLOTS``-slot
  window.

The static signal-protocol verifier (``triton_dist_tpu/analysis``) imports
this table as its ground truth: a captured wait edge's ``(site, kind)``
must decode identically here, in a timeout record, and in a telemetry row,
or the three layers have drifted. Change a value here and every consumer
moves together; change one consumer's copy and ``tests/test_analysis.py``
(plus the re-export pins in ``tests/test_obs.py``) fails.

Site numbering contract (enforced by ``analysis/verify.py``): within one
kernel launch, bounded-wait sites are the dense sequence ``0, 1, 2, …`` in
trace order — ``KernelDiagScope.next_wait_site`` is the only allocator.
Sites at or past :data:`TELEM_SLOTS` still get diagnostics but fall out of
the telemetry window (counted in its overflow header, never silently).
"""

from __future__ import annotations

# --- wait kinds -------------------------------------------------------------
# Small ints burned into int32 diagnostic/telemetry buffers; append-only
# (a freed code would re-label historical records).

KIND_SIGNAL = 1   # shmem.signal_wait_until
KIND_WAIT = 2     # shmem.wait (dl.wait parity)
KIND_BARRIER = 3  # one round of shmem.barrier_all
KIND_CHUNK = 4    # shmem.wait_chunk: a per-chunk arrival wait of a chunked
                  # put (the sub-shard granularity of the ring pipelines)
KIND_INTEGRITY = 5  # shmem.wait_chunk canary: the landed chunk's payload
                    # checksum disagreed with the one the producer folded
                    # into the chunk signal (resilience/integrity.py)

KIND_NAMES = {
    KIND_SIGNAL: "signal_wait_until",
    KIND_WAIT: "wait",
    KIND_BARRIER: "barrier_all",
    KIND_CHUNK: "chunk_wait",
    KIND_INTEGRITY: "integrity_check",
}

# Wait kinds that are BOUNDED: they funnel through ``watchdog.bounded_wait``,
# consume a site ordinal, and land in the telemetry window when armed.
# (KIND_INTEGRITY records reuse the diag buffer but describe a checksum
# verdict, not a wait — no site is allocated for them.)
BOUNDED_KINDS = frozenset(
    {KIND_SIGNAL, KIND_WAIT, KIND_BARRIER, KIND_CHUNK}
)

# --- telemetry site window --------------------------------------------------
# Trace-time wait sites recorded per kernel launch by the obs layer's
# wait-telemetry buffer (obs/telemetry.py derives its whole record layout
# from this). Sites past the window bump the overflow header at runtime;
# the static verifier reports the overflow at TRACE time instead
# (analysis/verify.py check 4), so a schedule that outgrows the window is
# known before any chip run.
TELEM_SLOTS = 32

# Per-family site-window policy (ISSUE 12 satellite — the last standing
# protocol_lint warning retired by DECISION, not by silence). The
# telemetry window is a fixed per-launch SMEM budget; some tune-space
# corners legitimately allocate more wait sites than it holds, and that
# is an ACCEPTED diagnostic posture, not a protocol defect: diagnostics
# (timeout records) still name every site, the schedule is still proved
# credit-balanced and deadlock-free, and only SPIN ATTRIBUTION for the
# overflow sites collapses into the overflow header. A family earns a
# row here by (a) the overflow arising from a *bounded, reviewed*
# tune-space corner (not open-ended growth), and (b) a recorded waived
# ceiling so outgrowing the REVIEWED bound surfaces as a fresh warning.
#
# - ag_gemm @ chunks=8, world 8: 7 ring steps × 8 chunk waits + 3
#   barrier rounds = 59 sites. The 8-chunk candidate exists only at the
#   tail of AG_GEMM_TUNE_SPACE; spins for sites 32..58 aggregate into
#   the overflow header, which chip sessions read next to the per-site
#   histograms (obs/telemetry.py). Reviewed + accepted in ISSUE 12.
TELEM_SITE_WAIVERS: dict[str, int] = {
    "ag_gemm": 64,
}


def telem_site_budget(family: str) -> int:
    """The per-launch site count above which the static verifier WARNS
    for ``family``: the telemetry window, or the family's reviewed waiver
    ceiling (``TELEM_SITE_WAIVERS``). Runtime behavior is unchanged —
    sites past ``TELEM_SLOTS`` always bump the overflow header."""
    return TELEM_SITE_WAIVERS.get(family, TELEM_SLOTS)


def kind_name(code: int) -> str:
    """Readable name of a KIND_* code — the one spelling shared by timeout
    records, telemetry rows, and the static verifier's reports."""
    return KIND_NAMES.get(int(code), f"<kind {int(code)}>")
