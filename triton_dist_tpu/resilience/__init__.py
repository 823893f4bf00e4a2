"""Resilience subsystem: watchdogged waits, fault injection, graceful
fallback to XLA collectives, and elastic degraded-mode execution.

Six parts (see docs/resilience.md for the full contract):

- :mod:`watchdog` / :mod:`records` — bounded distributed waits that write a
  structured diagnostic record and NaN-poison outputs instead of spinning
  forever; surfaced host-side as :class:`DistTimeoutError`.
  Arm with ``config.update(timeout_iters=N)``.
- :mod:`faults` — deterministic interpret-mode signal chaos
  (drop/duplicate/delay a signal, straggle a PE; ``max_triggers`` bounds a
  plan to model transient vs persistent faults).
  Arm with ``config.update(fault_plan=FaultPlan(...))``.
- :mod:`guard` / :mod:`health` — ``guarded_call`` degrades a failing fused
  op to its golden ``jax.lax`` collective and records the downgrade in the
  process-wide health registry. On by default
  (``config.update(fallback_to_xla=False)`` for the loud CI posture).
- :mod:`retry` — transient failures (watchdog trips) retried with
  deterministic exponential backoff before escalating; deterministic
  failures keep going straight to the guard.
  Arm with ``config.update(retry_policy=RetryPolicy(...))``.
- :mod:`elastic` — PE state machine (healthy → suspect → quarantined →
  probation → healthy): persistent stragglers are quarantined, the
  topology is rebuilt over the survivors (``elastic.effective_mesh``),
  and recovered PEs are probed back in.
  Arm with ``config.update(elastic=True)``.
- :mod:`integrity` — the data-integrity layer (ISSUE 8): payload
  corruption detection (per-chunk canaries on the chunked puts, output
  guards at every guarded op entry), the detect → retry → golden-fallback
  recovery ladder with corruption counted separately from timeouts, and
  the containment hooks above the ops (train-step skip, serving
  per-request poison quarantine).
  Arm with ``config.update(integrity=IntegrityConfig(...))``.
"""

from triton_dist_tpu.resilience import elastic as elastic
from triton_dist_tpu.resilience import health as health
from triton_dist_tpu.resilience import integrity as integrity
from triton_dist_tpu.resilience import retry as retry
from triton_dist_tpu.resilience import sites as sites
from triton_dist_tpu.resilience import watchdog as watchdog
from triton_dist_tpu.resilience.faults import (
    KINDS as FAULT_KINDS,
    PAYLOAD_KINDS as PAYLOAD_FAULT_KINDS,
    FaultPlan,
)
from triton_dist_tpu.resilience.integrity import (
    IntegrityConfig,
    IntegrityError,
)
from triton_dist_tpu.resilience.guard import (
    UnsupportedTopologyError,
    fallbackable,
    golden_path,
    guard_op,
    guarded_call,
)
from triton_dist_tpu.resilience.records import (
    DIAG_LEN,
    DistTimeoutError,
    decode_diag,
    decode_record,
    family_code_for,
    family_name_for,
)
from triton_dist_tpu.resilience.retry import (
    FakeClock,
    RetryPolicy,
    call_with_retry,
    classify,
)


def reset() -> None:
    """Clear all process-global resilience state — health statistics and
    pins, elastic peer states, and fault-plan trigger counts — between
    tests or benchmark phases."""
    from triton_dist_tpu.resilience import faults as _faults

    health.reset()
    elastic.reset()
    _faults.reset_triggers()


__all__ = [
    "DIAG_LEN",
    "DistTimeoutError",
    "FAULT_KINDS",
    "FakeClock",
    "FaultPlan",
    "IntegrityConfig",
    "IntegrityError",
    "PAYLOAD_FAULT_KINDS",
    "RetryPolicy",
    "UnsupportedTopologyError",
    "call_with_retry",
    "classify",
    "decode_diag",
    "decode_record",
    "elastic",
    "fallbackable",
    "family_code_for",
    "family_name_for",
    "golden_path",
    "guard_op",
    "guarded_call",
    "health",
    "integrity",
    "reset",
    "retry",
    "watchdog",
]
