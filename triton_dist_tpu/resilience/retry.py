"""Retry with deterministic exponential backoff for transient failures.

The guard layer (guard.py) answers "can this failure EVER succeed here?" —
a Mosaic compile failure is deterministic, and the
golden XLA path is the cure. This module answers the other question: "was
this failure TRANSIENT?" A watchdog trip (:class:`DistTimeoutError`) is a
timing event — a late peer, comm jitter, one lost signal — and production
fleets absorb those with a bounded retry before declaring anything sick.

Classification reuses the existing classification (docs/resilience.md):

- **transient** — a ``DistTimeoutError`` anywhere in the cause chain.
  Retried under the policy; each failed attempt feeds the elastic layer's
  peer attribution (elastic.py), so retry exhaustion escalates to PE
  quarantine rather than being rediscovered step after step.
- **deterministic** — everything else. Never retried: compile/shape/API
  failures go straight back to the caller, where the existing golden-path
  guard (``guard_op`` / ``guarded_call``) decides on degradation.

Determinism: backoff jitter comes from a PRNG seeded with
``(policy.seed, family)``, so a given op family's retry schedule is
reproducible run-to-run — chaos tests assert the exact sleep sequence.
The clock is injectable (:func:`set_clock`, :class:`FakeClock`) so tests
never actually sleep.

Disabled (``config.retry_policy is None``, the default) this module is
never consulted: op entries keep their pre-existing single-attempt path.
"""

from __future__ import annotations

import contextlib
import dataclasses
import random
import time
from typing import Any, Callable

from triton_dist_tpu.resilience import health
from triton_dist_tpu.resilience.records import DistTimeoutError

# failure classes (the retry-relevant projection of the guard classification)
TRANSIENT = "transient"
DETERMINISTIC = "deterministic"
# detected data corruption (IntegrityError in the chain, ISSUE 8):
# retryable like a transient, but counted SEPARATELY (integrity_retry
# health events) and attributed through note_integrity_exc — a fleet must
# be able to tell comm jitter from data rot
CORRUPT = "corrupt"


def timeout_in_chain(exc: BaseException) -> DistTimeoutError | None:
    """The first :class:`DistTimeoutError` in the cause chain, or None."""
    from triton_dist_tpu.resilience.records import exc_in_chain

    return exc_in_chain(exc, DistTimeoutError)


def classify(exc: BaseException) -> str:
    """TRANSIENT iff a watchdog trip is anywhere in the cause chain (incl.
    wrapped by the autotuner's terminal RuntimeError); CORRUPT iff an
    :class:`~triton_dist_tpu.resilience.integrity.IntegrityError` is (a
    detected corruption — retried under the same policy but counted
    separately); everything else — compile failures, shape errors, missing
    APIs, device faults — is DETERMINISTIC and belongs to the golden-path
    guard, not a retry loop."""
    if timeout_in_chain(exc) is not None:
        return TRANSIENT
    from triton_dist_tpu.resilience.integrity import integrity_in_chain

    if integrity_in_chain(exc) is not None:
        return CORRUPT
    return DETERMINISTIC


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Per-op-entry retry policy (set via ``config.update(retry_policy=...)``).

    max_attempts:    total attempts including the first (1 = no retry).
    base_delay_s:    backoff before the first retry.
    multiplier:      exponential growth factor per retry.
    max_delay_s:     backoff cap.
    jitter:          ± fraction of each backoff step, drawn from a PRNG
                     seeded with ``(seed, family)`` — deterministic per
                     family, decorrelated across families so a fleet of
                     retrying entries doesn't thundering-herd.
    seed:            jitter PRNG seed.
    total_delay_budget_s: optional cap on cumulative backoff; a retry whose
                     delay would exceed it escalates immediately.
    """

    max_attempts: int = 3
    base_delay_s: float = 0.05
    multiplier: float = 2.0
    max_delay_s: float = 2.0
    jitter: float = 0.25
    seed: int = 0
    total_delay_budget_s: float | None = None

    def validate(self) -> "RetryPolicy":
        if self.max_attempts < 1:
            raise ValueError(
                f"RetryPolicy.max_attempts must be >= 1, got {self.max_attempts}"
            )
        if self.base_delay_s < 0 or self.max_delay_s < 0:
            raise ValueError("RetryPolicy delays must be >= 0")
        if self.multiplier < 1.0:
            raise ValueError(
                f"RetryPolicy.multiplier must be >= 1, got {self.multiplier}"
            )
        if not 0.0 <= self.jitter <= 1.0:
            raise ValueError(
                f"RetryPolicy.jitter must be in [0, 1], got {self.jitter}"
            )
        if self.total_delay_budget_s is not None and self.total_delay_budget_s < 0:
            raise ValueError("RetryPolicy.total_delay_budget_s must be >= 0")
        return self

    def delays(self, key: str = "") -> tuple[float, ...]:
        """The backoff before each retry (``max_attempts - 1`` entries):
        ``min(base * multiplier**n, max) * (1 ± jitter)``, jitter drawn from
        ``Random((seed, key))`` — identical for identical (policy, key)."""
        rng = random.Random(f"{self.seed}:{key}")
        out = []
        for n in range(self.max_attempts - 1):
            nominal = min(self.base_delay_s * self.multiplier**n, self.max_delay_s)
            out.append(max(0.0, nominal * (1.0 + self.jitter * rng.uniform(-1, 1))))
        return tuple(out)


# ---------------------------------------------------------------------------
# Injectable clock (tests drive retries with a FakeClock; nothing sleeps)
# ---------------------------------------------------------------------------

class SystemClock:
    monotonic = staticmethod(time.monotonic)
    sleep = staticmethod(time.sleep)


@dataclasses.dataclass
class FakeClock:
    """Deterministic test clock: ``sleep`` advances ``now`` and records the
    requested durations in ``sleeps``."""

    now: float = 0.0
    sleeps: list = dataclasses.field(default_factory=list)

    def monotonic(self) -> float:
        return self.now

    def sleep(self, seconds: float) -> None:
        self.now += seconds
        self.sleeps.append(seconds)


_clock: Any = SystemClock()


def set_clock(clock: Any) -> Any:
    """Swap the module clock (None restores the system clock). Returns the
    previous clock so tests can restore it."""
    global _clock
    prev = _clock
    _clock = clock if clock is not None else SystemClock()
    return prev


def get_clock() -> Any:
    return _clock


@contextlib.contextmanager
def clock_scope(clock: Any):
    """Context manager: install ``clock`` for the scope, restore on exit.
    The serving engine resolves its default clock from this module
    (serving/engine.py), so wrapping a serve loop or a bench sweep in
    ``clock_scope(FakeClock())`` puts backoffs AND serving timestamps on
    one deterministic timeline."""
    prev = set_clock(clock)
    try:
        yield clock
    finally:
        set_clock(prev)


# ---------------------------------------------------------------------------
# The generic exception-driven retry entry (jit_shard_map has its own
# record-driven loop in ops/common.py; both share the policy/clock/health
# plumbing here)
# ---------------------------------------------------------------------------

def call_with_retry(
    family: str,
    fn: Callable[..., Any],
    *args: Any,
    policy: RetryPolicy | None = None,
    clock: Any = None,
    **kwargs: Any,
) -> Any:
    """Run ``fn(*args, **kwargs)``, retrying TRANSIENT failures under
    ``policy`` (default: ``config.retry_policy``; None = single attempt).

    Every transient failure is offered to the elastic layer for peer
    attribution (a no-op unless ``config.elastic``), so strikes accumulate
    across retries and exhaustion lands on an already-quarantined PE. The
    final failure re-raises unchanged; a success after retries records a
    recovery event in the health registry.

    ``fn`` must be re-invokable with the same arguments: a step that
    DONATES its input buffers (``donate_argnums``) deletes them on the
    first attempt and must not be retried in place — re-materialize the
    donated state inside ``fn`` instead (the armed ``jit_shard_map``
    entries enforce this themselves by escalating instead of retrying)."""
    if policy is None:
        from triton_dist_tpu import config as tdt_config

        policy = tdt_config.get_config().retry_policy
    if policy is None:
        return fn(*args, **kwargs)
    clock = clock if clock is not None else _clock
    delays = policy.delays(key=family)
    slept = 0.0
    for attempt in range(policy.max_attempts):
        try:
            out = fn(*args, **kwargs)
        except Exception as exc:  # noqa: BLE001 — classified below
            cls = classify(exc)
            if cls is DETERMINISTIC:
                raise
            from triton_dist_tpu.resilience import elastic

            if cls is TRANSIENT:
                elastic.note_timeout_exc(exc, family=family)
            else:
                # CORRUPT: record + strike the PEs the integrity records
                # name — once per detection (the raise site may already
                # have; integrity.note_detection dedups on the flag)
                from triton_dist_tpu.resilience.integrity import (
                    note_detection,
                )

                note_detection(exc, family=family)
            last = attempt == policy.max_attempts - 1
            delay = 0.0 if last else delays[attempt]
            over_budget = (
                policy.total_delay_budget_s is not None
                and slept + delay > policy.total_delay_budget_s
            )
            if last or over_budget:
                raise
            if cls is TRANSIENT:
                health.record_retry(family, attempt + 1, delay, exc=exc)
            else:
                # corruption counted separately from timeouts (ISSUE 8)
                health.record_integrity_retry(
                    family, attempt + 1, delay, exc=exc
                )
            clock.sleep(delay)
            slept += delay
            continue
        if attempt:
            health.record_recovery(family, attempt)
            # stamp the absorbed retries onto the enclosing op span — the
            # obs layer's ladder-rung record (a no-op unless config.obs)
            from triton_dist_tpu import obs as _obs

            _obs.annotate(retries=attempt, retry_class=cls)
        return out
