"""Graceful degradation: guarded fused-op calls with golden XLA fallback.

Every fused distributed op in this framework has a mathematically identical
golden path built from ``jax.lax`` collectives (the same goldens the test
suite asserts against). :func:`guarded_call` runs the fused path and, when
it fails for an ENVIRONMENTAL reason — a Mosaic compile failure, an
unsupported topology — records the
downgrade in :mod:`triton_dist_tpu.resilience.health` and returns the
golden result instead, so a serving step degrades to a correct slow path
rather than taking the process down (the collective-fallback discipline
NCCL-era stacks get from their watchdog/abort machinery).

What does NOT fall back:

- user errors (bad shapes/dtypes/arguments): assertion/Value/Type errors
  raised by our own host-side validation re-raise unchanged;
- :class:`DistTimeoutError`: a runtime watchdog trip is a peer-loss event,
  not a compile problem — retrying the same step on the slow path would
  mask a sick fleet; it propagates (the health registry records it);
- anything raised by the fallback itself.

Set ``config.update(fallback_to_xla=False)`` to make every failure loud
(the posture of CI and of everything that measures: ``chip_smoke.py``,
``perfbench/``, ``scripts/``); the default is to degrade (serving posture).

:func:`golden_path` is the EXPLICIT way to run the goldens: inside the
scope every guarded entry serves its XLA twin directly — no failure, no
downgrade record — so a fused run can be compared with its unfused twin
through the same entry points (``chip_smoke.py --chips 4``).
"""

from __future__ import annotations

import contextlib
import functools
import re
import threading
from typing import Any, Callable

from triton_dist_tpu.resilience import health
from triton_dist_tpu.resilience.records import DistTimeoutError

_tls = threading.local()


def _guard_depth() -> int:
    return getattr(_tls, "depth", 0)


class UnsupportedTopologyError(NotImplementedError):
    """The fused kernel cannot serve this mesh/topology (e.g. an axis with
    no ICI path). Always eligible for the golden-XLA fallback."""


# Compile-layer failures carry these markers (Mosaic lowering, scoped-vmem
# rejection, Pallas lowering, collective-id exhaustion) — matched against
# the exception text because jax raises them as several concrete types
# across versions. Deliberately NO catch-all for XlaRuntimeError: a
# runtime/device fault (INTERNAL, HBM OOM at dispatch) is not an
# environmental failure the golden path cures — masking a dying chip as a
# quiet downgrade is exactly what this module's contract forbids.
_COMPILE_MARKERS = re.compile(
    r"mosaic|mlir|vmem|scoped|pallas|collective_id"
    r"|lowering|Unsupported|not supported|not implemented"
    # the autotuner's terminal failure: every candidate config failed — on a
    # healthy install that means the problem/topology fits no fused config
    r"|every candidate config failed",
    re.IGNORECASE,
)
def _timeout_in_chain(exc: BaseException) -> bool:
    """A DistTimeoutError anywhere in the cause chain (e.g. wrapped by the
    autotuner's terminal RuntimeError)."""
    from triton_dist_tpu.resilience.records import exc_in_chain

    return exc_in_chain(exc, DistTimeoutError) is not None


def fallbackable(exc: BaseException) -> bool:
    """Is this exception an environmental failure the golden path cures?"""
    # a watchdog trip is a peer-loss event: never cured by the slow path,
    # must stay loud (quarantine handles subsequent calls)
    if _timeout_in_chain(exc):
        return False
    if isinstance(exc, NotImplementedError):  # incl. UnsupportedTopologyError
        return True
    mod = type(exc).__module__ or ""
    if mod.startswith(("jaxlib", "jax.")) or mod == "jax":
        # compile/lowering-layer failures only; a genuine runtime/device
        # fault must stay loud (see _COMPILE_MARKERS note)
        return bool(_COMPILE_MARKERS.search(str(exc)))
    if isinstance(exc, RuntimeError) and _COMPILE_MARKERS.search(str(exc)):
        return True
    return False


_golden_depth = 0


def golden_active() -> bool:
    """Inside a :func:`golden_path` scope (part of ``jit_shard_map``'s
    program key: a cached fused program must not serve a golden run)."""
    return _golden_depth > 0


@contextlib.contextmanager
def golden_path():
    """Serve every guarded entry's XLA-collective golden EXPLICITLY while
    the scope is open (tracing happens at first call, so keep the scope
    open around the calls, not only around construction). An entry with
    no golden (quantized caches) raises rather than run fused."""
    global _golden_depth
    _golden_depth += 1
    try:
        yield
    finally:
        _golden_depth -= 1


def guarded_call(
    family: str,
    primary: Callable[..., Any],
    fallback: Callable[..., Any] | None,
    *args: Any,
    **kwargs: Any,
) -> Any:
    """Run ``primary(*args, **kwargs)``; on a :func:`fallbackable` failure
    record the downgrade and return ``fallback(*args, **kwargs)``.

    ``fallback=None`` means this configuration has no golden path (e.g.
    int8-quantized caches) — the failure re-raises unchanged.

    Nested under an OUTER guard (the ``guard_op`` entries wrap the
    autotuner, whose candidates trace the shard-level guarded functions),
    the inner fallback is suppressed: failures propagate so the sweep
    prices failing candidates honestly and only the outermost entry
    degrades — otherwise every candidate would silently degrade to an
    identical XLA program and the tuner would persist a meaningless
    "best" config. Direct shard-level calls (a user's own ``shard_map``)
    have no outer guard and keep their fallback."""
    return _guarded(family, primary, fallback, args, kwargs)


def _guarded(family, primary, fallback, args, kwargs):
    from triton_dist_tpu import obs as _obs

    # observability (ISSUE 9): one span per OUTERMOST guarded entry,
    # recording which ladder rung actually served the call (fused /
    # golden_pinned / golden_fallback / integrity / timeout). Nested
    # guard levels stay span-free — the op-entry span is the unit a
    # timeline reader cares about; disarmed this is one attribute read.
    if _guard_depth() > 0 or not _obs.span_enabled():
        return _guarded_impl(family, primary, fallback, args, kwargs,
                             sp=_obs.NULL_SPAN)
    with _obs.span(f"op:{family}", cat="op") as sp:
        return _guarded_impl(family, primary, fallback, args, kwargs, sp=sp)


def _guarded_impl(family, primary, fallback, args, kwargs, *, sp):
    from triton_dist_tpu import config as tdt_config
    from triton_dist_tpu.resilience import integrity as _integrity

    # output-integrity guards (ISSUE 8): finite check + magnitude envelope
    # on every outermost guarded entry when config.integrity arms them —
    # read-only, so the happy path stays bit-exact. Canary IntegrityErrors
    # raised inside the fused path (jit_shard_map) take the same ladder.
    checking = _guard_depth() == 0 and _integrity.output_checks_enabled()

    if golden_active():
        if fallback is None:
            raise NotImplementedError(
                f"{family} has no golden path to serve under golden_path()"
            )
        sp.set("rung", "golden_explicit")
        return fallback(*args, **kwargs)
    if fallback is None or not tdt_config.get_config().fallback_to_xla:
        # no golden rung / loud CI posture: detection still runs, loudly
        sp.set("rung", "fused")
        out = primary(*args, **kwargs)
        if checking:
            _integrity.check_result(family, out)
        return out
    if _guard_depth() > 0:
        return primary(*args, **kwargs)
    if health.short_circuited(family) is not None:
        # pinned to the golden path: a watchdog trip left the family's
        # collective semaphore state undefined (quarantine; see
        # docs/resilience.md).
        # Recorded once at pin time — not per call, to keep the event deque
        # and counters meaningful.
        sp.set("rung", "golden_pinned")
        out = fallback(*args, **kwargs)
        if checking:
            _integrity.check_result(family, out, source="golden")
        return out

    def run_primary():
        _tls.depth = _guard_depth() + 1
        try:
            out = primary(*args, **kwargs)
        finally:
            _tls.depth -= 1
        if checking:
            _integrity.check_result(family, out)
        return out

    try:
        sp.set("rung", "fused")
        return run_primary()
    except Exception as exc:  # noqa: BLE001 — filtered by fallbackable()
        if _integrity.integrity_in_chain(exc) is not None:
            sp.set("rung", "integrity")
            # the corruption ladder (resilience/integrity.py): detect →
            # bounded retry (counted separately from timeouts) → golden
            # fallback (checked too) — while every detection's records
            # strike the named PE toward quarantine. No family pin: a
            # canary drains its own credits, so unlike a watchdog trip a
            # corruption leaves no semaphore residue to protect against.
            try:
                return _integrity.recover(
                    family, run_primary, lambda: fallback(*args, **kwargs),
                    exc, fallback_allowed=True,
                )
            except Exception as ladder_exc:  # noqa: BLE001 — see below
                # timeout precedence (retry.classify's rule): anything
                # raised inside the ladder implicitly chains the original
                # IntegrityError as __context__, so "integrity in chain"
                # alone cannot distinguish a mid-ladder watchdog trip
                if (not _timeout_in_chain(ladder_exc)
                        and _integrity.integrity_in_chain(ladder_exc)
                        is not None):
                    raise
                # a NON-integrity failure surfaced mid-ladder (e.g. a
                # watchdog trip on a retry attempt): hand it to the SAME
                # classification a first-attempt failure gets — timeouts
                # quarantine-pin the family and stay loud, environmental
                # failures degrade to the golden path
                exc = ladder_exc
        if not fallbackable(exc):
            sp.set("rung", "error")
            sp.set("error", type(exc).__name__)
            if _timeout_in_chain(exc):
                sp.set("rung", "timeout")
                # the trip itself stays loud (this raise); LATER calls of
                # this family serve the golden path — its barrier semaphore
                # may hold residue (partially-drained credits, a late
                # straggler signal), and reusing it could pass a wait early
                # and silently serve last-step buffers
                health.short_circuit(
                    family, "quarantined after watchdog timeout"
                )
                # elastic interpret-mode runs release the pin straight
                # away: the world is about to shrink around the culprit
                # PE and simulated semaphores cannot hold residue
                from triton_dist_tpu.resilience import elastic

                elastic.maybe_release_family_pins()
            # explicit `raise exc`, not bare raise: after the mid-ladder
            # fall-through above, `exc` is the ladder's failure while the
            # exception "currently being handled" is still the original
            # IntegrityError — a bare raise would resurrect the wrong one
            raise exc
        health.record_downgrade(
            family,
            reason="fused path failed; served golden XLA collective path",
            exc=exc,
        )
        sp.set("rung", "golden_fallback")
        sp.set("cause", type(exc).__name__)
        return fallback(*args, **kwargs)


def guard_op(family: str, golden: Callable[..., Any] | None):
    """Decorator form of :func:`guarded_call` for the host-level ``*_op``
    entries: the decorated fused entry runs under the guard with ``golden``
    (same signature, extra kwargs ignored) as its XLA fallback. Applied
    OUTSIDE ``contextual_autotune`` so the sweep still prices failing
    candidates by falling through them — only a failure of the whole tuned
    entry (every candidate dead, or an explicit config that cannot serve
    this environment) degrades to the golden path."""

    def deco(fused: Callable[..., Any]) -> Callable[..., Any]:
        @functools.wraps(fused)
        def entry(*args: Any, **kwargs: Any) -> Any:
            return _guarded(family, fused, golden, args, kwargs)

        entry.__wrapped_fused__ = fused
        entry.__golden__ = golden
        return entry

    return deco
