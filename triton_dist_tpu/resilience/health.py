"""Process-wide health registry for the resilience layer.

Every graceful degradation (fused kernel → golden XLA collective) and every
watchdog timeout is recorded here, so serving/bench loops can answer "is
this process running the fast path?" without scraping logs — the TPU
analogue of the health surface NCCL watchdog threads give GPU stacks.

The registry is deliberately tiny and dependency-free: a bounded deque of
events plus per-(family, kind) counters behind one lock. Query it from
bench/serving code (``snapshot()``, ``degraded_families()``); reset it
between benchmark phases (``reset()``).
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Any

MAX_EVENTS = 256

# event kinds
DOWNGRADE = "downgrade"       # fused op fell back to the golden XLA path
TIMEOUT = "timeout"           # a watchdogged wait expired (DistTimeoutError)
RETRY = "retry"               # a transient failure was retried with backoff
RECOVERY = "recovery"         # an op entry succeeded after >= 1 retry
PE_QUARANTINE = "pe_quarantine"   # elastic: a peer left the world
PE_READMIT = "pe_readmit"         # elastic: a peer rejoined after probation
SERVING_REBUILD = "serving_rebuild"  # serving engine rebuilt its batcher
                                     # on a new world (shrink or regrow)
INTEGRITY = "integrity"             # corrupt data detected (canary or
                                    # output guard — integrity.py); never
                                    # silently consumed
INTEGRITY_RETRY = "integrity_retry"  # a corruption was retried in place —
                                     # counted SEPARATELY from the timeout
                                     # RETRY events so dashboards can tell
                                     # comm jitter from data rot
SKIP_STEP = "skip_step"             # a non-finite grad step was dropped
                                    # (train_step skip-step containment);
                                    # optimizer state untouched
POISONED = "poisoned"               # serving: one request's logits went
                                    # non-finite; that request was evicted
                                    # and typed-rejected, survivors kept
                                    # streaming (serving/engine.py)
BROWNOUT = "brownout"               # serving overload ladder transition
                                    # (serving/overload.py): normal ⇄
                                    # brownout1 ⇄ brownout2 ⇄
                                    # shed_all_batch, with the dominant
                                    # pressure term as the cause
SHED = "shed"                       # serving: one request load-shed with
                                    # a typed Shed terminal (deadline
                                    # expiry, overflow victim, or
                                    # shed_all_batch) — never a silent
                                    # drop
PREFIX_STRIKE = "prefix_strike"     # serving: a poisoned SHARED prefix
                                    # page struck this reader — evicted
                                    # for a cold re-prefill so corrupt KV
                                    # is never served (prefix_cache.py)
# the disaggregated KV handoff guard ladder (ISSUE 13, serving/handoff.py)
# — each rung attributed like the integrity ladder it mirrors:
HANDOFF_RETRY = "handoff_retry"     # one chunk re-sent in place after a
                                    # canary mismatch / bounded-wait
                                    # timeout (the absorbed-transient
                                    # rung — does not flip is_healthy,
                                    # the RETRY convention)
HANDOFF_RESTREAM = "handoff_restream"  # chunk retries exhausted: the
                                       # whole sequence re-streamed from
                                       # the prefill pool
HANDOFF_FALLBACK = "handoff_fallback"  # re-streams exhausted: the decode
                                       # pool cold-re-prefills locally —
                                       # the request is never lost,
                                       # corrupt KV is never decoded
POOL_COLLAPSE = "pool_collapse"     # a pool lost its last serviceable PE:
                                    # the topology collapsed to the
                                    # unified engine, in-flight work
                                    # replayed (serving/disagg.py)
REPLICA_FAILOVER = "replica_failover"  # fleet (ISSUE 16): a replica was
                                       # declared dead (typed step
                                       # failure or a firing burn-rate
                                       # alert) — its queued + in-flight
                                       # requests re-offered to
                                       # survivors with original SLO
                                       # anchors (serving/fleet.py)
REPLICA_DRAIN = "replica_drain"     # fleet: a replica finished a
                                    # GRACEFUL drain and retired —
                                    # planned maintenance, nothing
                                    # re-offered, informational for
                                    # is_healthy() (the failover twin
                                    # flips; a drain is the machinery
                                    # working on request)
# the ISSUE 17 recovery plane: every rung above the single engine can
# heal, and each healing transition is recorded here (and triggers a
# blackbox bundle — BLACKBOX_KINDS) so operators can audit recoveries
# exactly like failures. None of these flip is_healthy(): recovery is
# the machinery UNDOING a flip, not adding one.
POOL_REGROW = "pool_regrow"         # disagg: a pool's quarantined PE
                                    # passed probation and the pool
                                    # rebuilt at a larger world
                                    # (serving/disagg.py)
POOL_UNCOLLAPSE = "pool_uncollapse"  # disagg: after a clean probation
                                     # window the collapsed topology
                                     # re-carved its prefill pool —
                                     # collapse is no longer one-way
REPLICA_READMIT = "replica_readmit"  # fleet: a dead/drained replica
                                     # passed probation, rebuilt its
                                     # engine, and re-entered placement
                                     # with a cold trie + affinity ramp
                                     # (serving/fleet.py)
ALERT = "alert"                     # an SLO burn-rate rule fired or
                                    # resolved (obs/alerts.py, ISSUE 15)
                                    # — informational for is_healthy():
                                    # the alert PREDICTS the flip, the
                                    # degradation it predicts flips
SPEC_K = "spec_k"                   # serving: the speculative batcher's
                                    # adaptive-k moved (ISSUE 20) —
                                    # informational for is_healthy():
                                    # every emitted token is still
                                    # verified by the target, k backoff
                                    # is tuning, not degradation (the
                                    # SHED_SPEC brownout rung that drops
                                    # speculation outright records as
                                    # BROWNOUT like every ladder move)

# the kinds that flip is_healthy(): each one means some work was NOT
# done on the fast clean path (the flight recorder's burn-rate alerts
# count these as "flips" — obs/alerts.py health_flip_rate)
FLIP_KINDS = (DOWNGRADE, TIMEOUT, PE_QUARANTINE, INTEGRITY, SKIP_STEP,
              POISONED, BROWNOUT, SHED, HANDOFF_RESTREAM,
              HANDOFF_FALLBACK, POOL_COLLAPSE, REPLICA_FAILOVER)

# short-circuit pin kinds (why a family is pinned to its golden path)
PIN_QUARANTINE = "quarantine"  # watchdog trip: device semaphore residue


@dataclasses.dataclass(frozen=True)
class HealthEvent:
    kind: str               # DOWNGRADE or TIMEOUT
    family: str             # kernel family / op entry name
    reason: str             # human-readable cause
    detail: Any = None      # decoded diag records / exception repr
    walltime: float = 0.0   # time.time() at record


_lock = threading.Lock()
_events: collections.deque[HealthEvent] = collections.deque(maxlen=MAX_EVENTS)
_counters: dict[tuple[str, str], int] = {}
_total_dropped = 0
# WHAT was evicted, not just how much: a deque past MAX_EVENTS keeps the
# newest 256, and without kind attribution a storm of retries could
# silently push the one integrity event out of the window — the total
# alone can't tell an operator whether the lost detail mattered
# (per-(family, kind) counters are never dropped; only event DETAIL is)
_dropped_by_kind: dict[str, int] = {}
# families guarded_call serves straight from the golden path without
# retrying the fused one: {family: (reason, pin_kind)}. The way in is a
# watchdog quarantine (PIN_QUARANTINE: after a timeout the family's
# collective semaphore state is undefined; reusing it could silently
# corrupt the next launch); the elastic layer releases these in interpret
# mode (elastic.py).
_short_circuit: dict[str, tuple[str, str]] = {}


def record_downgrade(family: str, reason: str, exc: BaseException | None = None) -> None:
    _record(HealthEvent(
        kind=DOWNGRADE, family=family, reason=reason,
        detail=None if exc is None else f"{type(exc).__name__}: {exc}",
        walltime=time.time(),
    ))


def record_timeout(family: str, records: list[dict]) -> None:
    _record(HealthEvent(
        kind=TIMEOUT, family=family,
        reason=f"watchdog expired on {len(records)} PE(s)",
        detail=records, walltime=time.time(),
    ))
    # quarantine regardless of raise posture: the family's persistent
    # collective semaphore may hold residue (a straggler signal landing
    # after the in-kernel drain); relaunching the fused kernel on it could
    # pass a wait early and silently serve stale buffers. jit_shard_map
    # refuses quarantined launches; guarded entries serve the golden path.
    short_circuit(family, "quarantined after watchdog timeout",
                  kind=PIN_QUARANTINE)


def record_retry(
    family: str, attempt: int, delay_s: float, records: Any = None,
    exc: BaseException | None = None,
) -> None:
    """One transient failure absorbed by the retry layer (retry.py)."""
    _record(HealthEvent(
        kind=RETRY, family=family,
        reason=f"transient failure; retry {attempt} after {delay_s:.3g}s",
        detail=records if records is not None
        else (None if exc is None else f"{type(exc).__name__}: {exc}"),
        walltime=time.time(),
    ))


def record_recovery(family: str, retries: int) -> None:
    """An op entry succeeded after ``retries`` retried attempts."""
    _record(HealthEvent(
        kind=RECOVERY, family=family,
        reason=f"recovered after {retries} retry(ies)",
        walltime=time.time(),
    ))


def record_integrity(family: str, exc: BaseException | None = None,
                     records: Any = None, reason: str | None = None) -> None:
    """Corrupt data detected by the integrity layer (integrity.py): a
    canary mismatch, a non-finite output, or an envelope violation."""
    _record(HealthEvent(
        kind=INTEGRITY, family=family,
        reason=reason or (
            f"{getattr(exc, 'detector', 'corruption')} check tripped"
            if exc is not None else "corruption detected"
        ),
        detail=records if records is not None
        else (None if exc is None else f"{type(exc).__name__}: {exc}"),
        walltime=time.time(),
    ))


def record_integrity_retry(
    family: str, attempt: int, delay_s: float,
    exc: BaseException | None = None,
) -> None:
    """One corruption absorbed by the bounded integrity-retry rung —
    a separate counter from the timeout retries (integrity.py ladder)."""
    _record(HealthEvent(
        kind=INTEGRITY_RETRY, family=family,
        reason=f"corrupt output; retry {attempt} after {delay_s:.3g}s",
        detail=None if exc is None else f"{type(exc).__name__}: {exc}",
        walltime=time.time(),
    ))


def record_skip_step(family: str) -> None:
    """A non-finite gradient step was dropped (optimizer state untouched)
    — train_step's skip-step containment (integrity.py)."""
    _record(HealthEvent(
        kind=SKIP_STEP, family=family,
        reason="non-finite grads; step dropped, optimizer state untouched",
        walltime=time.time(),
    ))


def record_poisoned_request(family: str, uid: Any, reason: str) -> None:
    """The serving engine evicted + typed-rejected one poisoned request
    (serving/engine.py per-request quarantine)."""
    _record(HealthEvent(
        kind=POISONED, family=family,
        reason=f"request {uid!r}: {reason}", walltime=time.time(),
    ))


def record_prefix_strike(family: str, uid: Any, reason: str) -> None:
    """A poisoned shared prefix page struck reader ``uid`` — it was
    evicted and resubmitted for a cold re-prefill (ISSUE 12 fan-out).
    Informational for :func:`is_healthy` purposes: the POISONED event
    that caused the strike already flipped it (the SERVING_REBUILD
    rationale)."""
    _record(HealthEvent(
        kind=PREFIX_STRIKE, family=family,
        reason=f"request {uid!r}: {reason}", walltime=time.time(),
    ))


def record_brownout(family: str, frm: str, to: str, *, pressure: float,
                    cause: str) -> None:
    """One overload-ladder transition (serving/overload.py), with the
    dominant pressure term (queue / drain / slo) as the attributed
    cause."""
    _record(HealthEvent(
        kind=BROWNOUT, family=family,
        reason=f"{frm} -> {to} (pressure={pressure:.3f}, cause={cause})",
        walltime=time.time(),
    ))


def record_spec_k(family: str, frm: int, to: int, *, alpha: float) -> None:
    """One adaptive-k move of the speculative serving batcher
    (serving/speculative.py), with the windowed acceptance rate that
    triggered it. Informational — SPEC_K never flips is_healthy()."""
    _record(HealthEvent(
        kind=SPEC_K, family=family,
        reason=f"k {frm} -> {to} (alpha={alpha:.3f})",
        walltime=time.time(),
    ))


def record_shed(family: str, uid: Any, priority: str, reason: str) -> None:
    """One request load-shed by the overload controller — typed terminal,
    counted here so fleet dashboards see shed volume next to timeouts and
    corruption (the deque may drop old DETAIL under a shed storm; the
    per-(family, kind) counter never does)."""
    _record(HealthEvent(
        kind=SHED, family=family,
        reason=f"request {uid!r} [{priority}]: {reason}",
        walltime=time.time(),
    ))


def record_handoff_retry(family: str, uid: Any, chunk: int, pe: int,
                         reason: str) -> None:
    """One KV-handoff chunk re-sent in place (the first ladder rung,
    serving/handoff.py): ``pe`` is the attributed culprit — the decode
    PE whose landing failed its canary (victim == culprit), or the
    prefill sender whose chunk signal never arrived (by absence)."""
    _record(HealthEvent(
        kind=HANDOFF_RETRY, family=family,
        reason=f"request {uid!r} chunk {chunk} (pe{int(pe)}): {reason}",
        walltime=time.time(),
    ))


def record_handoff_restream(family: str, uid: Any, pe: int,
                            reason: str) -> None:
    """Chunk retries exhausted: the whole sequence re-streams from the
    prefill pool (rung 2 of the handoff ladder)."""
    _record(HealthEvent(
        kind=HANDOFF_RESTREAM, family=family,
        reason=f"request {uid!r} (pe{int(pe)}): {reason}",
        walltime=time.time(),
    ))


def record_handoff_fallback(family: str, uid: Any, reason: str) -> None:
    """Re-streams exhausted: the decode pool cold-re-prefills locally
    (the terminal rung — the request is never lost)."""
    _record(HealthEvent(
        kind=HANDOFF_FALLBACK, family=family,
        reason=f"request {uid!r}: {reason}", walltime=time.time(),
    ))


def record_pool_collapse(family: str, pool: str, reason: str) -> None:
    """A serving pool lost its last serviceable PE and the disaggregated
    topology collapsed to the unified engine (serving/disagg.py)."""
    _record(HealthEvent(
        kind=POOL_COLLAPSE, family=family,
        reason=f"pool {pool!r}: {reason}", walltime=time.time(),
    ))


def record_replica_failover(family: str, replica: str, reason: str, *,
                            reoffered: int) -> None:
    """The fleet router declared replica ``replica`` dead and re-offered
    its ``reoffered`` queued + in-flight requests to survivors with their
    original arrival/deadline anchors (serving/fleet.py, ISSUE 16). The
    replica id rides ``detail`` so incident bundles name it."""
    _record(HealthEvent(
        kind=REPLICA_FAILOVER, family=family,
        reason=f"replica {replica!r}: {reason}",
        detail={"replica": replica, "reoffered": int(reoffered)},
        walltime=time.time(),
    ))


def record_replica_drain(family: str, replica: str) -> None:
    """Replica ``replica`` finished a graceful drain and retired —
    planned maintenance (the failover twin that loses nothing and flips
    nothing)."""
    _record(HealthEvent(
        kind=REPLICA_DRAIN, family=family,
        reason=f"replica {replica!r}: drained and retired",
        detail={"replica": replica}, walltime=time.time(),
    ))


def record_alert(family: str, rule: str, state: str, *, signal: str,
                 fast: float, slow: float) -> None:
    """One SLO burn-rate rule transition (obs/alerts.py, ISSUE 15):
    ``state`` is "firing" or "resolved", ``fast``/``slow`` the window
    values at the transition. Informational for :func:`is_healthy` —
    the alert PREDICTS a flip; the degradation it predicts flips."""
    _record(HealthEvent(
        kind=ALERT, family=family,
        reason=f"rule {rule} [{signal}] {state} "
               f"(fast={fast:.4g}, slow={slow:.4g})",
        walltime=time.time(),
    ))


def _pe_family(pe: int, owner: "str | None") -> str:
    """The health family of one PE's elastic events: ``pe{N}`` in the
    process-global default scope (the pre-ISSUE-17 name, byte-unchanged),
    ``pe{N}@{owner}`` in an owned :class:`ElasticScope` — so counters
    alone prove which namespace a strike landed in (the fleet soak's
    scope-isolation invariant)."""
    base = f"pe{int(pe)}"
    return base if owner is None else f"{base}@{owner}"


def record_pe_quarantine(pe: int, reason: str,
                         owner: "str | None" = None) -> None:
    """The elastic layer quarantined peer ``pe`` (elastic.py), in the
    scope named by ``owner`` (None = the default scope)."""
    _record(HealthEvent(
        kind=PE_QUARANTINE, family=_pe_family(pe, owner), reason=reason,
        walltime=time.time(),
    ))


def record_pe_readmission(pe: int, owner: "str | None" = None) -> None:
    """Peer ``pe`` passed probation and rejoined the world."""
    _record(HealthEvent(
        kind=PE_READMIT, family=_pe_family(pe, owner),
        reason="clean probation probe(s); re-admitted",
        walltime=time.time(),
    ))


def record_pool_regrow(family: str, pool: str, world: int,
                       pes: "list[int] | tuple[int, ...]" = ()) -> None:
    """A disagg pool's quarantined PE(s) passed probation and the pool
    rebuilt at ``world`` PEs (serving/disagg.py, ISSUE 17). Informational
    for :func:`is_healthy` — the quarantine that shrank the pool already
    flipped it; the regrow is the recovery plane working."""
    _record(HealthEvent(
        kind=POOL_REGROW, family=family,
        reason=f"pool {pool!r}: re-admitted pe(s) "
               f"{sorted(int(p) for p in pes)}; regrown to world={int(world)}",
        detail={"pool": pool, "world": int(world),
                "pes": [int(p) for p in pes]},
        walltime=time.time(),
    ))


def record_pool_uncollapse(family: str, pool: str, reason: str) -> None:
    """The collapsed disagg topology re-carved pool ``pool`` after a
    clean probation window (serving/disagg.py, ISSUE 17) — the reverse
    arc of :func:`record_pool_collapse`. Informational for
    :func:`is_healthy` (the collapse flipped; this is the undo)."""
    _record(HealthEvent(
        kind=POOL_UNCOLLAPSE, family=family,
        reason=f"pool {pool!r}: {reason}", walltime=time.time(),
    ))


def record_replica_readmit(family: str, replica: str, reason: str, *,
                           world: int) -> None:
    """The fleet router resurrected replica ``replica``: clean probation
    probes, a fresh ``world``-PE engine build, and re-entry into
    placement with a cold trie + affinity ramp (serving/fleet.py, ISSUE
    17). The failover/drain that removed it flipped health; the
    resurrection is informational."""
    _record(HealthEvent(
        kind=REPLICA_READMIT, family=family,
        reason=f"replica {replica!r}: {reason}",
        detail={"replica": replica, "world": int(world)},
        walltime=time.time(),
    ))


def record_serving_rebuild(family: str, world: int, reason: str) -> None:
    """The serving engine rebuilt its batcher on a ``world``-PE mesh
    (serving/engine.py: elastic shrink or probation regrow, with every
    in-flight request prefix-replayed). Informational — a rebuild is the
    degraded-mode machinery WORKING, so it does not flip
    :func:`is_healthy` (the quarantine that caused a shrink already
    did)."""
    _record(HealthEvent(
        kind=SERVING_REBUILD, family=family,
        reason=f"world={int(world)}: {reason}", walltime=time.time(),
    ))


def _record(ev: HealthEvent) -> None:
    global _total_dropped
    with _lock:
        if len(_events) == _events.maxlen:
            _total_dropped += 1
            oldest = _events[0]
            _dropped_by_kind[oldest.kind] = (
                _dropped_by_kind.get(oldest.kind, 0) + 1
            )
        _events.append(ev)
        key = (ev.family, ev.kind)
        _counters[key] = _counters.get(key, 0) + 1
    # the flight-recorder fan-out (ISSUE 15) runs OUTSIDE the lock: the
    # metrics plane mirrors every event as a labeled counter, and a
    # health-FLIPPING event freezes a post-mortem bundle — whose capture
    # reads this registry and elastic.summary() (lock re-entry)
    _publish(ev)


def _publish(ev: HealthEvent) -> None:
    """Mirror one event into the obs metrics plane and offer it to the
    black box (both no-ops when disarmed — the pre-metrics posture).
    Lazy import: obs pulls this module in through its exporters."""
    from triton_dist_tpu.obs import blackbox as _blackbox
    from triton_dist_tpu.obs import metrics as _metrics

    _metrics.counter("health_events_total", kind=ev.kind, family=ev.family)
    _blackbox.on_health_event(ev)


def events(kind: str | None = None) -> list[HealthEvent]:
    with _lock:
        return [e for e in _events if kind is None or e.kind == kind]


def counters() -> dict[tuple[str, str], int]:
    with _lock:
        return dict(_counters)


def degraded_families() -> set[str]:
    """Families that have taken the golden-XLA fallback at least once."""
    with _lock:
        return {f for (f, k), n in _counters.items() if k == DOWNGRADE and n > 0}


def timed_out_families() -> set[str]:
    with _lock:
        return {f for (f, k), n in _counters.items() if k == TIMEOUT and n > 0}


def retried_families() -> set[str]:
    """Families that have absorbed at least one transient retry."""
    with _lock:
        return {f for (f, k), n in _counters.items() if k == RETRY and n > 0}


def is_healthy() -> bool:
    """True iff no downgrade, timeout, or corruption has been recorded
    since reset(). Retries/recoveries alone don't flip this — an absorbed
    transient is the system working — but quarantines, unrecovered
    timeouts, detected corruption, dropped train steps, poisoned serving
    requests, overload brownouts, and load sheds do: they all mean some
    work was NOT done on the fast clean path (a shed/brownout is the
    overload machinery working AS DESIGNED, but an operator still needs
    one bit that says "this process refused or degraded work"). The
    flipping kind set IS :data:`FLIP_KINDS` — also the burn-rate alerts'
    ``health_flip_rate`` feed (obs/alerts.py via :func:`flip_total`).
    The black box triggers on its OWN narrower ``BLACKBOX_KINDS`` subset
    (plus the informational ``prefix_strike``) — a shed storm must not
    write a bundle per shed."""
    with _lock:
        return not any(
            k in FLIP_KINDS for (_, k), n in _counters.items() if n > 0
        )


def flip_total() -> int:
    """Total health-FLIPPING events recorded since reset() — the
    cumulative feed of the ``health_flip_rate`` burn-rate signal
    (obs/alerts.py derives per-window deltas from it)."""
    with _lock:
        return sum(n for (_, k), n in _counters.items() if k in FLIP_KINDS)


def corrupt_families() -> set[str]:
    """Families with at least one detected-corruption event."""
    with _lock:
        return {f for (f, k), n in _counters.items()
                if k == INTEGRITY and n > 0}


def snapshot() -> dict:
    """One JSON-able view for bench/serving logs."""
    with _lock:
        snap = {
            "healthy": True,
            "counters": {f"{f}:{k}": n for (f, k), n in sorted(_counters.items())},
            "short_circuited": {f: r for f, (r, _) in _short_circuit.items()},
            # no silent caps (ISSUE 9 satellite): the bounded deque's
            # evictions are counted AND attributed by kind — emitted via
            # obs.snapshot() with the rest of the snapshot
            "dropped_events": _total_dropped,
            "dropped_by_kind": dict(sorted(_dropped_by_kind.items())),
            "last_events": [
                {
                    "kind": e.kind, "family": e.family, "reason": e.reason,
                    "detail": e.detail, "walltime": e.walltime,
                }
                for e in list(_events)[-8:]
            ],
        }
    snap["healthy"] = is_healthy()
    # the elastic layer's peer states ride along so one snapshot answers
    # "is this process fast AND whole?" (lazy import: elastic imports us)
    from triton_dist_tpu.resilience import elastic

    snap["elastic"] = elastic.summary()
    return snap


def short_circuit(family: str, reason: str, kind: str = PIN_QUARANTINE) -> None:
    """Pin ``family`` to its golden path for the rest of the process (or
    until :func:`reset` / :func:`clear_short_circuit`)."""
    with _lock:
        _short_circuit.setdefault(family, (reason, kind))


def short_circuited(family: str) -> str | None:
    """The reason ``family`` is pinned to its golden path, or None."""
    with _lock:
        pin = _short_circuit.get(family)
        return pin[0] if pin is not None else None


def clear_short_circuit(family: str) -> None:
    """Release one family's golden-path pin. Callers own the safety
    argument (the elastic layer clears quarantine pins in interpret mode,
    where simulated semaphores cannot hold residue; probes clear their own
    family so recovery is never refused)."""
    with _lock:
        _short_circuit.pop(family, None)


def clear_timeout_quarantines() -> None:
    """Release every PIN_QUARANTINE pin (interpret-mode recovery: the
    elastic layer excised or re-admitted the culprit PE and simulated
    semaphores are rebuilt per launch)."""
    with _lock:
        for f in [f for f, (_, k) in _short_circuit.items()
                  if k == PIN_QUARANTINE]:
            del _short_circuit[f]


def reset(*, keep_short_circuit: bool = False) -> None:
    """Clear the statistics. ``keep_short_circuit=True`` preserves ALL
    golden-path pins — use it when resetting between phases of one process
    (bench): clearing a Python dict does not clean a quarantined family's
    device semaphore, so re-enabling its fused kernel would risk exactly
    the silent corruption the quarantine exists to prevent."""
    global _total_dropped
    with _lock:
        _events.clear()
        _counters.clear()
        if not keep_short_circuit:
            _short_circuit.clear()
        _total_dropped = 0
        _dropped_by_kind.clear()
