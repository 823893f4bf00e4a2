"""Elastic degraded-mode execution: PE quarantine and topology shrink.

The retry layer (retry.py) absorbs *transient* timeouts; this module
absorbs *persistent* ones. Watchdog diagnostic records are attributed to a
peer, strikes accumulate through a per-PE state machine, and a PE that
keeps costing timeouts is quarantined: the collective topology is rebuilt
over the survivors (``effective_mesh`` → ``parallel.mesh.shrink_mesh`` /
``parallel.topology.surviving_ring``) so every op family keeps producing
mathematically correct results at reduced parallelism. Quarantined PEs are
probed with a cheap world barrier and re-admitted after a clean probation.

PE state machine (one ``PeerHealth`` per flattened device index of the
governing world mesh)::

    healthy --timeout--> suspect --timeouts >= suspect_threshold--> quarantined
      ^  ^                  |                                          |
      |  +---strikes decay--+                              probe (probation)
      |                                                        |         |
      +---- clean probes >= probation_probes ---- probation <--+    failed probe
                                                      |                  |
                                                      +---> quarantined <+

Attribution: on TPU the kernel that times out is the *victim*, not the
culprit — the straggler is busy spinning (or its signal was dropped) while
everyone else's bounded wait expires. So the per-PE diagnostic records
name the culprit by absence: when every surviving PE but one reports a
timeout, the silent one is the straggler. Ambiguous patterns (all PEs
tripped, several silent) attribute nothing — quarantining the wrong PE is
strictly worse than staying degraded-but-correct.

Scoped namespaces (the ISSUE 17 recovery plane): peer state lives in
instantiable :class:`ElasticScope` objects keyed by owner (an engine, a
disagg pool pair, a fleet replica), so one replica's strikes can never
quarantine another replica's PEs. The process-global registry survives as
the DEFAULT scope: every module-level function delegates to it, so
existing call sites — op entries, the retry/guard/integrity ladders, the
single serving engine — are byte-unchanged. Engines thread their scope
explicitly (``ServingEngine(elastic_scope=...)``); ``serving/fleet.py``
builds one scope per replica.

Everything here is keyed by flattened device position along the governing
world's comm axis (1-D worlds; multi-axis meshes skip attribution). Scope
state sits behind one per-scope lock, observable via
``health.snapshot()["elastic"]``, and reset by :func:`reset` (which
clears EVERY live scope, the per-test isolation posture). Disabled
(``config.elastic=False``, the default) every entry point is a cheap
no-op and ``effective_mesh`` returns its argument unchanged.
"""

from __future__ import annotations

import dataclasses
import threading
import weakref
from typing import Any, Callable

from triton_dist_tpu.resilience import health
from triton_dist_tpu.resilience import retry as _retry

# PE states
HEALTHY = "healthy"
SUSPECT = "suspect"
QUARANTINED = "quarantined"
PROBATION = "probation"

STATES = (HEALTHY, SUSPECT, QUARANTINED, PROBATION)


@dataclasses.dataclass
class PeerHealth:
    pe: int
    state: str = HEALTHY
    strikes: int = 0
    clean_probes: int = 0


# every live scope, for reset() — weak so a dropped engine's scope dies
# with it instead of pinning its peer dict for the process lifetime
_scopes_lock = threading.Lock()
_scopes: "weakref.WeakSet[ElasticScope]" = weakref.WeakSet()


def enabled() -> bool:
    from triton_dist_tpu import config as tdt_config

    return bool(tdt_config.get_config().elastic)


def attribute_straggler(records: list[dict], world_size: int) -> int | None:
    """The culprit PE named by absence: with ``world_size`` PEs in the
    collective and decoded timeout ``records`` from the victims, exactly
    one silent PE is the straggler. Returns None when the pattern is
    ambiguous (no victims, several silent PEs, or every PE tripped —
    which points at the fabric, not a peer)."""
    if not records or world_size < 2:
        return None
    tripped = {int(r["pe"]) for r in records if 0 <= int(r["pe"]) < world_size}
    if not tripped:
        return None
    silent = set(range(world_size)) - tripped
    if len(silent) == 1:
        return silent.pop()
    return None


class ElasticScope:
    """One namespace of PE strike/quarantine state (ISSUE 17).

    ``owner`` names the scope in health events: quarantines and
    re-admissions recorded through an owned scope land under family
    ``pe{N}@{owner}`` instead of the default scope's ``pe{N}``, so a
    fleet soak can prove strikes never crossed replica boundaries
    straight from the health counters. ``owner=None`` is reserved for
    the process-global DEFAULT scope (byte-identical legacy families).
    """

    def __init__(self, owner: str | None = None):
        self.owner = owner
        self._lock = threading.Lock()
        self._peers: dict[int, PeerHealth] = {}
        # shrunk meshes cached per (mesh, axis, quarantined set): the
        # degraded serving path runs effective_mesh every step, and
        # rebuilding the Mesh (plus re-running slice-boundary detection)
        # per step would put host work on exactly the path this layer
        # keeps cheap. Cleared by reset().
        self._shrunk_cache: dict = {}
        with _scopes_lock:
            _scopes.add(self)

    # -- peer bookkeeping ----------------------------------------------

    def _get(self, pe: int) -> PeerHealth:
        p = self._peers.get(pe)
        if p is None:
            p = self._peers[pe] = PeerHealth(pe=int(pe))
        return p

    def state(self, pe: int) -> str:
        with self._lock:
            p = self._peers.get(pe)
            return p.state if p is not None else HEALTHY

    def peer_states(self) -> dict[int, str]:
        with self._lock:
            return {pe: p.state for pe, p in sorted(self._peers.items())}

    def quarantined_pes(self) -> tuple[int, ...]:
        with self._lock:
            return tuple(
                pe for pe, p in sorted(self._peers.items())
                if p.state == QUARANTINED
            )

    def summary(self) -> dict:
        """Light JSON-able view for ``health.snapshot()`` / bench logs."""
        with self._lock:
            non_healthy = {
                str(pe): {"state": p.state, "strikes": p.strikes}
                for pe, p in sorted(self._peers.items())
                if p.state != HEALTHY
            }
        out: dict = {"enabled": enabled(), "degraded": bool(non_healthy),
                     "peers": non_healthy}
        if self.owner is not None:
            out["owner"] = self.owner
        return out

    def reset(self) -> None:
        """Forget all peer state (between tests / benchmark phases)."""
        with self._lock:
            self._peers.clear()
        self._shrunk_cache.clear()

    # -- attribution + strikes -----------------------------------------

    def report_timeout(self, pe: int, family: str | None = None) -> str:
        """One timeout attributed to ``pe``: healthy→suspect, suspect
        strikes accumulate to quarantine at ``config.suspect_threshold``,
        and a strike during probation re-quarantines immediately. Returns
        the new state."""
        return self._strike(pe, family, "timeout")

    def report_corruption(self, pe: int, family: str | None = None) -> str:
        """One detected data corruption attributed to ``pe``
        (integrity.py): the SAME strike machinery as timeouts —
        corruption and absence share one ladder into quarantine — with
        the quarantine reason naming data corruption so the health
        registry can tell the two apart."""
        return self._strike(pe, family, "corruption")

    def _strike(self, pe: int, family: str | None, what: str) -> str:
        from triton_dist_tpu import config as tdt_config

        threshold = max(1, int(tdt_config.get_config().suspect_threshold))
        reason = None
        with self._lock:
            p = self._get(pe)
            if p.state == QUARANTINED:
                return p.state
            p.strikes += 1
            p.clean_probes = 0
            if p.state == PROBATION or p.strikes >= threshold:
                p.state = QUARANTINED
                p.clean_probes = 0
                reason = (
                    f"{p.strikes} strike(s), last a {what}"
                    + (f" (family {family!r})" if family else "")
                )
            else:
                p.state = SUSPECT
            state = p.state
        if reason is not None:
            # record OUTSIDE the peer lock: the health funnel fans out to
            # the flight recorder (obs/blackbox.py), whose bundle freezes
            # elastic.summary() — recording under the lock would
            # self-deadlock
            health.record_pe_quarantine(pe, reason=reason, owner=self.owner)
            maybe_release_family_pins()
        return state

    def report_success(self, pe: int) -> str:
        """One clean step involving ``pe``: strikes decay by one; a
        suspect with no strikes left returns to healthy.
        Quarantine/probation are only exited through probes."""
        with self._lock:
            p = self._peers.get(pe)
            if p is None:
                return HEALTHY
            if p.state in (QUARANTINED, PROBATION):
                return p.state
            p.strikes = max(0, p.strikes - 1)
            if p.strikes == 0:
                p.state = HEALTHY
            return p.state

    def note_clean_step(self, world_size: int | None = None) -> None:
        """A watchdog-armed step completed cleanly: decay every suspect's
        strikes (called by the op entries; no-op unless elastic is
        enabled)."""
        if not enabled():
            return
        with self._lock:
            suspects = [pe for pe, p in self._peers.items()
                        if p.state == SUSPECT]
        for pe in suspects:
            self.report_success(pe)

    def note_timeout_records(
        self, records: list[dict], world_size: int,
        family: str | None = None,
    ) -> int | None:
        """Attribute one timed-out step's records to a peer and strike
        it. Returns the struck PE (or None: disabled / unattributable)."""
        if not enabled():
            return None
        pe = attribute_straggler(records, world_size)
        if pe is None:
            return None
        self.report_timeout(pe, family=family)
        return pe

    def note_timeout_exc(
        self, exc: BaseException, family: str | None = None,
    ) -> int | None:
        """Exception-path attribution: pull the DistTimeoutError out of
        the cause chain and strike the attributed peer (needs the error
        to carry ``world_size``, which op entries set)."""
        if not enabled():
            return None
        err = _retry.timeout_in_chain(exc)
        if err is None or getattr(err, "world_size", None) is None:
            return None
        return self.note_timeout_records(
            err.records, int(err.world_size), family=family or err.family
        )

    def note_integrity_records(
        self, records: list[dict], world_size: int | None = None,
        family: str | None = None,
    ) -> int | None:
        """Strike the PE each integrity record names, DIRECTLY — no
        by-absence inference. A canary record's PE field is the consumer
        that observed a corrupt landing, and the payload-fault model
        (faults.py) makes landing-site corruption the corrupt PE's own
        memory: victim == culprit, so the record IS the attribution.
        Returns the last struck PE (None: disabled / no named PEs)."""
        if not enabled():
            return None
        struck: int | None = None
        for r in records:
            pe = int(r.get("pe", -1))
            if pe < 0 or (world_size is not None and pe >= world_size):
                continue
            self.report_corruption(pe, family=family)
            struck = pe
        return struck

    def note_integrity_exc(
        self, exc: BaseException, family: str | None = None,
    ) -> int | None:
        """Exception-path corruption attribution (the ``note_timeout_exc``
        convention extended to :class:`IntegrityError`, ISSUE 8): pull
        the IntegrityError out of the cause chain and strike the PEs its
        records name. Host-tier detections (output guards) carry no
        records and attribute nothing — blaming a peer without evidence
        is strictly worse than staying degraded-but-correct."""
        if not enabled():
            return None
        from triton_dist_tpu.resilience.integrity import integrity_in_chain

        err = integrity_in_chain(exc)
        if err is None or not err.records:
            return None
        return self.note_integrity_records(
            err.records, getattr(err, "world_size", None),
            family=family or err.family,
        )

    def quarantine(self, pe: int, reason: str = "operator request") -> None:
        """Force a PE into quarantine (operator/test entry)."""
        with self._lock:
            p = self._get(pe)
            if p.state == QUARANTINED:
                return
            p.state = QUARANTINED
            p.clean_probes = 0
        # outside the peer lock (the _strike rationale: the health funnel
        # fans out to the flight recorder, which reads elastic.summary())
        health.record_pe_quarantine(pe, reason=reason, owner=self.owner)
        maybe_release_family_pins()

    # -- topology shrink + recovery ------------------------------------

    def effective_mesh(self, mesh, axis: str = "tp"):
        """The mesh this step should run over: ``mesh`` itself while
        every PE is serviceable, or the survivor mesh (quarantined
        positions dropped along ``axis``, shardings re-derivable from the
        returned mesh) once this scope has quarantined peers. Identity
        (same object, zero work beyond one config read) when elastic is
        disabled.

        Elastic worlds are 1-D: quarantined PEs are tracked by flattened
        device index, which only names a position along ``axis`` when the
        mesh has a single axis — a multi-axis mesh with quarantined peers
        is refused rather than excising the wrong device column."""
        if not enabled():
            return mesh
        dropped = self.quarantined_pes()
        if not dropped:
            return mesh
        if mesh.devices.ndim != 1:
            raise ValueError(
                f"elastic.effective_mesh: quarantined PEs {dropped} are "
                f"flattened world indices, but mesh {dict(mesh.shape)} has "
                f"{mesh.devices.ndim} axes — elastic shrink supports 1-D "
                f"worlds only (shrink multi-axis meshes explicitly via "
                f"parallel.mesh.shrink_mesh with axis positions)"
            )
        cache_key = (mesh, axis, dropped)
        hit = self._shrunk_cache.get(cache_key)
        if hit is None:
            from triton_dist_tpu.parallel.mesh import shrink_mesh

            hit = self._shrunk_cache[cache_key] = shrink_mesh(
                mesh, dropped, axis=axis
            )
        return hit

    def serviceable_mesh(
        self, mesh, axis: str = "tp",
        validate: Callable[[int], bool] | None = None,
    ):
        """:meth:`effective_mesh`, then — when the caller's model cannot
        run at the survivor count — shrink further to the largest world
        size ``validate`` accepts (dropping trailing survivors).

        Sharded models constrain their world size (kv heads, ffn
        columns, the sequence shard of a serving KV cache must all
        divide), so excising one quarantined PE can land on a count the
        model cannot use: 4 → 3 survivors with 4 kv heads. A serving
        loop would rather run 2-wide and degraded than refuse to serve
        (ISSUE 6 elastic wiring) — ``validate`` is its divisibility
        predicate, and healthy PEs beyond the chosen prefix sit out
        until probation re-admits the quarantined one and the full world
        returns. Identity semantics match ``effective_mesh``: disabled
        or whole worlds come back unchanged."""
        eff = self.effective_mesh(mesh, axis=axis)
        if validate is None or eff.devices.ndim != 1:
            return eff
        devs = list(eff.devices.flat)
        for k in range(len(devs), 0, -1):
            if not validate(k):
                continue
            if k == len(devs):
                return eff
            import numpy as np
            from jax.sharding import Mesh

            return Mesh(np.array(devs[:k]), (axis,))
        raise ValueError(
            f"no serviceable world size <= {len(devs)} survivors: the "
            f"validate predicate rejected every candidate (model "
            f"constraints cannot be met at any degraded world size)"
        )

    def probe_quarantined(
        self,
        mesh,
        axis: str = "tp",
        probe: Callable[[], bool] | None = None,
        pes: "list[int] | tuple[int, ...] | None" = None,
    ) -> dict[int, str]:
        """Move quarantined PEs to probation and run one world probe
        over the full mesh. A clean probe counts toward
        ``config.probation_probes``; reaching it re-admits the PE
        (healthy, strikes cleared, re-admission recorded in the health
        registry). A failed probe sends every CANDIDATE straight back to
        quarantine — and only the candidates: ``pes`` restricts the
        round to a subset (a disagg pool probing its own slice, ISSUE 17
        satellite 6), so one pool's failed probe can never reset another
        pool's probation counters. ``pes=None`` probes every
        quarantined/probation peer in this scope (the pre-scoping
        behavior, byte-identical). Returns {pe: new_state} for the
        candidates probed (empty when none qualify)."""
        from triton_dist_tpu import config as tdt_config

        allowed = None if pes is None else {int(pe) for pe in pes}
        with self._lock:
            targets = [
                pe for pe, p in sorted(self._peers.items())
                if p.state in (QUARANTINED, PROBATION)
                and (allowed is None or pe in allowed)
            ]
            for pe in targets:
                self._peers[pe].state = PROBATION
        if not targets:
            return {}
        ok = probe() if probe is not None else probe_world(mesh, axis=axis)
        needed = max(1, int(tdt_config.get_config().probation_probes))
        out: dict[int, str] = {}
        readmitted = []
        with self._lock:
            for pe in targets:
                p = self._get(pe)
                if not ok:
                    p.state = QUARANTINED
                    p.clean_probes = 0
                else:
                    p.clean_probes += 1
                    if p.clean_probes >= needed:
                        p.state = HEALTHY
                        p.strikes = 0
                        p.clean_probes = 0
                        readmitted.append(pe)
                out[pe] = p.state
        for pe in readmitted:
            health.record_pe_readmission(pe, owner=self.owner)
        if readmitted:
            maybe_release_family_pins()
        return out


# ---------------------------------------------------------------------------
# The process-global DEFAULT scope + delegating module API
# ---------------------------------------------------------------------------

# the default scope IS the pre-ISSUE-17 process-global registry: every
# module-level function below delegates to it, so op entries, the
# retry/guard ladders, and un-scoped engines see byte-identical behavior
DEFAULT = ElasticScope(owner=None)


def default_scope() -> ElasticScope:
    return DEFAULT


def state(pe: int) -> str:
    return DEFAULT.state(pe)


def peer_states() -> dict[int, str]:
    return DEFAULT.peer_states()


def quarantined_pes() -> tuple[int, ...]:
    return DEFAULT.quarantined_pes()


def summary() -> dict:
    """Light JSON-able view for ``health.snapshot()`` / bench logs —
    the DEFAULT scope's peers, exactly the pre-scoping dict. Owned
    scopes carry their own summaries (engines snapshot them); they are
    deliberately NOT folded in here, so the default surface stays
    byte-identical whether or not a fleet is running."""
    return DEFAULT.summary()


def scope_summaries() -> dict:
    """Summaries of every live OWNED scope that has non-healthy peers,
    keyed by owner (sorted). The black-box recorder folds these into a
    bundle's attribution chain so a scoped strike (``pe{N}@r{i}``) is
    explainable from the artifact alone; empty scopes are omitted so
    runs without owned degradation keep pre-scoping bundle bytes."""
    with _scopes_lock:
        live = [s for s in _scopes if s.owner is not None]
    out = {}
    for sc in sorted(live, key=lambda s: str(s.owner)):
        snap = sc.summary()
        if snap.get("peers"):
            out[sc.owner] = snap
    return out


def reset() -> None:
    """Forget all peer state in EVERY live scope (between tests /
    benchmark phases) — the default scope and every owned one, so a
    test's fleet replica scopes cannot leak quarantines into the next
    test through a cached engine."""
    with _scopes_lock:
        scopes = list(_scopes)
    for sc in scopes:
        sc.reset()


def report_timeout(pe: int, family: str | None = None) -> str:
    return DEFAULT.report_timeout(pe, family=family)


def report_corruption(pe: int, family: str | None = None) -> str:
    return DEFAULT.report_corruption(pe, family=family)


def report_success(pe: int) -> str:
    return DEFAULT.report_success(pe)


def note_clean_step(world_size: int | None = None) -> None:
    DEFAULT.note_clean_step(world_size)


def note_timeout_records(
    records: list[dict], world_size: int, family: str | None = None
) -> int | None:
    return DEFAULT.note_timeout_records(records, world_size, family=family)


def note_timeout_exc(exc: BaseException, family: str | None = None) -> int | None:
    return DEFAULT.note_timeout_exc(exc, family=family)


def note_integrity_records(
    records: list[dict], world_size: int | None = None,
    family: str | None = None,
) -> int | None:
    return DEFAULT.note_integrity_records(records, world_size, family=family)


def note_integrity_exc(exc: BaseException, family: str | None = None) -> int | None:
    return DEFAULT.note_integrity_exc(exc, family=family)


def quarantine(pe: int, reason: str = "operator request") -> None:
    DEFAULT.quarantine(pe, reason=reason)


def effective_mesh(mesh, axis: str = "tp"):
    return DEFAULT.effective_mesh(mesh, axis=axis)


def serviceable_mesh(mesh, axis: str = "tp", validate: Callable[[int], bool] | None = None):
    return DEFAULT.serviceable_mesh(mesh, axis=axis, validate=validate)


def probe_quarantined(
    mesh,
    axis: str = "tp",
    probe: Callable[[], bool] | None = None,
    pes: "list[int] | tuple[int, ...] | None" = None,
) -> dict[int, str]:
    return DEFAULT.probe_quarantined(mesh, axis=axis, probe=probe, pes=pes)


def maybe_release_family_pins() -> None:
    """In interpret mode, excising the culprit PE (or re-admitting a healed
    one) clears the watchdog family quarantines: simulated semaphores are
    rebuilt per launch, so the hardware residue the pin protects against
    cannot exist, and the shrunk/recovered world should run the fused path.
    Compiled TPU runs keep their pins — a quarantined family's device
    semaphore stays dirty regardless of which peer caused the trip. With
    the elastic layer disabled this is a no-op: the pre-existing pin
    semantics (docs/resilience.md) apply unchanged."""
    from triton_dist_tpu import config as tdt_config

    if enabled() and tdt_config.interpreting():
        health.clear_timeout_quarantines()


_maybe_release_family_pins = maybe_release_family_pins


# ---------------------------------------------------------------------------
# World probes (stateless: shared by every scope)
# ---------------------------------------------------------------------------

def _probe_fused(mesh, axis: str):
    """Watchdogged device barrier over the whole world — the cheap probe.
    Times out (DistTimeoutError) if any PE, including the quarantined one,
    fails to join within the budget."""
    from jax.sharding import PartitionSpec as P

    from triton_dist_tpu.ops import common as ops_common

    fn = lambda: ops_common.barrier_all_op(axis=axis)  # noqa: E731
    return ops_common.jit_shard_map(
        fn, mesh, (), P(axis), key=("elastic_probe_fused", axis)
    )()


def _probe_golden(mesh, axis: str):
    """XLA-collective probe for environments where the fused barrier cannot
    build (no Mosaic interpreter / compile failure): a psum over the axis
    still requires every PE to participate; XLA owns the transport."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from triton_dist_tpu.ops import common as ops_common

    def fn():
        return jnp.full((1,), jax.lax.psum(jnp.int32(1), axis), jnp.int32)

    return ops_common.jit_shard_map(
        fn, mesh, (), P(axis), key=("elastic_probe_xla", axis)
    )()


def probe_world(mesh, axis: str = "tp") -> bool:
    """One probation probe: a cheap barrier over the FULL world mesh
    (quarantined PEs included). True = every PE joined within the watchdog
    budget; False = the probe itself timed out. Deterministic failures of
    the fused probe (it cannot build in this environment) fall through to
    the golden XLA probe rather than failing the probation."""
    from triton_dist_tpu import config as tdt_config
    from triton_dist_tpu.resilience import guard as _guard
    from triton_dist_tpu.resilience.records import DistTimeoutError

    # a previous failed probe must not pin probing itself to a refused
    # launch — probes are the recovery path, they always get a fresh try
    health.clear_short_circuit("elastic_probe_fused")
    # the probe's failure signal IS the DistTimeoutError: under the
    # poison-and-continue posture (raise_on_timeout=False) a timed-out
    # probe would return normally and count as clean, re-admitting a
    # still-sick PE — force the loud posture for the probe's duration
    prev_raise = tdt_config.get_config().raise_on_timeout
    tdt_config.update(raise_on_timeout=True)
    try:
        _probe_fused(mesh, axis)
        return True
    except DistTimeoutError:
        return False
    except Exception as exc:  # noqa: BLE001 — guard classification decides
        if not _guard.fallbackable(exc):
            raise
        _probe_golden(mesh, axis)
        return True
    finally:
        tdt_config.update(raise_on_timeout=prev_raise)
