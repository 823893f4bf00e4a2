"""Chaos-soak harness (ISSUE 11): long seeded campaigns composing the
faults the matrix only tests in isolation.

``scripts/chaos_matrix.sh`` proves each fault class alone — a dropped
signal, a straggler, a corrupt payload, a poisoned request. Production
outages are compositions: a flash crowd lands *while* a PE is straggling
*while* a DMA path corrupts payloads, and the failure modes that matter
(lost requests, deadlocked drain loops, double-counted health events)
only appear at the seams between recovery paths. A **campaign** is one
seeded serve run that composes:

- **flash-crowd λ bursts** — ``traffic.TrafficSpec(process="burst")``
  with priorities and deadlines, offered against a deliberately small
  queue so the overload ladder, overflow sheds, and retry budgets all
  engage;
- **a persistent straggler** — fabricated ``DistTimeoutError`` records
  naming every PE *but* the straggler (the by-absence attribution
  convention), repeated so the strike threshold quarantines it and the
  engine shrinks the mesh **mid-overload**, prefix-replaying in-flight
  work while the queue is still slammed;
- **payload corruption** — fabricated ``IntegrityError`` canary records
  naming a corrupt PE directly (the victim-==-culprit convention of
  resilience/faults.py), driving the integrity rebuild arc;
- **a poisoned shared prefix page** (ISSUE 12, ``SoakSpec.shared_prefix``
  campaigns): burst traffic over Zipf shared prefixes with the radix
  prefix cache armed, plus scheduled non-finite-logit poisons landing on
  a slot with a SHARED chain — driving the strike fan-out (every reader
  of the struck chain evicted and cold-re-prefilled) composed with the
  rebuild arcs above, which drop the whole trie mid-flight;
- **the disaggregated two-pool topology** (ISSUE 13, ``SoakSpec.disagg``
  campaigns): burst traffic through a prefill pool + decode pool with a
  fault-tolerant KV handoff between them, composing corrupt-KV-chunk
  injection mid-handoff (the ``FaultPlan pool="decode"`` payload seam —
  the guard ladder's re-send → re-stream → decode-local-fallback rungs
  all engage, culprits struck), a prefill-pool straggler (pool-scoped
  by-absence attribution → quarantine → the POOL shrinks mid-stream),
  and — when scheduled — a prefill-pool timeout storm that collapses the
  topology to the unified engine with every in-flight request replayed;
- **speculative serving** (ISSUE 20, ``SoakSpec.speculative``
  campaigns): burst traffic through the unified engine with SELF-DRAFT
  speculative decoding armed, composing scheduled corrupt-draft
  injections (the batcher's sticky ``corrupt_draft_next`` seam — every
  one must be rejected by the verify pass) with the straggler shrink +
  prefix-replay arc mid-speculation; judged byte-for-byte against a
  clean NON-speculative run of the same trace
  (:func:`check_spec_invariants`);
- **the N-replica fleet** (ISSUE 16, ``SoakSpec.fleet`` campaigns):
  burst traffic routed by prefix affinity over N disaggregated replicas,
  composing corrupt-KV-chunk injection on the replicas' handoff seams
  with — when scheduled — a decode-pool timeout storm that KILLS one
  replica mid-burst (consecutive-failure exhaustion → the typed
  ``UnrecoverableEngineError`` → router failover re-offers every queued
  and in-flight request to the survivors with the original SLO anchors;
  :func:`check_fleet_invariants` asserts zero lost).

Faults are injected at the documented host-level chaos seam (the
``ContinuousBatcher.step`` wrap of tests/test_serving.py): only the
in-kernel wait is simulated; retry, attribution, quarantine, shrink,
replay, shedding, and the brownout ladder are all the production paths.

Invariants asserted on every campaign (:func:`check_invariants`):

1. **no lost request** — every offered uid reaches exactly ONE terminal
   state (Finished / Shed / Poisoned / terminal Rejected);
2. **no deadlock** — the serve loop drains within the step budget and
   leaves no queued or in-flight state behind;
3. **accounting balance** — serving counters, per-class shed counters,
   and the health registry agree with the terminal census (a recovery
   path that double-counts or skips an event fails here);
4. **seeded replay** — the same spec reproduces a byte-identical
   campaign fingerprint (terminal states, tokens, ladder transitions);
5. **one bundle per flip** (ISSUE 15, :func:`check_blackbox_invariant`)
   — every campaign runs under an armed flight recorder
   (:func:`_flight_recorder`: metrics plane + black box) and every
   event of the black-box trigger set (``BLACKBOX_KINDS``: brownouts,
   handoff restream/fallback, pool collapse, prefix strikes,
   quarantines, integrity) must freeze exactly one post-mortem bundle —
   no duplicates, no misses, no suppression.

``scripts/chaos_soak.py`` is the CLI; the quick cells ride
``scripts/chaos_matrix.sh`` and the full 20-campaign soak is the
``soak`` (slow) pytest tier of tests/test_overload.py.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
from typing import Any

import numpy as np

from triton_dist_tpu.resilience import retry as _retry
from triton_dist_tpu.resilience.records import DistTimeoutError
from triton_dist_tpu.serving.engine import (
    Finished,
    Poisoned,
    Rejected,
    Shed,
)


@dataclasses.dataclass(frozen=True)
class SoakSpec:
    """One campaign's composition, fully derived from ``seed``.

    The traffic is a flash-crowd burst mix with priorities and deadlines;
    ``n_timeouts`` straggler trips (all naming the same ``straggler_pe``
    by absence — persistent, so the strike threshold quarantines it) and
    ``n_corruptions`` canary trips are scheduled at seed-derived step
    numbers. ``max_steps`` is the deadlock watchdog."""

    seed: int = 0
    n_requests: int = 24
    rate_rps: float = 30.0
    burst_every_s: float = 0.6
    burst_n: int = 8
    priority_mix: tuple = ((0.6, "interactive"), (0.4, "batch"))
    deadline_ms: tuple = ("uniform", 500, 6000)
    max_queue: int = 6
    virtual_step_s: float = 0.05
    world: int = 4
    s_max: int = 16
    batch: int = 2     # built-in model's slot count (serving concurrency)
    n_timeouts: int = 2
    n_corruptions: int = 1
    straggler_pe: int = 1
    corrupt_pe: int = 2
    fault_window: int = 40      # fault steps drawn from [2, 2+window)
    max_steps: int = 50_000
    # shared-prefix campaign knobs (ISSUE 12): prefix_pool > 0 arms the
    # radix prefix cache (page_size required) and prepends Zipf-drawn
    # system prompts; n_poisons scheduled non-finite-logit poisons prefer
    # a slot holding a SHARED chain, so the strike fan-out path runs
    prefix_pool: int = 0
    prefix_tokens: int = 8
    prefix_share: float = 1.0
    page_size: int = 0
    n_poisons: int = 0
    # disaggregated campaign knobs (ISSUE 13): disagg_prefill_pes > 0
    # runs the two-pool topology; n_chunk_corruptions budgets the
    # corrupt-KV-chunk FaultPlan fired mid-handoff (pool="decode");
    # collapse_at_step > 0 schedules a persistent prefill-pool timeout
    # storm from that (pool) step on, driving quarantine → shrink →
    # topology collapse to unified
    disagg_prefill_pes: int = 0
    n_chunk_corruptions: int = 0
    collapse_at_step: int = 0
    handoff_chunks: int = 2
    # ISSUE 18: decode-pool admission on FIRST-page-landed (the
    # pipelined handoff) instead of last — same ladder, same faults,
    # earlier admission; False keeps the historical posture
    pipelined_handoff: bool = False
    # fleet campaign knobs (ISSUE 16): fleet_replicas > 0 runs the
    # N-replica router over disaggregated replicas (1 prefill PE + the
    # rest decode each); replica_kill_at_step > 0 storms the KILL
    # TARGET's decode pool with timeouts from that (pool-step) count on
    # — consecutive-failure exhaustion raises the typed
    # UnrecoverableEngineError out of the replica and the ROUTER's
    # failover re-offers its work to survivors mid-burst
    fleet_replicas: int = 0
    replica_kill_at_step: int = 0
    replica_kill_target: int = 1
    # recovery-plane campaign knobs (ISSUE 17): fleet_recovery runs the
    # fleet elastic-ON with per-replica ElasticScopes and arms the whole
    # recovery ladder (pool probation regrow, reversible collapse,
    # replica resurrection). replica_revive_at_step closes the kill
    # storm's window — it counts GLOBAL fleet decode steps (any
    # replica), so a dead target's window still closes while the
    # survivor serves. pool_strag_at_step fires a two-step straggler
    # pair on the SURVIVOR's decode pool (quarantine → pool shrink →
    # probation regrow); prefill_storm_at_step storms the survivor's
    # prefill pool into collapse (→ probation → un-collapse). Both
    # count that pool's OWN steps.
    fleet_recovery: bool = False
    replica_revive_at_step: int = 0
    pool_strag_at_step: int = 0
    prefill_storm_at_step: int = 0
    # speculative campaign knobs (ISSUE 20): spec_k >= 2 arms self-draft
    # speculative decoding (draft == target) on the unified engine, so
    # the greedy token streams are PROVABLY byte-identical to a clean
    # plain run — the campaign's judged invariant. n_draft_corruptions
    # schedules sticky corrupt-draft injections (the batcher's chaos
    # seam flips one drafted token mid-round); every one must be
    # REJECTED by the verify pass with the stream untouched. The
    # straggler arc composes: speculation must survive the shrink →
    # prefix-replay rebuild with its draft state rebuilt cold.
    spec_k: int = 0
    n_draft_corruptions: int = 0

    @classmethod
    def fleet_recovery_spec(cls, seed: int = 0, **over) -> "SoakSpec":
        """The ISSUE 17 soak shape: burst traffic through a 2-replica
        fleet of disaggregated engines (2 prefill + 2 decode PEs each on
        world=8), elastic ON and replica-scoped, composing — on the
        survivor — a decode straggler pair (PE quarantine → pool shrink
        → probation regrow mid-serve) and a prefill-pool storm (collapse
        → clean probation → un-collapse) with — on the target — a
        windowed decode timeout storm (typed death → failed probes
        while the storm lasts → resurrection with a cold trie and an
        affinity ramp once it clears). Strikes must stay inside their
        replica's scope and every re-admitted replica must serve again
        (:func:`check_fleet_invariants`)."""
        kw = dict(
            seed=seed, world=8, fleet_replicas=2, disagg_prefill_pes=2,
            n_requests=28, rate_rps=10.0, burst_every_s=0.8, burst_n=4,
            max_queue=12, n_timeouts=0, n_corruptions=0,
            n_chunk_corruptions=0, fault_window=30,
            fleet_recovery=True,
            replica_kill_at_step=14, replica_revive_at_step=34,
            pool_strag_at_step=4, prefill_storm_at_step=3,
            max_steps=60_000,
        )
        kw.update(over)
        return cls(**kw)

    @classmethod
    def fleet(cls, seed: int = 0, **over) -> "SoakSpec":
        """The ISSUE 16 soak shape: burst traffic with priorities and
        deadlines through a 2-replica fleet of disaggregated engines
        (1 prefill + 1 decode PE each on world=4) × corrupt KV chunks
        mid-handoff × — every second seed — a replica killed mid-burst
        by a decode-pool timeout storm (failover re-offers its queued +
        in-flight work to the survivor with the original SLO anchors)."""
        kw = dict(
            seed=seed, world=4, fleet_replicas=2, disagg_prefill_pes=1,
            n_requests=16, rate_rps=14.0, burst_n=5, max_queue=10,
            n_timeouts=0, n_corruptions=0, n_chunk_corruptions=2,
            fault_window=30,
            replica_kill_at_step=0 if seed % 2 else 12,
        )
        kw.update(over)
        return cls(**kw)

    @classmethod
    def disagg(cls, seed: int = 0, **over) -> "SoakSpec":
        """The ISSUE 13 soak shape: burst traffic with priorities and
        deadlines through the two-pool topology × corrupt KV chunks
        mid-handoff × a prefill-pool straggler (shrink mid-stream) × —
        every third seed — a scheduled pool collapse."""
        kw = dict(
            seed=seed, world=4, disagg_prefill_pes=2,
            n_requests=18, rate_rps=16.0, burst_n=6, max_queue=10,
            n_timeouts=2, n_corruptions=0, n_chunk_corruptions=3,
            fault_window=30,
            collapse_at_step=0 if seed % 3 else 24,
        )
        kw.update(over)
        return cls(**kw)

    @classmethod
    def speculative(cls, seed: int = 0, **over) -> "SoakSpec":
        """The ISSUE 20 soak shape: burst traffic through the unified
        engine with SELF-DRAFT speculative decoding armed (k=3) ×
        scheduled corrupt-draft injections × a persistent straggler
        (mesh shrink + prefix replay mid-speculation). Judged against a
        clean NON-SPECULATIVE run of the same trace: the finished set
        and every finished request's token stream must be byte-identical
        (greedy; the corrupted drafts must each be rejected by the
        verify pass), and the whole campaign must replay bit-identically
        from its seed. Overload/deadline pressure is deliberately OFF —
        shed decisions are timing-dependent and would make the plain
        reference incomparable; the ladder × speculation composition is
        pinned in tests/test_spec_serving.py instead."""
        kw = dict(
            seed=seed, spec_k=3, n_draft_corruptions=2,
            n_requests=12, rate_rps=12.0, burst_n=5,
            s_max=32, max_queue=64,
            # a narrow window: speculative campaigns take ~k× fewer
            # steps than plain ones, and a fault drawn past the drain
            # would deterministically never fire
            n_timeouts=1, n_corruptions=0, fault_window=12,
        )
        kw.update(over)
        return cls(**kw)

    @classmethod
    def shared_prefix(cls, seed: int = 0, **over) -> "SoakSpec":
        """The ISSUE 12 soak shape: burst traffic over shared prefixes ×
        a straggler × payload corruption × a poisoned shared page."""
        kw = dict(
            seed=seed, prefix_pool=2, prefix_tokens=8, page_size=4,
            s_max=32, batch=4, max_queue=10, rate_rps=12.0, burst_n=6,
            n_poisons=1, n_timeouts=1, n_corruptions=1,
            n_requests=18, fault_window=30,
        )
        kw.update(over)
        return cls(**kw)

    def validate(self) -> "SoakSpec":
        if self.n_requests < 1 or self.world < 2:
            raise ValueError("need n_requests >= 1 and world >= 2")
        if not 0 <= self.straggler_pe < self.world:
            raise ValueError("straggler_pe out of range")
        if not 0 <= self.corrupt_pe < self.world:
            raise ValueError("corrupt_pe out of range")
        if self.fault_window < (
            self.n_timeouts + self.n_corruptions + self.n_poisons
            + self.n_draft_corruptions
        ):
            raise ValueError("fault_window too small for the fault count")
        if self.spec_k == 1:
            raise ValueError(
                "spec_k=1 cannot accept a draft under the k-1 cap — use "
                "0 (off) or >= 2"
            )
        if self.n_draft_corruptions and not self.spec_k:
            raise ValueError(
                "n_draft_corruptions corrupts a DRAFT token — set spec_k "
                "too"
            )
        if self.spec_k:
            if self.disagg_prefill_pes or self.fleet_replicas:
                raise ValueError(
                    "speculative campaigns run the unified engine — "
                    "spec_k composes with neither the disagg nor the "
                    "fleet shapes"
                )
            if self.prefix_pool or self.n_corruptions or self.n_poisons:
                raise ValueError(
                    "the speculative campaign's seams are draft "
                    "corruption + the straggler; n_corruptions / "
                    "n_poisons / prefix_pool are the other shapes' seams"
                )
        if self.prefix_pool and not self.page_size:
            raise ValueError(
                "shared-prefix campaigns need page_size (the prefix cache "
                "rides the paged pool)"
            )
        if self.n_poisons and not self.prefix_pool:
            raise ValueError(
                "n_poisons targets shared chains — set prefix_pool too"
            )
        if self.fleet_replicas:
            if not self.disagg_prefill_pes:
                raise ValueError(
                    "fleet campaigns run disaggregated replicas — set "
                    "disagg_prefill_pes (per replica) too"
                )
            if self.world % self.fleet_replicas:
                raise ValueError(
                    f"world={self.world} does not split into "
                    f"fleet_replicas={self.fleet_replicas} equal slices"
                )
            if not 0 <= self.replica_kill_target < self.fleet_replicas:
                raise ValueError("replica_kill_target out of range")
            per = self.world // self.fleet_replicas
            if not 1 <= self.disagg_prefill_pes < per:
                raise ValueError(
                    f"disagg_prefill_pes={self.disagg_prefill_pes} must "
                    f"leave a decode pool inside each replica's "
                    f"{per}-device slice"
                )
        elif self.replica_kill_at_step:
            raise ValueError(
                "replica_kill_at_step is a fleet fault — set "
                "fleet_replicas too"
            )
        if self.fleet_recovery and not self.fleet_replicas:
            raise ValueError(
                "fleet_recovery arms the fleet recovery plane — set "
                "fleet_replicas too"
            )
        if not self.fleet_recovery and (
            self.replica_revive_at_step
            or self.pool_strag_at_step
            or self.prefill_storm_at_step
        ):
            raise ValueError(
                "replica_revive_at_step / pool_strag_at_step / "
                "prefill_storm_at_step are recovery-plane faults — set "
                "fleet_recovery too"
            )
        if self.replica_revive_at_step and not self.replica_kill_at_step:
            raise ValueError(
                "replica_revive_at_step closes a kill storm — set "
                "replica_kill_at_step too"
            )
        if (
            self.replica_revive_at_step
            and self.replica_revive_at_step <= self.replica_kill_at_step
        ):
            raise ValueError(
                "replica_revive_at_step must come after "
                "replica_kill_at_step (the storm window is "
                "[kill, revive) in global decode steps)"
            )
        if self.disagg_prefill_pes:
            if not self.fleet_replicas and not (
                1 <= self.disagg_prefill_pes < self.world
            ):
                raise ValueError(
                    f"disagg_prefill_pes={self.disagg_prefill_pes} must "
                    f"leave a decode pool inside world={self.world}"
                )
            if self.prefix_pool:
                raise ValueError(
                    "disagg and shared-prefix campaign shapes are "
                    "separate sets (compose later)"
                )
            if self.n_corruptions or self.n_poisons:
                raise ValueError(
                    "disagg campaigns model corruption at the HANDOFF "
                    "seam (n_chunk_corruptions); n_corruptions/n_poisons "
                    "are the unified-engine seams"
                )
        if (self.n_chunk_corruptions or self.collapse_at_step) and (
            not self.disagg_prefill_pes
        ):
            raise ValueError(
                "chunk corruption / pool collapse are handoff faults — "
                "set disagg_prefill_pes too"
            )
        if self.pipelined_handoff and not self.disagg_prefill_pes:
            raise ValueError(
                "pipelined_handoff gates decode-pool admission — set "
                "disagg_prefill_pes too"
            )
        return self


@dataclasses.dataclass
class CampaignResult:
    spec: SoakSpec
    terminals: dict            # uid -> terminal kind name
    n_steps_hint: int          # batcher step calls observed by the injector
    rebuilds: int
    transitions: list          # ladder transitions (dicts)
    snapshot: dict             # engine snapshot
    health: dict               # health registry snapshot
    fingerprint: str
    failures: list             # invariant violations (empty = green)
    error: str | None = None   # an escaped exception (deadlock/storm)

    @property
    def ok(self) -> bool:
        return not self.failures and self.error is None


def _timeout_records(world: int, straggler: int) -> list[dict]:
    """By-absence attribution: every PE but the straggler reports the
    expired wait (the convention of elastic.note_timeout_records)."""
    return [
        {"pe": pe, "kind": "barrier_all", "site": 0, "status": "timeout",
         "expected": 1, "observed": 0, "budget": 16}
        for pe in range(world) if pe != straggler
    ]


def _integrity_records(corrupt_pe: int) -> list[dict]:
    """Victim == culprit: the canary record names the corrupt PE
    directly (resilience/faults.py landing-site model)."""
    return [{"pe": corrupt_pe, "kind": "integrity", "site": 0,
             "status": "integrity", "expected": 0, "observed": 1}]


def fault_schedule(spec: SoakSpec) -> dict[int, tuple[str, int]]:
    """step-call-number -> ("timeout" | "integrity" | "poison", pe),
    seed-derived. Distinct steps, so two faults never race one step (the
    matrix covers single-step behavior; the soak covers the composition
    over time)."""
    rng = np.random.default_rng([int(spec.seed), 0x50AC])
    n = spec.n_timeouts + spec.n_corruptions + spec.n_poisons
    steps = sorted(
        int(s) for s in rng.choice(
            np.arange(2, 2 + spec.fault_window), size=n, replace=False
        )
    )
    kinds = (
        [("timeout", spec.straggler_pe)] * spec.n_timeouts
        + [("integrity", spec.corrupt_pe)] * spec.n_corruptions
        + [("poison", -1)] * spec.n_poisons   # pe unused: targets a slot
    )
    rng.shuffle(kinds)  # interleave the fault classes over the campaign
    return {s: tuple(k) for s, k in zip(steps, kinds)}


@contextlib.contextmanager
def _inject_faults(schedule: dict, world: int):
    """The host-level chaos seam: wrap ``ContinuousBatcher.step`` so call
    number ``k`` raises its scheduled fault (tests/test_serving.py's
    technique, promoted into the harness). Restores the real step on
    exit; rebuilt batchers (shrink/regrow/downshift) stay wrapped — a
    persistent straggler outlives every rebuild."""
    from triton_dist_tpu.models.decode import ContinuousBatcher
    from triton_dist_tpu.resilience.integrity import DET_CANARY, IntegrityError

    real_step = ContinuousBatcher.step
    calls = {"n": 0}
    # armed-but-unfired poisons: a LIST, so n_poisons >= 2 scheduled at
    # close steps never overwrite each other (each fires in turn)
    pending: dict = {"poison": []}

    def flaky(self):
        calls["n"] += 1
        fault = schedule.get(calls["n"])
        if fault is not None:
            kind, pe = fault
            if kind == "timeout":
                raise DistTimeoutError(
                    "batcher_step", _timeout_records(world, pe),
                    world_size=world,
                )
            if kind == "integrity":
                raise IntegrityError(
                    "batcher_step", DET_CANARY,
                    "soak-injected payload corruption",
                    records=_integrity_records(pe), world_size=world,
                )
            # kind == "poison" (ISSUE 12): arm a pending poison — fired
            # below, preferring a slot whose shared chain has ANOTHER
            # reader so the strike fan-out path actually runs
            pending["poison"].append(calls["n"])
        out = real_step(self)
        if pending["poison"]:
            px = self.prefix_cache
            deferred = calls["n"] - pending["poison"][0]
            target = None
            if px is not None:
                # first choice: a chain some OTHER slot is also reading —
                # poisoning it must strike every reader; defer (bounded)
                # until such a moment exists, then fall back to any
                # chained, then any occupied slot. All seed-deterministic.
                target = next(
                    (j for j, r in enumerate(self.slot_req)
                     if r is not None and px.chain_len(j) > 0
                     and px.n_readers(j) >= 2),
                    None,
                )
                if target is None and deferred >= 150:
                    target = next(
                        (j for j, r in enumerate(self.slot_req)
                         if r is not None and px.chain_len(j) > 0),
                        None,
                    )
            if target is None and deferred >= 300:
                target = next(
                    (j for j, r in enumerate(self.slot_req)
                     if r is not None),
                    None,
                )
            if target is not None:
                pending["poison"].pop(0)
                self._poison_slot(
                    target, "soak-injected poisoned shared page"
                )
        return out

    ContinuousBatcher.step = flaky
    try:
        yield calls
    finally:
        ContinuousBatcher.step = real_step


@contextlib.contextmanager
def _flight_recorder():
    """Arm the ISSUE 15 flight recorder around one campaign: the metrics
    plane plus the black box writing into a throwaway dir, spans off.
    Observation-only by construction — campaign fingerprints hash
    decisions (terminals / transitions / counters), none of which the
    recorder can touch — so replay byte-identity is preserved while
    every campaign proves the bundle-per-flip invariant
    (:func:`check_blackbox_invariant`) as part of its green conditions."""
    import shutil
    import tempfile

    from triton_dist_tpu import config as tdt_config
    from triton_dist_tpu import obs

    prev = tdt_config.get_config().obs
    tmp = tempfile.mkdtemp(prefix="tdt_soak_blackbox_")
    obs.metrics.reset()
    obs.alerts.reset()
    obs.blackbox.reset()
    tdt_config.update(obs=obs.ObsConfig(
        spans=False,
        metrics=obs.MetricsConfig(),
        blackbox=obs.BlackboxConfig(dir=tmp, max_bundles=4096),
    ))
    try:
        yield
    finally:
        tdt_config.update(obs=prev)
        obs.metrics.reset()
        obs.alerts.reset()
        obs.blackbox.reset()
        shutil.rmtree(tmp, ignore_errors=True)


def check_blackbox_invariant(health_snap: dict) -> list:
    """The ISSUE 15 soak invariant: exactly ONE post-mortem bundle per
    health-flipping event — no duplicates, no misses, no suppression —
    judged per triggering kind against the black-box census. Call while
    the campaign's :func:`_flight_recorder` scope is still armed."""
    from triton_dist_tpu.obs import blackbox as _bb

    census = _bb.census()
    by_kind: dict[str, int] = {}
    for key, n in health_snap.get("counters", {}).items():
        kind = key.rsplit(":", 1)[-1]
        if kind in _bb.BLACKBOX_KINDS:
            by_kind[kind] = by_kind.get(kind, 0) + n
    fails: list[str] = []
    if census["suppressed"]:
        fails.append(
            f"black box suppressed {census['suppressed']} bundle(s) — the "
            f"campaign out-wrote max_bundles (no silent caps: raise it)"
        )
    if census["by_kind"] != by_kind:
        fails.append(
            f"bundle census {census['by_kind']} != health flip census "
            f"{by_kind} — not exactly one bundle per flipping event"
        )
    if census["written"] != sum(by_kind.values()):
        fails.append(
            f"bundles written {census['written']} != total flipping "
            f"events {sum(by_kind.values())}"
        )
    return fails


def _terminal_kind(res: Any) -> str:
    for cls in (Finished, Shed, Poisoned, Rejected):
        if isinstance(res, cls):
            return cls.__name__.lower()
    return f"<unknown {type(res).__name__}>"


def campaign_fingerprint(result: "CampaignResult") -> str:
    """Byte-stable digest of everything a campaign decided: per-uid
    terminal states (tokens included), ladder transitions, rebuild count,
    and the terminal counters — the seeded-replay pin."""
    h = hashlib.sha256()
    h.update(repr(dataclasses.asdict(result.spec)).encode())
    for uid in sorted(result.terminals):
        h.update(repr((uid, result.terminals[uid])).encode())
    h.update(repr(result.transitions).encode())
    h.update(repr((result.rebuilds,)).encode())
    reqs = result.snapshot.get("requests", {})
    h.update(repr(sorted(reqs.items())).encode())
    return h.hexdigest()


def check_invariants(eng, result: CampaignResult, offered_uids: set) -> list:
    """The campaign's green conditions (module docstring). Returns the
    violation list (empty = green)."""
    fails: list[str] = []
    snap = result.snapshot
    reqs = snap.get("requests", {})
    term = result.terminals

    # 1. no lost request: exactly-one-terminal-state per offered uid
    got = set(term)
    if got != offered_uids:
        fails.append(
            f"terminal census mismatch: missing={sorted(offered_uids - got)} "
            f"extra={sorted(got - offered_uids)}"
        )
    unknown = {u: k for u, k in term.items() if k.startswith("<unknown")}
    if unknown:
        fails.append(f"non-terminal results: {unknown}")

    # 2. no deadlock residue: nothing queued or in flight after the drain
    if eng._pending or eng._states:
        fails.append(
            f"residual work after serve: queue={len(eng._pending)} "
            f"in_flight={len(eng._states)}"
        )

    # 3. accounting balance: counters == terminal census, both tiers
    census = {}
    for k in term.values():
        census[k] = census.get(k, 0) + 1
    pairs = (
        ("finished", census.get("finished", 0)),
        ("shed", census.get("shed", 0)),
        ("poisoned", census.get("poisoned", 0)),
        ("rejected_final", census.get("rejected", 0)),
    )
    for name, want in pairs:
        have = reqs.get(name, 0)
        if have != want:
            fails.append(
                f"counter {name}={have} disagrees with terminal census "
                f"{want}"
            )
    if reqs.get("submitted", 0) != len(offered_uids) + reqs.get(
        "resubmitted", 0
    ):
        fails.append(
            f"submitted={reqs.get('submitted', 0)} != offered "
            f"{len(offered_uids)} + resubmitted {reqs.get('resubmitted', 0)}"
        )
    ov = snap.get("overload", {})
    if sum(ov.get("sheds_by_class", {}).values()) != reqs.get("shed", 0):
        fails.append(
            f"controller sheds_by_class {ov.get('sheds_by_class')} does not "
            f"sum to the shed counter {reqs.get('shed', 0)}"
        )
    # scheduled strike coverage actually ran: a shared-prefix campaign
    # whose deferred poison never found a target must FAIL, not silently
    # skip the fan-out path it exists to exercise
    if result.spec.n_poisons and reqs.get("poisoned", 0) < result.spec.n_poisons:
        fails.append(
            f"scheduled {result.spec.n_poisons} poison(s) but only "
            f"{reqs.get('poisoned', 0)} fired — the strike coverage this "
            f"campaign advertises did not run (retune the spec)"
        )
    hc = result.health.get("counters", {})
    if hc.get("serving_engine:serving_rebuild", 0) != result.rebuilds:
        fails.append(
            f"health serving_rebuild={hc.get('serving_engine:serving_rebuild', 0)} "
            f"!= engine rebuilds {result.rebuilds}"
        )
    if hc.get("serving_engine:shed", 0) != reqs.get("shed", 0):
        fails.append(
            f"health shed={hc.get('serving_engine:shed', 0)} != metrics "
            f"shed {reqs.get('shed', 0)}"
        )
    if hc.get("serving_engine:brownout", 0) != len(result.transitions):
        fails.append(
            f"health brownout={hc.get('serving_engine:brownout', 0)} != "
            f"controller transitions {len(result.transitions)}"
        )
    return fails


def _spec_fault_schedule(spec: SoakSpec) -> dict[int, tuple[str, int]]:
    """step-call-number -> ("timeout" | "draft", pe) for the speculative
    campaign, seed-derived like :func:`fault_schedule` (distinct steps,
    interleaved kinds)."""
    rng = np.random.default_rng([int(spec.seed), 0x5DEC])
    n = spec.n_timeouts + spec.n_draft_corruptions
    steps = sorted(
        int(s) for s in rng.choice(
            np.arange(2, 2 + spec.fault_window), size=n, replace=False
        )
    )
    kinds = (
        [("timeout", spec.straggler_pe)] * spec.n_timeouts
        + [("draft", -1)] * spec.n_draft_corruptions
    )
    rng.shuffle(kinds)
    return {s: tuple(k) for s, k in zip(steps, kinds)}


@contextlib.contextmanager
def _inject_spec_faults(schedule: dict, world: int):
    """The speculative chaos seam (ISSUE 20): wrap
    ``SpeculativeBatcher.step`` (it overrides the base ``step``, so the
    :func:`_inject_faults` wrap would never fire). Scheduled "timeout"
    faults raise the usual by-absence straggler records; scheduled
    "draft" faults arm the batcher's sticky ``corrupt_draft_next`` flag
    — and RE-ARM it every step until a speculative round actually
    consumes it, so an idle step, a prompt-feed-only round, or a
    mid-schedule rebuild (fresh batcher, armed flag lost) cannot
    silently swallow a corruption the campaign's invariants charge
    for."""
    from triton_dist_tpu.serving.speculative import SpeculativeBatcher

    real_step = SpeculativeBatcher.step
    calls = {"n": 0}
    pending = {"draft": 0}

    def flaky(self):
        calls["n"] += 1
        fault = schedule.get(calls["n"])
        if fault is not None:
            kind, pe = fault
            if kind == "timeout":
                raise DistTimeoutError(
                    "batcher_step", _timeout_records(world, pe),
                    world_size=world,
                )
            pending["draft"] += 1   # kind == "draft"
        if pending["draft"]:
            self.corrupt_draft_next = True
        before = self.spec_draft_faults_injected
        out = real_step(self)
        if pending["draft"] and self.spec_draft_faults_injected > before:
            pending["draft"] -= 1
            # one corruption per round: disarm until the next one is due
            if not pending["draft"]:
                self.corrupt_draft_next = False
        return out

    SpeculativeBatcher.step = flaky
    try:
        yield calls
    finally:
        SpeculativeBatcher.step = real_step


def check_spec_invariants(eng, result: CampaignResult, offered_uids: set,
                          reference: dict, streams: dict) -> list:
    """The speculative campaign's green conditions: the standard
    unified-engine invariants (:func:`check_invariants`) plus the ISSUE
    20 contract — every scheduled draft corruption fired and was
    REJECTED by the verify pass (>= 1 rollback apiece), speculative
    rounds actually ran, and the finished set AND every finished token
    stream are byte-identical to the clean non-speculative
    ``reference`` run ({uid: tokens})."""
    fails = check_invariants(eng, result, offered_uids)
    spec = result.spec
    sp = result.snapshot.get("speculative")
    if sp is None:
        fails.append(
            "no speculative section in the engine snapshot — the "
            "campaign ran disarmed"
        )
        return fails
    if not sp["rounds"]:
        fails.append(
            "no speculative round ever ran — the draft+verify path this "
            "campaign exists to exercise was never entered (retune the "
            "spec)"
        )
    if sp["draft_faults_injected"] != spec.n_draft_corruptions:
        fails.append(
            f"draft corruptions fired {sp['draft_faults_injected']} != "
            f"scheduled {spec.n_draft_corruptions} — the chaos seam "
            f"never reached a speculative round (retune the spec)"
        )
    if sp["draft_faults_injected"] and (
        sp["rollback_total"] < sp["draft_faults_injected"]
    ):
        fails.append(
            f"rollbacks {sp['rollback_total']} < injected draft faults "
            f"{sp['draft_faults_injected']} — a corrupted draft token "
            f"survived the verify pass"
        )
    fin = {u for u, k in result.terminals.items() if k == "finished"}
    if fin != set(reference):
        fails.append(
            f"finished set diverged from the plain reference: "
            f"missing={sorted(set(reference) - fin)} "
            f"extra={sorted(fin - set(reference))}"
        )
    diverged = sorted(
        u for u in fin & set(reference) if streams.get(u) != reference[u]
    )
    if diverged:
        fails.append(
            f"token streams diverged from the clean non-speculative run "
            f"for {diverged} — acceptance/rollback/commit is not "
            f"stream-preserving"
        )
    return fails


def _run_speculative_campaign(spec: SoakSpec) -> CampaignResult:
    """One seeded speculative campaign (dispatched by
    :func:`run_campaign` when ``spec.spec_k > 0``): the unified engine
    with self-draft speculation armed, judged byte-for-byte against a
    clean plain run of the same trace (run first, outside the flight
    recorder, with its health/obs noise wiped before the judged
    run)."""
    import jax

    from triton_dist_tpu import config as tdt_config
    from triton_dist_tpu import resilience
    from triton_dist_tpu.serving import (
        ServingConfig,
        ServingEngine,
        SpecDecodeConfig,
        TrafficSpec,
        generate_trace,
    )
    from triton_dist_tpu.serving.metrics import SLOTargets
    from jax.sharding import Mesh

    if len(jax.devices()) < spec.world:
        raise RuntimeError(
            f"soak needs {spec.world} devices (run under "
            f"--xla_force_host_platform_device_count, as "
            f"scripts/chaos_soak.py and conftest.py do); have "
            f"{len(jax.devices())}"
        )
    cfgsnap = tdt_config.get_config()
    saved = (cfgsnap.elastic, cfgsnap.suspect_threshold,
             cfgsnap.probation_probes)
    resilience.reset()
    tdt_config.update(
        elastic=True, suspect_threshold=max(1, spec.n_timeouts),
        probation_probes=1,
    )
    try:
        from triton_dist_tpu.models import init_params
        from triton_dist_tpu.models.tp_transformer import TransformerConfig
        from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig
        from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig
        from jax.random import PRNGKey

        cfg = TransformerConfig(
            vocab=32, hidden=32, ffn=64, n_layers=1, n_q_heads=4,
            n_kv_heads=4, head_dim=8, batch=spec.batch, seq=8,
            ag_config=AGGemmConfig(8, 16, 16),
            rs_config=GemmRSConfig(8, 16, 16),
        )
        params = init_params(PRNGKey(1), cfg)
        mesh = Mesh(np.array(jax.devices()[:spec.world]), ("tp",))
        traffic = TrafficSpec(
            rate_rps=spec.rate_rps, n_requests=spec.n_requests,
            process="burst", burst_every_s=spec.burst_every_s,
            burst_n=spec.burst_n,
            prompt_len=("uniform", 2, 4), output_len=("uniform", 4, 8),
            vocab=cfg.vocab, seed=spec.seed, uid_prefix=f"sp{spec.seed}-",
            priority_mix=spec.priority_mix, deadline_ms=spec.deadline_ms,
        )

        def build_engine(sd, clock, tag):
            # no overload/deadline enforcement: shed decisions are
            # timing-dependent, and the reference comparison needs both
            # arms to finish the same request set
            return ServingEngine(
                cfg, params, mesh, s_max=spec.s_max, clock=clock,
                serving=ServingConfig(
                    max_queue=spec.max_queue,
                    virtual_step_s=spec.virtual_step_s,
                    probe_interval_steps=4,
                    slo=SLOTargets(ttft_ms=1500.0),
                    speculative=sd,
                ),
                obs_tag=tag,
            )

        ref_clock = _retry.FakeClock()
        with _retry.clock_scope(ref_clock):
            ref_eng = build_engine(None, ref_clock, "ref:")
            ref_done = ref_eng.serve(
                generate_trace(traffic), max_steps=spec.max_steps
            )
        reference = {
            u: list(r.tokens) for u, r in ref_done.items()
            if isinstance(r, Finished)
        }
        # wipe the reference run's (empty, but structurally possible)
        # health residue so the judged run's accounting stands alone
        resilience.reset()

        trace = generate_trace(traffic)
        schedule = _spec_fault_schedule(spec)
        clock = _retry.FakeClock()
        with _flight_recorder():
            with _retry.clock_scope(clock):
                eng = build_engine(
                    SpecDecodeConfig(
                        draft_cfg=cfg, draft_params=params, k=spec.spec_k
                    ),
                    clock, "",
                )
                error = None
                with _inject_spec_faults(schedule, spec.world) as calls:
                    try:
                        done = eng.serve(trace, max_steps=spec.max_steps)
                    except RuntimeError as exc:
                        error = f"{type(exc).__name__}: {exc}"
                        done = dict(eng.results)
            streams = {
                u: list(r.tokens) for u, r in done.items()
                if isinstance(r, Finished)
            }
            result = CampaignResult(
                spec=spec,
                terminals={u: _terminal_kind(r) for u, r in done.items()},
                n_steps_hint=calls["n"],
                rebuilds=eng.rebuilds,
                transitions=[
                    dataclasses.asdict(t)
                    for t in (eng._overload.transitions
                              if eng._overload else ())
                ],
                snapshot=eng.snapshot(),
                health=resilience.health.snapshot(),
                fingerprint="",
                failures=[],
                error=error,
            )
            result.fingerprint = campaign_fingerprint(result)
            offered = {a.request.uid for a in trace}
            result.failures = (
                check_spec_invariants(eng, result, offered, reference,
                                      streams)
                + check_blackbox_invariant(result.health)
            )
        return result
    finally:
        tdt_config.update(
            elastic=saved[0], suspect_threshold=saved[1],
            probation_probes=saved[2],
        )
        resilience.reset()


@contextlib.contextmanager
def _inject_pool_faults(schedule: dict, *, collapse_at: int):
    """The pool-aware chaos seam (ISSUE 13): only batcher steps running
    inside the PREFILL ``faults.pool_scope`` count (the decode pool and
    any unified engine are untouched). Scheduled ``timeout`` faults
    fabricate POOL-LOCAL by-absence records (straggler = pool position 1
    while the pool has one, else 0), and from step ``collapse_at`` on
    (when > 0) EVERY prefill step times out — the storm that quarantines
    the pool's PEs / exhausts its failure budget and collapses the
    topology to unified."""
    from triton_dist_tpu.models.decode import ContinuousBatcher
    from triton_dist_tpu.resilience import faults as _faults

    real_step = ContinuousBatcher.step
    calls = {"n": 0}

    def flaky(self):
        if _faults.current_pool() != "prefill":
            return real_step(self)
        calls["n"] += 1
        k = calls["n"]
        fault = schedule.get(k)
        storm = collapse_at and k >= collapse_at
        if storm or (fault is not None and fault[0] == "timeout"):
            w = int(self.mesh.shape[self.cfg.axis])
            straggler = 1 if w > 1 else 0
            recs = [
                {"pe": p, "kind": "barrier_all", "site": 0,
                 "status": "timeout", "expected": 1, "observed": 0,
                 "budget": 16}
                for p in range(w) if p != straggler
            ]
            raise DistTimeoutError("batcher_step", recs, world_size=w)
        return real_step(self)

    ContinuousBatcher.step = flaky
    try:
        yield calls
    finally:
        ContinuousBatcher.step = real_step


def check_disagg_invariants(eng, result: CampaignResult,
                            offered_uids: set) -> list:
    """The disagg campaign's green conditions: the four module-docstring
    invariants over the TWO-POOL composition, plus handoff-ladder and
    collapse accounting."""
    fails: list[str] = []
    snap = result.snapshot
    reqs = snap.get("requests", {})
    term = result.terminals
    spec = result.spec

    got = set(term)
    if got != offered_uids:
        fails.append(
            f"terminal census mismatch: missing={sorted(offered_uids - got)} "
            f"extra={sorted(got - offered_uids)}"
        )
    unknown = {u: k for u, k in term.items() if k.startswith("<unknown")}
    if unknown:
        fails.append(f"non-terminal results: {unknown}")

    if eng._states or eng._landings:
        fails.append(
            f"residual work after serve: in_flight={len(eng._states)} "
            f"pending_landings={len(eng._landings)}"
        )
    for name, pool in (("prefill", eng.prefill), ("decode", eng.decode)):
        if name == "prefill" and eng.collapsed:
            continue  # the dead pool's state is abandoned by design
        if pool._pending or not pool._batcher.idle:
            fails.append(f"pool {name} left queued/in-flight work behind")

    census: dict[str, int] = {}
    for k in term.values():
        census[k] = census.get(k, 0) + 1
    for name, want in (
        ("finished", census.get("finished", 0)),
        ("shed", census.get("shed", 0)),
        ("poisoned", census.get("poisoned", 0)),
    ):
        if reqs.get(name, 0) != want:
            fails.append(
                f"counter {name}={reqs.get(name, 0)} disagrees with "
                f"terminal census {want}"
            )
    ho = snap.get("handoff", {})
    if ho.get("transfers", 0) != (
        ho.get("delivered", 0) + ho.get("fallbacks", 0)
    ):
        fails.append(
            f"handoff ladder does not balance: transfers="
            f"{ho.get('transfers')} != delivered {ho.get('delivered')} + "
            f"fallbacks {ho.get('fallbacks')}"
        )
    if reqs.get("handoffs", 0) != ho.get("transfers", 0):
        fails.append(
            f"engine handoffs={reqs.get('handoffs', 0)} != plane "
            f"transfers {ho.get('transfers', 0)}"
        )
    hc = result.health.get("counters", {})
    if hc.get("kv_handoff:handoff_fallback", 0) != ho.get("fallbacks", 0):
        fails.append(
            f"health handoff_fallback="
            f"{hc.get('kv_handoff:handoff_fallback', 0)} != plane "
            f"fallbacks {ho.get('fallbacks', 0)}"
        )
    if spec.n_chunk_corruptions and not ho.get("canary_mismatches", 0):
        fails.append(
            "scheduled chunk corruption never fired — the handoff ladder "
            "this campaign advertises did not run (retune the spec)"
        )
    want_collapse = 1 if spec.collapse_at_step else 0
    if reqs.get("pool_collapses", 0) != want_collapse:
        fails.append(
            f"pool_collapses={reqs.get('pool_collapses', 0)} != scheduled "
            f"{want_collapse}"
        )
    if hc.get("serving_disagg:pool_collapse", 0) != want_collapse:
        fails.append(
            f"health pool_collapse="
            f"{hc.get('serving_disagg:pool_collapse', 0)} != scheduled "
            f"{want_collapse}"
        )
    if spec.n_timeouts and not snap.get("engine", {}).get("collapsed") and (
        snap.get("pools", {}).get("prefill", {})
        .get("engine", {}).get("world_size", spec.disagg_prefill_pes)
        >= spec.disagg_prefill_pes
    ):
        fails.append(
            "scheduled prefill straggler never shrank the pool — the "
            "mid-stream shrink arc did not run (retune the spec)"
        )
    return fails


def _run_disagg_campaign(spec: SoakSpec) -> CampaignResult:
    """One seeded two-pool campaign (dispatched by :func:`run_campaign`
    when ``spec.disagg_prefill_pes > 0``)."""
    import jax

    from triton_dist_tpu import config as tdt_config
    from triton_dist_tpu import resilience
    from triton_dist_tpu.resilience.faults import FaultPlan
    from triton_dist_tpu.serving import (
        DisaggServingConfig,
        DisaggServingEngine,
        HandoffConfig,
        OverloadConfig,
        ServingConfig,
        TrafficSpec,
        generate_trace,
    )
    from triton_dist_tpu.serving.metrics import SLOTargets
    from jax.sharding import Mesh

    if len(jax.devices()) < spec.world:
        raise RuntimeError(
            f"soak needs {spec.world} devices (run under "
            f"--xla_force_host_platform_device_count, as "
            f"scripts/chaos_soak.py and conftest.py do); have "
            f"{len(jax.devices())}"
        )
    cfgsnap = tdt_config.get_config()
    saved = (cfgsnap.elastic, cfgsnap.suspect_threshold,
             cfgsnap.probation_probes, cfgsnap.fault_plan)
    resilience.reset()
    tdt_config.update(
        elastic=True, suspect_threshold=max(1, spec.n_timeouts),
        probation_probes=1,
        fault_plan=(
            FaultPlan("bitflip", pe=-1, pool="decode",
                      max_triggers=spec.n_chunk_corruptions)
            if spec.n_chunk_corruptions else None
        ),
    )
    try:
        from triton_dist_tpu.models import init_params
        from triton_dist_tpu.models.tp_transformer import TransformerConfig
        from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig
        from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig
        from jax.random import PRNGKey

        cfg = TransformerConfig(
            vocab=32, hidden=32, ffn=64, n_layers=1, n_q_heads=4,
            n_kv_heads=4, head_dim=8, batch=spec.batch, seq=8,
            ag_config=AGGemmConfig(8, 16, 16),
            rs_config=GemmRSConfig(8, 16, 16),
        )
        params = init_params(PRNGKey(1), cfg)
        mesh = Mesh(np.array(jax.devices()[:spec.world]), ("tp",))
        traffic = TrafficSpec(
            rate_rps=spec.rate_rps, n_requests=spec.n_requests,
            process="burst", burst_every_s=spec.burst_every_s,
            burst_n=spec.burst_n,
            prompt_len=("uniform", 2, 6), output_len=("uniform", 2, 5),
            vocab=cfg.vocab, seed=spec.seed, uid_prefix=f"dg{spec.seed}-",
            priority_mix=spec.priority_mix, deadline_ms=spec.deadline_ms,
        )
        trace = generate_trace(traffic)
        schedule = fault_schedule(spec)
        clock = _retry.FakeClock()
        with _flight_recorder():
            with _retry.clock_scope(clock):
                eng = DisaggServingEngine(
                    cfg, params, mesh, s_max=spec.s_max, clock=clock,
                    serving=DisaggServingConfig(
                        prefill_pes=spec.disagg_prefill_pes,
                        virtual_step_s=spec.virtual_step_s,
                        slo=SLOTargets(ttft_ms=1500.0),
                        handoff=HandoffConfig(
                            page_tokens=4,
                            chunks_per_page=spec.handoff_chunks,
                            virtual_chunk_s=0.002,
                        ),
                        pipelined_admission=spec.pipelined_handoff,
                        prefill=ServingConfig(
                            max_queue=spec.max_queue, max_step_failures=3,
                            overload=OverloadConfig(
                                min_dwell_steps=4, window_steps=8,
                                retry_budget=4,
                            ),
                        ),
                        decode=ServingConfig(
                            max_queue=spec.max_queue,
                            overload=OverloadConfig(
                                min_dwell_steps=4, window_steps=8,
                                retry_budget=4,
                            ),
                        ),
                    ),
                )
                error = None
                with _inject_pool_faults(
                    schedule, collapse_at=spec.collapse_at_step
                ) as calls:
                    try:
                        done = eng.serve(trace, max_steps=spec.max_steps)
                    except RuntimeError as exc:
                        error = f"{type(exc).__name__}: {exc}"
                        done = dict(eng.results)
            transitions = []
            for pool in (eng.prefill, eng.decode):
                if pool._overload is not None:
                    transitions.extend(
                        dataclasses.asdict(t)
                        for t in pool._overload.transitions
                    )
            result = CampaignResult(
                spec=spec,
                terminals={u: _terminal_kind(r) for u, r in done.items()},
                n_steps_hint=calls["n"],
                rebuilds=eng.prefill.rebuilds + eng.decode.rebuilds,
                transitions=transitions,
                snapshot=eng.snapshot(),
                health=resilience.health.snapshot(),
                fingerprint="",
                failures=[],
                error=error,
            )
            result.fingerprint = campaign_fingerprint(result)
            offered = {a.request.uid for a in trace}
            # the bundle-per-flip check runs INSIDE the recorder scope
            # (the census dies with it)
            result.failures = (
                check_disagg_invariants(eng, result, offered)
                + check_blackbox_invariant(result.health)
            )
        return result
    finally:
        tdt_config.update(
            elastic=saved[0], suspect_threshold=saved[1],
            probation_probes=saved[2], fault_plan=saved[3],
        )
        resilience.reset()


@contextlib.contextmanager
def _inject_fleet_faults(*, kill_at: int, target: str):
    """The replica-aware chaos seam (ISSUE 16): only batcher steps
    running inside the kill target's ``metrics.label_scope(replica=...)``
    AND the decode ``faults.pool_scope`` count — every other replica and
    pool is untouched. From (pool-step) ``kill_at`` on, every such step
    times out: the decode pool's consecutive-failure budget exhausts,
    the typed :class:`UnrecoverableEngineError` propagates out of the
    replica's tick, and the ROUTER — not anything inside the replica —
    must recover every request it owned."""
    from triton_dist_tpu.models.decode import ContinuousBatcher
    from triton_dist_tpu.obs import metrics as _metrics
    from triton_dist_tpu.resilience import faults as _faults

    real_step = ContinuousBatcher.step
    calls = {"n": 0}

    def flaky(self):
        if (_metrics.current_labels().get("replica") != target
                or _faults.current_pool() != "decode"):
            return real_step(self)
        calls["n"] += 1
        if kill_at and calls["n"] >= kill_at:
            w = int(self.mesh.devices.size)
            recs = [
                {"pe": p, "kind": "barrier_all", "site": 0,
                 "status": "timeout", "expected": 1, "observed": 0,
                 "budget": 16}
                for p in range(w) if p != 0
            ]
            raise DistTimeoutError("batcher_step", recs, world_size=w)
        return real_step(self)

    ContinuousBatcher.step = flaky
    try:
        yield calls
    finally:
        ContinuousBatcher.step = real_step


@contextlib.contextmanager
def _inject_recovery_faults(*, kill_at: int, revive_at: int, target: str,
                            strag_at: int, storm_at: int, survivor: str):
    """The recovery-plane chaos seam (ISSUE 17): three composed fault
    arcs, each keyed on the replica ``metrics.label_scope`` + pool
    ``faults.pool_scope`` ambient labels so nothing leaks across
    replicas.

    - ``target`` decode storm over GLOBAL decode steps ``[kill_at,
      revive_at)`` — global (any replica's decode step advances the
      window) because the dead target's own counter freezes at death,
      and a window keyed on it would never close. While the storm
      lasts, ``elastic.probe_world`` is ALSO gated false for the
      target, so the router's resurrection probes fail honestly until
      the window clears; the first clean round after ``revive_at``
      re-admits the replica.
    - ``survivor`` decode straggler pair at its OWN pool steps
      ``[strag_at, strag_at+2)``: two strikes on the silent PE hit the
      quarantine threshold without exhausting the step-failure budget —
      pool shrinks, serves degraded, then probation regrows it.
    - ``survivor`` prefill storm at its OWN pool steps ``[storm_at,
      storm_at+6)``: long enough to exhaust the consecutive-failure
      budget even across a mid-storm quarantine rebuild — the pool
      dies, the topology collapses to unified, and the clean probation
      window after the storm un-collapses it."""
    from triton_dist_tpu.models.decode import ContinuousBatcher
    from triton_dist_tpu.obs import metrics as _metrics
    from triton_dist_tpu.resilience import elastic as _elastic
    from triton_dist_tpu.resilience import faults as _faults

    real_step = ContinuousBatcher.step
    real_probe = _elastic.probe_world
    calls = {"n": 0}
    own: dict[tuple, int] = {}

    def _storming() -> bool:
        if not kill_at or calls["n"] < kill_at:
            return False
        return not revive_at or calls["n"] < revive_at

    def _timeout(w: int, silent: int) -> DistTimeoutError:
        recs = [
            {"pe": p, "kind": "barrier_all", "site": 0,
             "status": "timeout", "expected": 1, "observed": 0,
             "budget": 16}
            for p in range(w) if p != silent
        ]
        return DistTimeoutError("batcher_step", recs, world_size=w)

    def flaky(self):
        rep = _metrics.current_labels().get("replica")
        pool = _faults.current_pool()
        if rep is None or pool not in ("prefill", "decode"):
            return real_step(self)
        w = int(self.mesh.devices.size)
        mine = own[(rep, pool)] = own.get((rep, pool), 0) + 1
        if pool == "decode":
            calls["n"] += 1
            if rep == target and _storming():
                raise _timeout(w, 0)
            if (rep == survivor and strag_at
                    and strag_at <= mine < strag_at + 2):
                raise _timeout(w, 1 % w)
        elif (rep == survivor and storm_at
                and storm_at <= mine < storm_at + 6):
            raise _timeout(w, 0)
        return real_step(self)

    def gated_probe(mesh, axis="tp"):
        if (_metrics.current_labels().get("replica") == target
                and _storming()):
            return False
        return real_probe(mesh, axis=axis)

    ContinuousBatcher.step = flaky
    _elastic.probe_world = gated_probe
    try:
        yield calls
    finally:
        ContinuousBatcher.step = real_step
        _elastic.probe_world = real_probe


def check_fleet_invariants(fl, result: CampaignResult,
                           offered_uids: set) -> list:
    """The fleet campaign's green conditions: the module-docstring
    invariants over the N-replica composition — zero lost across a
    replica death, router accounting balance, and failover/health
    agreement."""
    fails: list[str] = []
    snap = result.snapshot
    reqs = snap.get("requests", {})
    term = result.terminals
    spec = result.spec

    # 1. no lost request — across replica death and re-offer
    got = set(term)
    if got != offered_uids:
        fails.append(
            f"terminal census mismatch: missing={sorted(offered_uids - got)} "
            f"extra={sorted(got - offered_uids)}"
        )
    unknown = {u: k for u, k in term.items() if k.startswith("<unknown")}
    if unknown:
        fails.append(f"non-terminal results: {unknown}")

    # 2. no residue — at the router and inside every surviving replica
    if fl._states:
        fails.append(
            f"router residue after serve: in_flight={len(fl._states)}"
        )
    for rep in fl.replicas:
        if rep.alive and rep.engine._states:
            fails.append(
                f"replica {rep.name} left {len(rep.engine._states)} "
                f"request(s) behind"
            )

    # 3. accounting balance at the fleet tier: every _submit_offer is
    # counted, so submitted == offered + reject re-offers + failover
    # re-offers — a silently double-routed or dropped offer breaks this
    census: dict[str, int] = {}
    for k in term.values():
        census[k] = census.get(k, 0) + 1
    for name, want in (
        ("finished", census.get("finished", 0)),
        ("shed", census.get("shed", 0)),
        ("poisoned", census.get("poisoned", 0)),
    ):
        if reqs.get(name, 0) != want:
            fails.append(
                f"fleet counter {name}={reqs.get(name, 0)} disagrees "
                f"with terminal census {want}"
            )
    want_submitted = (len(offered_uids) + reqs.get("reoffered", 0)
                      + reqs.get("failover_reoffered", 0))
    if reqs.get("submitted", 0) != want_submitted:
        fails.append(
            f"submitted={reqs.get('submitted', 0)} != offered "
            f"{len(offered_uids)} + reoffered {reqs.get('reoffered', 0)} "
            f"+ failover_reoffered {reqs.get('failover_reoffered', 0)}"
        )

    # 4. the scheduled faults actually ran, and health agrees
    hc = result.health.get("counters", {})
    want_failovers = 1 if spec.replica_kill_at_step else 0
    if reqs.get("failovers", 0) != want_failovers:
        fails.append(
            f"failovers={reqs.get('failovers', 0)} != scheduled "
            f"{want_failovers}"
        )
    if hc.get("serving_fleet:replica_failover", 0) != want_failovers:
        fails.append(
            f"health replica_failover="
            f"{hc.get('serving_fleet:replica_failover', 0)} != scheduled "
            f"{want_failovers}"
        )
    if spec.replica_kill_at_step and not spec.fleet_recovery:
        dead = snap.get("engine", {}).get("dead", [])
        want_dead = f"r{spec.replica_kill_target}"
        if dead != [want_dead]:
            fails.append(
                f"dead replicas {dead} != [{want_dead!r}] — the storm "
                f"killed the wrong replica (or none)"
            )
    if spec.n_chunk_corruptions and not hc.get(
        "kv_handoff:handoff_retry", 0
    ):
        fails.append(
            "scheduled chunk corruption never fired — the handoff ladder "
            "this campaign advertises did not run (retune the spec)"
        )

    # 5. the recovery plane (ISSUE 17): every arc the spec scheduled
    # must have completed its round trip, and PE strikes must have
    # stayed inside their replica's scope
    if spec.fleet_recovery:
        target = f"r{spec.replica_kill_target}"
        if spec.replica_kill_at_step and spec.replica_revive_at_step:
            dead = snap.get("engine", {}).get("dead", [])
            if dead:
                fails.append(
                    f"replicas {dead} still dead after the storm window "
                    f"closed — resurrection never completed"
                )
            if hc.get("serving_fleet:replica_readmit", 0) < 1:
                fails.append(
                    "no replica_readmit health event — the scheduled "
                    "resurrection arc did not run"
                )
            fin = (
                snap.get("replicas", {}).get(target, {})
                .get("requests", {}).get("finished", 0)
            )
            if not fin:
                fails.append(
                    f"resurrected {target} finished 0 requests — its "
                    f"fresh engine never served (ramp too long, or the "
                    f"traffic tail ended before re-admission)"
                )
        if spec.pool_strag_at_step and not hc.get(
            "serving_pool_decode:pool_regrow", 0
        ):
            fails.append(
                "no decode pool_regrow health event — the scheduled "
                "straggler quarantine never probed back in"
            )
        if spec.prefill_storm_at_step:
            if not hc.get("serving_disagg:pool_collapse", 0):
                fails.append(
                    "no pool_collapse — the scheduled prefill storm "
                    "never killed the pool (retune the spec)"
                )
            if not hc.get("serving_disagg:pool_uncollapse", 0):
                fails.append(
                    "no pool_uncollapse health event — the collapsed "
                    "topology never re-carved after its clean window"
                )
        # scope isolation: every PE strike family must carry its
        # replica owner — a bare ``pe{N}`` family means a strike
        # escaped into the process-global namespace (the exact
        # cross-contamination scoped namespaces exist to prevent)
        owners: set[str] = set()
        for key in hc:
            fam = key.rsplit(":", 1)[0]
            if not fam.startswith("pe") or not fam[2:3].isdigit():
                continue
            if "@" not in fam:
                fails.append(
                    f"unscoped PE health family {fam!r} in an "
                    f"elastic_scope fleet — a strike crossed into the "
                    f"default namespace"
                )
            else:
                owners.add(fam.split("@", 1)[1])
        replica_names = {r.name for r in fl.replicas}
        stray = owners - replica_names
        if stray:
            fails.append(
                f"PE strike owners {sorted(stray)} are not replicas "
                f"{sorted(replica_names)}"
            )
    return fails


def _run_fleet_campaign(spec: SoakSpec) -> CampaignResult:
    """One seeded fleet campaign (dispatched by :func:`run_campaign`
    when ``spec.fleet_replicas > 0``): N disaggregated replicas behind
    the router, chunk corruption on the decode handoff seam, and — when
    scheduled — one replica killed mid-burst.

    Two shapes share this runner. The LEGACY shape
    (``fleet_recovery=False``) keeps elastic DISABLED: before ISSUE 17,
    PE strike attribution was one process-global namespace indexed by
    mesh position, and N replicas' identically-numbered slices would
    have cross-contaminated it (a strike on r0's decode PE would have
    quarantined r1's) — that shape pins the failover-only posture.
    The RECOVERY shape (``SoakSpec.fleet_recovery_spec``) runs elastic
    ON with ``FleetConfig(elastic_scope=True)``: each replica owns an
    :class:`~triton_dist_tpu.resilience.elastic.ElasticScope`, strikes
    land in ``pe{N}@r{i}`` health families, and the full recovery
    ladder is armed — pool probation regrow
    (``DisaggServingConfig.pool_probe_steps``), reversible collapse
    (``collapse_probation_steps``), and replica resurrection
    (``FleetConfig.resurrect``). docs/resilience.md "Recovery
    plane"."""
    import jax

    from triton_dist_tpu import config as tdt_config
    from triton_dist_tpu import resilience
    from triton_dist_tpu.resilience.faults import FaultPlan
    from triton_dist_tpu.serving import (
        DisaggServingConfig,
        HandoffConfig,
        OverloadConfig,
        ServingConfig,
        TrafficSpec,
        generate_trace,
    )
    from triton_dist_tpu.serving.fleet import (
        FleetConfig,
        FleetRouter,
        ResurrectConfig,
    )
    from triton_dist_tpu.serving.metrics import SLOTargets
    from jax.sharding import Mesh

    if len(jax.devices()) < spec.world:
        raise RuntimeError(
            f"soak needs {spec.world} devices (run under "
            f"--xla_force_host_platform_device_count, as "
            f"scripts/chaos_soak.py and conftest.py do); have "
            f"{len(jax.devices())}"
        )
    cfgsnap = tdt_config.get_config()
    saved = (cfgsnap.elastic, cfgsnap.fault_plan)
    resilience.reset()
    recovery = spec.fleet_recovery
    tdt_config.update(
        elastic=bool(recovery),
        fault_plan=(
            FaultPlan("bitflip", pe=-1, pool="decode",
                      max_triggers=spec.n_chunk_corruptions)
            if spec.n_chunk_corruptions else None
        ),
    )
    try:
        from triton_dist_tpu.models import init_params
        from triton_dist_tpu.models.tp_transformer import TransformerConfig
        from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig
        from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig
        from jax.random import PRNGKey

        cfg = TransformerConfig(
            vocab=32, hidden=32, ffn=64, n_layers=1, n_q_heads=4,
            n_kv_heads=2, head_dim=8, batch=spec.batch, seq=8,
            ag_config=AGGemmConfig(8, 16, 16),
            rs_config=GemmRSConfig(8, 16, 16),
        )
        params = init_params(PRNGKey(1), cfg)
        mesh = Mesh(np.array(jax.devices()[:spec.world]), ("tp",))
        traffic = TrafficSpec(
            rate_rps=spec.rate_rps, n_requests=spec.n_requests,
            process="burst", burst_every_s=spec.burst_every_s,
            burst_n=spec.burst_n,
            prompt_len=("uniform", 2, 6), output_len=("uniform", 2, 5),
            vocab=cfg.vocab, seed=spec.seed, uid_prefix=f"fl{spec.seed}-",
            priority_mix=spec.priority_mix, deadline_ms=spec.deadline_ms,
        )
        trace = generate_trace(traffic)
        clock = _retry.FakeClock()
        pool_serving = ServingConfig(
            max_queue=spec.max_queue, max_step_failures=3,
            overload=OverloadConfig(
                min_dwell_steps=4, window_steps=8, retry_budget=4,
            ),
        )
        with _flight_recorder():
            with _retry.clock_scope(clock):
                fl = FleetRouter(
                    cfg, params, mesh, s_max=spec.s_max, clock=clock,
                    fleet=FleetConfig(
                        replicas=spec.fleet_replicas,
                        disagg=DisaggServingConfig(
                            prefill_pes=spec.disagg_prefill_pes,
                            virtual_step_s=spec.virtual_step_s,
                            slo=SLOTargets(ttft_ms=1500.0),
                            handoff=HandoffConfig(
                                page_tokens=4,
                                chunks_per_page=spec.handoff_chunks,
                                virtual_chunk_s=0.002,
                            ),
                            prefill=pool_serving,
                            decode=pool_serving,
                            pool_probe_steps=3 if recovery else None,
                            collapse_probation_steps=(
                                5 if recovery else None
                            ),
                        ),
                        slo=SLOTargets(ttft_ms=1500.0),
                        elastic_scope=recovery,
                        resurrect=(
                            ResurrectConfig(probe_steps=5, ramp_steps=2)
                            if recovery else None
                        ),
                    ),
                )
                error = None
                if recovery:
                    survivor = (
                        f"r{(spec.replica_kill_target + 1) % spec.fleet_replicas}"
                    )
                    injector = _inject_recovery_faults(
                        kill_at=spec.replica_kill_at_step,
                        revive_at=spec.replica_revive_at_step,
                        target=f"r{spec.replica_kill_target}",
                        strag_at=spec.pool_strag_at_step,
                        storm_at=spec.prefill_storm_at_step,
                        survivor=survivor,
                    )
                else:
                    injector = _inject_fleet_faults(
                        kill_at=spec.replica_kill_at_step,
                        target=f"r{spec.replica_kill_target}",
                    )
                with injector as calls:
                    try:
                        done = fl.serve(trace, max_steps=spec.max_steps)
                    except RuntimeError as exc:
                        error = f"{type(exc).__name__}: {exc}"
                        done = dict(fl.results)
            transitions = []
            for rep in fl.replicas:
                for pool in (rep.engine.prefill, rep.engine.decode):
                    if pool._overload is not None:
                        transitions.extend(
                            dataclasses.asdict(t)
                            for t in pool._overload.transitions
                        )
            result = CampaignResult(
                spec=spec,
                terminals={u: _terminal_kind(r) for u, r in done.items()},
                n_steps_hint=calls["n"],
                rebuilds=sum(
                    rep.engine.prefill.rebuilds + rep.engine.decode.rebuilds
                    for rep in fl.replicas
                ),
                transitions=transitions,
                snapshot=fl.snapshot(),
                health=resilience.health.snapshot(),
                fingerprint="",
                failures=[],
                error=error,
            )
            result.fingerprint = campaign_fingerprint(result)
            offered = {a.request.uid for a in trace}
            result.failures = (
                check_fleet_invariants(fl, result, offered)
                + check_blackbox_invariant(result.health)
            )
        return result
    finally:
        tdt_config.update(elastic=saved[0], fault_plan=saved[1])
        resilience.reset()


def run_campaign(spec: SoakSpec, *, model=None) -> CampaignResult:
    """Run one seeded campaign and evaluate its invariants. Process-global
    state (config, resilience registries, module clock) is snapshotted
    and restored, so campaigns compose with each other and with a live
    pytest session. ``model=(cfg, params)`` overrides the built-in tiny
    4-PE transformer (the test fixture reuse hook). A spec with
    ``disagg_prefill_pes > 0`` runs the two-pool topology campaign
    (:func:`check_disagg_invariants`); ``fleet_replicas > 0`` runs the
    N-replica router campaign (:func:`check_fleet_invariants`);
    ``spec_k > 0`` runs the speculative-decoding campaign
    (:func:`check_spec_invariants`)."""
    if spec.validate().fleet_replicas:
        return _run_fleet_campaign(spec)
    if spec.disagg_prefill_pes:
        return _run_disagg_campaign(spec)
    if spec.spec_k:
        return _run_speculative_campaign(spec)
    import jax

    from triton_dist_tpu import config as tdt_config
    from triton_dist_tpu import resilience
    from triton_dist_tpu.serving import (
        OverloadConfig,
        ServingConfig,
        ServingEngine,
        TrafficSpec,
        generate_trace,
    )
    from triton_dist_tpu.serving.metrics import SLOTargets
    from jax.sharding import Mesh

    spec.validate()
    if len(jax.devices()) < spec.world:
        raise RuntimeError(
            f"soak needs {spec.world} devices (run under "
            f"--xla_force_host_platform_device_count, as scripts/chaos_soak.py "
            f"and conftest.py do); have {len(jax.devices())}"
        )
    cfgsnap = tdt_config.get_config()
    saved = (cfgsnap.elastic, cfgsnap.suspect_threshold,
             cfgsnap.probation_probes)
    resilience.reset()
    tdt_config.update(
        elastic=True, suspect_threshold=spec.n_timeouts, probation_probes=1
    )
    try:
        if model is None:
            from triton_dist_tpu.models import init_params
            from triton_dist_tpu.models.tp_transformer import TransformerConfig
            from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig
            from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig

            # n_kv_heads == world so the (world-1)-survivor mesh is
            # model-invalid and a shrink must land on world//2 — the
            # interesting serviceable-mesh case, mid-overload
            cfg = TransformerConfig(
                vocab=32, hidden=32, ffn=64, n_layers=1, n_q_heads=4,
                n_kv_heads=4, head_dim=8, batch=spec.batch, seq=8,
                ag_config=AGGemmConfig(8, 16, 16),
                rs_config=GemmRSConfig(8, 16, 16),
            )
            from jax.random import PRNGKey

            params = init_params(PRNGKey(1), cfg)
        else:
            cfg, params = model
        mesh = Mesh(np.array(jax.devices()[:spec.world]), ("tp",))
        px_traffic = {}
        if spec.prefix_pool:
            px_traffic = dict(
                prefix_pool=spec.prefix_pool,
                prefix_len=("fixed", spec.prefix_tokens),
                prefix_share=spec.prefix_share,
            )
        traffic = TrafficSpec(
            rate_rps=spec.rate_rps, n_requests=spec.n_requests,
            process="burst", burst_every_s=spec.burst_every_s,
            burst_n=spec.burst_n,
            prompt_len=("uniform", 2, 4), output_len=("uniform", 2, 5),
            vocab=cfg.vocab, seed=spec.seed, uid_prefix=f"c{spec.seed}-",
            priority_mix=spec.priority_mix, deadline_ms=spec.deadline_ms,
            **px_traffic,
        )
        trace = generate_trace(traffic)
        schedule = fault_schedule(spec)
        batcher_kw = {}
        if spec.page_size:
            batcher_kw["page_size"] = spec.page_size
        clock = _retry.FakeClock()
        with _flight_recorder():
            with _retry.clock_scope(clock):
                from triton_dist_tpu.models.prefix_cache import (
                    PrefixCacheConfig,
                )

                eng = ServingEngine(
                    cfg, params, mesh, s_max=spec.s_max, clock=clock,
                    serving=ServingConfig(
                        max_queue=spec.max_queue,
                        virtual_step_s=spec.virtual_step_s,
                        probe_interval_steps=4,
                        slo=SLOTargets(ttft_ms=1500.0),
                        overload=OverloadConfig(
                            min_dwell_steps=4, window_steps=8,
                            retry_budget=4,
                            # identity downshift: brownout2 still drives
                            # the rebuild+replay arc (composition with the
                            # fault rebuilds is exactly what the soak is
                            # for)
                            downshift=lambda c: c,
                        ),
                        prefix_cache=(
                            PrefixCacheConfig() if spec.prefix_pool else None
                        ),
                    ),
                    **batcher_kw,
                )
                error = None
                with _inject_faults(schedule, spec.world) as calls:
                    try:
                        done = eng.serve(trace, max_steps=spec.max_steps)
                    except RuntimeError as exc:
                        error = f"{type(exc).__name__}: {exc}"
                        done = dict(eng.results)
            result = CampaignResult(
                spec=spec,
                terminals={u: _terminal_kind(r) for u, r in done.items()},
                n_steps_hint=calls["n"],
                rebuilds=eng.rebuilds,
                transitions=[
                    dataclasses.asdict(t)
                    for t in (eng._overload.transitions
                              if eng._overload else ())
                ],
                snapshot=eng.snapshot(),
                health=resilience.health.snapshot(),
                fingerprint="",
                failures=[],
                error=error,
            )
            result.fingerprint = campaign_fingerprint(result)
            offered = {a.request.uid for a in trace}
            # one bundle per health-flipping event (ISSUE 15) — judged
            # while the campaign's flight-recorder scope is still armed
            result.failures = (
                check_invariants(eng, result, offered)
                + check_blackbox_invariant(result.health)
            )
        return result
    finally:
        tdt_config.update(
            elastic=saved[0], suspect_threshold=saved[1],
            probation_probes=saved[2],
        )
        resilience.reset()
