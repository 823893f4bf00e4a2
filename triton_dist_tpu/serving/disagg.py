"""Disaggregated prefill/decode serving (ISSUE 13 tentpole, ROADMAP #2).

Production fleets split prefill and decode onto separate accelerator
pools because the two phases have opposite rooflines: prefill is
MXU-bound over whole prompts, decode is bandwidth-bound one token at a
time — and a slot that holds a unified engine's batch for
``prefill + decode`` steps makes queued TTFT explode at high offered
load. This module composes the repo's existing machinery into that
two-pool topology, with **robustness as the contract**:

- **Two pools, one mesh** — a 1-D serving mesh is carved into a prefill
  pool (the first ``prefill_pes`` devices) and a decode pool (the rest);
  each pool runs its own :class:`~triton_dist_tpu.serving.engine.
  ServingEngine` (its own ``ContinuousBatcher``, its own elastic
  shrink/rebuild arc with POOL-SCOPED PE attribution, its own
  :class:`~triton_dist_tpu.serving.overload.OverloadController` — the
  per-pool admission story PR 11 pre-built).
- **The request lifecycle** — submit → prefill pool (prompt feed + the
  FIRST token: the client's TTFT comes from the prefill pool) → the
  **KV handoff** (``serving/handoff.py``: the ``ops/kv_stream.py``
  chunked wire with per-chunk canaries, modeled at the documented host
  seam) → decode-pool admission **on last-page-landed** → decode to
  completion. The decode pool re-materializes the landed KV by feeding
  the prompt (the host-tier landing form — byte-identical by the
  prefix-replay containment argument; feed steps ride decode steps the
  way DMA landings overlap compute), regenerating the first token as
  position L's decode — the cross-pool consistency check: it must equal
  the prefill pool's token.
- **The trie is the transfer manifest** — pages are keyed as the
  ISSUE 12 radix trie keys them, so shared prefixes stream ONCE; with
  the prefix cache armed on the prefill pool they are also PREFILLED
  once.
- **Degradation ladder** (never a lost request):

  * a corrupt/dropped chunk walks the handoff guard ladder — re-send →
    re-stream → decode-local cold re-prefill — with the culprit PE
    struck through the elastic state machine (``serving/handoff.py``);
  * a browned-out or shrunk prefill pool sheds NEW work to decode-local
    prefill (its overload ladder at ``local_prefill_rung``+, or a
    Rejected at its door, routes the request straight into the decode
    pool — cold, correct, slower);
  * the prefill pool losing its LAST serviceable PE **collapses the
    topology to the unified engine**: every in-flight prefill replays
    into the decode pool (the cold-restart contract regenerates all
    streams byte-identically), recorded as a ``pool_collapse`` health
    event; the decode pool IS the unified engine from then on.

Every timestamp rides the injectable clock; ``virtual_step_s`` charges
ONE step per topology tick (the pools run concurrently in a real fleet,
so stepping both pools in one tick costs one step of virtual time).
"""

from __future__ import annotations

import dataclasses
import heapq
from typing import Any

import numpy as np
from jax.sharding import Mesh

from triton_dist_tpu import obs as _obs
from triton_dist_tpu.obs import metrics as _mx
from triton_dist_tpu.models.decode import (
    Request, STATE_CACHE_KINDS, refuse_ring, refuse_state,
)
from triton_dist_tpu.resilience import elastic, faults, health
from triton_dist_tpu.resilience import retry as _retry
from triton_dist_tpu.serving.engine import (
    Finished,
    Poisoned,
    Rejected,
    ServingConfig,
    ServingEngine,
    Shed,
    UnrecoverableEngineError,
)
from triton_dist_tpu.serving.handoff import (
    DECODE_POOL,
    HandoffConfig,
    HandoffPlane,
    PREFILL_POOL,
)
from triton_dist_tpu.serving.metrics import ServingMetrics, SLOTargets
from triton_dist_tpu.serving.overload import PRIORITIES


class PoolCollapse(RuntimeError):
    """A pool has no serviceable PE left (every device quarantined, or
    no survivor count passes the model's divisibility predicate)."""


class _PoolEngine(ServingEngine):
    """A :class:`ServingEngine` that serves ONE pool of a disaggregated
    topology: its elastic arc runs pool-scoped — quarantined-PE indices
    are the TOPOLOGY's global numbering (pool position + ``pe_offset``),
    so a struck decode PE can never shrink the prefill pool — and every
    step runs inside the pool's ``faults.pool_scope`` (the FaultPlan
    ``pool=`` injection seam). With ``pool_probe_steps`` armed (ISSUE 17
    recovery plane) the pool runs its own probation rounds: the probe
    barriers the POOL sub-mesh only, with the candidate set pinned to
    this pool's global indices, and re-admitted PEs rejoin mid-serve
    through the ordinary rebuild+replay arc. ``pool_probe_steps=None``
    keeps the pre-recovery posture byte-identically: quarantined pool
    PEs stay out."""

    def __init__(self, *args, pool_name: str, pe_offset: int,
                 pool_probe_steps: "int | None" = None, **kw):
        self._pool_name = str(pool_name)
        self._pe_offset = int(pe_offset)
        self._pool_probe_steps = (
            None if pool_probe_steps is None else int(pool_probe_steps)
        )
        super().__init__(*args, **kw)
        self.family = f"serving_pool_{self._pool_name}"

    def _pool_quarantined(self) -> list[int]:
        """This pool's quarantined PEs, GLOBAL indices."""
        n = int(self.full_mesh.devices.size)
        lo, hi = self._pe_offset, self._pe_offset + n
        return [pe for pe in self._elastic.quarantined_pes()
                if lo <= pe < hi]

    def _target_mesh(self):
        if self.full_mesh.devices.ndim != 1 or not elastic.enabled():
            return self.full_mesh
        n = int(self.full_mesh.devices.size)
        dropped = {
            pe - self._pe_offset
            for pe in self._elastic.quarantined_pes()
            if self._pe_offset <= pe < self._pe_offset + n
        }
        if not dropped:
            return self.full_mesh
        devs = [
            d for i, d in enumerate(self.full_mesh.devices.flat)
            if i not in dropped
        ]
        for k in range(len(devs), 0, -1):
            if self._world_ok(k):
                return Mesh(np.array(devs[:k]), (self.cfg.axis,))
        raise PoolCollapse(
            f"pool {self._pool_name!r}: no serviceable world among "
            f"{len(devs)} survivor(s) of {n} "
            f"(quarantined pool positions: {sorted(dropped)})"
        )

    def _attribute_timeout(self, exc: BaseException) -> None:
        # pool-scoped by-absence attribution: the records name POOL
        # positions; the strike lands on the global index
        if not elastic.enabled():
            return
        err = _retry.timeout_in_chain(exc)
        if err is None or getattr(err, "world_size", None) is None:
            return
        pe = elastic.attribute_straggler(err.records, int(err.world_size))
        if pe is not None:
            self._elastic.report_timeout(pe + self._pe_offset,
                                         family=self.family)

    def _attribute_integrity(self, exc: BaseException) -> None:
        if not elastic.enabled():
            return
        from triton_dist_tpu.resilience.integrity import integrity_in_chain

        err = integrity_in_chain(exc)
        if err is None or not err.records:
            return
        world = getattr(err, "world_size", None)
        for r in err.records:
            pe = int(r.get("pe", -1))
            if pe < 0 or (world is not None and pe >= int(world)):
                continue
            self._elastic.report_corruption(pe + self._pe_offset,
                                            family=self.family)

    def _maybe_probe(self) -> None:
        """Pool probation regrow (ISSUE 17, tentpole b). The historical
        barrier-scope problem — a probation round would barrier the
        pool's sub-mesh against GLOBAL quarantine indices — is solved by
        probing the pool sub-mesh inside the pool's own fault scope (we
        run inside ``_step_once``'s ``faults.pool_scope``) with the
        candidate set pinned via ``pes=`` to this pool's slice of the
        global numbering, so one pool's failed probe can never reset the
        other pool's probation counters (satellite 6)."""
        if self._pool_probe_steps is None:
            return  # pre-recovery posture: struck pool PEs stay out
        if self.full_mesh.devices.ndim != 1 or not elastic.enabled():
            return
        mine = self._pool_quarantined()
        if not mine:
            self._steps_since_probe = 0
            return
        self._steps_since_probe += 1
        if self._steps_since_probe < self._pool_probe_steps:
            return
        self._steps_since_probe = 0
        self._elastic.probe_quarantined(
            self.full_mesh, axis=self.cfg.axis, pes=mine,
        )
        target = self._target_mesh()
        if list(target.devices.flat) != list(self.mesh.devices.flat):
            rejoined = [
                pe for pe in mine
                if self._elastic.state(pe) != elastic.QUARANTINED
            ]
            health.record_pool_regrow(
                self.family, self._pool_name,
                world=int(target.devices.size), pes=rejoined,
            )
            _mx.counter("serving_pool_regrows_total", engine=self.family)
            self._rebuild("probation re-admission regrew the pool")

    def _step_once(self) -> bool:
        with faults.pool_scope(self._pool_name):
            return super()._step_once()


@dataclasses.dataclass(frozen=True)
class DisaggServingConfig:
    """Policy of the two-pool topology.

    prefill_pes:   devices carved off the FRONT of the mesh for the
                   prefill pool (the rest decode).
    handoff:       the KV handoff plane policy (wire, chunking, the
                   guard-ladder retry/re-stream bounds).
    prefill / decode: each pool's :class:`ServingConfig` — its own
                   queue bound, admission policy, OverloadConfig (one
                   controller per pool), and — prefill side — the
                   ISSUE 12 prefix cache. Pool ``virtual_step_s`` must
                   stay None: the COORDINATOR charges one step per
                   topology tick (pools run concurrently).
    virtual_step_s: that per-tick charge (None = real time).
    local_prefill_rung: prefill-pool overload rung (0=normal ..
                   3=shed_all_batch) at/above which NEW submissions
                   bypass the prefill pool into decode-local prefill —
                   the brownout shed path.
    slo:           end-to-end targets scored at the coordinator tier.
    pool_probe_steps: ISSUE 17 recovery plane — every N worked pool
                   steps with quarantined PEs in the pool's slice, the
                   pool runs a probation probe round over its OWN
                   sub-mesh (candidates pinned to its global indices);
                   re-admitted PEs rejoin mid-serve through rebuild+
                   replay. None (default) keeps the pre-recovery
                   posture byte-identically: struck pool PEs stay out.
    collapse_probation_steps: ISSUE 17 recovery plane — after N clean
                   (rebuild-free, worked) unified ticks post-collapse
                   AND a clean prefill-slice probe round, the
                   coordinator re-carves the two-pool topology
                   (un-collapse). In-flight requests finish where they
                   run; new submissions take the disagg path again.
                   None (default): collapse stays terminal, byte-
                   identically.
    pipelined_admission: ISSUE 18 — admit a delivered handoff into the
                   decode pool at its FIRST page's landing time
                   (``HandoffResult.page_landings[0]``) instead of the
                   last (``t_landed``): the decode pool's suffix-only
                   ranged prefill can start attending page 0 while
                   later pages are still on the wire, overlapping
                   transfer with decode-side work. Fallback outcomes
                   (rung 3: decode-local cold re-prefill — no landed
                   pages to pipeline over) keep the last-page gate.
                   Host-tier only: no kv_stream signal edges change.
                   False (default) keeps last-page-landed admission
                   byte-identically.
    """

    prefill_pes: int = 1
    handoff: HandoffConfig = HandoffConfig()
    prefill: ServingConfig = ServingConfig()
    decode: ServingConfig = ServingConfig()
    virtual_step_s: float | None = None
    local_prefill_rung: int = 2
    slo: SLOTargets | None = None
    max_steps_idle: int = 4
    pool_probe_steps: int | None = None
    collapse_probation_steps: int | None = None
    pipelined_admission: bool = False

    def validate(self) -> "DisaggServingConfig":
        if self.prefill_pes < 1:
            raise ValueError(
                f"prefill_pes must be >= 1, got {self.prefill_pes}"
            )
        if self.pool_probe_steps is not None and self.pool_probe_steps < 1:
            raise ValueError(
                f"pool_probe_steps must be >= 1 (or None to disarm), got "
                f"{self.pool_probe_steps}"
            )
        if (self.collapse_probation_steps is not None
                and self.collapse_probation_steps < 1):
            raise ValueError(
                f"collapse_probation_steps must be >= 1 (or None to "
                f"disarm), got {self.collapse_probation_steps}"
            )
        if not 1 <= self.local_prefill_rung <= 3:
            raise ValueError(
                f"local_prefill_rung must be in [1, 3], got "
                f"{self.local_prefill_rung}"
            )
        for name, sc in (("prefill", self.prefill), ("decode", self.decode)):
            sc.validate()
            if sc.virtual_step_s is not None:
                raise ValueError(
                    f"DisaggServingConfig.{name}.virtual_step_s must be "
                    f"None — the coordinator charges one step per topology "
                    f"tick (pools run concurrently); set "
                    f"DisaggServingConfig.virtual_step_s instead"
                )
        self.handoff.validate()
        if self.virtual_step_s is not None and self.virtual_step_s < 0:
            raise ValueError("virtual_step_s must be >= 0")
        return self


@dataclasses.dataclass
class _DState:
    req: Request                  # the ORIGINAL request as submitted
    t_enqueue: float
    priority: str
    deadline_ms: float | None
    phase: str                    # "prefill" | "transfer" | "decode"
    route: str                    # "disagg" | "local" | ...
    t_prefill_admitted: float | None = None
    t_first: float | None = None  # the client's first token (TTFT)
    t_landed: float | None = None
    handoff: Any = None           # HandoffResult
    resumed: int = 0


class DisaggServingEngine:
    """The two-pool coordinator (module docstring). Construction mirrors
    :class:`ServingEngine`; ``batcher_kw`` (``page_size``, ``fd_config``,
    ``interpret``) applies to both pools::

        eng = DisaggServingEngine(
            cfg, params, mesh, s_max=32,
            serving=DisaggServingConfig(prefill_pes=2),
        )
        eng.serve(generate_trace(spec)); eng.snapshot()
    """

    family = "serving_disagg"

    def __init__(
        self,
        cfg,
        params,
        mesh,
        *,
        s_max: int,
        serving: DisaggServingConfig | None = None,
        metrics: ServingMetrics | None = None,
        clock: Any = None,
        obs_tag: str = "",
        elastic_scope: Any = None,
        **batcher_kw: Any,
    ):
        self.cfg = cfg
        if cfg.cache_kind == "kv_window":
            refuse_ring("the disaggregated handoff (whole page chains "
                        "streamed between pools)")
        if cfg.cache_kind in STATE_CACHE_KINDS:
            refuse_state("the disaggregated handoff (page chains streamed "
                         "between pools; a slot's state rides no page)",
                         cfg.cache_kind)
        self.serving = (serving or DisaggServingConfig()).validate()
        # the elastic namespace BOTH pools share (pool-offset PE
        # attribution keys it by topology-global index); None = the
        # process-global DEFAULT scope, the pre-ISSUE-17 behavior
        self._elastic = (
            elastic_scope if elastic_scope is not None else elastic.DEFAULT
        )
        self.clock = clock if clock is not None else _retry.get_clock()
        self._obs_tag = str(obs_tag)
        if mesh.devices.ndim != 1:
            raise ValueError(
                "disaggregated serving carves a 1-D mesh into two pools; "
                f"got {dict(mesh.shape)}"
            )
        devices = list(mesh.devices.flat)
        n_p = self.serving.prefill_pes
        if n_p >= len(devices):
            raise ValueError(
                f"prefill_pes={n_p} leaves no decode pool on a "
                f"{len(devices)}-device mesh"
            )
        page = batcher_kw.get("page_size")
        if page and page != self.serving.handoff.page_tokens:
            raise ValueError(
                f"handoff.page_tokens={self.serving.handoff.page_tokens} "
                f"must equal the paged batcher's page_size={page} — the "
                f"transfer manifest IS the trie's page chain"
            )
        axis = cfg.axis
        self.full_mesh = mesh
        self.s_max = int(s_max)
        # the un-collapse arc re-carves the prefill pool from the same
        # slice — keep the carve (params + batcher policy + sub-mesh)
        self.params = params
        self._batcher_kw = dict(batcher_kw)
        self._n_prefill = n_p
        self._prefill_mesh = Mesh(np.array(devices[:n_p]), (axis,))
        self.prefill = _PoolEngine(
            cfg, params, self._prefill_mesh,
            s_max=s_max, serving=self.serving.prefill, clock=self.clock,
            obs_tag=f"{self._obs_tag}pf:", pool_name=PREFILL_POOL,
            pe_offset=0, elastic_scope=self._elastic,
            pool_probe_steps=self.serving.pool_probe_steps, **batcher_kw,
        )
        self.decode = _PoolEngine(
            cfg, params, Mesh(np.array(devices[n_p:]), (axis,)),
            s_max=s_max, serving=self.serving.decode, clock=self.clock,
            obs_tag=f"{self._obs_tag}dec:", pool_name=DECODE_POOL,
            pe_offset=n_p, elastic_scope=self._elastic,
            pool_probe_steps=self.serving.pool_probe_steps, **batcher_kw,
        )
        self.handoff_plane = HandoffPlane(
            self.serving.handoff, s_max=s_max,
            prefill_world=n_p, decode_world=len(devices) - n_p,
            elastic_scope=self._elastic,
        )
        any_ov = (
            self.serving.prefill.overload is not None
            or self.serving.decode.overload is not None
        )
        self.metrics = metrics or ServingMetrics(
            slo=self.serving.slo, classes=PRIORITIES if any_ov else None,
        )
        self.collapsed = False
        self._uncollapse_clean = 0
        self.results: dict[Any, Any] = {}
        self._states: dict[Any, _DState] = {}
        # (t_due, seq, uid) heaps: landings awaiting decode admission,
        # and decode submissions bounced by a full queue (re-offered)
        self._landings: list = []
        self._seq = 0
        self._uid_counter = 0
        self._decode_rebuilds_seen = 0
        self._stopping = False
        self._t0 = self.clock.monotonic()
        self._phase_stats: dict[str, Any] = {}
        _obs.register_serving_engine(self)
        # coordinator-tier burn-rate alerting (ISSUE 15): fed by the
        # handoff ladder (handoff_retry_rate) and the cross-pool e2e
        # scoring, on top of each pool engine's own evaluator
        self._alerts = None
        self._alerts_resolved = False

    # -- submission ------------------------------------------------------

    def _route_local(self) -> str | None:
        """Why a new submission should bypass the prefill pool (None =
        take the disaggregated path)."""
        if self.collapsed:
            return "topology collapsed to unified"
        ctrl = self.prefill._overload
        if (ctrl is not None
                and ctrl.rung() >= self.serving.local_prefill_rung):
            return f"prefill pool browned out ({ctrl.state})"
        return None

    def submit(
        self,
        req: Request,
        *,
        arrival_t: float | None = None,
        priority: str = "interactive",
        deadline_ms: float | None = None,
    ):
        """Enqueue one request into the topology. Returns its uid, a
        typed :class:`Rejected` (both pools refused), or a typed
        :class:`Shed` (a pool's overload controller refused it at the
        door — a terminal, never a silent drop)."""
        now = self.clock.monotonic() if arrival_t is None else float(arrival_t)
        if req.uid is None:
            req = dataclasses.replace(req, uid=f"d{self._uid_counter}")
            self._uid_counter += 1
        if req.uid in self._states or req.uid in self.results:
            raise ValueError(f"duplicate request uid {req.uid!r}")
        self.decode._batcher.validate_request(req)
        self.metrics.count("submitted")
        st = _DState(
            req=req, t_enqueue=now, priority=priority,
            deadline_ms=deadline_ms, phase="prefill", route="disagg",
        )
        why_local = self._route_local()
        if why_local is None:
            res = self.prefill.submit(
                dataclasses.replace(req, max_new_tokens=1),
                arrival_t=now, priority=priority, deadline_ms=deadline_ms,
            )
            if isinstance(res, Shed):
                # the prefill controller's door refusal is a TERMINAL —
                # surface it as this topology's result
                self._count_terminal("shed", priority)
                self.results[req.uid] = res
                return res
            if not isinstance(res, Rejected):
                self._states[req.uid] = st
                return req.uid
            why_local = "prefill pool queue full"
        # decode-local prefill: the shed path of a browned-out / full /
        # collapsed prefill pool — cold, correct, slower
        st.route = "local"
        st.phase = "decode"
        res = self.decode.submit(
            req, arrival_t=now, priority=priority, deadline_ms=deadline_ms,
        )
        if isinstance(res, Shed):
            self._count_terminal("shed", priority)
            self.results[req.uid] = res
            return res
        if isinstance(res, Rejected):
            # NOT terminal: serve() re-offers a double rejection, so it
            # stays out of the serving_requests_total terminal census
            self.metrics.count("rejected")
            return Rejected(
                req.uid,
                f"both pools refused: {why_local}; decode: {res.reason}",
                res.queue_depth, res.priority,
            )
        # counted only on ACCEPTANCE: a doubly-rejected (re-offered)
        # arrival must not inflate the degradation-contract readout
        self.metrics.count("local_prefills")
        self._states[req.uid] = st
        return req.uid

    # -- prefill → handoff → decode --------------------------------------

    def _drain_pool_results(self) -> None:
        for uid in list(self.prefill.results):
            if uid in self._states:
                self._on_prefill_result(uid, self.prefill.results.pop(uid))
        for uid in list(self.decode.results):
            if uid in self._states:
                self._on_decode_result(uid, self.decode.results.pop(uid))

    def _on_prefill_result(self, uid: Any, res: Any) -> None:
        st = self._states[uid]
        if isinstance(res, (Shed, Poisoned)):
            # pool-tier terminal (deadline expired in the prefill queue /
            # poisoned prefill logits): passthrough, exactly one terminal
            self._count_terminal(
                "shed" if isinstance(res, Shed) else "poisoned",
                st.priority,
            )
            self._states.pop(uid)
            self.results[uid] = res
            return
        if isinstance(res, Rejected):
            # terminal Rejected inside the pool cannot happen here (the
            # coordinator, not the pool, owns resubmission) — keep loud
            raise RuntimeError(
                f"prefill pool produced a terminal Rejected for {uid!r}"
            )
        assert isinstance(res, Finished), res
        st.t_prefill_admitted = res.t_admitted
        st.t_first = res.t_first_token
        st.resumed += res.resumed
        t0 = res.tokens[0]
        orig = st.req
        if orig.max_new_tokens <= 1 or (
            orig.eos_id is not None and t0 == orig.eos_id
        ):
            # complete at prefill: the first token was the whole answer
            self.metrics.count("prefill_completed")
            self._finalize(uid, list(res.tokens), res.t_finished,
                           res.t_tokens)
            return
        # the KV handoff: stream the prompt's page chain to the decode
        # pool through the guard ladder; admission gates on t_landed
        st.phase = "transfer"
        ho = self.handoff_plane.transfer(uid, orig.prompt,
                                         now=res.t_finished)
        st.handoff = ho
        st.t_landed = ho.t_landed
        if (self.serving.pipelined_admission
                and ho.outcome == "delivered" and ho.page_landings):
            # ISSUE 18 pipelined admission: gate on the FIRST page's
            # landing — the decode pool starts while the tail streams.
            # st.t_landed moves with the gate so the serving:transfer
            # span decomposition stays exact (transfer ends at
            # admission; the overlapped tail is decode-side time).
            st.t_landed = ho.page_landings[0]
        self.metrics.count("handoffs")
        ae = self._alert_eng()
        if ae is not None:
            # the handoff-retry burn feed: rung-1 re-sends AND rung-2
            # re-streams both count — each is the ladder absorbing a wire
            # fault (obs/alerts.py handoff_retry_rate)
            ae.observe_handoff(ho.t_landed,
                               retries=ho.retries + ho.restreams)
        if ho.outcome == "fallback":
            # rung 3: the decode pool re-prefills cold — count it as a
            # resumption (TTFT stays the prefill pool's token; the decode
            # stream regenerates byte-identically per the strike contract)
            self.metrics.count("handoff_fallbacks")
            st.route = "fallback"
            st.resumed += 1
        self._push_landing(st.t_landed, uid)

    def _push_landing(self, t: float, uid: Any) -> None:
        heapq.heappush(self._landings, (float(t), self._seq, uid))
        self._seq += 1

    def _flush_landings(self, now: float) -> None:
        """Admission on last-page-landed: once a handoff's final chunk
        has landed (engine clock), the request enters the decode pool —
        anchored at its ORIGINAL arrival time, so deadlines and TTFT/e2e
        keep accruing across the transfer."""
        while self._landings and self._landings[0][0] <= now:
            _, _, uid = heapq.heappop(self._landings)
            st = self._states.get(uid)
            if st is None:
                continue  # terminal elsewhere (collapse replay raced)
            st.phase = "decode"
            res = self.decode.submit(
                st.req, arrival_t=st.t_enqueue, priority=st.priority,
                deadline_ms=st.deadline_ms,
            )
            if isinstance(res, Shed):
                self._count_terminal("shed", st.priority)
                self._states.pop(uid)
                self.results[uid] = res
            elif isinstance(res, Rejected):
                # decode queue full: the landed pages wait; re-offer on
                # the next tick (bounded — offered traffic is finite and
                # the decode pool keeps draining)
                st.phase = "transfer"
                self._push_landing(
                    now + (self.serving.virtual_step_s or 1e-3), uid
                )

    def _on_decode_result(self, uid: Any, res: Any) -> None:
        st = self._states[uid]
        if isinstance(res, (Shed, Poisoned)):
            self._count_terminal(
                "shed" if isinstance(res, Shed) else "poisoned",
                st.priority,
            )
            self._states.pop(uid)
            self.results[uid] = res
            return
        if isinstance(res, Rejected):
            raise RuntimeError(
                f"decode pool produced a terminal Rejected for {uid!r}"
            )
        assert isinstance(res, Finished), res
        # cross-pool consistency (the decode pool regenerates the first
        # token the prefill pool already served; the two must agree) is
        # pinned in tests — a runtime assertion here would mask the
        # fault-injection soaks that deliberately corrupt handoff state
        t_tokens = res.t_tokens
        if st.t_first is None:
            st.t_first = res.t_first_token
        elif t_tokens:
            # the client saw its first token when the prefill pool made
            # it; the decode pool's copy of it came later
            t_tokens = (st.t_first,) + t_tokens[1:]
        st.resumed += res.resumed
        self._finalize(uid, list(res.tokens), res.t_finished, t_tokens)

    def _count_terminal(self, terminal: str, priority: str) -> None:
        """One coordinator-tier terminal: the private tally AND its
        metrics-plane mirror (the every-tally-is-also-mirrored
        contract; :meth:`_finalize` mirrors ``finished`` itself)."""
        self.metrics.count(terminal)
        _mx.counter("serving_requests_total", engine=self.family,
                    terminal=terminal, priority=priority)

    def _finalize(self, uid: Any, tokens: list, now: float,
                  t_tokens: tuple) -> None:
        st = self._states.pop(uid)
        prio = st.priority if self.metrics.classes else None
        ttft_ms = (st.t_first - st.t_enqueue) * 1e3
        e2e_ms = (now - st.t_enqueue) * 1e3
        tpot_ms = (
            (now - st.t_first) / (len(tokens) - 1) * 1e3
            if len(tokens) > 1 else None
        )
        deadline_ok = None
        if st.deadline_ms is not None:
            deadline_ok = now <= st.t_enqueue + st.deadline_ms / 1e3
            if not deadline_ok:
                self.metrics.count("deadline_missed")
        self.metrics.observe_first_token(
            ttft_ms, resumed=st.resumed > 0, priority=prio
        )
        goodput_ok = self.metrics.observe_finished(
            ttft_ms=ttft_ms, e2e_ms=e2e_ms, tpot_ms=tpot_ms,
            n_tokens=len(tokens), priority=prio, deadline_ok=deadline_ok,
        )
        if _mx.enabled():
            _mx.counter("serving_requests_total", engine=self.family,
                        terminal="finished", priority=st.priority)
            _mx.counter("serving_tokens_total", len(tokens),
                        engine=self.family)
            if goodput_ok:
                _mx.counter("serving_tokens_goodput_total", len(tokens),
                            engine=self.family)
            # resumed first-tokens ride their own series, the engine.py
            # convention — replay TTFT must not skew the clean p99
            _mx.observe(
                "serving_resumed_ttft_ms" if st.resumed
                else "serving_ttft_ms",
                ttft_ms, engine=self.family,
            )
            _mx.observe("serving_e2e_ms", e2e_ms, engine=self.family)
        ae = self._alert_eng()
        if ae is not None:
            ae.observe_request(now, slo_ok=goodput_ok, ttft_ms=ttft_ms)
        if uid in self.results:
            raise RuntimeError(
                f"request {uid!r} finished twice — disagg bookkeeping bug"
            )
        fin = Finished(
            uid=uid, tokens=tokens, t_enqueue=st.t_enqueue,
            t_admitted=st.t_prefill_admitted, t_first_token=st.t_first,
            t_finished=now, resumed=st.resumed, t_tokens=t_tokens,
        )
        self.results[uid] = fin
        self._record_phase_spans(st, fin)

    def _record_phase_spans(self, st: _DState, fin: Finished) -> None:
        """The ISSUE 13 obs satellite: per-request lifecycle with the
        TRANSFER phase — ``queued → prefill → transfer → decode``
        decomposes ``e2e`` exactly for every handed-off request (the
        handoff starts the instant the prefill pool produced the first
        token, and decode admission gates on last-page-landed). Engine
        clock timestamps; no-op when obs is disarmed."""
        if not _obs.span_enabled():
            return
        track = f"{self._obs_tag}req:{fin.uid}"

        def phase(name, t0, t1, **attrs):
            _obs.record_span(name, t0, t1, cat="serving", track=track,
                             uid=str(fin.uid), **attrs)
            stats = self._phase_stats.get(name)
            if stats is None:
                stats = self._phase_stats[name] = _obs.tracer.DurationStats()
            stats.record((t1 - t0) * 1e3)

        ho = st.handoff
        # a fallback-outcome handoff still RAN (and is exactly the case
        # trace_summary must be able to diagnose), so it gets the full
        # phase decomposition too; only routes with no handoff at all
        # (local / collapse) reduce to the e2e span
        if ho is not None and st.t_landed is not None:
            phase("serving:queued", fin.t_enqueue, fin.t_admitted)
            phase("serving:prefill", fin.t_admitted, fin.t_first_token,
                  pool=PREFILL_POOL)
            phase("serving:transfer", fin.t_first_token, st.t_landed,
                  pages_streamed=ho.pages_streamed,
                  pages_deduped=ho.pages_deduped, chunks=ho.chunks_sent,
                  retries=ho.retries, restreams=ho.restreams,
                  outcome=ho.outcome)
            phase("serving:decode", st.t_landed, fin.t_finished,
                  n_tokens=len(fin.tokens), pool=DECODE_POOL)
        phase("serving:e2e", fin.t_enqueue, fin.t_finished,
              resumed=fin.resumed, n_tokens=len(fin.tokens),
              route=st.route)

    # -- pool collapse ----------------------------------------------------

    def _collapse(self, why: str) -> None:
        """The prefill pool is gone: fold the topology into the unified
        engine (the decode pool) with every in-prefill request replayed
        — the cold-restart contract regenerates each stream
        byte-identically, so no request and no token is lost."""
        if self.collapsed:
            return
        self.collapsed = True
        now = self.clock.monotonic()
        self.metrics.count("pool_collapses")
        _mx.counter("serving_pool_collapses_total", engine=self.family)
        health.record_pool_collapse(self.family, PREFILL_POOL, why)
        # completed prefills survive FIRST (the drain_finished contract):
        # a Finished sitting undrained in the dying pool hands off
        # normally here — replaying it below too would double-land it
        self._drain_pool_results()
        replayed = 0
        for uid, st in list(self._states.items()):
            if st.phase != "prefill":
                continue  # transfer/decode phases are decode-bound already
            st.route = "collapse"
            st.phase = "decode"
            st.resumed += 1
            self.metrics.count("resumed")
            # the prefill pool may or may not have admitted it — either
            # way the decode pool restarts it cold from the original
            # prompt; pool-engine state is abandoned with the pool
            self._push_landing(now, uid)
            replayed += 1
        # decode-side streamed pages stay valid (their KV is decode-pool
        # resident); only the prefill side died
        _obs.record_span(
            "serving:pool_collapse", now, now, cat="serving",
            track=f"{self._obs_tag}engine", pool=PREFILL_POOL, reason=why,
            replayed=replayed,
        )

    # -- reversible collapse (ISSUE 17, tentpole c) -----------------------

    def _maybe_uncollapse(self) -> None:
        """After ``collapse_probation_steps`` clean (rebuild-free,
        worked) unified ticks, probe the prefill slice; if every PE the
        collapse left quarantined passes, re-carve the two-pool
        topology. A failed probe restarts the probation window — the
        same restart-on-failure arc a PE's own probation runs."""
        cps = self.serving.collapse_probation_steps
        if cps is None or not self.collapsed or self._uncollapse_clean < cps:
            return
        mine = [pe for pe in self._elastic.quarantined_pes()
                if pe < self._n_prefill]
        if mine:
            with faults.pool_scope(PREFILL_POOL):
                self._elastic.probe_quarantined(
                    self._prefill_mesh, axis=self.cfg.axis, pes=mine,
                )
            if any(self._elastic.state(pe) == elastic.QUARANTINED
                   for pe in mine):
                self._uncollapse_clean = 0
                return
        self._uncollapse()

    def _uncollapse(self) -> None:
        """Re-carve the prefill pool on its original slice. In-flight
        requests finish where they run (collapse-routed work stays
        decode-bound, zero lost); only NEW submissions take the disagg
        path again. The handoff manifest needs no invalidation — the
        decode pool (the transfer target) survived the whole arc."""
        now = self.clock.monotonic()
        self.prefill = _PoolEngine(
            self.cfg, self.params, self._prefill_mesh, s_max=self.s_max,
            serving=self.serving.prefill, clock=self.clock,
            obs_tag=f"{self._obs_tag}pf:", pool_name=PREFILL_POOL,
            pe_offset=0, elastic_scope=self._elastic,
            pool_probe_steps=self.serving.pool_probe_steps,
            **self._batcher_kw,
        )
        self.collapsed = False
        self._uncollapse_clean = 0
        self.metrics.count("pool_uncollapses")
        _mx.counter("serving_pool_uncollapses_total", engine=self.family)
        health.record_pool_uncollapse(
            self.family, PREFILL_POOL,
            f"{self.serving.collapse_probation_steps} clean unified "
            f"step(s); prefill pool re-carved at "
            f"world={int(self.prefill.world_size)}",
        )
        _obs.record_span(
            "serving:pool_uncollapse", now, now, cat="serving",
            track=f"{self._obs_tag}engine", pool=PREFILL_POOL,
            world=int(self.prefill.world_size),
        )

    # -- burn-rate alerts (ISSUE 15) --------------------------------------

    def _alert_eng(self):
        """Coordinator-tier evaluator, lazily resolved from
        ``ObsConfig.alerts`` (None when disarmed) — the ServingEngine
        convention, through the same shared seam."""
        if not self._alerts_resolved:
            self._alerts_resolved = True
            slo = self.serving.slo
            self._alerts = _obs.alerts.resolve_engine(
                family=self.family,
                slo_ttft_ms=None if slo is None else slo.ttft_ms,
            )
        return self._alerts

    def _alerts_step(self) -> None:
        ae = self._alert_eng()
        if ae is None:
            return
        now = self.clock.monotonic()
        ae.observe_flips(now, health.flip_total())
        _obs.alerts.evaluate_and_record(
            ae, now, count=self.metrics.count, obs_tag=self._obs_tag,
        )

    # -- the tick loop ----------------------------------------------------

    def _check_decode_rebuild(self) -> None:
        if self.decode.rebuilds != self._decode_rebuilds_seen:
            self._decode_rebuilds_seen = self.decode.rebuilds
            self.handoff_plane.invalidate()

    def _tick(self) -> bool:
        """One topology step: the prefill pool, the handoff pipeline, and
        the decode pool each advance once; ONE ``virtual_step_s`` is
        charged (the pools run concurrently). Returns False when nothing
        had work."""
        worked = False
        rb_before = self.decode.rebuilds
        # a decode-pool rebuild (elastic shrink, downshift) built a FRESH
        # cache: nothing previously streamed is resident anymore, so the
        # transfer manifest must forget it BEFORE any drain can run a
        # transfer that would dedup onto destroyed pages — checked again
        # right after the decode step, which is where rebuilds happen
        self._check_decode_rebuild()
        if not self.collapsed:
            try:
                worked |= self.prefill._step_once()
            except (PoolCollapse, UnrecoverableEngineError) as exc:
                # ONLY the typed pool-is-dead signals collapse; a loud
                # bookkeeping-bug RuntimeError must stay loud, never be
                # swallowed into a spurious collapse
                self._collapse(f"prefill pool unrecoverable: {exc}")
                worked = True
        self._drain_pool_results()
        self._flush_landings(self.clock.monotonic())
        worked |= self.decode._step_once()
        self._check_decode_rebuild()
        self._drain_pool_results()
        if worked and self.serving.virtual_step_s:
            self.clock.sleep(self.serving.virtual_step_s)
        # coordinator-tier alerts after both pools advanced (the pool
        # engines evaluated their own rules inside their _step_once)
        self._alerts_step()
        # reversible collapse (ISSUE 17): only WORKED, rebuild-free
        # unified ticks count toward the probation window — an idle
        # topology proves nothing, and a rebuild mid-window restarts it
        if (self.collapsed and worked
                and self.serving.collapse_probation_steps is not None):
            if self.decode.rebuilds == rb_before:
                self._uncollapse_clean += 1
            else:
                self._uncollapse_clean = 0
            self._maybe_uncollapse()
        if worked and _mx.enabled():
            _mx.gauge("serving_in_flight", len(self._states),
                      engine=self.family)
            _mx.gauge("serving_pending_landings", len(self._landings),
                      engine=self.family)
            _mx.gauge("serving_collapsed", int(self.collapsed),
                      engine=self.family)
        return worked

    def serve(self, traffic=(), *, max_steps: int = 1_000_000) -> dict:
        """Drive an iterable of :class:`~triton_dist_tpu.serving.traffic.
        Arrival` until every offered request reaches its terminal state.
        Returns ``dict(self.results)``."""
        heap: list = []
        seq = 0
        for a in sorted(traffic, key=lambda a: a.t_s):
            heap.append((a.t_s, seq, a))
            seq += 1
        heapq.heapify(heap)
        steps = 0
        while True:
            now = self.clock.monotonic()
            if self._stopping and heap:
                for _, _, a in heap:
                    self.metrics.count("cancelled")
                heap.clear()
            while heap and heap[0][0] <= now:
                _, _, a = heapq.heappop(heap)
                res = self.submit(
                    a.request, arrival_t=a.t_s,
                    priority=getattr(a, "priority", "interactive"),
                    deadline_ms=getattr(a, "deadline_ms", None),
                )
                if isinstance(res, Rejected):
                    # BOTH pools refused (queues full): the offered
                    # request is the serve loop's to re-offer — never a
                    # silent drop. It re-enters after one tick with its
                    # ORIGINAL arrival time (TTFT/deadline anchors hold,
                    # the PR 11 retry convention); the loop's step budget
                    # bounds a permanently wedged topology.
                    self.metrics.count("reoffered")
                    heapq.heappush(heap, (
                        self.clock.monotonic()
                        + (self.serving.virtual_step_s or 1e-3),
                        seq, a,
                    ))
                    seq += 1
            if self._tick():
                steps += 1
                if steps >= max_steps:
                    raise RuntimeError(
                        f"serve(max_steps={max_steps}) exhausted with work "
                        f"still in flight; finished results are intact in "
                        f"self.results"
                    )
                continue
            pending = []
            if heap:
                pending.append(heap[0][0])
            if self._landings:
                pending.append(self._landings[0][0])
            if pending:
                dt = min(pending) - self.clock.monotonic()
                if dt > 0:
                    self.clock.sleep(dt)
                continue
            if self._states:
                raise RuntimeError(
                    f"disagg serve wedged: {len(self._states)} request(s) "
                    f"without work or a pending landing "
                    f"({sorted(self._states)})"
                )
            return dict(self.results)

    def run_until_idle(self, max_steps: int = 1_000_000) -> dict:
        return self.serve((), max_steps=max_steps)

    def stop(self, drain: bool = True) -> None:
        self._stopping = True
        self.prefill.stop(drain=drain)
        self.decode.stop(drain=drain)

    # -- readout ----------------------------------------------------------

    @property
    def world_size(self) -> int:
        return (0 if self.collapsed else self.prefill.world_size) + (
            self.decode.world_size
        )

    def snapshot(self) -> dict:
        """Coordinator-tier metrics + the handoff plane's counters + each
        pool's own snapshot. Deterministic under a FakeClock."""
        now = self.clock.monotonic()
        snap = self.metrics.snapshot()
        elapsed = max(now - self._t0, 1e-9)
        snap["tokens"]["per_s"] = round(
            self.metrics.tokens_generated / elapsed, 6
        )
        snap["tokens"]["goodput_per_s"] = round(
            self.metrics.tokens_goodput / elapsed, 6
        )
        snap["engine"] = {
            "topology": "disagg",
            "collapsed": self.collapsed,
            "prefill_world": (
                0 if self.collapsed else self.prefill.world_size
            ),
            "decode_world": self.decode.world_size,
            "in_flight": len(self._states),
            "pending_landings": len(self._landings),
            "clock_s": round(now - self._t0, 9),
        }
        snap["handoff"] = self.handoff_plane.snapshot()
        if self._alerts is not None:
            snap["alerts"] = self._alerts.snapshot()
        snap["pools"] = {
            PREFILL_POOL: self.prefill.snapshot(),
            DECODE_POOL: self.decode.snapshot(),
        }
        if _obs.span_enabled():
            snap["span_ms"] = {
                name: st.snapshot()
                for name, st in sorted(self._phase_stats.items())
            }
        return snap
