"""The serving engine: an SLO-metered, traffic-driven, elastic loop over
:class:`~triton_dist_tpu.models.decode.ContinuousBatcher` (ISSUE 6
tentpole — the subsystem ABOVE the kernel-level scheduler: arrivals,
lifecycle timestamps, backpressure, and fault-tolerant mesh shrink while
serving live traffic).

Request lifecycle (every timestamp captured at the host scheduling
boundary, on the INJECTABLE clock — ``resilience/retry.py``'s module
clock by default, so a ``FakeClock`` makes whole serve runs, latency
percentiles included, deterministic)::

    submit ──► [bounded queue] ──► admitted ──► first token ──► finished
       │            │ backpressure                  │
       └ Rejected ◄─┘ (reject-on-full | block)      └ resumed (replay)
       └ Shed    ◄─── overload controller (ISSUE 11, when armed):
                      deadline expiry / overflow victim / shed_all_batch
                      — serving/overload.py, docs/serving.md "Overload"
       └ (prefix-struck, ISSUE 12: a poisoned SHARED prefix page evicts
          every reader of the chain — restarted COLD from the original
          prompt, counted `prefix_struck`, TTFT re-measured as resumed;
          never a terminal state — docs/serving.md "Prefix cache")

Elastic wiring (engine + ``resilience/elastic.py``): a
``DistTimeoutError`` escaping the jitted step has already been through
the op-entry retry/attribution machinery (``ops/common.jit_shard_map``
retries transient trips, strikes the straggler by absence, quarantines at
threshold, and — because the step DONATES its cache — escalates rather
than relaunching over freed buffers). The engine is the host-level
re-materialization layer those semantics require: it offers the failure
to peer attribution once more (the ``retry.call_with_retry`` convention),
rebuilds the batcher on the serviceable survivor mesh
(``elastic.serviceable_mesh`` — possibly smaller than the survivor count
when model divisibility demands it), and **prefix-replays** every
in-flight request: prompt + tokens-generated-so-far re-enter as a new
prompt, so no generated token is ever lost and greedy continuations are
byte-identical to an uninterrupted run; sampled continuations carry their
live RNG (``Request.rng``). TTFT is re-measured as a ``resumed`` event.
Probation re-admission (periodic ``elastic.probe_quarantined``) grows the
mesh back mid-serving through the same replay path.
"""

from __future__ import annotations

import dataclasses
import heapq
from collections import deque
from typing import Any

from triton_dist_tpu import obs as _obs
from triton_dist_tpu.obs import metrics as _mx
from triton_dist_tpu.models.decode import ContinuousBatcher, Request
from triton_dist_tpu.models.prefix_cache import (
    PX_COUNTERS,
    PX_GAUGES,
    PrefixCacheConfig,
)
from triton_dist_tpu.resilience import elastic, health
from triton_dist_tpu.resilience import retry as _retry
from triton_dist_tpu.serving import overload as _overload
from triton_dist_tpu.serving.metrics import ServingMetrics, SLOTargets
from triton_dist_tpu.serving.overload import (
    OverloadConfig,
    OverloadController,
    PRIORITIES,
    priority_rank,
)
from triton_dist_tpu.serving.traffic import Arrival

BACKPRESSURE = ("reject", "block")
ADMISSION = ("fcfs", "spf")
# the batcher's decode-round counters in the snapshot's "batcher" section
_ROUND_COUNTERS = ("rounds", "rounds_ahead", "ahead_discarded")


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Host-side serving policy.

    max_queue:        bound on the arrival queue (backpressure trips past it).
    backpressure:     "reject" returns a typed :class:`Rejected`;
                      "block" serves (steps the engine) until space frees.
    admission:        "fcfs" or "spf" (shortest-prompt-first).
    virtual_step_s:   charge each decode step this much time on the
                      engine clock — pair with a ``FakeClock`` for
                      deterministic latency tests and the
                      ``bench_serving`` virtual-clock rows. None (default)
                      = real time only.
    probe_interval_steps: steps between probation probes while any PE is
                      quarantined (the regrow cadence).
    max_step_failures: consecutive step timeouts tolerated (each one
                      rebuilds + replays) before the engine re-raises.
    slo:              latency targets scored per finished request.
    world_ok:         optional override for the degraded-world
                      divisibility predicate (``n -> bool``).
    overload:         an :class:`~triton_dist_tpu.serving.overload.
                      OverloadConfig` arms the overload controller
                      (ISSUE 11): deadline shedding, priority classes,
                      per-class retry budgets, and the brownout ladder.
                      None (the default) = the pre-overload engine,
                      byte for byte. Requires ``backpressure="reject"``
                      (shed decisions and block-by-serving conflict).
    prefix_cache:     a :class:`~triton_dist_tpu.models.prefix_cache.
                      PrefixCacheConfig` arms the radix-shared paged KV
                      prefix cache (ISSUE 12): admission-time
                      longest-prefix match skips the feed for every
                      fully shared page, copy-on-write claims fresh
                      pages at the divergence, refcounted release rides
                      the slot lifecycle, and a poisoned shared page
                      strikes (cold-re-prefills) every reader. Needs the
                      paged batcher (``page_size=`` in the batcher
                      kwargs). None (the default) = the pre-cache
                      engine, byte for byte.
    prefill_chunk_tokens: chunked-prefill scheduling (ISSUE 18): prompts
                      longer than this admit through bounded suffix-only
                      ranged-prefill chunks interleaved with decode
                      steps, so one long prompt cannot stall a
                      decode-heavy batch. Needs ``prefill=True`` in the
                      batcher kwargs. None (the default) = unchunked
                      admission, byte for byte.
    virtual_prefill_work_s: charge each unit of prefill WORK — a swept
                      query×key token-pair — this much time on the
                      engine clock, alongside ``virtual_step_s``. A bulk
                      bucket prefill computes the dense padded
                      bucket×bucket rectangle (mask applied after the
                      sweep), so a 24-token prompt at bucket 32 bills
                      1024 pairs in one step; suffix-only ranged chunks
                      sweep only their chunk_bucket×hi strips (336 pairs
                      for the same prompt at chunk 4) — the kernel-true
                      cost asymmetry under which chunked admission's
                      tail-latency win is measurable. None (default) =
                      prefill charges nothing, as before.
    speculative:      a :class:`~triton_dist_tpu.serving.speculative.
                      SpecDecodeConfig` arms speculative decoding as a
                      serving mode (ISSUE 20): the batcher proposes k
                      draft tokens per slot per round and verifies them
                      in ONE batched ranged pass, accepting per-slot.
                      Greedy streams are byte-identical to plain
                      serving; seeded-sampled streams are
                      replay-deterministic. With ``virtual_step_s`` the
                      step charge scales by the round's cost units
                      (plain round = 1.0), so FakeClock A/Bs measure the
                      real step-count win. Composes with the
                      ``overload`` ladder's ``shed_speculation`` rung
                      (drop the draft under pressure, counted rebuild,
                      reverted on descent). None (the default) = the
                      pre-spec engine, byte for byte.
    """

    max_queue: int = 256
    backpressure: str = "reject"
    admission: str = "fcfs"
    virtual_step_s: float | None = None
    probe_interval_steps: int = 32
    max_step_failures: int = 8
    slo: SLOTargets | None = None
    world_ok: Any = None
    overload: OverloadConfig | None = None
    prefix_cache: PrefixCacheConfig | None = None
    prefill_chunk_tokens: int | None = None
    virtual_prefill_work_s: float | None = None
    speculative: Any = None

    def validate(self) -> "ServingConfig":
        if self.speculative is not None:
            self.speculative.validate()
        if self.max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {self.max_queue}")
        if self.prefill_chunk_tokens is not None and self.prefill_chunk_tokens < 1:
            raise ValueError("prefill_chunk_tokens must be >= 1 (or None)")
        if (self.virtual_prefill_work_s is not None
                and self.virtual_prefill_work_s < 0):
            raise ValueError("virtual_prefill_work_s must be >= 0")
        if self.prefix_cache is not None:
            self.prefix_cache.validate()
        if self.overload is not None:
            self.overload.validate()
            if self.backpressure != "reject":
                raise ValueError(
                    'overload control requires backpressure="reject" — '
                    "blocking submits would serve traffic the shed policy "
                    "exists to refuse"
                )
        if self.backpressure not in BACKPRESSURE:
            raise ValueError(
                f"backpressure must be one of {BACKPRESSURE}, "
                f"got {self.backpressure!r}"
            )
        if self.admission not in ADMISSION:
            raise ValueError(
                f"admission must be one of {ADMISSION}, "
                f"got {self.admission!r}"
            )
        if self.probe_interval_steps < 1:
            raise ValueError("probe_interval_steps must be >= 1")
        if self.max_step_failures < 1:
            raise ValueError("max_step_failures must be >= 1")
        if self.virtual_step_s is not None and self.virtual_step_s < 0:
            raise ValueError("virtual_step_s must be >= 0")
        return self


class UnrecoverableEngineError(RuntimeError):
    """The engine exhausted ``max_step_failures`` consecutive failing
    steps without recovering — the rebuild/replay machinery cannot make
    progress. A TYPED signal (not a bare RuntimeError) so a supervising
    topology (serving/disagg.py) can distinguish "this pool is dead"
    from a loud bookkeeping-bug RuntimeError it must never swallow."""


@dataclasses.dataclass(frozen=True)
class Rejected:
    """Typed backpressure result: the queue was full under the "reject"
    policy. The request was NOT enqueued (it is not counted anywhere but
    the rejection counter) — resubmit later or switch to "block"."""

    uid: Any
    reason: str
    queue_depth: int
    priority: str | None = None


@dataclasses.dataclass(frozen=True)
class Shed:
    """Typed load-shed terminal (ISSUE 11): the overload controller
    refused or evicted this request — deadline expired in the queue, it
    was the overflow victim (lowest class, newest arrival), or the ladder
    reached ``shed_all_batch``. The request never silently drops: this
    object is its exactly-one terminal state (the no-lost-request
    invariant the chaos soak asserts). Only produced with
    ``ServingConfig.overload`` armed."""

    uid: Any
    reason: str
    priority: str
    t_enqueue: float
    t_shed: float


@dataclasses.dataclass(frozen=True)
class Poisoned:
    """Typed per-request poison rejection (ISSUE 8): this request's logit
    row went non-finite under an armed ``config.integrity``, so it was
    EVICTED from its slot and rejected — the engine kept serving and its
    batch neighbors' token streams are untouched (byte-identical to a run
    without the poison; chaos-asserted). ``tokens`` holds whatever was
    generated before the poison (diagnostic only — do NOT serve them as a
    completion)."""

    uid: Any
    tokens: list
    reason: str
    t_enqueue: float
    t_poisoned: float
    resumed: int


@dataclasses.dataclass(frozen=True)
class Finished:
    """One completed request with its lifecycle timestamps (engine-clock
    seconds) and the full generated token list (replay prefixes
    included)."""

    uid: Any
    tokens: list
    t_enqueue: float
    t_admitted: float | None
    t_first_token: float | None
    t_finished: float
    resumed: int
    # engine-clock time at which each token of ``tokens`` became visible
    # to the engine (one stamp a step, so a speculative round's tokens
    # share one); stamps of a replay prefix are kept. Without a replay
    # the first equals ``t_first_token``; the last equals ``t_finished``
    t_tokens: tuple = ()

    @property
    def ttft_ms(self) -> float:
        return (self.t_first_token - self.t_enqueue) * 1e3

    @property
    def e2e_ms(self) -> float:
        return (self.t_finished - self.t_enqueue) * 1e3


@dataclasses.dataclass
class _ReqState:
    req: Request                     # the ORIGINAL request as submitted
    t_enqueue: float
    t_admitted: float | None = None
    t_first: float | None = None
    first_recorded: bool = False     # original-TTFT sample already taken
    awaiting_first: bool = True      # no token seen since (re)admission
    tokens: list = dataclasses.field(default_factory=list)  # replay prefix
    # when each token so far was seen (replay prefix + the live slot's)
    t_tokens: list = dataclasses.field(default_factory=list)
    resumed: int = 0
    priority: str = "interactive"    # overload class (ISSUE 11)
    deadline: float | None = None    # absolute engine-clock deadline


class ServingEngine:
    """See module docstring. Construction mirrors ``ContinuousBatcher``
    (cfg/params/mesh/s_max plus its keyword surface: ``page_size``,
    ``fd_config``, ``prefill``, ``interpret``), because the engine must be
    able to REBUILD the batcher on a different mesh mid-serving::

        eng = ServingEngine(cfg, params, mesh, s_max=256,
                            serving=ServingConfig(max_queue=64))
        eng.submit(Request([1, 2, 3], max_new_tokens=8))
        eng.run_until_idle()
        eng.results["r0"].tokens, eng.snapshot()

    or traffic-driven: ``eng.serve(generate_trace(spec))``.
    """

    def __init__(
        self,
        cfg,
        params,
        mesh,
        *,
        s_max: int,
        serving: ServingConfig | None = None,
        metrics: ServingMetrics | None = None,
        clock: Any = None,
        obs_tag: str = "",
        elastic_scope: Any = None,
        **batcher_kw: Any,
    ):
        self.cfg, self.params = cfg, params
        self.full_mesh = mesh
        self.s_max = int(s_max)
        # the elastic namespace this engine strikes/probes in (ISSUE 17):
        # None ⇒ the process-global default scope, byte-identical to the
        # pre-scoping engine. A fleet passes one scope per replica so
        # strikes never cross replica slices. Set before _target_mesh —
        # the first mesh resolution already consults it.
        self._elastic = (elastic_scope if elastic_scope is not None
                         else elastic.DEFAULT)
        self.batcher_kw = dict(batcher_kw)
        self.serving = (serving or ServingConfig()).validate()
        # default clock = the resilience module clock, so one
        # retry.set_clock(FakeClock()) / retry.clock_scope(...) puts
        # backoffs and serving timestamps on the same timeline
        self.clock = clock if clock is not None else _retry.get_clock()
        # overload control (ISSUE 11): None ⇒ the pre-overload engine,
        # byte for byte — no controller, no per-class metric surface
        self._overload = (
            OverloadController(
                self.serving.overload, max_queue=self.serving.max_queue
            )
            if self.serving.overload is not None else None
        )
        self.metrics = metrics or ServingMetrics(
            slo=self.serving.slo,
            classes=PRIORITIES if self._overload is not None else None,
        )
        self.family = "serving_engine"
        self._pending: deque[_ReqState] = deque()
        self._states: dict[Any, _ReqState] = {}
        self.results: dict[Any, Finished] = {}
        self.rebuilds = 0
        self._failures = 0
        self._steps_since_probe = 0
        self._uid_counter = 0
        self._stopping = False
        self._base_cfg = cfg           # restored when brownout2 descends
        # how many downshift stages are composed onto _base_cfg right now
        # (0 = serving the base config; the legacy single-callable hook
        # only ever reaches depth 1; a two-stage ladder reaches 2 at
        # brownout3)
        self._downshift_depth = 0
        self._w8_params = None         # once-quantized serving banks cache
        self._fp8_params = None        # ... and the fp8 twin (ISSUE 19)
        # speculation shed (ISSUE 20): True while the SHED_SPEC brownout
        # rung holds — _build serves the PLAIN batcher; reverted on
        # descent through the same counted-rebuild machinery as the
        # precision downshifts
        self._spec_shed = False
        # speculative counters accumulated across batcher rebuilds (each
        # rebuild starts fresh tallies, like the px trie) + the
        # already-mirrored watermark for the _mx delta counters
        self._spec_totals: dict[str, int] = {}
        self._spec_mx_seen: dict[str, int] = {}
        # prefix-cache counters accumulated across batcher rebuilds (each
        # rebuild starts a FRESH trie — the pool is the batcher's)
        self._px_totals: dict[str, int] = {}
        # the retired batchers' decode-round counters (a rebuild's batcher
        # counts from 0)
        self._round_totals = dict.fromkeys(_ROUND_COUNTERS, 0)
        # per-step deltas feeding the controller's pressure window
        self._step_arrived = 0
        self._step_finished = 0
        # running tallies, read as deltas by the tdt.engine.observe span
        self._n_first_tokens = 0
        self._n_finished = 0
        self._step_slo_ok = 0
        self._step_slo_scored = 0
        self.mesh = self._target_mesh()
        self._batcher = self._build(self.mesh)
        self._t0 = self.clock.monotonic()
        # obs (ISSUE 9): live engines fold their metrics into
        # obs.snapshot(); weak registration, so a dropped engine vanishes.
        # Phase stats are ENGINE-LOCAL (the global tracer is process-wide
        # — two live engines must not contaminate each other's p50/p99).
        # obs_tag prefixes this engine's span TRACKS so concurrent or
        # sequential engines sharing request uids (the λ-sweep re-seeds
        # req0.. per rate) land on distinct exported lanes.
        _obs.register_serving_engine(self)
        self._obs_tag = str(obs_tag)
        self._phase_stats: dict[str, Any] = {}
        # burn-rate alerting (ISSUE 15): resolved LAZILY on the first
        # step, so a subclass's family override (_PoolEngine) and a
        # post-construction ObsConfig(alerts=...) arming are both seen
        self._alerts = None
        self._alerts_resolved = False

    # -- world management ----------------------------------------------

    @property
    def world_size(self) -> int:
        return int(self.mesh.devices.size)

    def _world_ok(self, n: int) -> bool:
        """Can the model + cache geometry run at world size ``n``? (The
        serviceable-mesh predicate; override via ServingConfig.world_ok.)"""
        if self.serving.world_ok is not None:
            return bool(self.serving.world_ok(n))
        c = self.cfg
        if n < 1:
            return False
        if c.n_kv_heads % n or c.n_q_heads % n or c.ffn % n or c.vocab % n:
            return False
        if self.s_max % n:
            return False
        # s_max % n == 0 also covers prefill bucketing: _bucket's terminal
        # bucket is s_max (batch * s_max then divides n too), so admission
        # can never fail to find a bucket on an approved world — at worst
        # an awkward n makes every prompt pay the full-s_max masked
        # prefill (slow, never wrong)
        page = self.batcher_kw.get("page_size")
        if page and (self.s_max // n) % page:
            return False
        # EP decode shards the per-group batch rows over the axis
        if getattr(c, "ep_max_m", None) is not None and c.batch % n:
            return False
        return True

    def _target_mesh(self):
        """The mesh serving should run on right now: the full mesh while
        every PE is serviceable, else the largest model-valid survivor
        prefix. Elastic shrink only governs 1-D worlds (elastic.py); a
        hierarchical mesh serves un-shrunk."""
        if self.full_mesh.devices.ndim != 1 or not elastic.enabled():
            return self.full_mesh
        return self._elastic.serviceable_mesh(
            self.full_mesh, axis=self.cfg.axis, validate=self._world_ok
        )

    def _serving_params(self):
        """The param tree the batcher should serve. With a scaled-format
        MoE config (``cfg.gg_config.w8`` or ``.fp8``) and FLOAT expert
        banks, quantize them ONCE
        here (ISSUE 13 satellite — the tp_transformer.py:360 noted
        follow-up retired at the engine tier): every decode/prefill call
        then feeds pre-quantized int8 pools + explicit scales straight
        through, skipping ``resolve_w8``'s per-call quantize bank
        read+write. Bit-identical to the on-the-fly path by construction
        (``resolve_w8`` and ``quantize_moe_serving_params`` share
        ``quantize_expert_weights``; unit-pinned in tests). Cached — a
        rebuild (elastic shrink, brownout downshift) re-reads it, and a
        downshift REVERT (cfg back to non-w8) serves the original float
        banks again."""
        c = self.cfg
        gg = getattr(c, "gg_config", None)
        fp8 = getattr(gg, "fp8", False)
        if not (getattr(gg, "w8", False) or fp8):
            return self.params
        layers = (
            self.params.get("layers")
            if isinstance(self.params, dict) else None
        )
        if not layers or "w_up" not in layers[0]:
            return self.params
        if "w_up_scale" in layers[0]:
            return self.params  # caller already fed pre-quantized pools
        import jax.numpy as jnp

        if not jnp.issubdtype(layers[0]["w_up"].dtype, jnp.floating):
            return self.params  # int8/fp8 without scales: stays loud below
        from triton_dist_tpu.models.tp_transformer import (
            quantize_moe_serving_params,
        )

        if fp8:
            # brownout3's operand format (ISSUE 19): float8_e4m3 pools at
            # quarter-rate HBM bytes, cached separately from the w8 banks
            # so a 2 -> 3 -> 2 rung walk re-quantizes neither
            if self._fp8_params is None:
                self._fp8_params = quantize_moe_serving_params(
                    self.params, fmt="fp8"
                )
            return self._fp8_params
        if self._w8_params is None:
            self._w8_params = quantize_moe_serving_params(self.params)
        return self._w8_params

    def _build(self, mesh) -> ContinuousBatcher:
        kw = dict(self.batcher_kw)
        if self.serving.prefix_cache is not None:
            kw["prefix_cache"] = self.serving.prefix_cache
        if self.serving.prefill_chunk_tokens is not None:
            kw["prefill_chunk_tokens"] = self.serving.prefill_chunk_tokens
        if self.serving.speculative is not None and not self._spec_shed:
            # the speculative batcher (ISSUE 20); under the SHED_SPEC
            # brownout rung the engine builds the PLAIN batcher instead —
            # shedding speculation IS this dispatch flipping, composed
            # through the same rebuild+replay the downshifts use
            from triton_dist_tpu.serving.speculative import SpeculativeBatcher

            batcher = SpeculativeBatcher(
                self.cfg, self._serving_params(), mesh, s_max=self.s_max,
                spec_decode=self.serving.speculative, **kw,
            )
            batcher.on_k_change = self._on_spec_k_change
        else:
            batcher = ContinuousBatcher(
                self.cfg, self._serving_params(), mesh, s_max=self.s_max,
                **kw
            )
        # a fresh batcher's prefill-work counter restarts at 0: resync the
        # engine's charge watermark so rebuilt+replayed admissions charge
        # their own work, not a stale delta
        self._prefill_work_seen = 0
        return batcher

    # -- submission / admission ----------------------------------------

    def submit(
        self,
        req: Request,
        *,
        arrival_t: float | None = None,
        priority: str = "interactive",
        deadline_ms: float | None = None,
    ):
        """Enqueue one request. Returns its uid, or a typed
        :class:`Rejected` when the bounded queue is full under the
        "reject" policy ("block" steps the engine until space frees), or
        a typed :class:`Shed` when the overload controller refuses it at
        the door (ISSUE 11). ``arrival_t`` backdates the enqueue
        timestamp to the offered arrival time (the serve loop passes it
        so queueing delay accrued while the host was mid-step still
        counts toward TTFT). ``priority``/``deadline_ms`` are consulted
        only with ``ServingConfig.overload`` armed; the deadline budget
        is measured from the (possibly backdated) arrival time."""
        now = self.clock.monotonic() if arrival_t is None else float(arrival_t)
        ctrl = self._overload
        if ctrl is not None:
            priority_rank(priority)  # loud on policy typos
        if req.uid is None:
            req = dataclasses.replace(req, uid=f"r{self._uid_counter}")
            self._uid_counter += 1
        if req.uid in self._states or req.uid in self.results:
            raise ValueError(f"duplicate request uid {req.uid!r}")
        self._batcher.validate_request(req)
        self.metrics.count("submitted")
        self._step_arrived += 1
        if ctrl is not None and not ctrl.submit_allowed(priority):
            return self._record_shed(
                req.uid, priority, now, self.clock.monotonic(),
                "ladder at shed_all_batch: batch refused at submit",
            )
        if len(self._pending) >= self.serving.max_queue and ctrl is not None:
            # shed-before-reject (ISSUE 11): expired queue entries go
            # first; then the overflow victim — the newest member of the
            # worst queued class, and only one strictly below the
            # incoming request's class (never same-class displacement)
            self._shed_expired(self.clock.monotonic())
            if len(self._pending) >= self.serving.max_queue:
                victim = ctrl.shed_victim(
                    [(s.priority, i) for i, s in enumerate(self._pending)]
                )
                if victim is not None and (
                    priority_rank(self._pending[victim].priority)
                    > priority_rank(priority)
                ):
                    vst = self._pending[victim]
                    del self._pending[victim]
                    self._states.pop(vst.req.uid)
                    self._record_shed(
                        vst.req.uid, vst.priority, vst.t_enqueue,
                        self.clock.monotonic(),
                        "overflow shed: displaced by a higher class at a "
                        "full queue",
                    )
        if len(self._pending) >= self.serving.max_queue:
            if self.serving.backpressure == "reject":
                self.metrics.count("rejected")
                return Rejected(
                    req.uid,
                    f"arrival queue full ({self.serving.max_queue})",
                    len(self._pending),
                    priority if ctrl is not None else None,
                )
            while len(self._pending) >= self.serving.max_queue:
                if not self._step_once():
                    raise RuntimeError(
                        "blocking submit cannot make progress: the arrival "
                        "queue is full but the engine is idle (max_queue "
                        "smaller than the batch can absorb?)"
                    )
        st = _ReqState(
            req=req, t_enqueue=now, priority=priority,
            deadline=None if deadline_ms is None else now + deadline_ms / 1e3,
        )
        self._states[req.uid] = st
        self._pending.append(st)
        self._admit(self.clock.monotonic())
        return req.uid

    def _pop_admission(self) -> _ReqState:
        """Next request under the admission policy; with the controller
        in a brownout state, strict-priority first (interactive beats
        batch — deferral, not denial: batch still runs whenever no
        interactive request is waiting, so a brownout can never wedge the
        queue), the configured policy ordering within a class."""
        strict = self._overload is not None and self._overload.strict_priority()
        if not strict and self.serving.admission == "fcfs":
            return self._pending.popleft()  # the disarmed hot path

        def key(i):
            st = self._pending[i]
            cls = priority_rank(st.priority) if strict else 0
            if self.serving.admission == "fcfs":
                return (cls, i)
            return (cls, len(st.req.prompt), i)

        best = min(range(len(self._pending)), key=key)
        st = self._pending[best]
        del self._pending[best]
        return st

    def _shed_expired(self, now: float) -> None:
        """Deadline-expiry shedding (ISSUE 11): queued requests whose
        deadline has passed are shed BEFORE admission — serving them
        would burn capacity on work the client has already abandoned.
        In-flight requests are never evicted for a deadline; they finish
        and are scored SLO-missed (``_finalize``)."""
        if self._overload is None:
            return
        expired = [
            i for i, st in enumerate(self._pending)
            if st.deadline is not None and now > st.deadline
        ]
        for i in reversed(expired):
            st = self._pending[i]
            del self._pending[i]
            self._states.pop(st.req.uid)
            self._record_shed(
                st.req.uid, st.priority, st.t_enqueue, now,
                "deadline expired in queue",
            )

    def _admit(self, now: float) -> int:
        ctrl = self._overload
        if ctrl is not None:
            self._shed_expired(now)
        admitted = 0
        while self._batcher.n_free_slots > 0 and self._pending:
            st = self._pop_admission()
            st.t_admitted = now
            self.metrics.count("admitted")
            self._batcher.submit(st.req)
            admitted += 1
        return admitted

    # -- the step loop --------------------------------------------------

    def _step_once(self) -> bool:
        """Admit + one batcher step. False when there is nothing to do."""
        b = self._batcher
        with _obs.span("tdt.engine.step", pending=len(self._pending),
                       in_flight=b.n_active + len(b.queue)):
            with _obs.span("tdt.engine.admit") as sp:
                sp.set("admitted", self._admit(self.clock.monotonic()))
            if b.idle:
                return False
            try:
                b.step()
            except Exception as exc:  # noqa: BLE001 — classified below
                from triton_dist_tpu.resilience import integrity as _integrity

                if _retry.timeout_in_chain(exc) is not None:
                    self._on_step_timeout(exc)
                    return True
                if _integrity.integrity_in_chain(exc) is not None:
                    # whole-step corruption detected BELOW the logits (a
                    # canary / output guard tripped inside the jitted
                    # step): same containment as a timeout — attribute,
                    # rebuild, and prefix-replay every in-flight request
                    # (no token of the poisoned step was ever consumed);
                    # the per-REQUEST quarantine path is the batcher's
                    # logit check, not this
                    self._on_step_integrity(exc)
                    return True
                raise
            self._failures = 0
            self._charge_virtual_time()
            self._observe_step()
            return True

    def _charge_virtual_time(self) -> None:
        """What the step just run costs on a virtual engine clock."""
        if self.serving.virtual_step_s:
            if self.serving.speculative is not None:
                # speculative step-count accounting (ISSUE 20): a
                # draft+verify round charges its cost-model units (the
                # plain round, and the shed/dormant batcher, charge 1.0)
                self.clock.sleep(
                    self.serving.virtual_step_s
                    * getattr(self._batcher, "last_step_units", 1.0)
                )
            else:
                self.clock.sleep(self.serving.virtual_step_s)
        if self.serving.virtual_prefill_work_s:
            # work-proportional prefill charge (ISSUE 18): this step's
            # swept query×key token-pairs through the MXU prefill paths
            # (dense bucket rectangle, or ranged-chunk strips) cost time
            # on the engine clock — the kernel-true cost model under
            # which an unchunked long admission visibly stalls the whole
            # batch and chunked admission both spreads AND shrinks it
            total = self._batcher.prefill_work_total
            delta = total - self._prefill_work_seen
            self._prefill_work_seen = total
            if delta > 0:
                self.clock.sleep(delta * self.serving.virtual_prefill_work_s)

    def _observe_step(self) -> None:
        with _obs.span("tdt.engine.observe") as sp:
            n_first, n_finished = self._n_first_tokens, self._n_finished
            self._observe(self.clock.monotonic())
            # alerts evaluate AFTER this step's finishes were scored and
            # BEFORE the ladder observes them (ISSUE 15): the burn-rate
            # rule sees the misses on the step they happen, the ladder
            # needs the pressure window to integrate them — so a goodput
            # burn alert FIRES before the ladder can reach shed_all_batch
            # (pinned in tests/test_flight_recorder.py: alerts lead
            # degradation)
            self._alerts_step()
            self._overload_step()
            self._maybe_probe()
            sp.set("first_tokens", self._n_first_tokens - n_first)
            sp.set("finished", self._n_finished - n_finished)

    # -- burn-rate alerts (ISSUE 15) ------------------------------------

    def _alert_eng(self):
        """The lazily-resolved per-engine burn-rate evaluator (None when
        ``ObsConfig.alerts`` is disarmed at first use)."""
        if not self._alerts_resolved:
            self._alerts_resolved = True
            slo = self.serving.slo
            self._alerts = _obs.alerts.resolve_engine(
                family=self.family,
                slo_ttft_ms=None if slo is None else slo.ttft_ms,
            )
        return self._alerts

    def _alerts_step(self) -> None:
        """Advance every rule on the engine clock; each transition is
        recorded through the ONE shared fan-out
        (``obs.alerts.evaluate_and_record``: engine counter, health
        event, ``obs:alert`` instant, metrics-plane counter)."""
        ae = self._alert_eng()
        if ae is None:
            return
        now = self.clock.monotonic()
        ae.observe_flips(now, health.flip_total())
        _obs.alerts.evaluate_and_record(
            ae, now, count=self.metrics.count, obs_tag=self._obs_tag,
        )

    # -- overload control (ISSUE 11) ------------------------------------

    def _overload_step(self) -> None:
        """Feed this step's deltas into the controller's pressure window
        and apply any ladder transition it returns."""
        ctrl = self._overload
        if ctrl is None:
            return
        tr = ctrl.observe_step(
            now=self.clock.monotonic(),
            queue_depth=len(self._pending),
            arrived=self._step_arrived,
            finished=self._step_finished,
            slo_ok=self._step_slo_ok,
            slo_scored=self._step_slo_scored,
        )
        self._step_arrived = self._step_finished = 0
        self._step_slo_ok = self._step_slo_scored = 0
        if _mx.enabled():
            # the controller's pressure terms, composite, and ladder rung
            # as labeled gauges (ISSUE 15: the flight recorder sees the
            # pressure BUILD, not just the transition it caused)
            _mx.gauge("overload_pressure", ctrl.last_pressure,
                      engine=self.family)
            for term, v in ctrl.pressure_terms(len(self._pending)).items():
                _mx.gauge("overload_pressure_term", v, engine=self.family,
                          term=term)
            _mx.gauge("overload_rung", ctrl.rung(), engine=self.family)
        if tr is not None:
            _mx.counter("overload_transitions_total", engine=self.family,
                        to=tr.to)
            self._on_brownout_transition(tr)

    def _on_brownout_transition(self, tr) -> None:
        """One ladder move: record it (health registry + obs span with the
        attributed cause), shed the queued batch backlog on reaching
        ``shed_all_batch``, and apply/revert the precision downshift
        around the brownout2 boundary (through the same rebuild +
        prefix-replay machinery the elastic arc uses — no in-flight
        request loses a token over a precision change)."""
        ctrl = self._overload
        self.metrics.count("brownout_transitions")
        self.metrics.count(f"brownout_to_{tr.to}")
        health.record_brownout(
            self.family, tr.frm, tr.to, pressure=tr.pressure, cause=tr.cause
        )
        _obs.record_span(
            "serving:brownout", tr.t_s, tr.t_s, cat="serving",
            track=f"{self._obs_tag}engine", frm=tr.frm, to=tr.to,
            pressure=tr.pressure, cause=tr.cause,
        )
        if tr.to == _overload.SHED_ALL_BATCH:
            now = self.clock.monotonic()
            batch = [
                i for i, st in enumerate(self._pending)
                if priority_rank(st.priority) > 0
            ]
            for i in reversed(batch):
                st = self._pending[i]
                del self._pending[i]
                self._states.pop(st.req.uid)
                self._record_shed(
                    st.req.uid, st.priority, st.t_enqueue, now,
                    "ladder reached shed_all_batch: queued batch shed",
                )
        want_shed = ctrl.wants_spec_shed()
        if want_shed != self._spec_shed:
            self._spec_shed = want_shed
            if (self.serving.speculative is not None
                    and self.serving.speculative.k >= 2):
                # the NEGATIVE-cost rung (ISSUE 20): drop/restore the
                # draft model via the same counted rebuild + prefix
                # replay as the precision stages below — no in-flight
                # request loses a token over the mode flip. On a
                # non-speculative engine the rung is recorded but
                # rebuilds nothing (armed-untriggered ≡ disarmed).
                if want_shed:
                    self.metrics.count("spec_sheds")
                    self._rebuild(
                        f"brownout speculation shed ({tr.frm} -> {tr.to})"
                    )
                else:
                    self._rebuild(
                        f"brownout recovery: speculation restored "
                        f"({tr.frm} -> {tr.to})"
                    )
        depth = ctrl.downshift_depth()
        if depth != self._downshift_depth:
            deeper = depth > self._downshift_depth
            self._downshift_depth = depth
            cfg = self._base_cfg
            for stage in ctrl.config.downshift_stages()[:depth]:
                cfg = stage(cfg)
            self.cfg = cfg
            if deeper:
                self.metrics.count("precision_downshifts")
                self._rebuild(
                    f"brownout precision downshift ({tr.frm} -> {tr.to})"
                )
            else:
                self._rebuild(
                    f"brownout recovery: precision restored "
                    f"({tr.frm} -> {tr.to})"
                )

    def _record_shed(self, uid: Any, priority: str, t_enqueue: float,
                     now: float, reason: str) -> "Shed":
        """One request's typed load-shed terminal: metrics + per-class
        counters, a health event, an obs instant, and the results entry
        (exactly-one-terminal-state bookkeeping)."""
        self.metrics.count("shed")
        self.metrics.count_class("shed", priority)
        _mx.counter("serving_requests_total", engine=self.family,
                    terminal="shed", priority=priority)
        if self._overload is not None:
            self._overload.note_shed(priority)
        health.record_shed(self.family, uid, priority, reason)
        if uid in self.results:
            raise RuntimeError(
                f"request {uid!r} shed after a terminal state — shed "
                f"bookkeeping bug"
            )
        shed = Shed(uid=uid, reason=reason, priority=priority,
                    t_enqueue=t_enqueue, t_shed=now)
        self.results[uid] = shed
        _obs.record_span("serving:shed", now, now, cat="serving",
                         track=f"{self._obs_tag}req:{uid}", uid=str(uid),
                         reason=reason, priority=priority)
        return shed

    def _record_terminal_rejected(self, rej: "Rejected") -> None:
        """Retry budget exhausted: the Rejected becomes the request's
        terminal state (never silently dropped — the soak invariant)."""
        if rej.uid in self.results:
            raise RuntimeError(
                f"request {rej.uid!r} rejected after a terminal state — "
                f"retry bookkeeping bug"
            )
        self.metrics.count("rejected_final")
        self.metrics.count_class("rejected_final", rej.priority)
        _mx.counter("serving_requests_total", engine=self.family,
                    terminal="rejected_final",
                    priority=rej.priority or "interactive")
        self.results[rej.uid] = rej

    def _observe(self, now: float) -> None:
        b = self._batcher
        self.metrics.observe_step(
            queue_depth=len(self._pending) + len(b.queue),
            occupied=b.n_active, slots=self.cfg.batch,
        )
        if _mx.enabled():
            # the continuous-export mirror of the private step tallies
            # (ISSUE 15 tentpole): labeled by engine so pool engines
            # (serving_pool_prefill/decode) land on their own series
            _mx.counter("serving_steps_total", engine=self.family)
            _mx.gauge("serving_queue_depth",
                      len(self._pending) + len(b.queue), engine=self.family)
            _mx.gauge("serving_slots_occupied", b.n_active,
                      engine=self.family)
            _mx.gauge("serving_world_size", self.world_size,
                      engine=self.family)
            elapsed = max(now - self._t0, 1e-9)
            _mx.gauge("serving_tokens_goodput_per_s",
                      round(self.metrics.tokens_goodput / elapsed, 6),
                      engine=self.family)
            if self.serving.speculative is not None:
                # the ISSUE 20 mirror: acceptance-rate / live-k gauges,
                # rollback + accepted-token counters as DELTAS against
                # the cumulative tallies (counters must only ever go up,
                # and the tallies survive rebuilds via _fold_spec)
                cum = self._spec_cum()
                if cum["tokens_offered"]:
                    _mx.gauge(
                        "spec_accept_rate",
                        round(cum["tokens_accepted"]
                              / cum["tokens_offered"], 6),
                        engine=self.family,
                    )
                _mx.gauge("spec_k_live",
                          getattr(self._batcher, "k_live", 0),
                          engine=self.family)
                for name, key in (
                    ("spec_rollback_total", "rollback_total"),
                    ("spec_tokens_accepted_total", "tokens_accepted"),
                ):
                    d = cum[key] - self._spec_mx_seen.get(key, 0)
                    if d > 0:
                        _mx.counter(name, d, engine=self.family)
                        self._spec_mx_seen[key] = cum[key]
        for i, r in enumerate(b.slot_req):
            if r is None:
                continue
            st = self._states[r.uid]
            if b.slot_out[i]:
                if st.awaiting_first:
                    self._record_first(st, now)
                self._stamp_tokens(st, len(b.slot_out[i]), now)
        for uid, toks, reason in b.drain_poisoned():
            self._finalize_poisoned(uid, toks, reason, now)
        for uid, reason in b.drain_struck():
            self._restart_struck(uid, reason, now)
        for uid, toks in b.drain_finished():
            self._finalize(uid, toks, now)

    @staticmethod
    def _stamp_tokens(st: _ReqState, n_live: int, now: float) -> None:
        """Give ``now`` to every token of ``st`` not stamped yet: its
        replay prefix plus the ``n_live`` tokens of its current slot."""
        n_new = len(st.tokens) + n_live - len(st.t_tokens)
        if n_new > 0:
            st.t_tokens.extend([now] * n_new)

    def _restart_struck(self, uid: Any, reason: str, now: float) -> None:
        """Prefix-strike fan-out (ISSUE 12): this in-flight request was
        reading a shared page of a POISONED slot's chain, so everything it
        generated is suspect — restart it COLD: the original request
        re-enters the batcher (fresh seed-derived RNG, tokens discarded),
        re-prefills into fresh private pages (the struck chain is gone
        from the trie), and regenerates the same stream a never-corrupted
        run produces. TTFT after the strike re-measures as a resumed
        event, like every other disruption."""
        st = self._states[uid]
        st.tokens = []
        st.t_tokens = []
        st.resumed += 1
        st.awaiting_first = True
        if not st.first_recorded:
            st.t_first = None
        self.metrics.count("prefix_struck")
        _mx.counter("serving_prefix_struck_total", engine=self.family)
        _obs.record_span(
            "serving:px_strike", now, now, cat="serving",
            track=f"{self._obs_tag}req:{uid}", uid=str(uid), reason=reason,
        )
        self._batcher.submit(st.req)

    def _record_first(self, st: _ReqState, now: float) -> None:
        st.awaiting_first = False
        st.t_first = now
        self._n_first_tokens += 1
        ttft_ms = (now - st.t_enqueue) * 1e3
        prio = st.priority if self._overload is not None else None
        if st.resumed:
            # the replay contract: TTFT after a disruption is re-measured
            # and reported as a RESUMED event, never mixed into the clean
            # TTFT distribution
            self.metrics.observe_first_token(ttft_ms, resumed=True,
                                             priority=prio)
            _mx.observe("serving_resumed_ttft_ms", ttft_ms,
                        engine=self.family)
        elif not st.first_recorded:
            st.first_recorded = True
            self.metrics.observe_first_token(ttft_ms, resumed=False,
                                             priority=prio)
            _mx.observe("serving_ttft_ms", ttft_ms, engine=self.family)

    def _finalize(self, uid: Any, toks: list, now: float) -> None:
        st = self._states.pop(uid)
        if st.awaiting_first and toks:
            # finished within its admission step (instant EOS / prefill
            # one-shot): the first token was never observed mid-slot
            self._record_first(st, now)
        self._stamp_tokens(st, len(toks), now)
        tokens = st.tokens + list(toks)
        ttft_ms = (st.t_first - st.t_enqueue) * 1e3
        e2e_ms = (now - st.t_enqueue) * 1e3
        # per-output-token latency over the FINAL uninterrupted segment
        # only: after a replay, st.t_first is the post-resume first token,
        # so dividing by the TOTAL count would average the replay prefix's
        # tokens into a span that never generated them and understate tpot
        # exactly in the elastic-arc runs this metric exists to judge
        tpot_ms = (
            (now - st.t_first) / (len(toks) - 1) * 1e3
            if len(toks) > 1 else None
        )
        # deadline scoring (ISSUE 11): an in-flight request past its
        # deadline FINISHES (evicting device work buys nothing) but is
        # scored SLO-missed — its tokens never count toward goodput
        deadline_ok = None
        if st.deadline is not None:
            deadline_ok = now <= st.deadline
            if not deadline_ok:
                self.metrics.count("deadline_missed")
                self.metrics.count_class("deadline_missed", st.priority)
        goodput_ok = self.metrics.observe_finished(
            ttft_ms=ttft_ms, e2e_ms=e2e_ms, tpot_ms=tpot_ms,
            n_tokens=len(tokens),
            priority=st.priority if self._overload is not None else None,
            deadline_ok=deadline_ok,
        )
        if _mx.enabled():
            _mx.counter("serving_requests_total", engine=self.family,
                        terminal="finished", priority=st.priority)
            _mx.counter("serving_tokens_total", len(tokens),
                        engine=self.family)
            if goodput_ok:
                _mx.counter("serving_tokens_goodput_total", len(tokens),
                            engine=self.family)
            _mx.observe("serving_e2e_ms", e2e_ms, engine=self.family)
            if tpot_ms is not None:
                _mx.observe("serving_tpot_ms", tpot_ms, engine=self.family)
        ae = self._alert_eng()
        if ae is not None:
            # the goodput-burn / TTFT-burn feed: one sample per scored
            # finish, on the engine clock (evaluated in _alerts_step)
            ae.observe_request(now, slo_ok=goodput_ok, ttft_ms=ttft_ms)
        self._step_finished += 1
        self._n_finished += 1
        if self.metrics.slo is not None or st.deadline is not None:
            self._step_slo_scored += 1
            if goodput_ok:
                self._step_slo_ok += 1
        if uid in self.results:
            raise RuntimeError(
                f"request {uid!r} finished twice — replay bookkeeping bug"
            )
        self.results[uid] = Finished(
            uid=uid, tokens=tokens, t_enqueue=st.t_enqueue,
            t_admitted=st.t_admitted, t_first_token=st.t_first,
            t_finished=now, resumed=st.resumed,
            t_tokens=tuple(st.t_tokens),
        )
        self._record_phase_spans(self.results[uid], n_tokens=len(tokens))

    def _record_phase_spans(self, fin: "Finished", *, n_tokens: int) -> None:
        """Per-request lifecycle phases into the obs tracer (ISSUE 9):
        ``serving:queued`` (enqueue → slot grant), ``serving:prefill``
        (admission → first token), ``serving:decode`` (first token →
        finished), and the whole ``serving:e2e`` arc — each on its own
        request track so exported timelines show concurrent requests as
        parallel lanes. Timestamps are the ENGINE clock's (explicit, via
        record_span), so FakeClock runs export byte-identically. No-op
        when obs is disarmed."""
        if not _obs.span_enabled():
            return
        track = f"{self._obs_tag}req:{fin.uid}"

        def phase(name, t0, t1, **attrs):
            _obs.record_span(name, t0, t1, cat="serving", track=track,
                             uid=str(fin.uid), **attrs)
            st = self._phase_stats.get(name)
            if st is None:
                st = self._phase_stats[name] = _obs.tracer.DurationStats()
            st.record((t1 - t0) * 1e3)

        if fin.t_admitted is not None:
            phase("serving:queued", fin.t_enqueue, fin.t_admitted)
        if fin.t_first_token is not None:
            if fin.t_admitted is not None:
                phase("serving:prefill", fin.t_admitted, fin.t_first_token,
                      resumed=fin.resumed)
            phase("serving:decode", fin.t_first_token, fin.t_finished,
                  n_tokens=n_tokens)
        phase("serving:e2e", fin.t_enqueue, fin.t_finished,
              resumed=fin.resumed, n_tokens=n_tokens)

    def _finalize_poisoned(self, uid: Any, toks: list, reason: str,
                           now: float) -> None:
        """Per-request poison quarantine (ISSUE 8): the batcher evicted
        this request on a non-finite logit row — typed-reject it (the
        result becomes a :class:`Poisoned`, never a Finished) and keep
        serving everyone else. The poisoned request costs exactly one
        slot eviction; survivors' streams are untouched."""
        st = self._states.pop(uid)
        self.metrics.count("poisoned")
        _mx.counter("serving_requests_total", engine=self.family,
                    terminal="poisoned", priority=st.priority)
        if uid in self.results:
            raise RuntimeError(
                f"request {uid!r} finished twice — poison bookkeeping bug"
            )
        self.results[uid] = Poisoned(
            uid=uid, tokens=st.tokens + list(toks), reason=reason,
            t_enqueue=st.t_enqueue, t_poisoned=now, resumed=st.resumed,
        )
        _obs.record_span("serving:poisoned", now, now, cat="serving",
                         track=f"{self._obs_tag}req:{uid}", uid=str(uid),
                         reason=reason)

    # -- elastic shrink / regrow ---------------------------------------

    def _attribute_timeout(self, exc: BaseException) -> None:
        """Peer attribution for one step timeout — overridable so a POOL
        engine (serving/disagg.py) can offset the records' pool-local PE
        indices into the topology's global numbering before striking."""
        self._elastic.note_timeout_exc(exc, family=self.family)

    def _attribute_integrity(self, exc: BaseException) -> None:
        """Corruption-attribution twin of :meth:`_attribute_timeout`."""
        self._elastic.note_integrity_exc(exc, family=self.family)

    def _on_step_timeout(self, exc: BaseException) -> None:
        # offer the failure to peer attribution (the call_with_retry
        # convention; a no-op unless config.elastic) — by quarantine
        # threshold the straggler is out and _target_mesh shrinks
        self._attribute_timeout(exc)
        self.metrics.count("step_timeouts")
        self._failures += 1
        if self._failures > self.serving.max_step_failures:
            raise UnrecoverableEngineError(
                f"serving engine: {self._failures} consecutive step "
                f"timeouts without recovering — rebuild/replay cannot make "
                f"progress (see resilience.health.snapshot())"
            ) from exc
        self._rebuild("step timeout")

    def _on_step_integrity(self, exc: BaseException) -> None:
        # the corruption twin of _on_step_timeout: strike the PEs the
        # integrity records name (note_integrity_exc — the extended
        # note_timeout_exc convention), then rebuild + prefix-replay; a
        # persistently corrupt PE accumulates strikes to quarantine and
        # _target_mesh shrinks around it, exactly the straggler arc
        self._attribute_integrity(exc)
        self.metrics.count("step_integrity")
        self._failures += 1
        if self._failures > self.serving.max_step_failures:
            raise UnrecoverableEngineError(
                f"serving engine: {self._failures} consecutive corrupt "
                f"steps without recovering — rebuild/replay cannot make "
                f"progress (see resilience.health.snapshot())"
            ) from exc
        self._rebuild("step integrity failure")

    def _rebuild(self, reason: str) -> None:
        """Rebuild the batcher on the current target mesh and prefix-replay
        every in-flight request. The old step's donated cache is dead
        either way (a timed-out donating step consumed it), so replay —
        prompt + tokens-so-far re-entering as a fresh prompt — is the
        re-materialization path; no generated token is lost."""
        with _obs.span("tdt.engine.rebuild", reason=reason) as sp:
            old = self._batcher
            now = self.clock.monotonic()
            rebuild_t0 = now
            # completed work survives first (the drain_finished contract);
            # poisoned evictions are final too — they must not re-enter replay
            for uid, toks, poison_reason in old.drain_poisoned():
                self._finalize_poisoned(uid, toks, poison_reason, now)
            for uid, toks in old.drain_finished():
                self._finalize(uid, toks, now)
            # struck readers restart into the NEW batcher below; px counters
            # accumulate at the engine so a rebuild never zeroes the hit-rate
            struck = old.drain_struck()
            self._fold_px(old.prefix_cache_stats())
            self._fold_spec(old)
            for k in _ROUND_COUNTERS:
                self._round_totals[k] += getattr(old, k)
            active, queued = old.export_in_flight()
            target = self._target_mesh()
            self.rebuilds += 1
            self.metrics.count("rebuilds")
            _mx.counter("serving_rebuilds_total", engine=self.family)
            health.record_serving_rebuild(
                self.family, world=int(target.devices.size),
                reason=f"{reason}; {len(active)} in-flight replayed, "
                       f"{len(queued)} re-queued",
            )
            self.mesh = target
            self._batcher = self._build(target)
            for req, toks, rng in active:
                st = self._states[req.uid]
                st.tokens.extend(toks)
                self._stamp_tokens(st, 0, now)
                st.resumed += 1
                st.awaiting_first = True
                st.t_first = st.t_first if st.first_recorded else None
                self.metrics.count("resumed")
                # prefix replay: everything generated so far becomes prompt;
                # the live RNG continues a sampled stream mid-draw
                self._batcher.submit(dataclasses.replace(
                    st.req,
                    prompt=list(st.req.prompt) + st.tokens,
                    max_new_tokens=st.req.max_new_tokens - len(st.tokens),
                    rng=rng,
                ))
            for req in queued:
                # admitted but never started (possibly already a replay):
                # resubmit verbatim
                self._batcher.submit(req)
            for uid, strike_reason in struck:
                self._restart_struck(uid, strike_reason, now)
            # the rebuild/replay arc as one engine-track span (ISSUE 9) —
            # engine-clock timestamps, so FakeClock runs export identically
            _obs.record_span(
                "serving:rebuild", rebuild_t0, self.clock.monotonic(),
                cat="serving", track=f"{self._obs_tag}engine", reason=reason,
                world=int(target.devices.size), replayed=len(active),
                requeued=len(queued),
            )
            sp.set("replayed", len(active))

    def _maybe_probe(self) -> None:
        if self.full_mesh.devices.ndim != 1 or not elastic.enabled():
            return
        if not self._elastic.quarantined_pes():
            self._steps_since_probe = 0
            return
        self._steps_since_probe += 1
        if self._steps_since_probe < self.serving.probe_interval_steps:
            return
        self._steps_since_probe = 0
        self._elastic.probe_quarantined(self.full_mesh, axis=self.cfg.axis)
        target = self._target_mesh()
        if list(target.devices.flat) != list(self.mesh.devices.flat):
            self._rebuild("probation re-admission regrew the world")

    # -- driving --------------------------------------------------------

    def serve(self, traffic=(), *, max_steps: int = 1_000_000) -> dict:
        """Drive a (time-sorted or not) iterable of :class:`Arrival`
        through the engine until all offered traffic is ingested and —
        unless :meth:`stop` said otherwise — every request reached its
        terminal state. Between work, the loop sleeps the (injectable)
        clock to the next arrival. With the overload controller armed, a
        :class:`Rejected` submit draws from the per-class retry budget
        and re-enters the schedule after the deterministic backoff
        (``overload.try_resubmit``); budget/attempt exhaustion makes the
        Rejected terminal. Returns ``dict(self.results)``."""
        # (t_s, seq, arrival, attempt) min-heap: resubmits re-enter the
        # schedule at now + backoff without re-sorting; seq keeps equal
        # timestamps FIFO and Arrival objects out of the comparison
        heap: list = []
        seq = 0
        for a in sorted(traffic, key=lambda a: a.t_s):
            heap.append((a.t_s, seq, a, 0))
            seq += 1
        heapq.heapify(heap)
        with _obs.span("tdt.engine.serve", offered=len(heap)):
            steps = 0
            while True:
                now = self.clock.monotonic()
                if self._stopping and heap:
                    for _, _, a, attempt in heap:
                        uid = a.request.uid
                        if (self._overload is not None and attempt > 0
                                and uid is not None):
                            # an already-offered request awaiting its backoff:
                            # cancellation makes its Rejected terminal — the
                            # never-a-silent-drop invariant survives stop()
                            self._record_terminal_rejected(Rejected(
                                uid, "cancelled by stop() while awaiting "
                                "resubmit", len(self._pending),
                                getattr(a, "priority", "interactive"),
                            ))
                        else:
                            self.metrics.count("cancelled")
                    heap.clear()
                if heap and heap[0][0] <= now:
                    seq = self._ingest(heap, seq, now)
                if self._step_once():
                    steps += 1
                    if steps >= max_steps:
                        raise RuntimeError(
                            f"serve(max_steps={max_steps}) exhausted with "
                            f"work still in flight; finished results are "
                            f"intact in self.results"
                        )
                    continue
                if heap:
                    dt = heap[0][0] - self.clock.monotonic()
                    if dt > 0:
                        with _obs.span("tdt.engine.sleep",
                                       dt_us=int(dt * 1e6)):
                            self.clock.sleep(dt)
                    continue
                return dict(self.results)

    def _ingest(self, heap: list, seq: int, now: float) -> int:
        """Submit every arrival due by ``now``; returns the next ``seq``.
        ``late_us`` is how long after its due time the loop popped an
        entry (it looks at the heap only between steps)."""
        n = late_sum = late_max = 0
        with _obs.span("tdt.engine.ingest") as sp:
            while heap and heap[0][0] <= now:
                t_due, _, a, attempt = heapq.heappop(heap)
                n += 1
                late = int((now - t_due) * 1e6)
                late_sum += late
                late_max = max(late_max, late)
                # arrival_t is ALWAYS the originally-offered time (a.t_s),
                # resubmits included: TTFT/e2e accrue from when the client
                # first asked, and the deadline budget anchors there too —
                # a retry must not rebase the SLO it is judged against
                res = self.submit(
                    a.request, arrival_t=a.t_s,
                    priority=getattr(a, "priority", "interactive"),
                    deadline_ms=getattr(a, "deadline_ms", None),
                )
                if isinstance(res, Rejected) and self._overload is not None:
                    delay = self._overload.try_resubmit(
                        res.priority, attempt, now=self.clock.monotonic()
                    )
                    if delay is None:
                        self._record_terminal_rejected(res)
                    else:
                        self.metrics.count("resubmitted")
                        self.metrics.count_class("resubmitted", res.priority)
                        heapq.heappush(heap, (
                            self.clock.monotonic() + delay, seq, a,
                            attempt + 1,
                        ))
                        seq += 1
            sp.set("n", n)
            sp.set("late_us_sum", late_sum)
            sp.set("late_us_max", late_max)
        return seq

    def run_until_idle(self, max_steps: int = 1_000_000) -> dict:
        """Serve what is already queued/in flight (no new traffic)."""
        return self.serve((), max_steps=max_steps)

    def stop(self, drain: bool = True) -> None:
        """Stop ingesting new traffic. ``drain=True`` (graceful): every
        already-enqueued request still runs to completion on the next
        ``serve``/``run_until_idle``. ``drain=False``: the arrival queue
        is cancelled (counted, never silently dropped); in-flight slots
        still finish — abandoning them mid-device would lose work for no
        capacity gain."""
        self._stopping = True
        if not drain:
            while self._pending:
                st = self._pending.popleft()
                del self._states[st.req.uid]
                self.metrics.count("cancelled")

    # -- readout --------------------------------------------------------

    def _fold_px(self, stats: dict | None) -> None:
        if not stats:
            return
        for k in PX_COUNTERS:
            self._px_totals[k] = self._px_totals.get(k, 0) + stats.get(k, 0)

    # -- speculative readout (ISSUE 20) ----------------------------------

    _SPEC_COUNTERS = ("rounds", "tokens_offered", "tokens_accepted",
                      "rollback_total", "bonus_total", "k_transitions",
                      "draft_faults_injected")

    def _fold_spec(self, old) -> None:
        """Accumulate a retiring batcher's speculative counters — a
        rebuild (elastic, downshift, spec shed) starts fresh tallies."""
        snap = getattr(old, "spec_snapshot", None)
        if snap is None:
            return
        for k, v in snap().items():
            if k in self._SPEC_COUNTERS:
                self._spec_totals[k] = self._spec_totals.get(k, 0) + v

    def _spec_cum(self) -> dict:
        """Cumulative speculative counters: retired batchers + live."""
        live = getattr(self._batcher, "spec_snapshot", None)
        live = live() if live is not None else {}
        return {
            k: self._spec_totals.get(k, 0) + live.get(k, 0)
            for k in self._SPEC_COUNTERS
        }

    def _on_spec_k_change(self, frm: int, to: int, alpha: float) -> None:
        """The live batcher's adaptive-k callback: health event (the
        informational SPEC_K kind), engine counter, _mx counter."""
        self.metrics.count("spec_k_transitions")
        health.record_spec_k(self.family, frm, to, alpha=alpha)
        _mx.counter("spec_k_transitions_total", engine=self.family)

    def _spec_section(self) -> dict | None:
        """The engine snapshot's "speculative" section (None when
        disarmed, so disarmed snapshots stay byte-identical)."""
        if self.serving.speculative is None:
            return None
        cum = self._spec_cum()
        offered = cum["tokens_offered"]
        out = {
            "k": self.serving.speculative.k,
            "k_live": getattr(self._batcher, "k_live", 0),
            "shed": self._spec_shed,
            "accept_rate": (
                round(cum["tokens_accepted"] / offered, 6) if offered
                else None
            ),
            **cum,
        }
        return out

    def _px_snapshot(self) -> dict | None:
        """Prefix-cache counters summed across every batcher this engine
        has run (rebuilds start fresh tries), gauges from the live one."""
        cur = self._batcher.prefix_cache_stats()
        if cur is None and not self._px_totals:
            return None
        out = {
            k: (cur or {}).get(k, 0) + self._px_totals.get(k, 0)
            for k in PX_COUNTERS
        }
        for k in PX_GAUGES:
            out[k] = (cur or {}).get(k, 0)
        out["hit_rate"] = round(out["hits"] / max(1, out["lookups"]), 6)
        return out

    def snapshot(self) -> dict:
        """The engine's health.snapshot() analogue: serving metrics plus
        world/queue/compile-cache facts. Deterministic under a FakeClock
        (nothing here reads wall time)."""
        now = self.clock.monotonic()
        snap = self.metrics.snapshot()
        elapsed = max(now - self._t0, 1e-9)
        snap["tokens"]["per_s"] = round(
            self.metrics.tokens_generated / elapsed, 6
        )
        # goodput (ISSUE 11): SLO-attaining throughput — the A/B axis the
        # overload λ-sweep plots (collapses past saturation without the
        # controller, plateaus with it)
        snap["tokens"]["goodput_per_s"] = round(
            self.metrics.tokens_goodput / elapsed, 6
        )
        snap["engine"] = {
            "world_size": self.world_size,
            "full_world_size": int(self.full_mesh.devices.size),
            "rebuilds": self.rebuilds,
            "queue_depth": len(self._pending),
            "in_flight": len(self._states) - len(self._pending),
            "prefill_bucket_programs": self._batcher.prefill_bucket_count,
            "clock_s": round(now - self._t0, 9),
        }
        if self._overload is not None:
            snap["overload"] = self._overload.snapshot()
        if self._alerts is not None:
            # only when the alert tier is armed, so disarmed snapshots
            # stay byte-identical to pre-flight-recorder ones (pinned)
            snap["alerts"] = self._alerts.snapshot()
        # decode rounds, and how many of them the lookahead carried: a round
        # whose step the round before had sent, and steps sent in vain
        snap["batcher"] = {
            k: self._round_totals[k] + getattr(self._batcher, k)
            for k in _ROUND_COUNTERS
        }
        px = self._px_snapshot()
        if px is not None:
            # the ISSUE 12 surface: hit-rate, pages-shared gauge, and
            # prefill-tokens-saved counters the bench A/B reads
            snap["prefix_cache"] = px
        sp = self._spec_section()
        if sp is not None:
            # the ISSUE 20 surface: acceptance rate, live k, rollback
            # and accepted-token totals the bench info lines read
            snap["speculative"] = sp
        if _obs.span_enabled():
            # per-phase p50/p99 from the span tracer (ISSUE 9 satellite):
            # the λ-sweep rows carry a step-time BREAKDOWN (queued /
            # prefill / decode), not just end-to-end percentiles. Only
            # present when obs is armed, so disarmed snapshots are
            # byte-identical to pre-obs ones. ENGINE-LOCAL stats, not the
            # process-global tracer's — two live engines (a canary beside
            # production, an elastic regrow test) must each report their
            # OWN requests' percentiles.
            snap["span_ms"] = {
                name: st.snapshot()
                for name, st in sorted(self._phase_stats.items())
            }
        return snap
