"""The serving subsystem (ISSUE 6): an SLO-metered, traffic-driven,
elastic serving loop layered over the kernel-level scheduler
(``models/decode.ContinuousBatcher``).

Five parts (docs/serving.md "Serving engine" is the full contract):

- :mod:`engine` — :class:`ServingEngine`: lifecycle timestamps at the
  host scheduling boundary (enqueue → admitted → first token →
  finished), a bounded arrival queue with reject/block backpressure,
  pluggable admission (FCFS / shortest-prompt-first), graceful drain,
  and the elastic arc: on a step timeout the batcher is rebuilt on the
  serviceable survivor mesh with every in-flight request prefix-replayed
  (prompt + tokens-so-far; no generation lost), and probation
  re-admission grows the world back mid-serving.
- :mod:`speculative` — speculative decoding as a serving mode (ISSUE
  20): per-slot draft+verify rounds in the continuous batcher, armed via
  ``ServingConfig(speculative=SpecDecodeConfig(...))``, adaptive-k, and
  the overload ladder's negative-cost ``shed_speculation`` rung.
- :mod:`overload` — the overload controller (ISSUE 11): deadline
  propagation with typed ``Shed`` expiry, interactive/batch priority
  classes with per-class resubmit token buckets, and the pressure-driven
  brownout ladder (strict priority → precision downshift →
  shed-all-batch, hysteresis on recovery) — armed via
  ``ServingConfig(overload=OverloadConfig(...))``, engine-agnostic by
  design (the disaggregated-pool topology runs one per pool).
- :mod:`traffic` — seeded, replayable synthetic workloads (Poisson /
  deterministic / flash-crowd burst arrivals, length mixtures incl.
  preset-derived ones, per-arrival priority/deadline, Zipf shared-prefix
  mixes); same seed ⇒ byte-identical trace.

- :mod:`disagg` + :mod:`handoff` — disaggregated prefill/decode serving
  (ISSUE 13, docs/serving.md "Disaggregated serving"):
  :class:`DisaggServingEngine` carves the mesh into a prefill pool and
  a decode pool (one ``ServingEngine`` + ``OverloadController`` each,
  pool-scoped elastic attribution), streams finished paged KV across
  the boundary through the fault-tolerant :class:`HandoffPlane` (the
  ``ops/kv_stream.py`` chunked wire's protocol at the host seam: chunk
  canaries, the re-send → re-stream → decode-local-fallback guard
  ladder, the trie as the transfer manifest), admits decode on
  last-page-landed, and degrades pool-level: brownout sheds to
  decode-local prefill, a dead prefill pool collapses to unified with
  zero lost requests.
- :mod:`fleet` — the router plane over N replicas (ISSUE 16,
  docs/serving.md "Fleet"): :class:`FleetRouter` carves a 1-D mesh into
  N equal slices running one full engine each (unified or
  disaggregated), routes each arrival by prefix affinity (the trie page
  keys, cross-replica never-prefill-twice) with pressure-aware fallback
  (brownout rung / outstanding / pressure — a ``shed_all_batch`` replica
  stops receiving batch traffic at the router), and fails over a dead
  replica (typed step death or a firing per-replica flip-burn alert) by
  re-offering every queued + in-flight request to survivors with the
  ORIGINAL arrival/deadline anchors — zero lost, never-rebase-the-SLO.
  ``FleetConfig(replicas=1)`` is byte-identical to the bare engine.
  Since ISSUE 17 the fleet also runs the RECOVERY plane: per-replica
  elastic namespaces (``FleetConfig(elastic_scope=True)`` — one
  ``ElasticScope`` per replica, strikes never cross), replica
  resurrection (``FleetConfig(resurrect=ResurrectConfig(...))`` —
  dead/drained replicas probe back in with a cold trie and an
  affinity-only ramp), disagg pools regrow via pool-scoped probation
  rounds (``DisaggServingConfig.pool_probe_steps``), and a collapsed
  topology un-collapses after a clean probation window
  (``DisaggServingConfig.collapse_probation_steps``) — every knob
  None/off-disarmed, byte-identical off (docs/resilience.md
  "Recovery plane").

Plus the radix-shared paged KV prefix cache (ISSUE 12;
``models/prefix_cache.py``, docs/serving.md "Prefix cache"), armed via
``ServingConfig(prefix_cache=PrefixCacheConfig(...))``: admission-time
longest-prefix match over a trie of refcounted page chains skips the
prefill feed for every fully shared page; None = the pre-cache engine,
byte for byte.
- :mod:`metrics` — streaming log-binned histograms (TTFT,
  per-output-token, e2e), load gauges, SLO attainment, goodput
  (SLO-attaining throughput) and per-class counters, and a
  ``snapshot()`` mirroring ``resilience/health.py``. Since ISSUE 15
  every engine/pool/controller/cache/handoff tally is ALSO mirrored
  into the obs metrics plane (``obs/metrics.py``, labeled per engine),
  engines evaluate SLO burn-rate alerts on their own clock
  (``obs/alerts.py``; armed via ``ObsConfig(alerts=...)``), and every
  health-flipping event freezes a post-mortem bundle
  (``obs/blackbox.py``) — all None-disarmed, byte-identical off.

Everything runs on an injectable clock (``resilience/retry.py``'s module
clock by default), so whole serve runs — latency percentiles included —
are deterministic under a :class:`~triton_dist_tpu.resilience.FakeClock`.
"""

from triton_dist_tpu.models.prefix_cache import PrefixCacheConfig
from triton_dist_tpu.serving.disagg import (
    DisaggServingConfig,
    DisaggServingEngine,
    PoolCollapse,
)
from triton_dist_tpu.serving.fleet import (
    FleetConfig,
    FleetRouter,
    ResurrectConfig,
)
from triton_dist_tpu.serving.engine import (
    Finished,
    Poisoned,
    Rejected,
    ServingConfig,
    ServingEngine,
    Shed,
)
from triton_dist_tpu.serving.handoff import (
    HandoffConfig,
    HandoffPlane,
    HandoffResult,
)
from triton_dist_tpu.serving.metrics import (
    ServingMetrics,
    SLOTargets,
    StreamingHistogram,
)
from triton_dist_tpu.serving.overload import (
    BROWNOUT3,
    LADDER,
    OverloadConfig,
    OverloadController,
    PRIORITIES,
    SHED_SPEC,
    priority_rank,
)
from triton_dist_tpu.serving.speculative import (
    SpecDecodeConfig,
    SpeculativeBatcher,
)
from triton_dist_tpu.serving.traffic import (
    Arrival,
    TrafficSpec,
    generate_trace,
    preset_mix,
    shared_prefix_mix,
    trace_fingerprint,
)

__all__ = [
    "Arrival",
    "DisaggServingConfig",
    "DisaggServingEngine",
    "Finished",
    "FleetConfig",
    "FleetRouter",
    "HandoffConfig",
    "HandoffPlane",
    "HandoffResult",
    "PoolCollapse",
    "BROWNOUT3",
    "LADDER",
    "OverloadConfig",
    "OverloadController",
    "PRIORITIES",
    "Poisoned",
    "PrefixCacheConfig",
    "Rejected",
    "ResurrectConfig",
    "SHED_SPEC",
    "ServingConfig",
    "ServingEngine",
    "ServingMetrics",
    "SLOTargets",
    "Shed",
    "SpecDecodeConfig",
    "SpeculativeBatcher",
    "StreamingHistogram",
    "TrafficSpec",
    "generate_trace",
    "preset_mix",
    "priority_rank",
    "shared_prefix_mix",
    "trace_fingerprint",
]
