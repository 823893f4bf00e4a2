"""Unified observability layer (ISSUE 9 + the ISSUE 15 flight
recorder): host span tracing + device wait telemetry + a continuous
metrics plane + SLO burn-rate alerts + post-mortem incident bundles,
exported as one timeline and one versioned snapshot schema.

Six pieces (docs/observability.md for the full contract):

- :mod:`tracer` — a host-side structured span tracer on the injectable
  resilience clock: nested spans around every guarded op entry (recording
  which ladder rung actually ran — fused / retry / golden fallback /
  integrity), ``jit_shard_map`` dispatch (trace vs cached call), autotune
  sweeps (candidates + crowned config), and the serving engine's
  per-request lifecycle. Ring-buffered like ``resilience/health.py``; a
  FakeClock makes exports byte-identical. ``span`` is also a
  ``jax.profiler.TraceAnnotation`` whenever a profiler session runs,
  armed or not: the serving loop's ``tdt.engine.*`` / ``tdt.batcher.*``
  spans land in the device trace itself that way.
- :mod:`telemetry` — the device tier: with
  ``config.update(obs=ObsConfig(wait_stats=True))`` on top of an armed
  watchdog, every bounded wait site writes its observed spin count into a
  per-kernel telemetry buffer riding the existing diag-output plumbing
  (``ops/common.dist_pallas_call``) — success-path wait-cost attribution
  with NO new signal edges, decoded host-side into per-(family, site,
  kind) spin histograms.
- :mod:`export` — ``export_chrome_trace()`` (a Perfetto-loadable JSON
  that drops into the same ``group_profile`` run dir as the XProf
  planes) and ``snapshot()`` (span stats + wait telemetry +
  ``resilience.health`` + live serving-engine metrics + the flight
  recorder's sections in one dict, under the versioned
  ``export.SNAPSHOT_SCHEMA`` top-level key registry).
- :mod:`metrics` (ISSUE 15) — the continuous metrics plane: a
  dependency-free registry of labeled counters / gauges / streaming
  histograms every serving subsystem mirrors its private tallies into,
  exported as Prometheus text and deterministic sorted-key JSON
  (``MetricsConfig``).
- :mod:`alerts` (ISSUE 15) — multi-window SLO burn-rate rules (goodput,
  p99 TTFT, handoff retry rate, health-flip rate) evaluated on the
  engine clock, pinned to fire BEFORE the brownout ladder reaches
  ``shed_all_batch`` — alerts lead degradation (``AlertConfig``).
- :mod:`blackbox` (ISSUE 15) — the post-mortem black box: every
  health-FLIPPING event freezes a bounded, deterministic incident
  bundle (last-N spans, metrics snapshot, alert state, attribution
  chain), rendered by ``scripts/postmortem.py`` (``BlackboxConfig``).

Disarmed (``config.obs is None``, the default): zero new kernel outputs,
every op result bit-exact, and each host call site pays one attribute
read. Armed: observation-only — clean armed runs stay bit-exact
(chaos-pinned in tests/test_obs.py, the PR 8 canary discipline), and
the flight-recorder tiers arm independently (``ObsConfig(metrics=...)``
etc., each None by default = the byte-identical pre-metrics posture,
pinned in tests/test_flight_recorder.py).
"""

from __future__ import annotations

import dataclasses

from triton_dist_tpu.obs import alerts as alerts
from triton_dist_tpu.obs import blackbox as blackbox
from triton_dist_tpu.obs import export as export
from triton_dist_tpu.obs import metrics as metrics
from triton_dist_tpu.obs import telemetry as telemetry
from triton_dist_tpu.obs import tracer as tracer
from triton_dist_tpu.obs.alerts import AlertConfig, AlertRule
from triton_dist_tpu.obs.blackbox import BlackboxConfig
from triton_dist_tpu.obs.export import (
    SNAPSHOT_SCHEMA,
    chrome_events,
    export_chrome_trace,
    maybe_export_into,
    register_serving_engine,
    snapshot,
    validate_snapshot,
)
from triton_dist_tpu.obs.metrics import MetricsConfig
from triton_dist_tpu.obs.tracer import (
    NULL_SPAN,
    annotate,
    annotate_span,
    dropped_spans,
    instant,
    record_span,
    span,
    span_enabled,
    span_stats,
    spans,
)


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Arm via ``config.update(obs=ObsConfig(...))``.

    spans:      host-side span tracing (guarded op entries, jit dispatch,
                autotune sweeps, serving lifecycle). Host-only — never
                changes a traced program.
    wait_stats: device wait telemetry. Needs the armed watchdog
                (``config.timeout_iters > 0`` — the bounded waits are
                where a spin count exists); silently inert without it,
                exactly like the chunk signals themselves. Adds one
                ``int32[telemetry.TELEM_LEN]`` SMEM output per kernel and
                ~a dozen scalar SMEM ops per wait — a diagnostic posture,
                not a fast path (see docs/observability.md "Overhead").
    max_spans:  span ring-buffer bound; evictions are counted and
                surfaced as ``dropped_spans`` (streaming per-name stats
                are unaffected — no silent caps).
    metrics:    a :class:`~triton_dist_tpu.obs.metrics.MetricsConfig`
                arms the continuous metrics plane (ISSUE 15): every
                serving subsystem mirrors its tallies into the labeled
                counter/gauge/histogram registry. None (default) = the
                byte-identical pre-metrics posture.
    alerts:     an :class:`~triton_dist_tpu.obs.alerts.AlertConfig`
                arms SLO burn-rate alerting in every serving engine
                (evaluated on the engine clock, recorded into health /
                obs / metrics). None (default) = no alert evaluation.
    blackbox:   a :class:`~triton_dist_tpu.obs.blackbox.BlackboxConfig`
                arms the post-mortem black box: every health-flipping
                event writes one deterministic incident bundle into
                ``blackbox.dir``. None (default) = no bundles.
    """

    spans: bool = True
    wait_stats: bool = False
    max_spans: int = 4096
    metrics: "MetricsConfig | None" = None
    alerts: "AlertConfig | None" = None
    blackbox: "BlackboxConfig | None" = None

    def validate(self) -> "ObsConfig":
        if self.max_spans < 1:
            raise ValueError(
                f"ObsConfig.max_spans must be >= 1, got {self.max_spans}"
            )
        for sub in (self.metrics, self.alerts, self.blackbox):
            if sub is not None:
                sub.validate()
        return self


def get_obs_config() -> "ObsConfig | None":
    from triton_dist_tpu import config as tdt_config

    return tdt_config.get_config().obs


def wait_stats_enabled() -> bool:
    """Whether the device wait-telemetry tier is requested (the kernel
    side additionally requires the armed watchdog — ``ops/common``
    checks both)."""
    cfg = get_obs_config()
    return cfg is not None and cfg.wait_stats


def reset() -> None:
    """Clear spans, the wait-telemetry aggregation, AND the flight
    recorder's registries — metrics series, alert states, blackbox
    census (per-test / per-λ isolation; config stays untouched)."""
    tracer.reset()
    telemetry.reset()
    metrics.reset()
    alerts.reset()
    blackbox.reset()


__all__ = [
    "AlertConfig",
    "AlertRule",
    "BlackboxConfig",
    "MetricsConfig",
    "ObsConfig",
    "NULL_SPAN",
    "SNAPSHOT_SCHEMA",
    "alerts",
    "annotate",
    "annotate_span",
    "blackbox",
    "chrome_events",
    "dropped_spans",
    "export",
    "export_chrome_trace",
    "get_obs_config",
    "instant",
    "maybe_export_into",
    "metrics",
    "record_span",
    "register_serving_engine",
    "reset",
    "snapshot",
    "span",
    "span_enabled",
    "span_stats",
    "spans",
    "telemetry",
    "tracer",
    "validate_snapshot",
    "wait_stats_enabled",
]
