"""Exporters: fold host spans + device wait telemetry + health + serving
metrics into one artifact (ISSUE 9c).

Two surfaces:

- :func:`export_chrome_trace` — a Perfetto-loadable chrome trace JSON:
  every finished span becomes a ``"ph": "X"`` complete event (instants
  become ``"ph": "i"``), and every aggregated per-(family, site, kind)
  wait-spin histogram becomes an instant on a dedicated
  ``device wait telemetry`` process, its histogram in ``args``. Dropped
  into a ``group_profile`` run dir it sits next to the XProf XPlane
  files, so the profile viewer renders kernels and host spans as one
  timeline; ``merge=True`` folds events into an existing artifact (the
  bench driver's per-metric subprocesses share one ``--obs-trace`` file
  that way). Serialization is ``sort_keys`` + fixed separators and every
  timestamp comes from the injectable clock, so a FakeClock run exports
  byte-identically (asserted in tests/test_obs.py).
- :func:`snapshot` — one JSON-able dict merging span stats, the wait
  telemetry summary, ``resilience.health.snapshot()``, the snapshot
  of every live :class:`~triton_dist_tpu.serving.engine.ServingEngine`
  (engines self-register at construction; weakly, so a dead engine never
  pins memory or shows up as a ghost), and — when the flight recorder is
  armed (ISSUE 15) — the metrics plane, the live alert states, and the
  black-box bundle census.

The top-level snapshot key set is THE versioned schema
(:data:`SNAPSHOT_SCHEMA` / :data:`SNAPSHOT_SECTIONS`): every section an
``obs.snapshot()`` artifact may carry is
registered here with its contract, :func:`validate_snapshot` refuses
unknown keys at snapshot time, and serving-engine snapshots are held to
the :data:`ENGINE_SECTIONS` registry by the schema test
(tests/test_flight_recorder.py) — a future section must register or it
cannot land (no silent schema collisions).
"""

from __future__ import annotations

import json
import os
import weakref
from typing import Any

from triton_dist_tpu.obs import telemetry as _telemetry
from triton_dist_tpu.obs import tracer as _tracer

# the versioned snapshot schema (ISSUE 15 satellite): bump the suffix on
# any INCOMPATIBLE change to a registered section's shape
SNAPSHOT_SCHEMA = "tdt-snapshot-v1"

# obs.snapshot() / --health-json top-level sections. "always" sections
# appear in every snapshot; "armed" ones only with their tier armed —
# so a disarmed snapshot stays byte-identical to its pre-flight-recorder
# self (the arming discipline).
SNAPSHOT_SECTIONS = {
    "schema": "always: the SNAPSHOT_SCHEMA version string",
    "spans": "always: per-name span duration stats (tracer.span_stats)",
    "dropped_spans": "always: span-ring evictions (counted, never silent)",
    "wait_telemetry": "always: per-(family, site, kind) spin aggregation",
    "health": "always: resilience.health.snapshot() (elastic included)",
    "serving": "always: live serving engines' snapshots (None when none)",
    "metrics": "armed (ObsConfig.metrics): metrics-plane JSON snapshot",
    "alerts": "armed (ObsConfig.alerts): live burn-rate rule states",
    "blackbox": "armed (ObsConfig.blackbox): incident-bundle census",
}

# ServingEngine.snapshot() / DisaggServingEngine.snapshot() top-level
# sections (pool snapshots under "pools" recurse into this same table).
ENGINE_SECTIONS = {
    "requests": "always: terminal/lifecycle counters",
    "tokens": "always: generated/goodput totals + per_s rates",
    "latency_ms": "always: ttft/resumed_ttft/tpot/e2e histograms",
    "load": "always: queue-depth / slot-occupancy histograms",
    "slo": "always: SLO targets + attainment (None without targets)",
    "by_class": "armed (overload): per-priority-class counters + TTFT",
    "engine": "always: world/queue/clock facts (disagg: topology facts)",
    "overload": "armed (overload): ladder state, pressure, sheds",
    "batcher": "always: decode rounds, rounds whose step was sent ahead, "
               "steps sent in vain (summed across rebuilds)",
    "prefix_cache": "armed (prefix_cache): PX counters + gauges",
    "speculative": "armed (speculative): accept rate, live k, rollback "
                   "and accepted-token totals",
    "span_ms": "armed (obs spans): per-phase p50/p99 breakdown",
    "alerts": "armed (obs alerts): this engine's rule states",
    "handoff": "disagg only: the handoff plane's counter set",
    "pools": "disagg only: per-pool engine snapshots (ENGINE_SECTIONS)",
}


def validate_snapshot(snap: dict, sections: dict = SNAPSHOT_SECTIONS, *,
                      what: str = "obs.snapshot") -> dict:
    """Refuse top-level keys the schema registry does not name (the
    future-sections-cannot-silently-collide pin). Returns ``snap``."""
    unknown = set(snap) - set(sections)
    if unknown:
        raise ValueError(
            f"{what}: unregistered snapshot section(s) {sorted(unknown)} — "
            f"register them in obs/export.py (SNAPSHOT_SECTIONS / "
            f"ENGINE_SECTIONS) and document them in docs/observability.md"
        )
    return snap

_serving_engines: "weakref.WeakValueDictionary[int, Any]" = (
    weakref.WeakValueDictionary()
)
_serving_seq = 0


def register_serving_engine(engine: Any) -> None:
    """Called by ``ServingEngine.__init__`` so :func:`snapshot` can fold
    live engines' metrics in without the engine ever importing back."""
    global _serving_seq
    _serving_engines[_serving_seq] = engine
    _serving_seq += 1


def _track_tid(track: str) -> int:
    """Stable tid per track NAME (crc32), not per-export ordinals: merged
    artifacts (the bench driver's per-metric subprocesses share one
    ``--obs-trace`` file) must map the same track to the same lane in
    every contributing process, or lanes from different metrics collide.
    Deterministic, so FakeClock exports stay byte-identical."""
    import zlib

    return zlib.crc32(track.encode()) & 0x7FFFFFFF


def chrome_events(label: str | None = None) -> list[dict]:
    """The trace-event list (no file I/O): host spans on pid 0, decoded
    per-site wait-spin histograms on pid 1. ``label`` (e.g. the bench
    metric name) rides into every event's args for merged artifacts."""
    spans = _tracer.spans()
    events: list[dict] = [
        {"ph": "M", "pid": 0, "tid": 0, "name": "process_name",
         "args": {"name": "triton_dist_tpu host spans"}},
        {"ph": "M", "pid": 1, "tid": 0, "name": "process_name",
         "args": {"name": "triton_dist_tpu device wait telemetry"}},
    ]
    # label every track's lane (sorted: deterministic event order)
    for track in sorted({sp.track for sp in spans}):
        events.append({
            "ph": "M", "pid": 0, "tid": _track_tid(track),
            "name": "thread_name", "args": {"name": track},
        })
    for sp in spans:
        args = {k: _jsonable(v) for k, v in sorted(sp.attrs.items())}
        if label is not None:
            args["label"] = label
        ev = {
            "name": sp.name,
            "cat": sp.cat,
            "pid": 0,
            "tid": _track_tid(sp.track),
            "ts": round(sp.t_start * 1e6, 3),   # chrome ts is µs
            "args": args,
        }
        if sp.t_end is not None and sp.t_end > sp.t_start:
            ev["ph"] = "X"
            ev["dur"] = round((sp.t_end - sp.t_start) * 1e6, 3)
        else:
            ev["ph"] = "i"
            ev["s"] = "t"
        events.append(ev)
    summary = _telemetry.wait_summary()
    for site in summary["sites"]:
        args = {
            "calls": site["calls"],
            "total_spins": site["total_spins"],
            "max_spins": site["max_spins"],
            "mean_spins": site["mean_spins"],
            "spin_bins": site["bins"],
            "bin_edges": summary["bin_edges"],
        }
        if label is not None:
            args["label"] = label
        events.append({
            "name": (f"wait {site['family']} site {site['site']} "
                     f"({site['kind']})"),
            "cat": "wait_telemetry",
            "ph": "i",
            "s": "t",
            "pid": 1,
            "tid": 0,
            "ts": 0.0,
            "args": args,
        })
    for fam, n in sorted(summary["overflow_sites"].items()):
        events.append({
            "name": f"wait {fam}: {n} wait(s) past the telemetry window",
            "cat": "wait_telemetry", "ph": "i", "s": "t",
            "pid": 1, "tid": 0, "ts": 0.0,
            "args": {"overflow_sites": n, "telem_slots": _telemetry.TELEM_SLOTS},
        })
    return events


def _jsonable(v: Any) -> Any:
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    return repr(v)


def export_chrome_trace(
    path: str, *, merge: bool = False, label: str | None = None
) -> str:
    """Write (or ``merge`` into) a Perfetto-loadable chrome trace at
    ``path`` and return the path. Atomic whole-file replace, so a killed
    run leaves valid JSON."""
    events = chrome_events(label=label)
    doc: dict = {"traceEvents": events, "displayTimeUnit": "ms"}
    if merge:
        try:
            with open(path) as f:
                prev = json.load(f)
            if isinstance(prev, dict) and isinstance(
                prev.get("traceEvents"), list
            ):
                doc["traceEvents"] = prev["traceEvents"] + events
        except (FileNotFoundError, ValueError):
            pass
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True,
                  separators=(",", ": "))
    os.replace(tmp, path)
    return path


def maybe_export_into(run_dir: str) -> str | None:
    """Drop ``obs_trace.json`` into a profile run dir when obs is armed
    (``utils.group_profile`` calls this on exit, so XProf planes and the
    span/telemetry timeline land in ONE directory). Best-effort: an
    export failure must never take the profiled run down."""
    from triton_dist_tpu import config as tdt_config

    if tdt_config.get_config().obs is None:
        return None
    try:
        return export_chrome_trace(os.path.join(run_dir, "obs_trace.json"))
    except OSError as e:  # pragma: no cover - disk-full etc.
        import sys

        print(f"obs: chrome-trace export into {run_dir!r} failed: {e}",
              file=sys.stderr, flush=True)
        return None


def snapshot() -> dict:
    """One merged observability view under the versioned schema
    (:data:`SNAPSHOT_SECTIONS`): span stats + wait telemetry +
    ``resilience.health`` + every live serving engine's metrics, plus
    the armed flight-recorder sections (metrics plane / alert states /
    bundle census — absent when disarmed, so a disarmed snapshot is
    byte-identical to its pre-flight-recorder self)."""
    from triton_dist_tpu import config as tdt_config
    from triton_dist_tpu.obs import alerts as _alerts
    from triton_dist_tpu.obs import blackbox as _blackbox
    from triton_dist_tpu.obs import metrics as _metrics
    from triton_dist_tpu.resilience import health

    serving = {}
    for key in sorted(_serving_engines.keys()):
        eng = _serving_engines.get(key)
        if eng is not None:
            serving[f"engine{key}"] = eng.snapshot()
    snap = {
        "schema": SNAPSHOT_SCHEMA,
        "spans": _tracer.span_stats(),
        "dropped_spans": _tracer.dropped_spans(),
        "wait_telemetry": _telemetry.wait_summary(),
        "health": health.snapshot(),
        "serving": serving or None,
    }
    ocfg = tdt_config.get_config().obs
    if ocfg is not None:
        if getattr(ocfg, "metrics", None) is not None:
            snap["metrics"] = _metrics.json_snapshot()
        if getattr(ocfg, "alerts", None) is not None:
            snap["alerts"] = _alerts.state_snapshot()
        if getattr(ocfg, "blackbox", None) is not None:
            snap["blackbox"] = _blackbox.census()
    return validate_snapshot(snap)
