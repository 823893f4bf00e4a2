"""Flight recorder (ISSUE 15): the unified metrics plane, SLO burn-rate
alerts, and deterministic post-mortem incident bundles.

Tier structure (the test_overload.py convention):

- **host tier**: metrics-registry units (types, labels, series bound,
  export formats), alert-rule windowing/hysteresis units, black-box
  bundle mechanics (one bundle per triggering kind, suppression counted,
  atomic deterministic JSON), the snapshot schema registry;
- **engine tier** (world-1 mesh, tiny 1-block model): byte-identical
  metrics exports and incident bundles across two FakeClock replays of
  one seeded serve (``cmp``-verified, the bench-artifact discipline),
  the alert-fires-BEFORE-shed_all_batch ordering pin, and the
  disarmed ≡ pre-metrics byte-identity pin for engine/overload/handoff
  snapshots;
- **chaos tier** (``pytest.mark.chaos``, rides chaos_matrix.sh): the
  quick seeded soak campaign under the armed flight recorder — exactly
  one bundle per health-flipping event (no duplicates, no misses), with
  real flips so the invariant is not vacuous;
- **CLI tier**: scripts/postmortem.py renders bundles deterministically
  and scripts/trace_summary.py --incidents folds them into its tables.
"""

import filecmp
import importlib.util
import json
import os
import pathlib

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu import config as tdt_config
from triton_dist_tpu import obs
from triton_dist_tpu.models import init_params
from triton_dist_tpu.models.decode import Request
from triton_dist_tpu.models.tp_transformer import TransformerConfig
from triton_dist_tpu.obs import alerts as al
from triton_dist_tpu.obs import blackbox as bb
from triton_dist_tpu.obs import metrics as mx
from triton_dist_tpu.obs.export import ENGINE_SECTIONS, SNAPSHOT_SECTIONS
from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig
from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig
from triton_dist_tpu.resilience import health, retry, soak
from triton_dist_tpu.serving import (
    Arrival,
    HandoffConfig,
    HandoffPlane,
    OverloadConfig,
    ServingConfig,
    ServingEngine,
    SLOTargets,
    TrafficSpec,
    generate_trace,
)


@pytest.fixture(autouse=True)
def _restore_config():
    cfg = tdt_config.get_config()
    snap = (cfg.obs, cfg.timeout_iters, cfg.elastic, cfg.suspect_threshold)
    yield
    tdt_config.update(
        obs=snap[0], timeout_iters=snap[1], elastic=snap[2],
        suspect_threshold=snap[3],
    )
    retry.set_clock(None)
    obs.reset()


@pytest.fixture(scope="session")
def mesh1() -> Mesh:
    return Mesh(np.array(jax.devices()[:1]), ("tp",))


@pytest.fixture(scope="module")
def tiny1():
    cfg = TransformerConfig(
        vocab=32, hidden=32, ffn=64, n_layers=1, n_q_heads=4, n_kv_heads=2,
        head_dim=8, batch=2, seq=8,
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16),
    )
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name,
        pathlib.Path(__file__).resolve().parents[1] / "scripts" / f"{name}.py",
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# Host tier: the metrics registry
# ---------------------------------------------------------------------------

def test_metrics_disarmed_is_a_noop():
    mx.counter("c", engine="e")
    mx.gauge("g", 1.0)
    mx.observe("h", 5.0)
    assert mx.json_snapshot()["series"] == []
    assert not mx.enabled()


def test_metrics_registry_units():
    tdt_config.update(obs=obs.ObsConfig(metrics=obs.MetricsConfig()))
    assert mx.enabled()
    mx.counter("reqs", engine="a")
    mx.counter("reqs", 2, engine="a")
    mx.counter("reqs", engine="b")
    mx.gauge("depth", 3, engine="a")
    mx.gauge("depth", 7, engine="a")          # gauges overwrite
    for v in (1.0, 10.0, 100.0):
        mx.observe("lat_ms", v)
    snap = mx.json_snapshot()
    rows = {(r["name"], tuple(sorted(r["labels"].items()))): r
            for r in snap["series"]}
    assert rows[("reqs", (("engine", "a"),))]["value"] == 3
    assert rows[("reqs", (("engine", "b"),))]["value"] == 1
    assert rows[("depth", (("engine", "a"),))]["value"] == 7
    hist = rows[("lat_ms", ())]["value"]
    assert hist["count"] == 3 and hist["max_ms"] == 100.0
    # a name cannot change type (silent unit confusion stays loud)
    with pytest.raises(ValueError, match="already registered"):
        mx.gauge("reqs", 1.0, engine="a")


def test_metrics_series_bound_counted_never_silent():
    tdt_config.update(obs=obs.ObsConfig(
        metrics=obs.MetricsConfig(max_series=2)
    ))
    mx.counter("a")
    mx.counter("b")
    mx.counter("c")          # refused: past the bound
    mx.counter("a")          # existing series still records
    assert mx.dropped_series() == 1
    snap = mx.json_snapshot()
    assert {r["name"] for r in snap["series"]} == {"a", "b"}
    assert snap["dropped_series"] == 1
    assert "metrics_dropped_series 1" in mx.prometheus_text()
    with pytest.raises(ValueError, match="max_series"):
        obs.MetricsConfig(max_series=0).validate()


def test_metrics_prometheus_format():
    tdt_config.update(obs=obs.ObsConfig(metrics=obs.MetricsConfig()))
    mx.counter("reqs_total", 4, engine="e", terminal="finished")
    mx.gauge("queue", 2.0, engine="e")
    mx.observe("ttft_ms", 50.0, engine="e")
    text = mx.prometheus_text()
    assert "# TYPE tdt_reqs_total counter" in text
    assert 'tdt_reqs_total{engine="e",terminal="finished"} 4' in text
    assert "# TYPE tdt_queue gauge" in text
    assert "# TYPE tdt_ttft_ms summary" in text
    assert 'tdt_ttft_ms{engine="e",quantile="0.99"}' in text
    assert 'tdt_ttft_ms_count{engine="e"} 1' in text
    assert text.endswith("\n")


# ---------------------------------------------------------------------------
# Host tier: burn-rate alert units
# ---------------------------------------------------------------------------

def test_alert_config_validation():
    obs.AlertConfig().validate()
    with pytest.raises(ValueError, match="fast_s"):
        obs.AlertConfig(fast_s=3.0, slow_s=1.0).validate()
    with pytest.raises(ValueError, match="signal"):
        al.AlertRule("x", "nope").validate()
    with pytest.raises(ValueError, match="clear_ratio"):
        al.AlertRule("x", "slo_miss_frac", clear_ratio=0.0).validate()
    rules = obs.AlertConfig().resolve_rules(slo_ttft_ms=100.0)
    assert {r.name for r in rules} == {
        "goodput_burn", "handoff_retry_burn", "health_flip_burn",
        "ttft_p99_burn",
    }
    # no TTFT SLO target => no TTFT rule to evaluate against
    assert "ttft_p99_burn" not in {
        r.name for r in obs.AlertConfig().resolve_rules(None)
    }


def test_alert_fires_on_both_windows_and_resolves_with_hysteresis():
    eng = al.AlertEngine(
        obs.AlertConfig(fast_s=1.0, slow_s=4.0), family="t",
    )
    # misses only inside the fast window: the slow window dilutes them
    # below its threshold at t=1.5 -> no fire yet
    for t in (0.2, 0.4, 0.6, 0.8):
        eng.observe_request(t, slo_ok=True, ttft_ms=1.0)
    eng.observe_request(1.2, slo_ok=False, ttft_ms=1.0)
    assert eng.evaluate(1.3) == []
    # sustained misses breach fast (>=0.5) AND slow (>=0.25): fires once
    for t in (1.4, 1.6, 1.8, 2.0):
        eng.observe_request(t, slo_ok=False, ttft_ms=1.0)
    evs = eng.evaluate(2.1)
    assert [e.state for e in evs] == [al.FIRING]
    assert evs[0].rule == "goodput_burn"
    assert eng.evaluate(2.2) == [], "no re-fire while firing"
    # recovery: both windows must fall below clear_ratio x threshold
    for t in (5.5, 5.7, 5.9, 6.1, 6.3):
        eng.observe_request(t, slo_ok=True, ttft_ms=1.0)
    evs = eng.evaluate(6.4)
    assert [e.state for e in evs] == [al.RESOLVED]
    # the process-wide registry saw both transitions
    snap = al.state_snapshot()
    assert snap["rules"]["t:goodput_burn"]["state"] == al.RESOLVED
    assert snap["counters"]["t:goodput_burn:firing"] == 1
    assert snap["counters"]["t:goodput_burn:resolved"] == 1


def test_alert_health_flip_rate_from_cumulative_feed():
    eng = al.AlertEngine(
        obs.AlertConfig(fast_s=1.0, slow_s=2.0), family="t",
    )
    eng.observe_flips(0.5, 1)
    eng.observe_flips(0.8, 4)        # +3 flips: 4/s over the fast window
    evs = eng.evaluate(1.0)
    assert any(e.rule == "health_flip_burn" and e.state == al.FIRING
               for e in evs)
    # a stale (non-increasing) cumulative feed never goes negative
    eng.observe_flips(1.2, 2)
    assert eng._flip_total == 2


# ---------------------------------------------------------------------------
# Host tier: black-box bundle mechanics
# ---------------------------------------------------------------------------

def _arm_blackbox(tmp_path, **kw):
    cfg = obs.BlackboxConfig(dir=str(tmp_path), **kw)
    tdt_config.update(obs=obs.ObsConfig(
        metrics=obs.MetricsConfig(), blackbox=cfg,
    ))
    return cfg


def test_blackbox_one_bundle_per_flipping_kind(tmp_path):
    _arm_blackbox(tmp_path)
    with retry.clock_scope(retry.FakeClock()):
        health.record_brownout("serving_engine", "normal", "brownout1",
                               pressure=0.7, cause="queue")
        health.record_retry("fam", 1, 0.1)        # non-triggering kind
        health.record_pe_quarantine(3, reason="2 strike(s)")
    census = bb.census()
    assert census["written"] == 2 and census["suppressed"] == 0
    assert census["by_kind"] == {"brownout": 1, "pe_quarantine": 1}
    files = sorted(os.listdir(tmp_path))
    assert files == ["incident_0000_brownout.json",
                     "incident_0001_pe_quarantine.json"]
    with open(tmp_path / files[1]) as f:
        bundle = json.load(f)
    assert bundle["schema"] == bb.INCIDENT_SCHEMA
    assert bundle["trigger"]["kind"] == "pe_quarantine"
    assert bundle["trigger"]["family"] == "pe3"
    # the metrics plane mirrored every health event, flips or not
    series = {r["name"] for r in bundle["metrics"]["series"]}
    assert "health_events_total" in series
    # no wall-clock leaks into the bundle bytes
    assert "walltime" not in json.dumps(bundle)


def test_blackbox_bound_suppresses_and_counts(tmp_path):
    _arm_blackbox(tmp_path, max_bundles=1)
    with retry.clock_scope(retry.FakeClock()):
        health.record_brownout("e", "normal", "brownout1",
                               pressure=0.6, cause="queue")
        health.record_brownout("e", "brownout1", "brownout2",
                               pressure=0.8, cause="slo")
    census = bb.census()
    assert census["written"] == 1 and census["suppressed"] == 1
    with pytest.raises(ValueError, match="unknown blackbox kinds"):
        obs.BlackboxConfig(dir="x", kinds=("nope",)).validate()


def test_blackbox_disarmed_writes_nothing(tmp_path):
    health.record_brownout("e", "normal", "brownout1",
                           pressure=0.6, cause="queue")
    assert bb.census()["written"] == 0
    assert not list(tmp_path.iterdir())


# ---------------------------------------------------------------------------
# Host tier: the snapshot schema registry
# ---------------------------------------------------------------------------

def test_snapshot_schema_registry():
    snap = obs.snapshot()
    assert snap["schema"] == obs.SNAPSHOT_SCHEMA
    assert set(snap) <= set(SNAPSHOT_SECTIONS)
    # armed tiers surface their sections; disarmed ones stay absent
    tdt_config.update(obs=obs.ObsConfig(
        metrics=obs.MetricsConfig(), alerts=obs.AlertConfig(),
    ))
    armed = obs.snapshot()
    assert {"metrics", "alerts"} <= set(armed)
    assert "blackbox" not in armed
    # an unregistered section is refused loudly (no silent collisions)
    with pytest.raises(ValueError, match="unregistered"):
        obs.validate_snapshot({"schema": 1, "mystery": {}})
    # the engine-section registry names the disagg composition too
    assert {"handoff", "pools", "overload", "prefix_cache",
            "alerts"} <= set(ENGINE_SECTIONS)


# ---------------------------------------------------------------------------
# Engine tier
# ---------------------------------------------------------------------------

_CROWD_SPEC = dict(rate_rps=20.0, n_requests=12, seed=7, process="burst",
                   burst_every_s=0.5, burst_n=6,
                   prompt_len=("uniform", 2, 4), output_len=("uniform", 2, 5),
                   vocab=32, deadline_ms=("uniform", 300, 2000))


def _serve_once(tiny1, mesh1, *, obs_cfg, overload=True, slo_ttft=80.0):
    """One seeded FakeClock serve (burst traffic, overload armed) under
    ``obs_cfg``; returns (engine, results)."""
    cfg, params = tiny1
    tdt_config.update(obs=obs_cfg)
    obs.reset()
    health.reset()
    clock = retry.FakeClock()
    with retry.clock_scope(clock):
        eng = ServingEngine(
            cfg, params, mesh1, s_max=16, clock=clock,
            serving=ServingConfig(
                max_queue=4, virtual_step_s=0.01,
                slo=SLOTargets(ttft_ms=slo_ttft),
                overload=OverloadConfig(
                    min_dwell_steps=4, window_steps=4,
                ) if overload else None,
            ),
        )
        done = eng.serve(generate_trace(TrafficSpec(**_CROWD_SPEC)))
    return eng, done


def test_metrics_export_byte_identical_two_fakeclock_runs(tiny1, mesh1,
                                                          tmp_path):
    """The acceptance pin: two FakeClock replays of one seeded serve
    export byte-identical Prometheus text AND JSON (cmp, like every
    bench artifact)."""
    # warmup: first-touch environment events (a jax line that cannot
    # build a fused kernel records its one-time downgrade + env pin on
    # the FIRST serve of the process) must land before the measured pair
    _serve_once(tiny1, mesh1, obs_cfg=None)
    paths = []
    for run in ("a", "b"):
        eng, _ = _serve_once(tiny1, mesh1, obs_cfg=obs.ObsConfig(
            spans=False, metrics=obs.MetricsConfig(),
        ))
        prom = str(tmp_path / f"metrics_{run}.prom")
        js = str(tmp_path / f"metrics_{run}.json")
        with retry.clock_scope(eng.clock):
            # the JSON export's one timestamp comes from the injectable
            # clock — export on the run's own FakeClock timeline
            mx.export_prometheus(prom)
            mx.export_json(js)
        paths.append((prom, js))
    assert filecmp.cmp(paths[0][0], paths[1][0], shallow=False)
    assert filecmp.cmp(paths[0][1], paths[1][1], shallow=False)
    # the plane mirrored the engine's private tallies
    text = open(paths[0][0]).read()
    for needle in (
        "tdt_serving_ttft_ms", "tdt_serving_e2e_ms",
        'tdt_serving_requests_total{engine="serving_engine",'
        'priority="interactive",terminal="finished"}',
        "tdt_serving_tokens_goodput_total", "tdt_serving_queue_depth",
        "tdt_overload_pressure", "tdt_overload_rung",
        "tdt_health_events_total",
    ):
        assert needle in text, needle
    doc = json.load(open(paths[0][1]))
    assert doc["schema"] == mx.JSON_SCHEMA


def test_alert_fires_before_shed_all_batch(tiny1, mesh1):
    """The ordering pin (ISSUE 15 tentpole): in a seeded overload run
    that climbs the full ladder, the goodput-burn alert fires BEFORE the
    ladder reaches shed_all_batch — alerts lead degradation instead of
    narrating it."""
    cfg, params = tiny1
    tdt_config.update(obs=obs.ObsConfig(alerts=obs.AlertConfig()))
    obs.reset()
    health.reset()
    clock = retry.FakeClock()
    with retry.clock_scope(clock):
        eng = ServingEngine(
            cfg, params, mesh1, s_max=16, clock=clock,
            serving=ServingConfig(
                max_queue=4, virtual_step_s=0.01,
                slo=SLOTargets(ttft_ms=5.0),       # everything misses
                overload=OverloadConfig(min_dwell_steps=64,
                                        window_steps=4),
            ),
        )
        crowd = [
            Arrival(t_s=0.0, request=Request([1, 2], max_new_tokens=4,
                                             uid=f"c{k}"))
            for k in range(12)
        ]
        eng.serve(crowd)
    evs = health.events()
    kinds = [(e.kind, e.reason) for e in evs]
    shed_idx = next(i for i, (k, r) in enumerate(kinds)
                    if k == health.BROWNOUT and "-> shed_all_batch" in r)
    alert_idx = next(i for i, (k, r) in enumerate(kinds)
                     if k == health.ALERT and "goodput_burn" in r
                     and "firing" in r)
    assert alert_idx < shed_idx, (
        f"alert at event {alert_idx} must lead shed_all_batch at "
        f"{shed_idx}: {kinds}"
    )
    # the alert surfaced everywhere the flight recorder promises
    snap = eng.snapshot()
    assert snap["alerts"]["rules"]["goodput_burn"]["state"] in (
        al.FIRING, al.RESOLVED
    )
    assert snap["requests"]["alerts_firing"] >= 1
    assert any(s.name == "obs:alert" for s in obs.spans())
    assert al.state_snapshot()["counters"][
        "serving_engine:goodput_burn:firing"] >= 1


def test_bundles_byte_identical_across_replays(tiny1, mesh1, tmp_path):
    """Two FakeClock replays of one seeded overload campaign write the
    SAME bundle set with byte-identical contents (cmp)."""
    _serve_once(tiny1, mesh1, obs_cfg=None)   # env-pin warmup (cmp pin)
    dirs = []
    for run in ("a", "b"):
        d = tmp_path / run
        _serve_once(tiny1, mesh1, obs_cfg=obs.ObsConfig(
            metrics=obs.MetricsConfig(),
            blackbox=obs.BlackboxConfig(dir=str(d)),
        ), slo_ttft=5.0)
        census = bb.census()
        assert census["written"] >= 1, "the campaign must actually flip"
        assert census["suppressed"] == 0
        dirs.append(d)
    names = sorted(os.listdir(dirs[0]))
    assert names == sorted(os.listdir(dirs[1]))
    for name in names:
        assert filecmp.cmp(dirs[0] / name, dirs[1] / name, shallow=False), (
            f"bundle {name} differs between replays"
        )


def test_disarmed_metrics_byte_identity_engine_and_overload(tiny1, mesh1):
    """The arming-discipline pin: running the SAME seeded serve with the
    metrics plane armed changes nothing in the engine/overload snapshot
    or the served tokens — observation only, byte for byte."""
    def run(obs_cfg):
        eng, done = _serve_once(tiny1, mesh1, obs_cfg=obs_cfg)
        return (
            json.dumps(eng.snapshot(), sort_keys=True),
            {u: getattr(r, "tokens", None) for u, r in done.items()},
        )

    disarmed_snap, disarmed_tokens = run(None)
    armed_snap, armed_tokens = run(obs.ObsConfig(
        spans=False, metrics=obs.MetricsConfig(),
    ))
    assert armed_snap == disarmed_snap
    assert armed_tokens == disarmed_tokens


def test_disarmed_metrics_byte_identity_handoff_plane():
    """The handoff plane's mirrored counters are observation-only: a
    transfer with the plane armed returns the identical result and
    snapshot as disarmed."""
    def run():
        plane = HandoffPlane(
            HandoffConfig(virtual_chunk_s=0.001), s_max=16,
            prefill_world=2, decode_world=2,
        )
        r1 = plane.transfer("u0", list(range(10)), now=1.0)
        r2 = plane.transfer("u1", list(range(10)), now=2.0)  # full dedup
        return r1, r2, plane.snapshot()

    base = run()
    tdt_config.update(obs=obs.ObsConfig(metrics=obs.MetricsConfig()))
    armed = run()
    assert armed == base
    # ...while the plane's counters were mirrored into the registry
    series = {r["name"]: r["value"]
              for r in mx.json_snapshot()["series"]
              if not isinstance(r["value"], dict)}
    assert series["handoff_transfers_total"] == 2
    assert series["handoff_pages_deduped_total"] == base[1].pages_deduped


def test_engine_snapshot_keys_registered(tiny1, mesh1):
    """The schema pin on the engine surface: every top-level section an
    armed engine snapshot carries is registered in ENGINE_SECTIONS."""
    eng, _ = _serve_once(tiny1, mesh1, obs_cfg=obs.ObsConfig(
        metrics=obs.MetricsConfig(), alerts=obs.AlertConfig(),
    ))
    snap = eng.snapshot()
    assert set(snap) <= set(ENGINE_SECTIONS), (
        set(snap) - set(ENGINE_SECTIONS)
    )


def test_px_counter_mirror_seam():
    """The prefix-cache mirror seam: a counter bump lands in both the
    private tally and the metrics plane (the engine-tier sharing flows
    are covered by tests/test_prefix_cache.py; the soak runs them under
    the armed recorder)."""
    from triton_dist_tpu.models.prefix_cache import (
        PagePrefixCache,
        PrefixCacheConfig,
    )

    tdt_config.update(obs=obs.ObsConfig(metrics=obs.MetricsConfig()))
    cache = PagePrefixCache(PrefixCacheConfig(), n_slots=2, page=4,
                            pps_local=4, n_pes=1)
    cache._bump("hits")
    cache._bump("prefill_tokens_saved", 8)
    assert cache.stats()["hits"] == 1
    series = {r["name"]: r["value"]
              for r in mx.json_snapshot()["series"]}
    assert series["px_hits"] == 1
    assert series["px_prefill_tokens_saved"] == 8


# ---------------------------------------------------------------------------
# Chaos tier: the quick soak under the armed recorder
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_quick_soak_one_bundle_per_flip():
    """The bundle-per-flip invariant on a real multi-fault campaign:
    run_campaign arms the flight recorder itself and fails the campaign
    if the census and the health flip counters disagree — assert the
    campaign is green AND actually flipped (not vacuous). A bundle a flip
    asks nothing of the mesh: the world of two flips, rebuilds and steps
    as the world of four does (3, 4, 29) in a third of the time; the
    campaign on four is tests/test_overload.py's."""
    result = soak.run_campaign(soak.SoakSpec(
        seed=1, n_requests=6, max_queue=4, fault_window=20, world=2,
        corrupt_pe=0,
    ))
    assert result.ok, result.failures
    flips = sum(
        n for key, n in result.health["counters"].items()
        if key.rsplit(":", 1)[-1] in bb.BLACKBOX_KINDS
    )
    assert flips >= 1, "campaign produced no flips — invariant vacuous"
    # the recorder scope died with the campaign (no leak into this test)
    assert bb.census()["written"] == 0


@pytest.mark.chaos
def test_check_blackbox_invariant_catches_a_missing_bundle(tmp_path):
    """The invariant has teeth: a flip recorded while the black box is
    DISARMED (a miss) fails the census check."""
    _arm_blackbox(tmp_path)
    with retry.clock_scope(retry.FakeClock()):
        health.record_brownout("e", "normal", "brownout1",
                               pressure=0.6, cause="queue")
        tdt_config.update(obs=None)      # the miss: recorder off
        health.record_brownout("e", "brownout1", "brownout2",
                               pressure=0.8, cause="slo")
    fails = soak.check_blackbox_invariant(health.snapshot())
    assert fails and "bundle census" in fails[0]


# ---------------------------------------------------------------------------
# CLI tier
# ---------------------------------------------------------------------------

def _make_bundles(tmp_path):
    from triton_dist_tpu.resilience import elastic

    _arm_blackbox(tmp_path)
    with retry.clock_scope(retry.FakeClock()):
        mx.gauge("serving_queue_depth", 4, engine="serving_engine")
        health.record_brownout("serving_engine", "brownout2",
                               "shed_all_batch", pressure=0.93,
                               cause="slo")
        # through the elastic layer, so the bundle's attribution chain
        # carries the quarantined peer
        elastic.quarantine(1, reason="3 strike(s), last a timeout")
    tdt_config.update(obs=None)
    return sorted(
        str(tmp_path / f) for f in os.listdir(tmp_path)
        if f.startswith("incident_")
    )


def test_postmortem_cli_renders_deterministically(tmp_path, capsys):
    paths = _make_bundles(tmp_path)
    pm = _load_script("postmortem")
    assert pm.main(["--dir", str(tmp_path)]) == 0
    out1 = capsys.readouterr().out
    assert "incident" in out1 and "shed_all_batch" in out1
    assert "serving_queue_depth" in out1
    assert "2 incident bundle(s) rendered" in out1
    assert pm.main(["--dir", str(tmp_path)]) == 0
    assert capsys.readouterr().out == out1, "render must be deterministic"
    # summary mode: one line per bundle
    assert pm.main(["--dir", str(tmp_path), "--summary"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 2 and all("[" in ln for ln in lines)
    # single-file mode
    assert pm.main([paths[0]]) == 0
    assert "brownout" in capsys.readouterr().out


def test_trace_summary_folds_incidents(tmp_path, capsys):
    _make_bundles(tmp_path)
    ts = _load_script("trace_summary")
    assert ts.main(["--incidents", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "incidents (2 bundle(s)" in out
    assert "brownout" in out and "pe_quarantine" in out
    assert "pe1:quarantined" in out.lower()


