"""Overload robustness (ISSUE 11): SLO-aware admission, the brownout
degradation ladder, per-class retry budgets, deadline shedding, and the
multi-fault chaos soak.

Tier structure mirrors tests/test_serving.py:

- **host tier**: controller unit behavior (pressure math, ladder
  hysteresis on synthetic observations, retry-budget determinism, shed
  victim order), traffic burst/priority/deadline draws and the
  fingerprint-stability contract, metrics goodput accounting;
- **engine tier** (world-1 mesh, tiny 1-block model): deadline-expiry
  shedding, priority shed order at a full queue, terminal Rejected after
  retry-budget exhaustion, the brownout ladder climbing AND recovering
  under a FakeClock serve, the downshift rebuild hook, and the
  disarmed/never-triggered byte-identity pin;
- **chaos tier** (``pytest.mark.chaos``, runs in chaos_matrix.sh): the
  quick seeded soak campaign (burst × straggler × corruption) green with
  every invariant, plus bit-identical seeded replay;
- **soak tier** (``pytest.mark.soak`` ⇒ slow): the full 20-campaign
  acceptance run (scripts/chaos_soak.py is the CLI twin).
"""

import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu import config as tdt_config
from triton_dist_tpu import resilience
from triton_dist_tpu.models import init_params
from triton_dist_tpu.models.decode import Request
from triton_dist_tpu.models.tp_transformer import TransformerConfig
from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig
from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig
from triton_dist_tpu.resilience import health, retry, soak
from triton_dist_tpu.serving import (
    Arrival,
    OverloadConfig,
    OverloadController,
    Rejected,
    ServingConfig,
    ServingEngine,
    ServingMetrics,
    Shed,
    SLOTargets,
    TrafficSpec,
    generate_trace,
    trace_fingerprint,
)
from triton_dist_tpu.serving import overload as ov


@pytest.fixture(autouse=True)
def _restore_config():
    cfg = tdt_config.get_config()
    snap = (cfg.timeout_iters, cfg.fault_plan, cfg.elastic,
            cfg.suspect_threshold, cfg.probation_probes)
    yield
    tdt_config.update(
        timeout_iters=snap[0], fault_plan=snap[1], elastic=snap[2],
        suspect_threshold=snap[3], probation_probes=snap[4],
    )
    retry.set_clock(None)


@pytest.fixture(scope="session")
def mesh1() -> Mesh:
    return Mesh(np.array(jax.devices()[:1]), ("tp",))


def _cfg(**over):
    base = dict(
        vocab=32, hidden=32, ffn=64, n_layers=1, n_q_heads=4, n_kv_heads=2,
        head_dim=8, batch=2, seq=8,
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16),
    )
    base.update(over)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def tiny1():
    cfg = _cfg()
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


# ---------------------------------------------------------------------------
# Host tier: controller units
# ---------------------------------------------------------------------------

def test_overload_config_validation():
    OverloadConfig().validate()
    with pytest.raises(ValueError, match="hysteresis"):
        OverloadConfig(enter_pressure=(0.5, 0.7, 0.9),
                       exit_pressure=(0.5, 0.5, 0.7)).validate()
    with pytest.raises(ValueError, match="non-decreasing"):
        OverloadConfig(enter_pressure=(0.9, 0.7, 0.95)).validate()
    with pytest.raises(ValueError, match="min_dwell_steps"):
        OverloadConfig(min_dwell_steps=0).validate()
    with pytest.raises(ValueError, match="reject"):
        ServingConfig(backpressure="block",
                      overload=OverloadConfig()).validate()
    with pytest.raises(ValueError, match="unknown priority"):
        ov.priority_rank("realtime")


def test_ladder_climbs_fast_descends_with_hysteresis():
    """Climbs are immediate (one rung per step); descent needs BOTH the
    exit threshold and the dwell — the no-flapping contract."""
    c = OverloadConfig(min_dwell_steps=3, window_steps=4)
    ctrl = OverloadController(c, max_queue=10)

    def step(qd, **kw):
        return ctrl.observe_step(now=0.0, queue_depth=qd, **kw)

    assert ctrl.state == ov.NORMAL
    # full queue + total SLO miss: pressure 0.5 + 0.3 = 0.8 ⇒ climb
    tr = step(10, arrived=4, finished=0, slo_ok=0, slo_scored=4)
    assert tr is not None and (tr.frm, tr.to) == (ov.NORMAL, ov.BROWNOUT1)
    tr = step(10, arrived=4, finished=0, slo_ok=0, slo_scored=4)
    assert tr is not None and tr.to == ov.BROWNOUT2
    assert ctrl.wants_downshift() is False  # no downshift hook configured
    # pressure now ~1.0 (drain deficit saturates) ⇒ top rung
    tr = step(10, arrived=4, finished=0, slo_ok=0, slo_scored=4)
    assert tr is not None and tr.to == ov.SHED_ALL_BATCH
    assert not ctrl.submit_allowed("batch") and ctrl.submit_allowed(
        "interactive"
    )
    # pressure drops to zero — but dwell (3) blocks immediate descent
    assert step(0) is None
    assert step(0) is None
    tr = step(0)
    assert tr is not None and tr.to == ov.BROWNOUT2, (
        "descent only after min_dwell_steps, one rung at a time"
    )
    assert step(0) is None and step(0) is None
    assert step(0).to == ov.BROWNOUT1
    assert step(0) is None and step(0) is None
    assert step(0).to == ov.NORMAL
    # causes attributed on every transition
    assert all(t.cause in ("queue", "drain", "slo") for t in ctrl.transitions)


def test_pressure_terms_bounded_and_attributed():
    ctrl = OverloadController(
        OverloadConfig(window_steps=4), max_queue=8
    )
    assert ctrl.pressure(0) == 0.0
    ctrl.observe_step(now=0.0, queue_depth=8, arrived=2, finished=2,
                      slo_ok=2, slo_scored=2)
    # only the queue term: 0.5 * 1.0
    assert abs(ctrl.pressure(8) - 0.5) < 1e-9
    snap = ctrl.snapshot()
    assert snap["cause"] == "queue" and 0.0 <= snap["pressure"] <= 1.0


def test_retry_budget_deterministic_backoff_and_exhaustion():
    pol = retry.RetryPolicy(max_attempts=3, base_delay_s=0.1, jitter=0.25)
    c = OverloadConfig(retry_policy=pol, retry_budget=3,
                       retry_refill_per_s=0.0)
    ctrl = OverloadController(c, max_queue=4)
    want = pol.delays(key="resubmit:interactive")
    # deterministic: the exact RetryPolicy schedule, per class
    assert ctrl.try_resubmit("interactive", 0, now=0.0) == want[0]
    assert ctrl.try_resubmit("interactive", 1, now=0.0) == want[1]
    # attempt bound: max_attempts - 1 resubmits
    assert ctrl.try_resubmit("interactive", 2, now=0.0) is None
    # bucket: 2 tokens drawn above, 1 left; class buckets are separate
    assert ctrl.try_resubmit("batch", 0, now=0.0) is not None
    assert ctrl.try_resubmit("interactive", 0, now=0.0) is not None
    assert ctrl.try_resubmit("interactive", 0, now=0.0) is None, (
        "interactive bucket exhausted"
    )
    # refill on the caller-supplied clock
    c2 = OverloadConfig(retry_policy=pol, retry_budget=1,
                        retry_refill_per_s=1.0)
    ctrl2 = OverloadController(c2, max_queue=4)
    assert ctrl2.try_resubmit("batch", 0, now=0.0) is not None
    assert ctrl2.try_resubmit("batch", 0, now=0.5) is None
    assert ctrl2.try_resubmit("batch", 0, now=1.6) is not None


def test_shed_victim_newest_of_worst_class():
    ctrl = OverloadController(OverloadConfig(), max_queue=4)
    q = [("interactive", 0), ("batch", 1), ("interactive", 2), ("batch", 3)]
    assert ctrl.shed_victim(q) == 3, "newest member of the worst class"
    assert ctrl.shed_victim([("interactive", 0), ("interactive", 1)]) is None
    assert ctrl.shed_victim([]) is None


# ---------------------------------------------------------------------------
# Host tier: traffic (burst process, overload fields, fingerprints)
# ---------------------------------------------------------------------------

def test_burst_process_mean_rate_and_crowds():
    spec = TrafficSpec(rate_rps=10.0, n_requests=32, process="burst",
                       burst_n=8, seed=3)
    trace = generate_trace(spec)
    assert len(trace) == 32
    # default crowd period = burst_n / λ keeps the mean offered rate at λ
    crowd_starts = [trace[k].t_s for k in range(0, 32, 8)]
    assert all(
        b - a == pytest.approx(0.8, abs=0.35)
        for a, b in zip(crowd_starts, crowd_starts[1:])
    )
    # within a crowd the spacing is the burst rate (10 λ), far tighter
    gaps = [trace[i + 1].t_s - trace[i].t_s for i in range(3)]
    assert np.mean(gaps) < 1.0 / 10.0
    # replayable like every other process
    assert trace_fingerprint(generate_trace(spec)) == trace_fingerprint(trace)


def test_overload_fields_draw_isolated_and_fingerprint_stable():
    """Setting priority_mix/deadline_ms must change neither arrival times
    nor prompts (separate PRNG), and an unchanged spec keeps its
    historical fingerprint (the new fields only hash when set)."""
    base = TrafficSpec(rate_rps=5.0, n_requests=16, seed=9)
    rich = dataclasses.replace(
        base,
        priority_mix=((0.5, "interactive"), (0.5, "batch")),
        deadline_ms=("uniform", 100, 500),
    )
    t0, t1 = generate_trace(base), generate_trace(rich)
    for a, b in zip(t0, t1):
        assert a.t_s == b.t_s and a.request.prompt == b.request.prompt
    # defaults on the plain trace; both classes drawn on the rich one
    assert all(
        a.priority == "interactive" and a.deadline_ms is None for a in t0
    )
    prios = {a.priority for a in t1}
    assert prios == {"interactive", "batch"}
    assert all(100 <= a.deadline_ms <= 500 for a in t1)
    # the fingerprint only moves when the fields are set
    assert trace_fingerprint(t0) != trace_fingerprint(t1)
    assert trace_fingerprint(t0) == trace_fingerprint(generate_trace(base))
    with pytest.raises(ValueError, match="unknown priority"):
        dataclasses.replace(
            base, priority_mix=((1.0, "realtime"),)
        ).validate()


def test_metrics_goodput_and_class_surface():
    m = ServingMetrics(slo=SLOTargets(ttft_ms=100.0),
                       classes=("interactive", "batch"))
    ok = m.observe_finished(ttft_ms=50.0, e2e_ms=200.0, tpot_ms=None,
                            n_tokens=4, priority="interactive",
                            deadline_ok=True)
    assert ok and m.tokens_goodput == 4
    # SLO attained but deadline missed ⇒ throughput, not goodput
    ok = m.observe_finished(ttft_ms=50.0, e2e_ms=200.0, tpot_ms=None,
                            n_tokens=8, priority="batch", deadline_ok=False)
    assert not ok and m.tokens_goodput == 4 and m.tokens_generated == 12
    # SLO missed ⇒ not goodput either
    ok = m.observe_finished(ttft_ms=500.0, e2e_ms=900.0, tpot_ms=None,
                            n_tokens=2, priority="interactive",
                            deadline_ok=None)
    assert not ok and m.tokens_goodput == 4
    m.observe_first_token(42.0, priority="interactive")
    snap = m.snapshot()
    assert snap["tokens"]["goodput"] == 4
    assert snap["by_class"]["ttft_ms"]["interactive"]["count"] == 1
    # class surface absent without opt-in (disarmed snapshots unchanged)
    assert "by_class" not in ServingMetrics().snapshot()


# ---------------------------------------------------------------------------
# Engine tier (world-1): shedding, budgets, ladder, byte-identity
# ---------------------------------------------------------------------------

def _engine(tiny1, mesh1, *, clock=None, **serving_kw):
    cfg, params = tiny1
    clock = clock or retry.FakeClock()
    return ServingEngine(
        cfg, params, mesh1, s_max=16, clock=clock,
        serving=ServingConfig(virtual_step_s=0.01, **serving_kw),
    ), clock


def test_deadline_expiry_sheds_queued_not_inflight(tiny1, mesh1):
    eng, clock = _engine(tiny1, mesh1, overload=OverloadConfig())
    # fill both slots, then queue two more with a deadline that will
    # expire while they wait
    uids = []
    for k in range(2):
        uids.append(eng.submit(Request([1, 2], max_new_tokens=8),
                               deadline_ms=10_000.0))
    for k in range(2):
        uids.append(eng.submit(Request([3, 4], max_new_tokens=2),
                               deadline_ms=20.0))
    clock.sleep(0.5)  # both queued deadlines are now past
    done = eng.run_until_idle()
    assert isinstance(done[uids[2]], Shed) and isinstance(done[uids[3]], Shed)
    assert "deadline expired" in done[uids[2]].reason
    # the in-flight pair had generous deadlines and finishes normally
    assert done[uids[0]].tokens and done[uids[1]].tokens
    snap = eng.snapshot()
    assert snap["requests"]["shed"] == 2
    assert snap["by_class"]["counters"]["shed_interactive"] == 2
    assert health.snapshot()["counters"]["serving_engine:shed"] == 2
    # a shed is a typed terminal: exactly one state per uid
    assert set(done) == set(uids)


def test_overflow_shed_strikes_lowest_class_newest_first(tiny1, mesh1):
    eng, clock = _engine(
        tiny1, mesh1, max_queue=2, overload=OverloadConfig()
    )
    # occupy both slots so the queue actually backs up
    r0 = eng.submit(Request([1, 2], max_new_tokens=8))
    r1 = eng.submit(Request([1, 2], max_new_tokens=8))
    b0 = eng.submit(Request([5, 6], max_new_tokens=1), priority="batch")
    b1 = eng.submit(Request([5, 6], max_new_tokens=1), priority="batch")
    assert isinstance(b0, str) and isinstance(b1, str)
    # interactive arriving at a full queue displaces the NEWEST batch
    i0 = eng.submit(Request([7, 8], max_new_tokens=1))
    assert isinstance(i0, str)
    assert isinstance(eng.results[b1], Shed), "newest batch shed first"
    assert "overflow" in eng.results[b1].reason
    # batch arriving at a full queue of its own class: Rejected, never a
    # same-class displacement
    b2 = eng.submit(Request([5, 6], max_new_tokens=1), priority="batch")
    assert isinstance(b2, Rejected) and b2.priority == "batch"
    # the remaining queued batch (b0) is still strictly below an
    # incoming interactive: displaced next
    i1 = eng.submit(Request([7, 8], max_new_tokens=1))
    assert isinstance(i1, str) and isinstance(eng.results[b0], Shed)
    # with the queue all-interactive, an interactive arrival has no
    # strictly-lower victim: Rejected
    i2 = eng.submit(Request([7, 8], max_new_tokens=1))
    assert isinstance(i2, Rejected) and i2.priority == "interactive"
    done = eng.run_until_idle()
    assert set(done) >= {r0, r1, i0, i1}


def test_shed_all_batch_refuses_at_the_door(tiny1, mesh1):
    eng, _ = _engine(tiny1, mesh1, overload=OverloadConfig())
    eng._overload.state = ov.SHED_ALL_BATCH
    res = eng.submit(Request([1, 2], max_new_tokens=1), priority="batch")
    assert isinstance(res, Shed) and "shed_all_batch" in res.reason
    assert isinstance(
        eng.submit(Request([1, 2], max_new_tokens=1)), str
    ), "interactive still admitted at the top rung"


def test_retry_budget_exhaustion_terminal_rejected(tiny1, mesh1):
    """serve(): a Rejected draws backoff from the per-class bucket and
    re-enters; exhaustion records the Rejected as the terminal state —
    nothing is silently dropped."""
    eng, clock = _engine(
        tiny1, mesh1, max_queue=1,
        overload=OverloadConfig(
            retry_budget=2, retry_refill_per_s=0.0,
            retry_policy=retry.RetryPolicy(max_attempts=2,
                                           base_delay_s=0.02),
        ),
    )
    # an instantaneous interactive flash crowd against queue=1, slots=2
    trace = [
        Arrival(t_s=0.0, request=Request([1, 2], max_new_tokens=6,
                                         uid=f"q{k}"))
        for k in range(8)
    ]
    done = eng.serve(trace)
    assert set(done) == {f"q{k}" for k in range(8)}
    kinds = {u: type(r).__name__ for u, r in done.items()}
    assert "Rejected" in kinds.values(), kinds
    snap = eng.snapshot()
    assert snap["requests"]["rejected_final"] >= 1
    assert snap["requests"].get("resubmitted", 0) <= 2, (
        "resubmits bounded by the class token bucket"
    )
    assert (
        snap["requests"]["finished"] + snap["requests"]["rejected_final"]
        + snap["requests"].get("shed", 0) == 8
    )


def test_resubmit_keeps_original_arrival_for_ttft_and_deadline(tiny1, mesh1):
    """A retry must not rebase the SLO it is judged against: a
    resubmitted request's t_enqueue (⇒ TTFT/e2e) and deadline budget
    anchor at the ORIGINALLY offered arrival time, not the resubmit."""
    eng, clock = _engine(
        tiny1, mesh1, max_queue=1,
        overload=OverloadConfig(
            retry_policy=retry.RetryPolicy(max_attempts=3,
                                           base_delay_s=0.3, jitter=0.0),
        ),
    )
    # 3 instantaneous arrivals against queue=1 (slots=2): the third is
    # Rejected at t=0 and resubmitted after the 0.3 s backoff
    trace = [
        Arrival(t_s=0.0, request=Request([1, 2], max_new_tokens=4,
                                         uid=f"a{k}"))
        for k in range(4)
    ]
    done = eng.serve(trace)
    assert eng.snapshot()["requests"].get("resubmitted", 0) >= 1
    fins = {u: r for u, r in done.items() if type(r).__name__ == "Finished"}
    assert set(fins) == {"a0", "a1", "a2", "a3"}
    # every t_enqueue is the offered arrival (0.0), resubmits included —
    # so the retried request's TTFT contains its backoff wait
    assert all(r.t_enqueue == 0.0 for r in fins.values()), fins
    assert max(r.ttft_ms for r in fins.values()) >= 300.0

    # deadline twin: a budget that expires DURING the backoff must shed,
    # not be silently re-based past its expiry
    eng2, _ = _engine(
        tiny1, mesh1, max_queue=1,
        overload=OverloadConfig(
            retry_policy=retry.RetryPolicy(max_attempts=3,
                                           base_delay_s=0.5, jitter=0.0),
        ),
    )
    trace2 = [
        Arrival(t_s=0.0, request=Request([1, 2], max_new_tokens=6,
                                         uid=f"b{k}"),
                deadline_ms=400)
        for k in range(4)
    ]
    done2 = eng2.serve(trace2)
    kinds = {u: type(r).__name__ for u, r in done2.items()}
    assert set(done2) == {"b0", "b1", "b2", "b3"}
    assert "Shed" in kinds.values() or "Rejected" in kinds.values(), kinds
    sheds = [r for r in done2.values() if isinstance(r, Shed)]
    for s in sheds:
        assert s.t_enqueue == 0.0, "deadline anchored at the offer"


def test_brownout_ladder_engages_and_recovers_in_serve(tiny1, mesh1):
    """A flash crowd drives the ladder up (health + obs record every
    transition with a cause); the sparse tail drains pressure and the
    ladder walks back to normal — hysteresis end to end on a FakeClock."""
    from triton_dist_tpu import obs

    eng, clock = _engine(
        tiny1, mesh1, max_queue=4,
        slo=SLOTargets(ttft_ms=5.0),      # everything misses: slo term up
        overload=OverloadConfig(min_dwell_steps=2, window_steps=4),
    )
    tdt_config.update(obs=obs.ObsConfig())
    try:
        obs.reset()
        crowd = [
            Arrival(t_s=0.0, request=Request([1, 2], max_new_tokens=4,
                                             uid=f"c{k}"))
            for k in range(8)
        ]
        tail = [
            Arrival(t_s=3.0 + k, request=Request([1, 2], max_new_tokens=1,
                                                 uid=f"t{k}"))
            for k in range(4)
        ]
        eng.serve(crowd + tail)
        snap = eng.snapshot()
        ovs = snap["overload"]
        assert ovs["transitions"] >= 2
        ups = [t for t in eng._overload.transitions
               if ov.LADDER.index(t.to) > ov.LADDER.index(t.frm)]
        downs = [t for t in eng._overload.transitions
                 if ov.LADDER.index(t.to) < ov.LADDER.index(t.frm)]
        assert ups and downs, eng._overload.transitions
        assert ovs["state"] == ov.NORMAL, "recovered by the sparse tail"
        # every transition in the health registry with a cause...
        ev = health.events(health.BROWNOUT)
        assert len(ev) == ovs["transitions"]
        assert all("cause=" in e.reason for e in ev)
        # ...and as obs spans (the armed-transitions acceptance pin)
        stats = obs.span_stats()
        assert stats.get("serving:brownout", {}).get("count", 0) == len(ev)
        assert not health.is_healthy(), "a brownout flips the health bit"
    finally:
        tdt_config.update(obs=None)
        obs.reset()


def test_downshift_hook_rebuilds_and_reverts(tiny1, mesh1):
    """brownout2's precision downshift goes through the rebuild+replay
    machinery and reverts on descent; the hook sees the BASE config."""
    seen = []

    def downshift(cfg):
        seen.append(cfg)
        return cfg  # identity: the tiny model has no w8 axis to flip

    eng, clock = _engine(
        tiny1, mesh1, max_queue=4, slo=SLOTargets(ttft_ms=5.0),
        overload=OverloadConfig(min_dwell_steps=2, window_steps=4,
                                downshift=downshift),
    )
    crowd = [
        Arrival(t_s=0.0, request=Request([1, 2], max_new_tokens=4,
                                         uid=f"c{k}"))
        for k in range(8)
    ]
    tail = [
        Arrival(t_s=3.0 + k, request=Request([1, 2], max_new_tokens=1,
                                             uid=f"t{k}"))
        for k in range(4)
    ]
    done = eng.serve(crowd + tail)
    snap = eng.snapshot()
    assert snap["requests"].get("precision_downshifts", 0) >= 1
    assert seen and all(c is eng._base_cfg for c in seen)
    assert eng.cfg is eng._base_cfg, "precision restored on descent"
    assert eng.rebuilds >= 2, "downshift + restore both rebuilt"
    # rebuild reasons name the brownout arcs
    reasons = [e.reason for e in health.events(health.SERVING_REBUILD)]
    assert any("downshift" in r for r in reasons)
    assert any("restored" in r for r in reasons)
    # replay kept every request: all finished despite two rebuilds
    assert all(type(r).__name__ == "Finished" for r in done.values())


def test_armed_but_untriggered_matches_disarmed_byte_for_byte(tiny1, mesh1):
    """The observation-equivalence pin: with the ladder armed but
    unreachable (thresholds at the ceiling, no deadlines, roomy queue)
    every served token stream is byte-identical to the disarmed engine's
    — arming the controller costs nothing until it acts."""
    spec = TrafficSpec(rate_rps=20.0, n_requests=10, seed=11,
                       prompt_len=("uniform", 2, 4),
                       output_len=("uniform", 2, 5), vocab=32,
                       temperature=0.8)

    def run(overload):
        eng, _ = _engine(tiny1, mesh1, max_queue=64, overload=overload)
        done = eng.serve(generate_trace(spec))
        return {u: r.tokens for u, r in done.items()}

    armed = run(OverloadConfig(
        enter_pressure=(0.97, 0.98, 0.99),
        exit_pressure=(0.5, 0.6, 0.7),
    ))
    disarmed = run(None)
    assert armed == disarmed


def test_no_lost_request_under_compound_overload(tiny1, mesh1):
    """Every offered uid reaches exactly one terminal state even when
    sheds, rejects, retries, and deadline expiry all fire in one run."""
    eng, clock = _engine(
        tiny1, mesh1, max_queue=3,
        overload=OverloadConfig(min_dwell_steps=2, window_steps=4,
                                retry_budget=2),
    )
    spec = TrafficSpec(
        rate_rps=50.0, n_requests=24, process="burst", burst_n=6,
        prompt_len=("uniform", 2, 4), output_len=("uniform", 1, 4),
        vocab=32, seed=5,
        priority_mix=((0.5, "interactive"), (0.5, "batch")),
        deadline_ms=("uniform", 50, 1500),
    )
    done = eng.serve(generate_trace(spec))
    assert set(done) == {f"req{k}" for k in range(24)}
    census = {}
    for r in done.values():
        census[type(r).__name__] = census.get(type(r).__name__, 0) + 1
    assert census.get("Finished", 0) >= 1
    assert sum(census.values()) == 24
    snap = eng.snapshot()
    assert snap["requests"]["shed"] == census.get("Shed", 0)
    assert snap["requests"].get("rejected_final", 0) == census.get(
        "Rejected", 0
    )


# ---------------------------------------------------------------------------
# Chaos tier: the seeded soak (quick cells; the 20-campaign run is soak)
# ---------------------------------------------------------------------------

# the smallest campaign that still trips both scheduled faults (a campaign
# that outlives neither fails its own "never fired" invariant): what a
# campaign costs is its requests, and a third of them decide nothing more
_QUICK = dict(seed=0, n_requests=8, n_timeouts=1, n_corruptions=1,
              fault_window=20)


@pytest.fixture(scope="module")
def quick_campaign():
    return soak.run_campaign(soak.SoakSpec(**_QUICK))


@pytest.mark.chaos
def test_quick_soak_campaign_green(quick_campaign):
    """One multi-fault campaign (flash crowd × persistent straggler ×
    payload corruption) through the production engine: every invariant
    holds (no lost request, no deadlock, accounting balanced)."""
    res = quick_campaign
    assert res.error is None, res.error
    assert res.ok, res.failures
    assert res.rebuilds >= 2, "straggler + corruption arcs both rebuilt"
    assert set(res.terminals), "campaign served traffic"


@pytest.mark.chaos
def test_soak_replay_bit_identical(quick_campaign):
    """The same spec run again: the first run is the green cell's."""
    a, b = quick_campaign, soak.run_campaign(soak.SoakSpec(**_QUICK))
    assert a.ok and b.ok, (a.failures, b.failures)
    assert a.fingerprint == b.fingerprint
    assert a.terminals == b.terminals


@pytest.mark.chaos
def test_soak_fault_schedule_seeded_and_composed():
    spec = soak.SoakSpec(seed=4).validate()
    sched = soak.fault_schedule(spec)
    assert sched == soak.fault_schedule(spec), "seed-derived, stable"
    kinds = [k for k, _ in sched.values()]
    assert kinds.count("timeout") == spec.n_timeouts
    assert kinds.count("integrity") == spec.n_corruptions
    assert len(sched) == len(set(sched)), "distinct steps"
    # by-absence straggler records vs direct corruption records
    recs = soak._timeout_records(4, straggler=1)
    assert [r["pe"] for r in recs] == [0, 2, 3]
    assert soak._integrity_records(2)[0]["pe"] == 2


@pytest.mark.soak
def test_full_soak_twenty_campaigns():
    """The ISSUE 11 acceptance run (CLI twin: scripts/chaos_soak.py):
    >= 20 seeded multi-fault campaigns green, one re-run bit-identical.
    soak ⇒ slow (conftest), so tier-1 never pays for this."""
    results = [soak.run_campaign(soak.SoakSpec(seed=s)) for s in range(20)]
    bad = [(r.spec.seed, r.failures, r.error) for r in results if not r.ok]
    assert not bad, bad
    again = soak.run_campaign(soak.SoakSpec(seed=results[0].spec.seed))
    assert again.fingerprint == results[0].fingerprint
