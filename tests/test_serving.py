"""Serving engine (triton_dist_tpu/serving/, docs/serving.md "Serving
engine"; ISSUE 6): SLO metrics, replayable traffic, lifecycle/backpressure
/admission semantics, deterministic virtual-clock latency, and the elastic
serving arc — a step timeout mid-serving quarantines the straggler, the
engine rebuilds on the serviceable survivor mesh with every in-flight
request prefix-replayed, probation re-admission regrows the world, and
every submitted request finishes exactly once with tokens byte-identical
to an uninterrupted run.

Tier structure mirrors tests/test_elastic.py:

- **host tier** (no device work): histograms, SLO math, traffic replay,
  serviceable-mesh selection, prefill-bucket bound, bench emission shape;
- **engine tier**: real ``ContinuousBatcher`` steps on a world-1 mesh
  (tiny 1-block model; the keyed ``jit_shard_map`` cache shares the step
  program across tests);
- **chaos tier** (``pytest.mark.chaos``, runs in ``chaos_matrix.sh``):
  the elastic serving arcs on a 4-PE mesh with fabricated
  ``DistTimeoutError``s driving the production engine paths — only the
  in-kernel wait is simulated, exactly like the host-level arc of
  tests/test_elastic.py.
"""

import dataclasses
import json

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu import config as tdt_config
from triton_dist_tpu import resilience
from triton_dist_tpu.models import init_params
from triton_dist_tpu.models.decode import ContinuousBatcher, Request
from triton_dist_tpu.models.tp_transformer import TransformerConfig
from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig
from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig
from triton_dist_tpu.resilience import elastic, health, retry
from triton_dist_tpu.resilience.records import DistTimeoutError
from triton_dist_tpu.serving import (
    Rejected,
    ServingConfig,
    ServingEngine,
    ServingMetrics,
    SLOTargets,
    StreamingHistogram,
    TrafficSpec,
    generate_trace,
    preset_mix,
    trace_fingerprint,
)
from triton_dist_tpu.serving import traffic as traffic_mod


@pytest.fixture(autouse=True)
def _restore_config():
    cfg = tdt_config.get_config()
    snap = (cfg.timeout_iters, cfg.fault_plan, cfg.raise_on_timeout,
            cfg.fallback_to_xla, cfg.retry_policy, cfg.elastic,
            cfg.suspect_threshold, cfg.probation_probes)
    yield
    tdt_config.update(
        timeout_iters=snap[0], fault_plan=snap[1], raise_on_timeout=snap[2],
        fallback_to_xla=snap[3], retry_policy=snap[4], elastic=snap[5],
        suspect_threshold=snap[6], probation_probes=snap[7],
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


@pytest.fixture(scope="module")
def tiny4():
    # n_kv_heads=4 so the 3-survivor world is model-INVALID and the
    # serviceable mesh must degrade further to 2 — the interesting case
    cfg = _cfg(n_kv_heads=4)
    return cfg, init_params(jax.random.PRNGKey(1), cfg)


def _recs(pes):
    return [{"pe": pe, "kind": "barrier_all", "site": 0, "status": "timeout",
             "expected": 1, "observed": 0, "budget": 10} for pe in pes]


# ---------------------------------------------------------------------------
# Host tier: metrics
# ---------------------------------------------------------------------------

def test_histogram_record_percentile_merge():
    h = StreamingHistogram(lo=1.0, hi=1e4, bins_per_decade=8)
    for v in (2.0, 3.0, 50.0, 60.0, 700.0):
        h.record(v)
    assert h.total == 5 and h.max == 700.0
    # percentiles are bin upper edges: monotone, bracketing the samples
    assert 2.0 <= h.percentile(0.2) <= 4.0
    assert h.percentile(0.5) <= h.percentile(0.95) <= h.percentile(1.0)
    assert 700.0 <= h.percentile(1.0) <= 1000.0
    # merge == recording the union
    h2 = StreamingHistogram(lo=1.0, hi=1e4, bins_per_decade=8)
    for v in (5.0, 5000.0):
        h2.record(v)
    h.merge(h2)
    assert h.total == 7
    both = StreamingHistogram(lo=1.0, hi=1e4, bins_per_decade=8)
    for v in (2.0, 3.0, 50.0, 60.0, 700.0, 5.0, 5000.0):
        both.record(v)
    assert h.counts == both.counts and h.snapshot() == both.snapshot()


def test_histogram_bounds_and_geometry():
    h = StreamingHistogram(lo=1.0, hi=100.0, bins_per_decade=4)
    h.record(0.01)     # underflow
    h.record(1e9)      # overflow
    assert h.counts[0] == 1 and h.counts[-1] == 1
    assert h.percentile(0.5) == 1.0      # underflow reports lo
    assert h.percentile(1.0) == 100.0    # overflow reports hi
    # fraction_le: the SLO estimate counts whole bins only
    h2 = StreamingHistogram(lo=1.0, hi=100.0, bins_per_decade=4)
    for v in (2.0, 2.0, 50.0):
        h2.record(v)
    assert h2.fraction_le(10.0) == pytest.approx(2 / 3)
    assert h2.fraction_le(1000.0) == 1.0
    with pytest.raises(ValueError, match="geometry"):
        h.merge(StreamingHistogram(lo=1.0, hi=100.0, bins_per_decade=8))
    assert StreamingHistogram().percentile(0.5) == 0.0  # empty
    assert StreamingHistogram().fraction_le(1.0) == 1.0


def test_slo_attainment_fractions():
    m = ServingMetrics(slo=SLOTargets(ttft_ms=100.0, e2e_ms=1000.0))
    m.observe_finished(ttft_ms=50.0, e2e_ms=500.0, tpot_ms=10.0, n_tokens=4)
    m.observe_finished(ttft_ms=150.0, e2e_ms=500.0, tpot_ms=10.0, n_tokens=4)
    m.observe_finished(ttft_ms=50.0, e2e_ms=2000.0, tpot_ms=None, n_tokens=1)
    slo = m.snapshot()["slo"]
    assert slo["scored"] == 3
    assert slo["attained"] == pytest.approx(1 / 3)
    assert slo["attained_ttft_ms"] == pytest.approx(2 / 3)
    assert slo["attained_e2e_ms"] == pytest.approx(2 / 3)
    # no targets -> no SLO section, but the histograms still fill
    m2 = ServingMetrics()
    m2.observe_finished(ttft_ms=1.0, e2e_ms=2.0, tpot_ms=None, n_tokens=1)
    snap = m2.snapshot()
    assert snap["slo"] is None and snap["latency_ms"]["e2e"]["count"] == 1
    json.dumps(snap)  # snapshot must stay JSON-able


# ---------------------------------------------------------------------------
# Host tier: traffic
# ---------------------------------------------------------------------------

def test_traffic_trace_byte_identical_replay():
    spec = TrafficSpec(rate_rps=7.0, n_requests=20, process="poisson",
                       prompt_len=("mix", ((0.7, 2, 4), (0.3, 5, 9))),
                       output_len=("uniform", 1, 6), vocab=64,
                       temperature=0.5, seed=11)
    t1, t2 = generate_trace(spec), generate_trace(spec)
    assert trace_fingerprint(t1) == trace_fingerprint(t2)
    assert [a.t_s for a in t1] == [a.t_s for a in t2]
    assert [a.request.prompt for a in t1] == [a.request.prompt for a in t2]
    # a different seed must actually move the trace
    other = generate_trace(dataclasses.replace(spec, seed=12))
    assert trace_fingerprint(other) != trace_fingerprint(t1)
    # deterministic process: exact 1/λ spacing
    det = generate_trace(dataclasses.replace(spec, process="deterministic"))
    gaps = np.diff([a.t_s for a in det])
    np.testing.assert_allclose(gaps, 1.0 / 7.0, rtol=1e-12)
    # per-request seeds are distinct (neighbor-independent sampling)
    seeds = [a.request.seed for a in t1]
    assert len(set(seeds)) == len(seeds)
    with pytest.raises(ValueError, match="rate_rps"):
        TrafficSpec(rate_rps=0, n_requests=1).validate()
    with pytest.raises(ValueError, match="prompt_len"):
        TrafficSpec(rate_rps=1, n_requests=1,
                    prompt_len=("bogus", 1)).validate()


def test_traffic_preset_mix_admissible():
    s_max = 64
    spec = preset_mix("moe-gelu-8x", s_max=s_max, rate_rps=3.0,
                      n_requests=50, seed=4, vocab=128)
    assert spec.vocab == 128  # override for shrunk serving heads
    assert (traffic_mod.max_length(spec.prompt_len)
            + traffic_mod.max_length(spec.output_len)) <= s_max
    trace = generate_trace(spec)
    for a in trace:
        assert 1 <= len(a.request.prompt) + a.request.max_new_tokens <= s_max
        assert all(0 <= t < 128 for t in a.request.prompt)
    # the default vocabulary comes from the preset's architecture table
    full = preset_mix("llama-3.1-8b", s_max=s_max, rate_rps=1.0, n_requests=1)
    assert full.vocab == 128256


# ---------------------------------------------------------------------------
# Host tier: serviceable mesh + prefill buckets + bench emission
# ---------------------------------------------------------------------------

def test_serviceable_mesh_degrades_to_model_valid_world(mesh4):
    tdt_config.update(elastic=True)
    ok = lambda n: n in (1, 2, 4)  # noqa: E731 — kv-head-style constraint
    assert elastic.serviceable_mesh(mesh4, validate=ok) is mesh4
    elastic.quarantine(3, reason="test")
    m = elastic.serviceable_mesh(mesh4, validate=ok)
    assert m.devices.shape == (2,), "3 survivors are model-invalid -> 2"
    assert list(m.devices.flat) == list(mesh4.devices.flat)[:2]
    # no predicate: plain effective_mesh semantics (3 survivors)
    assert elastic.serviceable_mesh(mesh4).devices.shape == (3,)
    with pytest.raises(ValueError, match="no serviceable"):
        elastic.serviceable_mesh(mesh4, validate=lambda n: False)


def test_prefill_bucket_bound_mixed_lengths(tiny1, mesh1):
    """Recompilation-storm guard (ISSUE 6 satellite): every prompt length
    in 3..200 maps into the power-of-two bucket set, so a mixed workload
    compiles at most log2(s_max) prefill programs — never one per
    length."""
    cfg, params = tiny1
    b = ContinuousBatcher(cfg, params, mesh1, s_max=256, prefill=True)
    buckets = {b._bucket(length) for length in range(3, 201)}
    assert buckets <= {4, 8, 16, 32, 64, 128, 256}
    assert len(buckets) <= 7
    assert all(bk & (bk - 1) == 0 for bk in buckets), "powers of two"
    assert b.prefill_bucket_count == 0, "no compiles before admission"


def test_steps_exhausted_error_contract():
    """Tier-1 pin for the satellite bugfix surface (the full batcher run
    lives in the slow tier, tests/test_decode.py): the exhaustion error
    is a RuntimeError (existing handlers keep working), names both uid
    rosters, and points at drain_finished()."""
    from triton_dist_tpu.models.decode import StepsExhaustedError

    err = StepsExhaustedError(7, ["s1", "s2"], ["done1"])
    assert isinstance(err, RuntimeError)
    assert err.max_steps == 7
    assert err.pending_uids == ("s1", "s2")
    assert err.finished_uids == ("done1",)
    assert "drain_finished" in str(err) and "max_steps=7" in str(err)


# ---------------------------------------------------------------------------
# Engine tier (world-1 mesh; real batcher steps)
# ---------------------------------------------------------------------------

def _reqs(cfg, spec_list, seed=5):
    key = jax.random.PRNGKey(seed)
    out = []
    for i, (plen, mx) in enumerate(spec_list):
        toks = list(np.asarray(jax.random.randint(
            jax.random.fold_in(key, i), (plen,), 0, cfg.vocab, np.int32
        )))
        out.append(Request([int(t) for t in toks], max_new_tokens=mx, uid=i))
    return out


def test_engine_matches_direct_batcher_and_lifecycle(tiny1, mesh1):
    cfg, params = tiny1
    shapes = [(3, 4), (5, 3), (2, 5)]

    direct = ContinuousBatcher(cfg, params, mesh1, s_max=16)
    for r in _reqs(cfg, shapes):
        direct.submit(r)
    want = dict(direct.run(max_steps=200))

    clock = retry.FakeClock()
    eng = ServingEngine(cfg, params, mesh1, s_max=16, clock=clock,
                        serving=ServingConfig(virtual_step_s=0.01))
    for r in _reqs(cfg, shapes):
        assert eng.submit(r) == r.uid
    done = eng.run_until_idle()
    assert set(done) == set(want)
    for uid, res in done.items():
        assert res.tokens == want[uid], f"request {uid}"
        assert res.t_enqueue <= res.t_admitted <= res.t_first_token
        assert res.t_first_token <= res.t_finished
        assert res.resumed == 0
    snap = eng.snapshot()
    assert snap["requests"]["submitted"] == 3
    assert snap["requests"]["finished"] == 3
    assert snap["tokens"]["generated"] == sum(len(t) for t in want.values())
    assert snap["latency_ms"]["ttft"]["count"] == 3
    assert snap["engine"]["world_size"] == 1
    json.dumps(snap)


def test_engine_backpressure_reject(tiny1, mesh1):
    cfg, params = tiny1
    eng = ServingEngine(cfg, params, mesh1, s_max=16,
                        clock=retry.FakeClock(),
                        serving=ServingConfig(max_queue=1))
    reqs = _reqs(cfg, [(2, 2)] * 4, seed=6)
    assert eng.submit(reqs[0]) == 0   # -> slot
    assert eng.submit(reqs[1]) == 1   # -> slot (batch=2)
    assert eng.submit(reqs[2]) == 2   # -> queue (1/1)
    rej = eng.submit(reqs[3])
    assert isinstance(rej, Rejected) and rej.uid == 3
    assert rej.queue_depth == 1
    done = eng.run_until_idle()
    assert set(done) == {0, 1, 2}, "the rejected request was never enqueued"
    snap = eng.snapshot()
    assert snap["requests"]["rejected"] == 1
    assert snap["requests"]["submitted"] == 4
    # invalid requests are rejected loudly at submit, not mid-serve
    with pytest.raises(ValueError, match="exceeds s_max"):
        eng.submit(Request([1] * 10, max_new_tokens=10, uid="big"))


def test_engine_backpressure_block(tiny1, mesh1):
    cfg, params = tiny1
    eng = ServingEngine(cfg, params, mesh1, s_max=16,
                        clock=retry.FakeClock(),
                        serving=ServingConfig(max_queue=1,
                                              backpressure="block",
                                              virtual_step_s=0.01))
    for r in _reqs(cfg, [(2, 2)] * 5, seed=7):
        out = eng.submit(r)     # blocks (steps the engine) when full
        assert not isinstance(out, Rejected)
    done = eng.run_until_idle()
    assert set(done) == {0, 1, 2, 3, 4}
    assert "rejected" not in eng.snapshot()["requests"]


def test_engine_admission_shortest_prompt_first(tiny1, mesh1):
    cfg, params = tiny1
    cfg1 = dataclasses.replace(cfg, batch=1)
    params1 = init_params(jax.random.PRNGKey(0), cfg1)
    eng = ServingEngine(cfg1, params1, mesh1, s_max=16,
                        clock=retry.FakeClock(),
                        serving=ServingConfig(admission="spf",
                                              virtual_step_s=0.01))
    long, mid, short = _reqs(cfg1, [(6, 2), (5, 2), (2, 2)], seed=8)
    eng.submit(long)            # admitted immediately (free slot)
    eng.submit(mid)             # queued
    eng.submit(short)           # queued behind mid, but shorter
    done = eng.run_until_idle()
    assert set(done) == {0, 1, 2}
    assert done[2].t_admitted < done[1].t_admitted, (
        "shortest-prompt-first must admit the short request before the "
        "earlier-but-longer one"
    )


def test_engine_deterministic_latency_under_fake_clock(tiny1, mesh1):
    """ISSUE 6 acceptance: two runs with the same traffic seed and a
    FakeClock produce IDENTICAL metric snapshots — latency percentiles
    included."""
    cfg, params = tiny1
    spec = TrafficSpec(rate_rps=8.0, n_requests=8,
                       prompt_len=("uniform", 2, 4),
                       output_len=("uniform", 2, 5), vocab=cfg.vocab, seed=3)

    def run():
        eng = ServingEngine(
            cfg, params, mesh1, s_max=16, clock=retry.FakeClock(),
            serving=ServingConfig(virtual_step_s=0.05,
                                  slo=SLOTargets(ttft_ms=1e3, e2e_ms=5e3)),
        )
        done = eng.serve(generate_trace(spec))
        return done, eng.snapshot()

    done1, snap1 = run()
    done2, snap2 = run()
    assert snap1 == snap2
    assert {u: r.tokens for u, r in done1.items()} == {
        u: r.tokens for u, r in done2.items()
    }
    assert snap1["latency_ms"]["ttft"]["p50"] > 0
    assert snap1["slo"]["attained"] == 1.0


def test_engine_stop_drain_and_cancel(tiny1, mesh1):
    cfg, params = tiny1
    # graceful drain: everything already enqueued still completes
    eng = ServingEngine(cfg, params, mesh1, s_max=16,
                        clock=retry.FakeClock(),
                        serving=ServingConfig(virtual_step_s=0.01))
    for r in _reqs(cfg, [(2, 2)] * 4, seed=9):
        eng.submit(r)
    eng.stop(drain=True)
    assert set(eng.run_until_idle()) == {0, 1, 2, 3}
    # fast stop: the arrival queue is cancelled (counted), in-flight
    # slots still finish — abandoning device work loses tokens for free
    eng2 = ServingEngine(cfg, params, mesh1, s_max=16,
                         clock=retry.FakeClock(),
                         serving=ServingConfig(virtual_step_s=0.01))
    for r in _reqs(cfg, [(2, 3)] * 4, seed=10):
        eng2.submit(r)          # 2 slots + 2 queued
    eng2.stop(drain=False)
    done = eng2.run_until_idle()
    assert set(done) == {0, 1}
    assert eng2.snapshot()["requests"]["cancelled"] == 2


def test_engine_default_clock_via_clock_scope(tiny1, mesh1):
    """An engine built with no explicit clock resolves the resilience
    module clock, so retry.clock_scope(FakeClock()) puts backoffs AND
    serving timestamps on one deterministic timeline — and the scope
    restores the previous clock on exit."""
    cfg, params = tiny1
    prev = retry.get_clock()
    with retry.clock_scope(retry.FakeClock()) as clock:
        assert retry.get_clock() is clock
        eng = ServingEngine(cfg, params, mesh1, s_max=16,
                            serving=ServingConfig(virtual_step_s=0.25))
        assert eng.clock is clock
        eng.submit(Request([1, 2], max_new_tokens=2, uid="c"))
        done = eng.run_until_idle()
        assert len(done["c"].tokens) == 2
        # time passed only on the fake clock: one step per fed/generated
        # token at the configured virtual cost
        assert clock.now == pytest.approx(0.25 * 3)
    assert retry.get_clock() is prev, "scope must restore the clock"


def test_engine_prefill_bucket_gauge(tiny1, mesh1):
    """The compile-cache size is observable through the engine snapshot
    and grows with BUCKETS, not with distinct prompt lengths."""
    cfg, params = tiny1
    eng = ServingEngine(cfg, params, mesh1, s_max=16, prefill=True,
                        clock=retry.FakeClock(),
                        serving=ServingConfig(virtual_step_s=0.01))
    for r in _reqs(cfg, [(3, 2), (4, 2), (7, 2)], seed=11):
        eng.submit(r)           # lengths 3, 4 -> bucket 4; 7 -> bucket 8
    done = eng.run_until_idle()
    assert set(done) == {0, 1, 2}
    assert eng.snapshot()["engine"]["prefill_bucket_programs"] == 2


def test_sampling_guarantee_neighbor_mix_and_slot_change(tiny1, mesh1):
    """docs/serving.md's sampling guarantee, pinned (ISSUE 6 satellite):
    the same Request(seed=...) yields identical tokens (a) under a
    different batch-neighbor mix and (b) after eviction + re-admission
    into a DIFFERENT slot over a dirty cache."""
    cfg, params = tiny1
    b = ContinuousBatcher(cfg, params, mesh1, s_max=16)
    mk = lambda uid: Request([3, 1, 4], max_new_tokens=5, temperature=0.9,  # noqa: E731
                             top_k=4, seed=123, uid=uid)
    # round 1: R in slot 0, short greedy neighbor in slot 1
    b.submit(mk("r1"))
    b.submit(Request([2, 2], max_new_tokens=2, uid="n1"))
    first = dict(b.run(max_steps=100))
    # round 2 (same batcher, dirty cache): a long sampled dummy claims
    # slot 0 first, so R re-admits into slot 1 beside a different neighbor
    b.submit(Request([5, 6, 7, 8], max_new_tokens=6, temperature=0.7,
                     seed=999, uid="d"))
    b.submit(mk("r2"))
    second = dict(b.run(max_steps=100))
    assert first["r1"] == second["r2"], (
        "seeded sampling must not depend on slot index, cache dirt, or "
        "batch neighbors"
    )


def test_engine_replay_preserves_greedy_and_sampled_streams(tiny1, mesh1,
                                                            monkeypatch):
    """Prefix replay without any elastic machinery: a step timeout on a
    healthy world rebuilds the batcher in place and re-queues prompt +
    tokens-so-far. Greedy AND seeded-sampled outputs must be
    byte-identical to an uninterrupted run (the sampled stream continues
    through the live RNG that rides the replay request)."""
    cfg, params = tiny1
    reqs = lambda: [  # noqa: E731
        Request([1, 2, 3], max_new_tokens=6, uid="g"),
        Request([4, 5], max_new_tokens=6, temperature=0.8, top_k=6,
                seed=77, uid="s"),
    ]
    golden_eng = ServingEngine(cfg, params, mesh1, s_max=16,
                               clock=retry.FakeClock(),
                               serving=ServingConfig(virtual_step_s=0.01))
    for r in reqs():
        golden_eng.submit(r)
    golden = golden_eng.run_until_idle()

    calls = {"n": 0}
    real_step = ContinuousBatcher.step

    def flaky(self):
        calls["n"] += 1
        if calls["n"] == 4:  # mid-generation, both slots past first token
            raise DistTimeoutError("batcher_step", _recs([0]), world_size=1)
        return real_step(self)

    monkeypatch.setattr(ContinuousBatcher, "step", flaky)
    eng = ServingEngine(cfg, params, mesh1, s_max=16,
                        clock=retry.FakeClock(),
                        serving=ServingConfig(virtual_step_s=0.01))
    for r in reqs():
        eng.submit(r)
    done = eng.run_until_idle()
    assert {u: r.tokens for u, r in done.items()} == {
        u: r.tokens for u, r in golden.items()
    }
    assert done["g"].resumed == 1 and done["s"].resumed == 1
    assert eng.rebuilds == 1
    snap = eng.snapshot()
    assert snap["requests"]["resumed"] == 2
    assert snap["latency_ms"]["resumed_ttft"]["count"] >= 1, (
        "TTFT after a disruption is re-measured as a resumed event"
    )


# ---------------------------------------------------------------------------
# Chaos tier: the elastic serving arcs (mesh4)
# ---------------------------------------------------------------------------

def _serve_tiny4(tiny4, mesh4, *, fault_at=None, fault_recs=None,
                 probe_interval=3, max_failures=8):
    """One traffic-driven serve over tiny4/mesh4 with an optional
    fabricated step timeout at call #fault_at (the host-level arc: only
    the in-kernel wait is simulated; retry/attribution/shrink/replay/
    probe are the production paths)."""
    cfg, params = tiny4
    spec = TrafficSpec(rate_rps=50.0, n_requests=5,
                       prompt_len=("uniform", 2, 4),
                       output_len=("uniform", 3, 6), vocab=cfg.vocab, seed=7)
    clock = retry.FakeClock()
    retry.set_clock(clock)
    eng = ServingEngine(
        cfg, params, mesh4, s_max=16, clock=clock,
        serving=ServingConfig(virtual_step_s=0.05,
                              probe_interval_steps=probe_interval,
                              max_step_failures=max_failures),
    )
    calls = {"n": 0}
    real_step = ContinuousBatcher.step

    def flaky(self):
        calls["n"] += 1
        if fault_at is not None and calls["n"] in (
            fault_at if isinstance(fault_at, tuple) else (fault_at,)
        ):
            raise DistTimeoutError("batcher_step", fault_recs, world_size=4)
        return real_step(self)

    ContinuousBatcher.step = flaky
    try:
        done = eng.serve(generate_trace(spec))
    finally:
        ContinuousBatcher.step = real_step
    return eng, done


@pytest.fixture(scope="module")
def golden_tiny4(tiny4, mesh4):
    """The uninterrupted serve both arcs are compared with, run once."""
    try:
        return _serve_tiny4(tiny4, mesh4)
    finally:
        retry.set_clock(None)


@pytest.mark.chaos
def test_serving_elastic_arc(tiny4, mesh4, golden_tiny4):
    """ISSUE 6 acceptance: persistent-straggler step timeout mid-serving →
    PE quarantined → the engine shrinks to the serviceable world (2: the
    3-survivor count is model-invalid) and keeps serving with every
    in-flight request prefix-replayed → probation re-admits → the world
    regrows to 4 mid-serving → every submitted request finishes exactly
    once with tokens byte-identical to the uninterrupted run."""
    golden_eng, golden = golden_tiny4
    assert golden_eng.rebuilds == 0 and len(golden) == 5

    resilience.reset()
    tdt_config.update(elastic=True, suspect_threshold=1, probation_probes=1)
    eng, done = _serve_tiny4(tiny4, mesh4, fault_at=3,
                             fault_recs=_recs([0, 2, 3]))
    assert set(done) == set(golden)
    for uid in golden:
        assert done[uid].tokens == golden[uid].tokens, f"request {uid}"
    assert eng.rebuilds == 2, "one shrink + one regrow"
    assert eng.world_size == 4, "probation re-admission regrew the world"
    counters = health.snapshot()["counters"]
    assert counters["pe1:pe_quarantine"] == 1
    assert counters["pe1:pe_readmit"] == 1
    assert counters["serving_engine:serving_rebuild"] == 2
    worlds = [e.reason.split(":")[0] for e in
              health.events(health.SERVING_REBUILD)]
    assert worlds == ["world=2", "world=4"], (
        "shrink must land on the largest MODEL-VALID world (2, not 3)"
    )
    assert any(r.resumed for r in done.values()), "prefix replay happened"
    assert eng.snapshot()["requests"]["resumed"] >= 1


@pytest.mark.chaos
def test_serving_arc_unattributable_timeout_keeps_full_world(
        tiny4, mesh4, golden_tiny4):
    """Every PE tripping (fabric-wide) must not quarantine anyone: the
    engine rebuilds on the FULL world and service continues losslessly."""
    _, golden = golden_tiny4
    resilience.reset()
    tdt_config.update(elastic=True, suspect_threshold=1)
    eng, done = _serve_tiny4(tiny4, mesh4, fault_at=3,
                             fault_recs=_recs([0, 1, 2, 3]))
    assert elastic.quarantined_pes() == ()
    assert eng.world_size == 4 and eng.rebuilds == 1
    assert {u: r.tokens for u, r in done.items()} == {
        u: r.tokens for u, r in golden.items()
    }


@pytest.mark.chaos
def test_serving_engine_escalates_after_max_failures(tiny4, mesh4):
    """A timeout storm the rebuild/replay loop cannot absorb must
    escalate loudly, not spin forever."""
    resilience.reset()
    with pytest.raises(RuntimeError, match="consecutive step timeouts"):
        _serve_tiny4(tiny4, mesh4, fault_at=tuple(range(1, 20)),
                     fault_recs=_recs([0, 2, 3]), max_failures=2)
