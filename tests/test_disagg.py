"""Disaggregated prefill/decode serving with the fault-tolerant KV
handoff plane (ISSUE 13; ``serving/disagg.py`` + ``serving/handoff.py``
+ ``ops/kv_stream.py``, docs/serving.md "Disaggregated serving").

Tier structure mirrors tests/test_serving.py:

- **host tier**: the handoff plane's manifest/trie semantics, ladder
  arithmetic, pool-scoped FaultPlan selection, config validation — no
  device work at all;
- **engine tier**: real two-pool ``DisaggServingEngine`` runs on a
  4-device CPU mesh (2 prefill + 2 decode), pinned byte-identical to
  the unified engine — greedy AND seeded-sampled — with the transfer
  phase decomposing e2e exactly;
- **chaos tier** (``pytest.mark.chaos``, rides ``chaos_matrix.sh``):
  corrupt/dropped KV chunks mid-handoff walking the full guard ladder
  with attributed strikes, the prefill-pool shrink-mid-stream arc, the
  pool-collapse-to-unified arc; the quick disagg soak campaign with
  bit-identical seeded replay is test_disagg_soak.py.
"""

from __future__ import annotations

import dataclasses

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
from triton_dist_tpu.resilience.faults import FaultPlan, pool_scope
from triton_dist_tpu.resilience.records import DistTimeoutError
from triton_dist_tpu.serving import (
    DisaggServingConfig,
    DisaggServingEngine,
    Finished,
    HandoffConfig,
    HandoffPlane,
    ServingConfig,
    ServingEngine,
    TrafficSpec,
    generate_trace,
)


@pytest.fixture(autouse=True)
def _restore_config():
    cfg = tdt_config.get_config()
    snap = (cfg.timeout_iters, cfg.fault_plan, cfg.raise_on_timeout,
            cfg.fallback_to_xla, cfg.retry_policy, cfg.elastic,
            cfg.suspect_threshold, cfg.probation_probes, cfg.obs)
    resilience.reset()
    elastic.reset()
    yield
    tdt_config.update(
        timeout_iters=snap[0], fault_plan=snap[1], raise_on_timeout=snap[2],
        fallback_to_xla=snap[3], retry_policy=snap[4], elastic=snap[5],
        suspect_threshold=snap[6], probation_probes=snap[7], obs=snap[8],
    )
    retry.set_clock(None)
    resilience.reset()
    elastic.reset()


def _cfg(**over):
    base = dict(
        vocab=32, hidden=32, ffn=64, n_layers=1, n_q_heads=4, n_kv_heads=2,
        head_dim=8, batch=2, seq=8,
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16),
    )
    base.update(over)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, init_params(jax.random.PRNGKey(1), cfg)


def _mesh(lo, hi):
    return Mesh(np.array(jax.devices()[lo:hi]), ("tp",))


def _serve_disagg(cfg, params, trace, *, serving=None, **kw):
    clock = retry.FakeClock()
    with retry.clock_scope(clock):
        eng = DisaggServingEngine(
            cfg, params, _mesh(0, 4), s_max=16, clock=clock,
            serving=serving or DisaggServingConfig(
                prefill_pes=2, virtual_step_s=0.05,
                handoff=HandoffConfig(page_tokens=4, chunks_per_page=2,
                                      virtual_chunk_s=0.001),
            ),
            **kw,
        )
        done = eng.serve(trace)
    return eng, done


def _serve_unified(cfg, params, trace, *, n=2):
    clock = retry.FakeClock()
    with retry.clock_scope(clock):
        eng = ServingEngine(
            cfg, params, _mesh(2, 2 + n), s_max=16, clock=clock,
            serving=ServingConfig(virtual_step_s=0.05),
        )
        done = eng.serve(trace)
    return eng, done


# ---------------------------------------------------------------------------
# Host tier: the handoff plane
# ---------------------------------------------------------------------------

def _plane(**over):
    kw = dict(page_tokens=4, chunks_per_page=2)
    kw.update(over)
    return HandoffPlane(HandoffConfig(**kw), s_max=16, prefill_world=2,
                        decode_world=2)


def test_manifest_is_the_trie_key_chain():
    """Page identity = the FULL token prefix through the page — the
    radix-trie node identity of models/prefix_cache.py — so two prompts
    sharing page-g TOKENS but diverging earlier are different pages."""
    p = _plane()
    m = p.manifest([1, 2, 3, 4, 5, 6, 7, 8, 9])
    assert [g for g, _ in m] == [0, 1, 2]
    assert m[0][1] == (1, 2, 3, 4)
    assert m[1][1] == (1, 2, 3, 4, 5, 6, 7, 8)
    assert m[2][1] == (1, 2, 3, 4, 5, 6, 7, 8, 9)  # partial final page
    # divergence at page 0 makes EVERY later page distinct
    m2 = p.manifest([9, 2, 3, 4, 5, 6, 7, 8])
    assert m2[1][1] != m[1][1]


def test_shared_prefixes_stream_once():
    p = _plane()
    sys_prompt = [7, 7, 7, 7, 1, 1, 1, 1]
    r1 = p.transfer("a", sys_prompt + [2, 3], now=0.0)
    assert (r1.outcome, r1.pages_streamed, r1.pages_deduped) == (
        "delivered", 3, 0)
    # the second reader of the same system prompt streams only its
    # divergent page (the trie-as-manifest contract)
    r2 = p.transfer("b", sys_prompt + [4, 5], now=1.0)
    assert (r2.pages_streamed, r2.pages_deduped) == (1, 2)
    # a third, byte-identical prompt streams nothing new
    r3 = p.transfer("c", sys_prompt + [2, 3], now=2.0)
    assert (r3.pages_streamed, r3.pages_deduped) == (0, 3)
    assert p.counters["pages_streamed"] == 4
    assert p.counters["pages_deduped"] == 5


def test_transfer_charges_virtual_time_on_landing():
    p = _plane(virtual_chunk_s=0.01)
    r = p.transfer("a", list(range(8)), now=5.0)  # 2 pages × 2 chunks
    assert r.t_start == 5.0
    assert r.t_landed == pytest.approx(5.0 + 4 * 0.01)


def test_ladder_corrupt_chunk_retries_then_delivers():
    """One bounded corruption: the canary mismatch re-sends in place
    (rung 1), the culprit decode PE is struck, the transfer delivers."""
    tdt_config.update(elastic=True, suspect_threshold=8)
    tdt_config.update(fault_plan=FaultPlan(
        "bitflip", pe=-1, pool="decode", max_triggers=1))
    try:
        p = _plane()
        r = p.transfer("a", list(range(8)), now=0.0)
    finally:
        tdt_config.update(fault_plan=None, elastic=False)
    assert r.outcome == "delivered"
    assert r.retries == 1 and r.restreams == 0
    assert p.counters["canary_mismatches"] == 1
    assert r.culprit_pe in (2, 3)  # a decode-pool GLOBAL index
    assert elastic.state(r.culprit_pe) == "suspect"
    assert health.counters().get(("kv_handoff", "handoff_retry")) == 1


def test_ladder_persistent_corruption_walks_to_fallback():
    """Persistent corruption exhausts re-sends, re-streams, and lands on
    the decode-local cold re-prefill rung — every rung recorded, the
    request never lost."""
    tdt_config.update(elastic=True, suspect_threshold=100)
    tdt_config.update(fault_plan=FaultPlan("nan_inject", pe=-1,
                                           pool="decode"))
    try:
        p = _plane(max_restreams=1)
        r = p.transfer("a", list(range(8)), now=0.0)
    finally:
        tdt_config.update(fault_plan=None, elastic=False)
    assert r.outcome == "fallback"
    assert r.restreams == 1
    hc = health.counters()
    assert hc.get(("kv_handoff", "handoff_restream")) == 1
    assert hc.get(("kv_handoff", "handoff_fallback")) == 1
    assert not health.is_healthy()


def test_ladder_dropped_chunk_names_prefill_sender():
    """A dropped chunk signal is a bounded-wait timeout: the silent
    PREFILL sender is the culprit (by absence), charged chunk_timeout_s
    plus the deterministic backoff."""
    tdt_config.update(elastic=True, suspect_threshold=8)
    tdt_config.update(fault_plan=FaultPlan(
        "drop_signal", pe=-1, pool="prefill", site=0, max_triggers=1))
    try:
        p = _plane(chunk_timeout_s=0.5)
        r = p.transfer("a", list(range(8)), now=0.0)
    finally:
        tdt_config.update(fault_plan=None, elastic=False)
    assert r.outcome == "delivered" and r.retries == 1
    assert p.counters["chunk_timeouts"] == 1
    assert r.culprit_pe in (0, 1)  # a prefill-pool GLOBAL index
    assert r.t_landed > 0.5  # the expired wait was charged


def test_fault_plan_pool_selector_scopes_injection():
    """The ISSUE 13 FaultPlan satellite: pool= targets exactly one side
    of the handoff; the wrong side (and the no-pool world) never fires,
    and existing single-pool plans (pool=None) are untouched."""
    from triton_dist_tpu.resilience import faults

    plan = FaultPlan("drop_signal", pool="prefill").validate()
    tdt_config.update(fault_plan=plan)
    try:
        assert faults.active_plan() is None  # outside any pool scope
        with pool_scope("decode"):
            assert faults.active_plan() is None
        with pool_scope("prefill"):
            assert faults.active_plan() is plan
            with pool_scope("decode"):  # innermost scope wins
                assert faults.active_plan() is None
        # pool=None (every pre-disagg plan): byte-unchanged semantics —
        # fires everywhere, scope or not
        tdt_config.update(fault_plan=FaultPlan("drop_signal"))
        assert faults.active_plan() is not None
        with pool_scope("prefill"):
            assert faults.active_plan() is not None
    finally:
        tdt_config.update(fault_plan=None)
    with pytest.raises(ValueError, match="pool"):
        FaultPlan("drop_signal", pool="").validate()
    # a pool-scoped chunk-corruption plan leaves the plane alone when it
    # names the OTHER side
    tdt_config.update(fault_plan=FaultPlan("bitflip", pe=-1,
                                           pool="prefill"))
    try:
        p = _plane()
        r = p.transfer("a", list(range(8)), now=0.0)
    finally:
        tdt_config.update(fault_plan=None)
    assert r.outcome == "delivered" and r.retries == 0


def test_disagg_config_validation():
    with pytest.raises(ValueError, match="virtual_step_s"):
        DisaggServingConfig(
            prefill=ServingConfig(virtual_step_s=0.05)).validate()
    with pytest.raises(ValueError, match="prefill_pes"):
        DisaggServingConfig(prefill_pes=0).validate()
    with pytest.raises(ValueError, match="wire"):
        HandoffConfig(wire="fp64").validate()
    # the device-tier tuple a handoff policy selects is a real member of
    # the verified tune space
    from triton_dist_tpu.ops.kv_stream import KV_STREAM_TUNE_SPACE

    assert HandoffConfig(chunks_per_page=2).kv_stream_config() in (
        KV_STREAM_TUNE_SPACE
    )


# ---------------------------------------------------------------------------
# Engine tier: the two-pool topology
# ---------------------------------------------------------------------------

def _traffic(n=6, seed=3, **over):
    kw = dict(
        rate_rps=20.0, n_requests=n, prompt_len=("uniform", 2, 5),
        output_len=("uniform", 2, 4), vocab=32, seed=seed,
    )
    kw.update(over)
    return generate_trace(TrafficSpec(**kw))


def test_disagg_byte_identical_to_unified_greedy(model):
    cfg, params = model
    trace = _traffic()
    eng, done = _serve_disagg(cfg, params, trace)
    _, done_u = _serve_unified(cfg, params, trace)
    assert set(done) == {a.request.uid for a in trace}
    for uid in done:
        assert isinstance(done[uid], Finished)
        assert done[uid].tokens == done_u[uid].tokens, uid
        # per-token stamps cross the pools: the first is the prefill
        # pool's (the client's TTFT), the rest the decode pool's
        fin = done[uid]
        assert len(fin.t_tokens) == len(fin.tokens)
        assert list(fin.t_tokens) == sorted(fin.t_tokens)
        assert fin.t_tokens[0] == fin.t_first_token
        assert fin.t_tokens[-1] == fin.t_finished
    snap = eng.snapshot()
    assert snap["requests"]["handoffs"] == len(
        [u for u in done if len(done[u].tokens) > 1]
    )
    assert snap["handoff"]["fallbacks"] == 0
    assert not eng.collapsed


def test_disagg_byte_identical_seeded_sampled(model):
    cfg, params = model
    trace = _traffic(seed=11, temperature=0.8, top_k=4)
    _, done = _serve_disagg(cfg, params, trace)
    _, done_u = _serve_unified(cfg, params, trace)
    for uid in done:
        assert done[uid].tokens == done_u[uid].tokens, uid


def test_cross_pool_first_token_consistency(model):
    """The decode pool regenerates the first token the prefill pool
    already served; the two derive it from the same prefix + seed and
    must agree — the cross-pool consistency pin."""
    cfg, params = model
    clock = retry.FakeClock()
    with retry.clock_scope(clock):
        eng = DisaggServingEngine(
            cfg, params, _mesh(0, 4), s_max=16, clock=clock,
            serving=DisaggServingConfig(prefill_pes=2, virtual_step_s=0.05),
        )
        uid = eng.submit(Request([3, 1, 4, 1, 5], max_new_tokens=4,
                                 temperature=0.9, seed=7, uid="x"))
        eng.run_until_idle()
    fin = eng.results[uid]
    # TTFT came from the prefill pool; the decode stream regenerated the
    # same first token as position L's decode
    assert fin.t_first_token is not None
    assert len(fin.tokens) == 4


def test_transfer_phase_decomposes_e2e_exactly(model):
    """The ISSUE 13 obs satellite: queued → prefill → transfer → decode
    sums EXACTLY to e2e for every handed-off request, and the
    serving:transfer span carries the handoff attribution."""
    from triton_dist_tpu import obs

    cfg, params = model
    tdt_config.update(obs=obs.ObsConfig())
    obs.reset()
    try:
        eng, done = _serve_disagg(cfg, params, _traffic())
        spans = list(obs.tracer.spans())
        snap = eng.snapshot()
    finally:
        tdt_config.update(obs=None)
        obs.reset()
    by_req: dict = {}
    for s in spans:
        if s.name.startswith("serving:"):
            by_req.setdefault(s.track, {})[s.name] = s
    checked = 0
    for track, ss in by_req.items():
        if "serving:transfer" not in ss:
            continue
        checked += 1
        t = ss["serving:transfer"]
        assert t.attrs["outcome"] == "delivered"
        assert t.attrs["pages_streamed"] + t.attrs["pages_deduped"] >= 1
        # exact decomposition: each phase starts where the last ended
        assert ss["serving:queued"].t_end == ss["serving:prefill"].t_start
        assert ss["serving:prefill"].t_end == t.t_start
        assert t.t_end == ss["serving:decode"].t_start
        assert ss["serving:queued"].t_start == ss["serving:e2e"].t_start
        assert ss["serving:decode"].t_end == ss["serving:e2e"].t_end
    assert checked >= 1
    assert "serving:transfer" in snap["span_ms"]


def test_disagg_ttft_beats_unified_at_high_load(model):
    """The A/B the topology exists for: at an offered load that saturates
    the unified engine's slots, dedicated prefill slots keep TTFT down
    (first tokens keep flowing while decode is busy)."""
    cfg, params = model
    trace = _traffic(n=16, seed=5, rate_rps=40.0,
                     prompt_len=("uniform", 2, 4),
                     output_len=("uniform", 4, 6))
    eng, done = _serve_disagg(cfg, params, trace)
    uni, done_u = _serve_unified(cfg, params, trace)
    d = eng.snapshot()["latency_ms"]["ttft"]["p99"]
    u = uni.snapshot()["latency_ms"]["ttft"]["p99"]
    assert d < u, (d, u)


def test_prefill_overflow_sheds_to_decode_local(model):
    """A full prefill-pool queue routes new work decode-local (cold,
    correct, slower) instead of rejecting it."""
    cfg, params = model
    clock = retry.FakeClock()
    with retry.clock_scope(clock):
        eng = DisaggServingEngine(
            cfg, params, _mesh(0, 4), s_max=16, clock=clock,
            serving=DisaggServingConfig(
                prefill_pes=2, virtual_step_s=0.05,
                prefill=ServingConfig(max_queue=1),
            ),
        )
        for i in range(8):
            res = eng.submit(Request([1, 2, 3, 4], max_new_tokens=3,
                                     uid=f"r{i}"))
            assert res == f"r{i}"  # never rejected: the decode pool absorbs
        done = eng.run_until_idle()
    assert len(done) == 8
    assert eng.snapshot()["requests"]["local_prefills"] >= 1
    from triton_dist_tpu.serving import Arrival

    _, done_u = _serve_unified(
        cfg, params,
        [Arrival(t_s=0.0, request=Request([1, 2, 3, 4], max_new_tokens=3,
                                          uid=f"r{i}"))
         for i in range(8)],
    )
    for uid in done:
        assert done[uid].tokens == done_u[uid].tokens


def test_w8_serving_params_quantized_once(model):
    """ISSUE 13 satellite (the tp_transformer.py:360 noted follow-up):
    a w8 MoE serving engine quantizes FLOAT expert banks ONCE at build —
    the batcher's params carry pre-quantized int8 pools + explicit
    scales (so resolve_w8's per-call quantize bank read+write never
    runs) — and the quantized-once tree is bit-identical to what the
    on-the-fly path quantizes per call."""
    from triton_dist_tpu.models.tp_transformer import (
        MoETransformerConfig, init_moe_params,
    )
    from triton_dist_tpu.ops.group_gemm import (
        GroupGemmConfig, quantize_expert_weights, resolve_w8,
    )

    cfg = MoETransformerConfig(
        vocab=32, hidden=32, ffn=64, n_layers=1, n_q_heads=4, n_kv_heads=2,
        head_dim=8, batch=2, seq=8, n_experts=4, topk=2,
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16),
        gg_config=GroupGemmConfig(block_m=8, block_n=16, w8=True),
    )
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, _mesh(0, 1), s_max=16)
    served = eng._batcher.params["layers"][0]
    assert served["w_up"].dtype == np.int8
    assert "w_up_scale" in served and "w_down_scale" in served
    # bit-identity vs the on-the-fly path (both route through
    # quantize_expert_weights)
    w_fly, s_fly = resolve_w8(params["layers"][0]["w_up"], None,
                              cfg.gg_config)
    w_once, s_once = quantize_expert_weights(params["layers"][0]["w_up"])
    assert np.array_equal(np.asarray(w_fly), np.asarray(w_once))
    assert np.array_equal(np.asarray(s_fly), np.asarray(s_once))
    # the cache serves ONE quantization for the engine's lifetime
    assert eng._serving_params() is eng._serving_params()
    # a non-w8 engine (or pre-quantized params) passes through untouched
    cfg2 = dataclasses.replace(
        cfg, gg_config=GroupGemmConfig(block_m=8, block_n=16))
    eng2 = ServingEngine(cfg2, params, _mesh(0, 1), s_max=16)
    assert eng2._serving_params() is params


def test_both_pools_full_reoffers_never_drops(model):
    """A burst larger than BOTH pools' queues: serve() re-offers each
    doubly-rejected arrival instead of dropping it — every offered uid
    still reaches exactly one terminal state."""
    from triton_dist_tpu.serving import Arrival

    cfg, params = model
    trace = [
        Arrival(t_s=0.0, request=Request([1, 2, 3], max_new_tokens=2,
                                         uid=f"b{i}"))
        for i in range(12)
    ]
    clock = retry.FakeClock()
    with retry.clock_scope(clock):
        eng = DisaggServingEngine(
            cfg, params, _mesh(0, 4), s_max=16, clock=clock,
            serving=DisaggServingConfig(
                prefill_pes=2, virtual_step_s=0.05,
                prefill=ServingConfig(max_queue=1),
                decode=ServingConfig(max_queue=1),
            ),
        )
        done = eng.serve(trace)
    assert set(done) == {a.request.uid for a in trace}
    assert all(isinstance(r, Finished) for r in done.values())
    assert eng.snapshot()["requests"]["reoffered"] >= 1


def test_decode_rebuild_invalidates_transfer_manifest(model):
    """A decode-pool rebuild destroys its cache, so the transfer
    manifest must forget previously streamed pages — the next shared
    prefix re-streams instead of dedup'ing onto dead pages."""
    cfg, params = model
    clock = retry.FakeClock()
    with retry.clock_scope(clock):
        eng = DisaggServingEngine(
            cfg, params, _mesh(0, 4), s_max=16, clock=clock,
            serving=DisaggServingConfig(prefill_pes=2, virtual_step_s=0.05),
        )
        eng.submit(Request([1, 2, 3, 4, 5], max_new_tokens=2, uid="a"))
        eng.run_until_idle()
        assert eng.handoff_plane.snapshot()["pages_resident"] > 0
        # simulate a decode-pool rebuild having happened
        eng.decode.rebuilds += 1
        eng.submit(Request([1, 2, 3, 4, 5], max_new_tokens=2, uid="b"))
        eng.run_until_idle()
    ho = eng.handoff_plane.snapshot()
    # the second identical prompt re-streamed (no dedup onto dead pages)
    assert ho["pages_deduped"] == 0
    assert ho["pages_streamed"] >= 2


# ---------------------------------------------------------------------------
# Chaos tier
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_corrupt_chunk_mid_handoff_attributed_recovery(model):
    """THE acceptance arc: a corrupted KV chunk mid-handoff produces an
    attributed recovery — the named decode PE is struck through the
    elastic state machine, every rung lands in the health registry, and
    every request finishes byte-identically to unified-engine cold
    prefill (greedy AND seeded-sampled)."""
    cfg, params = model
    for temp_kw in ({}, dict(temperature=0.8, top_k=4)):
        resilience.reset()
        elastic.reset()
        trace = _traffic(n=4, seed=5, prompt_len=("fixed", 5),
                         output_len=("fixed", 3), **temp_kw)
        tdt_config.update(elastic=True, suspect_threshold=2,
                          fault_plan=FaultPlan("bitflip", pe=-1,
                                               pool="decode", site=1,
                                               max_triggers=12))
        try:
            eng, done = _serve_disagg(cfg, params, trace)
        finally:
            tdt_config.update(fault_plan=None, elastic=False)
        snap = eng.snapshot()
        ho = snap["handoff"]
        assert ho["canary_mismatches"] > 0
        assert ho["restreams"] > 0 and ho["fallbacks"] > 0
        # the culprit decode PE is STRUCK by name (global index)
        struck = [pe for pe, st in elastic.peer_states().items()
                  if st != "healthy"]
        assert struck and all(pe >= 2 for pe in struck), (
            elastic.peer_states()
        )
        hc = health.counters()
        assert hc.get(("kv_handoff", "handoff_retry"), 0) > 0
        assert hc.get(("kv_handoff", "handoff_fallback"), 0) > 0
        # zero lost, byte-identical to unified cold prefill
        _, done_u = _serve_unified(cfg, params, trace)
        assert set(done) == {a.request.uid for a in trace}
        for uid in done:
            assert done[uid].tokens == done_u[uid].tokens, (uid, temp_kw)


@pytest.mark.chaos
def test_prefill_straggler_shrinks_pool_mid_stream(model):
    """A prefill-pool straggler quarantines (pool-scoped by-absence
    attribution at the GLOBAL index) and the POOL shrinks mid-stream —
    the decode pool never shrinks, and serving completes byte-identical."""
    cfg, params = model
    trace = _traffic(n=6, seed=9)
    tdt_config.update(elastic=True, suspect_threshold=2)
    real_step = ContinuousBatcher.step
    calls = {"n": 0}

    def flaky(self):
        from triton_dist_tpu.resilience import faults as F

        if F.current_pool() == "prefill":
            calls["n"] += 1
            if calls["n"] in (2, 3):
                w = int(self.mesh.shape["tp"])
                recs = [{"pe": p, "kind": "barrier_all", "site": 0,
                         "status": "timeout", "expected": 1, "observed": 0,
                         "budget": 16} for p in range(w) if p != 1]
                raise DistTimeoutError("batcher_step", recs, world_size=w)
        return real_step(self)

    ContinuousBatcher.step = flaky
    try:
        eng, done = _serve_disagg(cfg, params, trace)
    finally:
        ContinuousBatcher.step = real_step
        tdt_config.update(elastic=False)
    # pool position 1 == GLOBAL PE 1 quarantined; decode pool untouched
    assert elastic.state(1) == "quarantined"
    assert all(elastic.state(pe) == "healthy" for pe in (2, 3))
    snap = eng.snapshot()
    assert snap["pools"]["prefill"]["engine"]["world_size"] == 1
    assert snap["pools"]["decode"]["engine"]["world_size"] == 2
    assert not eng.collapsed
    _, done_u = _serve_unified(cfg, params, trace)
    assert set(done) == {a.request.uid for a in trace}
    for uid in done:
        assert done[uid].tokens == done_u[uid].tokens, uid


@pytest.mark.chaos
def test_prefill_pool_collapse_degrades_to_unified(model):
    """The prefill pool losing its last PE collapses the topology to the
    unified engine: every in-flight request replays into the decode pool
    and finishes — zero lost requests, byte-identical tokens, one
    attributed pool_collapse health event."""
    cfg, params = model
    trace = _traffic(n=8, seed=7, rate_rps=30.0)
    tdt_config.update(elastic=True, suspect_threshold=2)
    real_step = ContinuousBatcher.step
    calls = {"n": 0}

    def flaky(self):
        from triton_dist_tpu.resilience import faults as F

        if F.current_pool() == "prefill":
            calls["n"] += 1
            if calls["n"] >= 2:  # a storm the pool cannot survive
                w = int(self.mesh.shape["tp"])
                recs = [{"pe": p, "kind": "barrier_all", "site": 0,
                         "status": "timeout", "expected": 1, "observed": 0,
                         "budget": 16} for p in range(w) if p != 1]
                raise DistTimeoutError("batcher_step", recs, world_size=w)
        return real_step(self)

    ContinuousBatcher.step = flaky
    try:
        eng, done = _serve_disagg(
            cfg, params, trace,
            serving=DisaggServingConfig(
                prefill_pes=2, virtual_step_s=0.05,
                prefill=ServingConfig(max_step_failures=3),
                handoff=HandoffConfig(page_tokens=4, chunks_per_page=1),
            ),
        )
    finally:
        ContinuousBatcher.step = real_step
        tdt_config.update(elastic=False)
    assert eng.collapsed
    snap = eng.snapshot()
    assert snap["requests"]["pool_collapses"] == 1
    assert health.counters().get(("serving_disagg", "pool_collapse")) == 1
    assert not health.is_healthy()
    # zero lost requests, byte-identical to unified cold prefill
    assert set(done) == {a.request.uid for a in trace}
    assert all(isinstance(r, Finished) for r in done.values())
    _, done_u = _serve_unified(cfg, params, trace)
    for uid in done:
        assert done[uid].tokens == done_u[uid].tokens, uid
    # and the collapsed topology keeps serving new work (unified mode)
    clock = retry.FakeClock()
    with retry.clock_scope(clock):
        eng.clock = clock
        eng.decode.clock = clock
        uid = eng.submit(Request([1, 2, 3], max_new_tokens=2, uid="post"))
        eng.run_until_idle()
    assert isinstance(eng.results["post"], Finished)
