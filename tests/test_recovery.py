"""The recovery plane (ISSUE 17): scoped elastic namespaces, pool
probation regrow, reversible collapse, and replica resurrection.

Tier structure mirrors tests/test_disagg.py / tests/test_fleet.py:

- **host tier**: the new knob validation (arming discipline), the
  :class:`~triton_dist_tpu.resilience.elastic.ElasticScope` namespace
  semantics (one scope's strikes never touch another, ``pe{N}@owner``
  health families, the ``pes=`` probe filter that keeps one pool's
  failed probe from resetting another pool's probation counters —
  satellite 6), ``elastic.scope_summaries()``, the affinity-only
  resurrection ramp, and the router-side residency eviction mirror
  (satellite 1) on a real replicas=1 fleet;
- **chaos tier** (``pytest.mark.chaos``, wired into
  ``scripts/chaos_matrix.sh`` full and ``--quick``): a quarantined
  decode pool regrows by probation MID-SERVE (tokens byte-identical to
  unified), a collapsed topology un-collapses after a clean probation
  window and serves two-pool again, a dead replica resurrects (probe
  rounds -> fresh engine -> cold trie) and then serves again, the
  armed-but-untriggered byte-identity pins; the quick recovery
  soak campaign (``resilience/soak.py SoakSpec.fleet_recovery_spec``)
  with bit-identical seeded replay is test_recovery_soak.py;
- **soak tier** (``pytest.mark.soak``, implies slow; also
  test_recovery_soak.py): the full recovery campaign set
  scripts/chaos_soak.py runs.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu import config as tdt_config
from triton_dist_tpu import obs
from triton_dist_tpu import resilience
from triton_dist_tpu.models import init_params
from triton_dist_tpu.models.decode import ContinuousBatcher, Request
from triton_dist_tpu.models.prefix_cache import (
    PagePrefixCache,
    PrefixCacheConfig,
)
from triton_dist_tpu.models.tp_transformer import TransformerConfig
from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig
from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig
from triton_dist_tpu.resilience import elastic, health, retry
from triton_dist_tpu.resilience.records import DistTimeoutError
from triton_dist_tpu.serving import (
    DisaggServingConfig,
    DisaggServingEngine,
    Finished,
    FleetConfig,
    FleetRouter,
    HandoffConfig,
    ResurrectConfig,
    ServingConfig,
    ServingEngine,
    TrafficSpec,
    generate_trace,
)
from triton_dist_tpu.serving.engine import UnrecoverableEngineError


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
    obs.reset()
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


@pytest.fixture(scope="session")
def mesh1() -> Mesh:
    return Mesh(np.array(jax.devices()[:1]), ("tp",))


@pytest.fixture(scope="session")
def mesh4() -> Mesh:
    return Mesh(np.array(jax.devices()[:4]), ("tp",))


def _mesh(lo, hi):
    return Mesh(np.array(jax.devices()[lo:hi]), ("tp",))


def _traffic(n=6, seed=3, **over):
    kw = dict(
        rate_rps=20.0, n_requests=n, prompt_len=("uniform", 2, 5),
        output_len=("uniform", 2, 4), vocab=32, seed=seed,
    )
    kw.update(over)
    return generate_trace(TrafficSpec(**kw))


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
# Host tier: config validation (arming discipline)
# ---------------------------------------------------------------------------

def test_recovery_config_validation():
    with pytest.raises(ValueError, match="pool_probe_steps"):
        DisaggServingConfig(pool_probe_steps=0).validate()
    with pytest.raises(ValueError, match="collapse_probation_steps"):
        DisaggServingConfig(collapse_probation_steps=0).validate()
    with pytest.raises(ValueError, match="probe_steps"):
        ResurrectConfig(probe_steps=0).validate()
    with pytest.raises(ValueError, match="ramp_steps"):
        ResurrectConfig(ramp_steps=-1).validate()
    # FleetConfig validates its resurrect block
    with pytest.raises(ValueError, match="probe_steps"):
        FleetConfig(resurrect=ResurrectConfig(probe_steps=0)).validate()
    # armed shapes are legal; None disarms (the default posture)
    DisaggServingConfig(pool_probe_steps=3,
                        collapse_probation_steps=5).validate()
    DisaggServingConfig().validate()
    FleetConfig(elastic_scope=True, resurrect=ResurrectConfig()).validate()
    assert DisaggServingConfig().pool_probe_steps is None
    assert DisaggServingConfig().collapse_probation_steps is None
    assert FleetConfig().resurrect is None
    assert FleetConfig().elastic_scope is False


# ---------------------------------------------------------------------------
# Host tier: scoped elastic namespaces (tentpole a)
# ---------------------------------------------------------------------------

def test_scoped_strikes_stay_in_their_namespace(mesh1):
    """Two owned scopes and the DEFAULT scope share PE numbering but
    never state: r0's quarantine is invisible to r1 and to the module
    surface, and its health events land under ``pe{N}@r0``."""
    tdt_config.update(elastic=True, suspect_threshold=2, probation_probes=1)
    a = elastic.ElasticScope(owner="r0")
    b = elastic.ElasticScope(owner="r1")
    assert a.report_timeout(1, family="t") == "suspect"
    assert a.report_timeout(1, family="t") == "quarantined"
    assert a.state(1) == "quarantined"
    assert b.state(1) == "healthy"
    assert elastic.state(1) == "healthy", "DEFAULT scope untouched"
    hc = health.counters()
    assert hc.get(("pe1@r0", "pe_quarantine")) == 1
    assert ("pe1", "pe_quarantine") not in hc, "no unscoped family leaked"
    assert ("pe1@r1", "pe_quarantine") not in hc
    # readmission through the scope carries the owner too
    out = a.probe_quarantined(mesh1, probe=lambda: True)
    assert out == {1: "healthy"}
    assert health.counters().get(("pe1@r0", "pe_readmit")) == 1
    assert b.peer_states() == {} and elastic.peer_states() == {}


def test_probe_pes_filter_isolates_probation_counters(mesh1):
    """The satellite-6 regression pin: a probe round restricted via
    ``pes=`` must not touch the excluded candidates' probation progress
    — and a FAILED round in one scope never resets another scope's."""
    tdt_config.update(elastic=True, suspect_threshold=1, probation_probes=2)
    sc = elastic.ElasticScope(owner="rX")
    other = elastic.ElasticScope(owner="rY")
    sc.quarantine(1)
    sc.quarantine(2)
    other.quarantine(1)
    # one clean probe on pe1 only: halfway through its 2-probe probation
    assert sc.probe_quarantined(mesh1, pes=[1], probe=lambda: True) == {
        1: "probation"
    }
    assert sc.state(2) == "quarantined", "pe2 was not a candidate"
    # a FAILED probe restricted to pe2 re-quarantines pe2 ONLY
    assert sc.probe_quarantined(mesh1, pes=[2], probe=lambda: False) == {
        2: "quarantined"
    }
    assert other.state(1) == "quarantined", "other scope untouched"
    # pe1's clean-probe progress survived the failed pe2 round: ONE more
    # clean probe re-admits it (a reset would leave it in probation)
    assert sc.probe_quarantined(mesh1, pes=[1], probe=lambda: True) == {
        1: "healthy"
    }
    assert health.counters().get(("pe1@rX", "pe_readmit")) == 1
    assert ("pe1@rY", "pe_readmit") not in health.counters()


def test_scope_summaries_only_degraded_owned_scopes():
    """``scope_summaries()`` is what the black box folds into a bundle's
    attribution: empty when nothing owned is degraded (pre-scoping
    bundle bytes), and never includes the DEFAULT scope."""
    tdt_config.update(elastic=True, suspect_threshold=2)
    assert elastic.scope_summaries() == {}
    sc = elastic.ElasticScope(owner="r7")
    assert elastic.scope_summaries() == {}, "clean owned scope omitted"
    sc.report_timeout(0, family="t")
    summ = elastic.scope_summaries()
    assert list(summ) == ["r7"]
    assert summ["r7"]["owner"] == "r7"
    assert summ["r7"]["peers"]["0"]["state"] == "suspect"
    # DEFAULT degradation shows on the module surface, never in scopes
    elastic.DEFAULT.report_timeout(3, family="t")
    assert list(elastic.scope_summaries()) == ["r7"]
    assert "owner" not in elastic.summary()
    assert elastic.summary()["peers"]["3"]["state"] == "suspect"


# ---------------------------------------------------------------------------
# Host tier: the residency eviction mirror seam (satellite 1)
# ---------------------------------------------------------------------------

def _px(slots=4, page=4, pps=8, pes=1, **cfg):
    return PagePrefixCache(
        PrefixCacheConfig(**cfg), n_slots=slots, page=page,
        pps_local=pps, n_pes=pes,
    )


def test_evict_listener_default_none_and_lru_notification():
    """The trie's ``evict_listener`` seam: None by default (byte-zero
    overhead), and an LRU pool-pressure eviction reports every removed
    node as its FULL-prefix key (the router's affinity fingerprint)."""
    px = _px(slots=2, page=4, pps=4)          # tiny pool: 8 pages/PE
    assert px.evict_listener is None
    dropped: list = []
    px.evict_listener = lambda keys: dropped.extend(keys)
    a, b = list(range(0, 9)), list(range(9, 18))
    px.acquire(0, a, 4)
    px.publish(0, 0, a[0:4])
    px.publish(0, 1, a[4:8])
    px.release(0)
    px.acquire(0, b, 4)
    px.publish(0, 0, b[0:4])
    px.publish(0, 1, b[4:8])
    assert dropped == [], "no eviction yet"
    # a third full admission must evict a's retained chain (LRU-oldest)
    px.acquire(1, list(range(20, 29)), 4)
    px.audit()
    assert set(dropped) == {tuple(a[0:4]), tuple(a[0:8])}, dropped
    assert px.stats()["evicted_pages"] >= 1


def test_evict_listener_fires_on_strike_detach():
    """The poison path notifies too: a struck chain's keys leave the
    router's residency model the moment the trie detaches them."""
    px = _px()
    dropped: list = []
    px.evict_listener = lambda keys: dropped.extend(keys)
    prompt = list(range(10))
    px.acquire(0, prompt, 4)
    px.publish(0, 0, prompt[0:4])
    px.publish(0, 1, prompt[4:8])
    px.acquire(1, prompt, 4)
    readers = px.release(0, strike=True)
    assert readers == [1]
    assert set(dropped) == {tuple(prompt[0:4]), tuple(prompt[0:8])}
    px.release(1)
    px.audit()


def test_router_mirror_drops_evicted_resident_keys(model, mesh1):
    """Satellite 1 end-to-end at replicas=1: the router attaches the
    mirror, marks residency on route, and a trie eviction drops exactly
    the evicted page keys from the replica's affinity model."""
    cfg, params = model
    clock = retry.FakeClock()
    with retry.clock_scope(clock):
        fl = FleetRouter(
            cfg, params, mesh1, s_max=16, clock=clock,
            fleet=FleetConfig(
                replicas=1,
                serving=ServingConfig(virtual_step_s=0.05,
                                      prefix_cache=PrefixCacheConfig()),
            ),
            page_size=4,
        )
        rep = fl.replicas[0]
        pxs = fl._rep_caches(rep)
        assert pxs and pxs[0].evict_listener is not None
        fl.submit(Request([1, 2, 3, 4], max_new_tokens=2, uid="a"))
        fl.run_until_idle()
    assert isinstance(fl.results["a"], Finished)
    key = (1, 2, 3, 4)
    assert key in rep.resident
    px = pxs[0]
    node = px._root.children.get(key)
    assert node is not None and node.ref == 0, "published, released page"
    px._evict_subtree(node)
    assert key not in rep.resident, "mirror dropped the evicted key"


def test_ramp_excludes_cold_replica_from_pressure_routing(model, mesh4):
    """A just-resurrected (ramping) replica takes affinity traffic only:
    pressure placement skips it while any other candidate exists, but a
    resident-prefix hit still reaches it, and as sole survivor it takes
    everything."""
    cfg, params = model
    fl = FleetRouter(
        cfg, params, mesh4, s_max=8, clock=retry.FakeClock(),
        fleet=FleetConfig(replicas=2,
                          serving=ServingConfig(virtual_step_s=0.05)),
    )
    fl.replicas[1].ramp = 2
    # cold prompt: the ramping replica sits out pressure placement
    assert [r.idx for r, _ in fl._route([9, 9, 9], "interactive")] == [0]
    # affinity still reaches it
    fl._mark_resident(fl.replicas[1], [1, 2, 3, 4, 5])
    order = fl._route([1, 2, 3, 4, 5], "interactive")
    assert order[0][0].idx == 1 and order[0][1] == "affinity"
    # sole survivor: the ramp never empties the candidate list
    fl.replicas[0].alive = False
    assert [r.idx for r, _ in fl._route([9, 9, 9], "interactive")] == [1]


# ---------------------------------------------------------------------------
# Chaos tier: pool probation regrow mid-serve (tentpole b)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_decode_pool_regrows_by_probation_mid_serve(model):
    """A decode-pool straggler pair quarantines global PE 3 and shrinks
    the pool to world 1; with ``pool_probe_steps`` armed the pool probes
    its OWN sub-mesh, re-admits the PE, and regrows to world 2 MID-SERVE
    — tokens stay byte-identical to the unified engine."""
    cfg, params = model
    trace = _traffic(n=6, seed=9)
    tdt_config.update(elastic=True, suspect_threshold=2, probation_probes=1)
    real_step = ContinuousBatcher.step
    calls = {"n": 0}

    def flaky(self):
        from triton_dist_tpu.resilience import faults as F

        if F.current_pool() == "decode":
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
        eng, done = _serve_disagg(
            cfg, params, trace,
            serving=DisaggServingConfig(
                prefill_pes=2, virtual_step_s=0.05, pool_probe_steps=2,
                handoff=HandoffConfig(page_tokens=4, chunks_per_page=2,
                                      virtual_chunk_s=0.001),
            ),
        )
    finally:
        ContinuousBatcher.step = real_step
        tdt_config.update(elastic=False)
    # decode pool position 1 == GLOBAL PE 3: struck, then re-admitted
    assert elastic.state(3) == "healthy"
    hc = health.counters()
    assert hc.get(("pe3", "pe_quarantine")) == 1
    assert hc.get(("pe3", "pe_readmit")) == 1
    assert hc.get(("serving_pool_decode", "pool_regrow")) >= 1
    assert ("serving_pool_prefill", "pool_regrow") not in hc
    snap = eng.snapshot()
    assert snap["pools"]["decode"]["engine"]["world_size"] == 2, (
        "regrown back to the full pool"
    )
    assert not eng.collapsed
    # zero lost, byte-identical through shrink AND regrow
    _, done_u = _serve_unified(cfg, params, trace)
    assert set(done) == {a.request.uid for a in trace}
    for uid in done:
        assert done[uid].tokens == done_u[uid].tokens, uid


# ---------------------------------------------------------------------------
# Chaos tier: reversible collapse (tentpole c)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_collapse_then_uncollapse_round_trip(model):
    """A windowed prefill storm collapses the topology; once it clears,
    ``collapse_probation_steps`` clean unified ticks + a clean
    prefill-slice probe re-carve the two-pool topology MID-SERVE — and
    the un-collapsed engine serves new work through both pools again."""
    cfg, params = model
    trace = _traffic(n=8, seed=7, rate_rps=30.0)
    tdt_config.update(elastic=True, suspect_threshold=2, probation_probes=1)
    real_step = ContinuousBatcher.step
    calls = {"n": 0}

    def flaky(self):
        from triton_dist_tpu.resilience import faults as F

        if F.current_pool() == "prefill":
            calls["n"] += 1
            if 2 <= calls["n"] < 8:  # a storm the pool cannot survive,
                w = int(self.mesh.shape["tp"])  # then clean air
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
                collapse_probation_steps=2,
                prefill=ServingConfig(max_step_failures=3),
                handoff=HandoffConfig(page_tokens=4, chunks_per_page=1),
            ),
        )
    finally:
        ContinuousBatcher.step = real_step
        tdt_config.update(elastic=False)
    snap = eng.snapshot()
    assert snap["requests"]["pool_collapses"] == 1
    assert not eng.collapsed, "probation re-carved the topology"
    hc = health.counters()
    assert hc.get(("serving_disagg", "pool_collapse")) == 1
    assert hc.get(("serving_disagg", "pool_uncollapse")) == 1
    # the struck prefill PE passed the un-collapse probe
    assert elastic.state(1) == "healthy"
    assert snap["pools"]["prefill"]["engine"]["world_size"] == 2
    # zero lost through the whole round trip, byte-identical to unified
    assert set(done) == {a.request.uid for a in trace}
    assert all(isinstance(r, Finished) for r in done.values())
    _, done_u = _serve_unified(cfg, params, trace)
    for uid in done:
        assert done[uid].tokens == done_u[uid].tokens, uid
    # and the re-carved topology serves NEW work two-pool again
    clock = retry.FakeClock()
    with retry.clock_scope(clock):
        eng.clock = clock
        eng.prefill.clock = clock
        eng.decode.clock = clock
        eng.submit(Request([1, 2, 3, 4, 5], max_new_tokens=2, uid="post"))
        eng.run_until_idle()
    assert isinstance(eng.results["post"], Finished)
    assert eng.snapshot()["requests"]["pool_collapses"] == 1, (
        "no re-collapse: the storm is over"
    )


# ---------------------------------------------------------------------------
# Chaos tier: replica resurrection (tentpole d)
# ---------------------------------------------------------------------------

def _fleet_recovery(model, mesh, *, clock, kill_after=None):
    cfg, params = model
    fl = FleetRouter(
        cfg, params, mesh, s_max=8, clock=clock,
        fleet=FleetConfig(
            replicas=2, serving=ServingConfig(virtual_step_s=0.05),
            elastic_scope=True,
            resurrect=ResurrectConfig(probe_steps=2, ramp_steps=1),
        ),
    )
    return fl


def _reqs(n):
    return [
        Request([1 + i % 5, 2 + i % 3, 3], max_new_tokens=3, uid=f"q{i}")
        for i in range(n)
    ]


@pytest.mark.chaos
def test_replica_resurrection_serves_again(model, mesh4):
    """A replica killed by a typed step death fails over (zero lost),
    then resurrects after clean probe rounds — fresh engine, cold trie,
    ``replica_readmit`` recorded — and takes NEW traffic afterwards."""
    # baseline: the same armed fleet, nobody dies
    clock = retry.FakeClock()
    with retry.clock_scope(clock):
        base_fl = _fleet_recovery(model, mesh4, clock=clock)
        for req in _reqs(8):
            base_fl.submit(req, arrival_t=0.0, deadline_ms=60_000.0)
        base = base_fl.run_until_idle()
    assert base_fl.snapshot()["fleet"]["resurrections"] == 0

    clock = retry.FakeClock()
    with retry.clock_scope(clock):
        fl = _fleet_recovery(model, mesh4, clock=clock)
        for req in _reqs(8):
            fl.submit(req, arrival_t=0.0, deadline_ms=60_000.0)
        # instance-level kill: r1 dies on its second step
        orig = fl.replicas[1].engine._step_once
        calls = {"n": 0}

        def dying():
            calls["n"] += 1
            if calls["n"] > 1:
                raise UnrecoverableEngineError("injected replica death")
            return orig()

        fl.replicas[1].engine._step_once = dying
        done = fl.run_until_idle()
        snap = fl.snapshot()
        assert snap["fleet"]["failovers"] == 1
        assert snap["fleet"]["resurrections"] == 1
        assert snap["engine"]["dead"] == [], "r1 is back"
        assert fl.replicas[1].alive
        hc = health.counters()
        assert hc.get(("serving_fleet", "replica_failover")) == 1
        assert hc.get(("serving_fleet", "replica_readmit")) == 1
        # zero lost, byte-identical to the unkilled fleet
        assert set(done) == set(base)
        for uid in base:
            assert isinstance(done[uid], Finished), uid
            assert done[uid].tokens == base[uid].tokens, uid
        # the resurrected replica SERVES: ramp spent, pressure placement
        # sees the idle fresh engine again
        fl.replicas[1].ramp = 0
        fl.submit(Request([7, 7, 7], max_new_tokens=2, uid="n0"))
        fl.submit(Request([8, 8, 8], max_new_tokens=2, uid="n1"))
        assert 1 in (fl._owner["n0"], fl._owner["n1"])
        fl.run_until_idle()
    assert isinstance(fl.results["n0"], Finished)
    assert isinstance(fl.results["n1"], Finished)
    assert fl.snapshot()["replicas"]["r1"]["requests"]["finished"] > 0


@pytest.mark.chaos
def test_resurrect_disarmed_replica_stays_down(model, mesh4):
    """The arming pin's behavioral half: ``resurrect=None`` keeps a dead
    replica dead — no probes, no readmit, the ISSUE 16 posture."""
    cfg, params = model
    clock = retry.FakeClock()
    with retry.clock_scope(clock):
        fl = FleetRouter(
            cfg, params, mesh4, s_max=8, clock=clock,
            fleet=FleetConfig(replicas=2,
                              serving=ServingConfig(virtual_step_s=0.05)),
        )
        for req in _reqs(8):
            fl.submit(req, arrival_t=0.0, deadline_ms=60_000.0)
        orig = fl.replicas[1].engine._step_once
        calls = {"n": 0}

        def dying():
            calls["n"] += 1
            if calls["n"] > 1:
                raise UnrecoverableEngineError("injected replica death")
            return orig()

        fl.replicas[1].engine._step_once = dying
        done = fl.run_until_idle()
    snap = fl.snapshot()
    assert snap["engine"]["dead"] == ["r1"]
    assert snap["fleet"]["resurrections"] == 0
    assert ("serving_fleet", "replica_readmit") not in health.counters()
    assert all(isinstance(r, Finished) for r in done.values())


# ---------------------------------------------------------------------------
# Chaos tier: armed-but-untriggered byte-identity (arming discipline)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_armed_untriggered_disagg_byte_identical(model):
    """``pool_probe_steps`` + ``collapse_probation_steps`` armed on a
    fault-free run: tokens AND timestamps identical to the disarmed
    topology — the recovery plane costs nothing until something breaks."""
    cfg, params = model
    trace = _traffic(n=5, seed=4)
    tdt_config.update(elastic=True)

    def run(**knobs):
        _, done = _serve_disagg(
            cfg, params, trace,
            serving=DisaggServingConfig(
                prefill_pes=2, virtual_step_s=0.05,
                handoff=HandoffConfig(page_tokens=4, chunks_per_page=2,
                                      virtual_chunk_s=0.001),
                **knobs,
            ),
        )
        return {u: (r.tokens, r.t_enqueue, r.t_first_token, r.t_finished)
                for u, r in done.items()}

    disarmed = run()
    armed = run(pool_probe_steps=2, collapse_probation_steps=3)
    assert armed == disarmed


@pytest.mark.chaos
def test_armed_untriggered_fleet_byte_identical(model, mesh4):
    """``elastic_scope`` + ``resurrect`` armed on a fault-free fleet:
    byte-identical terminals to the pre-recovery router."""
    cfg, params = model

    def run(**fleet_knobs):
        clock = retry.FakeClock()
        with retry.clock_scope(clock):
            fl = FleetRouter(
                cfg, params, mesh4, s_max=8, clock=clock,
                fleet=FleetConfig(
                    replicas=2, serving=ServingConfig(virtual_step_s=0.05),
                    **fleet_knobs,
                ),
            )
            for req in _reqs(6):
                fl.submit(req, arrival_t=0.0, deadline_ms=60_000.0)
            done = fl.run_until_idle()
        return {u: (r.tokens, r.t_enqueue, r.t_first_token, r.t_finished)
                for u, r in done.items()}

    disarmed = run()
    armed = run(elastic_scope=True, resurrect=ResurrectConfig())
    assert armed == disarmed
