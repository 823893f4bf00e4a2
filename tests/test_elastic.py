"""Elastic degraded mode (resilience/elastic.py, docs/resilience.md):
the PE state machine, straggler attribution, topology shrink, and the
full arc — step fails, is retried with backoff, the persistent straggler
PE is quarantined, the shrunk world stays bit-correct, and the PE is
re-admitted after a clean probation probe.

Two arc tiers, mirroring tests/test_chaos.py:

- a **host-level arc** that runs everywhere: the watchdog diagnostic
  records are synthesized by a traced fn offered to the real
  ``jit_shard_map`` collection machinery, so the retry loop, trigger
  accounting, attribution, quarantine, mesh shrink, and probation are all
  the production code paths — only the in-kernel wait is simulated;
- a **live arc** (Mosaic TPU interpreter required) driving the real fused
  kernels under a persistent-straggler FaultPlan.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_dist_tpu import config as tdt_config
from triton_dist_tpu import resilience
from triton_dist_tpu.ops import common as ops_common
from triton_dist_tpu.parallel.mesh import shrink_mesh
from triton_dist_tpu.parallel.topology import remap_world, surviving_ring
from triton_dist_tpu.resilience import (
    FaultPlan,
    elastic,
    health,
    retry,
    watchdog,
)
from triton_dist_tpu.resilience import records as R

pytestmark = pytest.mark.chaos



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


# ---------------------------------------------------------------------------
# PE state machine
# ---------------------------------------------------------------------------

def test_states_healthy_suspect_quarantined():
    tdt_config.update(elastic=True, suspect_threshold=2)
    assert elastic.state(3) == elastic.HEALTHY
    assert elastic.report_timeout(3) == elastic.SUSPECT
    assert elastic.report_timeout(3) == elastic.QUARANTINED
    assert elastic.quarantined_pes() == (3,)
    # further strikes on a quarantined PE are idempotent
    assert elastic.report_timeout(3) == elastic.QUARANTINED
    assert health.snapshot()["counters"]["pe3:pe_quarantine"] == 1
    assert not health.is_healthy()


def test_suspect_strikes_decay_to_healthy():
    tdt_config.update(elastic=True, suspect_threshold=3)
    elastic.report_timeout(2)
    elastic.report_timeout(2)
    assert elastic.state(2) == elastic.SUSPECT
    elastic.report_success(2)
    assert elastic.state(2) == elastic.SUSPECT  # one strike left
    elastic.report_success(2)
    assert elastic.state(2) == elastic.HEALTHY
    # note_clean_step decays every suspect
    elastic.report_timeout(1)
    elastic.note_clean_step()
    assert elastic.state(1) == elastic.HEALTHY


def test_probation_readmission_needs_clean_probes():
    tdt_config.update(elastic=True, probation_probes=2)
    elastic.quarantine(5, reason="test")
    out = elastic.probe_quarantined(None, probe=lambda: True)
    assert out == {5: elastic.PROBATION}, "one clean probe of two"
    out = elastic.probe_quarantined(None, probe=lambda: True)
    assert out == {5: elastic.HEALTHY}
    assert health.snapshot()["counters"]["pe5:pe_readmit"] == 1
    assert elastic.quarantined_pes() == ()


def test_failed_probe_requarantines():
    tdt_config.update(elastic=True, probation_probes=2)
    elastic.quarantine(6, reason="test")
    assert elastic.probe_quarantined(None, probe=lambda: True) == {
        6: elastic.PROBATION
    }
    assert elastic.probe_quarantined(None, probe=lambda: False) == {
        6: elastic.QUARANTINED
    }
    # the clean-probe count restarts from zero
    assert elastic.probe_quarantined(None, probe=lambda: True) == {
        6: elastic.PROBATION
    }
    assert "pe6:pe_readmit" not in health.snapshot()["counters"]


def test_timeout_during_probation_requarantines():
    tdt_config.update(elastic=True, probation_probes=2, suspect_threshold=5)
    elastic.quarantine(4, reason="test")
    elastic.probe_quarantined(None, probe=lambda: True)
    assert elastic.state(4) == elastic.PROBATION
    assert elastic.report_timeout(4) == elastic.QUARANTINED


def test_bounded_plan_rejects_family_filter():
    # trigger accounting is per armed op-entry launch, process-wide: a
    # family-scoped budget would be spent by launches the fault never
    # touched and heal without firing
    with pytest.raises(ValueError, match="max_triggers"):
        FaultPlan("drop_signal", family="all_gather", max_triggers=1).validate()
    FaultPlan("drop_signal", max_triggers=1).validate()
    FaultPlan("drop_signal", family="all_gather").validate()


def test_probe_detects_timeout_under_poison_posture(monkeypatch):
    """raise_on_timeout=False must not turn a timed-out probe into a clean
    one: probe_world forces the loud posture for its own launch."""
    from triton_dist_tpu.resilience.records import DistTimeoutError

    tdt_config.update(elastic=True, raise_on_timeout=False)
    seen = {}

    def fused_probe(mesh, axis):
        seen["raise_on_timeout"] = tdt_config.get_config().raise_on_timeout
        raise DistTimeoutError("elastic_probe_fused", _recs([0, 2, 3]),
                               world_size=4)

    monkeypatch.setattr(elastic, "_probe_fused", fused_probe)
    assert elastic.probe_world(None) is False
    assert seen["raise_on_timeout"] is True, "probe must run loud"
    assert tdt_config.get_config().raise_on_timeout is False, "restored"


def test_disabled_entry_points_are_noops():
    assert tdt_config.get_config().elastic is False
    assert elastic.note_timeout_records(
        [{"pe": 0}], world_size=4
    ) is None
    elastic.note_clean_step()
    assert elastic.peer_states() == {}


# ---------------------------------------------------------------------------
# Attribution
# ---------------------------------------------------------------------------

def _recs(pes):
    return [{"pe": pe, "kind": "barrier_all", "site": 0, "status": "timeout",
             "expected": 1, "observed": 0, "budget": 10} for pe in pes]


def test_attribution_names_culprit_by_absence():
    assert elastic.attribute_straggler(_recs([0, 2, 3]), 4) == 1
    # every PE tripped: the fabric, not a peer
    assert elastic.attribute_straggler(_recs([0, 1, 2, 3]), 4) is None
    # several silent PEs: ambiguous
    assert elastic.attribute_straggler(_recs([0, 1]), 4) is None
    assert elastic.attribute_straggler([], 4) is None
    assert elastic.attribute_straggler(_recs([0]), 1) is None
    # out-of-range PE indices (unknown: -1) are ignored
    assert elastic.attribute_straggler(_recs([-1]), 4) is None


# ---------------------------------------------------------------------------
# Topology shrink
# ---------------------------------------------------------------------------

def test_surviving_ring_and_remap():
    assert surviving_ring(8, {3, 5}) == (0, 1, 2, 4, 6, 7)
    assert remap_world(4, {1}) == {0: 0, 2: 1, 3: 2}
    assert surviving_ring(4, ()) == (0, 1, 2, 3)
    with pytest.raises(ValueError, match="no surviving"):
        surviving_ring(2, {0, 1})
    with pytest.raises(ValueError, match="outside axis"):
        surviving_ring(4, {4})


def test_shrink_mesh(mesh8, mesh2x4):
    shrunk = shrink_mesh(mesh8, {3, 5})
    assert tuple(shrunk.axis_names) == ("tp",)
    assert shrunk.devices.shape == (6,)
    expected = [d for i, d in enumerate(mesh8.devices.tolist()) if i not in (3, 5)]
    assert shrunk.devices.tolist() == expected
    # nothing quarantined: identity, same object
    assert shrink_mesh(mesh8, ()) is mesh8
    # multi-axis: only the named axis shrinks
    shrunk2 = shrink_mesh(mesh2x4, {1}, axis="tp")
    assert shrunk2.devices.shape == (2, 3)
    with pytest.raises(ValueError, match="axis"):
        shrink_mesh(mesh8, {0}, axis="ep")


def test_effective_mesh(mesh8):
    # disabled: identity regardless of peer state
    assert elastic.effective_mesh(mesh8) is mesh8
    tdt_config.update(elastic=True)
    assert elastic.effective_mesh(mesh8) is mesh8, "no quarantine yet"
    elastic.quarantine(2, reason="test")
    eff = elastic.effective_mesh(mesh8)
    assert eff.devices.shape == (7,)
    assert mesh8.devices.tolist()[2] not in eff.devices.tolist()
    # the degraded path is cached: same shrunk Mesh object per step
    assert elastic.effective_mesh(mesh8) is eff


def test_effective_mesh_refuses_multi_axis_worlds(mesh2x4):
    """Quarantined PEs are flattened world indices; on a multi-axis mesh
    they don't name an axis position — excising the wrong device column
    must be impossible."""
    tdt_config.update(elastic=True)
    assert elastic.effective_mesh(mesh2x4) is mesh2x4
    elastic.quarantine(5, reason="test")
    with pytest.raises(ValueError, match="1-D worlds"):
        elastic.effective_mesh(mesh2x4)


# ---------------------------------------------------------------------------
# Host-level arc: the production retry/attribution/shrink/probe paths with
# the in-kernel wait simulated through the real diag-collection machinery
# ---------------------------------------------------------------------------

def _fake_straggler_entry(mesh, family):
    """A jit_shard_map op entry whose traced fn consults the armed
    FaultPlan (exactly like the real injector: trace-time, healed plans
    vanish via the cache token) and offers a synthetic timeout diagnostic
    naming every PE except the straggler as a victim."""
    from triton_dist_tpu.resilience import faults

    def fn(x):
        plan = faults.active_plan(family)
        if plan is not None:
            me = jax.lax.axis_index("tp")
            victim = me != plan.pe
            row = jnp.zeros((R.DIAG_LEN,), jnp.int32)
            row = row.at[R.F_STATUS].set(
                jnp.where(victim, R.STATUS_TIMEOUT, R.STATUS_OK).astype(jnp.int32)
            )
            row = row.at[R.F_FAMILY].set(R.family_code_for(family))
            row = row.at[R.F_PE].set(me.astype(jnp.int32))
            row = row.at[R.F_KIND].set(R.KIND_BARRIER)
            row = row.at[R.F_EXPECTED].set(1)
            row = row.at[R.F_BUDGET].set(
                tdt_config.get_config().timeout_iters
            )
            watchdog.offer(row)
        return x * 2

    return ops_common.jit_shard_map(fn, mesh, P("tp"), P("tp"), key=(family,))


def test_arc_transient_timeout_retried_and_recovered(mesh4):
    """A one-burst fault (max_triggers=1): the first attempt times out,
    the backoff outlives the fault, the retry succeeds. No quarantine."""
    clock = retry.FakeClock()
    retry.set_clock(clock)
    policy = retry.RetryPolicy(max_attempts=3, base_delay_s=0.05, jitter=0.25,
                               seed=5)
    tdt_config.update(
        timeout_iters=7, retry_policy=policy, elastic=True,
        suspect_threshold=2,
        fault_plan=FaultPlan("drop_signal", pe=1, max_triggers=1),
    )
    x = jnp.arange(16, dtype=jnp.float32).reshape(8, 2)
    out = _fake_straggler_entry(mesh4, "fakearc_transient")(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x) * 2)
    snap = health.snapshot()
    assert snap["counters"]["fakearc_transient:retry"] == 1
    assert snap["counters"]["fakearc_transient:recovery"] == 1
    assert "fakearc_transient:timeout" not in snap["counters"]
    # exactly the first scheduled backoff was slept
    assert tuple(clock.sleeps) == policy.delays("fakearc_transient")[:1]
    # one strike marked the peer suspect; the clean retry decayed it
    assert elastic.state(1) == elastic.HEALTHY
    assert health.is_healthy()


def test_arc_persistent_straggler_quarantine_shrink_readmit(mesh4):
    """The full elastic arc on the production host paths: persistent
    straggler → retries exhaust → PE quarantined → shrunk-world collective
    bit-identical to the golden at reduced world size → probation probe →
    PE re-admitted → full world again."""
    from triton_dist_tpu.ops.allgather import all_gather_op

    clock = retry.FakeClock()
    retry.set_clock(clock)
    policy = retry.RetryPolicy(max_attempts=3, base_delay_s=0.05, jitter=0.0)
    tdt_config.update(
        timeout_iters=7, retry_policy=policy, elastic=True,
        suspect_threshold=2, probation_probes=1,
        fault_plan=FaultPlan("drop_signal", pe=1),  # persistent: never heals
    )
    x = jnp.arange(16, dtype=jnp.float32).reshape(8, 2)
    entry = _fake_straggler_entry(mesh4, "fakearc_persistent")
    with pytest.raises(resilience.DistTimeoutError) as ei:
        entry(x)
    assert ei.value.world_size == 4
    # every attempt struck the silent peer; exhaustion found it quarantined
    assert elastic.state(1) == elastic.QUARANTINED
    snap = health.snapshot()
    assert snap["counters"]["fakearc_persistent:retry"] == 2
    assert snap["counters"]["fakearc_persistent:timeout"] == 1
    assert snap["counters"]["pe1:pe_quarantine"] == 1
    assert len(clock.sleeps) == 2
    # interpret mode: the family pin was released (the world shrinks; no
    # device residue exists), so the rebuilt world is not stuck on golden
    assert health.short_circuited("fakearc_persistent") is None

    # --- shrunk world: 3 survivors, collectives still bit-correct -------
    shrunk = elastic.effective_mesh(mesh4)
    assert shrunk.devices.shape == (3,)
    tdt_config.update(fault_plan=None)  # the sick PE is out of the world
    x2 = jnp.arange(12 * 4, dtype=jnp.float32).reshape(12, 4)
    out = all_gather_op(x2, shrunk)
    assert np.array_equal(np.asarray(out), np.asarray(x2)), (
        "shrunk-world allgather must be bit-identical to the golden"
    )

    # --- probation: a clean world barrier re-admits the PE --------------
    states = elastic.probe_quarantined(mesh4)
    assert states == {1: elastic.HEALTHY}
    assert health.snapshot()["counters"]["pe1:pe_readmit"] == 1
    assert elastic.effective_mesh(mesh4) is mesh4
    out = all_gather_op(x, mesh4)
    assert np.array_equal(np.asarray(out), np.asarray(x))


def test_arc_unattributable_timeout_never_quarantines(mesh4):
    """Every PE tripping (fabric-wide failure) must not quarantine anyone:
    shrinking the world around a healthy peer is worse than staying loud."""
    clock = retry.FakeClock()
    retry.set_clock(clock)
    tdt_config.update(
        timeout_iters=7,
        retry_policy=retry.RetryPolicy(max_attempts=2, jitter=0.0),
        elastic=True, suspect_threshold=1,
        fault_plan=FaultPlan("drop_signal", pe=-1),  # afflict every PE
    )

    def fn(x):
        from triton_dist_tpu.resilience import faults

        plan = faults.active_plan("fakearc_fabric")
        if plan is not None:
            me = jax.lax.axis_index("tp")
            row = jnp.zeros((R.DIAG_LEN,), jnp.int32)
            row = row.at[R.F_STATUS].set(R.STATUS_TIMEOUT)
            row = row.at[R.F_PE].set(me.astype(jnp.int32))
            watchdog.offer(row)
        return x

    entry = ops_common.jit_shard_map(
        fn, mesh4, P("tp"), P("tp"), key=("fakearc_fabric",)
    )
    with pytest.raises(resilience.DistTimeoutError):
        entry(jnp.zeros((8, 2), jnp.float32))
    assert elastic.quarantined_pes() == ()
    assert elastic.peer_states() == {}


def test_stored_entry_wrapper_sees_healed_plan(mesh4):
    """Serving code stores the jit_shard_map wrapper once; after a bounded
    fault heals, the stored wrapper must run the clean program (resolved
    per call, not at wrap time) — even on the single-attempt path."""
    tdt_config.update(
        timeout_iters=7, raise_on_timeout=False,
        fault_plan=FaultPlan("drop_signal", pe=1, max_triggers=1),
    )
    assert tdt_config.get_config().retry_policy is None
    entry = _fake_straggler_entry(mesh4, "fakearc_stored")
    x = jnp.arange(8 * 2, dtype=jnp.float32).reshape(8, 2)
    out1 = np.asarray(entry(x))
    assert np.isnan(out1).any(), "first call is poisoned by the fault"
    # the timeout pinned the family; a recovered serving loop clears it
    health.clear_short_circuit("fakearc_stored")
    out2 = np.asarray(entry(x))
    assert np.array_equal(out2, np.asarray(x) * 2), (
        "healed plan must retrace the clean program through the stored "
        "wrapper"
    )


def test_donating_entries_never_retry_in_place(mesh4):
    """Donated inputs are deleted by the first invocation: a timed-out
    donating entry must escalate, not relaunch over freed buffers."""
    clock = retry.FakeClock()
    retry.set_clock(clock)
    tdt_config.update(
        timeout_iters=7, elastic=True,
        retry_policy=retry.RetryPolicy(max_attempts=3, jitter=0.0),
        fault_plan=FaultPlan("drop_signal", pe=1),
    )
    from triton_dist_tpu.resilience import faults

    def fn(x):
        plan = faults.active_plan("fakearc_donate")
        if plan is not None:
            me = jax.lax.axis_index("tp")
            row = jnp.zeros((R.DIAG_LEN,), jnp.int32)
            row = row.at[R.F_STATUS].set(
                jnp.where(me != plan.pe, R.STATUS_TIMEOUT,
                          R.STATUS_OK).astype(jnp.int32)
            )
            row = row.at[R.F_PE].set(me.astype(jnp.int32))
            watchdog.offer(row)
        return x + 1

    entry = ops_common.jit_shard_map(
        fn, mesh4, P("tp"), P("tp"), key=("fakearc_donate",),
        donate_argnums=(0,),
    )
    with pytest.raises(resilience.DistTimeoutError):
        entry(jnp.zeros((8, 2), jnp.float32))
    assert clock.sleeps == [], "no in-place retry over donated buffers"
    assert "fakearc_donate:retry" not in health.snapshot()["counters"]


# ---------------------------------------------------------------------------
# ElasticStep layer wrapper
# ---------------------------------------------------------------------------

def test_elastic_step_tracks_surviving_world(mesh8):
    from triton_dist_tpu.layers import ElasticStep

    tdt_config.update(elastic=True)
    built = []

    def build(mesh):
        built.append(mesh.devices.shape[0])
        return lambda v: v + mesh.devices.shape[0]

    step = ElasticStep(build=build, mesh=mesh8)
    assert step.world_size == 8
    assert step(1) == 9 and step(2) == 10
    assert built == [8], "healthy path builds once"
    elastic.quarantine(3, reason="test")
    assert step.world_size == 7
    assert step(1) == 8
    assert built == [8, 7], "shrunk world builds its own step"
    # probe (stubbed via elastic) re-admits; the full-world step is cached
    tdt_config.update(probation_probes=1)
    elastic.probe_quarantined(mesh8, probe=lambda: True)
    assert step.world_size == 8
    assert step(1) == 9
    assert built == [8, 7]


def test_elastic_step_retries_transient_failures(mesh4):
    from triton_dist_tpu.layers import ElasticStep
    from triton_dist_tpu.resilience.records import DistTimeoutError

    clock = retry.FakeClock()
    retry.set_clock(clock)
    tdt_config.update(
        elastic=True,
        retry_policy=retry.RetryPolicy(max_attempts=2, jitter=0.0),
    )
    calls = {"n": 0}

    def build(mesh):
        def fn(v):
            calls["n"] += 1
            if calls["n"] == 1:
                raise DistTimeoutError("step_fam", _recs([0, 1, 3]),
                                       world_size=4)
            return v

        return fn

    step = ElasticStep(build=build, mesh=mesh4, family="step_fam")
    assert step(5) == 5
    assert calls["n"] == 2
    assert health.snapshot()["counters"]["step_fam:retry"] == 1
    assert elastic.state(2) == elastic.SUSPECT, "failed attempt struck pe2"


# ---------------------------------------------------------------------------
# Zero-overhead when disabled
# ---------------------------------------------------------------------------

def test_disabled_config_takes_preexisting_paths(mesh4, monkeypatch):
    """With retry/elastic off (the defaults), op entries must not touch the
    elastic layer at all: the unarmed jit_shard_map result is the cached
    jitted program itself, and the armed path never consults retry/elastic."""
    cfg = tdt_config.get_config()
    assert cfg.retry_policy is None and cfg.elastic is False

    f1 = ops_common.jit_shard_map(
        lambda x: x, mesh4, P("tp"), P("tp"), key=("zero_overhead_probe",)
    )
    f2 = ops_common.jit_shard_map(
        lambda x: x, mesh4, P("tp"), P("tp"), key=("zero_overhead_probe",)
    )
    assert f1 is f2, "unarmed entries return the cached jitted program"

    def bomb(*a, **k):
        raise AssertionError("elastic/retry consulted on the disabled path")

    monkeypatch.setattr(elastic, "note_timeout_records", bomb)
    monkeypatch.setattr(elastic, "note_clean_step", bomb)
    monkeypatch.setattr(retry, "get_clock", bomb)
    tdt_config.update(timeout_iters=7)
    entry = ops_common.jit_shard_map(
        lambda x: x + 1, mesh4, P("tp"), P("tp"), key=("zero_overhead_armed",)
    )
    x = jnp.ones((8, 2), jnp.float32)
    np.testing.assert_array_equal(np.asarray(entry(x)), np.asarray(x) + 1)


# ---------------------------------------------------------------------------
# Live arc (Mosaic TPU interpreter): real fused kernels, real injector
# ---------------------------------------------------------------------------

def test_elastic_arc_live(mesh4):
    """ISSUE 2 acceptance: the full arc against the real fused allgather —
    persistent straggler PE times the step out, retries back off and
    exhaust, the PE is quarantined, the shrunk-world fused collective is
    bit-identical to the golden at reduced world size, and a clean barrier
    probe re-admits the PE."""
    from triton_dist_tpu.ops.allgather import all_gather_op

    clock = retry.FakeClock()
    retry.set_clock(clock)
    tdt_config.update(
        timeout_iters=300, raise_on_timeout=True,
        retry_policy=retry.RetryPolicy(max_attempts=2, jitter=0.0),
        elastic=True, suspect_threshold=2, probation_probes=1,
        fault_plan=FaultPlan.persistent_straggler(1, delay_iters=50_000),
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 128), jnp.float32)
    with pytest.raises(resilience.DistTimeoutError):
        all_gather_op(x, mesh4)
    assert elastic.state(1) == elastic.QUARANTINED
    snap = health.snapshot()
    assert snap["counters"]["all_gather:retry"] == 1
    assert snap["counters"]["pe1:pe_quarantine"] == 1

    # the straggling device is out of the rebuilt world; the injector's
    # logical PE index would otherwise re-target a renumbered survivor
    tdt_config.update(fault_plan=None)
    shrunk = elastic.effective_mesh(mesh4)
    assert shrunk.devices.shape == (3,)
    x2 = jax.random.normal(jax.random.PRNGKey(1), (6, 128), jnp.float32)
    out = all_gather_op(x2, shrunk)
    assert np.array_equal(np.asarray(out), np.asarray(x2)), (
        "shrunk-world fused allgather must be bit-identical to the golden"
    )
    assert not health.degraded_families(), (
        "the shrunk world must run the fused path, not the golden fallback"
    )

    # probation: the real watchdogged barrier over the full world
    assert elastic.probe_quarantined(mesh4) == {1: elastic.HEALTHY}
    assert elastic.effective_mesh(mesh4) is mesh4
    out = all_gather_op(x, mesh4)
    assert np.array_equal(np.asarray(out), np.asarray(x))
