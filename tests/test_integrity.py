"""Data-integrity layer (ISSUE 8; resilience/integrity.py,
docs/resilience.md "Data integrity").

Acceptance contract: injected payload corruption
(bitflip/torn_chunk/stale_read/nan_inject) is DETECTED — never silently
consumed — with the corrupt PE named; the recovery ladder (detect →
bounded retry, counted separately from timeouts → golden-XLA fallback →
PE quarantine) reaches a bit-exact golden result; the serving engine
loses exactly the poisoned request while survivors' token streams stay
byte-identical; and with integrity checks armed but no fault plan,
detection is observation-only (clean paths bit-exact, health clean).

Tier structure (the test_chaos.py convention):

- **host tier** (runs everywhere): checksum/corruption algebra, config
  validation, record codec, the guard-layer ladder with fabricated
  corrupt primaries, retry classification, elastic attribution,
  train-step skip semantics, and the serving cells (fabricated faults
  through the production engine paths, FakeClock).
- **interpreter tier** (needs the Mosaic TPU interpreter): live payload
  injection against the chunked ring kernels with the per-chunk canary —
  the in-kernel detection path.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_dist_tpu import config as tdt_config
from triton_dist_tpu import resilience
from triton_dist_tpu.resilience import (
    FaultPlan,
    IntegrityConfig,
    IntegrityError,
    elastic,
    health,
    integrity,
    retry,
)
from triton_dist_tpu.resilience import faults as F
from triton_dist_tpu.resilience import records as R
from triton_dist_tpu.resilience.guard import guarded_call
from triton_dist_tpu.resilience.records import DistTimeoutError


TIMEOUT_ITERS = 300


@pytest.fixture(autouse=True)
def _restore_config():
    cfg = tdt_config.get_config()
    snap = (cfg.timeout_iters, cfg.fault_plan, cfg.raise_on_timeout,
            cfg.fallback_to_xla, cfg.retry_policy, cfg.elastic,
            cfg.suspect_threshold, cfg.probation_probes, cfg.integrity)
    yield
    tdt_config.update(
        timeout_iters=snap[0], fault_plan=snap[1], raise_on_timeout=snap[2],
        fallback_to_xla=snap[3], retry_policy=snap[4], elastic=snap[5],
        suspect_threshold=snap[6], probation_probes=snap[7],
        integrity=snap[8],
    )
    retry.set_clock(None)


# ---------------------------------------------------------------------------
# Host tier: checksum / corruption algebra
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kind", F.PAYLOAD_KINDS)
def test_payload_checksum_detects_each_kind(kind, dtype):
    """Every payload-corruption kind moves the canary checksum (the
    detection primitive); identical bytes fold identically."""
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16)).astype(dtype)
    c0 = int(integrity.payload_checksum(x))
    assert c0 == int(integrity.payload_checksum(jnp.array(x))), "deterministic"
    assert 0 <= c0 < integrity.CANARY_MOD
    xc = F._corrupt_payload(x, kind)
    assert int(integrity.payload_checksum(xc)) != c0, kind
    # the corruption is real, not just a checksum artifact
    assert not np.array_equal(
        np.asarray(x, np.float32), np.asarray(xc, np.float32),
        equal_nan=True,
    )


def test_corrupt_payload_semantics():
    x = jnp.ones((8, 4), jnp.float32)
    assert np.all(np.asarray(F._corrupt_payload(x, "stale_read")) == 0)
    torn = np.asarray(F._corrupt_payload(x, "torn_chunk"))
    np.testing.assert_array_equal(torn[:4], 1.0)   # first half landed
    np.testing.assert_array_equal(torn[4:], 0.0)   # tail stale
    nan = np.asarray(F._corrupt_payload(x, "nan_inject"))
    assert np.isnan(nan[0, 0]) and np.isfinite(nan[1:]).all()
    flip = np.asarray(F._corrupt_payload(x, "bitflip"))
    assert flip[0, 0] != 1.0 and np.all(flip.reshape(-1)[1:] == 1.0)


def test_fault_plan_payload_kinds_validate():
    for kind in F.PAYLOAD_KINDS:
        tdt_config.update(fault_plan=FaultPlan(kind, pe=1))
        assert tdt_config.get_config().fault_plan.kind == kind
    tdt_config.update(fault_plan=None)
    # the signal-kind composition rules are unchanged
    with pytest.raises(ValueError, match="family"):
        FaultPlan("bitflip", max_triggers=1, family="x").validate()
    # payload kinds never alter signal increments (apply_signal_fault is
    # the signal-kind injector only)
    tdt_config.update(timeout_iters=TIMEOUT_ITERS,
                      fault_plan=FaultPlan("nan_inject", pe=-1))
    from triton_dist_tpu.resilience import watchdog

    with watchdog.kernel_scope(None, "integrity_test_family") as scope:
        scope.pe = jnp.int32(0)
        out = F.apply_signal_fault(jnp.int32(1), scope.pe)
    assert int(out) == 1


def test_integrity_config_validation():
    with pytest.raises(ValueError, match="retries"):
        IntegrityConfig(retries=-1).validate()
    with pytest.raises(ValueError, match="max_abs"):
        IntegrityConfig(max_abs=0.0).validate()
    with pytest.raises(ValueError, match="IntegrityConfig"):
        tdt_config.update(integrity="yes please")
    tdt_config.update(integrity=IntegrityConfig(max_abs=1e6, retries=2))
    assert integrity.output_checks_enabled()
    assert not integrity.canary_enabled()
    tdt_config.update(integrity=None)
    assert not integrity.output_checks_enabled()


def test_decode_record_integrity_kind():
    code = R.family_code_for("integrity_codec_family")
    row = [0] * R.DIAG_LEN
    row[R.F_STATUS] = R.STATUS_INTEGRITY
    row[R.F_FAMILY] = code
    row[R.F_PE] = 3
    row[R.F_SITE] = 2
    row[R.F_KIND] = R.KIND_INTEGRITY
    row[R.F_EXPECTED] = 17
    row[R.F_OBSERVED] = 99
    rec = R.decode_record(row)
    assert rec["status"] == "integrity"
    assert rec["kind"] == "integrity_check"
    assert rec["pe"] == 3
    # decode_diag surfaces it like any non-OK record
    diag = np.zeros((4, R.DIAG_LEN), np.int32)
    diag[3] = row
    recs = R.decode_diag(diag)
    assert len(recs) == 1 and recs[0]["status"] == "integrity"
    err = IntegrityError("fam", integrity.DET_CANARY, records=recs,
                         world_size=4)
    assert "pe 3" in str(err) and "canary" in str(err)


# ---------------------------------------------------------------------------
# Host tier: output guards + the recovery ladder (fabricated primaries)
# ---------------------------------------------------------------------------

def test_check_result_detectors_and_happy_path():
    tdt_config.update(integrity=IntegrityConfig(max_abs=100.0))
    with pytest.raises(IntegrityError) as ei:
        integrity.check_result("fam", {"a": jnp.array([1.0, jnp.nan])})
    assert ei.value.detector == "nonfinite"
    with pytest.raises(IntegrityError) as ei:
        integrity.check_result("fam", jnp.array([1e4]))
    assert ei.value.detector == "envelope"
    # int leaves (split tables, token ids) are never envelope-checked
    out = (jnp.arange(5, dtype=jnp.int32) * 10**6, jnp.array([2.0]))
    got = integrity.check_result("fam", out)
    assert got is out, "observation-only: the happy path returns the "\
        "object untouched"


def test_guard_ladder_retry_then_recovery():
    """Transient corruption (one bad output, then clean) is absorbed by
    the bounded integrity-retry rung — counted separately from timeouts,
    golden fallback never consulted."""
    tdt_config.update(integrity=IntegrityConfig(retries=2))
    calls = {"n": 0}

    def primary():
        calls["n"] += 1
        if calls["n"] == 1:
            return jnp.array([jnp.inf])
        return jnp.array([4.0])

    def golden():
        raise AssertionError("fallback must not run: retry recovered")

    out = guarded_call("ladder_fam", primary, golden)
    assert float(out[0]) == 4.0 and calls["n"] == 2
    counters = health.counters()
    assert counters[("ladder_fam", health.INTEGRITY)] == 1
    assert counters[("ladder_fam", health.INTEGRITY_RETRY)] == 1
    assert counters[("ladder_fam", health.RECOVERY)] == 1
    assert ("ladder_fam", health.RETRY) not in counters, (
        "corruption must not be counted as a timeout retry"
    )
    assert ("ladder_fam", health.DOWNGRADE) not in counters


def test_guard_ladder_falls_back_to_golden_bit_exact():
    """Persistent corruption exhausts the retries and lands on the golden
    rung — output bit-exact to the golden path, downgrade recorded."""
    tdt_config.update(integrity=IntegrityConfig(retries=1))
    golden_val = jax.random.normal(jax.random.PRNGKey(3), (4, 4))
    calls = {"n": 0}

    def primary():
        calls["n"] += 1
        return golden_val.at[0, 0].set(jnp.nan)

    out = guarded_call("ladder_fb", primary, lambda: golden_val)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(golden_val))
    assert calls["n"] == 2, "initial attempt + 1 bounded retry"
    counters = health.counters()
    assert counters[("ladder_fb", health.INTEGRITY)] == 2
    assert counters[("ladder_fb", health.INTEGRITY_RETRY)] == 1
    assert counters[("ladder_fb", health.DOWNGRADE)] == 1
    assert health.corrupt_families() == {"ladder_fb"}
    assert not health.is_healthy()
    # NOT pinned: corruption leaves no semaphore residue; the next call
    # re-attempts the fused path
    assert health.short_circuited("ladder_fb") is None


def test_guard_ladder_corrupt_golden_stays_loud():
    """A corrupt GOLDEN result means the data itself is poisoned — no
    lower rung exists; the ladder must raise, not return it."""
    tdt_config.update(integrity=IntegrityConfig(retries=0))
    bad = jnp.array([jnp.nan])
    with pytest.raises(IntegrityError):
        guarded_call("ladder_bad_gold", lambda: bad, lambda: bad)


def test_guard_no_fallback_still_detects():
    tdt_config.update(integrity=IntegrityConfig())
    with pytest.raises(IntegrityError):
        guarded_call("no_gold", lambda: jnp.array([jnp.nan]), None)
    # the detection lands in the registry even on ladder-less postures
    # (no-fallback here; same for fallback_to_xla=False and the pinned
    # golden branch — recording happens at the check_result raise site)
    assert health.counters()[("no_gold", health.INTEGRITY)] == 1
    tdt_config.update(fallback_to_xla=False)
    with pytest.raises(IntegrityError):
        guarded_call("loud_gold", lambda: jnp.array([jnp.nan]),
                     lambda: jnp.array([1.0]))
    tdt_config.update(fallback_to_xla=True)
    assert health.counters()[("loud_gold", health.INTEGRITY)] == 1
    assert not health.is_healthy()


def test_observation_only_when_disarmed_and_on_clean_paths():
    """config.integrity=None keeps every path byte-identical and silent;
    armed-but-clean records nothing."""
    val = jax.random.normal(jax.random.PRNGKey(4), (8,))
    out1 = guarded_call("clean_fam", lambda: val, lambda: val * 0)
    tdt_config.update(integrity=IntegrityConfig(max_abs=1e6))
    out2 = guarded_call("clean_fam", lambda: val, lambda: val * 0)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert health.is_healthy() and not health.counters()


def test_classify_corrupt_separately():
    err = IntegrityError("f", integrity.DET_CANARY)
    assert retry.classify(err) == retry.CORRUPT
    wrapped = RuntimeError("step failed")
    wrapped.__cause__ = err
    assert retry.classify(wrapped) == retry.CORRUPT
    # a timeout anywhere wins (louder event, its own arc)
    both = RuntimeError("x")
    both.__cause__ = DistTimeoutError("f", [])
    both.__context__ = err
    assert retry.classify(both) == retry.TRANSIENT
    assert retry.classify(ValueError("shape")) == retry.DETERMINISTIC


def test_call_with_retry_counts_corruption_separately():
    tdt_config.update(retry_policy=retry.RetryPolicy(
        max_attempts=3, base_delay_s=0.01, jitter=0.0))
    clock = retry.FakeClock()
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        if calls["n"] < 3:
            raise IntegrityError("rfam", integrity.DET_NONFINITE)
        return 7

    assert retry.call_with_retry("rfam", fn, clock=clock) == 7
    counters = health.counters()
    assert counters[("rfam", health.INTEGRITY_RETRY)] == 2
    assert ("rfam", health.RETRY) not in counters
    assert counters[("rfam", health.RECOVERY)] == 1
    assert len(clock.sleeps) == 2


@pytest.mark.chaos
def test_integrity_strikes_quarantine_pe():
    """The elastic rung of the ladder: integrity records strike the named
    PE DIRECTLY (victim == culprit under the landing-site fault model),
    reaching quarantine through the PR 2 state machine with a
    corruption-naming reason."""
    tdt_config.update(elastic=True, suspect_threshold=2)
    recs = [{"pe": 2, "kind": "integrity_check", "site": 0,
             "status": "integrity", "expected": 5, "observed": 9,
             "budget": 0}]
    err = IntegrityError("qfam", integrity.DET_CANARY, records=recs,
                         world_size=4)
    assert elastic.note_integrity_exc(err) == 2
    assert elastic.state(2) == elastic.SUSPECT
    assert elastic.note_integrity_exc(RuntimeError("no integrity")) is None
    wrapped = RuntimeError("step")
    wrapped.__cause__ = err
    assert elastic.note_integrity_exc(wrapped) == 2
    assert elastic.state(2) == elastic.QUARANTINED
    ev = health.events(health.PE_QUARANTINE)
    assert ev and "corruption" in ev[-1].reason
    # host-tier detections carry no records: no strike without evidence
    assert elastic.note_integrity_exc(
        IntegrityError("qfam", integrity.DET_NONFINITE)
    ) is None


@pytest.mark.chaos
def test_one_detection_one_strike():
    """A single detection whose raise site already struck its PE (the
    jit_shard_map canary convention: record + strike, then mark) must NOT
    be struck again by the recovery ladder — one corruption costs one
    strike, so the healthy → suspect → quarantined ladder is preserved at
    the default threshold."""
    tdt_config.update(elastic=True, suspect_threshold=2,
                      integrity=IntegrityConfig(retries=0))
    recs = [{"pe": 1, "kind": "integrity_check", "site": 0,
             "status": "integrity", "expected": 3, "observed": 4,
             "budget": 0}]

    def primary():
        # what jit_shard_map._raise_integrity does: record, strike, mark
        err = IntegrityError("one_strike", integrity.DET_CANARY,
                             records=recs, world_size=4)
        health.record_integrity("one_strike", err)
        elastic.note_integrity_records(recs, 4, family="one_strike")
        err._tdt_recorded = True
        raise err

    out = guarded_call("one_strike", primary, lambda: jnp.array([1.0]))
    assert float(out[0]) == 1.0
    assert elastic.state(1) == elastic.SUSPECT, (
        "one detection = one strike; quarantine needs threshold strikes"
    )
    assert health.counters()[("one_strike", health.INTEGRITY)] == 1


def test_timeout_mid_ladder_takes_guard_classification():
    """A watchdog trip on a RETRY attempt of the corruption ladder gets
    the same treatment as a first-attempt trip: loud raise + family
    quarantine pin (not an unhandled escape past the guard)."""
    tdt_config.update(integrity=IntegrityConfig(retries=2))
    calls = {"n": 0}

    def primary():
        calls["n"] += 1
        if calls["n"] == 1:
            return jnp.array([jnp.nan])          # detection -> ladder
        raise DistTimeoutError("mid_ladder", _int_recs_none(), world_size=2)

    def _int_recs_none():
        return [{"pe": 0, "kind": "barrier_all", "site": 0,
                 "status": "timeout", "expected": 1, "observed": 0,
                 "budget": 10}]

    with pytest.raises(DistTimeoutError):
        guarded_call("mid_ladder", primary, lambda: jnp.array([1.0]))
    assert health.short_circuited("mid_ladder") is not None, (
        "the mid-ladder timeout must quarantine-pin the family exactly "
        "like a first-attempt timeout"
    )


# ---------------------------------------------------------------------------
# Host tier: train-step skip semantics (grads containment)
# ---------------------------------------------------------------------------

def _tiny_cfg(**over):
    from triton_dist_tpu.models.tp_transformer import TransformerConfig
    from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig
    from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig

    base = dict(
        vocab=32, hidden=32, ffn=64, n_layers=1, n_q_heads=4, n_kv_heads=2,
        head_dim=8, batch=2, seq=8,
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16),
    )
    base.update(over)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def _mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("tp",))


def _train_step_j(cfg, mesh, skip):
    from triton_dist_tpu.models.tp_transformer import (
        TPTransformer, param_specs, train_step,
    )
    from triton_dist_tpu.ops.common import _shard_map

    model = TPTransformer(cfg)
    specs = param_specs(cfg)

    def step(t, y, p):
        return train_step(model, p, t, y, lr=1e-1, dp_axis=None,
                          skip_nonfinite=skip)

    return jax.jit(_shard_map(
        step, mesh, (P("tp"), P(), specs),
        (specs, P(), P()) if skip else (specs, P()),
    )), specs


def test_train_step_skip_nonfinite(_mesh1):
    """ISSUE 8 containment: a non-finite grad step is DROPPED whole —
    params bit-identical, skipped=1 — while a clean step under the flag
    applies the EXACT update of the ungated step."""
    from triton_dist_tpu.models.tp_transformer import init_params

    cfg = _tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    m = cfg.batch * cfg.seq
    tokens = jax.random.randint(jax.random.PRNGKey(1), (m,), 0, cfg.vocab,
                                jnp.int32)
    targets = jax.random.randint(jax.random.PRNGKey(2), (m,), 0, cfg.vocab,
                                 jnp.int32)
    put = lambda p, s: jax.tree.map(  # noqa: E731
        lambda x, sp: jax.device_put(x, NamedSharding(_mesh1, sp)), p, s
    )
    gated, specs = _train_step_j(cfg, _mesh1, skip=True)
    plain, _ = _train_step_j(cfg, _mesh1, skip=False)

    # clean step: gated == ungated, bit for bit; skipped == 0
    p_sh = put(params, specs)
    p_gated, loss_g, skipped = gated(tokens, targets, p_sh)
    p_plain, loss_p = plain(tokens, targets, put(params, specs))
    assert int(skipped) == 0
    assert float(loss_g) == float(loss_p)
    for a, b in zip(jax.tree.leaves(p_gated), jax.tree.leaves(p_plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # poisoned step (NaN weight -> NaN loss/grads): dropped whole
    bad = jax.tree.map(lambda x: x, params)
    bad["lm_head"] = bad["lm_head"].at[0, 0].set(jnp.nan)
    p_out, loss_bad, skipped = gated(tokens, targets, put(bad, specs))
    assert int(skipped) == 1
    assert not np.isfinite(float(loss_bad))
    for a, b in zip(jax.tree.leaves(p_out), jax.tree.leaves(bad)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg="a skipped step must leave params untouched",
        )
    # the host-side counter hook
    integrity.record_skip_step()
    assert health.counters()[("train_step", health.SKIP_STEP)] == 1
    assert not health.is_healthy()


def test_train_step_skip_with_optimizer_state(_mesh1):
    """The optax path: a dropped step leaves the OPTIMIZER STATE untouched
    too (adam moments poisoned by one NaN step would corrupt every later
    step — the whole point of the containment)."""
    optax = pytest.importorskip("optax")
    from triton_dist_tpu.models.tp_transformer import (
        TPTransformer, init_params, opt_state_specs, param_specs, train_step,
    )
    from triton_dist_tpu.ops.common import _shard_map

    cfg = _tiny_cfg()
    model = TPTransformer(cfg)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)
    specs = param_specs(cfg)
    os_specs = opt_state_specs(opt, params, specs)
    m = cfg.batch * cfg.seq
    tokens = jax.random.randint(jax.random.PRNGKey(1), (m,), 0, cfg.vocab,
                                jnp.int32)
    targets = jax.random.randint(jax.random.PRNGKey(2), (m,), 0, cfg.vocab,
                                 jnp.int32)

    def step(t, y, p, s):
        return train_step(model, p, t, y, dp_axis=None, opt=opt,
                          opt_state=s, skip_nonfinite=True)

    stepj = jax.jit(_shard_map(
        step, _mesh1, (P("tp"), P(), specs, os_specs),
        (specs, os_specs, P(), P()),
    ))
    put = lambda p, s: jax.tree.map(  # noqa: E731
        lambda x, sp: jax.device_put(x, NamedSharding(_mesh1, sp)), p, s
    )
    bad = dict(params)
    bad["lm_head"] = bad["lm_head"].at[0, 0].set(jnp.nan)
    p_out, s_out, _, skipped = stepj(
        tokens, targets, put(bad, specs), put(opt_state, os_specs)
    )
    assert int(skipped) == 1
    for a, b in zip(jax.tree.leaves(s_out), jax.tree.leaves(opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Serving cells (chaos tier: production engine paths, fabricated faults)
# ---------------------------------------------------------------------------

def _engine(cfg, params, mesh, **serving_over):
    from triton_dist_tpu.serving import ServingConfig, ServingEngine

    clock = retry.FakeClock()
    retry.set_clock(clock)
    return ServingEngine(
        cfg, params, mesh, s_max=16, clock=clock,
        serving=ServingConfig(virtual_step_s=0.01, **serving_over),
    )


def _requests(cfg, shapes, seed=5):
    from triton_dist_tpu.models.decode import Request

    key = jax.random.PRNGKey(seed)
    out = []
    for i, (plen, mx) in enumerate(shapes):
        toks = [int(t) for t in np.asarray(jax.random.randint(
            jax.random.fold_in(key, i), (plen,), 0, cfg.vocab, jnp.int32
        ))]
        out.append(Request(toks, max_new_tokens=mx, uid=i))
    return out


@pytest.fixture(scope="module")
def tiny1():
    from triton_dist_tpu.models import init_params

    cfg = _tiny_cfg()
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def tiny4():
    from triton_dist_tpu.models import init_params

    cfg = _tiny_cfg(n_kv_heads=4)
    return cfg, init_params(jax.random.PRNGKey(1), cfg)


@pytest.fixture(scope="module")
def _mesh4():
    return Mesh(np.array(jax.devices()[:4]), ("tp",))


@pytest.mark.chaos
def test_serving_poison_quarantine_survivors_byte_identical(tiny1, _mesh1):
    """ISSUE 8 acceptance: a NaN logit row evicts and typed-rejects
    exactly THAT slot's request; the engine keeps serving and the
    survivors' token streams are byte-identical to a fault-free run."""
    from triton_dist_tpu.serving import Finished, Poisoned

    cfg, params = tiny1
    shapes = [(3, 5), (4, 6), (2, 4)]

    eng = _engine(cfg, params, _mesh1)
    for r in _requests(cfg, shapes):
        eng.submit(r)
    golden = eng.run_until_idle()
    assert all(isinstance(r, Finished) for r in golden.values())

    # poison slot 0's logits on decode call #3 — the uid occupying slot 0
    # becomes the quarantined request (injection wraps the jitted step's
    # host callable; everything downstream is the production path)
    resilience.reset()
    tdt_config.update(integrity=IntegrityConfig())
    eng2 = _engine(cfg, params, _mesh1)
    orig = eng2._batcher._step
    calls = {"n": 0}

    def poisoned_step(params_, cache, tok, pos):
        logits, cache = orig(params_, cache, tok, pos)
        calls["n"] += 1
        if calls["n"] == 3:
            logits = logits.at[0].set(jnp.nan)
        return logits, cache

    eng2._batcher._step = poisoned_step
    for r in _requests(cfg, shapes):
        eng2.submit(r)
    done = eng2.run_until_idle()
    poisoned = {u: r for u, r in done.items() if isinstance(r, Poisoned)}
    survivors = {u: r for u, r in done.items() if isinstance(r, Finished)}
    assert len(poisoned) == 1, "exactly the poisoned request is lost"
    (bad_uid, bad), = poisoned.items()
    assert bad.reason == "non-finite logits"
    for uid, res in survivors.items():
        assert res.tokens == golden[uid].tokens, (
            f"survivor {uid} must stream byte-identically"
        )
    snap = eng2.snapshot()
    assert snap["requests"]["poisoned"] == 1
    assert health.counters()[
        ("continuous_batcher", health.POISONED)
    ] == 1
    assert not health.is_healthy()


@pytest.mark.chaos
def test_a_members_non_finite_prefill_logits_poison_its_slot_only(
        tiny1, _mesh1):
    """A bucket pass that admits two requests (docs/serving.md "The
    admission's discipline") keeps the finite check a member's own: the
    one whose row came back NaN is evicted before a token exists, the
    member beside it in the pass is served what it gets alone."""
    from triton_dist_tpu.models.decode import ContinuousBatcher

    cfg, params = tiny1
    shapes = [(3, 4), (4, 5)]

    def batcher(reqs):
        b = ContinuousBatcher(cfg, params, _mesh1, s_max=16, prefill=True)
        for r in reqs:
            b.submit(r)
        return b

    want = dict(batcher(_requests(cfg, shapes)[1:]).run(max_steps=50))
    b = batcher(_requests(cfg, shapes))
    prog = b._prefill_prog(4)

    def nan_in_row_0(*args):
        cache, last = prog(*args)
        return cache, last.at[0].set(jnp.nan)

    b._prefill_progs[4] = nan_in_row_0
    tdt_config.update(integrity=IntegrityConfig())
    b._admit()
    assert b.prefill_passes_total == 1
    assert b.drain_poisoned() == [(0, [], "non-finite prefill logits")]
    assert [r and r.uid for r in b.slot_req] == [None, 1]
    assert dict(b.run(max_steps=50)) == want
    assert health.counters()[("continuous_batcher", health.POISONED)] == 1


@pytest.mark.chaos
@pytest.mark.parametrize("pools", ["v", "kv"])
def test_serving_nan_kv_stays_in_its_slot(_mesh1, pools):
    """The containment ``_poison_slot`` argues from, held at the KERNEL
    (ISSUE 38): slot 0's pages turn NaN in the pool (not its logits:
    those follow on their own). The others stream byte-identically,
    although slot 0 keeps its length, evicted or not, so the paged decode
    fetches its NaN pages every step, into a buffer whose page slots the
    shorter row after it leaves unfetched (3 live pages under a 4-page
    span, 5 under 8). A NaN v reaches slot 0's logits and exactly that
    request is lost; under a NaN k as well the softmax's sum is NaN and
    the finalize emits zeros for it, so the request finishes, on
    garbage."""
    from triton_dist_tpu.models import init_params
    from triton_dist_tpu.serving import (
        Finished, Poisoned, ServingConfig, ServingEngine,
    )

    cfg = _tiny_cfg(batch=4)
    params = init_params(jax.random.PRNGKey(0), cfg)
    shapes = [(20, 6), (5, 12), (9, 10), (10, 8)]

    def engine():
        clock = retry.FakeClock()
        retry.set_clock(clock)
        return ServingEngine(
            cfg, params, _mesh1, s_max=32, page_size=4, prefill=True,
            clock=clock, serving=ServingConfig(virtual_step_s=0.01))

    eng = engine()
    for r in _requests(cfg, shapes):
        eng.submit(r)
    golden = eng.run_until_idle()
    assert all(isinstance(r, Finished) for r in golden.values())

    resilience.reset()
    tdt_config.update(integrity=IntegrityConfig())
    eng2 = engine()
    orig = eng2._batcher._step
    calls = {"n": 0}

    def step_on_nan_pages(params_, cache, tok, pos):
        calls["n"] += 1
        if calls["n"] == 2:
            pages = np.asarray(cache["block_table"])[0, 0]    # slot 0's
            cache = dict(cache, **{
                x: cache[x].at[:, pages].set(jnp.nan) for x in pools})
        return orig(params_, cache, tok, pos)

    eng2._batcher._step = step_on_nan_pages
    for r in _requests(cfg, shapes):
        eng2.submit(r)
    done = eng2.run_until_idle()
    lost = {u for u, r in done.items() if isinstance(r, Poisoned)}
    assert lost == ({0} if pools == "v" else set())
    assert done[0].tokens != golden[0].tokens
    for uid in (1, 2, 3):
        assert isinstance(done[uid], Finished)
        assert done[uid].tokens == golden[uid].tokens, (
            f"survivor {uid} must stream byte-identically")
    assert eng2.snapshot()["requests"].get("poisoned", 0) == len(lost)


@pytest.mark.chaos
def test_serving_step_integrity_error_rebuilds_and_replays(tiny1, _mesh1,
                                                           monkeypatch):
    """A whole-step IntegrityError (canary/guard tripping INSIDE the
    jitted step) takes the rebuild + prefix-replay arc — no token of the
    corrupt step is consumed, and the final streams are byte-identical to
    an uninterrupted run."""
    from triton_dist_tpu.models.decode import ContinuousBatcher

    cfg, params = tiny1
    shapes = [(3, 5), (2, 4)]
    eng = _engine(cfg, params, _mesh1)
    for r in _requests(cfg, shapes, seed=8):
        eng.submit(r)
    golden = eng.run_until_idle()

    resilience.reset()
    calls = {"n": 0}
    real_step = ContinuousBatcher.step

    def flaky(self):
        calls["n"] += 1
        if calls["n"] == 3:
            raise IntegrityError("batcher_step", integrity.DET_CANARY,
                                 records=[], world_size=1)
        return real_step(self)

    monkeypatch.setattr(ContinuousBatcher, "step", flaky)
    eng2 = _engine(cfg, params, _mesh1)
    for r in _requests(cfg, shapes, seed=8):
        eng2.submit(r)
    done = eng2.run_until_idle()
    assert {u: r.tokens for u, r in done.items()} == {
        u: r.tokens for u, r in golden.items()
    }
    assert eng2.rebuilds == 1
    assert eng2.snapshot()["requests"]["step_integrity"] == 1


@pytest.mark.chaos
def test_serving_stop_drain_races_persistent_straggler(tiny4, _mesh4,
                                                       monkeypatch):
    """ISSUE 8 satellite: ``stop(drain=True)`` racing a persistent
    straggler — the drain must complete EVERY enqueued request on the
    shrunk serviceable mesh (no request lost to the shrink, no deadlock,
    FakeClock arc so it runs everywhere)."""
    from triton_dist_tpu.models.decode import ContinuousBatcher
    from triton_dist_tpu.serving import Finished

    cfg, params = tiny4
    resilience.reset()
    tdt_config.update(elastic=True, suspect_threshold=1, probation_probes=1)

    recs = [{"pe": pe, "kind": "barrier_all", "site": 0, "status": "timeout",
             "expected": 1, "observed": 0, "budget": 10} for pe in (0, 2, 3)]
    calls = {"n": 0}
    real_step = ContinuousBatcher.step

    def flaky(self):
        calls["n"] += 1
        # the straggler keeps tripping until its PE is quarantined and
        # the engine rebuilds on the shrunk mesh (world 4 -> 2: three
        # survivors are model-invalid with 4 kv heads)
        if calls["n"] in (2, 3) and elastic.state(1) != elastic.QUARANTINED:
            raise DistTimeoutError("batcher_step", recs, world_size=4)
        return real_step(self)

    monkeypatch.setattr(ContinuousBatcher, "step", flaky)
    # probe interval huge: the world must NOT regrow mid-drain, proving
    # the drain itself completes on the DEGRADED mesh
    eng = _engine(cfg, params, _mesh4, probe_interval_steps=10_000)
    reqs = _requests(cfg, [(3, 5), (2, 4), (4, 3), (2, 6)], seed=9)
    for r in reqs:
        eng.submit(r)
    eng.stop(drain=True)             # race: drain begins, straggler trips
    done = eng.run_until_idle()
    assert set(done) == {r.uid for r in reqs}, "drain completes EVERYTHING"
    assert all(isinstance(r, Finished) for r in done.values())
    assert eng.world_size == 2, "completed on the shrunk serviceable mesh"
    assert eng.rebuilds >= 1
    assert elastic.state(1) == elastic.QUARANTINED
    assert any(r.resumed for r in done.values()), "prefix replay ran"


# ---------------------------------------------------------------------------
# Interpreter tier: live payload injection against the chunked kernels
# ---------------------------------------------------------------------------

def _mesh2():
    return Mesh(np.array(jax.devices()[:2]), ("tp",))


@pytest.mark.chaos
@pytest.mark.parametrize("kind", F.PAYLOAD_KINDS)
def test_canary_detects_payload_corruption_chunked_allgather(kind):
    """ISSUE 8 acceptance (kernel tier): each payload kind injected into
    the chunked ring allgather's landings is DETECTED by the per-chunk
    canary — the raised IntegrityError's records name the new kind
    ('integrity_check') and the corrupt PE — and the recovery ladder
    (healed plan + bounded retry) reaches a bit-exact result."""
    from triton_dist_tpu.ops.allgather import all_gather_op

    mesh2 = _mesh2()
    x = jax.random.normal(jax.random.PRNGKey(30), (2 * 16, 4), jnp.float32)
    tdt_config.update(
        timeout_iters=TIMEOUT_ITERS,
        integrity=IntegrityConfig(canary=True, retries=0),
        fault_plan=FaultPlan(kind, pe=1),
        raise_on_timeout=True,
    )
    with pytest.raises(IntegrityError) as ei:
        all_gather_op(x, mesh2, method="ring_1d", chunks_per_shard=2)
    assert ei.value.records, "canary must carry decoded records"
    assert {r["kind"] for r in ei.value.records} == {"integrity_check"}
    assert {r["pe"] for r in ei.value.records} == {1}, (
        "the corrupt PE is named directly (victim == culprit)"
    )
    # recovery: the fault heals after one armed launch; the retry ladder
    # then serves the bit-exact clean result
    tdt_config.update(
        fault_plan=FaultPlan(kind, pe=1, max_triggers=1),
        retry_policy=retry.RetryPolicy(max_attempts=2, base_delay_s=0.0,
                                       jitter=0.0),
        integrity=IntegrityConfig(canary=True, retries=1),
    )
    out = all_gather_op(x, mesh2, method="ring_1d", chunks_per_shard=2)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


@pytest.mark.chaos
def test_canary_happy_path_bit_exact():
    """Acceptance: integrity checks armed, NO fault plan — the chunked
    kernels' outputs stay bit-exact vs the unarmored run (detection is
    observation-only on the happy path) and health stays clean."""
    from triton_dist_tpu.ops.allgather import all_gather_op

    mesh2 = _mesh2()
    x = jax.random.normal(jax.random.PRNGKey(31), (2 * 16, 4), jnp.float32)
    base = np.asarray(
        all_gather_op(x, mesh2, method="ring_1d", chunks_per_shard=2)
    )
    tdt_config.update(
        timeout_iters=TIMEOUT_ITERS,
        integrity=IntegrityConfig(canary=True, max_abs=1e9),
    )
    armed = np.asarray(
        all_gather_op(x, mesh2, method="ring_1d", chunks_per_shard=2)
    )
    np.testing.assert_array_equal(armed, base)
    assert health.is_healthy()
