"""Speculative serving (triton_dist_tpu/serving/speculative.py,
docs/serving.md "Speculative decoding"; ISSUE 20): per-slot acceptance
in the continuous batcher, adaptive-k, and the negative-cost
``shed_speculation`` brownout rung.

Tier structure mirrors tests/test_serving.py:

- **host tier**: SpecDecodeConfig validation (no device work);
- **engine tier** (world-1 mesh, real batcher steps, FakeClock):
  greedy byte-identity + the step-count throughput win, seeded-sampled
  replay, per-slot divergent acceptance through the chaos seam, the
  prefix-cache page audit over BOTH tries, the dormant-k0 ≡ disarmed
  pin, and the adaptive-k backoff unit;
- **chaos tier** (``pytest.mark.chaos``, also run by chaos_matrix.sh):
  the shed_spec rung arc end to end, and the seeded speculative soak
  campaign (straggler × draft corruption on a 4-PE world) with its
  bit-identical replay.
"""

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
from triton_dist_tpu.resilience import health, retry, soak
from triton_dist_tpu.serving import (
    Arrival,
    OverloadConfig,
    PrefixCacheConfig,
    ServingConfig,
    ServingEngine,
    SLOTargets,
    SpecDecodeConfig,
    SpeculativeBatcher,
    TrafficSpec,
    generate_trace,
    shared_prefix_mix,
)
from triton_dist_tpu.serving import overload as ov


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


def _self_draft(cfg, params, k=3, **over):
    """Self-draft (draft == target): α = 1 by construction under greedy,
    which isolates the serving machinery — acceptance, rollback, cost
    accounting — from draft quality."""
    return SpecDecodeConfig(draft_cfg=cfg, draft_params=params, k=k, **over)


def _engine(tiny1, mesh1, sd, *, s_max=16, clock=None, **serving_kw):
    cfg, params = tiny1
    clock = clock or retry.FakeClock()
    eng = ServingEngine(
        cfg, params, mesh1, s_max=s_max, clock=clock,
        serving=ServingConfig(virtual_step_s=0.01, speculative=sd,
                              **serving_kw),
    )
    return eng, clock


def _reqs(cfg, spec_list, seed=5, **kw):
    key = jax.random.PRNGKey(seed)
    out = []
    for i, (plen, mx) in enumerate(spec_list):
        toks = list(np.asarray(jax.random.randint(
            jax.random.fold_in(key, i), (plen,), 0, cfg.vocab, np.int32
        )))
        out.append(Request([int(t) for t in toks], max_new_tokens=mx,
                           uid=i, **kw))
    return out


# ---------------------------------------------------------------------------
# Host tier: config validation
# ---------------------------------------------------------------------------

def test_spec_config_validation():
    ok = SpecDecodeConfig(draft_cfg=object(), draft_params=object(), k=4)
    assert ok.validate() is ok
    assert SpecDecodeConfig(k=0).validate().k == 0   # dormant needs no draft
    with pytest.raises(ValueError, match="k-1"):
        SpecDecodeConfig(k=1).validate()
    with pytest.raises(ValueError, match="draft_cfg"):
        SpecDecodeConfig(k=2).validate()
    with pytest.raises(ValueError, match="hysteresis"):
        SpecDecodeConfig(draft_cfg=object(), draft_params=object(),
                         alpha_low=0.7, alpha_high=0.7).validate()
    with pytest.raises(ValueError, match="k_min"):
        SpecDecodeConfig(draft_cfg=object(), draft_params=object(),
                         k_min=1).validate()
    with pytest.raises(ValueError, match="k_min"):
        SpecDecodeConfig(draft_cfg=object(), draft_params=object(),
                         k=2, k_min=3).validate()


# ---------------------------------------------------------------------------
# Engine tier: greedy byte-identity + the step-count win
# ---------------------------------------------------------------------------

def test_greedy_byte_identity_and_throughput_gain(tiny1, mesh1):
    """The tentpole acceptance pair on one FakeClock A/B: a self-draft
    speculative engine emits token for token what the plain engine emits
    (greedy), and the step-count accounting (``last_step_units`` scaling
    ``virtual_step_s``) makes it measurably FASTER — outputs long
    relative to k, so the accepted drafts outweigh the draft+verify
    surcharge."""
    cfg, params = tiny1
    shapes = [(3, 12), (2, 12), (4, 12), (2, 12)]

    plain, _ = _engine(tiny1, mesh1, None)
    for r in _reqs(cfg, shapes):
        plain.submit(r)
    want = {u: r.tokens for u, r in plain.run_until_idle().items()}

    spec, _ = _engine(tiny1, mesh1, _self_draft(cfg, params, k=3))
    for r in _reqs(cfg, shapes):
        spec.submit(r)
    got = {u: r.tokens for u, r in spec.run_until_idle().items()}
    assert got == want, "greedy speculative serving is byte-identical"

    psnap, ssnap = plain.snapshot(), spec.snapshot()
    assert "speculative" not in psnap, "disarmed snapshots unchanged"
    sp = ssnap["speculative"]
    assert sp["rounds"] > 0 and sp["k_live"] == 3
    assert sp["tokens_accepted"] > 0
    # α < 1 even for self-draft: it is measured over COMMITTED tokens,
    # and max_new truncation throws the round's drafted overhang away
    assert sp["accept_rate"] is not None and sp["accept_rate"] > 0.6
    assert ssnap["tokens"]["generated"] == psnap["tokens"]["generated"]
    assert ssnap["tokens"]["per_s"] > psnap["tokens"]["per_s"], (
        "the FakeClock A/B must show the step-count win"
    )


def test_sampled_determinism_bit_identical_replay(tiny1, mesh1):
    """Seeded sampling through the rejection-sampling accept path: two
    fresh engines over the same trace emit bit-identical streams (the
    per-slot RNG draw order is fixed), and the speculative tallies
    replay exactly too."""
    spec = TrafficSpec(rate_rps=20.0, n_requests=8, seed=11,
                       prompt_len=("uniform", 2, 4),
                       output_len=("uniform", 6, 12), vocab=32,
                       temperature=0.8)

    def run():
        cfg, params = tiny1
        eng, _ = _engine(tiny1, mesh1, _self_draft(cfg, params, k=3),
                         max_queue=64)
        done = eng.serve(generate_trace(spec))
        return {u: r.tokens for u, r in done.items()}, (
            eng.snapshot()["speculative"]
        )

    a, sp_a = run()
    b, sp_b = run()
    assert a == b
    assert sp_a == sp_b
    assert sp_a["rounds"] > 0


def test_per_slot_divergent_acceptance(tiny1, mesh1):
    """The per-slot claim itself: in ONE round, the slot whose draft was
    corrupted (the chaos seam) accepts nothing while its neighbor
    accepts the full k-1 — a lockstep ``min`` would have stalled both —
    and the corrupted slot's emitted token is still the target's own
    argmax, so the streams stay byte-identical to plain decode."""
    cfg, params = tiny1
    bt = SpeculativeBatcher(cfg, params, mesh1, s_max=16,
                            spec_decode=_self_draft(cfg, params, k=3))
    reqs = _reqs(cfg, [(2, 8), (3, 8)], seed=9)
    for r in reqs:
        bt.submit(r)
    # feed prompts until BOTH slots are generating (spec-eligible)
    for _ in range(8):
        if all(r is not None and bt.slot_fed[i] >= len(r.prompt)
               for i, r in enumerate(bt.slot_req)):
            break
        bt.step()
    else:
        pytest.fail("slots never both became spec-eligible")

    rollback0 = bt.spec_rollback_total
    bt.corrupt_draft_next = True
    bt.step()
    assert bt.spec_draft_faults_injected == 1
    assert not bt.corrupt_draft_next, "seam consumed by the spec round"
    # slot 0 (spec[0], the corrupted one) rejects the flipped token at
    # j=0; slot 1 self-drafts the target's own chain and accepts k-1
    assert bt.last_accepts == {0: 0, 1: 2}, bt.last_accepts
    assert bt.spec_rollback_total - rollback0 >= 2
    assert bt.last_step_units > 1.0

    done = dict(bt.run(max_steps=200))
    plain = ContinuousBatcher(cfg, params, mesh1, s_max=16)
    for r in _reqs(cfg, [(2, 8), (3, 8)], seed=9):
        plain.submit(r)
    assert done == dict(plain.run(max_steps=200))


def test_rollback_page_cursor_audit_under_prefix_cache(tiny1, mesh1):
    """Speculative serving over the paged pool + prefix trie: rejected
    suffixes roll back by cursor, never by page surgery — so after a
    shared-prefix serve BOTH tries (target and draft mirror) still pass
    the full page-accounting partition audit, and the streams match the
    plain paged+prefix engine byte for byte."""
    cfg, params = tiny1
    spec = shared_prefix_mix(s_max=32, rate_rps=10.0, n_requests=8,
                             n_prefixes=2, prefix_tokens=8,
                             vocab=cfg.vocab, seed=4)
    trace = generate_trace(spec)

    def run(sd):
        eng = ServingEngine(
            cfg, params, mesh1, s_max=32, clock=retry.FakeClock(),
            serving=ServingConfig(virtual_step_s=0.01, speculative=sd,
                                  prefix_cache=PrefixCacheConfig(),
                                  max_queue=64),
            page_size=4,
        )
        done = eng.serve(trace)
        return eng, {u: r.tokens for u, r in done.items()}

    _, want = run(None)
    eng, got = run(_self_draft(cfg, params, k=3))
    assert got == want
    bt = eng._batcher
    assert isinstance(bt, SpeculativeBatcher)
    assert bt.spec_rounds > 0
    bt._px.audit()
    assert bt._draft_px is not None, "paged target arms the draft mirror"
    bt._draft_px.audit()
    # rollbacks really happened over pool pages (truncation waste at
    # minimum) and no page leaked through them — that is the audit above
    assert eng.snapshot()["speculative"]["rollback_total"] >= 0


def test_dormant_k0_pinned_to_disarmed(tiny1, mesh1):
    """``SpecDecodeConfig(k=0)`` is dormant, not merely quiet: every
    round delegates to the plain decode path at plain cost, so streams
    AND the virtual clock are identical to a disarmed engine — the only
    visible difference is the (all-zero) snapshot section."""
    cfg, params = tiny1
    shapes = [(3, 6), (2, 5), (4, 4)]

    def run(sd):
        eng, clock = _engine(tiny1, mesh1, sd)
        for r in _reqs(cfg, shapes, seed=3):
            eng.submit(r)
        done = eng.run_until_idle()
        return {u: r.tokens for u, r in done.items()}, clock.monotonic(), eng

    want, t_plain, _ = run(None)
    got, t_dormant, eng = run(SpecDecodeConfig(k=0))
    assert got == want
    assert t_dormant == t_plain, "dormant rounds charge plain step units"
    sp = eng.snapshot()["speculative"]
    assert sp["rounds"] == 0 and sp["tokens_offered"] == 0
    assert sp["accept_rate"] is None


def test_adaptive_k_backoff_unit(tiny1, mesh1):
    """The rolling-α controller in isolation (``_note_round`` is the
    whole surface): k backs off one step per EXHAUSTED window below
    alpha_low down to k_min, regrows above alpha_high up to k, and the
    cleared window is the dwell — one bad round never moves it."""
    cfg, params = tiny1
    seen = []
    bt = SpeculativeBatcher(
        cfg, params, mesh1, s_max=16,
        spec_decode=_self_draft(cfg, params, k=4, adaptive=True,
                                alpha_window=4, k_min=2),
    )
    bt.on_k_change = lambda old, new, alpha: seen.append((old, new))
    assert bt.k_live == 4

    for _ in range(3):
        bt._note_round(0, 3)
    assert bt.k_live == 4, "window not full: no move yet (the dwell)"
    bt._note_round(0, 3)
    assert bt.k_live == 3, "cold window backs off one step"
    for _ in range(4):
        bt._note_round(0, 2)
    assert bt.k_live == 2
    for _ in range(8):
        bt._note_round(0, 1)
    assert bt.k_live == 2, "k_min is the floor"
    for _ in range(4):
        bt._note_round(1, 1)
    assert bt.k_live == 3, "hot window regrows one step"
    for _ in range(4):
        bt._note_round(2, 2)
    assert bt.k_live == 4
    for _ in range(8):
        bt._note_round(3, 3)
    assert bt.k_live == 4, "configured k is the ceiling"
    assert [(o, n) for o, n, _ in bt.spec_k_transitions] == [
        (4, 3), (3, 2), (2, 3), (3, 4)
    ]
    assert seen == [(4, 3), (3, 2), (2, 3), (3, 4)]
    assert all(0.0 <= a <= 1.0 for _, _, a in bt.spec_k_transitions)


# ---------------------------------------------------------------------------
# Chaos tier: the shed_spec rung arc
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_shed_speculation_rung_climb_and_revert(tiny1, mesh1):
    """The negative-cost rung end to end: a flash crowd drives the
    5-state ladder through SHED_SPEC (a counted rebuild that swaps the
    plain batcher in, through the elastic replay machinery), the sparse
    tail walks it back down (a second counted rebuild restores the
    draft), no request is lost, and — greedy self-draft — every stream
    is byte-identical to an unpressured speculative engine."""
    cfg, params = tiny1
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

    eng, _ = _engine(
        tiny1, mesh1, _self_draft(cfg, params, k=3),
        max_queue=4, slo=SLOTargets(ttft_ms=5.0),
        overload=OverloadConfig(
            shed_speculation=True, min_dwell_steps=2, window_steps=4,
            enter_pressure=(0.5, 0.6, 0.7, 0.8),
            exit_pressure=(0.3, 0.4, 0.5, 0.6),
        ),
    )
    done = eng.serve(crowd + tail)
    rungs = {t.to for t in eng._overload.transitions}
    assert ov.SHED_SPEC in rungs, eng._overload.transitions
    snap = eng.snapshot()
    assert snap["requests"].get("spec_sheds", 0) >= 1
    assert eng.rebuilds >= 2, "shed AND restore each rebuilt"
    assert not eng._spec_shed, "speculation restored on descent"
    reasons = [e.reason for e in health.events(health.SERVING_REBUILD)]
    assert any("speculation shed" in r for r in reasons)
    assert any("speculation restored" in r for r in reasons)
    assert all(type(r).__name__ == "Finished" for r in done.values())

    # byte-identity: greedy self-draft serving emits plain greedy decode
    # whatever mode flips happened mid-serve
    calm, _ = _engine(tiny1, mesh1, _self_draft(cfg, params, k=3),
                      max_queue=64)
    want = calm.serve(crowd + tail)
    assert {u: r.tokens for u, r in done.items()} == {
        u: r.tokens for u, r in want.items()
    }


@pytest.mark.chaos
def test_shed_rung_armed_on_plain_engine_is_byte_identical(tiny1, mesh1):
    """Armed-untriggered ≡ disarmed, rung edition: the same crowd drives
    a NON-speculative engine through SHED_SPEC — the transition is
    recorded but nothing rebuilds, and the streams match the engine with
    no overload controller at all."""
    crowd = [
        Arrival(t_s=0.0, request=Request([1, 2], max_new_tokens=4,
                                         uid=f"c{k}"))
        for k in range(8)
    ]
    eng, _ = _engine(
        tiny1, mesh1, None,
        max_queue=4, slo=SLOTargets(ttft_ms=5.0),
        overload=OverloadConfig(
            shed_speculation=True, min_dwell_steps=2, window_steps=4,
            enter_pressure=(0.5, 0.6, 0.7, 0.8),
            exit_pressure=(0.3, 0.4, 0.5, 0.6),
        ),
    )
    done = eng.serve(list(crowd))
    assert ov.SHED_SPEC in {t.to for t in eng._overload.transitions}
    assert eng.rebuilds == 0, "nothing to shed on a plain engine"
    calm, _ = _engine(tiny1, mesh1, None, max_queue=64)
    want = calm.serve(list(crowd))
    assert {u: r.tokens for u, r in done.items()} == {
        u: r.tokens for u, r in want.items()
    }
