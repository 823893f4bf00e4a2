"""Ranged prefill, serving and chaos tiers (split from
test_ranged_prefill.py, see its docstring): engine byte-identity, the
prefill work charge, the long-prompt traffic stream, pipelined disagg
admission and its chaos campaign."""

import jax

import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import TransformerConfig, init_params
from triton_dist_tpu.models.decode import Request
from triton_dist_tpu.models.prefix_cache import PrefixCacheConfig

from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig


from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig

from ranged_helpers import _mk, _model_cfg, _serve, bt_prompts, model


def test_engine_px_prefill_byte_identity(mesh4, bt_prompts):
    """Engine tier: the px+prefill arm and the chunked arm produce the
    cold engine's exact token streams — greedy AND seeded-sampled."""
    from triton_dist_tpu.serving.engine import ServingConfig

    cfg = _model_cfg(n_layers=1)
    model = (cfg, init_params(jax.random.PRNGKey(2), cfg))
    p1, p2 = bt_prompts

    def reqs(sample):
        kw = dict(temperature=0.8, seed=5) if sample else {}
        return [_mk("a", p1, **kw), _mk("b", p1, **kw), _mk("c", p2, **kw)]

    for sample in (False, True):
        cold = _serve(model, mesh4, reqs(sample))
        px = _serve(
            model, mesh4, reqs(sample),
            serving=ServingConfig(prefix_cache=PrefixCacheConfig()),
            page_size=4, prefill=True,
        )
        chunked = _serve(
            model, mesh4, reqs(sample),
            serving=ServingConfig(prefill_chunk_tokens=3), prefill=True,
        )
        want = {u: cold.results[u].tokens for u in ("a", "b", "c")}
        assert {u: px.results[u].tokens for u in want} == want, sample
        assert {u: chunked.results[u].tokens for u in want} == want, sample


def test_engine_prefill_work_charge(mesh4, bt_prompts):
    """virtual_prefill_work_s prices the swept rectangle on the engine
    clock: the bulk arm charges bucket² pairs where the chunked arm
    charges its strips — strictly less virtual time for the same tokens
    — and a zero/None knob charges nothing (byte-identical clocks)."""
    from triton_dist_tpu.serving.engine import ServingConfig

    cfg = _model_cfg(n_layers=1)
    model = (cfg, init_params(jax.random.PRNGKey(2), cfg))
    p1, _ = bt_prompts

    def elapsed(serving, **kw):
        eng = _serve(model, mesh4, [_mk("a", p1)], serving=serving, **kw)
        return eng.clock.monotonic(), eng.results["a"].tokens

    t_bulk, tok_bulk = elapsed(
        ServingConfig(virtual_step_s=0.05, virtual_prefill_work_s=0.01),
        prefill=True,
    )
    t_chunk, tok_chunk = elapsed(
        ServingConfig(
            virtual_step_s=0.05, virtual_prefill_work_s=0.01,
            prefill_chunk_tokens=3,
        ),
        prefill=True,
    )
    t_free, tok_free = elapsed(
        ServingConfig(virtual_step_s=0.05), prefill=True
    )
    assert tok_bulk == tok_chunk == tok_free
    # bulk sweeps the 8×8 rectangle (0.64s); chunks sweep 52 pairs
    # (0.52s) but pay 2 extra parked steps (0.10s)
    assert t_bulk - t_free == pytest.approx(64 * 0.01)
    assert t_chunk == pytest.approx(t_free + 52 * 0.01 + 2 * 0.05)

    with pytest.raises(ValueError, match="virtual_prefill_work_s"):
        ServingConfig(virtual_prefill_work_s=-1.0).validate()


def test_traffic_long_prompt_stream():
    """The long-prompt traffic stream (ISSUE 18): an unset spec keeps its
    historical fingerprint byte-identically; an armed spec replaces ONLY
    the long prompts (non-long requests keep exact times and tokens);
    replay is byte-stable; the prefix pool composes (prepend happens
    after replacement); validation is loud."""
    from triton_dist_tpu.serving.traffic import (
        TrafficSpec, generate_trace, trace_fingerprint,
    )

    base = dict(
        rate_rps=4.0, n_requests=24, prompt_len=("uniform", 2, 6),
        output_len=("fixed", 4), vocab=32, seed=11,
    )
    plain = generate_trace(TrafficSpec(**base))
    # unset long-prompt fields = the field-less historical trace
    assert trace_fingerprint(plain) == trace_fingerprint(
        generate_trace(TrafficSpec(**base))
    )
    armed_spec = TrafficSpec(
        **base, long_prompt_frac=0.3, long_prompt_len=("fixed", 20)
    )
    armed = generate_trace(armed_spec)
    assert trace_fingerprint(armed) == trace_fingerprint(
        generate_trace(armed_spec)
    )
    n_long = 0
    for a, b in zip(plain, armed):
        assert a.t_s == b.t_s
        if len(b.request.prompt) == 20:
            n_long += 1
        else:
            assert a.request.prompt == b.request.prompt
    assert 0 < n_long < len(plain)
    # prefix prepend composes AFTER long replacement: armed long prompts
    # under a prefix pool are prefix + 20 tokens
    pxspec = TrafficSpec(
        **base, long_prompt_frac=0.3, long_prompt_len=("fixed", 20),
        prefix_pool=1, prefix_len=("fixed", 4), prefix_share=1.0,
    )
    pxtrace = generate_trace(pxspec)
    for a, b in zip(armed, pxtrace):
        assert b.request.prompt[4:] == a.request.prompt
    with pytest.raises(ValueError, match="long_prompt_len"):
        TrafficSpec(**base, long_prompt_frac=0.5).validate()
    with pytest.raises(ValueError, match="long_prompt_frac"):
        TrafficSpec(**base, long_prompt_len=("fixed", 20)).validate()


# ---------------------------------------------------------------------------
# Disagg tier: page landings + pipelined first-page admission
# ---------------------------------------------------------------------------

def test_handoff_page_landings():
    """HandoffResult.page_landings: one FINAL landing per logical page,
    sorted by page index, strictly increasing for streamed pages, the
    last equal to t_landed — and deduped pages land at the manifest walk
    instant."""
    from triton_dist_tpu.serving.handoff import HandoffConfig, HandoffPlane

    p = HandoffPlane(
        HandoffConfig(page_tokens=4, chunks_per_page=2, virtual_chunk_s=0.001),
        s_max=16, prefill_world=2, decode_world=2,
    )
    r = p.transfer("a", list(range(10)), now=1.0)
    assert len(r.page_landings) == r.pages_total == 3
    assert r.page_landings[-1] == r.t_landed
    assert all(a < b for a, b in zip(r.page_landings, r.page_landings[1:]))
    assert r.page_landings[0] < r.t_landed
    # the shared pages dedupe: their landings are the walk instant
    r2 = p.transfer("b", list(range(8)) + [99, 98], now=5.0)
    assert r2.pages_deduped == 2
    assert r2.page_landings[0] == 5.0 and r2.page_landings[1] == 5.0
    assert r2.page_landings[2] > 5.0


def _serve_disagg(pipelined):
    from triton_dist_tpu import config as tdt_config, obs
    from triton_dist_tpu.resilience import retry
    from triton_dist_tpu.serving.disagg import (
        DisaggServingConfig, DisaggServingEngine,
    )
    from triton_dist_tpu.serving.handoff import HandoffConfig
    from triton_dist_tpu.serving.traffic import Arrival

    cfg = TransformerConfig(
        vocab=32, hidden=32, ffn=64, n_layers=1, n_q_heads=4, n_kv_heads=2,
        head_dim=8, batch=2, seq=8,
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16),
    )
    params = init_params(jax.random.PRNGKey(1), cfg)
    mesh = Mesh(np.array(jax.devices()[:4]), ("tp",))
    rng = np.random.default_rng(0)
    trace = [
        Arrival(
            t_s=0.1 * i,
            request=Request(
                [int(x) for x in rng.integers(0, 32, 9)],
                max_new_tokens=4, uid=f"r{i}",
            ),
        )
        for i in range(4)
    ]
    tdt_config.update(obs=obs.ObsConfig())
    obs.reset()
    try:
        clock = retry.FakeClock()
        with retry.clock_scope(clock):
            eng = DisaggServingEngine(
                cfg, params, mesh, s_max=16, clock=clock,
                serving=DisaggServingConfig(
                    prefill_pes=2, virtual_step_s=0.05,
                    handoff=HandoffConfig(
                        page_tokens=4, chunks_per_page=2,
                        virtual_chunk_s=0.001,
                    ),
                    pipelined_admission=pipelined,
                ),
            )
            done = eng.serve(trace)
        spans = list(obs.tracer.spans())
    finally:
        tdt_config.update(obs=None)
        obs.reset()
    by_req = {}
    for s in spans:
        if s.name.startswith("serving:"):
            by_req.setdefault(s.track, {})[s.name] = s
    return eng, done, by_req


@pytest.mark.chaos
def test_pipelined_admission_earlier_and_spans_exact():
    """DisaggServingConfig.pipelined_admission: decode-pool admission
    gates on the FIRST page's landing — on the FakeClock timeline every
    multi-page request admits strictly before its last page lands (the
    off-arm gate) — while tokens stay byte-identical, the
    prefill/transfer/decode span decomposition stays exact, and the
    handoff counters don't move (same ladder, earlier gate)."""
    e_off, d_off, sp_off = _serve_disagg(False)
    e_on, d_on, sp_on = _serve_disagg(True)
    assert {u: r.tokens for u, r in d_on.items()} == {
        u: r.tokens for u, r in d_off.items()
    }
    n_earlier = 0
    for track, ss in sp_on.items():
        if "serving:transfer" not in ss:
            continue
        t = ss["serving:transfer"]
        assert ss["serving:prefill"].t_end == t.t_start
        assert t.t_end == ss["serving:decode"].t_start
        off_t = sp_off[track]["serving:transfer"]
        assert t.t_start == off_t.t_start
        if t.t_end < off_t.t_end:
            n_earlier += 1
    assert n_earlier >= 1
    assert e_on.snapshot()["handoff"] == e_off.snapshot()["handoff"]


def test_pipelined_admission_disarmed_default():
    """pipelined_admission defaults False, and False is byte-identical
    posture: the admission gate is the LAST page's landing."""
    from triton_dist_tpu.serving.disagg import DisaggServingConfig

    assert DisaggServingConfig().pipelined_admission is False
    e_off, _, sp_off = _serve_disagg(False)
    for track, ss in sp_off.items():
        if "serving:transfer" in ss:
            # off-arm transfer span ends at t_landed (the last page)
            assert ss["serving:transfer"].t_end == ss["serving:decode"].t_start


# ---------------------------------------------------------------------------
# Chaos tier: pipelined handoff under the full fault campaign
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_pipelined_disagg_campaign_quick_and_replay():
    """The chaos-matrix pipelined-disagg cell: corrupt KV chunks injected
    mid-handoff while the decode pool admits at FIRST-page-landed — the
    guard ladder must attribute and recover (zero lost requests, every
    invariant green) and the campaign replays bit-identically."""
    from triton_dist_tpu.resilience import soak

    spec = soak.SoakSpec.disagg(seed=1, pipelined_handoff=True)
    res = soak.run_campaign(spec)
    assert res.ok, (res.failures, res.error)
    again = soak.run_campaign(spec)
    assert again.fingerprint == res.fingerprint


@pytest.mark.chaos
@pytest.mark.slow
def test_pipelined_disagg_collapse_campaign():
    """The scheduled-pool-collapse composition under pipelined admission
    (every third seed): the topology collapses to unified mid-campaign
    with zero lost requests at the earlier admission gate."""
    from triton_dist_tpu.resilience import soak

    spec = soak.SoakSpec.disagg(seed=0, pipelined_handoff=True)
    assert spec.collapse_at_step > 0
    res = soak.run_campaign(spec)
    assert res.ok, (res.failures, res.error)
    assert res.snapshot["engine"]["collapsed"]


def test_soak_spec_pipelined_validation():
    """pipelined_handoff needs the disagg topology to gate."""
    from triton_dist_tpu.resilience import soak

    with pytest.raises(ValueError, match="pipelined_handoff"):
        soak.SoakSpec(seed=0, pipelined_handoff=True).validate()

