"""Ranged prefill, disagg tier (split from test_ranged_engine.py): the handoff
plane reports one landing a page, and pipelined admission gates the decode
pool on the FIRST page's landing with tokens and the transfer-span
decomposition unchanged (its chaos campaign is test_disagg_soak.py)."""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import TransformerConfig, init_params
from triton_dist_tpu.models.decode import Request
from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig
from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig


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


@pytest.fixture(scope="module")
def disagg_off():
    """The off arm (admission on the LAST page), served once for both
    tests that read it."""
    return _serve_disagg(False)


@pytest.mark.chaos
def test_pipelined_admission_earlier_and_spans_exact(disagg_off):
    """DisaggServingConfig.pipelined_admission: decode-pool admission
    gates on the FIRST page's landing — on the FakeClock timeline every
    multi-page request admits strictly before its last page lands (the
    off-arm gate) — while tokens stay byte-identical, the
    prefill/transfer/decode span decomposition stays exact, and the
    handoff counters don't move (same ladder, earlier gate)."""
    e_off, d_off, sp_off = disagg_off
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


def test_pipelined_admission_disarmed_default(disagg_off):
    """pipelined_admission defaults False, and False is byte-identical
    posture: the admission gate is the LAST page's landing."""
    from triton_dist_tpu.serving.disagg import DisaggServingConfig

    assert DisaggServingConfig().pipelined_admission is False
    e_off, _, sp_off = disagg_off
    for track, ss in sp_off.items():
        if "serving:transfer" in ss:
            # off-arm transfer span ends at t_landed (the last page)
            assert ss["serving:transfer"].t_end == ss["serving:decode"].t_start
