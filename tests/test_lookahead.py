"""``ContinuousBatcher(lookahead=True)``: a round that cannot change the
schedule sends the next step, fed on the device, before it pulls its own
tokens (ROADMAP A6). What must hold: the same tokens in the same rounds as
the plain batcher, a step sent in vain thrown away, and no step sent where
the round's tokens decide what runs next."""
from __future__ import annotations

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from triton_dist_tpu.models import init_params
from triton_dist_tpu.models.decode import ContinuousBatcher, Request
from triton_dist_tpu.models.tp_transformer import TransformerConfig
from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig
from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig


@pytest.fixture(scope="module")
def mesh1() -> Mesh:
    return Mesh(np.array(jax.devices()[:1]), ("tp",))


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig(
        vocab=32, hidden=32, ffn=64, n_layers=1, n_q_heads=4, n_kv_heads=2,
        head_dim=8, batch=2, seq=8,
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16))
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _reqs(cfg, shapes, prompts=5, **kw):
    rng = np.random.default_rng(prompts)
    return [Request([int(t) for t in rng.integers(0, cfg.vocab, n)], mx,
                    uid=i, **kw) for i, (n, mx) in enumerate(shapes)]


def _run(cfg, params, mesh, reqs, late=(), **kw):
    """Tokens by uid, rounds run, and the batcher; ``late`` requests are
    submitted after the third step, into a slot left idle until then."""
    b = ContinuousBatcher(cfg, params, mesh, s_max=32, **kw)
    for r in reqs:
        b.submit(r)
    for _ in range(3):
        b.step()
    for r in late:
        b.submit(r)
    return dict(b.run(max_steps=400)), b.rounds, b


@pytest.mark.parametrize("kw", [
    dict(),                                     # token-fed, contiguous cache
    dict(prefill=True),
    dict(prefill=True, page_size=8),
], ids=["token_fed", "prefill", "prefill_paged"])
def test_lookahead_serves_the_same_tokens_in_the_same_rounds(tiny, mesh1, kw):
    cfg, params = tiny
    shapes = [(3, 9), (5, 4), (2, 7), (6, 1), (4, 2), (3, 12)]
    want, rounds, plain = _run(cfg, params, mesh1, _reqs(cfg, shapes), **kw)
    got, rounds2, b = _run(cfg, params, mesh1, _reqs(cfg, shapes),
                           lookahead=True, **kw)
    assert got == want and rounds2 == rounds
    assert plain.rounds_ahead == 0 and plain._ahead is None
    # most rounds had their step sent by the round before; none in vain (a
    # step goes ahead only where no slot can free), none left in flight
    assert b.rounds_ahead > rounds // 3
    assert b.ahead_discarded == 0 and b._ahead is None


def test_nothing_compiles_after_the_first_step_sent_ahead(tiny, mesh1):
    """A warm-up of three tokens a slot sends one step ahead; a longer run
    after it (steps sent ahead of steps sent ahead) compiles nothing."""
    from jax import monitoring

    cfg, params = tiny
    b = ContinuousBatcher(cfg, params, mesh1, s_max=32, prefill=True,
                          page_size=8, lookahead=True)
    for r in _reqs(cfg, [(3, 3), (5, 3)]):
        b.submit(r)
    b.run(max_steps=50)
    assert b.rounds_ahead == 1
    compiled = []
    monitoring.register_event_duration_secs_listener(
        lambda event, *a, **kw: compiled.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    for r in _reqs(cfg, [(3, 9), (5, 7)], prompts=8):
        b.submit(r)
    b.run(max_steps=50)
    assert b.rounds_ahead > 6 and not compiled


def test_a_step_sent_in_vain_is_thrown_away(tiny, mesh1):
    """One request on two slots, a second submitted while a step is out
    ahead: its admission moves the cache (and, token-fed, the slot), so the
    step already sent is not the next round's; the tokens stay the plain
    batcher's."""
    cfg, params = tiny
    first, late = _reqs(cfg, [(3, 12)]), _reqs(cfg, [(4, 6)], prompts=6)
    late[0].uid = "late"
    for kw in (dict(), dict(prefill=True, page_size=8)):
        want, rounds, _ = _run(cfg, params, mesh1, first, late, **kw)
        got, rounds2, b = _run(cfg, params, mesh1, first, late,
                               lookahead=True, **kw)
        assert got == want and rounds2 == rounds, kw
        assert b.ahead_discarded == 1 and b.rounds_ahead > 0, kw


@pytest.mark.parametrize("kw", [
    dict(eos_id=31), dict(temperature=0.7, seed=3),
], ids=["stop_token", "sampled"])
def test_no_step_goes_ahead_where_the_tokens_decide(tiny, mesh1, kw):
    cfg, params = tiny
    shapes = [(3, 6), (5, 4)]
    want, _, _ = _run(cfg, params, mesh1, _reqs(cfg, shapes, **kw))
    got, _, b = _run(cfg, params, mesh1, _reqs(cfg, shapes, **kw),
                     lookahead=True)
    assert got == want and b.rounds_ahead == 0 and b.ahead_discarded == 0


def test_weights_swapped_under_a_step_sent_ahead(tiny, mesh1):
    cfg, params = tiny
    other = init_params(jax.random.PRNGKey(1), cfg)
    outs = []
    for look in (False, True):
        b = ContinuousBatcher(cfg, params, mesh1, s_max=32, lookahead=look)
        b.submit(_reqs(cfg, [(3, 10)])[0])
        for _ in range(5):
            b.step()
        b.params = other
        outs.append(dict(b.run(max_steps=100)))
        assert b.ahead_discarded == int(look)
    assert outs[0] == outs[1]


def test_speculative_batcher_refuses_lookahead(tiny, mesh1):
    from triton_dist_tpu.serving.speculative import SpeculativeBatcher

    cfg, params = tiny
    with pytest.raises(NotImplementedError, match="lookahead"):
        SpeculativeBatcher(cfg, params, mesh1, s_max=32, spec_decode=None,
                           lookahead=True)
