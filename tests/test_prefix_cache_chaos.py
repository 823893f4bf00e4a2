"""Radix-shared prefix cache on more than one PE and under poison (split from
test_prefix_cache.py, whose docstring holds the tier structure): a shared
chain whose pages live on different PEs, and the poisoned-shared-page
strike — every reader of a struck chain is evicted and cold-re-prefilled."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu import config as tdt_config
from triton_dist_tpu import resilience
from triton_dist_tpu.models import init_params
from triton_dist_tpu.models.decode import ContinuousBatcher, Request
from triton_dist_tpu.models.prefix_cache import PrefixCacheConfig
from triton_dist_tpu.resilience.integrity import IntegrityConfig
from triton_dist_tpu.serving import (
    Finished,
    Poisoned,
    PrefixCacheConfig as ServingPrefixCacheConfig,
)

# _restore_config is autouse: importing it arms it for this file too
from test_prefix_cache import _cfg, _engine, _restore_config, mesh1


@pytest.fixture(scope="module")
def tiny4b():
    # batch=4 slots so three readers can share one producer's chain
    cfg = _cfg(batch=4)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def test_multi_pe_chain_spans_pes(tiny4b):
    """World-4: a shared chain's pages live on DIFFERENT PEs (global page
    g on PE g // pps_local) and the per-PE table rows stay consistent —
    tokens byte-identical to the cold run."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    cfg, params = tiny4b
    cfg = dataclasses.replace(cfg, n_kv_heads=4)
    params = init_params(jax.random.PRNGKey(2), cfg)
    mesh = Mesh(np.array(jax.devices()[:4]), ("tp",))
    prefix = list(range(10, 22))             # 3 pages: PEs 0, 0, 1 @ s_max 32
    reqs = lambda: [  # noqa: E731
        Request(prefix + [1, 2], max_new_tokens=3, uid="p"),
        Request(prefix + [3], max_new_tokens=4, uid="c"),
    ]
    b0 = ContinuousBatcher(cfg, params, mesh, s_max=32, page_size=4)
    for r in reqs():
        b0.submit(r)
    cold = dict(b0.run(max_steps=200))
    b1 = ContinuousBatcher(cfg, params, mesh, s_max=32, page_size=4,
                           prefix_cache=PrefixCacheConfig())
    p, c = reqs()
    b1.submit(p)
    warm = dict(b1.run(max_steps=200))
    b1.submit(c)
    warm.update(b1.run(max_steps=200))
    assert warm == cold
    px = b1.prefix_cache
    assert px.stats()["hits"] == 1
    # pages_per_shard = (32/4)/4 = 2: global pages 0,1 on PE0, page 2 on
    # PE1 — the chain really spans PEs
    assert px.pps_local == 2 and px.stats()["prefill_tokens_saved"] == 12
    px.audit()


@pytest.mark.chaos
def test_poisoned_shared_page_strikes_every_reader(tiny4b, mesh1):
    """ISSUE 12 acceptance (quarantine fan-out): a poisoned slot whose
    chain is SHARED strikes every reader — each is evicted, the chain is
    detached from the trie, and every struck reader re-prefills cold and
    regenerates its stream byte-identically (greedy and seeded-sampled);
    the unrelated neighbor is untouched."""
    cfg, params = tiny4b
    prefix = list(range(10, 22))             # 3 shared pages at page 4

    def reqs():
        return [
            Request(prefix + [1, 2], max_new_tokens=3, uid="prod"),
            Request(prefix + [3], max_new_tokens=6, uid="rA"),
            Request(prefix + [4, 5], max_new_tokens=6, uid="rB",
                    temperature=0.8, top_k=6, seed=9),
            Request(prefix + [6], max_new_tokens=5, uid="rC"),
        ]

    def run(poison_uid=None):
        resilience.reset()
        eng = _engine(cfg, params, mesh1, ServingPrefixCacheConfig())
        if poison_uid is not None:
            tdt_config.update(integrity=IntegrityConfig())
            orig = eng._batcher._step
            calls = {"n": 0}

            def poisoned_step(params_, cache, tok, pos):
                logits, cache = orig(params_, cache, tok, pos)
                calls["n"] += 1
                if calls["n"] == 20:         # readers mid-decode
                    slot = next(
                        i for i, r in enumerate(eng._batcher.slot_req)
                        if r is not None and r.uid == poison_uid
                    )
                    logits = logits.at[slot].set(jnp.nan)
                return logits, cache

            eng._batcher._step = poisoned_step
        p, a, b, c = reqs()
        eng.submit(p, arrival_t=0.0)
        done = eng.run_until_idle()          # producer publishes the chain
        for r in (a, b, c):
            eng.submit(r)
        done.update(eng.run_until_idle())
        tdt_config.update(integrity=None)
        return done, eng.snapshot()

    golden, _ = run()
    assert all(isinstance(r, Finished) for r in golden.values())
    done, snap = run(poison_uid="rA")
    assert {u for u, r in done.items() if isinstance(r, Poisoned)} == {"rA"}
    for uid in ("prod", "rB", "rC"):
        assert done[uid].tokens == golden[uid].tokens, uid
    assert done["rB"].resumed == 1 and done["rC"].resumed == 1, (
        "both readers were struck and restarted"
    )
    assert snap["requests"]["prefix_struck"] == 2
    px = snap["prefix_cache"]
    assert px["struck_pages"] >= 3 and px["readers_struck"] == 2
    from triton_dist_tpu.resilience import health

    assert health.counters()[
        ("continuous_batcher", health.PREFIX_STRIKE)
    ] == 2
    assert not health.is_healthy(), "the POISONED event flips health"
