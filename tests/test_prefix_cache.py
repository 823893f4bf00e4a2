"""Radix-shared paged KV prefix cache (ISSUE 12; docs/serving.md "Prefix
cache"): trie insert/match/evict + refcount invariants, copy-on-write
divergence geometry, byte-identical outputs vs cold prefill (greedy AND
seeded sampling), the armed-but-unshared ≡ disarmed pin, the shared-prefix
traffic workload's draw isolation, and — chaos tier — the
poisoned-shared-page strike: every reader of a struck chain is evicted
and cold-re-prefilled, regenerating its stream byte-identically.

Tier structure (the test_serving.py convention):

- **host tier**: pure :class:`PagePrefixCache` bookkeeping (no device
  work) — match/publish/release/evict/strike with the ``audit()``
  invariant (every page owned exactly once; every shared page refcounted
  exactly once per reader) asserted after every mutation;
- **engine tier** (world-1 mesh, real batcher steps): sharing
  byte-identity, the metrics surface; the multi-PE table (mesh4) is in
  test_prefix_cache_chaos.py;
- **chaos tier** (``pytest.mark.chaos``, chaos_matrix.sh): the strike
  fan-out cell (test_prefix_cache_chaos.py) and the quick shared-prefix
  soak campaign (test_prefix_cache_soak.py).
"""

import dataclasses
import json

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu import config as tdt_config
from triton_dist_tpu.models import init_params
from triton_dist_tpu.models.decode import ContinuousBatcher
from triton_dist_tpu.models.prefix_cache import (
    PagePrefixCache,
    PrefixCacheConfig,
)
from triton_dist_tpu.models.tp_transformer import TransformerConfig
from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig
from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig
from triton_dist_tpu.resilience import retry
from triton_dist_tpu.serving import (
    PrefixCacheConfig as ServingPrefixCacheConfig,
    ServingConfig,
    ServingEngine,
    TrafficSpec,
    generate_trace,
    shared_prefix_mix,
    trace_fingerprint,
)


@pytest.fixture(autouse=True)
def _restore_config():
    cfg = tdt_config.get_config()
    snap = (cfg.integrity, cfg.elastic, cfg.suspect_threshold)
    yield
    tdt_config.update(integrity=snap[0], elastic=snap[1],
                      suspect_threshold=snap[2])
    retry.set_clock(None)


def _cfg(**over):
    base = dict(
        vocab=64, hidden=32, ffn=64, n_layers=1, n_q_heads=4, n_kv_heads=2,
        head_dim=8, batch=2, seq=8,
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16),
    )
    base.update(over)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def tiny1():
    cfg = _cfg()
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="session")
def mesh1() -> Mesh:
    return Mesh(np.array(jax.devices()[:1]), ("tp",))


# ---------------------------------------------------------------------------
# Host tier: the trie / allocator object
# ---------------------------------------------------------------------------

def _px(slots=4, page=4, pps=8, pes=1, **cfg):
    return PagePrefixCache(
        PrefixCacheConfig(**cfg), n_slots=slots, page=page,
        pps_local=pps, n_pes=pes,
    )


def test_match_publish_refcounts_and_release():
    """Every shared page is refcounted exactly once per reader; release
    drops the refs but RETAINS the pages for future hits."""
    px = _px()
    prompt = list(range(10))                 # 2 full pages + 2-token tail
    assert px.acquire(0, prompt, 4) == 0     # cold: miss
    px.audit()
    # feed publishes pages 0 and 1 (page 2 holds the tail + generation)
    assert px.publish(0, 0, prompt[0:4]) is False
    assert px.publish(0, 1, prompt[4:8]) is False
    px.audit()
    assert px.stats()["pages_shared"] == 2
    # second reader: hit over both full pages, capped before the tail
    assert px.acquire(1, prompt, 4) == 8
    px.audit()
    assert px.n_readers(0) == 2 and px.n_readers(1) == 2
    # a third, diverging after one page
    assert px.acquire(2, prompt[:4] + [99, 98, 97], 4) == 4
    px.audit()
    st = px.stats()
    assert st["hits"] == 2 and st["misses"] == 1
    assert st["prefill_tokens_saved"] == 12
    assert st["shared_refs"] == 2 + 2 + 1    # page0: 3 readers, page1: 2
    # releases drop refs to zero but keep the trie pages for future hits
    for slot in (0, 1, 2):
        assert px.release(slot) == []
        px.audit()
    st = px.stats()
    assert st["shared_refs"] == 0 and st["pages_shared"] == 2
    assert px.acquire(3, prompt, 4) == 8, "retained pages still hit"
    px.release(3)
    px.audit()


def test_match_capped_before_last_prompt_token():
    """The match never covers the whole prompt: the step producing the
    first generated token always runs (and writes) in a private page."""
    px = _px()
    prompt = list(range(8))                  # exactly 2 pages
    px.acquire(0, prompt, 4)
    px.publish(0, 0, prompt[0:4])
    px.publish(0, 1, prompt[4:8])
    # same 8-token prompt: only page 0 is matchable (cap at (L-1)//page)
    assert px.acquire(1, prompt, 4) == 4
    px.audit()


def test_cow_divergence_first_mid_last_token_of_page():
    """CoW geometry: divergence at the first/mid/last token of page 1
    claims page 1 fresh in every case (shared set = pages strictly before
    the divergent page); divergence inside page 0 is a clean miss."""
    base = list(range(20, 32))               # 3 pages
    px = _px()
    px.acquire(0, base + [1], 3)
    for g in range(3):
        px.publish(0, g, base[g * 4:(g + 1) * 4])
    for div_at, want_hit in ((4, 4), (6, 4), (7, 4), (0, 0), (3, 0)):
        variant = list(base)
        variant[div_at] = 59                 # diverge at this token
        slot_hit = px.acquire(1, variant + [1], 3)
        assert slot_hit == want_hit, (div_at, slot_hit)
        st = px.stats()
        px.release(1)
        px.audit()
    # divergence consumed fresh (CoW) pages on every hit admission
    assert st["cow_pages"] > 0


def test_publish_dedup_concurrent_identical_producers():
    """Two slots feeding the same prefix race benignly: the second
    publish dedups onto the first's node and repoints its table row."""
    px = _px()
    prompt = list(range(9))
    px.acquire(0, prompt, 4)
    px.acquire(1, prompt, 4)                 # same prefix, both cold
    px.audit()
    px.publish(0, 0, prompt[0:4])
    assert px.publish(1, 0, prompt[0:4]) is True   # dedup: table changed
    px.audit()
    st = px.stats()
    assert st["published_pages"] == 1 and st["deduped_publishes"] == 1
    assert px.table[0, 0, 0] == px.table[0, 1, 0], "rows share one page"
    assert px.n_readers(0) == 2
    px.release(0)
    px.release(1)
    px.audit()


def test_eviction_lru_under_pool_pressure_no_leak():
    """Retained (ref-0) pages evict LRU-first when the pool runs dry —
    and the accounting invariant holds through admissions that force it."""
    px = _px(slots=2, page=4, pps=4)         # tiny pool: 8 pages/PE
    a, b = list(range(0, 9)), list(range(9, 18))
    px.acquire(0, a, 4)
    px.publish(0, 0, a[0:4])
    px.publish(0, 1, a[4:8])
    px.release(0)
    px.acquire(0, b, 4)                      # needs 3 private pages
    px.publish(0, 0, b[0:4])
    px.publish(0, 1, b[4:8])
    px.audit()
    # pool: 4 trie pages + 3 slot-0 pages = 7 used, 1 free; a second full
    # admission (3 pages) must evict a's retained chain — LRU (a is older)
    px.acquire(1, list(range(20, 29)), 4)
    px.audit()
    st = px.stats()
    assert st["evicted_pages"] >= 1
    assert px.acquire is not None            # no exception = admission ok
    # a's chain was the evicted one: b still hits, a misses
    px.release(0)
    px.release(1)
    assert px.acquire(0, b, 4) == 8, "b survived (newer)"
    px.release(0)
    assert px.acquire(0, a, 4) == 0, "a was evicted (older)"
    px.release(0)
    px.audit()


def test_strike_detaches_chain_and_names_every_reader():
    px = _px()
    prompt = list(range(10))
    px.acquire(0, prompt, 4)
    px.publish(0, 0, prompt[0:4])
    px.publish(0, 1, prompt[4:8])
    px.acquire(1, prompt, 4)
    px.acquire(2, prompt[:8] + [60, 61], 4)
    px.acquire(3, list(range(40, 49)), 4)    # unrelated chain
    px.audit()
    readers = px.release(0, strike=True)     # slot 0 poisoned
    assert sorted(readers) == [1, 2], "every reader of the chain, no more"
    px.audit()
    st = px.stats()
    assert st["struck_pages"] == 2 and st["readers_struck"] == 2
    assert st["pages_shared"] == 0, "struck chain unreachable"
    # readers release (the batcher evicts them); struck pages return to
    # the pool only then
    free_before = px.stats()["free_pages"]
    px.release(1)
    px.release(2)
    px.audit()
    assert px.stats()["free_pages"] > free_before
    # a fresh identical admission is COLD: the struck chain cannot serve
    assert px.acquire(0, prompt, 4) == 0
    px.release(0)
    px.release(3)
    px.audit()


def test_min_hit_pages_and_config_validation():
    px = _px(min_hit_pages=2)
    prompt = list(range(10))
    px.acquire(0, prompt, 4)
    px.publish(0, 0, prompt[0:4])
    px.release(0)
    # only 1 page in the trie < min_hit_pages=2: treated as a miss
    assert px.acquire(1, prompt, 4) == 0
    px.release(1)
    px.audit()
    with pytest.raises(ValueError, match="min_hit_pages"):
        PrefixCacheConfig(min_hit_pages=0).validate()


def test_batcher_arming_requires_paged_flat(tiny1, mesh1):
    cfg, params = tiny1
    with pytest.raises(ValueError, match="page_size"):
        ContinuousBatcher(cfg, params, mesh1, s_max=16,
                          prefix_cache=PrefixCacheConfig())
    # prefill=True + prefix cache composes since ISSUE 18: a trie hit
    # ranged-prefills only the divergent suffix (tests/
    # test_ranged_prefill.py pins the byte-identity); the paged-pool
    # requirement stands — shared pages ARE the prior-KV block table
    bt = ContinuousBatcher(cfg, params, mesh1, s_max=16, page_size=4,
                           prefill=True, prefix_cache=PrefixCacheConfig())
    assert bt._px is not None


# ---------------------------------------------------------------------------
# Host tier: the shared-prefix traffic workload
# ---------------------------------------------------------------------------

def test_shared_prefix_draws_isolated_and_fingerprint_stable():
    """Setting the prefix fields changes neither arrival times nor the
    per-request SUFFIX (separate PRNG stream), and an unchanged spec
    keeps its historical fingerprint — the ISSUE 11 field discipline."""
    base = TrafficSpec(rate_rps=5.0, n_requests=12, seed=9)
    rich = dataclasses.replace(base, prefix_pool=3,
                               prefix_len=("fixed", 8), prefix_share=0.5)
    t0, t1 = generate_trace(base), generate_trace(rich)
    n_shared = 0
    for a, b in zip(t0, t1):
        assert a.t_s == b.t_s
        assert a.request.seed == b.request.seed
        if len(b.request.prompt) > len(a.request.prompt):
            n_shared += 1
            assert b.request.prompt[-len(a.request.prompt):] == \
                a.request.prompt, "old prompt becomes the suffix"
            assert len(b.request.prompt) == len(a.request.prompt) + 8
        else:
            assert b.request.prompt == a.request.prompt
    assert 0 < n_shared < 12, "share=0.5 mixes both"
    assert trace_fingerprint(t0) != trace_fingerprint(t1)
    assert trace_fingerprint(t0) == trace_fingerprint(generate_trace(base))


def test_shared_prefix_mix_zipf_and_admissible():
    spec = shared_prefix_mix(s_max=32, rate_rps=5.0, n_requests=60,
                             n_prefixes=4, prefix_tokens=12, zipf=1.5,
                             vocab=64, seed=2)
    trace = generate_trace(spec)
    prefixes = {}
    for a in trace:
        assert len(a.request.prompt) + a.request.max_new_tokens <= 32
        head = tuple(a.request.prompt[:12])
        prefixes[head] = prefixes.get(head, 0) + 1
    counts = sorted(prefixes.values(), reverse=True)
    assert len(prefixes) <= 4
    assert counts[0] > counts[-1], "Zipf skew: a hot prompt dominates"
    with pytest.raises(ValueError, match="exceeds"):
        shared_prefix_mix(s_max=16, rate_rps=1.0, n_requests=1,
                          prefix_tokens=12)
    with pytest.raises(ValueError, match="prefix_share"):
        TrafficSpec(rate_rps=1.0, n_requests=1, prefix_pool=2,
                    prefix_share=0.0).validate()


# ---------------------------------------------------------------------------
# Engine tier: byte-identity + metrics surface (world-1 mesh)
# ---------------------------------------------------------------------------

def _engine(cfg, params, mesh, px, **serving_kw):
    return ServingEngine(
        cfg, params, mesh, s_max=32, clock=retry.FakeClock(),
        serving=ServingConfig(virtual_step_s=0.05, prefix_cache=px,
                              **serving_kw),
        page_size=4,
    )


def test_shared_serving_byte_identical_greedy_and_sampled(tiny1, mesh1):
    """ISSUE 12 acceptance: shared-prefix serving is byte-identical to
    cold prefill — greedy AND seeded sampling — while the hit counters
    show the prefix feed was actually skipped."""
    cfg, params = tiny1
    spec = shared_prefix_mix(s_max=32, rate_rps=10.0, n_requests=12,
                             n_prefixes=2, prefix_tokens=12, vocab=cfg.vocab,
                             seed=3, temperature=0.7, top_k=8)
    trace = generate_trace(spec)

    def run(px):
        eng = _engine(cfg, params, mesh1, px)
        done = eng.serve(trace)
        return done, eng.snapshot()

    cold, _ = run(None)
    warm, snap = run(ServingPrefixCacheConfig())
    assert {u: r.tokens for u, r in cold.items()} == {
        u: r.tokens for u, r in warm.items()
    }
    px = snap["prefix_cache"]
    assert px["hits"] > 0 and px["prefill_tokens_saved"] > 0
    assert px["hit_rate"] > 0.5
    json.dumps(snap)


def test_armed_but_unshared_equals_disarmed(tiny1, mesh1):
    """The arming pin: random (unshared) traffic through an armed engine
    is byte-identical to the disarmed one — tokens AND timestamps (the
    step count cannot change when nothing hits)."""
    cfg, params = tiny1
    spec = TrafficSpec(rate_rps=8.0, n_requests=8,
                       prompt_len=("uniform", 2, 6),
                       output_len=("uniform", 2, 5), vocab=cfg.vocab, seed=5)
    trace = generate_trace(spec)

    def run(px):
        eng = _engine(cfg, params, mesh1, px)
        done = eng.serve(trace)
        snap = eng.snapshot()
        return done, snap

    cold, snap_c = run(None)
    warm, snap_w = run(ServingPrefixCacheConfig())
    assert {u: (r.tokens, r.t_enqueue, r.t_first_token, r.t_finished)
            for u, r in cold.items()} == {
        u: (r.tokens, r.t_enqueue, r.t_first_token, r.t_finished)
        for u, r in warm.items()
    }
    assert snap_w["prefix_cache"]["hits"] == 0
    snap_w.pop("prefix_cache")
    # the same rounds, counted apart: with a trie every round is the plain
    # one (its rows decide what is published), without one a round sends
    # the next step ahead (docs/serving.md "Lookahead")
    rounds_c, rounds_w = snap_c.pop("batcher"), snap_w.pop("batcher")
    assert rounds_c["rounds_ahead"] > 0
    assert rounds_w == dict(rounds_c, rounds_ahead=0, ahead_discarded=0)
    assert snap_c == snap_w, "armed-but-unshared snapshot == disarmed"


def test_ttft_collapses_under_sharing(tiny1, mesh1):
    """The perf claim at host scale: p50 TTFT under a >= 0.9 share ratio
    drops >= 2x vs the cold engine on the same FakeClock trace."""
    cfg, params = tiny1
    spec = shared_prefix_mix(s_max=32, rate_rps=10.0, n_requests=24,
                             n_prefixes=2, prefix_tokens=12,
                             vocab=cfg.vocab, seed=1)
    trace = generate_trace(spec)

    def p50(px):
        eng = _engine(cfg, params, mesh1, px)
        eng.serve(trace)
        snap = eng.snapshot()
        return (snap["latency_ms"]["ttft"]["p50"],
                snap.get("prefix_cache"))

    cold_p50, _ = p50(None)
    warm_p50, px = p50(ServingPrefixCacheConfig())
    assert px["hit_rate"] > 0.8
    assert warm_p50 * 2 <= cold_p50, (cold_p50, warm_p50)


def test_engine_px_counters_survive_rebuild(tiny1, mesh1, monkeypatch):
    """A mid-serve rebuild (step timeout) starts a FRESH trie, but the
    engine accumulates the counters — the hit-rate the snapshot reports
    covers the whole serve, and the replayed requests still finish
    byte-identically."""
    from triton_dist_tpu.resilience.records import DistTimeoutError

    cfg, params = tiny1
    spec = shared_prefix_mix(s_max=32, rate_rps=10.0, n_requests=8,
                             n_prefixes=1, prefix_tokens=12,
                             vocab=cfg.vocab, seed=4)
    trace = generate_trace(spec)
    golden_eng = _engine(cfg, params, mesh1, ServingPrefixCacheConfig())
    golden = golden_eng.serve(trace)
    lookups_clean = golden_eng.snapshot()["prefix_cache"]["lookups"]

    calls = {"n": 0}
    real_step = ContinuousBatcher.step

    def flaky(self):
        calls["n"] += 1
        if calls["n"] == 8:
            raise DistTimeoutError(
                "batcher_step",
                [{"pe": 0, "kind": "barrier_all", "site": 0,
                  "status": "timeout", "expected": 1, "observed": 0,
                  "budget": 10}],
                world_size=1,
            )
        return real_step(self)

    monkeypatch.setattr(ContinuousBatcher, "step", flaky)
    eng = _engine(cfg, params, mesh1, ServingPrefixCacheConfig())
    done = eng.serve(trace)
    assert {u: r.tokens for u, r in done.items()} == {
        u: r.tokens for u, r in golden.items()
    }
    assert eng.rebuilds == 1
    snap = eng.snapshot()
    assert snap["prefix_cache"]["lookups"] >= lookups_clean, (
        "counters accumulate across the rebuild (replays re-admit)"
    )
