"""Ranged prefill, batcher tier (split from test_ranged_prefill.py, see its
docstring): prefix-cache admission under ``prefill=True`` and chunked
prefill are byte-identical to token-fed admission."""

from triton_dist_tpu.models.prefix_cache import PrefixCacheConfig
from ranged_helpers import _bt_run, _mk, bt_prompts, model


def test_px_prefill_admission_byte_identity(mesh4, model, bt_prompts):
    """Prefix-cache admission under prefill=True: trie hit (ranged suffix
    pass), trie miss (whole-prompt ranged pass), and cold token-fed
    admission are one byte-identity class — greedy tokens equal across
    all three batchers, and the hit actually skipped fed tokens."""
    p1, p2 = bt_prompts
    reqs = lambda: [_mk("a", p1), _mk("b", p1), _mk("c", p2)]
    o_pxp, bt_pxp = _bt_run(
        model, mesh4, reqs(), page_size=4,
        prefix_cache=PrefixCacheConfig(), prefill=True,
    )
    o_pxt, _ = _bt_run(
        model, mesh4, reqs(), page_size=4, prefix_cache=PrefixCacheConfig()
    )
    o_tok, _ = _bt_run(model, mesh4, reqs(), page_size=4)
    assert o_pxp == o_pxt == o_tok
    stats = bt_pxp.prefix_cache_stats()
    assert stats["hits"] >= 2 and stats["prefill_tokens_saved"] > 0


def test_px_prefill_sampled_byte_identity(mesh4, model, bt_prompts):
    """Seeded-sampled byte-identity: the ranged-suffix hit admission must
    reproduce the token-fed sampled stream exactly (same per-request
    RNG), and hit ≡ miss for identical requests."""
    p1, _ = bt_prompts
    sreqs = lambda: [
        _mk("a", p1, temperature=0.8, seed=3),
        _mk("b", p1, temperature=0.8, seed=3),
    ]
    s_pxp, _ = _bt_run(
        model, mesh4, sreqs(), page_size=4,
        prefix_cache=PrefixCacheConfig(), prefill=True,
    )
    s_pxt, _ = _bt_run(
        model, mesh4, sreqs(), page_size=4, prefix_cache=PrefixCacheConfig()
    )
    assert s_pxp == s_pxt
    assert s_pxp["a"] == s_pxp["b"]  # hit-path tokens ≡ miss-path tokens


def test_chunked_prefill_byte_identity(mesh4, model, bt_prompts):
    """Chunked admission (prefill_chunk_tokens) vs token-fed vs bulk
    prefill: one byte-identity class — and the swept-work counter prices
    the chunk strips strictly below the bulk bucket rectangle."""
    p1, p2 = bt_prompts
    reqs = lambda: [_mk("a", p1), _mk("c", p2)]
    c_on, bt_on = _bt_run(
        model, mesh4, reqs(), prefill=True, prefill_chunk_tokens=3
    )
    c_tok, _ = _bt_run(model, mesh4, reqs())
    c_off, bt_off = _bt_run(model, mesh4, reqs(), prefill=True)
    assert c_on == c_tok == c_off
    # 8-token prompt: bulk = 8×8 rectangle; chunks (0,3)(3,6)(6,8) sweep
    # 4·3 + 4·6 + 2·8 = 52 pairs — chunking does strictly less work
    assert bt_on.prefill_work_total == 2 * 52
    assert bt_off.prefill_work_total == 2 * 64
    assert bt_on.prefill_tokens_total == bt_off.prefill_tokens_total == 16


