"""Ranged prefill, batcher tier (split from test_ranged_prefill.py, see its
docstring): prefix-cache admission under ``prefill=True`` is byte-identical
to token-fed admission, and an armed chunk limit that never triggers to the
disarmed prefill batcher (chunked vs token-fed vs bulk is
test_prefill_work.py)."""

from triton_dist_tpu.models.prefix_cache import PrefixCacheConfig
from ranged_helpers import _bt_run, _mk, bt_prompts, model1


def test_px_prefill_admission_byte_identity(mesh2, model1, bt_prompts):
    """Prefix-cache admission under prefill=True: trie hit (ranged suffix
    pass), trie miss (whole-prompt ranged pass), and cold token-fed
    admission are one byte-identity class — greedy tokens equal across
    all three batchers, and the hit actually skipped fed tokens."""
    p1, p2 = bt_prompts
    reqs = lambda: [_mk("a", p1), _mk("b", p1), _mk("c", p2)]
    o_pxp, bt_pxp = _bt_run(
        model1, mesh2, reqs(), page_size=4,
        prefix_cache=PrefixCacheConfig(), prefill=True,
    )
    o_pxt, _ = _bt_run(
        model1, mesh2, reqs(), page_size=4, prefix_cache=PrefixCacheConfig()
    )
    o_tok, _ = _bt_run(model1, mesh2, reqs(), page_size=4)
    assert o_pxp == o_pxt == o_tok
    stats = bt_pxp.prefix_cache_stats()
    assert stats["hits"] >= 2 and stats["prefill_tokens_saved"] > 0


def test_px_prefill_sampled_byte_identity(mesh2, model1, bt_prompts):
    """Seeded-sampled byte-identity: the ranged-suffix hit admission must
    reproduce the token-fed sampled stream exactly (same per-request
    RNG), and hit ≡ miss for identical requests."""
    p1, _ = bt_prompts
    sreqs = lambda: [
        _mk("a", p1, temperature=0.8, seed=3),
        _mk("b", p1, temperature=0.8, seed=3),
    ]
    s_pxp, _ = _bt_run(
        model1, mesh2, sreqs(), page_size=4,
        prefix_cache=PrefixCacheConfig(), prefill=True,
    )
    s_pxt, _ = _bt_run(
        model1, mesh2, sreqs(), page_size=4, prefix_cache=PrefixCacheConfig()
    )
    assert s_pxp == s_pxt
    assert s_pxp["a"] == s_pxp["b"]  # hit-path tokens ≡ miss-path tokens


def test_chunked_armed_untriggered_byte_identity(mesh2, model1, bt_prompts):
    """prefill_chunk_tokens >= every prompt length: armed but never
    triggered must be byte-identical to the disarmed prefill batcher
    (including the work counter — no chunk pass ever ran)."""
    p1, _ = bt_prompts
    u_on, bt_u = _bt_run(
        model1, mesh2, [_mk("a", p1)], prefill=True, prefill_chunk_tokens=16
    )
    u_off, bt_d = _bt_run(model1, mesh2, [_mk("a", p1)], prefill=True)
    assert u_on == u_off
    assert bt_u.prefill_work_total == bt_d.prefill_work_total
