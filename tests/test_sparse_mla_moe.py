"""The latent family's SECOND configuration kind (models/mla_moe.py with a
plan of two attention kinds: full layers behind a learned indexer, window
layers with latent widths of their own on rings, headwise gates, the
latent rescale) at toy widths on the CPU, each piece against the plain
reference's equations (perfbench/references/dots_sparse_mla_moe.py,
imported as it stands: it shares no code with the program). The family's
contract and its size are tests/family_tier.py's; this file names the
family and keeps what only it has. Weights are float32 here, so the
tolerances are those of float32 arithmetic reordered (absorbed vs expanded
attention, grouped vs dense expert sums), not of bf16, and the selection
must then be the reference's to the row: a row of ``S(t)`` that differs
moves an output by about 1 / index_topk of a value vector, far over
``TOL`` at top-6, so the logit tests would see ONE; the selection tests
below count them outright.

``MATERIALIZED_UP_TO`` is 0 for the file (``tiled``): the toy buckets run
the tiled kernels and the selection's kernels, which a real run uses past
2048 rows only (one case of the full forward keeps the materialized forms
honest).

Toy geometry: two indexed full layers (a dense MLP, then experts: two, so
that one layer's index keys are not the other's) and a window layer over
experts; page 4, window 9 (no multiple of the page: a ring of 4 pages = 16
rows), index top-6, s_max 64; contexts run to 34, so a ring wraps twice
and a full layer selects 6 of up to 34 rows."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.models import gated_experts, mla_moe
from triton_dist_tpu.models.decode import (
    LatentPagedCacheSpec, WindowPagedKVCacheSpec, ring_pages,
)
from triton_dist_tpu.ops import sparse_index
from triton_dist_tpu.ops.flash_prefill import flash_prefill, xla_flash_prefill
from triton_dist_tpu.ops.mla_decode import (
    _xla_mla_decode, mla_paged_decode, sparse_mla_decode,
)

from family_tier import (  # noqa: F401
    TOL, Family, adapter, family, forward_logits, pytest_generate_tests,
    random_cache, ref,
    served, sized, tiled_kernels_at_toy_buckets, toy,
    test_an_admission_runs_and_writes_the_admitted_slot_only,
    test_a_backlog_is_admitted_a_slot_a_pass,
    test_batcher_prefill_then_decode_matches_reference,
    test_engine_serves_it_and_the_spans_carry_the_counters,
    test_every_part_of_a_pass_says_which_part_it_is,
    test_full_forward_matches_reference,
    test_what_the_kind_cannot_serve_is_refused_by_name,
)
from family_tier import (  # noqa: F401
    test_shares_of_the_bank_add_up_to_the_layer
    as test_the_eight_shares_of_the_bank_add_up_to_the_uncut_layer,
)

WINDOW, PAGE, S_MAX, TOPK = 9, 4, 64, 6
KINDS = ["full_attention", "full_attention", "sliding_attention"]
TOY = sized(dict(
    hidden=64, ffn=128, n_layers=3, n_q_heads=4, n_kv_heads=4, head_dim=8,
    vocab=128, rope_theta=10000.0, norm_eps=1e-5, dtype="float32",
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
    layer_types=KINDS, sliding_window_size=WINDOW,
    swa_num_attention_heads=2, swa_num_key_value_heads=2, swa_q_lora_rank=32,
    swa_kv_lora_rank=32, swa_qk_nope_head_dim=16, swa_qk_rope_head_dim=8,
    swa_v_head_dim=8, swa_rope_theta=500.0,
    attention_gate_type="headwise", swa_attention_gate_type="headwise",
    index_n_heads=4, index_head_dim=16, index_topk=TOPK,
    apply_mla_qkv_lora_rescale=True,
    n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    n_shared_experts=1, first_k_dense_replace=1, routed_scaling_factor=1,
    scoring_func="sigmoid",
    engine=dict(slots=2, s_max=S_MAX, page=PAGE, max_queue=64),
))
SIZES = TOY["sizes"]


def _engine_spans(cfg, params, by_name, requests, eng):
    """``index_rows``, ``selected_rows`` and ``window_rows`` beside the
    routing counters, counted from the lengths: two indexed full layers, a
    window layer, two expert layers."""
    admits = by_name["tdt.batcher.admit_prefill"]
    rounds = by_name["tdt.batcher.decode_round"]
    assert admits and rounds and eng._batcher.rounds_ahead > 0
    tri = lambda n, cap: sum(min(t + 1, cap) for t in range(n))
    lengths = [n for n, _ in requests]
    assert sorted(a["selected_rows"] for a in admits) == sorted(
        2 * tri(n, TOPK) for n in lengths)
    assert sorted(a["window_rows"] for a in admits) == sorted(
        tri(n, WINDOW) for n in lengths)
    assert sorted(a["index_rows"] for a in admits) == sorted(
        2 * tri(n, 10 ** 6) for n in lengths)
    for a in rounds:
        assert a["selected_rows"] <= 2 * 2 * TOPK
        assert a["window_rows"] <= 2 * WINDOW
        assert a["index_rows"] >= a["selected_rows"]
        assert (a["assignments"] + a["assignments_elsewhere"]
                == 2 * cfg.topk * 2)


FAMILY = Family(
    program="tdt_sparse_mla_moe", reference="dots_sparse_mla_moe",
    model=mla_moe, toy=TOY, spec=LatentPagedCacheSpec, tiled=True,
    layer=lambda ref, x, w, li, control, block: ref.layer(x, w, li, SIZES),
    pack=lambda adapter, w, cfg, li: adapter.pack_layer(
        w, cfg, cfg.attention_kinds[li]),
    # absorbed decode steps through 16-row rings, ragged positions:
    # "first" decodes across the ring's first wrap; "readmitted" lands on
    # the slot whose ring and index keys hold the stale rows of "short",
    # its own prefill wraps the ring and its steps wrap it again (contexts
    # to 34: selections of 6 among up to 34 rows). Two buckets, 16 and 32:
    # a bucket more is a program more
    cases={"first": (13, 12, 16), "short": (9, 5), "readmitted": (27, 7, 32)},
    forward={"tiled": (16, 0), "materialized": (16, 2048)},
    admissions=((-1, 21, 32),),
    pools={"lat": "block_table", "idx": "block_table",
           "lat_win": "block_table_win"},
    # the configuration kind's row of the table of scopes: JoyAI's row and
    # the indexer, the gate and the tiled prefill
    scopes=frozenset({
        "attn", "attn/qkv", "attn/kv_write", "attn/out", "attn/index",
        "attn/gate", "ffn", "ffn/gate_up", "ffn/act", "ffn/down", "ffn/route",
        "ffn/experts", "ffn/shared", "head"}),
    admission_scopes=frozenset({"attn/prefill"}),
    shares=8,
    uncut=lambda ref, x, m, w: (
        ref.experts_part(m, ref.combine_weights(x, w, False), w, False)
        + ref.shared_part(m, w, False)),
    refused=("ranged prefill",), refusal_says=("latent cache kind",),
    engine=dict(requests=[(12, 6), (9, 5)], kw=dict(lookahead=True),
                check=_engine_spans),
)


def test_plan_geometries_specs_and_pools(toy):
    cfg, params, _, _ = toy
    assert mla_moe.layer_kinds(cfg) == (
        ("full", "dense"), ("full", "moe"), ("window", "moe"))
    # the half of the plan the accepted adapter reads stays what it was
    assert mla_moe.layer_plan(cfg) == ("dense", "moe", "moe")
    full, win = cfg.geometry("full"), cfg.geometry("window")
    assert (full.n_heads, full.head_dim, full.row, full.indexed,
            full.window) == (4, 16, 128, True, None)
    assert (win.n_heads, win.head_dim, win.row, win.indexed,
            win.window) == (2, 24, 128, False, WINDOW)
    assert cfg.pass_counters == gated_experts.MOE_STATS + mla_moe.SPARSE_STATS
    assert (cfg.own_passes, cfg.cache_kind) == (True, "latent")
    init = mla_moe.init_mla_moe_params(jax.random.PRNGKey(0), cfg)
    assert jax.tree.map(jnp.shape, init) == jax.tree.map(jnp.shape, params)
    assert "wi_q" in params["layers"][0] and "wi_q" not in params["layers"][2]
    spec = LatentPagedCacheSpec(S_MAX, PAGE, static_table=True)
    cache = jax.eval_shape(lambda: spec.init(cfg, 1))
    ring = spec.ring(cfg)
    assert ring == 4 == ring_pages(WINDOW, PAGE, S_MAX)
    assert {k: v.shape for k, v in cache.items()} == {
        "lat": (2, 2 * 16, PAGE, 128), "idx": (2, 2 * 16, PAGE, 16),
        "lat_win": (1, 2 * ring, PAGE, 128), "block_table": (1, 2, 16),
        "block_table_win": (1, 2, ring), "n_alloc": (1,)}
    # the ring arithmetic lives once: the k/v window kind rings alike
    assert WindowPagedKVCacheSpec(S_MAX, PAGE, static_table=True).ring(
        dataclasses.replace(cfg, window=WINDOW)) == ring
    # a plain plan (every layer full, no indexer) holds the one pool
    plain = mla_moe.MLAMoEConfig(
        hidden=64, n_layers=2, n_q_heads=4, n_kv_heads=4, head_dim=16,
        batch=2)
    assert set(jax.eval_shape(lambda: spec.init(plain, 1))) == {
        "lat", "block_table", "n_alloc"}
    assert plain.pass_counters == gated_experts.MOE_STATS


def test_the_selection_is_the_references_to_the_row(toy, ref):
    """The rows of ``S(t)`` that differ from the reference's, counted: an
    admission's mask (the prefill kernel and the bisected threshold) and a
    step's (the paged kernel and the same threshold) against ``reference.selection``
    on the same float32 operands: none differs."""
    cfg, _, plain, _ = toy
    T = 32
    h = jax.random.normal(jax.random.PRNGKey(5), (T, cfg.hidden), jnp.float32)
    w = plain[1]
    c_q = jax.random.normal(jax.random.PRNGKey(6), (T, 32), jnp.float32)
    q_i, k_i, w_i = ref.index_scores(h, c_q, w, False, SIZES)
    want = np.asarray(ref.selection(q_i, k_i, w_i, jnp.arange(T), TOPK))
    got = np.asarray(sparse_index.selection_mask(
        q_i.reshape(T, -1), w_i, k_i, TOPK, g=4, scale=cfg.index_scale)) != 0
    assert (got != want).sum() == 0 and want.sum(-1).max() == TOPK
    assert (want.sum(-1)[:TOPK] == np.arange(1, TOPK + 1)).all()
    # a step: the last row's keys in scattered pages of a pool, layer 1
    table = jnp.array([[5, 1, 7, 0, 3, 6, 2, 4]], jnp.int32)
    pool = jnp.zeros((2, 8, PAGE, 16)).at[1, table[0]].set(
        k_i.reshape(8, PAGE, 16))
    for length in (T, 19, 3):
        lens = jnp.array([length], jnp.int32)
        scores = sparse_index.index_scores_paged(
            q_i[length - 1][None], w_i[length - 1][None], pool, 1, lens,
            table, scale=cfg.index_scale)
        got = np.asarray(sparse_index.topk_mask(scores, TOPK))[0]
        assert (got != want[length - 1]).sum() == 0 or length != T
        sub = np.asarray(ref.selection(
            q_i[length - 1][None], k_i[:length], w_i[length - 1][None],
            jnp.array([length - 1]), TOPK))[0]
        assert (got[:length] != sub).sum() == 0 and not got[length:].any()


def test_the_selection_equals_top_k_with_planted_ties():
    """``topk_mask`` keeps the set ``jax.lax.top_k`` picks: ties at the
    threshold go to the lower position; rows with fewer finite scores than
    ``topk`` keep them all."""
    rng = np.random.default_rng(3)
    s = rng.integers(-3, 4, (16, 40)).astype(np.float32)     # ties abound
    s[0] = 1.0                                               # one value
    s[1, 5:] = -np.inf                                       # 5 finite
    s[2] = np.where(np.arange(40) % 2 == 0, -0.0, 0.0)       # signed zeros
    for topk in (1, 7, 40):
        want = np.zeros(s.shape, bool)
        _, idx = jax.lax.top_k(jnp.asarray(s), topk)
        np.put_along_axis(want, np.asarray(idx), True, -1)
        want &= np.isfinite(s)
        got = np.asarray(sparse_index.topk_mask(jnp.asarray(s), topk))
        np.testing.assert_array_equal(got, want)
    got = np.asarray(sparse_index.topk_mask(jnp.asarray(s), 7))
    assert list(np.flatnonzero(got[0])) == list(range(7))    # lower first
    assert list(np.flatnonzero(got[1])) == list(range(5))


def test_a_top_k_no_smaller_than_the_context_is_the_dense_result(toy):
    """``index_topk >= context``: the indexed layers give what the same
    weights give with no indexer at all, at prefill and through a step's
    masked walk."""
    cfg, params, _, _ = toy
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 16), 0, cfg.vocab)
    wide = dataclasses.replace(cfg, index_topk=S_MAX)
    none = dataclasses.replace(cfg, index_topk=0, index_n_heads=0)
    got = forward_logits(FAMILY, wide, params, tokens)
    want = forward_logits(FAMILY, none, params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    narrow = forward_logits(FAMILY, cfg, params, tokens)
    assert np.abs(np.asarray(narrow) - np.asarray(want)).max() > 1e-2
    # a step over one layer's pools
    spec = LatentPagedCacheSpec(S_MAX, PAGE, static_table=True)
    rng = np.random.default_rng(4)
    cache = random_cache(cfg, spec, rng)
    b, geo = cfg.batch, cfg.geometry("full")
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    row, q = f(b, geo.row), f(b, geo.n_heads, geo.row)
    index = (f(b, 16), f(b, 4, 16), f(b, 4))
    pos = jnp.array([37, 9], jnp.int32)
    kw = dict(d_v=geo.kv_rank, scale=0.25, interpret=True)
    dense, _ = spec.write_and_attend(none, cache, "full", 1, row, q, pos, **kw)
    sparse, after = spec.write_and_attend(
        wide, cache, "full", 1, row, q, pos, index=index, **kw)
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)
    # the index key landed beside the latent row, in the slot's own page
    page = np.asarray(cache["block_table"])[0, 0, 37 // PAGE]
    np.testing.assert_array_equal(
        np.asarray(after["idx"])[1, page, 37 % PAGE], np.asarray(index[0][0]))


@pytest.mark.parametrize("lens", [[29, 64, 1, 0], [13, 16, 17, 9]])
def test_ring_decode_kernel_against_its_twin(lens):
    """The window form of the latent decode over a ring of 4 pages of 8
    rows, window 19: lengths inside the first lap, at a wrap, after
    several laps, empty; stale rows everywhere else."""
    rng = np.random.default_rng(1)
    b, ring, page, row, d_v, heads, window = 4, 4, 8, 128, 96, 3, 19
    pool = jnp.asarray(rng.standard_normal((2, b * ring, page, row)), jnp.float32)
    table = jnp.asarray(rng.permutation(b * ring).reshape(b, ring), jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, heads, row)), jnp.float32)
    lens = jnp.asarray(lens, jnp.int32)
    got = mla_paged_decode(q, pool, 1, lens, table, d_v=d_v, scale=0.1,
                           window=window, interpret=True)
    want = _xla_mla_decode(q, pool, 1, lens, table, d_v=d_v, scale=0.1,
                           window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # ... and against plain attention over the rows the ring holds
    for i, n in enumerate(np.asarray(lens)):
        if n == 0:
            assert not np.asarray(got[i]).any()
            continue
        at = np.arange(max(n - window, 0), n)
        rows = np.asarray(pool)[1][np.asarray(table)[i, (at // page) % ring],
                                   at % page]
        s = np.asarray(q[i]) @ rows.T * 0.1
        p = np.exp(s - s.max(-1, keepdims=True))
        np.testing.assert_allclose(
            np.asarray(got[i]), (p / p.sum(-1, keepdims=True)) @ rows[:, :d_v],
            rtol=1e-4, atol=1e-4)


def test_sparse_decode_and_flash_prefill_forms_against_plain_attention():
    """The paged decode under a selection of positions, scattered pages,
    ragged lengths, a chunk with no row kept; the tiled prefill
    with a value width of its own, a selection of keys and the kernel's
    name."""
    rng = np.random.default_rng(2)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    b, pages, page, row, d_v = 3, 8, 8, 128, 64
    q, pool = f(b, 5, row), f(2, b * pages, page, row)
    table = jnp.asarray(rng.permutation(b * pages).reshape(b, pages), jnp.int32)
    lens = jnp.array([64, 23, 0], jnp.int32)
    keep = rng.random((b, pages * page)) < 0.3
    keep[0, 16:48] = False                  # a whole grid step unselected
    keep[:, 0] = True
    got = np.asarray(sparse_mla_decode(q, pool, 1, lens, table, jnp.asarray(keep),
                                       d_v=d_v, scale=0.2, interpret=True))
    want = _xla_mla_decode(q, pool, 1, lens, table, d_v=d_v, scale=0.2,
                           keep=jnp.asarray(keep))
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-5)
    for i, n in enumerate(np.asarray(lens)):
        if n == 0:
            assert not got[i].any()
            continue
        at = np.flatnonzero(keep[i, :n])
        rows = np.asarray(pool)[1][np.asarray(table)[i, at // page], at % page]
        s = np.asarray(q[i]) @ rows.T * 0.2
        p = np.exp(s - s.max(-1, keepdims=True))
        np.testing.assert_allclose(
            got[i], (p / p.sum(-1, keepdims=True)) @ rows[:, :d_v],
            rtol=1e-4, atol=1e-4)
    n, L, h, d, dv = 2, 32, 3, 24, 8
    q, k, v = f(n, L, h, d), f(n, L, h, d), f(n, L, h, dv)
    lens = jnp.array([L, 21], jnp.int32)
    keep = jnp.asarray(rng.random((n, L, L)) < 0.4) | jnp.eye(L, dtype=bool)
    for window, mask in ((None, None), (5, None), (None, keep.astype(jnp.int8))):
        got = flash_prefill(q, k, v, lens, window=window, keep=mask,
                            block_q=8, block_k=16, interpret=True,
                            name="mla_flash_prefill")
        want = xla_flash_prefill(q, k, v, lens, window, mask)
        assert got.shape == (n, L, h * dv)
        for i, m in enumerate(np.asarray(lens)):
            np.testing.assert_allclose(np.asarray(got[i, :m]),
                                       np.asarray(want[i, :m]), **TOL)
    text = str(jax.make_jaxpr(lambda *a: flash_prefill(
        *a, lens, window=5, interpret=True, name="mla_flash_prefill"))(q, k, v))
    assert "name=mla_flash_prefill_w5" in text
    with pytest.raises(ValueError, match="selection"):
        flash_prefill(q, k, v, lens, window=5, keep=keep.astype(jnp.int8))
