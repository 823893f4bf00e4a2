"""The window-attention / gated-expert family (models/window_moe.py) at
toy widths on the CPU (a window layer over a dense MLP, a full layer and a
second window layer over experts; window 8, page 4, so a ring of 3 pages;
8 experts, top-2), each piece against the plain reference's equations
(perfbench/references/exaone_window_moe.py, imported as it stands: it
shares no code with the program). The family's contract and its size are
tests/family_tier.py's; this file names the family and keeps what only it
has. Weights are float32 here, so the tolerances are those of float32
arithmetic reordered (banded vs masked attention, online vs whole softmax,
grouped vs dense expert sums), not of bf16: the lower-precision control is
far outside them."""

import dataclasses
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.models import Request, gated_experts, window_moe
from triton_dist_tpu.models.decode import (
    PAGED_CACHE_KINDS, WindowPagedKVCacheSpec,
)
from triton_dist_tpu.models.tp_transformer import _causal_gqa_attention

from family_tier import (  # noqa: F401
    TOL, Family, adapter, family, forward_logits, make_batcher,
    pytest_generate_tests, ref, served, sized, tiled_kernels_at_toy_buckets,
    toy, verdict,
    test_an_admission_runs_and_writes_the_admitted_slot_only,
    test_a_backlog_is_admitted_a_slot_a_pass,
    test_batcher_prefill_then_decode_matches_reference,
    test_every_part_of_a_pass_says_which_part_it_is,
    test_full_forward_matches_reference,
    test_the_lower_precision_control_is_far_outside_the_tolerances,
    test_the_lowered_admission_does_not_grow_with_the_batch,
)
from family_tier import (  # noqa: F401
    test_engine_serves_it_and_the_spans_carry_the_counters
    as test_engine_serves_it_with_lookahead_and_the_spans_carry_the_counters,
    test_shares_of_the_bank_add_up_to_the_layer
    as test_the_eight_shares_of_the_bank_add_up_to_the_uncut_layer,
    test_what_the_kind_cannot_serve_is_refused_by_name
    as test_what_a_ring_cannot_serve_is_refused_by_name,
)

# (the package exports a function under the module's name)
fd = importlib.import_module("triton_dist_tpu.ops.flash_decode")

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "paged_flash_decode_dense.jaxpr.txt")

WINDOW, PAGE, S_MAX = 8, 4, 32
KINDS = ["sliding_attention", "full_attention", "sliding_attention"]
TOY = sized(dict(
    hidden=64, ffn=128, n_layers=3, n_q_heads=4, n_kv_heads=2, head_dim=8,
    vocab=128, rope_theta=10000.0, norm_eps=1e-5, dtype="float32",
    layer_types=KINDS, sliding_windows=[WINDOW, 0, WINDOW],
    sliding_window=WINDOW, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, num_shared_experts=1, first_k_dense_replace=1,
    routed_scaling_factor=2.5, scoring_func="sigmoid", n_group=1,
    topk_group=1, norm_topk_prob=True,
    engine=dict(slots=2, s_max=S_MAX, page=PAGE, max_queue=64),
))
SIZES = TOY["sizes"]


def _admitted(counters, bucket):
    # every expert is held here; an admission reads no key rows
    assert counters[len(gated_experts.MOE_STATS):] == [0, 0, 0]


def _half_the_bank(cfg, params):
    """A SHARE of the toy configuration: 4 of 8 experts held."""
    return dataclasses.replace(cfg, experts_held=(0, 4)), dict(
        params, layers=[
            dict(p, **{k: p[k][:4] for k in ("we_gate_up", "we_down")
                       if k in p})
            for p in params["layers"]])


def _engine_spans(cfg, params, by_name, requests, eng):
    assert eng._batcher.rounds_ahead > 0
    assert by_name["tdt.batcher.take_params"][0]["expert_bytes"] == \
        gated_experts.expert_bytes(params)
    rounds = by_name["tdt.batcher.decode_round"]
    admits = by_name["tdt.batcher.admit_prefill"]
    assert len(admits) == 2 and rounds
    for attrs in rounds:
        # 2 slots x top-2 x 2 expert layers, half the bank held here
        assert attrs["assignments"] + attrs["assignments_elsewhere"] == 2 * 2 * 2
        assert attrs["experts_hit"] <= min(attrs["assignments"], 2 * 4)
        assert attrs["expert_load_max"] <= 2
        # 2 window layers read at most the window, the full layer all
        assert 0 < attrs["window_rows"] <= 2 * 2 * WINDOW
        assert attrs["full_rows"] >= attrs["window_rows"] // 2
        # 2 slots x (the full layer's 8 pages + 2 window layers' rings of
        # 3): the tables' side is capacity; the walk's follows the lengths
        assert attrs["kv_pages_table"] == 2 * (S_MAX // PAGE + 2 * 3)
        assert 0 < attrs["kv_pages_live"] < attrs["kv_pages_table"]
    assert any(a["assignments_elsewhere"] > 0 for a in rounds)
    # both slots live and past the window: 2 layers x 2 slots x 8 rows,
    # while the full layer's rows go on growing
    late = [a for a in rounds if a["window_rows"] == 2 * 2 * WINDOW]
    assert late and max(a["full_rows"] for a in late) > 2 * WINDOW
    for attrs in admits:
        # the admitted slot's rows: bucket x top-2 x 2 expert layers
        assert (attrs["assignments"] + attrs["assignments_elsewhere"]
                == attrs["bucket"] * 2 * 2)
        assert attrs["window_rows"] == attrs["full_rows"] == 0


FAMILY = Family(
    program="tdt_window_moe", reference="exaone_window_moe", model=window_moe,
    toy=TOY, spec=WindowPagedKVCacheSpec,
    layer=lambda ref, x, w, li, control, block: ref.layer(
        x, w, SIZES, li, control),
    # against a ring of 12 positions: contexts below the window throughout;
    # ending AT the window; a prompt shorter than the window decoding past
    # it and across a ring wrap; a prompt longer than the ring, whose
    # prefill wraps, then decoding across the next wrap; a slot re-admitted
    # mid-run (two buckets, 8 and 16: a bucket more is a program more)
    cases={"below": (5, 2), "at": (5, 3), "past": (6, 8, 12),
           "wrapped": (14, 12, 24), "readmitted": (9, 4)},
    # below, at and past the window: the causal square up to the window's
    # edge and not past it, then the band
    forward={"5": (5, None), "8": (8, None), "19": (19, None)},
    # the first or the last slot, a prompt shorter than its bucket and one
    # longer than the ring (12 positions, so its window layers' rows wrap)
    admissions=((0, 5, 8), (-1, 5, 8), (0, 14, 16), (-1, 14, 16)),
    pools={"k_full": "block_table", "v_full": "block_table",
           "k_win": "block_table_win", "v_win": "block_table_win"},
    admitted=_admitted,
    # 32 x top-2 assignments, each of the 8 experts padded to a 128-row
    # block (64 + 8 x 127, rounded up = 1152), whatever the batch (4 slots'
    # rows would be 1280)
    lowered=(32, {128, 1152}),
    # window and full layers are both ``attn``; the toy plan has a dense
    # layer and two expert layers, each with a shared expert
    scopes=frozenset({
        "attn", "attn/qkv", "attn/kv_write", "attn/out", "ffn", "ffn/gate_up",
        "ffn/act", "ffn/down", "ffn/route", "ffn/experts", "ffn/shared",
        "head"}),
    shares=8, uncut=lambda ref, x, m, w: ref.moe_part(m, w, False),
    refused=("prefix cache", "ranged prefill", "contiguous cache",
             "wider mesh", "wider mesh, the spec", "verify",
             "speculative decoding", "handoff", "scratch page"),
    refusal_says=("kv_window", "one-device"),
    engine=dict(requests=[(6, 6), (7, 6)], kw=dict(lookahead=True),
                share=_half_the_bank, check=_engine_spans),
)


def test_the_plan_names_attention_and_mlp_and_the_cache_has_two_lifetimes(toy):
    cfg, params, _, _ = toy
    assert window_moe.layer_plan(cfg) == (
        ("window", "dense"), ("full", "moe"), ("window", "moe"))
    assert (cfg.own_passes, cfg.cache_kind) == (True, "kv_window")
    assert PAGED_CACHE_KINDS["kv_window"] is WindowPagedKVCacheSpec
    assert {"kv", "kv_window", "latent"} <= set(PAGED_CACHE_KINDS)
    from triton_dist_tpu.models.tp_transformer import specs_for

    specs = specs_for(cfg)
    assert jax.tree.structure(jax.tree.map(lambda x: 0, params)) == \
        jax.tree.structure(jax.tree.map(
            lambda s: 0, specs, is_leaf=lambda s: not isinstance(s, (dict, list))))
    init = window_moe.init_window_moe_params(jax.random.PRNGKey(0), cfg)
    assert jax.tree.map(jnp.shape, init) == jax.tree.map(jnp.shape, params)
    assert gated_experts.expert_bytes(params) == 2 * 8 * 3 * 64 * 32 * 4
    spec = WindowPagedKVCacheSpec(S_MAX, PAGE, static_table=True)
    assert 5 < spec.ring(cfg) * PAGE < 14      # (the admissions' lengths)
    assert spec.ring(cfg) == 3                 # ceil(8 / 4) + 1
    cache = spec.init(cfg, 1)
    # one full layer over s_max / page pages a slot, two window layers over
    # the ring
    assert cache["k_full"].shape == (1, 2 * 8, 2, PAGE, 8)
    assert cache["k_win"].shape == (2, 2 * 3, 2, PAGE, 8)
    assert cache["block_table"].shape == (1, 2, 8)
    assert cache["block_table_win"].shape == (1, 2, 3)
    assert set(spec.specs(cfg)) == set(cache)
    # a window as long as the sequence needs no more than the sequence
    assert spec.ring(dataclasses.replace(cfg, window=S_MAX)) == S_MAX // PAGE


def _ring_pools(rng, b, h_kv, d, lens, ring):
    """Contiguous k, v ``[b, h_kv, S_MAX, d]`` and the same rows as a ring
    would hold them after ``lens`` positions (older rows overwritten)."""
    k = rng.standard_normal((b, h_kv, S_MAX, d)).astype(np.float32)
    v = rng.standard_normal((b, h_kv, S_MAX, d)).astype(np.float32)
    table = (np.arange(b)[:, None] * ring + np.arange(ring)[None, :]).astype(np.int32)
    # the pool's pages in another order than the table's
    table = (table * 5) % (b * ring) if np.gcd(5, b * ring) == 1 else table
    kp = np.zeros((b * ring, h_kv, PAGE, d), np.float32)
    vp = np.zeros_like(kp)
    for i in range(b):
        for p in range(lens[i]):
            page = table[i, (p // PAGE) % ring]
            kp[page, :, p % PAGE], vp[page, :, p % PAGE] = k[i, :, p], v[i, :, p]
    return k, v, kp, vp, table


def _masked_decode(q, k, v, lens, window):
    """XLA decode over the contiguous cache with a ``[len - window, len)``
    mask: what a window layer's step has to give."""
    b, hq, d = q.shape
    g = hq // k.shape[1]
    s = np.einsum("bhgd,bhsd->bhgs", q.reshape(b, -1, g, d), k) / np.sqrt(d)
    pos = np.arange(S_MAX)[None, :]
    live = (pos < lens[:, None]) & (pos >= lens[:, None] - window)
    s = np.where(live[:, None, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhgs,bhsd->bhgd", p, v).reshape(b, hq, d)


@pytest.mark.parametrize("fuse_heads", [True, False])
@pytest.mark.parametrize("pages_per_step", [None, 1])
def test_window_kernel_against_masked_xla_decode(fuse_heads, pages_per_step):
    """The window kernel (interpreted) over a RING against a masked decode
    over the whole cache, lengths below, at and past the window and past
    ring wraps; and its XLA twin (the CPU tests' only) the same."""
    rng = np.random.default_rng(3)
    b, hq, h_kv, d, ring = 6, 4, 2, 128, 3
    lens = np.array([1, 5, 8, 13, 24, 32], np.int32)
    k, v, kp, vp, table = _ring_pools(rng, b, h_kv, d, lens, ring)
    q = rng.standard_normal((b, hq, d)).astype(np.float32)
    want = _masked_decode(q, k, v, lens, WINDOW)
    args = [jnp.asarray(x) for x in (q, kp, vp, lens, table)]
    got, lse = fd.paged_flash_decode(
        *args, window=WINDOW, fuse_heads=fuse_heads,
        pages_per_step=pages_per_step, return_lse=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    twin, lse_twin = fd._xla_paged_window_decode(
        *args, window=WINDOW, return_lse=True)
    np.testing.assert_allclose(np.asarray(twin), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_twin),
                               rtol=1e-5, atol=1e-5)


def test_window_kernel_reads_a_slot_of_length_zero_as_nothing():
    rng = np.random.default_rng(4)
    k, v, kp, vp, table = _ring_pools(rng, 2, 2, 128, np.array([0, 3]), 3)
    q = rng.standard_normal((2, 4, 128)).astype(np.float32)
    out, lse = fd.paged_flash_decode(
        *(jnp.asarray(x) for x in (q, kp, vp, np.array([0, 3], np.int32), table)),
        window=WINDOW, return_lse=True, interpret=True)
    assert not np.asarray(out[0]).any() and np.isneginf(np.asarray(lse[0])).all()
    assert np.isfinite(np.asarray(lse[1])).all()


def test_a_window_of_s_max_is_the_dense_familys_attention(toy):
    """Decode: the window kernel with ``window = s_max`` over a full-width
    table gives what the dense family's call (``window=None``) gives on
    the same pools. Prefill: the band with ``window >= L`` gives the dense
    family's causal attention on the same q, k, v."""
    cfg, _, _, _ = toy
    rng = np.random.default_rng(5)
    b, hq, h_kv, d, pages = 3, 4, 2, 128, S_MAX // PAGE
    lens = np.array([2, 17, 32], np.int32)
    kp = rng.standard_normal((b * pages, h_kv, PAGE, d)).astype(np.float32)
    vp = rng.standard_normal(kp.shape).astype(np.float32)
    table = rng.permutation(b * pages).reshape(b, pages).astype(np.int32)
    q = rng.standard_normal((b, hq, d)).astype(np.float32)
    args = [jnp.asarray(x) for x in (q, kp, vp, lens, table)]
    dense = fd.paged_flash_decode(*args, interpret=True)
    whole = fd.paged_flash_decode(*args, window=S_MAX, interpret=True)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(dense),
                               rtol=1e-6, atol=1e-6)
    L = 11
    q4 = jnp.asarray(rng.standard_normal((2, L, hq, 8)), jnp.float32)
    k4 = jnp.asarray(rng.standard_normal((2, L, h_kv, 8)), jnp.float32)
    v4 = jnp.asarray(rng.standard_normal((2, L, h_kv, 8)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(window_moe.banded_attention(q4, k4, v4, S_MAX)),
        np.asarray(_causal_gqa_attention(q4, k4, v4, cfg)), **TOL)


def test_the_dense_walk_and_a_window_of_s_max_agree_on_poisoned_tables():
    """Both forms walk a row's live pages and no others: over a pool whose
    dead pages hold a large finite value, with every table column past a
    row's last live page naming such a page, ``window=None`` and ``window
    = s_max`` give the masked decode over the contiguous cache."""
    from tests.test_flash_decode import _poisoned_pools

    rng = np.random.default_rng(6)
    lens = np.array([1, PAGE, 3 * PAGE + 1, S_MAX], np.int32)
    k, v, kp, vp, table = _poisoned_pools(
        rng, lens, 2, 128, None, page=PAGE, pages=S_MAX // PAGE)
    q = rng.standard_normal((len(lens), 4, 128)).astype(np.float32)
    want = _masked_decode(q, k, v, lens, S_MAX)
    args = [jnp.asarray(x) for x in (q, kp, vp, lens, table)]
    for window in (None, S_MAX, 2 * S_MAX):
        got = fd.paged_flash_decode(*args, window=window, interpret=True)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_the_dense_call_lowers_to_the_parents_program():
    """``window=None``: the jaxpr of ``paged_flash_decode`` at one shape is,
    letter for letter, the one kept under tests/golden/ (regenerated by
    PR 38, which moved the page loop into the kernel on purpose: the file
    is the dense cells' kernel as that PR left it), so a later edit that
    means the window form alone cannot move the dense cells' kernel
    unseen; and the kernels keep the names the benchmark's readers find."""
    b, hq, h_kv, d, page, pages = 4, 8, 2, 128, 16, 8
    q = jax.ShapeDtypeStruct((b, hq, d), jnp.bfloat16)
    pool = jax.ShapeDtypeStruct((b * pages, h_kv, page, d), jnp.bfloat16)
    lens = jax.ShapeDtypeStruct((b,), jnp.int32)
    table = jax.ShapeDtypeStruct((b, pages), jnp.int32)

    def call(window):
        return str(jax.make_jaxpr(
            lambda q, k, v, n, t: fd.paged_flash_decode(
                q, k, v, n, t, return_lse=True, interpret=True, window=window)
        )(q, pool, pool, lens, table))

    with open(GOLDEN) as f:
        assert call(None) + "\n" == f.read()
    windowed = call(2 * page)
    assert "name=paged_flash_decode_w32_fh" in windowed
    assert "name=paged_flash_decode_fh" in call(None)


def test_the_reference_given_a_share_gives_that_shares_part(toy, ref):
    """The reference configured as a share of the bank (experts 2 and 3 of
    8) gives what the program's share gives; the reference's share adds
    the shared expert, the program's does not hold expert 0 and leaves it
    to the share that does."""
    cfg, params, plain, _ = toy
    p, w = params["layers"][1], plain[1]
    h = jax.random.normal(jax.random.PRNGKey(4), (24, cfg.hidden), jnp.float32)
    ref.configure(dict(TOY, num_experts=2, experts_held=[2, 2],
                       published=dict(num_experts=8)))
    try:
        share = dataclasses.replace(cfg, experts_held=(2, 2))
        bank = dict(p, we_gate_up=p["we_gate_up"][2:4], we_down=p["we_down"][2:4])
        y, _ = jax.jit(lambda h, bank: gated_experts.moe_mlp(
            share, h, bank, 8))(h, bank)
        part = ref.moe_part(h, dict(w, **{k: w[k][2:4] for k in (
            "we_gate", "we_up", "we_down")}), False)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(part - ref.shared_part(h, w, False)),
            **TOL)
    finally:
        ref.configure(TOY)


def test_a_sliced_head_is_the_same_rows_of_the_whole_head(toy):
    """``vocab_held``: a share that holds rows 32..95 of embedding and
    head gives, on ids counted from 32, the whole model's logits at those
    rows."""
    cfg, params, _, _ = toy
    first, count = 32, 64
    part_cfg = dataclasses.replace(cfg, vocab=count, vocab_held=(first, count))
    part = window_moe.slice_vocab(params, first, count)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 10), first,
                                first + count)
    whole = forward_logits(FAMILY, cfg, params, tokens)
    got = forward_logits(FAMILY, part_cfg, part, tokens - first)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(whole[..., first:first + count]),
        rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="the slice IS the vocabulary"):
        dataclasses.replace(cfg, vocab_held=(0, 64))


def _serve_one(cfg, params, prompt, n_new, tamper=None):
    batcher = make_batcher(FAMILY, cfg, params)
    if tamper is not None:
        tamper(batcher)
    batcher.submit(Request(list(prompt), n_new, uid="a"))
    batcher.submit(Request(list(prompt[::-1]), n_new, uid="b"))
    return dict(batcher.run())["a"]


def _mispoint_a_ring_page(batcher):
    """Slot 0's second ring page is slot 1's: two requests write and read
    the same window rows."""
    table = np.array(batcher.cache["block_table_win"])
    table[:, 0, 1] = table[:, 1, 1]
    batcher.cache = dict(batcher.cache, block_table_win=jax.device_put(
        table, batcher.cache["block_table_win"].sharding))


@pytest.mark.parametrize("fault", ["none", "ring page mispointed",
                                   "window off by one"])
def test_a_planted_fault_is_not_correct(toy, ref, served, fault):
    """Through ``correct.verdict``, the comparison that decides a cell's
    ``correct``: the sound run (``served``'s) passes the toy limits; a ring
    page pointed at a neighbour's, and a window of 7 for 8, do not."""
    cfg, params, plain, outer = toy
    prompt = served[0]["wrapped"].prompt
    if fault == "none":
        ok, _ = verdict(FAMILY, ref, plain, outer, prompt,
                        served[1]["wrapped"])
        assert ok
        return
    if fault == "window off by one":
        out = _serve_one(dataclasses.replace(cfg, window=WINDOW - 1), params,
                         prompt, 6)
    else:
        out = _serve_one(cfg, params, prompt, 6, _mispoint_a_ring_page)
    ok, numbers = verdict(FAMILY, ref, plain, outer, prompt, out)
    assert not ok
    assert {k for k, (v, lim) in numbers.items() if v > lim} & {
        "max_gap", "mean_gap"}
