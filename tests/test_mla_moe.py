"""The latent-attention / gated-expert family (models/mla_moe.py) at toy
widths on the CPU (a dense layer and an expert layer; page 8), each piece
against the plain reference's equations
(perfbench/references/deepseek_mla_moe.py, imported as it stands: it
shares no code with the program). The family's contract and its size are
tests/family_tier.py's; this file names the family and keeps what only it
has. Weights are float32 here, so the tolerances are those of float32
arithmetic reordered (absorbed vs expanded attention, grouped vs dense
expert sums), not of bf16."""

import jax
import jax.numpy as jnp
import numpy as np

from triton_dist_tpu.models import Request, mla_moe
from triton_dist_tpu.models.decode import LatentPagedCacheSpec
from triton_dist_tpu.ops.moe_utils import select_experts

from family_tier import (  # noqa: F401
    Family, adapter, family, make_batcher, prompt_of, pytest_generate_tests,
    recorded_spans, ref, serve_through_the_engine, served, sized,
    tiled_kernels_at_toy_buckets, toy,
    test_an_admission_runs_and_writes_the_admitted_slot_only,
    test_a_backlog_is_admitted_a_slot_a_pass,
    test_batcher_prefill_then_decode_matches_reference,
    test_every_part_of_a_pass_says_which_part_it_is,
    test_full_forward_matches_reference,
    test_shares_of_the_bank_add_up_to_the_layer,
    test_the_lowered_admission_does_not_grow_with_the_batch,
)
from family_tier import (  # noqa: F401
    test_engine_serves_it_and_the_spans_carry_the_counters
    as test_engine_serves_it_and_the_spans_carry_the_routing_counters,
    test_what_the_kind_cannot_serve_is_refused_by_name
    as test_latent_kind_refuses_what_reads_kv_pools,
)

TOY = sized(dict(
    hidden=64, ffn=128, n_layers=2, n_q_heads=4, n_kv_heads=4, head_dim=8,
    vocab=128, rope_theta=10000.0, norm_eps=1e-6, dtype="float32",
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
    v_head_dim=8, n_routed_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, n_shared_experts=1, first_k_dense_replace=1,
    routed_scaling_factor=2.5, scoring_func="sigmoid", n_group=1, topk_group=1,
    engine=dict(slots=2, s_max=64, page=8, max_queue=64),
))


def _admitted(counters, bucket):
    assert 1 <= counters[2] <= bucket and counters[0] <= 8


def _engine_spans(cfg, params, by_name, requests, eng):
    assert by_name["tdt.batcher.take_params"][0]["expert_bytes"] == \
        mla_moe.expert_bytes(params)
    rounds = by_name["tdt.batcher.decode_round"]
    admits = by_name["tdt.batcher.admit_prefill"]
    assert len(admits) == len(requests) and rounds
    for attrs in rounds:
        # 2 slots x top-2 x 1 expert layer, every expert held here
        assert attrs["assignments"] == 2 * 2
        assert 2 <= attrs["experts_hit"] <= attrs["assignments"]
        assert 1 <= attrs["expert_load_max"] <= 2
    for attrs in admits:
        # the admitted slot's rows: bucket x top-2 x 1 expert layer
        assert attrs["assignments"] == attrs["bucket"] * 2
        assert attrs["experts_hit"] <= 8


FAMILY = Family(
    program="tdt_mla_moe", reference="deepseek_mla_moe", model=mla_moe,
    toy=TOY, spec=LatentPagedCacheSpec,
    layer=lambda ref, x, w, li, control, block: ref.layer(x, w, TOY["sizes"]),
    # ragged positions over 2 slots: "first" decodes across a page's edge
    # (position 16), "readmitted" lands on the slot "short" left
    cases={"first": (11, 7), "short": (5, 3), "readmitted": (9, 4)},
    forward={"12": (12, None)},
    # the first or the last slot, a prompt shorter than its bucket or
    # filling it
    admissions=((0, 5, 8), (-1, 5, 8), (0, 16, 16), (-1, 16, 16)),
    pools={"lat": "block_table"}, admitted=_admitted,
    # 64 x top-2 assignments, each of the 8 experts padded to a 128-row
    # block (128 + 8 x 127, rounded up = 1152), whatever the batch (2
    # slots' rows would be 1280, 4 slots' 1536)
    lowered=(64, {128, 1152}),
    # the toy plan has a dense layer and an expert layer with a shared expert
    scopes=frozenset({
        "attn", "attn/qkv", "attn/kv_write", "attn/out", "ffn", "ffn/gate_up",
        "ffn/act", "ffn/down", "ffn/route", "ffn/experts", "ffn/shared",
        "head"}),
    shares=2,
    uncut=lambda ref, x, m, w: (
        ref.experts_part(m, ref.combine_weights(x, w, False), w, False)
        + ref.shared_part(m, w, False)),
    refused=("contiguous cache", "ranged prefill", "wider mesh",
             "speculative decoding"),
    refusal_says=("latent",),
    engine=dict(requests=[(6, 3), (9, 3), (13, 3)], check=_engine_spans),
)


def test_layer_plan_and_specs(toy):
    cfg, params, _, _ = toy
    assert mla_moe.layer_plan(cfg) == ("dense", "moe")
    assert (cfg.own_passes, cfg.cache_kind) == (True, "latent")
    from triton_dist_tpu.models.tp_transformer import specs_for

    specs = specs_for(cfg)
    assert jax.tree.structure(jax.tree.map(lambda x: 0, params)) == \
        jax.tree.structure(jax.tree.map(
            lambda s: 0, specs, is_leaf=lambda s: not isinstance(s, (dict, list))))
    init = mla_moe.init_mla_moe_params(jax.random.PRNGKey(0), cfg)
    assert jax.tree.map(jnp.shape, init) == jax.tree.map(jnp.shape, params)
    assert mla_moe.expert_bytes(params) == 1 * 8 * 3 * 64 * 32 * 4
    cache = jax.eval_shape(lambda: FAMILY.make_spec().init(cfg, 1))
    assert set(cache) == {"lat", "block_table", "n_alloc"}
    assert cache["lat"].shape == (2, 2 * 8, 8, cfg.latent_row)


def test_absorbed_equals_expanded_on_the_same_cache(toy):
    """(c) decode attention in the absorbed form over a paged latent pool
    = the expanded form's last row, to float32 rounding."""
    cfg, params, _, _ = toy
    p = params["layers"][1]
    L, page = 19, 8
    h = jax.random.normal(jax.random.PRNGKey(3), (L, cfg.hidden), jnp.float32)
    geo = cfg.geometry("full")
    q_n, q_r, c_kv, k_r, _ = mla_moe._mla_project(
        cfg, geo, h, p, jnp.arange(L))
    want = mla_moe.mla_attend_expanded(geo, q_n, q_r, c_kv, k_r, p, 1, L)[-1]
    rows = mla_moe._latent_rows(geo, c_kv, k_r)
    rows = jnp.pad(rows, ((0, 3 * page - L), (0, 0))).reshape(3, page, -1)
    # pages scattered in a pool of 5, layer 1 of 2
    table = jnp.array([[4, 0, 2]], jnp.int32)
    pool = jnp.zeros((2, 5, page, cfg.latent_row), jnp.float32)
    pool = pool.at[1, table[0]].set(rows)
    from triton_dist_tpu.ops.mla_decode import mla_paged_decode

    q_lat = jnp.einsum("bhd,chd->bhc", q_n[-1:], p["wkv_b_k"])
    o_lat = mla_paged_decode(
        mla_moe._latent_rows(geo, q_lat, q_r[-1:]), pool, 1,
        jnp.array([L], jnp.int32), table, d_v=geo.kv_rank,
        scale=geo.head_dim ** -0.5)
    got = jnp.einsum("bhc,chd->bhd", o_lat, p["wkv_b_v"]).reshape(1, -1)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _route_numpy(logits, bias, topk, scale):
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    ids = np.argsort(-(s + bias), axis=-1, kind="stable")[:, :topk]
    chosen = np.take_along_axis(s, ids, -1)
    return chosen / chosen.sum(-1, keepdims=True) * scale, ids


def test_router_against_numpy_twin():
    """(d) sigmoid scores, choice by score + bias, weights from the
    unbiased scores, normalized, times the scaling factor."""
    rng = np.random.default_rng(5)
    logits = rng.permutation(40 * 8).reshape(40, 8).astype(np.float32) / 50 - 3
    bias = (rng.normal(size=8) * 0.5).astype(np.float32)
    w, ids = select_experts(jnp.asarray(logits), 2, scoring="sigmoid",
                            bias=jnp.asarray(bias), scale=2.5)
    w_np, ids_np = _route_numpy(logits, bias, 2, 2.5)
    np.testing.assert_array_equal(np.asarray(ids), ids_np)
    np.testing.assert_allclose(np.asarray(w), w_np, rtol=1e-5)
    # the bias moves the choice ...
    _, ids0 = select_experts(jnp.asarray(logits), 2, scoring="sigmoid")
    assert (np.asarray(ids0) != ids_np).any()
    # ... and never the weight: a bias that changes no choice changes nothing
    flat, _ = select_experts(jnp.asarray(logits), 2, scoring="sigmoid",
                             bias=jnp.full(8, 0.25), scale=2.5)
    plain, _ = select_experts(jnp.asarray(logits), 2, scoring="sigmoid",
                              scale=2.5)
    np.testing.assert_allclose(np.asarray(flat), np.asarray(plain), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(flat).sum(-1), 2.5, rtol=1e-5)
    # the softmax default is what it was
    w_s, _ = select_experts(jnp.asarray(logits), 2)
    np.testing.assert_allclose(np.asarray(w_s).sum(-1), 1.0, rtol=1e-5)


def test_routing_counters_on_a_hand_made_routing():
    """(f) experts_hit, assignments, expert_load_max, and the sorted rows
    the layer's pass walked and the rows its combine gathered as the pass
    hands them over."""
    ids = jnp.array([[0, 3], [3, 5], [3, 0], [7, 3]], jnp.int32)
    st = mla_moe.routing_stats(ids, jnp.ones_like(ids, bool), 8, 48, 8)
    assert [int(x) for x in st] == [4, 8, 4, 48, 8]   # 0,3,5,7; 8; expert 3
    # a share holding experts 4..7: local ids, the rest is elsewhere
    local = ids - 4
    here = (local >= 0) & (local < 4)
    st = mla_moe.routing_stats(jnp.where(here, local, 0), here, 4)
    assert [int(x) for x in st] == [2, 2, 1, 0, 0]    # experts 5 and 7


def test_lookahead_same_tokens_and_counters_round_for_round(toy):
    """``lookahead=True`` on this family: a round's step sent by the round
    before brings its own routing counters with it."""
    from jax import monitoring

    cfg, params, _, _ = toy
    seen = []
    with recorded_spans() as by_name:
        from triton_dist_tpu import obs

        for look in (False, True):
            obs.reset()
            rng = np.random.default_rng(2)
            b = make_batcher(FAMILY, cfg, params, lookahead=look)
            for i, (n_prompt, n_new) in enumerate([(11, 4), (5, 3), (9, 4)]):
                b.submit(Request(prompt_of(rng, cfg, n_prompt), n_new,
                                 uid=f"r{i}"))
            done = dict(b.run())
            rounds = by_name()["tdt.batcher.decode_round"]
            seen.append((done, [
                (a["round"], a["tokens"], a["experts_hit"], a["assignments"],
                 a["expert_load_max"]) for a in rounds]))
            assert sum(a.get("ahead", 0) for a in rounds) == b.rounds_ahead
            assert (b.rounds_ahead > 0) == look and b.ahead_discarded == 0
        # steps sent ahead of steps sent ahead: no program is built anew
        compiled = []
        monitoring.register_event_duration_secs_listener(
            lambda event, *a, **kw: compiled.append(event)
            if event == "/jax/core/compile/backend_compile_duration" else None)
        for i, (n_prompt, n_new) in enumerate([(11, 3), (5, 2)]):
            b.submit(Request(prompt_of(rng, cfg, n_prompt), n_new,
                             uid=f"s{i}"))
        b.run()
        assert not compiled
    assert seen[0] == seen[1]


def test_an_admissions_span_says_how_far_its_sorted_row_pass_went(adapter):
    """``sorted_rows_walked`` on ``tdt.batcher.admit_prefill``: with ONE
    expert layer of 16 experts and a bucket of at most 64 rows no expert
    fills a 128-row block, so the experts hit are the live blocks, and the
    pass walked them and no more (a chunk is one block of 128 rows at
    ``expert_ffn`` 32: ``gated_experts._chunk_blocks``), never the whole
    alignment of 17; a decode round walks its one straight-line call's
    every row, a constant. ``combine_rows_gathered`` beside it: the whole
    bank is held, every slot holds a result, and the combine gathers
    ``topk`` rows a token (the landed walk is a share's:
    tests/test_live_prefix.py reads the counter there)."""
    from triton_dist_tpu.models import gated_experts

    cfg = adapter.model_config(sized(dict(TOY, n_routed_experts=16)))
    params = mla_moe.init_mla_moe_params(jax.random.PRNGKey(5), cfg)
    chunk = gated_experts._chunk_blocks(cfg, gated_experts.PREFILL_BLOCK_M)
    assert chunk == 1 and mla_moe.layer_plan(cfg).count("moe") == 1
    rng = np.random.default_rng(2)
    _, by_name, _ = serve_through_the_engine(FAMILY, cfg, params, [
        Request(prompt_of(rng, cfg, n), 2, uid=f"u{n}") for n in (3, 7, 12)])
    admits = by_name["tdt.batcher.admit_prefill"]
    assert len(admits) == 3
    for attrs in admits:
        t = attrs["bucket"] * cfg.topk
        n_blocks = -(-(t + min(16, t) * 127) // 128)
        assert n_blocks > chunk                 # the chunked pass
        live = attrs["experts_hit"]
        assert attrs["sorted_rows_walked"] == (
            min(-(-live // chunk) * chunk, n_blocks) * 128)
        assert attrs["sorted_rows_walked"] < n_blocks * 128
        assert attrs["combine_rows_gathered"] == t == attrs["assignments"]
    rounds = by_name["tdt.batcher.decode_round"]
    # 2 slots x top-2 = 4 assignments on at most 4 experts: 4 + 4 x 15 rows
    assert rounds and {a["sorted_rows_walked"] for a in rounds} == {64}
    assert {a["combine_rows_gathered"] for a in rounds} == {4}
