"""The state-space / attention family (models/ssm_hybrid.py) at toy widths
on the CPU, its TWO PLANS as two descriptors of one file (``FAMILIES``):
``mamba`` (3 layers: Mamba-1 at 0 and 2, so that one layer's state is not
the other's, attention at 1; page 4; a dense MLP a layer) against
perfbench/references/jamba_ssm_hybrid.py, and ``mamba2-experts`` (the same
three layers with Mamba-2 mixers, 8 experts top-3 with a shared expert, the
four published scalars none of them 1; ONE period and not two: a second
costs the file 100 s of interpreted steps, and the published plan's test
below numbers ten layers) against
perfbench/references/granite_ssd_moe.py; each piece against its plain
reference's equations (imported as they stand: they share no code with the
program). The family's contract and its size are tests/family_tier.py's;
this file names the plans and keeps what only they have. Weights are float32 here, so the tolerances are
those of float32 arithmetic reordered (a state held ``[N, d]`` for
``[d, N]``, online for whole softmax, a packed gate/up), not of bf16."""

import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.models import Request, ssm_hybrid
from triton_dist_tpu.models.decode import (
    PAGED_CACHE_KINDS, StatePagedKVCacheSpec,
)

from family_tier import (  # noqa: F401
    PERFBENCH, TOL, Family, Recording, _ref_logits, adapter, admit, cells,
    family, make_batcher, only_for, prompt_of, pytest_generate_tests,
    random_cache, ref, sampled_rows_match, served, sized,
    tiled_kernels_at_toy_buckets, toy,
    test_a_step_sent_in_vain_serves_the_plain_rounds_tokens,
    test_shares_of_the_bank_add_up_to_the_layer,
    test_a_backlog_is_admitted_a_slot_a_pass,
    test_batcher_prefill_then_decode_matches_reference,
    test_decode_step_twice_on_the_same_inputs_is_decode_step_once,
    test_every_part_of_a_pass_says_which_part_it_is,
    test_token_fed_admission_matches_reference,
)
from family_tier import (  # noqa: F401
    test_engine_serves_it_and_the_spans_carry_the_counters
    as test_engine_serves_it_rebuilds_and_the_spans_carry_the_counters,
    test_what_the_kind_cannot_serve_is_refused_by_name
    as test_what_a_slots_state_cannot_serve_is_refused_by_name,
)

# (the package exports a function under the module's name)
fd = importlib.import_module("triton_dist_tpu.ops.flash_decode")
ss = importlib.import_module("triton_dist_tpu.ops.selective_scan")
ssd = importlib.import_module("triton_dist_tpu.ops.ssd")

PAGE, S_MAX = 4, 32
TOY = sized(dict(
    hidden=32, ffn=64, n_layers=3, n_q_heads=4, n_kv_heads=1, head_dim=8,
    vocab=64, rope_theta=None, norm_eps=1e-6, dtype="float32",
    attn_layer_period=2, attn_layer_offset=1, mamba_expand=2,
    mamba_d_state=16, mamba_d_conv=4, mamba_dt_rank=4, mamba_conv_bias=True,
    mamba_proj_bias=False, num_experts=1, tie_word_embeddings=True,
    engine=dict(slots=3, s_max=S_MAX, page=PAGE, max_queue=64),
))
SIZES = TOY["sizes"]
PUBLISHED = os.path.join(PERFBENCH, "configs", "ai21-jamba2-3b.json")


def _engine_spans(cfg, params, by_name, requests, eng):
    """The batcher's default of lookahead stayed on; the round's span
    carries ``state_slots`` and ``kv_rows``, the intake's ``state_bytes``."""
    assert eng._batcher.lookahead
    assert [a["state_bytes"] for a in by_name["tdt.batcher.take_params"]] \
        == [cfg.state_bytes()] * 2                  # built, and rebuilt
    rounds = by_name["tdt.batcher.decode_round"]
    assert rounds and all(a["state_slots"] == cfg.batch for a in rounds)
    # 1 attention layer x the lengths the step was given, growing
    assert all(a["kv_rows"] > 0 for a in rounds)
    assert max(a["kv_rows"] for a in rounds) > 3 * 6
    admits = by_name["tdt.batcher.admit_prefill"]
    assert len(admits) >= len(requests) + 1         # and the replayed ones
    assert all((a["state_slots"], a["kv_rows"]) == (1, 0) for a in admits)


FAMILY = Family(
    name="mamba", program="tdt_ssm_hybrid", reference="jamba_ssm_hybrid", model=ssm_hybrid,
    toy=TOY, spec=StatePagedKVCacheSpec,
    layer=lambda ref, x, w, li, control, block: ref.layer(x, w, SIZES),
    # a prompt below its bucket's edge (3 of 4), at it (4 of 4), across it
    # (5 -> 8) and over pages and buckets (13 -> 16, four pages); with 3
    # slots the last two are admitted into slots that served before
    cases={"below": (3, 3), "at": (4, 3), "across": (5, 3), "long": (13, 3),
           "readmitted": (6, 3)},
    # a mixer is ``ssm`` or ``attn`` by the plan, every MLP the dense ``ffn``
    scopes=frozenset({
        "attn", "attn/qkv", "attn/kv_write", "attn/out", "ssm", "ssm/proj",
        "ssm/conv", "ssm/scan", "ffn", "ffn/gate_up", "ffn/act", "ffn/down",
        "head"}),
    refused=("prefix cache", "ranged prefill", "contiguous cache",
             "wider mesh", "wider mesh, the spec", "verify", "the dense step",
             "speculative decoding", "handoff", "scratch page"),
    refusal_says=("kv_state", "one-device"), state_pool="ssm",
    engine=dict(requests=[(6, 5), (9, 4), (3, 5), (5, 3)], rebuild_after=3,
                check=_engine_spans),
)


# -- the second plan: Mamba-2 mixers over an expert bank ----------------------------

TOY2 = sized(dict(
    hidden=32, ffn=16, n_layers=3, n_q_heads=4, n_kv_heads=2, head_dim=8,
    vocab=64, rope_theta=None, norm_eps=1e-5, dtype="float32",
    hidden_size=32, layer_types=["mamba", "attention", "mamba"],
    mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16, mamba_d_conv=4,
    mamba_expand=2, mamba_n_groups=1, mamba_chunk_size=4,
    mamba_conv_bias=True, mamba_proj_bias=False, attention_bias=False,
    tie_word_embeddings=True, position_embedding_type="nope",
    num_local_experts=8, num_experts_per_tok=3, intermediate_size=16,
    shared_intermediate_size=32, embedding_multiplier=3.0,
    residual_multiplier=0.5, attention_multiplier=0.2, logits_scaling=4.0,
    engine=dict(slots=3, s_max=S_MAX, page=PAGE, max_queue=64),
))
SIZES2 = TOY2["sizes"]
PUBLISHED2 = os.path.join(PERFBENCH, "configs", "granite-4.0-h-small-ep4.json")
MOE_COUNTERS = ("experts_hit", "assignments", "expert_load_max",
                "sorted_rows_walked", "combine_rows_gathered")


def _engine_spans2(cfg, params, by_name, requests, eng):
    """The round's and the admission's spans carry the plan's counters:
    ``state_slots``, ``kv_rows``, ``prompt_chunks`` (an admission's: each
    Mamba-2 layer's chunks of 4 rows) and the routing counters; the
    intake's ``state_bytes`` and ``expert_bytes``."""
    assert eng._batcher.lookahead
    assert cfg.pass_counters == (
        "state_slots", "kv_rows", "prompt_chunks") + MOE_COUNTERS
    intake = by_name["tdt.batcher.take_params"]
    assert [a["state_bytes"] for a in intake] == [cfg.state_bytes()] * 2
    assert all(a["expert_bytes"] > 0 for a in intake)
    rounds = by_name["tdt.batcher.decode_round"]
    assert rounds and all(
        (a["state_slots"], a["prompt_chunks"]) == (cfg.batch, 0)
        and a["assignments"] == cfg.batch * cfg.topk * cfg.n_layers
        and 0 < a["experts_hit"] <= cfg.n_experts * cfg.n_layers
        for a in rounds)
    # 1 attention layer x the lengths the step was given, growing
    assert max(a["kv_rows"] for a in rounds) > 3 * 6
    admits = by_name["tdt.batcher.admit_prefill"]
    assert len(admits) >= len(requests) + 1         # and the replayed ones
    for a in admits:
        assert (a["state_slots"], a["kv_rows"]) == (1, 0)
        assert a["prompt_chunks"] == 2 * -(-a["prompt_len"] // 4)


FAMILY2 = Family(
    name="mamba2-experts", program="tdt_ssd_moe", reference="granite_ssd_moe",
    model=ssm_hybrid, toy=TOY2, spec=StatePagedKVCacheSpec,
    layer=lambda ref, x, w, li, control, block: ref.layer(
        x, w, SIZES2, li, control),
    embed=lambda ref, outer, tokens: ref.embed(outer, tokens),
    # the first plan's cases: chunks of 4 rows, so 3 of 4 rows is a partial
    # chunk, 13 -> 16 four chunks of which the last holds one true row
    cases=FAMILY.cases,
    scopes=frozenset({
        "attn", "attn/qkv", "attn/kv_write", "attn/out", "ssm", "ssm/proj",
        "ssm/conv", "ssm/scan", "ssm/norm", "ffn", "ffn/route", "ffn/experts",
        "ffn/shared", "head"}),
    refused=FAMILY.refused, refusal_says=FAMILY.refusal_says,
    state_pool="ssm", shares=4,
    uncut=lambda ref, x, m, w: ref.moe_part(m, w, False),
    engine=dict(requests=[(6, 5), (9, 4), (3, 5), (5, 3)], rebuild_after=3,
                check=_engine_spans2),
)
FAMILIES = (FAMILY, FAMILY2)


@pytest.fixture(scope="module")
def published(adapter):
    config = cells.load_json(PUBLISHED)
    config["sizes"] = {k: config[k] for k in cells.SIZE_KEYS}
    return config, adapter.model_config(config)


# -- (a) the two kernels ---------------------------------------------------------

def _scan_args(rng, L, d=64, n=16):
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (L, d))).astype(np.float32)
    return [jnp.asarray(x) for x in (
        f(L, d), dt, f(L, n), f(L, n), -np.exp(f(n, d)), f(d), f(n, d))]


@pytest.mark.parametrize("length", [5, 64, 70, 133])
def test_selective_scan_against_its_twin_and_the_token_by_token_recurrence(
        ref, length):
    """Lengths below a chunk (64), one chunk, not a multiple of the chunk,
    and over two chunks; the state comes in non-zero."""
    c, dt, b, cm, a, d_skip, h0 = _scan_args(np.random.default_rng(length),
                                             length)
    y, h = ss.selective_scan(c, dt, b, cm, a, d_skip, h0, interpret=True)
    y_x, h_x = ss._xla_selective_scan(c, dt, b, cm, a, d_skip, h0)
    y_r, h_r = ref.recurrence(c, dt, b, cm, a.T, d_skip, h0.T)
    for got, want in ((y, y_x), (h, h_x), (y, y_r), (h, h_r.T)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    # dt = 0 from position 3 on: the state stays where position 2 left it
    stop = dt.at[3:].set(0.0)
    _, h_stop = ss.selective_scan(c, stop, b, cm, a, d_skip, h0, interpret=True)
    _, h_3 = ref.recurrence(c[:3], dt[:3], b[:3], cm[:3], a.T, d_skip, h0.T)
    np.testing.assert_allclose(np.asarray(h_stop), np.asarray(h_3.T),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("stored", ["float32", "bfloat16"])
def test_selective_state_update_against_its_twin_and_the_recurrence(ref, stored):
    """Slots at even and odd positions (either row of the pool's axis of
    2 is read), two at position 0, and a stale state that is not finite
    under one of them; the step's bias and ``D`` come in the dtype they
    are stored in and ``dt = softplus(dt_in + b_dt)`` is the kernel's."""
    rng = np.random.default_rng(1)
    slots, d, n = 6, 64, 16
    c, dt, b, cm, a, d_skip, _ = _scan_args(rng, slots)
    b_dt, d_skip = (jnp.asarray(x, stored) for x in (
        rng.standard_normal(d).astype(np.float32), d_skip))
    wide = lambda x: x.astype(jnp.float32)
    dt_in = jnp.log(jnp.expm1(dt)) - wide(b_dt)
    dt = jax.nn.softplus(dt_in + wide(b_dt))
    pool = rng.standard_normal((3, 2, slots, n, d)).astype(np.float32)
    pos = jnp.asarray([1, 2, 0, 4, 0, 3])
    read = (np.asarray(pos) - 1) % 2
    pool[1, 1, 2] = np.nan
    pool = jnp.asarray(pool)
    y, got = ss.selective_state_update(
        pool, 1, pos, c, dt_in, b_dt, b, cm, a, d_skip, interpret=True)
    y_x, want = ss._xla_state_update(
        pool, 1, pos, c, dt_in, b_dt, b, cm, a, d_skip)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_x), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    for i in range(slots):
        h0 = jnp.zeros((d, n)) if pos[i] == 0 else pool[1, read[i], i].T
        y_r, h_r = ref.recurrence(c[i:i + 1], dt[i:i + 1], b[i:i + 1],
                                  cm[i:i + 1], a.T, wide(d_skip), h0)
        np.testing.assert_allclose(np.asarray(y[i]), np.asarray(y_r[0]),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got[1, 1 - read[i], i]),
                                   np.asarray(h_r.T), rtol=1e-5, atol=1e-5)
    # the other layers, and the rows read, are as they were
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(pool[0]))
    np.testing.assert_array_equal(np.asarray(got[1, 0, 0]),
                                  np.asarray(pool[1, 0, 0]))


# -- (c), (f) what an admission writes -------------------------------------------

def test_a_prompt_shorter_than_its_bucket_leaves_the_state_of_its_length(
        toy, ref):
    """5 tokens in a bucket of 8: layer 0's state is the reference's after
    token 5 (not after 8 rows), at the parity of position 4; its
    convolution ring holds inputs 1..4 at rows 1, 2, 3, 0; the logits are
    row 4's; and the counters say one slot's state was written."""
    cfg, params, plain, outer = toy
    spec = StatePagedKVCacheSpec(S_MAX, PAGE, static_table=True)
    rng = np.random.default_rng(2)
    prompt = prompt_of(rng, cfg, 5)
    cache, last, counters = admit(FAMILY, cfg, params, spec.init(cfg, 1), 1,
                                  prompt, 8)
    x = outer["embed"][jnp.asarray(prompt)].astype(jnp.float32)
    w = plain[0]
    _, h, u = ref.mamba_parts(ref._norm(x, w["norm_in"], 1e-6), w, SIZES, False)
    np.testing.assert_allclose(np.asarray(cache["ssm"][0, 4 % 2, 1]),
                               np.asarray(h.T), **TOL)
    for p in (1, 2, 3, 4):
        np.testing.assert_allclose(np.asarray(cache["conv"][0, p % 4, 1]),
                                   np.asarray(u[p]), **TOL)
    want = _ref_logits(FAMILY, ref, plain, outer, np.array([prompt]))[0, -1]
    np.testing.assert_allclose(np.asarray(last[1]), want, **TOL)
    assert [int(v) for v in counters] == [1, 0]
    # the same prompt in a bucket of its own length, in EVERY slot and with
    # no mask (``generate``'s form): the same state in each
    exact, last, counters = admit(FAMILY, cfg, params, spec.init(cfg, 1),
                                  None, prompt, 5)
    assert [int(v) for v in counters] == [cfg.batch, 0]
    for slot in range(cfg.batch):
        np.testing.assert_allclose(np.asarray(last[slot]), want, **TOL)
        for name in ("ssm", "conv"):
            np.testing.assert_allclose(
                np.asarray(cache[name][:, :, 1]),
                np.asarray(exact[name][:, :, slot]), rtol=1e-5, atol=1e-6)


@only_for("mamba", "mamba2-experts")
def test_an_admission_changes_no_other_slots_state_or_pages(family, toy):
    """Bitwise: slots 0 and 2 hold what they held, in every pool."""
    cfg, params, _, _ = toy
    spec = StatePagedKVCacheSpec(S_MAX, PAGE, static_table=True)
    rng = np.random.default_rng(3)
    before = random_cache(cfg, spec, rng)
    after, _, _ = admit(family, cfg, params, before, 1,
                        prompt_of(rng, cfg, 7), 8)
    others = np.array([0, 2])
    for name in ("ssm", "conv"):
        np.testing.assert_array_equal(
            np.asarray(after[name][:, :, others]),
            np.asarray(before[name][:, :, others]))
        assert not np.array_equal(np.asarray(after[name][:, :, 1]),
                                  np.asarray(before[name][:, :, 1]))
    pages = np.asarray(before["block_table"][0][others]).reshape(-1)
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(after[name][:, pages]),
                                      np.asarray(before[name][:, pages]))


# -- (e) a slot that served before ----------------------------------------------

@only_for("mamba", "mamba2-experts")
def test_a_readmitted_slot_serves_what_a_fresh_batcher_serves(family, toy):
    """The second request lands in slot 0, on the state the first left
    behind there, and serves the tokens it serves in a fresh batcher."""
    cfg, params, _, _ = toy
    rng = np.random.default_rng(8)
    first = Request(prompt_of(rng, cfg, 9), 3, uid="first")
    second = prompt_of(rng, cfg, 6)
    busy = make_batcher(family, cfg, params)
    busy.submit(first)
    busy.run()
    stale = np.asarray(busy.cache["ssm"][:, :, 0])
    assert stale.any()
    busy.submit(Request(second, 3, uid="second"))
    got = dict(busy.run())["second"]
    fresh = make_batcher(family, cfg, params)
    fresh.submit(Request(second, 3, uid="second"))
    assert got == dict(fresh.run())["second"]
    assert busy.spec.kind == "kv_state"


# -- (g), (h), (j) the published configuration --------------------------------------

def test_the_published_plan_has_attention_at_7_and_21_of_28(published):
    config, cfg = published
    plan = ssm_hybrid.layer_plan(cfg)
    assert len(plan) == 28 == config["num_hidden_layers"]
    assert [i for i, k in enumerate(plan) if k == "attention"] == [7, 21]
    assert plan.count("mamba") == 26
    assert (cfg.own_passes, cfg.cache_kind) == (True, "kv_state")
    assert cfg.pass_counters == ("state_slots", "kv_rows")
    assert ssm_hybrid._numbered(cfg)[7:9] == [("attention", 0), ("mamba", 7)]
    assert ssm_hybrid._numbered(cfg)[21] == ("attention", 1)
    assert (cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.dt_rank) == (
        5120, 16, 4, 160)
    assert (cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim) == (20, 1, 128)


def test_the_published_sizes_count_3_029_337_472_parameters(published):
    """From the program's own shapes (nothing is allocated): the tied head
    is one leaf, counted once."""
    _, cfg = published
    shapes = jax.eval_shape(
        lambda k: ssm_hybrid.init_ssm_hybrid_params(k, cfg),
        jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
        == 3_029_337_472
    assert "lm_head" not in shapes
    mamba, attn = shapes["layers"][0], shapes["layers"][7]
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    assert (count(mamba), count(attn)) == (104_161_472, 76_682_240)
    specs = cfg.param_specs()
    assert jax.tree.structure(jax.tree.map(lambda x: 0, shapes)) == \
        jax.tree.structure(jax.tree.map(
            lambda s: 0, specs, is_leaf=lambda s: not isinstance(s, (dict, list))))


def test_the_kv_pools_hold_the_two_attention_layers_and_the_state_its_slots(
        published):
    config, cfg = published
    eng = config["engine"]
    spec = PAGED_CACHE_KINDS["kv_state"](eng["s_max"], eng["page"],
                                         static_table=True)
    assert PAGED_CACHE_KINDS["kv_state"] is StatePagedKVCacheSpec
    cache = jax.eval_shape(lambda: spec.init(cfg, 1))
    pages = 64 * (2048 // 128)
    assert cache["k"].shape == cache["v"].shape == (2, pages, 1, 128, 128)
    assert cache["ssm"].shape == (26, 2, 64, 16, 5120)
    assert cache["conv"].shape == (26, 4, 64, 5120)
    assert cache["ssm"].dtype == cache["conv"].dtype == jnp.float32
    assert set(spec.specs(cfg)) == set(cache)
    state = sum(int(np.prod(cache[k].shape)) * 4 for k in ("ssm", "conv"))
    assert state == cfg.state_bytes() == 64 * 26 * (2 * 16 + 4) * 5120 * 4


# -- (k) a group of 20 on one kv head ----------------------------------------------

def test_a_group_of_20_on_one_kv_head_through_the_paged_kernel(ref):
    """The published attention shape (20 query heads, 1 kv head, width
    128) through ``paged_flash_decode`` interpreted, against the
    reference's attention at each slot's last position."""
    rng = np.random.default_rng(9)
    b, hq, d, page, pages = 3, 20, 128, 8, 4
    sizes = dict(head_dim=d, n_q_heads=hq, n_kv_heads=1)
    lens = np.array([1, 13, 32], np.int32)
    h = 64
    w = {k: jnp.asarray(rng.standard_normal(s).astype(np.float32) * h ** -0.5)
         for k, s in (("wq", (h, hq * d)), ("wk", (h, d)), ("wv", (h, d)))}
    w["wo"] = jnp.eye(hq * d, dtype=jnp.float32)
    x = rng.standard_normal((b, page * pages, h)).astype(np.float32)
    table = rng.permutation(b * pages).reshape(b, pages).astype(np.int32)
    kp = np.zeros((b * pages, 1, page, d), np.float32)
    vp = np.zeros_like(kp)
    q = np.zeros((b, hq, d), np.float32)
    want = np.zeros((b, hq * d), np.float32)
    for i in range(b):
        xi = jnp.asarray(x[i, :lens[i]])
        k, v = np.asarray(xi @ w["wk"]), np.asarray(xi @ w["wv"])
        for p in range(lens[i]):
            kp[table[i, p // page], 0, p % page] = k[p]
            vp[table[i, p // page], 0, p % page] = v[p]
        q[i] = np.asarray(xi[-1] @ w["wq"]).reshape(hq, d)
        want[i] = np.asarray(ref.attention(xi, w, sizes, False))[-1]
    got = fd.paged_flash_decode(
        *(jnp.asarray(a) for a in (q, kp, vp, lens, table)), interpret=True)
    np.testing.assert_allclose(np.asarray(got).reshape(b, -1), want,
                               rtol=1e-4, atol=1e-4)


# -- the second plan's own: Mamba-2 mixers over an expert bank ----------------------

def _ssd_args(rng, L, heads=8, p=8, n=16):
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (L, heads)))
    a = -rng.uniform(1.0, 16.0, heads)
    return [jnp.asarray(x, jnp.float32) for x in (
        f(L, heads * p), dt, a, f(L, n), f(L, n), f(heads))]


def _as_pool_state(h):
    """The reference's ``[heads, P, N]`` as the pool's ``[N, d]``."""
    return np.asarray(h).transpose(2, 0, 1).reshape(h.shape[2], -1)


@only_for("mamba2-experts")
@pytest.mark.parametrize("length,true", [
    (8, 8), (13, 13), (16, 6), (16, 16), (23, 17)])
def test_the_chunked_scan_is_the_token_by_token_recurrence(ref, length, true):
    """Chunks of 4 rows: lengths that are and are not whole chunks, and
    prompts shorter than their rows (``dt`` = 0 past the end, whole dead
    chunks among them): the kernel (interpreted) against its XLA twin and
    against the reference's recurrence ONE TOKEN A STEP; the state is the
    one after the last TRUE row, the dead chunks' rows of ``y`` are zeros."""
    x, dt, a, b, cm, d_skip = _ssd_args(np.random.default_rng(length), length)
    dt = dt.at[true:].set(0.0)
    y, h = ssd.ssd_chunk_scan(x, dt, a, b, cm, d_skip, true, chunk=4,
                              interpret=True)
    y_x, h_x = ssd._xla_chunk_scan(x, dt, a, b, cm, d_skip, 4)
    y_r, h_r = ref.recurrence(x[:true].reshape(true, 8, 8), dt[:true], a,
                              b[:true], cm[:true], d_skip)
    tol = dict(rtol=1e-5, atol=1e-5)
    live = -(-true // 4) * 4
    np.testing.assert_allclose(np.asarray(y[:live]), np.asarray(y_x[:live]), **tol)
    np.testing.assert_allclose(np.asarray(y[:true]),
                               np.asarray(y_r).reshape(true, -1), **tol)
    for got in (h, h_x):
        np.testing.assert_allclose(np.asarray(got), _as_pool_state(h_r), **tol)
    assert not np.asarray(y[live:]).any()


@only_for("mamba2-experts")
@pytest.mark.parametrize("stored", ["float32", "bfloat16"])
def test_the_head_state_update_against_its_twin_and_the_recurrence(ref, stored):
    """Slots at even and odd positions, two at position 0, a stale state
    that is not finite under one of them; the step's bias and ``D`` come a
    HEAD in the dtype they are stored in, and the decay is ``heads``
    exponentials a slot (no ``[N, d]`` operand: the kernel's operands are
    rows)."""
    rng = np.random.default_rng(1)
    slots, heads, p, n = 6, 8, 8, 16
    d = heads * p
    x, dt, a, b, cm, d_skip = _ssd_args(rng, slots)
    dt_bias, d_skip = (jnp.asarray(v, stored) for v in (
        rng.standard_normal(heads).astype(np.float32), d_skip))
    wide = lambda v: v.astype(jnp.float32)
    dt_in = jnp.log(jnp.expm1(dt)) - wide(dt_bias)
    dt = jax.nn.softplus(dt_in + wide(dt_bias))
    pool = rng.standard_normal((3, 2, slots, n, d)).astype(np.float32)
    pos = jnp.asarray([1, 2, 0, 4, 0, 3])
    read = (np.asarray(pos) - 1) % 2
    pool[1, 1, 2] = np.nan
    pool = jnp.asarray(pool)
    y, got = ssd.ssd_state_update(pool, 1, pos, x, dt_in, dt_bias, a, b, cm,
                                  d_skip, interpret=True)
    y_x, want = ssd._xla_update(
        pool, 1, pos, *ssd._step_rows(x, dt_in, dt_bias, a, p), b, cm)
    y_x = y_x + jnp.repeat(wide(d_skip), p) * x
    tol = dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_x), **tol)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)
    for i in range(slots):
        h0 = (jnp.zeros((heads, p, n)) if pos[i] == 0 else
              pool[1, read[i], i].reshape(n, heads, p).transpose(1, 2, 0))
        y_r, h_r = ref.recurrence(
            x[i:i + 1].reshape(1, heads, p), dt[i:i + 1], a, b[i:i + 1],
            cm[i:i + 1], wide(d_skip), h0)
        np.testing.assert_allclose(np.asarray(y[i]),
                                   np.asarray(y_r).reshape(-1), **tol)
        np.testing.assert_allclose(np.asarray(got[1, 1 - read[i], i]),
                                   _as_pool_state(h_r), **tol)
    # the other layers, and the rows read, are as they were
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(pool[0]))
    np.testing.assert_array_equal(np.asarray(got[1, 0, 0]),
                                  np.asarray(pool[1, 0, 0]))


@only_for("mamba2-experts")
def test_a_short_prompt_leaves_the_mamba2_state_of_its_length(family, toy, ref):
    """5 tokens in a bucket of 8 (chunks of 4: the second chunk holds one
    true row): layer 0's state is the reference's after token 5, at the
    parity of position 4; its ring, ``x | B | C`` wide, holds inputs 1..4
    at rows 1, 2, 3, 0; the logits are row 4's; the counters say one slot's
    state, two chunks in each of the two Mamba-2 layers, and every
    assignment of the bucket's rows."""
    cfg, params, plain, outer = toy
    spec = family.make_spec()
    prompt = prompt_of(np.random.default_rng(2), cfg, 5)
    cache, last, counters = admit(family, cfg, params, spec.init(cfg, 1), 1,
                                  prompt, 8)
    assert cache["conv"].shape[-1] == cfg.conv_channels == 64 + 2 * 16
    w = plain[0]
    x = ref.embed(outer, jnp.asarray(prompt))
    _, h, xbc = ref.mamba_parts(ref._norm(x, w["norm_in"], 1e-5), w, SIZES2,
                                False)
    np.testing.assert_allclose(np.asarray(cache["ssm"][0, 4 % 2, 1]),
                               _as_pool_state(h), **TOL)
    for p in (1, 2, 3, 4):
        np.testing.assert_allclose(np.asarray(cache["conv"][0, p % 4, 1]),
                                   np.asarray(xbc[p]), **TOL)
    want = _ref_logits(family, ref, plain, outer, np.array([prompt]))[0, -1]
    np.testing.assert_allclose(np.asarray(last[1]), want, **TOL)
    named = dict(zip(cfg.pass_counters, (int(v) for v in counters)))
    assert (named["state_slots"], named["kv_rows"]) == (1, 0)
    assert named["prompt_chunks"] == 2 * 2
    assert named["assignments"] == 8 * cfg.topk * cfg.n_layers


@only_for("mamba2-experts")
def test_the_sliced_head_gives_the_whole_heads_logits_at_its_rows(toy):
    """A quarter of the vocabulary held (``vocab_held``): the tied head
    over the slice gives the whole head's logits at the held rows."""
    import dataclasses

    cfg, params, _, _ = toy
    x = jax.random.normal(jax.random.PRNGKey(3), (5, cfg.hidden), jnp.float32)
    whole = ssm_hybrid._head(cfg, params, x)
    first, count = 16, 16
    share = dataclasses.replace(cfg, vocab=count, vocab_held=(first, count))
    held = ssm_hybrid._head(
        share, dict(params, embed=params["embed"][first:first + count]), x)
    np.testing.assert_allclose(np.asarray(held),
                               np.asarray(whole[:, first:first + count]), **TOL)
    with pytest.raises(ValueError, match="the slice IS the vocabulary"):
        dataclasses.replace(cfg, vocab_held=(0, count))


@pytest.fixture(scope="module")
def published2(adapter):
    config = cells.load_json(PUBLISHED2)
    config["sizes"] = {k: config[k] for k in cells.SIZE_KEYS}
    return config, adapter.model_config(config)


@only_for("mamba2-experts")
def test_the_published_cut_is_layers_0_to_9_with_attention_at_5(published2):
    config, cfg = published2
    plan = ssm_hybrid.layer_plan(cfg)
    assert (len(plan), config["num_hidden_layers"]) == (10, 40)
    assert plan == ("mamba2",) * 5 + ("attention",) + ("mamba2",) * 4
    assert [i for i, k in enumerate(config["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    assert ssm_hybrid.mlp_kind(cfg) == "experts"
    assert (cfg.own_passes, cfg.cache_kind) == (True, "kv_state")
    assert ssm_hybrid._numbered(cfg)[5:7] == [("attention", 0), ("mamba2", 5)]
    assert (cfg.d_inner, cfg.ssm_heads, cfg.ssm_head_dim, cfg.d_state,
            cfg.d_conv, cfg.ssm_chunk, cfg.conv_channels) == (
        8192, 128, 64, 128, 4, 256, 8448)
    assert (cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 8, 128)
    assert (cfg.n_experts, cfg.topk, cfg.expert_ffn, cfg.held,
            cfg.n_shared_experts * cfg.expert_ffn) == (72, 10, 768, (0, 18), 1536)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (12, 0.22, 1 / 128, 16)


@only_for("mamba2-experts")
def test_the_published_share_counts_2_955_758_208_parameters(published2):
    """From the program's own shapes (nothing is allocated): the tied head
    is one leaf; a Mamba-2 layer with 18 of 72 experts, the attention
    layer, and the pools of 32 slots."""
    config, cfg = published2
    shapes = jax.eval_shape(
        lambda k: ssm_hybrid.init_ssm_hybrid_params(k, cfg),
        jax.random.PRNGKey(0))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    assert count(shapes) == 2_955_758_208 and "lm_head" not in shapes
    assert (count(shapes["layers"][0]), count(shapes["layers"][5])) == (
        291_333_760, 230_989_824)
    eng = config["engine"]
    spec = PAGED_CACHE_KINDS["kv_state"](eng["s_max"], eng["page"],
                                         static_table=True)
    cache = jax.eval_shape(lambda: spec.init(cfg, 1))
    assert cache["ssm"].shape == (9, 2, 32, 128, 8192)
    assert cache["conv"].shape == (9, 4, 32, 8448)
    assert cache["k"].shape == cache["v"].shape == (1, 32 * 128, 8, 128, 128)
    state = sum(int(np.prod(cache[k].shape)) * 4 for k in ("ssm", "conv"))
    assert state == cfg.state_bytes() == 32 * 76_713_984
