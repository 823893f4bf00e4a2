"""The state-space / attention family (models/ssm_hybrid.py) at toy widths
on the CPU (3 layers: Mamba at 0 and 2, so that one layer's state is not
the other's, attention at 1; page 4), each piece against the plain
reference's equations (perfbench/references/jamba_ssm_hybrid.py, imported
as it stands: it shares no code with the program). The family's contract
and its size are tests/family_tier.py's; this file names the family and
keeps what only it has. Weights are float32 here, so the tolerances are
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
    family, make_batcher, prompt_of, pytest_generate_tests, random_cache, ref,
    sampled_rows_match, served, sized, tiled_kernels_at_toy_buckets, toy,
    test_a_step_sent_in_vain_serves_the_plain_rounds_tokens,
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
    program="tdt_ssm_hybrid", reference="jamba_ssm_hybrid", model=ssm_hybrid,
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


def test_an_admission_changes_no_other_slots_state_or_pages(toy):
    """Bitwise: slots 0 and 2 hold what they held, in every pool."""
    cfg, params, _, _ = toy
    spec = StatePagedKVCacheSpec(S_MAX, PAGE, static_table=True)
    rng = np.random.default_rng(3)
    before = random_cache(cfg, spec, rng)
    after, _, _ = admit(FAMILY, cfg, params, before, 1,
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

def test_a_readmitted_slot_serves_what_a_fresh_batcher_serves(toy):
    """The second request lands in slot 0, on the state the first left
    behind there, and serves the tokens it serves in a fresh batcher."""
    cfg, params, _, _ = toy
    rng = np.random.default_rng(8)
    first = Request(prompt_of(rng, cfg, 9), 3, uid="first")
    second = prompt_of(rng, cfg, 6)
    busy = make_batcher(FAMILY, cfg, params)
    busy.submit(first)
    busy.run()
    stale = np.asarray(busy.cache["ssm"][:, :, 0])
    assert stale.any()
    busy.submit(Request(second, 3, uid="second"))
    got = dict(busy.run())["second"]
    fresh = make_batcher(FAMILY, cfg, params)
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
