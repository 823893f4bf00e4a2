"""The latent-attention / gated-expert family (models/mla_moe.py) at toy
widths on the CPU, each piece against the plain reference's equations
(perfbench/references/deepseek_mla_moe.py, imported as it stands: it
shares no code with the program). Weights are float32 here, so the
tolerances below are those of float32 arithmetic reordered (absorbed vs
expanded attention, grouped vs dense expert sums), not of bf16."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.models import ContinuousBatcher, Request
from triton_dist_tpu.models import mla_moe
from triton_dist_tpu.models.decode import LatentPagedCacheSpec
from triton_dist_tpu.ops.moe_utils import select_experts

PERFBENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "perfbench")
if PERFBENCH not in sys.path:
    sys.path.insert(0, PERFBENCH)
from harness import cells  # noqa: E402

from admission_helpers import (  # noqa: E402
    admission_kernel_operands, check_admission,
)
from scope_helpers import check_pass  # noqa: E402

# float32 everywhere: what is left is the order of the sums
TOL = dict(rtol=2e-4, atol=2e-4)

TOY = dict(
    hidden=64, ffn=128, n_layers=3, n_q_heads=4, n_kv_heads=4, head_dim=8,
    vocab=128, rope_theta=10000.0, norm_eps=1e-6, dtype="float32",
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
    v_head_dim=8, n_routed_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, n_shared_experts=1, first_k_dense_replace=1,
    routed_scaling_factor=2.5, scoring_func="sigmoid", n_group=1, topk_group=1,
    engine=dict(slots=2, s_max=64, page=8, max_queue=64),
)
TOY["sizes"] = {k: TOY[k] for k in cells.SIZE_KEYS}


@pytest.fixture(scope="module")
def ref():
    mod = cells.load_module("references", "deepseek_mla_moe")
    mod.configure(TOY)
    yield mod
    mod.configure(TOY)


@pytest.fixture(scope="module")
def adapter():
    return cells.load_module("programs", "tdt_mla_moe")


@pytest.fixture(scope="module")
def toy(ref, adapter):
    """``(cfg, program params, plain layers, outer)`` from one seed."""
    cfg = adapter.model_config(TOY)
    key = ref.seed_key(7)
    plain = [ref.layer_weights(key, li, TOY["sizes"])
             for li in range(TOY["n_layers"])]
    outer = ref.outer_weights(key, TOY["sizes"])
    params = dict(outer, layers=[adapter.pack_layer(w, cfg) for w in plain])
    return cfg, params, plain, outer


def _ref_logits(ref, plain, outer, tokens):
    """The reference's logits at every position of ``tokens [n, T]``."""
    x = outer["embed"][tokens].astype(jnp.float32)
    for w in plain:
        x = ref.layer(x, w, TOY["sizes"])
    n, t = tokens.shape
    return np.asarray(ref.head(x, outer, jnp.zeros(n, jnp.int32), t,
                               TOY["sizes"], False))


def test_layer_plan_and_specs(toy):
    cfg, params, _, _ = toy
    plan = mla_moe.layer_plan(cfg)
    assert plan == ("dense", "moe", "moe")
    assert (cfg.own_passes, cfg.cache_kind) == (True, "latent")
    from triton_dist_tpu.models.tp_transformer import specs_for

    specs = specs_for(cfg)
    assert jax.tree.structure(jax.tree.map(lambda x: 0, params)) == \
        jax.tree.structure(jax.tree.map(
            lambda s: 0, specs, is_leaf=lambda s: not isinstance(s, (dict, list))))
    init = mla_moe.init_mla_moe_params(jax.random.PRNGKey(0), cfg)
    assert jax.tree.map(jnp.shape, init) == jax.tree.map(jnp.shape, params)
    assert mla_moe.expert_bytes(params) == 2 * 8 * 3 * 64 * 32 * 4


def test_full_forward_matches_reference(toy, ref):
    """(a) the program's expanded forward, grouped GEMMs and all."""
    cfg, params, plain, outer = toy
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab)
    got = mla_moe.forward_logits(cfg, params, tokens)
    np.testing.assert_allclose(
        np.asarray(got), _ref_logits(ref, plain, outer, tokens), **TOL)


class _Recording(Request):
    """A request that keeps every logit row it was sampled from and then
    takes the best token: logits are compared, not tokens."""

    def sample(self, logits, rng):
        self.__dict__.setdefault("rows", []).append(np.array(logits))
        return int(np.argmax(logits))


def test_batcher_prefill_then_decode_matches_reference(toy, ref):
    """(b) prefill into the latent paged pool, then absorbed decode steps,
    ragged positions, a slot re-admitted mid-run: every logit row the
    batcher sampled from against the reference's full forward over the
    same sequence."""
    cfg, params, plain, outer = toy
    mesh = Mesh(np.array(jax.devices()[:1]), (cfg.axis,))
    batcher = ContinuousBatcher(
        cfg, params, mesh, s_max=64, page_size=8, prefill=True)
    assert isinstance(batcher.spec, LatentPagedCacheSpec)
    assert set(batcher.cache) == {"lat", "block_table", "n_alloc"}
    assert batcher.cache["lat"].shape == (3, 2 * 8, 8, cfg.latent_row)
    rng = np.random.default_rng(0)
    reqs = [
        _Recording(list(rng.integers(0, cfg.vocab, n_prompt)), n_new,
                   temperature=1.0, uid=f"r{i}")
        for i, (n_prompt, n_new) in enumerate([(11, 9), (5, 4), (9, 8)])
    ]
    for r in reqs:          # 3 requests over 2 slots: r2 re-uses r1's slot
        batcher.submit(r)
    done = dict(batcher.run())
    assert sorted(done) == ["r0", "r1", "r2"]
    for r in reqs:
        out = done[r.uid]
        assert len(out) == r.max_new_tokens == len(r.rows)
        seq = np.array([list(r.prompt) + out])
        want = _ref_logits(ref, plain, outer, seq)[0]
        first = len(r.prompt) - 1
        np.testing.assert_allclose(
            np.stack(r.rows), want[first:first + len(out)], **TOL)


@pytest.mark.parametrize("length,bucket", [(5, 8), (16, 16)])
@pytest.mark.parametrize("slot", [0, -1])
def test_an_admission_runs_and_writes_the_admitted_slot_only(
        toy, slot, length, bucket):
    """A one-hot mask on the first or the last slot, a prompt shorter than
    its bucket or filling it: the other slot's pages bit-identical, the
    admitted slot's latent rows and logit row the unmasked whole-batch
    pass's, and one slot's rows counted."""
    cfg, params, _, _ = toy
    spec = LatentPagedCacheSpec(64, 8, static_table=True)
    stats = check_admission(
        cfg, params, spec, 64, {"lat": "block_table"}, slot % cfg.batch,
        length, bucket, n_moe=2, tol=TOL, seed=bucket + slot)
    assert 1 <= int(stats[2]) <= bucket and int(stats[0]) <= 2 * 8


def test_the_lowered_admission_does_not_grow_with_the_batch(toy):
    """The grouped GEMMs of an admission read the same operands at 2 slots
    and at 4: one slot's ``bucket x topk`` assignments, aligned."""
    cfg, params, _, _ = toy
    spec = LatentPagedCacheSpec(64, 8, static_table=True)
    two, four = (admission_kernel_operands(cfg, params, spec, 64, 64, b)
                 for b in (2, 4))
    assert len(two) == 2 * 2 and two == four        # 2 GEMMs x 2 expert layers
    # the sorted rows: 64 x top-2 assignments, each of the 8 experts padded
    # to a 128-row block (128 + 8 x 127, rounded up = 1152), whatever the
    # batch (2 slots' rows would be 1280, 4 slots' 1536), walked a chunk of
    # one block at a time (gated_experts._chunk_blocks at expert_ffn 32):
    # a chunk's rows in, and the whole result the down GEMM writes into
    assert {s[0] for call in two for s in call if len(s) == 2} == {128, 1152}


# the family's row of the table of scopes (docs/observability.md): the toy
# plan has a dense layer and two expert layers, each with a shared expert
SCOPES = {"attn", "attn/qkv", "attn/kv_write", "attn/out",
          "ffn", "ffn/gate_up", "ffn/act", "ffn/down", "ffn/route",
          "ffn/experts", "ffn/shared", "head"}


@pytest.mark.parametrize("which", ["step", "admission"])
def test_every_part_of_a_pass_says_which_part_it_is(toy, which):
    """The lowered step and admission carry every scope of the family's
    row and no other ``tdt.`` name, and every matrix product and kernel
    call lies under a part; only the step calls the decode kernel."""
    cfg, params, _, _ = toy
    spec = LatentPagedCacheSpec(64, 8, static_table=True)
    mesh = Mesh(np.array(jax.devices()[:1]), (cfg.axis,))
    check_pass(which, cfg, params, spec, mesh, 64, SCOPES)


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


def test_shares_of_the_bank_add_up_to_the_layer(toy, ref):
    """(e) the guide's share test: the layer run once per share of the
    experts (2 shares of 4), routed parts summed and the shared expert
    counted once, equals the whole layer (program and reference)."""
    import dataclasses

    cfg, params, plain, _ = toy
    p, w = params["layers"][1], plain[1]
    h = jax.random.normal(jax.random.PRNGKey(4), (24, cfg.hidden), jnp.float32)
    whole, stats = mla_moe.moe_mlp(cfg, h, p, 8)
    want = (ref.experts_part(h, ref.combine_weights(h, w, False), w, False)
            + ref.shared_part(h, w, False))
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), **TOL)
    parts, hit = [], 0
    for first in (0, 4):
        # the share that holds expert 0 adds the shared expert
        share = dataclasses.replace(cfg, experts_held=(first, 4))
        bank = dict(p, we_gate_up=p["we_gate_up"][first:first + 4],
                    we_down=p["we_down"][first:first + 4])
        y, st = mla_moe.moe_mlp(share, h, bank, 8)
        parts.append(y)
        hit += int(st[1])
    np.testing.assert_allclose(
        np.asarray(parts[0] + parts[1]), np.asarray(whole), **TOL)
    assert hit == int(stats[1]) == 24 * 2      # every assignment, once


def test_routing_counters_on_a_hand_made_routing():
    """(f) experts_hit, assignments, expert_load_max, and the sorted rows
    the layer's pass walked as the pass hands them over."""
    ids = jnp.array([[0, 3], [3, 5], [3, 0], [7, 3]], jnp.int32)
    st = mla_moe.routing_stats(ids, jnp.ones_like(ids, bool), 8, 48)
    assert [int(x) for x in st] == [4, 8, 4, 48]      # 0,3,5,7; 8; expert 3
    # a share holding experts 4..7: local ids, the rest is elsewhere
    local = ids - 4
    here = (local >= 0) & (local < 4)
    st = mla_moe.routing_stats(jnp.where(here, local, 0), here, 4)
    assert [int(x) for x in st] == [2, 2, 1, 0]       # experts 5 and 7


def test_lookahead_same_tokens_and_counters_round_for_round(toy):
    """``lookahead=True`` on this family: a round's step sent by the round
    before brings its own routing counters with it."""
    from jax import monitoring
    from triton_dist_tpu import config as tdt_config, obs
    from triton_dist_tpu.obs import ObsConfig

    cfg, params, _, _ = toy
    mesh = Mesh(np.array(jax.devices()[:1]), (cfg.axis,))
    before = tdt_config.get_config().obs
    tdt_config.update(obs=ObsConfig(spans=True))
    seen = []
    try:
        for look in (False, True):
            obs.reset()
            rng = np.random.default_rng(2)
            b = ContinuousBatcher(cfg, params, mesh, s_max=64, page_size=8,
                                  prefill=True, lookahead=look)
            for i, (n_prompt, n_new) in enumerate([(11, 7), (5, 4), (9, 6)]):
                b.submit(Request(list(rng.integers(0, cfg.vocab, n_prompt)),
                                 n_new, uid=f"r{i}"))
            done = dict(b.run())
            rounds = [sp.attrs for sp in obs.spans()
                      if sp.name == "tdt.batcher.decode_round"]
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
        for i, (n_prompt, n_new) in enumerate([(11, 7), (5, 4)]):
            b.submit(Request(list(rng.integers(0, cfg.vocab, n_prompt)),
                             n_new, uid=f"s{i}"))
        b.run()
        assert not compiled
    finally:
        tdt_config.update(obs=before)
        obs.reset()
    assert seen[0] == seen[1]


def test_latent_kind_refuses_what_reads_kv_pools(toy):
    cfg, params, _, _ = toy
    mesh = Mesh(np.array(jax.devices()[:1]), (cfg.axis,))
    with pytest.raises(NotImplementedError, match="contiguous cache"):
        ContinuousBatcher(cfg, params, mesh, s_max=64)
    with pytest.raises(NotImplementedError, match="ranged prefill"):
        ContinuousBatcher(cfg, params, mesh, s_max=64, page_size=8,
                          prefill=True, prefill_chunk_tokens=4)
    mesh2 = Mesh(np.array(jax.devices()[:2]), (cfg.axis,))
    with pytest.raises(NotImplementedError, match="wider than one device"):
        ContinuousBatcher(cfg, params, mesh2, s_max=64, page_size=8)
    from triton_dist_tpu.serving.speculative import SpeculativeBatcher

    with pytest.raises(NotImplementedError, match="speculative decoding"):
        SpeculativeBatcher(cfg, params, mesh, s_max=64, page_size=8,
                           spec_decode=None)


def test_engine_serves_it_and_the_spans_carry_the_routing_counters(toy):
    """The toy configuration through ``ServingEngine``: the same entry,
    scheduler and spans as the dense family, with the routing counters on
    the round's and the admission's spans and the banks' bytes on the
    intake's."""
    from triton_dist_tpu import config as tdt_config, obs
    from triton_dist_tpu.obs import ObsConfig
    from triton_dist_tpu.resilience import retry
    from triton_dist_tpu.serving import Arrival, ServingConfig, ServingEngine
    from triton_dist_tpu.serving.engine import Finished

    cfg, params, _, _ = toy
    mesh = Mesh(np.array(jax.devices()[:1]), (cfg.axis,))
    before = tdt_config.get_config().obs
    tdt_config.update(obs=ObsConfig(spans=True))
    obs.reset()
    try:
        clock = retry.FakeClock()
        with retry.clock_scope(clock):
            eng = ServingEngine(
                cfg, params, mesh, s_max=64, page_size=8, prefill=True,
                clock=clock, serving=ServingConfig(virtual_step_s=0.01))
            rng = np.random.default_rng(1)
            done = eng.serve([
                Arrival(0.0, Request(list(rng.integers(0, cfg.vocab, n)), 3,
                                     uid=f"u{n}"))
                for n in (6, 9, 13)])
        assert all(isinstance(done[f"u{n}"], Finished) for n in (6, 9, 13))
        spans = obs.spans()
    finally:
        tdt_config.update(obs=before)
        obs.reset()
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp.name, []).append(sp.attrs)
    assert by_name["tdt.batcher.take_params"][0]["expert_bytes"] == \
        mla_moe.expert_bytes(params)
    rounds = by_name["tdt.batcher.decode_round"]
    admits = by_name["tdt.batcher.admit_prefill"]
    assert len(admits) == 3 and rounds
    for attrs in rounds:
        # 2 slots x top-2 x 2 expert layers, every expert held here
        assert attrs["assignments"] == 2 * 2 * 2
        assert 2 <= attrs["experts_hit"] <= attrs["assignments"]
        assert 1 <= attrs["expert_load_max"] <= 2
    for attrs in admits:
        # the admitted slot's rows: bucket x top-2 x 2 expert layers
        assert attrs["assignments"] == attrs["bucket"] * 2 * 2
        assert attrs["experts_hit"] <= 2 * 8


def test_an_admissions_span_says_how_far_its_sorted_row_pass_went(adapter):
    """``sorted_rows_walked`` on ``tdt.batcher.admit_prefill``: with ONE
    expert layer of 16 experts and a bucket of at most 64 rows no expert
    fills a 128-row block, so the experts hit are the live blocks, and the
    pass walked them and no more (a chunk is one block of 128 rows at
    ``expert_ffn`` 32: ``gated_experts._chunk_blocks``), never the whole
    alignment of 17; a decode round walks its one straight-line call's
    every row, a constant."""
    from triton_dist_tpu import config as tdt_config, obs
    from triton_dist_tpu.models import gated_experts
    from triton_dist_tpu.obs import ObsConfig
    from triton_dist_tpu.resilience import retry
    from triton_dist_tpu.serving import Arrival, ServingConfig, ServingEngine

    one = dict(TOY, n_layers=2, n_routed_experts=16)
    one["sizes"] = {k: one[k] for k in cells.SIZE_KEYS}
    cfg = adapter.model_config(one)
    params = mla_moe.init_mla_moe_params(jax.random.PRNGKey(5), cfg)
    chunk = gated_experts._chunk_blocks(cfg, gated_experts.PREFILL_BLOCK_M)
    assert chunk == 1 and mla_moe.layer_plan(cfg).count("moe") == 1
    mesh = Mesh(np.array(jax.devices()[:1]), (cfg.axis,))
    before = tdt_config.get_config().obs
    tdt_config.update(obs=ObsConfig(spans=True))
    obs.reset()
    try:
        clock = retry.FakeClock()
        with retry.clock_scope(clock):
            eng = ServingEngine(
                cfg, params, mesh, s_max=64, page_size=8, prefill=True,
                clock=clock, serving=ServingConfig(virtual_step_s=0.01))
            rng = np.random.default_rng(2)
            eng.serve([
                Arrival(0.0, Request(list(rng.integers(0, cfg.vocab, n)), 2,
                                     uid=f"u{n}"))
                for n in (3, 7, 12)])
        spans = obs.spans()
    finally:
        tdt_config.update(obs=before)
        obs.reset()
    admits = [sp.attrs for sp in spans
              if sp.name == "tdt.batcher.admit_prefill"]
    assert len(admits) == 3
    for attrs in admits:
        t = attrs["bucket"] * cfg.topk
        n_blocks = -(-(t + min(16, t) * 127) // 128)
        assert n_blocks > chunk                 # the chunked pass
        live = attrs["experts_hit"]
        assert attrs["sorted_rows_walked"] == (
            min(-(-live // chunk) * chunk, n_blocks) * 128)
        assert attrs["sorted_rows_walked"] < n_blocks * 128
    rounds = [sp.attrs for sp in spans
              if sp.name == "tdt.batcher.decode_round"]
    # 2 slots x top-2 = 4 assignments on at most 4 experts: 4 + 4 x 15 rows
    assert rounds and {a["sorted_rows_walked"] for a in rounds} == {64}
