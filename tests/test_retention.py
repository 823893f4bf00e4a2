"""The power-retention family (models/retention.py) at toy widths on the
CPU (2 layers, so that one layer's state is not the other's; head width 16:
two blocks of 8, so the tiled symmetric square has a pair inside a block
and a pair across), each piece against the plain reference's equations
(perfbench/references/brumby_retention.py, imported as it stands: the
ATTENTION form, it shares no code with the program and never builds a
state). The family's contract and its size are tests/family_tier.py's; this
file names the family and keeps what only it has. Weights are float32 here,
so the tolerances are those of float32 arithmetic reordered (a state for a
sum over rows, chunks for the whole, a packed gate/up), not of bf16."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.models import Request, retention
from triton_dist_tpu.models.decode import (
    PAGED_CACHE_KINDS, RetentionStateCacheSpec,
)
from triton_dist_tpu.ops import retention as rt

from family_tier import (  # noqa: F401
    PERFBENCH, TOL, Family, Recording, _ref_logits, adapter, admit, cells,
    family, make_batcher, prompt_of, pytest_generate_tests, random_cache, ref,
    sampled_rows_match, served, sized, tiled_kernels_at_toy_buckets, toy,
    verdict,
    test_a_step_sent_in_vain_serves_the_plain_rounds_tokens,
    test_a_backlog_is_admitted_a_slot_a_pass,
    test_batcher_prefill_then_decode_matches_reference,
    test_decode_step_twice_on_the_same_inputs_is_decode_step_once,
    test_every_part_of_a_pass_says_which_part_it_is,
    test_full_forward_matches_reference,
    test_token_fed_admission_matches_reference,
)
from family_tier import (  # noqa: F401
    test_engine_serves_it_and_the_spans_carry_the_counters
    as test_engine_serves_it_rebuilds_and_the_spans_carry_the_counters,
    test_what_the_kind_cannot_serve_is_refused_by_name
    as test_what_a_slots_state_cannot_serve_is_refused_by_name,
)

PAGE, S_MAX = 4, 512
CHUNK = rt.chunk_len(16)                # 128
TOY = sized(dict(
    hidden=32, ffn=64, n_layers=2, n_q_heads=4, n_kv_heads=2, head_dim=16,
    vocab=64, rope_theta=1e6, norm_eps=1e-6, dtype="float32", power=2,
    attention_bias=False, tie_word_embeddings=False,
    engine=dict(slots=3, s_max=S_MAX, page=PAGE, max_queue=64),
))
SIZES = TOY["sizes"]
PUBLISHED = os.path.join(PERFBENCH, "configs", "brumby-14b-base.json")


def _engine_spans(cfg, params, by_name, requests, eng):
    """The batcher's default of lookahead stayed on; the round's span
    carries ``state_slots``, the admission's ``prompt_chunks``, the
    intake's ``state_bytes``."""
    assert eng._batcher.lookahead
    assert [a["state_bytes"] for a in by_name["tdt.batcher.take_params"]] \
        == [cfg.state_bytes()] * 2                  # built, and rebuilt
    rounds = by_name["tdt.batcher.decode_round"]
    assert rounds and all(
        (a["state_slots"], a["prompt_chunks"]) == (cfg.batch, 0) for a in rounds)
    admits = by_name["tdt.batcher.admit_prefill"]
    assert len(admits) >= len(requests) + 1         # and the replayed ones
    # one chunk a layer for every prompt under 128 rows
    assert all((a["state_slots"], a["prompt_chunks"]) == (1, cfg.n_layers)
               for a in admits)


FAMILY = Family(
    program="tdt_retention", reference="brumby_retention", model=retention,
    toy=TOY, spec=RetentionStateCacheSpec,
    layer=lambda ref, x, w, li, control, block: ref.layer(x, w, SIZES, control),
    # a prompt below its bucket's edge (3 of 4), at it (4 of 4), across it
    # (5 -> 8) and over a chunk (150 -> 256: two chunks of 128, the second
    # padded); with 3 slots the last two are admitted into slots that
    # served before
    cases={"below": (3, 3), "at": (4, 3), "across": (5, 3),
           "two chunks": (150, 3), "readmitted": (6, 3)},
    # the full forward under, at and past a chunk (L < C, L = C, L = 2C + 3)
    forward={"under a chunk": (40, None), "a chunk": (CHUNK, None),
             "past two chunks": (2 * CHUNK + 3, None)},
    scopes=frozenset({
        "retn", "retn/qkv", "retn/gate", "retn/out", "ffn", "ffn/gate_up",
        "ffn/act", "ffn/down", "head"}),
    admission_scopes=frozenset({"retn/prefill"}),
    refused=("prefix cache", "ranged prefill", "contiguous cache",
             "wider mesh", "wider mesh, the spec", "verify", "the dense step",
             "speculative decoding", "handoff", "scratch page"),
    refusal_says=("state cache kind", "one-device", "state model"),
    state_pool="s",
    # a context of a few tokens: where every ``a_ij`` of a row is far under
    # its terms ``q_i q_j k_i k_j``, the state's form keeps their float32
    # rounding in numerator and normaliser apart, which the attention
    # form's square does not have
    token_fed_tol=dict(rtol=5e-3, atol=5e-3),
    engine=dict(requests=[(6, 5), (9, 4), (3, 5), (5, 3)], rebuild_after=3,
                check=_engine_spans),
)


@pytest.fixture(scope="module")
def published(adapter):
    config = cells.load_json(PUBLISHED)
    config["sizes"] = {k: config[k] for k in cells.SIZE_KEYS}
    return config, adapter.model_config(config)


def _rel(got, want):
    return np.abs(np.asarray(got) - np.asarray(want)).max() / (
        np.abs(np.asarray(want)).max())


# -- (a) the symmetric square and the two kernels --------------------------------

@pytest.mark.parametrize("d", [8, 16, 128])
def test_phi_of_two_vectors_multiplies_to_their_product_squared(d):
    """``phi(x) . phi(y) == (x . y)^2`` in the tiled row order: 64 rows a
    pair of blocks, 8704 at the published width."""
    rng = np.random.default_rng(d)
    x, y = (rng.standard_normal((5, d)) for _ in range(2))
    px, py = (np.asarray(rt.phi(jnp.asarray(a)), np.float64) for a in (x, y))
    nb = d // 8
    assert px.shape == (5, 64 * nb * (nb + 1) // 2) == (5, rt.state_rows(d))
    # float32 products: a square far under its terms keeps their rounding
    np.testing.assert_allclose((px * py).sum(-1), (x * y).sum(-1) ** 2,
                               rtol=1e-4, atol=1e-4)
    assert rt.state_rows(128) == 8704


def _step_args(rng, b=5, h_kv=2, g=2, d=16, layers=2):
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    rows = rt.state_rows(d)
    # a state as a sequence leaves it: Z a sum of k k^T
    ks = f(layers, 2, b, h_kv, 7, d)
    return dict(
        s=f(layers, 2, b, h_kv, rows, d),
        z=jnp.einsum("...td,...te->...de", ks, ks),
        q=f(b, h_kv * g, d), k=f(b, h_kv, d), v=f(b, h_kv, d),
        log_g=-jnp.abs(f(b, h_kv)) * 0.05)


def test_retention_update_against_its_twin_and_the_sum_over_rows():
    """Slots at even and odd positions (either row of the pool's axis of 2
    is read), two at position 0, and a stale state that is not finite under
    one of them."""
    rng = np.random.default_rng(1)
    a = _step_args(rng)
    pos = jnp.asarray([1, 2, 0, 4, 0])
    read = (np.asarray(pos) - 1) % 2
    s = a["s"].at[1, 1, 2].set(jnp.nan)
    y, s_out, z_out = rt.retention_update(
        s, a["z"], 1, pos, a["q"], a["k"], a["v"], a["log_g"], interpret=True)
    y_x, s_x, z_x = rt._xla_retention_update(
        s, a["z"], 1, pos, rt._grouped(a["q"], 2), a["k"], a["v"], a["log_g"])
    assert _rel(y, y_x.reshape(y.shape)) < 1e-5
    np.testing.assert_allclose(np.asarray(s_out), np.asarray(s_x), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(z_out), np.asarray(z_x), rtol=1e-5,
                               atol=1e-5)
    # the state as it is written: g S + phi(k) v^T, at the other parity
    for i in range(5):
        old = 0.0 if pos[i] == 0 else np.asarray(a["s"][1, read[i], i])
        want = (np.exp(np.asarray(a["log_g"][i]))[:, None, None] * old
                + np.asarray(rt.phi(a["k"][i]))[..., None]
                * np.asarray(a["v"][i])[:, None, :])
        np.testing.assert_allclose(np.asarray(s_out[1, 1 - read[i], i]), want,
                                   rtol=1e-5, atol=1e-5)
    # the other layer, and the rows read, are as they were
    np.testing.assert_array_equal(np.asarray(s_out[0]), np.asarray(s[0]))
    np.testing.assert_array_equal(np.asarray(s_out[1, 0, 0]),
                                  np.asarray(s[1, 0, 0]))
    np.testing.assert_array_equal(np.asarray(z_out[1, 0, 0]),
                                  np.asarray(a["z"][1, 0, 0]))


def _prompt_args(rng, L, h_kv=2, g=2, d=16):
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    forget = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (L, h_kv)))
    return (f(L, h_kv * g, d), f(L, h_kv, d), f(L, h_kv, d),
            jnp.asarray(-forget, jnp.float32))


@pytest.mark.parametrize("length", [5, CHUNK, 2 * CHUNK + 3])
def test_retention_prefill_against_its_twin_and_the_attention_form(ref, length):
    """Lengths below a chunk (128), one chunk, and over two chunks: the
    kernel against its chunked twin and against the reference's weights
    ``a_ij`` over the whole sequence, which has no chunk and no state; the
    state against the sum it stands for."""
    q, k, v, log_g = _prompt_args(np.random.default_rng(length), length)
    y, s, z = rt.retention_prefill(q, k, v, log_g, interpret=True)
    y_x, s_x, z_x = rt._xla_retention_prefill(q, k, v, log_g)
    y_r = ref.retention(q, k, v, log_g)
    to_end = jnp.exp(jnp.cumsum(log_g[::-1], 0)[::-1] - log_g)    # [L, h]
    s_r = jnp.einsum("jhr,jhd->hrd", rt.phi(k) * to_end[..., None], v,
                     precision="highest")
    for got, want in ((y, y_x), (s, s_x), (z, z_x), (y, y_r), (s, s_r)):
        assert _rel(got, want) < 2e-5
    # k = 0 and log g = 0 from row 3 on: the state stays where row 2 left it
    stop = jnp.arange(length)[:, None] < 3
    _, s_stop, z_stop = rt.retention_prefill(
        q, jnp.where(stop[..., None], k, 0), v, jnp.where(stop, log_g, 0),
        interpret=True)
    _, s_3, z_3 = rt._xla_retention_prefill(q[:3], k[:3], v[:3], log_g[:3])
    assert _rel(s_stop, s_3) < 2e-5 and _rel(z_stop, z_3) < 2e-5


# -- (c) what an admission writes ---------------------------------------------------

def test_a_prompt_in_a_bucket_twice_its_length_leaves_the_state_of_its_own(toy):
    """5 tokens in a bucket of 16: ``S`` and ``Z`` are the state after
    token 5 (not after 16 rows), at the parity of position 4, as the same
    prompt leaves them in a bucket of its own length in EVERY slot with no
    mask (``generate``'s form); no other slot's state moves, bit for bit;
    and the counters say one slot and one chunk a layer."""
    cfg, params, _, _ = toy
    spec = FAMILY.make_spec()
    rng = np.random.default_rng(2)
    prompt = prompt_of(rng, cfg, 5)
    before = random_cache(cfg, spec, rng)
    cache, last, counters = admit(FAMILY, cfg, params, before, 1, prompt, 16)
    assert [int(v) for v in counters] == [1, cfg.n_layers]
    exact, last_all, counters = admit(FAMILY, cfg, params, spec.init(cfg, 1),
                                      None, prompt, 5)
    assert [int(v) for v in counters] == [cfg.batch, cfg.batch * cfg.n_layers]
    for name in ("s", "z"):
        for slot in range(cfg.batch):
            np.testing.assert_allclose(
                np.asarray(cache[name][:, 4 % 2, 1]),
                np.asarray(exact[name][:, 4 % 2, slot]), rtol=1e-5, atol=1e-6)
        others = np.array([0, 2])
        np.testing.assert_array_equal(np.asarray(cache[name][:, :, others]),
                                      np.asarray(before[name][:, :, others]))
        # the admitted slot's other parity is left to its next step
        np.testing.assert_array_equal(np.asarray(cache[name][:, 1, 1]),
                                      np.asarray(before[name][:, 1, 1]))
        assert exact[name][:, 0].any() and not exact[name][:, 1].any()
    for slot in range(cfg.batch):
        np.testing.assert_allclose(np.asarray(last[1]),
                                   np.asarray(last_all[slot]), **TOL)
    assert not np.asarray(last)[[0, 2]].any()


# -- (e) the planted faults (the slot that served before is ``served``'s) --------------------------

FAULTS = cells.load_module("tools", "retention_faults")


# the faults that move a served token within a few steps at toy widths; a
# state rounded to bfloat16 moves the logits (by more than 50 tolerances)
# and no token: it takes the published size's sums to show (PERF.md)
FLIPS_A_TOKEN = set(FAULTS.FAULTS) - {"bf16_state"}


@pytest.mark.parametrize("fault", ("none",) + FAULTS.FAULTS)
def test_a_planted_fault_is_not_correct(toy, ref, served, fault):
    """Through ``correct.verdict``, the comparison that decides a cell's
    ``correct``: the sound run (``served``'s) passes the toy limits; each
    fault of the cell's list (perfbench/tools/retention_faults.py, which
    plants the same on the chip) is far outside the tolerances and, but
    for the rounded state, fails the limits. The stale state is a
    request's that filled the slot before."""
    cfg, params, plain, outer = toy
    prompt = served[0]["two chunks"].prompt
    if fault == "none":
        ok, _ = verdict(FAMILY, ref, plain, outer, prompt,
                        served[1]["two chunks"])
        assert ok
        return
    with FAULTS.planted(fault):
        b = make_batcher(FAMILY, cfg, params)
        if fault == "stale_state_kept":
            b.submit(Request(prompt_of(np.random.default_rng(3), cfg, 150), 1,
                             uid="before"))
            b.run()
        r = Recording(list(prompt), 6, temperature=1.0, uid=0)
        b.submit(r)
        out = dict(b.run())[0]
    seq = np.array([list(prompt) + list(out)])
    want = _ref_logits(FAMILY, ref, plain, outer, seq)[0]
    first = len(prompt) - 1
    off = np.abs(np.stack(r.rows) - want[first:first + len(out)]).max()
    assert off > 50 * TOL["atol"], off
    ok, numbers = verdict(FAMILY, ref, plain, outer, prompt, out)
    assert ok == (fault not in FLIPS_A_TOKEN), (off, numbers)


# -- (g) the published configuration ------------------------------------------------

def test_the_published_sizes_count_14_769_945_920_parameters(published, ref):
    """From the program's own shapes (nothing is allocated), at the 40
    layers of the catalog's row; the reference's plain layout counts the
    same."""
    import dataclasses

    config, cfg = published
    assert (cfg.hidden, cfg.ffn, cfg.vocab) == (5120, 17408, 151936)
    assert (cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim, cfg.power) == (
        40, 8, 128, 2)
    assert config["reduced"] == ["n_layers"] and cfg.n_layers == 6
    whole = dataclasses.replace(cfg, n_layers=40)
    shapes = jax.eval_shape(
        lambda k: retention.init_retention_params(k, whole),
        jax.random.PRNGKey(0))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    assert count(shapes) == 14_769_945_920
    assert count(shapes["layers"][0]) == 330_352_904
    assert ref.count_parameters(dict(config["sizes"], n_layers=40)) \
        == 14_769_945_920
    specs = whole.param_specs()
    assert jax.tree.structure(jax.tree.map(lambda x: 0, shapes)) == \
        jax.tree.structure(jax.tree.map(
            lambda s: 0, specs, is_leaf=lambda s: not isinstance(s, (dict, list))))


def test_the_state_pools_hold_16_slots_of_36_mb_a_layer_and_parity(published):
    config, cfg = published
    eng = config["engine"]
    spec = PAGED_CACHE_KINDS["state"](eng["s_max"], eng["page"],
                                      static_table=True)
    assert PAGED_CACHE_KINDS["state"] is RetentionStateCacheSpec
    assert (cfg.own_passes, cfg.cache_kind) == (True, "state")
    assert cfg.pass_counters == ("state_slots", "prompt_chunks")
    cache = jax.eval_shape(lambda: spec.init(cfg, 1))
    assert cache["s"].shape == (6, 2, 16, 8, 8704, 128)
    assert cache["z"].shape == (6, 2, 16, 8, 128, 128)
    assert cache["s"].dtype == cache["z"].dtype == jnp.float32
    assert set(spec.specs(cfg)) == set(cache) == {"s", "z"}   # no page, no table
    state = sum(int(np.prod(x.shape)) * 4 for x in cache.values())
    assert state == cfg.state_bytes() == 6 * 2 * 16 * 36_175_872
    assert spec.pages_walked(cfg, np.array([5, 9])) == (0, 0)
    assert rt.chunk_len(128) == 512
