"""The window-attention / gated-expert family's SECOND block
(models/window_moe.py with SmallThinker's published form: pre-norm, no q/k
norm, a router that reads the layer's input before attention, softmax over
the chosen logits, ReLU-gated experts, no shared expert, no dense layer)
at toy widths on the CPU (a full and a window layer; window 8, page 4, so a
ring of 3 pages; a group of 7 query heads a kv head; 8 experts, top-2),
each piece against the plain reference's equations
(perfbench/references/smallthinker_prerouted_moe.py, imported as it
stands: it shares no code with the program), and the tiled prefill kernel
(ops/flash_prefill.py) against plain attention. The family's contract and
its size are tests/family_tier.py's; this file names the family and keeps
what only it has. ``MATERIALIZED_UP_TO`` is 0 for the file (``tiled``):
the toy buckets run through the tiled kernel, as the cell's 8192-row
bucket runs (one test below holds the two forms to one result). Weights
are float32 here, so the tolerances are those of float32 arithmetic
reordered (tiled vs masked attention, online vs whole softmax, grouped vs
dense expert sums), not of bf16: the lower-precision control, a router on
the wrong rows and a SiLU gate are all far outside them."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.models import Request, gated_experts, window_moe
from triton_dist_tpu.models.decode import WindowPagedKVCacheSpec
from triton_dist_tpu.ops.flash_prefill import (
    blocks_walked, default_blocks, flash_prefill, xla_flash_prefill,
)

from family_tier import (  # noqa: F401
    TOL, Family, _ref_logits, adapter, family, forward_logits, make_batcher,
    one_device, pytest_generate_tests, recorded_spans, ref, served, sized,
    tiled_kernels_at_toy_buckets, toy,
    test_an_admission_runs_and_writes_the_admitted_slot_only,
    test_a_backlog_is_admitted_a_slot_a_pass,
    test_batcher_prefill_then_decode_matches_reference,
    test_every_part_of_a_pass_says_which_part_it_is,
    test_full_forward_matches_reference,
    test_the_lower_precision_control_is_far_outside_the_tolerances,
)
from family_tier import (  # noqa: F401
    test_shares_of_the_bank_add_up_to_the_layer
    as test_the_shares_of_the_bank_add_up_to_the_whole_layer,
)

WINDOW, PAGE, S_MAX = 8, 4, 32
LAYOUT = [0, 1]
TOY = sized(dict(
    hidden=64, ffn=32, n_layers=2, n_q_heads=14, n_kv_heads=2, head_dim=8,
    vocab=128, rope_theta=10000.0, norm_eps=1e-6, dtype="float32",
    sliding_window_layout=LAYOUT, rope_layout=LAYOUT,
    sliding_window_size=WINDOW, moe_num_primary_experts=8,
    moe_num_active_primary_experts=2, moe_ffn_hidden_size=32,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    engine=dict(slots=2, s_max=S_MAX, page=PAGE, max_queue=64),
))
SIZES = TOY["sizes"]


def _admitted(counters, bucket):
    assert counters[len(gated_experts.MOE_STATS):] == [0, 0, 0]


FAMILY = Family(
    program="tdt_prerouted_moe", reference="smallthinker_prerouted_moe",
    model=window_moe, toy=TOY, spec=WindowPagedKVCacheSpec, seed=11,
    tiled=True,
    layer=lambda ref, x, w, li, control, block: ref.layer(
        x, w, SIZES, li, control, block),
    # against a ring of 12 positions: a context below the window
    # throughout; a prompt shorter than the window decoding across a ring
    # wrap; a prompt longer than the ring (its prefill wraps once, lands
    # the last 12 true rows of a 16-row bucket at their ring addresses),
    # then decoding across the next wrap; a slot re-admitted onto a stale
    # ring, which it wraps (two buckets, 8 and 16: a bucket more is a
    # program more). The reference reads queries in blocks of 8
    cases={"below": (5, 2), "past": (6, 8, 3 * PAGE),
           "wrapped": (14, 11, 2 * 3 * PAGE), "readmitted": (9, 5, 3 * PAGE)},
    block=8,
    # tiled attention at lengths below, at and past the window; the
    # reference's blocked attention is its whole attention
    forward={"5": (5, None), "8": (8, None), "19": (19, None)},
    # the last slot, a prompt shorter than its bucket and one longer than
    # the ring
    admissions=((-1, 5, 8), (-1, 14, 16)),
    pools={"k_full": "block_table", "v_full": "block_table",
           "k_win": "block_table_win", "v_win": "block_table_win"},
    admitted=_admitted,
    # no dense MLP, no shared expert; the admission's attention is the
    # tiled kernel's
    scopes=frozenset({"attn", "attn/qkv", "attn/kv_write", "attn/out", "ffn",
                      "ffn/route", "ffn/experts", "head"}),
    admission_scopes=frozenset({"attn/prefill"}),
    # softmax over the chosen, ReLU, no shared expert, routing from OTHER
    # rows than the experts multiply
    shares=4, prerouted=True,
    uncut=lambda ref, x, m, w: ref.experts_part(
        m, ref.combine_weights(x, w, False), w, False),
)


# -- the tiled prefill kernel ----------------------------------------------------

@pytest.mark.parametrize("window", [None, 12, 100], ids=["full", "w12", "w100"])
@pytest.mark.parametrize("lens", [(40, 40), (23, 7)], ids=["whole", "short"])
def test_flash_prefill_against_plain_attention(window, lens):
    """A group of 7 on 2 kv heads, ``L`` = 40 not a multiple of the blocks
    (16 queries, 8 keys), windows absent, smaller and larger than ``L``,
    lengths at and under ``L``: every TRUE row is plain attention's, every
    padding row is finite."""
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 40, h, 16)), jnp.float32)
               for h in (14, 2, 2))
    lens = jnp.asarray(lens, jnp.int32)
    got = np.asarray(flash_prefill(q, k, v, lens, window=window, block_q=16,
                                   block_k=8, interpret=True))
    want = np.asarray(xla_flash_prefill(q, k, v, lens, window))
    assert np.isfinite(got).all()
    for i, n in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(got[i, :n], want[i, :n], rtol=1e-5,
                                   atol=1e-5)


def test_flash_prefill_walks_the_band_and_no_block_outside_it():
    """The kernel's own bounds (``blocks_walked``): a window clips the
    causal square's blocks, a short prompt clips them again, and a prompt
    of length 0 walks nothing; the default blocks at the cell's shape."""
    assert default_blocks(8192, 7) == (128, 512)
    assert default_blocks(256, 8) == (128, 256)
    live, square = blocks_walked([8192], 8192, 7, None)
    assert live == square == sum(-(-(q + 128) // 512) for q in range(0, 8192, 128))
    band, _ = blocks_walked([8192], 8192, 7, 4096)
    assert band < 0.8 * square
    # a block of 128 queries at q0 sees keys q0 - 4095 .. q0 + 127
    assert band == sum((q + 127) // 512 - max(q - 4095, 0) // 512 + 1
                       for q in range(0, 8192, 128))
    short, _ = blocks_walked([4100], 8192, 7, 4096)
    assert short < 0.4 * square
    assert blocks_walked([0], 8192, 7, 4096)[0] == 0
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 32, h, 8)), jnp.float32)
               for h in (7, 1, 1))
    out = flash_prefill(q, k, v, jnp.zeros((1,), jnp.int32), window=8,
                        block_q=8, block_k=8, interpret=True)
    assert not np.asarray(out).any()


# -- the block --------------------------------------------------------------------

def test_the_second_block_allocates_no_leaf_it_does_not_have(toy):
    """``first_k_dense`` 0, ``n_shared_experts`` 0, no q/k norm and softmax
    scoring: no dense MLP, no shared expert, no q/k norm scale and no
    choice bias anywhere in the tree; rings of ``ceil(8 / 4) + 1`` pages."""
    cfg, params, _, _ = toy
    assert window_moe.layer_plan(cfg) == (("full", "moe"), ("window", "moe"))
    assert (cfg.norm_placement, cfg.qk_norm, cfg.router_rows, cfg.scoring,
            cfg.gate_act) == ("input", False, "layer_input", "softmax", "relu")
    init = window_moe.init_window_moe_params(jax.random.PRNGKey(0), cfg)
    assert jax.tree.map(jnp.shape, init) == jax.tree.map(jnp.shape, params)
    for layer in init["layers"]:
        assert set(layer) == {"wqkv", "wo", "attn_norm", "mlp_norm", "router",
                              "we_gate_up", "we_down"}
    specs = cfg.param_specs()
    assert [set(s) for s in specs["layers"]] == [set(p) for p in init["layers"]]
    spec = WindowPagedKVCacheSpec(S_MAX, PAGE, static_table=True)
    cache = spec.init(cfg, 1)
    assert spec.ring(cfg) == 3
    assert cache["k_full"].shape == (1, 2 * 8, 2, PAGE, 8)
    assert cache["k_win"].shape == (1, 2 * 3, 2, PAGE, 8)
    # K-EXAONE's block is the family's default
    default = window_moe.WindowMoEConfig(
        vocab=8, hidden=8, ffn=8, n_layers=1, n_q_heads=1, n_kv_heads=1,
        head_dim=8, batch=1, seq=8, layer_types=("full",))
    assert (default.norm_placement, default.qk_norm, default.router_rows,
            default.scoring, default.gate_act) == (
        "output", True, "mlp_input", "sigmoid", "silu")
    with pytest.raises(ValueError, match="gate_act"):
        dataclasses.replace(cfg, gate_act="gelu")


def test_the_choice_of_prefills_form_changes_no_result(toy, monkeypatch):
    """``prefill_attention`` chooses from the bucket and the window alone:
    the tiled kernel past ``MATERIALIZED_UP_TO`` rows, under it the band
    where a window can clip and the causal square where none can; all
    three give one result, and only the kernel's admission counts blocks."""
    cfg, params, _, _ = toy
    assert window_moe.MATERIALIZED_UP_TO == 0            # this file's fixture
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 19), 0, cfg.vocab)
    tiled = np.asarray(forward_logits(FAMILY, cfg, params, tokens))
    assert cfg.prefill_blocks(19, 32) is not None
    monkeypatch.setattr(window_moe, "MATERIALIZED_UP_TO", 2048)  # as shipped
    assert cfg.prefill_blocks(19, 32) is None
    assert cfg.prefill_blocks(4100, 8192) is not None
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 19, h, 8)), jnp.float32)
               for h in (14, 2, 2))
    lens = jnp.full((1,), 19, jnp.int32)
    for kind, window in (("full", None), ("window", 8)):
        np.testing.assert_allclose(
            np.asarray(window_moe.prefill_attention(cfg, kind, q, k, v, lens)),
            np.asarray(xla_flash_prefill(q, k, v, lens, window)), **TOL)
    np.testing.assert_allclose(
        np.asarray(forward_logits(FAMILY, cfg, params, tokens)), tiled, **TOL)
    # a window wider than the bucket clips nothing: the causal square
    wide = dataclasses.replace(cfg, window=64)
    np.testing.assert_allclose(
        np.asarray(window_moe.prefill_attention(wide, "window", q, k, v, lens)),
        np.asarray(xla_flash_prefill(q, k, v, lens, None)), **TOL)


@pytest.mark.parametrize("wrong", [
    dict(router_rows="mlp_input"), dict(gate_act="silu"),
    dict(norm_placement="output"), dict(scoring="sigmoid")],
    ids=lambda d: "-".join(f"{k}={v}" for k, v in d.items()))
def test_each_field_of_the_block_is_held_by_the_reference(toy, ref, wrong):
    """The reference's norm weights are not 1 (1 + 0.1 x normal), so a
    router that reads the NORMED rows (or the MLP's input) chooses other
    experts; a SiLU gate, output norms and sigmoid scores are other
    models: each alone leaves the tolerances by far."""
    cfg, params, plain, outer = toy
    assert float(jnp.abs(plain[0]["attn_norm"] - 1).max()) > 0.05
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 19), 0, cfg.vocab)
    want = _ref_logits(FAMILY, ref, plain, outer, tokens)
    bad = dataclasses.replace(cfg, **wrong)
    if "scoring" in wrong:      # the sigmoid router has a bias leaf
        params = dict(params, layers=[
            dict(p, router_bias=jnp.zeros((8,), jnp.float32))
            for p in params["layers"]])
    got = np.asarray(forward_logits(FAMILY, bad, params, tokens))
    assert np.abs(got - want).max() > 50 * TOL["atol"]


def test_the_routing_is_issued_before_attention(toy):
    """In the lowered step the router's product of each layer comes before
    that layer's decode kernel, as the model is written."""
    cfg, params, _, _ = toy
    spec = WindowPagedKVCacheSpec(S_MAX, PAGE, static_table=True)
    shapes = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    i32 = jax.ShapeDtypeStruct((cfg.batch,), jnp.int32)
    text = str(jax.make_jaxpr(jax.shard_map(
        lambda p, c, t, pos: cfg.decode_step(p, c, t, pos, spec=spec,
                                             interpret=True),
        mesh=one_device(cfg), check_vma=False,
        in_specs=(cfg.param_specs(), spec.specs(cfg), P(), P()),
        out_specs=(P(), spec.specs(cfg), P())))(
        shapes(params), jax.eval_shape(lambda: spec.init(cfg, 1)), i32, i32))
    order = [("route" if "top_k" in line else "attend")
             for line in text.splitlines()
             if "top_k" in line or "name=paged_flash_decode" in line]
    assert order == ["route", "attend"] * cfg.n_layers


def test_the_admissions_span_says_what_the_band_saved(toy):
    """``prefill_blocks_live`` / ``prefill_blocks_square`` on
    ``tdt.batcher.admit_prefill``: the key blocks the prompt's band holds
    against the causal square of its bucket, every layer and kv head."""
    cfg, params, _, _ = toy
    assert cfg.prefill_blocks(16, 16) == (2 * 2, 2 * 2)   # one block a layer
    big = dataclasses.replace(cfg, window=4096)
    live, square = big.prefill_blocks(4100, 8192)
    full, _ = blocks_walked([4100], 8192, 7, None)
    band, sq = blocks_walked([4100], 8192, 7, 4096)
    assert (live, square) == (2 * (full + band), 2 * 2 * sq)
    with recorded_spans() as by_name:
        batcher = make_batcher(FAMILY, cfg, params)
        batcher.submit(Request([1, 2, 3, 4, 5], 2, uid="a"))
        batcher.run()
        admit = by_name()["tdt.batcher.admit_prefill"]
    assert len(admit) == 1
    assert (admit[0]["prefill_blocks_live"], admit[0]["prefill_blocks_square"]
            ) == cfg.prefill_blocks(5, admit[0]["bucket"])
    assert {"experts_hit", "assignments", "window_rows"} <= set(admit[0])
