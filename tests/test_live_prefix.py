"""The sorted-row pass of ``gated_experts.moe_mlp`` stops at its last live
block: an alignment of more than one chunk is walked a chunk at a time, the
trip count read from the alignment on the device, and the result is the
whole pass's bit for bit; an alignment of at most one chunk (every decode
step) is walked whole, in straight-line calls. CPU, interpreted kernels,
toy widths (the real cells' shapes are traced, never run)."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.models import gated_experts as ge
from triton_dist_tpu.ops import moe_utils

PERFBENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "perfbench")
if PERFBENCH not in sys.path:
    sys.path.insert(0, PERFBENCH)
from harness import cells  # noqa: E402

BLOCK_M = 8
ROWS = 128


@dataclasses.dataclass(frozen=True)
class Toy:
    """What ``moe_mlp`` asks of a family's config: 16 experts, top-2,
    sigmoid scores with a choice bias (the test steers the routing with
    it), a chunk of ``2 x 3 x 8 / 8`` = 6 blocks of 8 rows."""
    held: tuple
    hidden: int = 32
    expert_ffn: int = 8
    n_experts: int = 16
    topk: int = 2
    routed_scaling: float = 2.5
    n_shared_experts: int = 1
    scoring: str = "sigmoid"
    gate_act: str = "silu"


def _layer(c: Toy, favoured=()):
    """One expert layer's leaves; ``favoured`` experts win every choice."""
    k = jax.random.split(jax.random.PRNGKey(3), 6)
    leaf = lambda key, *shape: (
        jax.random.normal(key, shape, jnp.float32) * 0.2).astype(jnp.bfloat16)
    n_held, f = c.held[1], c.expert_ffn
    bias = jnp.zeros((c.n_experts,), jnp.float32)
    if favoured:
        bias = bias.at[jnp.asarray(favoured)].set(10.0)
    return {
        "router": jax.random.normal(k[0], (c.hidden, c.n_experts), jnp.float32),
        "router_bias": bias,
        "we_gate_up": leaf(k[1], n_held, c.hidden, 2 * f),
        "we_down": leaf(k[2], n_held, f, c.hidden),
        "ws_gate_up": leaf(k[3], c.hidden, 2 * f),
        "ws_down": leaf(k[4], f, c.hidden),
    }, leaf(k[5], ROWS, c.hidden)


# regime -> (held share, experts every row chooses, live blocks' range)
REGIMES = {
    # 2 of 16 held, the router left alone: about 7/8 of the rows away
    "a_share_with_most_away": ((0, 2), (), None),
    # 4 of 16 held: a quarter of the bank, about 3/4 of the rows away
    "a_quarter_share": ((4, 4), (), None),
    # every expert held: only the worst-case tail is dead
    "the_full_bank": ((0, 16), (), None),
    # every row chooses two experts held elsewhere: zero chunks, the
    # shared expert alone
    "nothing_here": ((0, 2), (8, 9), 0),
    # every row chooses the two held: every chunk runs
    "everything_here": ((0, 2), (0, 1), 32),
}


def _weights_of_one_bit(monkeypatch):
    """Round every routing weight down to a power of two, in both passes
    alike. A share's chunked pass combines by the landed walk and the one
    straight-line call by ``topk`` gathers: the same float32 products
    added in the same order, but XLA's CPU backend contracts a multiply
    and an add into one rounding, and not the same pair in the two forms
    (1 ulp apart with free weights). With one-bit weights every product is
    exact, so the contraction rounds what the MATERIALISED products would,
    and ``array_equal`` tests the order of the additions."""
    route = ge.route

    def one_bit(c, h, p):
        w, ids = route(c, h, p)
        return 2.0 ** jnp.floor(jnp.log2(w)), ids

    monkeypatch.setattr(ge, "route", one_bit)


@pytest.mark.parametrize("regime", sorted(REGIMES))
def test_the_live_prefix_pass_is_the_whole_pass_bit_for_bit(regime, monkeypatch):
    """Chunks of 6 blocks over an alignment of 35 / 37 (a share) or 46 (the
    bank): the same output and counters as the one straight-line call
    over every block, with the rows never walked holding NaN (so one read
    as a number would show, by the pass or by the combine after it),
    ``sorted_rows_walked`` the live prefix rounded up to whole chunks, and
    ``combine_rows_gathered`` the landed slots + the rows + the trips'
    rounding on a share, ``topk x rows`` where every slot holds a result."""
    held, favoured, live_want = REGIMES[regime]
    c = Toy(held=held)
    p, h = _layer(c, favoured)
    _weights_of_one_bit(monkeypatch)
    walk_rows = 16
    monkeypatch.setattr(moe_utils, "COMBINE_WALK_ROWS", walk_rows)
    chunk = ge._chunk_blocks(c, BLOCK_M)
    al = ge.route_rows(c, h, p, BLOCK_M)[3]
    n_blocks = al.expert_ids.shape[0]
    assert chunk == 6 and n_blocks >= 2 * chunk + 1        # three chunks or more
    live = int(al.num_tokens_post_pad) // BLOCK_M
    valid = np.asarray(al.valid_rows)
    assert (valid[:live] > 0).all() and not valid[live:].any()
    if live_want is not None:
        assert live == live_want
    else:
        assert 0 < live < n_blocks

    run = jax.jit(lambda h, p: ge.moe_mlp(c, h, p, BLOCK_M, True))
    with monkeypatch.context() as m:
        m.setattr(jax.lax, "empty",
                  lambda shape, dtype: jnp.full(shape, jnp.nan, dtype))
        y, stats = run(h, p)
    with monkeypatch.context() as m:       # one chunk holds every block
        m.setattr(ge, "_chunk_blocks", lambda c, block_m: n_blocks)
        whole, whole_stats = jax.jit(
            lambda h, p: ge.moe_mlp(c, h, p, BLOCK_M, True))(h, p)

    assert np.isfinite(np.asarray(y, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(whole, np.float32))
    assert np.asarray(whole, np.float32).any()      # the shared expert at least
    stats, whole_stats = np.asarray(stats), np.asarray(whole_stats)
    np.testing.assert_array_equal(stats[:3], whole_stats[:3])
    walked = dict(zip(ge.MOE_STATS, stats))["sorted_rows_walked"]
    assert walked == min(-(-live // chunk) * chunk, n_blocks) * BLOCK_M
    assert whole_stats[3] == n_blocks * BLOCK_M
    if live == 0:       # no assignment here: the routed part adds nothing
        assert stats[1] == 0 and walked == 0
    gathered = dict(zip(ge.MOE_STATS, stats))["combine_rows_gathered"]
    assert whole_stats[4] == c.topk * ROWS
    if held[1] == c.n_experts:      # every slot landed: the whole form
        assert gathered == c.topk * ROWS
    else:       # the walk: what landed, a trip's rounding for each j, m
        landed = stats[1]
        assert landed + ROWS <= gathered <= landed + ROWS + c.topk * walk_rows
        assert (gathered - ROWS) % walk_rows == 0
        assert (landed == 0) == (gathered == ROWS)


def test_a_partly_dead_last_chunk_is_among_the_regimes():
    """The two free-routed shares end inside a chunk (their live prefix is
    no whole number of chunks), so the combine's rows come from a last
    chunk whose trailing blocks are dead: written as zeros, never NaN."""
    for regime in ("a_share_with_most_away", "a_quarter_share"):
        c = Toy(held=REGIMES[regime][0])
        p, h = _layer(c)
        al = ge.route_rows(c, h, p, BLOCK_M)[3]
        live = int(al.num_tokens_post_pad) // BLOCK_M
        assert live % ge._chunk_blocks(c, BLOCK_M), regime


def test_a_dead_block_names_the_last_live_block_of_a():
    """``dead_blocks_refetch_none``: live blocks fetch their own rows, a
    dead one the rows the step before fetched, and the grouped GEMM's
    result does not depend on it; ``into`` puts that result into a run of
    a larger buffer's blocks and touches no other."""
    from triton_dist_tpu.ops.group_gemm import (
        GroupGemmConfig, dead_blocks_refetch_none, group_gemm,
    )

    valid = jnp.asarray([0, 8, 3, 0, 0, 8, 0], jnp.int32)
    a_blocks = dead_blocks_refetch_none(valid)
    assert a_blocks.tolist() == [0, 1, 2, 2, 2, 5, 5]
    k = jax.random.split(jax.random.PRNGKey(0), 2)
    a = jax.random.normal(k[0], (7 * 8, 32), jnp.float32)
    b = jax.random.normal(k[1], (3, 32, 128), jnp.float32)
    ids = jnp.asarray([0, 0, 1, 1, 1, 2, 2], jnp.int32)
    cfg = GroupGemmConfig(block_m=8, block_n=128, block_k=32, ragged=True)
    want = group_gemm(a, b, ids, valid_rows=valid, config=cfg, interpret=True)
    got = group_gemm(a, b, ids, valid_rows=valid, config=cfg, interpret=True,
                     a_blocks=a_blocks)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.asarray(got)[8:16].any() and not np.asarray(got)[24:40].any()
    # ``into``: the same blocks written over blocks [2, 9) of a buffer of
    # 12, in place; the blocks around them keep what they held
    held = jnp.full((12 * 8, 128), jnp.nan, jnp.float32)
    first = jnp.asarray(2)          # traced in the pass that uses it
    there = jax.jit(lambda buf, first: group_gemm(
        a, b, ids, valid_rows=valid, config=cfg, interpret=True,
        a_blocks=a_blocks, into=(buf, first)))(held, first)
    np.testing.assert_array_equal(np.asarray(there)[16:72], np.asarray(want))
    assert np.isnan(np.asarray(there)[:16]).all()
    assert np.isnan(np.asarray(there)[72:]).all()
    with pytest.raises(ValueError, match="ragged"):
        group_gemm(a, b, ids, config=GroupGemmConfig(
            block_m=8, block_n=128, block_k=32), interpret=True,
            a_blocks=a_blocks)


def _expert_layer(cell_name: str):
    """``(cfg, one expert layer's leaves as shapes)`` of a benchmark cell
    at its published widths."""
    cell = cells.Cell(cells.benchmark(), cell_name)
    adapter = cells.load_module("programs", cell.config["program"])
    cfg = adapter.model_config(cell.config, interpret=True)
    family = sys.modules[type(cfg).__module__]
    init = getattr(family, "init_" + family.__name__.rsplit(".", 1)[-1]
                   + "_params")
    params = jax.eval_shape(functools.partial(init, cfg=cfg),
                            jax.random.PRNGKey(0))
    return cfg, next(p for p in params["layers"] if "we_gate_up" in p)


# cell -> (rows of a pass, block_m, chunks the alignment spans): every
# decode step and K-EXAONE's largest admission (bucket 256) fit one chunk;
# the admissions of 8192 (dots3, SmallThinker) and 256 rows x 8 of 256
# experts (JoyAI) span several
PASSES = {
    "dots3-note-prev-ep8.doc-reason": [(32, ge.DECODE_BLOCK_M, 1),
                                       (8192, ge.PREFILL_BLOCK_M, 8)],
    "granite-4.0-h-small-ep4.doc-reason": [(32, ge.DECODE_BLOCK_M, 1),
                                           (8192, ge.PREFILL_BLOCK_M, 19)],
    "k-exaone-236b-a23b-ep8.reason-long": [(32, ge.DECODE_BLOCK_M, 1),
                                           (256, ge.PREFILL_BLOCK_M, 1)],
    "joyai-llm-flash.reason": [(16, ge.DECODE_BLOCK_M, 1),
                               (256, ge.PREFILL_BLOCK_M, 8)],
    "smallthinker-21b-a3b.doc-reason": [(32, ge.DECODE_BLOCK_M, 1),
                                        (8192, ge.PREFILL_BLOCK_M, 13)],
}


@pytest.mark.parametrize("cell", sorted(PASSES))
def test_a_pass_of_at_most_one_chunk_traces_no_loop(cell):
    """At each gated-expert cell's published widths the decode step's
    expert pass (and an admission whose alignment fits one chunk) is
    today's straight-line calls, no ``while`` in its jaxpr; the long
    admissions trace exactly one, around both grouped GEMMs."""
    cfg, layer = _expert_layer(cell)
    assert cfg.batch == PASSES[cell][0][0]
    for rows, block_m, chunks in PASSES[cell]:
        h = jax.ShapeDtypeStruct((rows, cfg.hidden), cfg.dtype)
        text = str(jax.make_jaxpr(
            lambda h, p: ge.moe_mlp(cfg, h, p, block_m, True))(h, layer))
        t = rows * cfg.topk
        n_held = cfg.held[1]
        groups = n_held if n_held == cfg.n_experts else n_held + 1
        n_blocks = -(-(t + min(groups, t) * (block_m - 1)) // block_m)
        assert -(-n_blocks // ge._chunk_blocks(cfg, block_m)) == chunks, n_blocks
        share = n_held < cfg.n_experts
        # the sorted-row pass's loop and, behind a share's, the combine's
        # (a chunk of tokens' trips, inside a scan over the chunks)
        assert text.count("while[") == (chunks > 1) * (1 + share), (rows, chunks)
        assert text.count("pallas_call[") == 2


@pytest.mark.parametrize("cell", sorted(PASSES))
def test_the_combine_walks_behind_a_shares_chunked_pass_only(
        cell, chip_posture):
    """In the chip's posture (no validating ``lax.cond``) a routed pass
    sorts its assignments twice, as it did: the alignment's sort and the
    combine's of the padded slot ids. A whole bank's pass and a pass of at
    most one chunk keep the ``topk`` gathers of every token's row; a
    share's long admission sorts its ``rows`` tokens by their landed slots
    besides and gathers rows of the result a chunk a trip inside its walk
    and once after it."""
    cfg, layer = _expert_layer(cell)
    for rows, block_m, chunks in PASSES[cell]:
        h = jax.ShapeDtypeStruct((rows, cfg.hidden), cfg.dtype)
        text = str(jax.make_jaxpr(
            lambda h, p: ge.moe_mlp(cfg, h, p, block_m, True))(h, layer))
        sorts = [line for line in text.splitlines() if " sort[" in line]
        walk = chunks > 1 and cfg.held[1] < cfg.n_experts
        assert len(sorts) == 2 + walk, (rows, sorts)
        assert f"i32[{rows * cfg.topk}]" in sorts[0]
        if walk:
            assert f"i32[{rows}]" in sorts[2]
        gathers = lambda shape: sum(
            " gather[" in line and shape in line.split("=")[0]
            for line in text.splitlines())
        every = gathers(f"bf16[{rows},{cfg.hidden}]")
        assert every == (0 if walk else cfg.topk), (rows, every)
        if walk:
            trip = min(moe_utils.COMBINE_WALK_ROWS, rows)
            assert gathers(f"bf16[{trip},{cfg.hidden}]") == 1
            assert gathers(f"f32[{rows},{cfg.hidden}]") == 1
