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
    # every expert held: only the worst-case tail is dead
    "the_full_bank": ((0, 16), (), None),
    # every row chooses two experts held elsewhere: zero chunks, the
    # shared expert alone
    "nothing_here": ((0, 2), (8, 9), 0),
    # every row chooses the two held: every chunk runs
    "everything_here": ((0, 2), (0, 1), 32),
}


@pytest.mark.parametrize("regime", sorted(REGIMES))
def test_the_live_prefix_pass_is_the_whole_pass_bit_for_bit(regime, monkeypatch):
    """Chunks of 6 blocks over an alignment of 35 (a share) or 46 (the
    bank): the same output and counters as the one straight-line call
    over every block, with the rows never walked holding NaN (so one read
    as a number would show), and ``sorted_rows_walked`` the live prefix
    rounded up to whole chunks."""
    held, favoured, live_want = REGIMES[regime]
    c = Toy(held=held)
    p, h = _layer(c, favoured)
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
        assert text.count("while[") == (chunks > 1), (rows, chunks)
        assert text.count("pallas_call[") == 2
