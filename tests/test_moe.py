"""MoE ops vs goldens (≙ reference test_ag_group_gemm.py /
test_moe_reduce_rs.py: golden = torch grouped matmul + NCCL collectives;
here per-expert einsum + lax collectives). The whole TP-MoE MLP pipeline
is test_moe_pipeline.py."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.ops.allgather_group_gemm import ag_group_gemm_op
from triton_dist_tpu.ops.group_gemm import GroupGemmConfig, group_gemm
from triton_dist_tpu.ops.moe_reduce_rs import moe_reduce_rs_op
from triton_dist_tpu.ops import moe_utils
from triton_dist_tpu.ops.moe_utils import (
    combine_rows_gathered,
    gather_sorted_rows,
    moe_align_block_size,
    scatter_add_unsorted,
    select_experts,
)


def test_select_experts():
    logits = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    w, ids = select_experts(logits, 2)
    assert w.shape == (16, 2) and ids.shape == (16, 2)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)
    # ids are the argmax-2 experts
    want_ids = np.argsort(-np.asarray(logits), axis=-1)[:, :2]
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(want_ids, -1))


def test_moe_align_block_size():
    bm, n_exp = 4, 3
    topk_ids = jnp.array([2, 0, 0, 1, 2, 2, 0, 0, 0], jnp.int32)
    al = jax.jit(lambda i: moe_align_block_size(i, n_exp, bm))(topk_ids)
    t = topk_ids.shape[0]
    counts = np.bincount(np.asarray(topk_ids), minlength=n_exp)
    padded = ((counts + bm - 1) // bm) * bm
    assert int(al.num_tokens_post_pad) == padded.sum()
    sti = np.asarray(al.sorted_token_ids)
    eids = np.asarray(al.expert_ids)
    # every valid row's assignment belongs to its block's expert; blocks
    # are single-expert by construction
    seg_starts = np.concatenate([[0], np.cumsum(padded)[:-1]])
    for e in range(n_exp):
        seg = sti[seg_starts[e] : seg_starts[e] + padded[e]]
        valid = seg[seg < t]
        assert len(valid) == counts[e]
        np.testing.assert_array_equal(np.asarray(topk_ids)[valid], e)
    for blk, e in enumerate(eids):
        if blk * bm < padded.sum():
            assert seg_starts[e] <= blk * bm < seg_starts[e] + padded[e]


@pytest.mark.parametrize("dtype", [jnp.float32])
def test_group_gemm_vs_ragged_dot(dtype):
    n_exp, bm, k_dim, n_dim = 3, 8, 64, 256
    sizes = jnp.array([16, 8, 24], jnp.int32)  # already block-multiples
    t_pad = int(sizes.sum())
    a = jax.random.normal(jax.random.PRNGKey(1), (t_pad, k_dim)).astype(dtype)
    b = jax.random.normal(jax.random.PRNGKey(2), (n_exp, k_dim, n_dim)).astype(dtype)
    expert_ids = jnp.repeat(jnp.arange(n_exp, dtype=jnp.int32), sizes // bm)
    got = jax.jit(
        lambda a, b, e: group_gemm(a, b, e, config=GroupGemmConfig(bm, 128, 32))
    )(a, b, expert_ids)
    want = jax.lax.ragged_dot(a, b, group_sizes=sizes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_gather_scatter_roundtrip():
    bm, n_exp, topk, n_tokens, h = 4, 3, 2, 10, 16
    key = jax.random.PRNGKey(3)
    ids = jax.random.randint(key, (n_tokens, topk), 0, n_exp, jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(4), (n_tokens, h), jnp.float32)
    al = moe_align_block_size(ids.reshape(-1), n_exp, bm)
    rows = gather_sorted_rows(x, al, topk)
    w = jnp.full((n_tokens, topk), 0.5, jnp.float32)
    back = scatter_add_unsorted(rows, al, w, n_tokens)
    # each token appears topk times with weight 0.5 → back == x * topk * 0.5
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), rtol=1e-5, atol=1e-5)
    # the masked-scatter contract path (capacity-style alignments) must
    # agree on a bijective alignment
    back_sc = scatter_add_unsorted(rows, al, w, n_tokens, assume_bijective=False)
    np.testing.assert_allclose(
        np.asarray(back_sc), np.asarray(x), rtol=1e-5, atol=1e-5
    )
    # a DROPPED slot (simulated capacity overflow: its row goes sentinel)
    # contributes zero under the masked path instead of shifting rows
    al_drop = dataclasses.replace(
        al,
        sorted_token_ids=jnp.where(
            al.sorted_token_ids == 0, n_tokens * topk, al.sorted_token_ids
        ),
    )
    back_dr = scatter_add_unsorted(
        rows, al_drop, w, n_tokens, assume_bijective=False
    )
    want = np.asarray(x).copy()
    want[0] = want[0] / 2  # token 0 lost one of its two 0.5-weight slots
    np.testing.assert_allclose(np.asarray(back_dr), want, rtol=1e-5, atol=1e-5)
    # interpret/debug mode VALIDATES the bijection contract (ADVICE r5 #1):
    # the same dropped slot under assume_bijective=True is detected and
    # routed to the masked-scatter semantics instead of silently shifting
    # every later token onto the wrong rows
    back_guard = scatter_add_unsorted(rows, al_drop, w, n_tokens)
    np.testing.assert_allclose(
        np.asarray(back_guard), want, rtol=1e-5, atol=1e-5
    )


def _share(n_exp: int, n_held: int, m: int, topk: int, bm: int, h: int):
    """A share's routing as ``gated_experts`` aligns it (the experts held
    elsewhere one group, sorted last), with hand-made tokens: token 0 has
    NO landed slot, token 1 ALL ``topk``. ``y`` holds NaN in every row past
    the live prefix (the rows no chunk wrote) and some -0.0 among token 1's
    rows. The weights are powers of two: each product ``y x w`` is then
    exact in float32, so a compiler that contracts a multiply and an add
    into one rounding (XLA's CPU backend does, and not alike in the two
    forms: 1 ulp between them with free weights) gives the sum of the
    MATERIALISED products whatever it contracts, and ``array_equal`` tests
    the order of the additions, which is what the two forms could differ
    in."""
    k = jax.random.split(jax.random.PRNGKey(n_exp + m), 3)
    ids = jnp.argsort(jax.random.uniform(k[0], (m, n_exp)), axis=1)[:, :topk]
    ids = ids.at[0].set(n_held + jnp.arange(topk)).at[1].set(jnp.arange(topk))
    here = ids < n_held
    al = moe_align_block_size(
        jnp.where(here, ids, n_held).reshape(-1).astype(jnp.int32),
        n_held + 1, bm, ragged=True)
    live = int(jnp.sum((al.valid_rows > 0) & (al.expert_ids < n_held))) * bm
    y = jax.random.normal(k[1], (al.sorted_token_ids.shape[0], h), jnp.float32)
    inv = jnp.argsort(al.sorted_token_ids, stable=True)[:m * topk]
    y = y.at[inv[topk:2 * topk], :3].set(-0.0).astype(jnp.bfloat16)
    y = y.at[live:].set(jnp.nan)
    w = 2.0 ** jax.random.randint(k[2], (m, topk), -4, 1).astype(jnp.float32)
    return al, y, jnp.where(here, w, 0.0), here, inv.reshape(m, topk)


@pytest.mark.parametrize("n_exp,n_held,topk", [(24, 6, 5), (32, 4, 4)],
                         ids=["a_quarter", "an_eighth"])
def test_the_landed_walk_is_the_whole_form_bit_for_bit(
        n_exp, n_held, topk, monkeypatch):
    """``scatter_add_unsorted(written=here)`` walks the landed slots; the
    ``topk``-gather form over the same rows with zeros where nobody wrote
    (an unlanded slot's term is then ``0.0 x 0.0``, the +0.0 its selection
    gave before) is its reference, ``array_equal`` on float32: a token
    with no landed
    slot, one with all of them (-0.0 among its terms), trips whose last
    rows reach past their prefix, and NaN in every row of ``y`` that no
    chunk wrote (fetched and USED, it would show)."""
    m, h = 96, 24
    monkeypatch.setattr(moe_utils, "COMBINE_WALK_ROWS", 16)
    al, y, w, here, inv = _share(n_exp, n_held, m, topk, 4, h)
    landed = np.asarray(here).sum(1)
    assert landed[0] == 0 and landed[1] == topk
    assert any((landed > j).sum() % 16 for j in range(topk))
    got = jax.jit(lambda y, al, w, here: scatter_add_unsorted(
        y, al, w, m, written=here))(y, al, w, here)
    want = jax.jit(moe_utils._slot_sum)(jnp.nan_to_num(y), inv, w)
    assert got.dtype == jnp.float32 and np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.asarray(got)[0].any()
    # the sign of a zero too: where every slot landed and every term is
    # -0.0 the whole form gives -0.0, elsewhere +0.0
    np.testing.assert_array_equal(np.signbit(np.asarray(got)),
                                  np.signbit(np.asarray(want)))
    assert np.signbit(np.asarray(want)[1, :3]).all()
    # against plain numpy: float32 products, added in ascending k
    inv = np.asarray(inv)
    ref = np.zeros((m, h), np.float32)
    for k in range(topk):
        rows = np.asarray(y.astype(jnp.float32))[inv[:, k]]
        ref += np.where(np.asarray(here)[:, k, None],
                        rows * np.asarray(w)[:, k, None], np.float32(0))
    np.testing.assert_array_equal(np.asarray(got), ref)


@pytest.mark.parametrize("n_exp,n_held,topk", [(24, 6, 5), (32, 4, 4)],
                         ids=["a_quarter", "an_eighth"])
def test_the_combine_counts_the_rows_it_gathers(n_exp, n_held, topk,
                                                monkeypatch):
    """``combine_rows_gathered``: ``topk x m`` in the whole form; on the
    walk the landed slots, the ``m`` rows of the last gather and at most
    one chunk's rows of rounding for each ``j`` that any token reaches."""
    m, rows = 96, 16
    monkeypatch.setattr(moe_utils, "COMBINE_WALK_ROWS", rows)
    here = _share(n_exp, n_held, m, topk, 4, 8)[3]
    assert combine_rows_gathered(m, topk) == topk * m
    got = int(combine_rows_gathered(m, topk, here))
    landed = np.asarray(here).sum(1)
    reached = sum((landed > j).any() for j in range(topk))
    assert int(landed.sum()) + m <= got <= int(landed.sum()) + m + rows * reached
    assert got == m + sum(-(-int((landed > j).sum()) // rows) * rows
                          for j in range(topk))
    assert got < topk * m
    assert int(combine_rows_gathered(m, topk, jnp.zeros_like(here))) == m


@pytest.mark.parametrize("form", ["whole", "landed_walk"])
def test_the_combine_sorts_no_assignment_twice(form, chip_posture):
    """The sorts in the combine's program: the one over the padded slot
    ids (each slot's row) in both forms, and on the landed walk one more
    of ``m`` keys (the tokens by their landed slots), never a second one
    over the assignments. The whole form is ``topk`` gathers of every
    token's row and no loop; the walk gathers inside its loops, a chunk's
    rows a trip, and once after them."""
    m, topk, h = 96, 4, 8
    al, y, w, here, _ = _share(32, 4, m, topk, 4, h)
    written = here if form == "landed_walk" else None
    text = str(jax.make_jaxpr(lambda y, al, w: scatter_add_unsorted(
        y, al, w, m, written=written))(y, al, w))
    sorts = [line for line in text.splitlines() if " sort[" in line]
    assert f"i32[{al.sorted_token_ids.shape[0]}]" in sorts[0]
    gathers = lambda shape: sum(" gather[" in line and shape in line.split("=")[0]
                                for line in text.splitlines())
    if form == "whole":
        assert len(sorts) == 1 and "while[" not in text and "scan[" not in text
        assert gathers(f"bf16[{m},{h}]") == topk
    else:
        assert len(sorts) == 2 and f"i32[{m}]" in sorts[1]
        # a scan over the chunks of tokens, a loop over a chunk's trips
        assert text.count("scan[") == 1 and text.count("while[") == 1
        assert gathers(f"bf16[{m},{h}]") == 1 and gathers(f"f32[{m},{h}]") == 1


def _moe_golden(a, b, topk_ids):
    """Dense per-assignment golden: out[t*topk+k] = a[t] @ b[ids[t,k]]."""
    m, topk = topk_ids.shape
    flat = np.asarray(topk_ids).reshape(-1)
    a_np = np.asarray(a, np.float32)
    b_np = np.asarray(b, np.float32)
    return np.stack([a_np[i // topk] @ b_np[flat[i]] for i in range(m * topk)])


def test_ag_group_gemm(mesh4):
    m_tot, k_dim, n_dim, n_exp, topk = 16, 64, 256, 4, 2
    a = jax.random.normal(jax.random.PRNGKey(5), (m_tot, k_dim), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(6), (n_exp, k_dim, n_dim), jnp.float32)
    ids = jax.random.randint(jax.random.PRNGKey(7), (m_tot, topk), 0, n_exp, jnp.int32)
    got = ag_group_gemm_op(a, b, ids, mesh4, config=GroupGemmConfig(8, 64, 32))
    want = _moe_golden(a, b, ids)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_moe_reduce_rs(mesh4):
    n_tokens, f_dim, h_dim, n_exp, topk, bm = 16, 128, 64, 4, 2, 8
    key = jax.random.PRNGKey(8)
    ids = jax.random.randint(key, (n_tokens, topk), 0, n_exp, jnp.int32)
    al = moe_align_block_size(ids.reshape(-1), n_exp, bm)
    t_pad = al.sorted_token_ids.shape[0]
    h_sorted = jax.random.normal(jax.random.PRNGKey(9), (t_pad, f_dim), jnp.float32)
    w_down = jax.random.normal(jax.random.PRNGKey(10), (n_exp, f_dim, h_dim), jnp.float32)
    tw = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(11), (n_tokens, topk)))
    got = moe_reduce_rs_op(
        h_sorted, w_down, al.sorted_token_ids, al.expert_ids, tw, mesh4,
        config=GroupGemmConfig(bm, 64, 32),
    )
    # golden: full grouped GEMM + weighted unsort, no sharding
    y = np.stack(
        [
            np.asarray(h_sorted, np.float32)[r]
            @ np.asarray(w_down, np.float32)[int(al.expert_ids[r // bm])]
            for r in range(t_pad)
        ]
    )
    want = np.zeros((n_tokens, h_dim), np.float32)
    sti = np.asarray(al.sorted_token_ids)
    tw_np = np.asarray(tw, np.float32).reshape(-1)
    for r in range(t_pad):
        if sti[r] < n_tokens * topk:
            want[sti[r] // topk] += tw_np[sti[r]] * y[r]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_group_gemm_dw_matches_segment_sum():
    """Transpose grouped GEMM (expert-steered output accumulation) vs the
    per-block outer-product segment-sum golden; expert 2 has no rows and
    must come back exactly zero."""
    from triton_dist_tpu.ops.group_gemm import GroupGemmConfig, group_gemm_dw

    bm, n_blocks, k_dim, n_dim, n_exp = 8, 6, 32, 64, 4
    t_pad = bm * n_blocks
    a = jax.random.normal(jax.random.PRNGKey(90), (t_pad, k_dim), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(91), (t_pad, n_dim), jnp.float32)
    expert_ids = jnp.asarray([0, 3, 1, 0, 3, 3], jnp.int32)  # UNSORTED; 2 empty
    got = group_gemm_dw(
        a, g, expert_ids, n_exp, config=GroupGemmConfig(bm, 32, 16)
    )
    want = np.zeros((n_exp, k_dim, n_dim), np.float32)
    for i in range(n_blocks):
        e = int(expert_ids[i])
        want[e] += np.asarray(a[i * bm : (i + 1) * bm]).T @ np.asarray(
            g[i * bm : (i + 1) * bm]
        )
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    assert np.all(np.asarray(got)[2] == 0)


def test_moe_align_ranked_invariants():
    """Per-rank alignment: every block draws rows from exactly one rank's
    chunk, blocks are single-expert, and src_rows point at the right
    gathered-A rows."""
    from triton_dist_tpu.ops.moe_utils import moe_align_ranked

    n, m_loc, topk, n_exp, bm = 4, 8, 2, 3, 4
    ids = jax.random.randint(
        jax.random.PRNGKey(7), (n, m_loc * topk), 0, n_exp, jnp.int32
    )
    ral = jax.jit(
        lambda i: moe_align_ranked(i, n_exp, bm, m_loc)
    )(ids)
    lids = np.asarray(ral.local_ids)
    srows = np.asarray(ral.src_rows)
    eids = np.asarray(ral.expert_ids)
    t_loc = m_loc * topk
    assert ral.block_m == bm and ral.n_ranks == n
    for c in range(n):
        for r in range(ral.t_pad_loc):
            if lids[c, r] >= t_loc:
                # sentinel rows clamp to a row of their OWN chunk
                assert c * m_loc <= srows[c, r] < (c + 1) * m_loc
                continue
            # valid rows: correct source row + correct expert for the block
            assert srows[c, r] == c * m_loc + lids[c, r] // topk
            assert ids[c, lids[c, r]] == eids[c, r // bm]


def test_ag_group_gemm_overlap_vs_sequential(mesh4):
    """The single-kernel overlapped AG-GroupGEMM must produce exactly the
    rows the sequential composition produces (checked row-by-row via the
    rank-major alignment against a dense golden)."""
    from triton_dist_tpu.ops.allgather_group_gemm import ag_group_gemm_overlap
    from triton_dist_tpu.ops.moe_utils import moe_align_ranked

    n, m_loc, topk, n_exp, k_dim, n_loc = 4, 8, 2, 3, 32, 64
    bm = 4
    cfg = GroupGemmConfig(block_m=bm, block_n=32, block_k=32)
    ka, kb, ki = jax.random.split(jax.random.PRNGKey(11), 3)
    a = jax.random.normal(ka, (n * m_loc, k_dim), jnp.float32)
    b = jax.random.normal(kb, (n_exp, k_dim, n_loc), jnp.float32)
    ids = jax.random.randint(ki, (n * m_loc, topk), 0, n_exp, jnp.int32)

    def fn(a_loc, b_loc, ids_all):
        ral = moe_align_ranked(
            ids_all.reshape(n, m_loc * topk), n_exp, bm, m_loc
        )
        h, ag = ag_group_gemm_overlap(
            a_loc, b_loc, ral, axis="tp", config=cfg, gather_output=True
        )
        return h, ag, ral.local_ids, ral.src_rows, ral.expert_ids

    out, ag, lids, srows, eids = jax.jit(
        jax.shard_map(
            fn, mesh=mesh4,
            in_specs=(P("tp", None), P(None, None, None), P(None, None)),
            out_specs=(P(None, None),) * 5,
            check_vma=False,
        )
    )(
        jax.device_put(a, jax.NamedSharding(mesh4, P("tp", None))), b, ids
    )
    out, lids, srows, eids = map(np.asarray, (out, lids, srows, eids))
    # gather_output contract: the SORTED gathered slab — row (c, r) is the
    # source token row srows[c, r] (sentinels clamp to a row of own chunk)
    np.testing.assert_allclose(
        np.asarray(ag), np.asarray(a)[srows.reshape(-1)], atol=0, rtol=0
    )
    t_pad_loc = lids.shape[1]
    a_np, b_np = np.asarray(a), np.asarray(b)
    for c in range(n):
        for r in range(t_pad_loc):
            if lids[c, r] >= m_loc * topk:
                continue
            want = a_np[srows[c, r]] @ b_np[eids[c, r // bm]]
            np.testing.assert_allclose(
                out[c * t_pad_loc + r], want, rtol=1e-4, atol=1e-4
            )


def test_ag_group_gemm_overlap_multigroup(mesh4):
    """The VMEM-bounded multi-group gather path (gather_group_blocks forces
    several double-buffered row groups per chunk) must match the dense
    golden exactly like the single-group path."""
    from triton_dist_tpu.ops.allgather_group_gemm import ag_group_gemm_overlap
    from triton_dist_tpu.ops.moe_utils import moe_align_ranked

    n, m_loc, topk, n_exp, k_dim, n_loc = 4, 8, 2, 3, 32, 64
    bm = 4
    cfg = GroupGemmConfig(block_m=bm, block_n=32, block_k=32)
    ka, kb, ki = jax.random.split(jax.random.PRNGKey(17), 3)
    a = jax.random.normal(ka, (n * m_loc, k_dim), jnp.float32)
    b = jax.random.normal(kb, (n_exp, k_dim, n_loc), jnp.float32)
    ids = jax.random.randint(ki, (n * m_loc, topk), 0, n_exp, jnp.int32)

    def fn(a_loc, b_loc, ids_all):
        ral = moe_align_ranked(
            ids_all.reshape(n, m_loc * topk), n_exp, bm, m_loc
        )
        h = ag_group_gemm_overlap(
            a_loc, b_loc, ral, axis="tp", config=cfg, gather_group_blocks=2
        )
        return h, ral.local_ids, ral.src_rows, ral.expert_ids

    out, lids, srows, eids = map(np.asarray, jax.jit(
        jax.shard_map(
            fn, mesh=mesh4,
            in_specs=(P("tp", None), P(None, None, None), P(None, None)),
            out_specs=(P(None, None),) * 4,
            check_vma=False,
        )
    )(
        jax.device_put(a, jax.NamedSharding(mesh4, P("tp", None))), b, ids
    ))
    t_pad_loc = lids.shape[1]
    a_np, b_np = np.asarray(a), np.asarray(b)
    for c in range(n):
        for r in range(t_pad_loc):
            if lids[c, r] >= m_loc * topk:
                continue
            want = a_np[srows[c, r]] @ b_np[eids[c, r // bm]]
            np.testing.assert_allclose(
                out[c * t_pad_loc + r], want, rtol=1e-4, atol=1e-4
            )


def test_overlap_vmem_budgets_at_bench_scale():
    """Host-side shape derivations of the two overlapped kernels stay
    inside VMEM at the driver's REAL bench shapes (n=1 and n=8; the bugs
    this guards against — 142 MiB resident rows, a non-power-of-two cap
    walking pick_block down to bn=1 — only trigger at those scales, which
    interpreter tests can't reach)."""
    from triton_dist_tpu.ops.allgather_group_gemm import gather_group_blocks_for
    from triton_dist_tpu.ops.moe_reduce_rs import rs_block_n_for

    bm = 128
    for n in (1, 8):
        m_loc, topk, n_exp, h_dim, f_dim = 8192 // n, 2, 8, 4096, 14336
        t_pad_loc = ((m_loc * topk + n_exp * (bm - 1) + bm - 1) // bm) * bm
        nb = t_pad_loc // bm
        bpg = gather_group_blocks_for(nb, bm, h_dim, 2)
        assert 1 <= bpg <= nb
        assert 2 * bpg * bm * h_dim * 2 <= 16 * 2**20       # resident rows
        bn = rs_block_n_for(h_dim, 1024, m_loc, f_dim // n, 2, 2)
        assert bn >= 128 and h_dim % bn == 0
        assert (
            m_loc * bn * 4 + 2 * m_loc * bn * 2 + 2 * (f_dim // n) * bn * 2
            <= 48 * 2**20
        )
    # a pathological budget/shape mix must never collapse below 128 lanes
    assert rs_block_n_for(4096, 1024, 65536, 28672, 4, 4) >= 128


def test_group_gemm_w8_matches_f32():
    """int8-weight grouped GEMM (per-(expert, column) absmax scales):
    within weight-quantization tolerance of the f32 kernel; experts with
    zero rows and padded blocks behave identically."""
    from triton_dist_tpu.ops.group_gemm import (
        group_gemm, group_gemm_w8, quantize_expert_weights,
    )

    E, topk, m, H, F, bm = 4, 2, 96, 64, 128, 16
    tw, ids = select_experts(
        jax.random.normal(jax.random.PRNGKey(80), (m, E)), topk
    )
    al = moe_align_block_size(ids.reshape(-1), E, bm)
    x = jax.random.normal(jax.random.PRNGKey(81), (m, H), jnp.float32)
    sti = al.sorted_token_ids
    xs = jnp.where(
        (sti < m * topk)[:, None], x[jnp.clip(sti // topk, 0, m - 1)], 0
    )
    b = jax.random.normal(jax.random.PRNGKey(82), (E, H, F), jnp.float32) / 8
    b_q, scale = quantize_expert_weights(b)
    cfg = GroupGemmConfig(bm, 64, 32)
    want = np.asarray(group_gemm(xs, b, al.expert_ids, config=cfg))
    got = np.asarray(group_gemm_w8(xs, b_q, scale, al.expert_ids, config=cfg))
    denom = np.abs(want).max() + 1e-9
    assert np.abs(got - want).max() / denom < 2e-2
