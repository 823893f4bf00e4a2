"""Flash decode vs jnp reference (≙ reference test_flash_decode scripts:
golden = torch attention over the full cache)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_dist_tpu.ops.flash_decode import (
    FlashDecodeConfig,
    combine_partials,
    flash_decode,
    flash_decode_op,
    paged_flash_decode,
)


def _paginate(k, v, page_size, key=None, n_extra_pages=0):
    """Split a contiguous cache into shuffled pages + block table."""
    b, h_kv, s, d = k.shape
    ppseq = s // page_size
    n_pages = b * ppseq + n_extra_pages
    perm = (
        jax.random.permutation(key, n_pages)[: b * ppseq]
        if key is not None
        else jnp.arange(b * ppseq)
    )
    bt = perm.reshape(b, ppseq).astype(jnp.int32)
    kp = jnp.zeros((n_pages, h_kv, page_size, d), k.dtype)
    vp = jnp.zeros((n_pages, h_kv, page_size, d), v.dtype)
    k_chunks = k.reshape(b, h_kv, ppseq, page_size, d)
    v_chunks = v.reshape(b, h_kv, ppseq, page_size, d)
    for bi in range(b):
        for ci in range(ppseq):
            kp = kp.at[bt[bi, ci]].set(k_chunks[bi, :, ci])
            vp = vp.at[bt[bi, ci]].set(v_chunks[bi, :, ci])
    return kp, vp, bt


def _ref_decode(q, k, v, kv_lens):
    """Pure-jnp masked attention golden."""
    b, hq, d = q.shape
    _, h_kv, s, _ = k.shape
    g = hq // h_kv
    q4 = q.reshape(b, h_kv, g, d).astype(jnp.float32)
    scores = jnp.einsum("bhgd,bhsd->bhgs", q4, k.astype(jnp.float32))
    scores /= jnp.sqrt(jnp.float32(d))
    mask = jnp.arange(s)[None, :] < kv_lens[:, None]  # [b, s]
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(b, hq, d)


def _rand_case(key, b, hq, h_kv, s, d, dtype=jnp.float32):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    q = jax.random.normal(k1, (b, hq, d)).astype(dtype)
    k = jax.random.normal(k2, (b, h_kv, s, d)).astype(dtype)
    v = jax.random.normal(k3, (b, h_kv, s, d)).astype(dtype)
    kv_lens = jax.random.randint(k4, (b,), 1, s + 1, jnp.int32)
    return q, k, v, kv_lens


@pytest.mark.parametrize("g", [1, 4])
def test_flash_decode_local(g):
    b, h_kv, s, d = 2, 2, 256, 128
    q, k, v, kv_lens = _rand_case(jax.random.PRNGKey(0), b, h_kv * g, h_kv, s, d)
    got = flash_decode(q, k, v, kv_lens, config=FlashDecodeConfig(block_s=64))
    want = _ref_decode(q, k, v, kv_lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_flash_decode_full_and_empty_lens():
    b, h_kv, g, s, d = 3, 1, 2, 128, 128
    q, k, v, _ = _rand_case(jax.random.PRNGKey(1), b, h_kv * g, h_kv, s, d)
    kv_lens = jnp.array([s, 1, 7], jnp.int32)
    got = flash_decode(q, k, v, kv_lens, config=FlashDecodeConfig(block_s=32))
    want = _ref_decode(q, k, v, kv_lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_combine_partials_matches_monolithic():
    """Splitting a cache into shards and merging (out, lse) must reproduce
    full attention exactly (the reference's inter-rank combine invariant)."""
    b, h_kv, g, s, d = 2, 2, 1, 256, 128
    q, k, v, kv_lens = _rand_case(jax.random.PRNGKey(2), b, h_kv * g, h_kv, s, d)
    n = 4
    s_loc = s // n
    outs, lses = [], []
    for i in range(n):
        sl = slice(i * s_loc, (i + 1) * s_loc)
        local_lens = jnp.clip(kv_lens - i * s_loc, 0, s_loc)
        o, l = flash_decode(
            q, k[:, :, sl], v[:, :, sl], local_lens,
            config=FlashDecodeConfig(block_s=32), return_lse=True,
        )
        outs.append(o)
        lses.append(l)
    got = combine_partials(jnp.stack(outs), jnp.stack(lses))
    want = _ref_decode(q, k, v, kv_lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_flash_decode_sp_op(mesh4):
    """Full SP pipeline: KV sharded over 4 PEs, LL allgather + merge."""
    b, h_kv, g, s, d = 2, 1, 2, 128, 128
    q, k, v, _ = _rand_case(jax.random.PRNGKey(3), b, h_kv * g, h_kv, s, d)
    kv_lens = jnp.array([s, 40], jnp.int32)  # rank >1 partially/fully empty
    got = flash_decode_op(q, k, v, kv_lens, mesh4, config=FlashDecodeConfig(block_s=32))
    want = _ref_decode(q, k, v, kv_lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("g", [1, 4])
def test_paged_flash_decode_matches_contiguous(g):
    """Paged (shuffled pages, block-table indirection) must exactly match
    the contiguous kernel — the block table only changes page placement."""
    b, h_kv, s, d, page = 2, 2, 256, 128, 64
    q, k, v, kv_lens = _rand_case(jax.random.PRNGKey(5), b, h_kv * g, h_kv, s, d)
    kp, vp, bt = _paginate(k, v, page, key=jax.random.PRNGKey(6), n_extra_pages=3)
    got = paged_flash_decode(q, kp, vp, kv_lens, bt)
    want = flash_decode(q, k, v, kv_lens, config=FlashDecodeConfig(block_s=page))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)
    ref = _ref_decode(q, k, v, kv_lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("fuse_heads", [True, False])
def test_paged_flash_decode_quant(fuse_heads):
    """int8 page pools (the paged × int8 cell of the serving cache
    matrix): per-position absmax row scales fold in-kernel; tolerance
    matches the contiguous int8 path's quantization error, and ragged
    lengths mask exactly as in the bf16 kernel."""
    from triton_dist_tpu.ops.flash_decode import (
        paged_flash_decode_quant, quantize_kv_pages,
    )

    b, h_kv, g, s, d, page = 3, 2, 2, 256, 128, 64
    q, k, v, _ = _rand_case(jax.random.PRNGKey(21), b, h_kv * g, h_kv, s, d)
    # min length 1: the dense _ref_decode golden is NaN over an empty
    # prefix (0/0 softmax) while the kernel's contract emits zeros —
    # the zero-length path is covered by the SP-op test's golden
    kv_lens = jnp.array([s, 97, 1], jnp.int32)
    kp, vp, bt = _paginate(k, v, page, key=jax.random.PRNGKey(22),
                           n_extra_pages=2)
    k_q, v_q, ks, vs = quantize_kv_pages(kp, vp)
    got = paged_flash_decode_quant(
        q, k_q, v_q, ks, vs, kv_lens, bt, fuse_heads=fuse_heads,
    )
    want = _ref_decode(q, k, v, kv_lens)
    # same tolerance as the contiguous int8 tests — the quantization
    # error is identical by construction (shared quantize_kv math)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-2, atol=3e-2)


def test_paged_flash_decode_ragged_lens():
    """Partial last page + empty sequences mask correctly."""
    b, h_kv, g, s, d, page = 3, 1, 2, 128, 128, 32
    q, k, v, _ = _rand_case(jax.random.PRNGKey(7), b, h_kv * g, h_kv, s, d)
    kv_lens = jnp.array([s, 41, 1], jnp.int32)  # mid-page boundaries
    kp, vp, bt = _paginate(k, v, page, key=jax.random.PRNGKey(8))
    got = paged_flash_decode(q, kp, vp, kv_lens, bt)
    want = _ref_decode(q, k, v, kv_lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_paged_flash_decode_sp(mesh4):
    """Paged SP decode: each PE's page pool covers its sequence shard."""
    from jax.sharding import PartitionSpec as P

    from triton_dist_tpu.ops.flash_decode import paged_flash_decode_distributed

    b, h_kv, g, s, d, page = 2, 1, 2, 256, 128, 32
    world = 4
    s_loc = s // world
    q, k, v, _ = _rand_case(jax.random.PRNGKey(9), b, h_kv * g, h_kv, s, d)
    kv_lens = jnp.array([s, 100], jnp.int32)
    # build each PE's pool from its shard; stack pools on a leading axis
    pools = []
    for i in range(world):
        sl = slice(i * s_loc, (i + 1) * s_loc)
        kp, vp, bt = _paginate(
            k[:, :, sl], v[:, :, sl], page, key=jax.random.PRNGKey(10 + i)
        )
        pools.append((kp, vp, bt))
    kps = jnp.stack([p[0] for p in pools])
    vps = jnp.stack([p[1] for p in pools])
    bts = jnp.stack([p[2] for p in pools])

    def fn(q, kps, vps, bts, lens):
        me = jax.lax.axis_index("tp")
        local_lens = jnp.clip(lens - me * s_loc, 0, s_loc)
        return paged_flash_decode_distributed(
            q, kps[0], vps[0], local_lens, bts[0], axis="tp"
        )

    got = jax.jit(
        jax.shard_map(
            fn, mesh=mesh4,
            in_specs=(P(None, None, None), P("tp", None, None, None, None),
                      P("tp", None, None, None, None), P("tp", None, None), P(None)),
            out_specs=P(None, None, None), check_vma=False,
        )
    )(q, kps, vps, bts, kv_lens)
    want = _ref_decode(q, k, v, kv_lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_flash_decode_sp_world1():
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    b, h_kv, g, s, d = 1, 2, 2, 64, 128
    q, k, v, kv_lens = _rand_case(jax.random.PRNGKey(4), b, h_kv * g, h_kv, s, d)
    got = flash_decode_op(q, k, v, kv_lens, mesh, config=FlashDecodeConfig(block_s=32))
    want = _ref_decode(q, k, v, kv_lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_flash_decode_xla_candidate(mesh4):
    """block_s=0 (XLA-native formulation): same (out, lse) contract as the
    Pallas kernel at world-1 AND through the SP combine (partial shards,
    one fully-empty shard)."""
    cfg = FlashDecodeConfig(block_s=0)
    b, h_kv, g, s, d = 2, 1, 2, 128, 128
    q, k, v, _ = _rand_case(jax.random.PRNGKey(6), b, h_kv * g, h_kv, s, d)
    kv_lens = jnp.array([s, 40], jnp.int32)  # rank >1 partially/fully empty
    want = _ref_decode(q, k, v, kv_lens)
    got = flash_decode_op(q, k, v, kv_lens, mesh4, config=cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("tp",))
    got1 = flash_decode_op(q, k, v, kv_lens, mesh1, config=cfg)
    np.testing.assert_allclose(np.asarray(got1), np.asarray(want), rtol=2e-4, atol=2e-4)
    # standalone (out, lse) parity vs the kernel
    out_x, lse_x = flash_decode(q, k, v, kv_lens, config=cfg, return_lse=True)
    out_p, lse_p = flash_decode(
        q, k, v, kv_lens, config=FlashDecodeConfig(block_s=32),
        return_lse=True, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out_x), np.asarray(out_p), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(lse_x), np.asarray(lse_p), rtol=2e-4, atol=2e-4)


def test_flash_decode_quant_parity():
    """int8 KV cache (absmax row scales): output within quantization
    tolerance of the f32 path; zero-length rows handled."""
    from triton_dist_tpu.ops.flash_decode import (
        FlashDecodeConfig, flash_decode, flash_decode_quant, quantize_kv,
    )

    b, hq, h_kv, s, d = 2, 4, 2, 64, 128
    q, k, v, _ = _rand_case(jax.random.PRNGKey(30), b, hq, h_kv, s, d)
    kv_lens = jnp.array([s, 37], jnp.int32)
    cfg = FlashDecodeConfig(block_s=16)
    want = flash_decode(q, k, v, kv_lens, config=cfg)
    k_q, v_q, ks, vs = quantize_kv(k, v)
    got = flash_decode_quant(q, k_q, v_q, ks, vs, kv_lens, config=cfg)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=3e-2, atol=3e-2
    )


def test_flash_decode_quant_distributed(mesh4):
    """SP decode over a sequence-sharded int8 cache merges to the same
    answer as the f32 distributed path (within quantization error)."""
    from jax.sharding import PartitionSpec as P

    from triton_dist_tpu.ops.flash_decode import (
        FlashDecodeConfig, flash_decode_distributed,
        flash_decode_quant_distributed, quantize_kv,
    )

    b, hq, h_kv, s, d = 2, 4, 2, 128, 128
    q, k, v, _ = _rand_case(jax.random.PRNGKey(31), b, hq, h_kv, s, d)
    kv_lens = jnp.array([s, 57], jnp.int32)
    s_loc = s // 4
    cfg = FlashDecodeConfig(block_s=8)

    def local_lens(me):
        return jnp.clip(kv_lens - me * s_loc, 0, s_loc)

    def f32_fn(q, k_s, v_s):
        me = jax.lax.axis_index("tp")
        return flash_decode_distributed(
            q, k_s, v_s, local_lens(me), axis="tp", config=cfg
        )

    def q_fn(q, k_s, v_s):
        me = jax.lax.axis_index("tp")
        k_q, v_q, ks, vs = quantize_kv(k_s, v_s)
        return flash_decode_quant_distributed(
            q, k_q, v_q, ks, vs, local_lens(me), axis="tp", config=cfg
        )

    spec_kv = P(None, None, "tp", None)
    run = lambda fn: jax.jit(
        jax.shard_map(
            fn, mesh=mesh4, in_specs=(P(None, None, None), spec_kv, spec_kv),
            out_specs=P(None, None, None), check_vma=False,
        )
    )(q, k, v)
    want = run(f32_fn)
    jax.block_until_ready(want)
    got = run(q_fn)
    jax.block_until_ready(got)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=3e-2, atol=3e-2
    )


@pytest.mark.parametrize("g", [1, 4])
def test_flash_decode_fused_heads_matches_per_head(g):
    """fuse_heads moves the kv-head loop inside the kernel (one K/V slab
    per chunk step); the math is identical, so it must match the per-head
    kernel bit-for-bit at the same chunking."""
    b, h_kv, s, d = 2, 4, 256, 128
    q, k, v, kv_lens = _rand_case(
        jax.random.PRNGKey(40), b, h_kv * g, h_kv, s, d
    )
    want = flash_decode(q, k, v, kv_lens, config=FlashDecodeConfig(block_s=64))
    got = flash_decode(
        q, k, v, kv_lens,
        config=FlashDecodeConfig(block_s=64, fuse_heads=True),
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)
    ref = _ref_decode(q, k, v, kv_lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_flash_decode_fused_heads_ragged_and_lse():
    """Ragged lens (incl. empty) and the (out, lse) contract under
    fuse_heads — the SP combine consumes either kernel's partials."""
    b, h_kv, g, s, d = 3, 2, 2, 128, 128
    q, k, v, _ = _rand_case(jax.random.PRNGKey(41), b, h_kv * g, h_kv, s, d)
    kv_lens = jnp.array([s, 37, 0], jnp.int32)
    o_f, l_f = flash_decode(
        q, k, v, kv_lens,
        config=FlashDecodeConfig(block_s=32, fuse_heads=True),
        return_lse=True,
    )
    o_p, l_p = flash_decode(
        q, k, v, kv_lens, config=FlashDecodeConfig(block_s=32),
        return_lse=True,
    )
    np.testing.assert_allclose(np.asarray(o_f), np.asarray(o_p), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(l_f), np.asarray(l_p), rtol=1e-6, atol=1e-6)


def test_flash_decode_fused_heads_quant():
    """int8 + fuse_heads: per-position scales fold in per head."""
    from triton_dist_tpu.ops.flash_decode import flash_decode_quant, quantize_kv

    b, hq, h_kv, s, d = 2, 8, 4, 64, 128
    q, k, v, _ = _rand_case(jax.random.PRNGKey(42), b, hq, h_kv, s, d)
    kv_lens = jnp.array([s, 19], jnp.int32)
    want = flash_decode(q, k, v, kv_lens, config=FlashDecodeConfig(block_s=16))
    k_q, v_q, ks, vs = quantize_kv(k, v)
    got = flash_decode_quant(
        q, k_q, v_q, ks, vs, kv_lens,
        config=FlashDecodeConfig(block_s=16, fuse_heads=True),
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=3e-2, atol=3e-2
    )


@pytest.mark.parametrize("fuse_heads", [True, False])
def test_paged_flash_decode_head_fusion_paths(fuse_heads):
    """Both paged index paths (one DMA per page vs per (head, page)) hit
    the same answer on shuffled pools with ragged lens."""
    b, h_kv, g, s, d, page = 2, 2, 2, 128, 128, 32
    q, k, v, _ = _rand_case(jax.random.PRNGKey(43), b, h_kv * g, h_kv, s, d)
    kv_lens = jnp.array([s, 41], jnp.int32)
    kp, vp, bt = _paginate(k, v, page, key=jax.random.PRNGKey(44), n_extra_pages=2)
    got = paged_flash_decode(q, kp, vp, kv_lens, bt, fuse_heads=fuse_heads)
    want = _ref_decode(q, k, v, kv_lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("fuse_heads", [True, False])
def test_paged_flash_verify_grids(fuse_heads):
    """Multi-position paged verify (speculative serving attention): both
    grid shapes — fused-heads (one DMA per physical page, the serving
    default) and per-head — match the contiguous XLA verify golden over
    a shuffled page pool with per-row prefix lengths."""
    from triton_dist_tpu.ops.flash_decode import _xla_verify, paged_flash_verify

    b, S, h_kv, g, d, page = 2, 3, 2, 2, 64, 8
    hq = h_kv * g
    q = jax.random.normal(jax.random.PRNGKey(70), (b, S, hq, d), jnp.float32)
    kp = jax.random.normal(jax.random.PRNGKey(71), (8, h_kv, page, d), jnp.float32)
    vp = jax.random.normal(jax.random.PRNGKey(72), (8, h_kv, page, d), jnp.float32)
    bt = jnp.array([[6, 2, 4], [1, 3, 5]], jnp.int32)
    # pos0=7 puts row 0's span entirely inside page 0 while the seq's max
    # len (10) admits chunk 1 — a fully-masked row in an ACTIVE chunk, the
    # verify-specific case the online-softmax NaN guard (m_safe) exists for
    pos0 = jnp.array([7, 13], jnp.int32)
    lens = pos0[:, None] + jnp.arange(1, S + 1)[None, :]
    got = paged_flash_verify(q, kp, vp, lens, bt, fuse_heads=fuse_heads)
    kc = kp[bt].transpose(0, 2, 1, 3, 4).reshape(b, h_kv, 3 * page, d)
    vc = vp[bt].transpose(0, 2, 1, 3, 4).reshape(b, h_kv, 3 * page, d)
    want = _xla_verify(q, kc, vc, lens, return_lse=False)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("fuse_heads", [True, False])
def test_paged_decode_nondivisor_pages_per_step(fuse_heads):
    """Clamped duplicate-tail path (ADVICE r5 #3): P=2 over a 5-page table
    leaves the last step with one real page + one clamped DUPLICATE fetch
    of the table's final entry. Those duplicate span positions sit at
    >= max_pages*page_size >= kv_len, so the length mask must discard
    them — a regression here double-counts the final page's scores."""
    b, h_kv, g, s, d, page = 2, 2, 2, 160, 128, 32  # 5 pages/sequence
    q, k, v, _ = _rand_case(jax.random.PRNGKey(80), b, h_kv * g, h_kv, s, d)
    # one full-length sequence (every tail position live) and one ragged
    kv_lens = jnp.array([s, 77], jnp.int32)
    kp, vp, bt = _paginate(k, v, page, key=jax.random.PRNGKey(81), n_extra_pages=2)
    assert bt.shape[1] % 2 == 1  # non-divisor: the tail step is clamped
    got = paged_flash_decode(
        q, kp, vp, kv_lens, bt, fuse_heads=fuse_heads, pages_per_step=2
    )
    want = _ref_decode(q, k, v, kv_lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("fuse_heads", [True, False])
def test_paged_verify_nondivisor_pages_per_step(fuse_heads):
    """The verify grids' clamped duplicate tail, same P=2-over-5-pages
    shape, asserted against the contiguous golden with per-row lengths
    reaching into the final (partially duplicated) step."""
    from triton_dist_tpu.ops.flash_decode import _xla_verify, paged_flash_verify

    b, S, h_kv, g, s, d, page = 2, 3, 2, 2, 160, 128, 32
    hq = h_kv * g
    q = jax.random.normal(jax.random.PRNGKey(82), (b, S, hq, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(83), (b, h_kv, s, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(84), (b, h_kv, s, d), jnp.float32)
    kp, vp, bt = _paginate(k, v, page, key=jax.random.PRNGKey(85), n_extra_pages=2)
    pos0 = jnp.array([s - S, 100], jnp.int32)  # row spans end inside page 4
    lens = pos0[:, None] + jnp.arange(1, S + 1)[None, :]
    got = paged_flash_verify(
        q, kp, vp, lens, bt, fuse_heads=fuse_heads, pages_per_step=2
    )
    want = _xla_verify(q, k, v, lens, return_lse=False)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


# ---------------------------------------------------------------------------
# Logit soft-cap + non-pow-2 head dims (ISSUE 14 satellite; VERDICT
# missing #1 — the reference's soft_cap / BLOCK_DPE machinery,
# flash_decode.py:103-107,155-190). CPU goldens: every entry is pinned
# against a local tanh-capped reference; the kernel-level math
# (_online_softmax_step) is exercised directly as plain jnp, so the
# padding/capping algebra is covered even where the Pallas build is
# unavailable. Chip measurement stays deferred (ROADMAP item 1).
# ---------------------------------------------------------------------------

def _ref_decode_capped(q, k, v, kv_lens, soft_cap=0.0):
    """Masked-attention golden with the reference's logit soft-cap:
    ``s = cap * tanh(s / cap)`` on the scaled scores, before masking."""
    b, hq, d = q.shape
    _, h_kv, s_len, _ = k.shape
    g = hq // h_kv
    q4 = q.reshape(b, h_kv, g, d).astype(jnp.float32)
    scores = jnp.einsum("bhgd,bhsd->bhgs", q4, k.astype(jnp.float32))
    scores /= jnp.sqrt(jnp.float32(d))
    if soft_cap:
        scores = soft_cap * jnp.tanh(scores / soft_cap)
    mask = jnp.arange(s_len)[None, :] < kv_lens[:, None]
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(b, hq, d)


def test_kernel_head_dim_padding_table():
    """Power-of-2 dims pass through (today's shapes, bit-unchanged);
    non-pow-2 dims round up to the next power of two."""
    from triton_dist_tpu.ops.flash_decode import _kernel_head_dim

    assert _kernel_head_dim(64) == 64
    assert _kernel_head_dim(128) == 128
    assert _kernel_head_dim(256) == 256
    assert _kernel_head_dim(80) == 128
    assert _kernel_head_dim(96) == 128
    assert _kernel_head_dim(192) == 256
    with pytest.raises(ValueError):
        _kernel_head_dim(0)


@pytest.mark.parametrize("soft_cap", [0.0, 20.0])
def test_online_softmax_step_padding_exact(soft_cap):
    """The kernel step function (plain jnp — runnable on any box) must be
    EXACT under head-dim zero-padding: padded q·k terms add 0 to every
    score and padded v columns emit 0 output columns. This is the
    algebraic fact the host-level pad-and-slice relies on."""
    from triton_dist_tpu.ops.flash_decode import (
        _finalize_softmax, _kernel_head_dim, _online_softmax_step,
        _pad_head_dim,
    )

    g, sc, d = 4, 64, 96
    key = jax.random.PRNGKey(7)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (g, d), jnp.float32)
    k = jax.random.normal(kk, (sc, d), jnp.float32)
    v = jax.random.normal(kv_, (sc, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    def run(qx, kx, vx, dd):
        m0 = jnp.full((g, 1), -jnp.inf)
        l0 = jnp.zeros((g, 1))
        a0 = jnp.zeros((g, dd))
        m, l, a = _online_softmax_step(
            qx, kx, vx, None, None, 0, jnp.int32(50), scale, m0, l0, a0,
            soft_cap,
        )
        return _finalize_softmax(m, l, a)

    out_ref, lse_ref = run(q, k, v, d)
    dp = _kernel_head_dim(d)
    assert dp == 128
    out_pad, lse_pad = run(
        _pad_head_dim(q, dp), _pad_head_dim(k, dp), _pad_head_dim(v, dp), dp
    )
    np.testing.assert_array_equal(np.asarray(out_pad[:, :d]), np.asarray(out_ref))
    np.testing.assert_array_equal(np.asarray(out_pad[:, d:]), 0.0)
    np.testing.assert_array_equal(np.asarray(lse_pad), np.asarray(lse_ref))


@pytest.mark.parametrize("block_s", [0, 64])
def test_flash_decode_soft_cap(block_s):
    """soft_cap on the decode entry (XLA-native and kernel/golden paths)
    vs the tanh-capped reference; cap=0 stays bit-identical to the
    pre-knob result."""
    b, h_kv, g, s, d = 2, 2, 2, 256, 128
    q, k, v, kv_lens = _rand_case(jax.random.PRNGKey(11), b, h_kv * g, h_kv, s, d)
    got = flash_decode(
        q, k, v, kv_lens, config=FlashDecodeConfig(block_s=block_s, soft_cap=20.0)
    )
    want = _ref_decode_capped(q, k, v, kv_lens, soft_cap=20.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)
    # the capped result must actually differ from the uncapped one
    uncapped = flash_decode(q, k, v, kv_lens, config=FlashDecodeConfig(block_s=block_s))
    assert not np.allclose(np.asarray(got), np.asarray(uncapped))
    # soft_cap=0.0 is the identity posture — bit-identical to the default
    zero = flash_decode(
        q, k, v, kv_lens, config=FlashDecodeConfig(block_s=block_s, soft_cap=0.0)
    )
    np.testing.assert_array_equal(np.asarray(zero), np.asarray(uncapped))


def test_flash_verify_soft_cap_and_nonpow2():
    """The verify family: per-row prefix lengths × soft-cap × a d=96
    head dim, against the capped per-row reference."""
    from triton_dist_tpu.ops.flash_decode import flash_verify

    b, S, h_kv, g, s, d = 2, 3, 2, 2, 128, 96
    hq = h_kv * g
    q = jax.random.normal(jax.random.PRNGKey(21), (b, S, hq, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(22), (b, h_kv, s, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(23), (b, h_kv, s, d), jnp.float32)
    pos0 = jnp.array([s - S, 40], jnp.int32)
    lens = pos0[:, None] + jnp.arange(1, S + 1)[None, :]
    got = flash_verify(
        q, k, v, lens, config=FlashDecodeConfig(block_s=32, soft_cap=15.0)
    )
    # per-row golden: one capped decode per draft position
    for i in range(S):
        want = _ref_decode_capped(q[:, i], k, v, lens[:, i], soft_cap=15.0)
        np.testing.assert_allclose(
            np.asarray(got[:, i]), np.asarray(want), rtol=2e-4, atol=2e-4
        )


def test_flash_decode_nonpow2_head_dim():
    """d=96 (the reference's BLOCK_DPE case) through the decode entry —
    XLA path natively, kernel path via pad-and-slice — and through the
    SP merge (lse packing is d-agnostic)."""
    b, h_kv, g, s, d = 2, 2, 2, 256, 96
    q, k, v, kv_lens = _rand_case(jax.random.PRNGKey(31), b, h_kv * g, h_kv, s, d)
    want = _ref_decode_capped(q, k, v, kv_lens)
    for block_s in (0, 64):
        got = flash_decode(
            q, k, v, kv_lens, config=FlashDecodeConfig(block_s=block_s)
        )
        assert got.shape == (b, h_kv * g, d)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )
    # SP merge over shards keeps the exact-combine invariant at d=96
    n, s_loc = 4, s // 4
    outs, lses = [], []
    for i in range(n):
        sl = slice(i * s_loc, (i + 1) * s_loc)
        o, l = flash_decode(
            q, k[:, :, sl], v[:, :, sl],
            jnp.clip(kv_lens - i * s_loc, 0, s_loc),
            config=FlashDecodeConfig(block_s=32), return_lse=True,
        )
        outs.append(o)
        lses.append(l)
    got = combine_partials(jnp.stack(outs), jnp.stack(lses))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_paged_decode_soft_cap_nonpow2():
    """The paged entry takes soft_cap as a kwarg (its knobs are kwargs)
    and pads page pools for non-pow-2 head dims; pinned against the
    contiguous capped reference at d=96."""
    b, h_kv, g, s, d, page = 2, 2, 2, 256, 96, 64
    q, k, v, kv_lens = _rand_case(jax.random.PRNGKey(41), b, h_kv * g, h_kv, s, d)
    kp, vp, bt = _paginate(k, v, page, key=jax.random.PRNGKey(42), n_extra_pages=2)
    got = paged_flash_decode(q, kp, vp, kv_lens, bt, soft_cap=25.0)
    want = _ref_decode_capped(q, k, v, kv_lens, soft_cap=25.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


# -- the walk's discipline: a row's live pages, never its table row ----------

_WALK_PAGE, _WALK_PAGES = 16, 8          # a row holds 128 positions
_WALK_POISON = 1e30                      # finite: 0 x it is 0, any p x it is not
_WALK_LENS = {
    "zero": [0, 0, 0],
    "one": [1, 1, 1],
    "page-1": [_WALK_PAGE - 1] * 3,
    "page": [_WALK_PAGE] * 3,
    "page+1": [_WALK_PAGE + 1] * 3,
    "three_pages": [3 * _WALK_PAGE] * 3,
    "whole_row": [_WALK_PAGE * _WALK_PAGES] * 3,
    "mixed": [0, _WALK_PAGE * _WALK_PAGES, 17, 0, 47, 1, 100],
}


def _poisoned_pools(rng, lens, h_kv, d, window, page=_WALK_PAGE,
                    pages=_WALK_PAGES):
    """Contiguous k, v and their pools in which ONLY the positions a row
    attends hold them: every other position of a live page, every dead
    page and the page every dead table column names hold the poison. With
    a window the table is a ring (column = logical page % its width)."""
    width = pages if window is None else min(-(-window // page) + 1, pages)
    b = len(lens)
    k = rng.standard_normal((b, h_kv, page * pages, d)).astype(np.float32)
    v = rng.standard_normal(k.shape).astype(np.float32)
    dead = b * width                                  # the poisoned page
    kp = np.full((dead + 1, h_kv, page, d), _WALK_POISON, np.float32)
    vp = kp.copy()
    table = np.full((b, width), dead, np.int32)
    ids = rng.permutation(dead).reshape(b, width)
    for i, n in enumerate(lens):
        lo = 0 if window is None else max(n - window, 0)
        for pos in range(lo, n):
            col = (pos // page) % width
            table[i, col] = ids[i, col]
            kp[ids[i, col], :, pos % page] = k[i, :, pos]
            vp[ids[i, col], :, pos % page] = v[i, :, pos]
    return k, v, kp, vp, table


def _windowed_golden(q, k, v, lens, window):
    b, hq, d = q.shape
    h_kv = k.shape[1]
    s = np.einsum("bhgd,bhsd->bhgs", q.reshape(b, h_kv, hq // h_kv, d), k)
    s /= np.sqrt(d)
    pos = np.arange(k.shape[2])[None, :]
    lo = 0 if window is None else np.maximum(lens - window, 0)
    seen = (pos < lens[:, None]) & (pos >= np.reshape(lo, (-1, 1)))
    s = np.where(seen[:, None, None, :], s, -np.inf)
    m = np.max(s, axis=-1, keepdims=True, initial=-1e30)
    p = np.exp(s - m)
    l = p.sum(-1, keepdims=True)
    out = np.einsum("bhgs,bhsd->bhgd", p / np.maximum(l, 1e-30), v)
    return out.reshape(b, hq, d), (m + np.log(np.maximum(l, 1e-30))).reshape(b, hq)


@pytest.mark.parametrize("window", [None, _WALK_PAGE * 3 // 2])
@pytest.mark.parametrize("fuse_heads", [True, False])
@pytest.mark.parametrize("lens", list(_WALK_LENS))
def test_paged_decode_walks_live_pages_only(lens, fuse_heads, window):
    """Every position no length exposes is poisoned (a large finite value:
    the pool's dead positions, whole dead pages, and the page that every
    table column past a row's last live page names), and the stale VMEM
    of the interpreter is NaN: the result is the contiguous golden's, so
    no dead page slot reached ``p x v`` and no live one was skipped. Three
    pages a chunk: rows of several chunks, a short last one, empty rows
    between live ones."""
    rng = np.random.default_rng(len(lens) + 7 * fuse_heads)
    lens = np.asarray(_WALK_LENS[lens], np.int32)
    h_kv, g, d = 2, 2, 128
    k, v, kp, vp, table = _poisoned_pools(rng, lens, h_kv, d, window)
    q = rng.standard_normal((len(lens), h_kv * g, d)).astype(np.float32)
    want, want_lse = _windowed_golden(q, k, v, lens, window)
    for pages_per_step in (None, 3):
        got, lse = paged_flash_decode(
            *(jnp.asarray(x) for x in (q, kp, vp, lens, table)),
            fuse_heads=fuse_heads, window=window,
            pages_per_step=pages_per_step, return_lse=True, interpret=True)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
        live = lens > 0
        np.testing.assert_allclose(
            np.asarray(lse)[live], want_lse[live], rtol=2e-5, atol=2e-5)
        assert np.isneginf(np.asarray(lse)[~live]).all()


@pytest.mark.parametrize("fuse_heads", [True, False])
@pytest.mark.parametrize("form", ["plain", "window", "int8"])
def test_paged_decode_keeps_another_rows_nan_out(form, fuse_heads):
    """A request whose KV went non-finite stays that request's alone
    (``ContinuousBatcher._poison_slot``'s containment): rows 0 and 1 walk
    whole rows of NaN pages (V, and for an int8 pool its V scales), one
    into each of the two buffers, and the rows after them are short, so
    the span their last chunk is multiplied over is wider than their live
    pages (3 live of 4, 5 of 8 or of 6): the dead page slots still hold
    the NaN rows' pages. The later rows read bit for bit what they read
    beside finite neighbours. It fails on any form that lets a page slot
    it did not fetch into ``p x v``: ``0 x NaN`` is NaN."""
    from triton_dist_tpu.ops.flash_decode import quantize_kv_pages

    rng = np.random.default_rng(3 + fuse_heads)
    whole = _WALK_PAGE * _WALK_PAGES
    # (two short of the row: a window of 5 pages then lies over 6)
    lens = np.asarray([whole - 2, whole - 2, 3 * _WALK_PAGE - 5, 5 * _WALK_PAGE,
                       _WALK_PAGE + 1, whole - 2 * _WALK_PAGE, 1], np.int32)
    window = 5 * _WALK_PAGE if form == "window" else None
    h_kv, g, d = 2, 2, 128
    k, v, kp, vp, table = _poisoned_pools(rng, lens, h_kv, d, window)
    q = jnp.asarray(
        rng.standard_normal((len(lens), h_kv * g, d)).astype(np.float32))
    bad = table[:2].ravel()                 # every page of rows 0 and 1

    def decode(v_pool, **scales):
        return np.asarray(paged_flash_decode(
            q, pools[0], v_pool, jnp.asarray(lens), jnp.asarray(table),
            fuse_heads=fuse_heads, window=window, interpret=True, **scales))

    if form == "int8":
        # (a finite poison: an int8 payload holds no NaN, its scales do)
        kp, vp = np.clip(kp, -4, 4), np.clip(vp, -4, 4)
        *pools, ks, vs = quantize_kv_pages(jnp.asarray(kp), jnp.asarray(vp))
        clean = decode(pools[1], k_scales=ks, v_scales=vs)
        got = decode(pools[1], k_scales=ks.at[bad].set(jnp.nan),
                     v_scales=vs.at[bad].set(jnp.nan))
    else:
        pools = [jnp.asarray(kp), jnp.asarray(vp)]
        clean = decode(pools[1])
        pools[0] = pools[0].at[bad].set(jnp.nan)
        got = decode(pools[1].at[bad].set(jnp.nan))
        want, _ = _windowed_golden(np.asarray(q), k, v, lens, window)
        np.testing.assert_allclose(got[2:], want[2:], rtol=2e-5, atol=2e-5)
    assert not np.array_equal(got[:2], clean[:2])   # the poison is live
    assert np.isfinite(got[2:]).all()
    np.testing.assert_array_equal(got[2:], clean[2:])
