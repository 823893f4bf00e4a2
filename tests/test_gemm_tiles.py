"""Where a fused GEMM's tile comes from (``ops.common.gemm_tile``, PR 43):
the rule over the shapes the benchmark's cells run, the shapes a one-slot
admission will bring, and test-sized ones; explicit configs untouched; and
the kernels themselves with ``config=None`` at a shape where the rule
picks a tile of its own and the scatter kernel's own chunk another,
against the XLA goldens."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu.ops import common
from triton_dist_tpu.ops.allgather_gemm import (
    AGGemmConfig, _ag_gemm_xla, ag_gemm, ag_gemm_op,
)
from triton_dist_tpu.ops.common import GEMM_VMEM_BUDGET, gemm_tile
from triton_dist_tpu.ops.gemm_reduce_scatter import (
    GemmRSConfig, _gemm_rs_xla, gemm_rs, gemm_rs_op,
)

BF16 = jnp.bfloat16

# (m, n, k, n_adds) of one ``gemm_add_pipeline``
SHAPES = [
    # mistral-large-2407-tp4.summarize: a gemm_rs chunk (wo K=3072, w_down
    # K=7168; the own chunk fuses the three landed ones), an ag_gemm shard
    pytest.param(1024, 12288, 3072, 0, id="tp4-wo-remote"),
    pytest.param(1024, 12288, 3072, 3, id="tp4-wo-own"),
    pytest.param(1024, 12288, 7168, 0, id="tp4-down-remote"),
    pytest.param(1024, 12288, 7168, 3, id="tp4-down-own"),
    pytest.param(1024, 14336, 12288, 0, id="tp4-gate_up"),
    pytest.param(1024, 3584, 12288, 0, id="tp4-qkv"),
    pytest.param(1024, 8192, 12288, 0, id="tp4-head"),
    # mistral-7b-v0.3 on one chip: gemm_only under the fused ops' names
    pytest.param(4096, 4096, 14336, 0, id="7b-down-4096"),
    pytest.param(2048, 4096, 4096, 0, id="7b-wo-2048"),
    pytest.param(8192, 4096, 14336, 0, id="7b-down-8192"),
    pytest.param(4096, 28672, 4096, 0, id="7b-gate_up"),
    pytest.param(4096, 6144, 4096, 0, id="7b-qkv"),
    # ROADMAP A2-ii: the admitted slot's rows alone
    pytest.param(128, 12288, 7168, 3, id="a2ii-128-rows"),
    pytest.param(64, 12288, 7168, 3, id="a2ii-64-rows"),
    pytest.param(128, 3584, 12288, 0, id="a2ii-qkv"),
    # test-sized
    pytest.param(8, 16, 16, 0, id="toy"),
    pytest.param(8, 16, 16, 3, id="toy-own"),
    pytest.param(24, 200, 48, 1, id="odd"),
]


def _footprint(bm, bn, bk, n_adds, size=2):
    """Double-buffered A, B, out and add tiles + the f32 accumulator."""
    return (2 * (bm * bk + bk * bn) * size
            + 2 * (1 + n_adds) * bm * bn * size + 4 * bm * bn)


@pytest.mark.parametrize("m, n, k, n_adds", SHAPES)
def test_the_rule_gives_a_tile_that_fits_and_divides(m, n, k, n_adds):
    tile = gemm_tile(None, m, n, k, n_adds=n_adds, in_dtype=BF16, out_dtype=BF16)
    bm, bn, bk, vmem = tile
    for dim, blk in ((m, bm), (n, bn), (k, bk)):
        assert dim % blk == 0 and blk <= dim, (dim, blk)
        # a multiple of the MXU's 128 wherever the dimension has one
        assert blk % 128 == 0 or (dim % 128 and blk == dim), (dim, blk)
    # the limit the kernel asks for covers the tile and keeps the budget
    assert _footprint(bm, bn, bk, n_adds) < vmem <= GEMM_VMEM_BUDGET
    assert tile.tag == f"_{bm}m{bn}n{bk}k"
    # config=None upstream arrives as a config with no blocks set
    for cfg in (GemmRSConfig(), AGGemmConfig(chunks_per_shard=2)):
        assert gemm_tile(cfg, m, n, k, n_adds=n_adds, in_dtype=BF16,
                         out_dtype=BF16) == tile


def test_the_rule_follows_the_shape():
    tile = functools.partial(gemm_tile, None, in_dtype=BF16, out_dtype=BF16)
    # 3584 = 7 x 512: halving from 2048 fell to 512, a 268-MFLOP step
    assert tile(1024, 3584, 12288).bn in (3584, 1792, 896)
    # the own chunk's three adds cost it VMEM: its step is the smaller one
    remote, own = tile(1024, 12288, 7168), tile(1024, 12288, 7168, n_adds=3)
    assert own[:3] != remote[:3]
    assert np.prod(own[:3]) < np.prod(remote[:3])
    # each past the 1,074-MFLOP step that read 92-94% of the peak
    for t in (remote, own, tile(1024, 3584, 12288), tile(4096, 4096, 4096)):
        assert 2 * np.prod(t[:3], dtype=np.int64) >= 2 * 512 * 2048 * 512
    # fewer rows, wider blocks: the step does not collapse with the rows
    assert tile(128, 12288, 7168).bm == 128
    assert tile(128, 12288, 7168).bn * tile(128, 12288, 7168).bk >= 2048 * 1024
    # float32 operands take twice the room
    assert np.prod(gemm_tile(None, 1024, 12288, 7168, in_dtype=jnp.float32,
                             out_dtype=jnp.float32)[:3]) < np.prod(remote[:3])
    # nothing fits: the smallest footprint there is, and its true size
    odd = tile(8, 4_000_037, 8)    # a prime: one whole-dimension block
    assert odd[:3] == (8, 4_000_037, 8)
    assert odd.vmem_limit_bytes > max(
        GEMM_VMEM_BUDGET, _footprint(8, 4_000_037, 8, 0))


@pytest.mark.parametrize("rows, gate_up, down", [
    (128, (128, 4096, 1024), (128, 4096, 1024)),
    (256, (256, 4096, 1024), (256, 4096, 1024)),
    (512, (512, 2048, 2048), (512, 2048, 2048)),
    (1024, (1024, 2048, 1024), (1024, 2048, 1024)),
])
def test_a_walked_members_gemms_keep_their_tiles(rows, gate_up, down):
    """``mistral-7b-v0.3`` on one chip since PR 47: an admission runs ONE
    member's ``[bucket, .]`` rows a trip (``ContinuousBatcher._prefill_prog``),
    so these are the shapes its two large GEMMs really have. Pinned: a change
    of the rule or of its budget shows here as this path's."""
    tile = functools.partial(gemm_tile, None, in_dtype=BF16, out_dtype=BF16)
    assert tile(rows, 28672, 4096)[:3] == gate_up
    assert tile(rows, 4096, 14336)[:3] == down
    # the step does not collapse with the rows (the whole-batch pass's 4096
    # rows ran (1024, 2048, 1024))
    for t in (gate_up, down):
        assert 2 * np.prod(t, dtype=np.int64) >= 2 * 512 * 2048 * 512


@pytest.mark.parametrize("cls", [GemmRSConfig, AGGemmConfig])
def test_an_explicit_config_comes_back_untouched(cls):
    tile = functools.partial(gemm_tile, in_dtype=BF16, out_dtype=BF16)
    assert tile(cls(8, 16, 16), 8, 16, 16)[:3] == (8, 16, 16)
    assert tile(cls(256, 1024, 512), 1024, 12288, 3072)[:3] == (256, 1024, 512)
    # shrunk to a divisor by halving, as pick_block always did
    assert tile(cls(512, 2048, 512), 1024, 3584, 12288)[:3] == (512, 512, 512)
    assert tile(cls(1024, 2048, 1024), 256, 4096, 4096, n_adds=3)[:3] == (
        256, 2048, 1024)
    # never under the compiler's own default; what a large tile needs, over it
    assert tile(cls(8, 16, 16), 8, 16, 16).vmem_limit_bytes == 16 * 2**20
    assert tile(cls(1024, 2048, 1024), 1024, 12288, 7168, n_adds=3
                ).vmem_limit_bytes > 16 * 2**20
    # the dataclasses hold no second opinion
    assert (cls().block_m, cls().block_n, cls().block_k) == (None, None, None)
    assert cls(chunks_per_shard=4).block_m is None


@pytest.mark.parametrize("op, cls", [(gemm_rs_op, GemmRSConfig),
                                     (ag_gemm_op, AGGemmConfig)])
def test_block_m_zero_is_xlas_dot_on_one_device_and_raises_on_four(
        mesh4, op, cls):
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("tp",))
    a = jax.random.normal(jax.random.PRNGKey(6), (16, 128), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(7), (128, 128), jnp.float32)
    got = op(a, b, mesh1, config=cls(0, 0, 0))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(jnp.dot(a, b)), rtol=1e-4, atol=1e-4)
    with pytest.raises(Exception, match="world-1 only"):
        op(a, b, mesh4, config=cls(0, 0, 0))


# The kernels under the rule. A budget of 4.25 MiB stands in for the real
# one so that a [256, 256] x [256, 256] float32 chunk just fits it: the
# remote chunks' pipeline holds the whole chunk, one fused add (a ring
# step) or three (the scatter kernel's own chunk) halve a block.
SMALL_BUDGET = 17 * 2**18
M_LOC, K_LOC, N_DIM = 256, 256, 256


@pytest.fixture
def small_budget(monkeypatch):
    monkeypatch.setattr(common, "GEMM_VMEM_BUDGET", SMALL_BUDGET)
    tile = functools.partial(
        gemm_tile, None, M_LOC, N_DIM, K_LOC, in_dtype=jnp.float32,
        out_dtype=jnp.float32)
    assert tile()[:3] == (256, 256, 256)
    assert tile(n_adds=3)[:3] != tile()[:3]
    assert tile(n_adds=1)[:3] != tile()[:3]


def _sharded(fn, mesh, in_specs, out_specs):
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False))


@pytest.mark.parametrize("method, config", [
    ("scatter", None),
    ("ring", None),
    ("ring", GemmRSConfig(chunks_per_shard=2)),
], ids=["scatter", "ring", "ring-chunked"])
def test_gemm_rs_under_the_rule(mesh4, small_budget, method, config):
    a = jax.random.normal(jax.random.PRNGKey(0), (4 * M_LOC, 4 * K_LOC))
    b = jax.random.normal(jax.random.PRNGKey(1), (4 * K_LOC, N_DIM))
    specs = (P(None, "tp"), P("tp", None)), P("tp", None)
    got = _sharded(
        functools.partial(gemm_rs, axis="tp", method=method, config=config),
        mesh4, *specs)(a, b)
    want = _sharded(functools.partial(_gemm_rs_xla, axis="tp"), mesh4, *specs)(a, b)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("config", [None, AGGemmConfig(chunks_per_shard=2)],
                         ids=["whole", "chunked"])
def test_ag_gemm_under_the_rule(mesh4, small_budget, config):
    # per shard [256, 512] x [512, 384]: 384 = 3 x 128 is held whole (by
    # halving it fell to 128), and K is cut to fit
    a = jax.random.normal(jax.random.PRNGKey(2), (4 * M_LOC, 512))
    b = jax.random.normal(jax.random.PRNGKey(3), (512, 4 * 384))
    tile = gemm_tile(None, M_LOC, 384, 512, in_dtype=a.dtype, out_dtype=a.dtype)
    assert tile.bn == 384 and tile.bk < 512, tile
    specs = (P("tp", None), P(None, "tp")), P(None, "tp")
    got = _sharded(functools.partial(ag_gemm, axis="tp", config=config),
                   mesh4, *specs)(a, b)
    want = _sharded(functools.partial(_ag_gemm_xla, axis="tp"), mesh4, *specs)(a, b)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-3)


def test_the_tile_is_in_the_kernels_name(mesh4):
    """docs/observability.md: a ``breakdown`` line tells which tile
    produced its time; the family name every registry keys on stays."""
    a = jax.ShapeDtypeStruct((4 * 256, 4 * 256), jnp.float32)
    b = jax.ShapeDtypeStruct((4 * 256, 256), jnp.float32)
    specs = (P(None, "tp"), P("tp", None)), P("tp", None)
    text = _sharded(functools.partial(gemm_rs, axis="tp", method="scatter"),
                    mesh4, *specs).lower(a, b).as_text(debug_info=True)
    assert "gemm_rs_scatter_256m256n256k" in text
    text = _sharded(
        functools.partial(gemm_rs, axis="tp", method="scatter",
                          config=GemmRSConfig(8, 128, 64)),
        mesh4, *specs).lower(a, b).as_text(debug_info=True)
    assert "gemm_rs_scatter_8m128n64k" in text
    assert "gemm_rs_scatter_8m128n64k_" not in text    # one tile, named once
