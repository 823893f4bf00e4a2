"""The main path's kernels, compiled at Llama-3.1-8B widths for a DESCRIBED
``v5e:2x2`` — the chip's own compiler, no chip attached.

Interpret mode cannot see what Mosaic refuses (a slice not aligned to the
tiling, too much VMEM, a kernel that will not partition); these compiles
can, in a second or two each, so they guard every later PR at no chip
time. A compile that passes is not a run: ``chip_smoke.py`` is the run.

The topology is described inside a module-scoped fixture, never at import
(one process at a time may load libtpu; under xdist only the worker that
is handed this file may do so), and everything built from it is built in
fixtures or tests. All cases live in this one file for the same reason:
tried in PR 44 with two processes that describe the topology at once, a
second one compiles beside the first only under
``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` (the driver's command sets it,
scripts/run_tier1.sh does not); without it the second fails on
``/tmp/libtpu_lockfile``. So the file is not split, and is the first of
tests/conftest.py ``_LONG_POLES`` while it is the heaviest.
"""

import contextlib
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from triton_dist_tpu import config as tdt_config

# Llama-3.1-8B (models/presets.py): the serving shapes of chip_smoke.py
HIDDEN, FFN, VOCAB = 4096, 14336, 128256
N_Q, N_KV, HEAD = 32, 8, 128
SLOTS, S_MAX, PAGE = 8, 2048, 128
M_TOKENS = 4096  # one prefill admission: 8 slots x bucket 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    loud = tdt_config.get_config().fallback_to_xla
    tdt_config.update(fallback_to_xla=False)
    yield t
    tdt_config.update(fallback_to_xla=loud)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices[:4]), ("tp",))


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@contextlib.contextmanager
def _chip_posture():
    """Kernels compiled, not interpreted, while a whole pass is traced."""
    posture = tdt_config.get_config().interpret
    tdt_config.update(interpret=False)
    try:
        yield
    finally:
        tdt_config.update(interpret=posture)


def test_flash_decode_compiles(one_chip):
    from triton_dist_tpu.ops.flash_decode import flash_decode

    q = _struct((SLOTS, N_Q, HEAD), jnp.bfloat16, one_chip)
    kv = _struct((SLOTS, N_KV, S_MAX, HEAD), jnp.bfloat16, one_chip)
    lens = _struct((SLOTS,), jnp.int32, one_chip)
    text = _compiled_text(
        functools.partial(flash_decode, interpret=False), q, kv, kv, lens
    )
    assert "tpu_custom_call" in text


def _paged_args(one_chip):
    pages_per_seq = S_MAX // PAGE
    pool = _struct(
        (SLOTS * pages_per_seq, N_KV, PAGE, HEAD), jnp.bfloat16, one_chip
    )
    table = _struct((SLOTS, pages_per_seq), jnp.int32, one_chip)
    return pool, table


def test_paged_flash_decode_compiles(one_chip):
    from triton_dist_tpu.ops.flash_decode import paged_flash_decode

    pool, table = _paged_args(one_chip)
    q = _struct((SLOTS, N_Q, HEAD), jnp.bfloat16, one_chip)
    lens = _struct((SLOTS,), jnp.int32, one_chip)
    text = _compiled_text(
        functools.partial(paged_flash_decode, interpret=False),
        q, pool, pool, lens, table,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.float8_e4m3fn])
def test_quantized_paged_flash_decode_compiles(one_chip, dtype):
    """The int8 / fp8 pools' form (no cell runs it): the fused grid
    multiplies every kv head of a buffer at once, so the bf16 copy it
    makes of a buffer counts against the scoped VMEM the page slots are
    sized by (PR 38: 25 MB asked of 16 before it did)."""
    from triton_dist_tpu.ops.flash_decode import paged_flash_decode

    pool, table = _paged_args(one_chip)
    pool = _struct(pool.shape, dtype, one_chip)
    scales = _struct((pool.shape[0], N_KV, 1, PAGE), jnp.float32, one_chip)
    q = _struct((SLOTS, N_Q, HEAD), jnp.bfloat16, one_chip)
    lens = _struct((SLOTS,), jnp.int32, one_chip)
    text = _compiled_text(
        lambda q, k, v, n, t, ks, vs: paged_flash_decode(
            q, k, v, n, t, k_scales=ks, v_scales=vs, interpret=False),
        q, pool, pool, lens, table, scales, scales,
    )
    assert "tpu_custom_call" in text and "paged_flash_decode_q_fh" in text


def test_many_kv_head_paged_flash_decode_compiles(one_chip):
    """64 kv heads: one fused page slot is a 128-position sliver of the
    scoped VMEM, so the auto choice is the PER-HEAD grid, which no cell's
    shape takes any more; with a window, a soft cap and the lse, the
    options no other compile here sets."""
    from triton_dist_tpu.ops.flash_decode import paged_flash_decode

    heads, pages = 64, S_MAX // PAGE
    pool = _struct((4 * pages, heads, PAGE, HEAD), jnp.bfloat16, one_chip)
    text = _compiled_text(
        functools.partial(paged_flash_decode, window=300, soft_cap=30.0,
                          return_lse=True, interpret=False),
        _struct((4, heads, HEAD), jnp.bfloat16, one_chip), pool, pool,
        _struct((4,), jnp.int32, one_chip),
        _struct((4, pages), jnp.int32, one_chip),
    )
    assert "paged_flash_decode_w300" in text
    assert "paged_flash_decode_w300_fh" not in text


@pytest.mark.parametrize("rows", [16, 128])
def test_paged_flash_verify_compiles(one_chip, rows):
    """The ranged-prefill kernel: ``rows`` query positions per slot (a
    speculative chunk, a chunked-prefill chunk) against the paged pool."""
    from triton_dist_tpu.ops.flash_decode import paged_flash_verify

    pool, table = _paged_args(one_chip)
    q = _struct((SLOTS, rows, N_Q, HEAD), jnp.bfloat16, one_chip)
    lens = _struct((SLOTS, rows), jnp.int32, one_chip)
    text = _compiled_text(
        functools.partial(paged_flash_verify, interpret=False),
        q, pool, pool, lens, table,
    )
    assert "tpu_custom_call" in text


def test_mla_paged_decode_compiles(one_chip):
    """The absorbed latent-attention decode at the published widths of the
    benchmark's latent-attention configuration: 16 slots, 32 heads, rows of
    640 (512 latent + 64 rotated key + 64 pad), values of 512, 5 layers."""
    from triton_dist_tpu.ops.mla_decode import mla_paged_decode

    slots, heads, row, d_v, layers = 16, 32, 640, 512, 5
    pages_per_seq = S_MAX // PAGE
    pool = _struct((layers, slots * pages_per_seq, PAGE, row), jnp.bfloat16,
                   one_chip)
    table = _struct((slots, pages_per_seq), jnp.int32, one_chip)
    q = _struct((slots, heads, row), jnp.bfloat16, one_chip)
    lens = _struct((slots,), jnp.int32, one_chip)
    text = _compiled_text(
        lambda q, pool, lens, table: mla_paged_decode(
            q, pool, 3, lens, table, d_v=d_v, scale=192 ** -0.5,
            interpret=False),
        q, pool, lens, table,
    )
    assert "tpu_custom_call" in text and "mla_paged_decode" in text


@pytest.mark.parametrize("rows,block_m", [(16, 16), (4096, 128)])
def test_gated_expert_group_gemms_compile(one_chip, rows, block_m):
    """The routed experts' two grouped GEMMs at the same configuration's
    widths (256 experts of 2048 x 768, top-8): a decode step's 16 rows in
    16-row blocks over the tight alignment, a prefill's 4096 rows in
    128-row blocks; one whole expert matrix a tile."""
    from triton_dist_tpu.ops.group_gemm import GroupGemmConfig, group_gemm
    from triton_dist_tpu.utils import round_up

    hidden, fe, n_exp, topk = 2048, 768, 256, 8
    t = rows * topk
    t_pad = round_up(t + min(n_exp, t) * (block_m - 1), block_m)
    ids = _struct((t_pad // block_m,), jnp.int32, one_chip)

    def gemms(a, w_up, w_down, ids, valid):
        up = GroupGemmConfig(block_m=block_m, block_n=fe, block_k=hidden,
                             ragged=True)
        down = GroupGemmConfig(block_m=block_m, block_n=hidden, block_k=fe,
                               ragged=True)
        gu = group_gemm(a, w_up, ids, valid_rows=valid, config=up,
                        interpret=False)
        return group_gemm(gu[:, :fe] * gu[:, fe:], w_down, ids,
                          valid_rows=valid, config=down, interpret=False)

    text = _compiled_text(
        gemms,
        _struct((t_pad, hidden), jnp.bfloat16, one_chip),
        _struct((n_exp, hidden, 2 * fe), jnp.bfloat16, one_chip),
        _struct((n_exp, fe, hidden), jnp.bfloat16, one_chip), ids, ids,
    )
    assert text.count("tpu_custom_call") >= 2 and "group_gemm" in text


def test_window_paged_flash_decode_compiles(one_chip):
    """The window form of the paged decode at the published widths of the
    benchmark's window-attention configuration: 32 slots, 64 q / 8 kv
    heads of 128, window 128 over a ring of 2 pages a slot, four window
    layers' pools as one run of pages."""
    from triton_dist_tpu.ops.flash_decode import paged_flash_decode

    slots, heads, ring, layers = 32, 64, 2, 4
    pool = _struct((layers * slots * ring, N_KV, PAGE, HEAD), jnp.bfloat16,
                   one_chip)
    table = _struct((slots, ring), jnp.int32, one_chip)
    q = _struct((slots, heads, HEAD), jnp.bfloat16, one_chip)
    lens = _struct((slots,), jnp.int32, one_chip)
    text = _compiled_text(
        functools.partial(paged_flash_decode, window=128, interpret=False),
        q, pool, pool, lens, table,
    )
    assert "tpu_custom_call" in text and "paged_flash_decode_w128" in text


def test_state_space_kernels_compile(one_chip):
    """The state-space / attention configuration's kernels at its
    published widths (5120 channels, 16 states, 64 slots, 26 layers'
    state in one pool): the scan over a bucket of 512, the one-token
    update with the pool aliased in and out (no copy of 1.09 GB), the
    one-token convolution with its ring aliased in and out (no copy of 136
    MB; taps and bias as stored, bf16), and the paged decode at a group of
    20 query heads on ONE kv head."""
    from triton_dist_tpu.ops.conv_ring import conv_ring_step
    from triton_dist_tpu.ops.flash_decode import paged_flash_decode
    from triton_dist_tpu.ops.selective_scan import (
        selective_scan, selective_state_update,
    )

    d, n, slots, layers, bucket, taps = 5120, 16, 64, 26, 512, 4
    f32 = lambda *shape: _struct(shape, jnp.float32, one_chip)
    i32 = lambda *shape: _struct(shape, jnp.int32, one_chip)
    bf16 = lambda *shape: _struct(shape, jnp.bfloat16, one_chip)
    text = _compiled_text(
        functools.partial(selective_scan, interpret=False),
        f32(bucket, d), f32(bucket, d), f32(bucket, n), f32(bucket, n),
        f32(n, d), f32(d), f32(n, d))
    assert "tpu_custom_call" in text and "selective_scan" in text

    def in_place(step, pool, *args):
        """``step(pool, layer 3, *args)`` compiled with the pool donated:
        its text, having checked that the pool is aliased, not copied."""
        compiled = jax.jit(
            lambda pool, *a: step(pool, 3, *a, interpret=False),
            donate_argnums=(0,)).lower(pool, *args).compile()
        mem = compiled.memory_analysis()
        pool_bytes = int(np.prod(pool.shape)) * 4
        assert mem.alias_size_in_bytes >= pool_bytes > mem.temp_size_in_bytes
        return compiled.as_text()

    text = in_place(
        selective_state_update, f32(layers, 2, slots, n, d), i32(slots),
        f32(slots, d), f32(slots, d), bf16(d), f32(slots, n), f32(slots, n),
        f32(n, d), bf16(d))
    assert "selective_state_update" in text
    text = in_place(
        conv_ring_step, f32(layers, taps, slots, d), f32(slots, d),
        i32(slots), bf16(taps, d), bf16(d))
    assert "conv_ring_step" in text
    # the stored leaves reach the kernel as they are: no convert, no copy
    assert not re.search(r"= (bf16|f32)\[(4,)?5120\]\S* (convert|copy|fusion)\(",
                         text)
    pages = S_MAX // PAGE
    pool = _struct((2 * slots * pages, 1, PAGE, HEAD), jnp.bfloat16, one_chip)
    text = _compiled_text(
        functools.partial(paged_flash_decode, interpret=False),
        _struct((slots, 20, HEAD), jnp.bfloat16, one_chip), pool, pool,
        i32(slots), i32(slots, pages))
    assert "tpu_custom_call" in text and "paged_flash_decode" in text


@pytest.mark.parametrize("rows,block_m", [(32, 16), (8192, 128)])
def test_held_expert_group_gemms_compile(one_chip, rows, block_m):
    """The routed experts' two grouped GEMMs where a chip holds 16 of 128
    experts of 6144 x 2048 (top-8; every assignment keeps a row): one
    whole expert matrix is 25 MB, so a tile is the widest slice of it
    that fits (``gated_experts._tile_n``), the contraction never split."""
    from triton_dist_tpu.models.gated_experts import _tile_n
    from triton_dist_tpu.ops.group_gemm import GroupGemmConfig, group_gemm
    from triton_dist_tpu.utils import round_up

    hidden, fe, held, topk = 6144, 2048, 16, 8
    t = rows * topk
    t_pad = round_up(t + min(held, t) * (block_m - 1), block_m)
    ids = _struct((t_pad // block_m,), jnp.int32, one_chip)
    assert (_tile_n(hidden, fe, 2), _tile_n(fe, hidden, 2)) == (256, 768)

    def gemms(a, w_up, w_down, ids, valid):
        up = GroupGemmConfig(block_m=block_m, block_n=_tile_n(hidden, fe, 2),
                             block_k=hidden, ragged=True)
        down = GroupGemmConfig(block_m=block_m, block_n=_tile_n(fe, hidden, 2),
                               block_k=fe, ragged=True)
        gu = group_gemm(a, w_up, ids, valid_rows=valid, config=up,
                        interpret=False)
        return group_gemm(gu[:, :fe] * gu[:, fe:], w_down, ids,
                          valid_rows=valid, config=down, interpret=False)

    text = _compiled_text(
        gemms,
        _struct((t_pad, hidden), jnp.bfloat16, one_chip),
        _struct((held, hidden, 2 * fe), jnp.bfloat16, one_chip),
        _struct((held, fe, hidden), jnp.bfloat16, one_chip), ids, ids,
    )
    assert text.count("tpu_custom_call") >= 2 and "group_gemm" in text


def test_matmul_compiles(one_chip):
    from triton_dist_tpu.ops.gemm import matmul

    a = _struct((M_TOKENS, HIDDEN), jnp.bfloat16, one_chip)
    b = _struct((HIDDEN, FFN), jnp.bfloat16, one_chip)
    text = _compiled_text(functools.partial(matmul, interpret=False), a, b)
    assert "tpu_custom_call" in text


def _shard_mapped(fn, mesh, in_specs, out_specs):
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


@pytest.mark.parametrize(
    "n_cols",
    [
        pytest.param(2 * FFN, id="gate_up"),
        # 128256 / 4 = 32064 = 64 x 501: the picked column block is not
        # lane-aligned, which Mosaic refuses unless ag_gemm pads (PR 23)
        pytest.param(VOCAB, id="lm_head"),
    ],
)
def test_ag_gemm_compiles_on_four(mesh4, n_cols):
    from triton_dist_tpu.ops.allgather_gemm import ag_gemm

    a = _struct(
        (M_TOKENS, HIDDEN), jnp.bfloat16, NamedSharding(mesh4, P("tp", None))
    )
    b = _struct(
        (HIDDEN, n_cols), jnp.bfloat16, NamedSharding(mesh4, P(None, "tp"))
    )
    fn = _shard_mapped(
        functools.partial(ag_gemm, axis="tp", interpret=False), mesh4,
        (P("tp", None), P(None, "tp")), P(None, "tp"),
    )
    text = _compiled_text(fn, a, b)
    assert "tpu_custom_call" in text
    # the gather is the kernel's ring, not an XLA collective
    assert "all-gather" not in text


def test_gemm_rs_compiles_on_four(mesh4):
    from triton_dist_tpu.ops.gemm_reduce_scatter import gemm_rs

    a = _struct(
        (M_TOKENS, FFN), jnp.bfloat16, NamedSharding(mesh4, P(None, "tp"))
    )
    b = _struct(
        (FFN, HIDDEN), jnp.bfloat16, NamedSharding(mesh4, P("tp", None))
    )
    fn = _shard_mapped(
        functools.partial(gemm_rs, axis="tp", interpret=False), mesh4,
        (P(None, "tp"), P("tp", None)), P("tp", None),
    )
    text = _compiled_text(fn, a, b)
    assert "tpu_custom_call" in text
    assert "reduce-scatter" not in text


@pytest.mark.parametrize(
    "k_tot, tiles",
    [
        pytest.param(12288, "_1024m1536n1536k_512m768n3072k", id="wo"),
        pytest.param(28672, "_1024m2048n1024k_512m1024n1792k", id="w_down"),
    ],
)
def test_gemm_rs_scatter_compiles_under_the_rule_at_the_tp4_cells_widths(
    mesh4, k_tot, tiles
):
    """``mistral-large-2407-tp4.summarize``'s two row-parallel GEMMs with
    ``config=None`` (PR 43): the remote chunks' pipeline and the own
    chunk's each take the largest step ``ops.common.gemm_tile`` fits into
    its budget, and the limit the kernel then asks for has to cover what
    Mosaic allocates for both (35.8 MiB asked here: past the compiler's
    16 MiB default, which is why the parent's tile was small). The tiles
    are pinned: a change of the rule or of its budget shows here first."""
    from triton_dist_tpu.ops.gemm_reduce_scatter import gemm_rs

    a = _struct(
        (M_TOKENS, k_tot), jnp.bfloat16, NamedSharding(mesh4, P(None, "tp"))
    )
    b = _struct(
        (k_tot, 12288), jnp.bfloat16, NamedSharding(mesh4, P("tp", None))
    )
    fn = _shard_mapped(
        # (a 2x2 described here has no wrap-around, the auto method's
        # choice on the chip; the CPU's device list says otherwise)
        functools.partial(gemm_rs, axis="tp", method="scatter", interpret=False),
        mesh4, (P(None, "tp"), P("tp", None)), P("tp", None),
    )
    text = _compiled_text(fn, a, b)
    assert f"gemm_rs_scatter{tiles}" in text


def test_ag_gemm_compiles_under_the_rule_at_the_tp4_cells_qkv(mesh4):
    """N = 3584 a shard (7 x 512): the rule's column block is 1792, where
    halving from 2048 fell to 512 and a 268-MFLOP grid step."""
    from triton_dist_tpu.ops.allgather_gemm import ag_gemm

    a = _struct(
        (M_TOKENS, 12288), jnp.bfloat16, NamedSharding(mesh4, P("tp", None))
    )
    b = _struct(
        (12288, 4 * 3584), jnp.bfloat16, NamedSharding(mesh4, P(None, "tp"))
    )
    fn = _shard_mapped(
        functools.partial(ag_gemm, axis="tp", interpret=False), mesh4,
        (P("tp", None), P(None, "tp")), P(None, "tp"),
    )
    assert "ag_gemm_1024m1792n1024k" in _compiled_text(fn, a, b)


def test_paged_flash_decode_distributed_compiles_on_four(mesh4):
    """The TP=4 decode step's attention: paged flash-decode over each
    PE's sequence shard, then the ``full_mesh_push`` all-gather of the
    (out | lse) payload — 8*32 + 2 = 258 rows per PE, not a whole number of
    sublane tiles. Unpadded, that slot layout compiled and then halted the
    chip (PR 23); ``all_gather`` pads the rows. The compile is what can be
    held here: two kernels, and no XLA all-gather standing in for the push."""
    from triton_dist_tpu.ops.flash_decode import paged_flash_decode_distributed

    pages_per_shard = S_MAX // 4 // PAGE
    sharded = NamedSharding(mesh4, P("tp"))
    pool = _struct(
        (4 * SLOTS * pages_per_shard, N_KV, PAGE, HEAD), jnp.bfloat16, sharded
    )
    table = _struct((4 * SLOTS, pages_per_shard), jnp.int32, sharded)
    lens = _struct((4 * SLOTS,), jnp.int32, sharded)
    q = _struct((SLOTS, N_Q, HEAD), jnp.bfloat16, NamedSharding(mesh4, P()))
    assert SLOTS * N_Q + -(-SLOTS * N_Q // HEAD) == 258
    fn = _shard_mapped(
        functools.partial(
            paged_flash_decode_distributed, axis="tp", interpret=False
        ),
        mesh4, (P(), P("tp"), P("tp"), P("tp"), P("tp")), P(),
    )
    text = _compiled_text(fn, q, pool, pool, lens, table)
    assert text.count("tpu_custom_call") >= 2
    assert "all-gather" not in text


@pytest.mark.parametrize("rows", [0, 16], ids=["step", "verify_chunk"])
def test_kv_pool_is_written_and_read_in_place(topo, rows):
    """One layer of the paged k/v kind at serving widths, cache donated:
    the chip's compiler keeps the stacked pool in the layout it arrives
    in (no ``copy`` of the pool's shape, no pool-sized temporary) and
    aliases it in and out. With a scatter window of ``[h_kv, d]`` it
    re-lays the whole pool around every scatter, which is what
    ``_write_rows`` indexes the head for. ``rows`` > 0: the verify /
    ranged-prefill twin."""
    from triton_dist_tpu.models.decode import PagedKVCacheSpec
    from triton_dist_tpu.models.tp_transformer import TransformerConfig

    n_layers = 4   # 4 x 33.5 MB a tensor: more than fast memory can hide
    cfg = TransformerConfig(
        vocab=VOCAB, hidden=HIDDEN, ffn=FFN, n_layers=n_layers, n_q_heads=N_Q,
        n_kv_heads=N_KV, head_dim=HEAD, batch=SLOTS, dtype=jnp.bfloat16)
    spec = PagedKVCacheSpec(S_MAX, PAGE, static_table=True)
    mesh = Mesh(np.array(topo.devices[:1]), ("tp",))
    lead = (SLOTS, rows) if rows else (SLOTS,)

    def layer(cache, k_new, v_new, q, pos):
        attend = (
            spec.update_multi_and_attend if rows else spec.update_and_attend)
        return attend(
            cfg, cache, 1, k_new, v_new, q, pos, jax.lax.axis_index("tp"), 1,
            None, False)

    cs = spec.specs(cfg)
    fn = jax.jit(
        jax.shard_map(
            layer, mesh=mesh, in_specs=(cs, P(), P(), P(), P()),
            out_specs=(P(), cs), check_vma=False),
        donate_argnums=(0,))
    rep = NamedSharding(mesh, P())
    shapes = jax.eval_shape(lambda: spec.init(cfg, 1, 1))
    cache = jax.tree.map(
        lambda x, s: _struct(x.shape, x.dtype, NamedSharding(mesh, s)),
        shapes, cs)
    compiled = fn.lower(
        cache,
        _struct(lead + (N_KV, HEAD), jnp.bfloat16, rep),
        _struct(lead + (N_KV, HEAD), jnp.bfloat16, rep),
        _struct(lead + (N_Q, HEAD), jnp.bfloat16, rep),
        _struct((SLOTS,), jnp.int32, rep),
    ).compile()
    text = compiled.as_text()
    pool = "bf16[%d,%d,%d,%d,%d]" % shapes["k"].shape
    pool_bytes = 2 * np.prod(shapes["k"].shape)
    assert "tpu_custom_call" in text
    assert not re.findall(re.escape(pool) + r"\S* copy\(", text)
    assert pool + "{4,3,2,1,0" in text and pool + "{4,2,3,1,0" not in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // n_layers


def test_the_member_walk_carries_the_pools_in_place(topo):
    """The dense family's one-chip admission at serving widths (PR 47): a
    ``while`` over the pass's members whose carry is the donated cache. The
    chip's compiler has to keep the pools in place through the loop (no
    ``copy`` of a pool's shape, the pools aliased in and out): beside 7.5 GB
    of weights a second copy of them does not fit. And the trip runs ONE
    member's rows: no temporary as wide as the whole batch's."""
    from triton_dist_tpu.models import decode, tp_transformer
    from triton_dist_tpu.models.tp_transformer import TransformerConfig

    bucket, n_layers = 512, 2
    cfg = TransformerConfig(
        vocab=VOCAB, hidden=HIDDEN, ffn=FFN, n_layers=n_layers, n_q_heads=N_Q,
        n_kv_heads=N_KV, head_dim=HEAD, batch=SLOTS, dtype=jnp.bfloat16,
        interpret=False)
    spec = decode.PagedKVCacheSpec(S_MAX, PAGE, static_table=True)
    mesh = Mesh(np.array(topo.devices[:1]), ("tp",))
    rep = NamedSharding(mesh, P())
    place = lambda shapes, specs: jax.tree.map(
        lambda x, s: _struct(x.shape, x.dtype, NamedSharding(mesh, s)),
        shapes, specs)
    shapes = jax.eval_shape(
        functools.partial(tp_transformer._init_tree, cfg=cfg),
        jax.random.PRNGKey(0))
    ps, cs = decode.specs_for(cfg, shapes), spec.specs(cfg)
    pools = jax.eval_shape(lambda: spec.init(cfg, 1, 1))
    with _chip_posture():
        compiled = jax.jit(
            jax.shard_map(
                decode._member_walk(cfg, spec, S_MAX, bucket), mesh=mesh,
                in_specs=(ps, cs, P(), P(), P(), P()), out_specs=(cs, P()),
                check_vma=False),
            donate_argnums=(1,),
        ).lower(
            place(shapes, ps), place(pools, cs),
            _struct((SLOTS, bucket), jnp.int32, rep),
            _struct((SLOTS,), jnp.int32, rep),
            _struct((SLOTS,), jnp.int32, rep), _struct((), jnp.int32, rep),
        ).compile()
    text = compiled.as_text()
    pool = "bf16[%d,%d,%d,%d,%d]" % pools["k"].shape
    pool_bytes = 2 * np.prod(pools["k"].shape)
    assert len(re.findall(r" while\(", text)) == 1 and "tpu_custom_call" in text
    assert not re.findall(re.escape(pool) + r"\S* copy\(", text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    # the widest temporaries are one member's, gate|up [bucket, 2F] and the
    # head over its rows [bucket, V] (134 MB read), not the batch's (8 x)
    assert mem.temp_size_in_bytes < 2 * bucket * (2 * FFN + VOCAB) * 2


# -- SmallThinker-21BA3B at its published widths (perfbench's configuration) ------

@pytest.mark.parametrize("window", [4096, None], ids=["w4096", "full"])
def test_flash_prefill_compiles(one_chip, window):
    """The tiled prefill attention at the bucket the cell admits: one
    slot's 8192 rows, 28 q heads on 4 kv heads of 128 (a group of 7
    stacked into one 896-row operand), no ``[L, L]`` temporary."""
    from triton_dist_tpu.ops.flash_prefill import flash_prefill

    L, hq, h_kv = 8192, 28, 4
    compiled = jax.jit(functools.partial(
        flash_prefill, window=window, interpret=False)).lower(
        _struct((1, L, hq, HEAD), jnp.bfloat16, one_chip),
        _struct((1, L, h_kv, HEAD), jnp.bfloat16, one_chip),
        _struct((1, L, h_kv, HEAD), jnp.bfloat16, one_chip),
        _struct((1,), jnp.int32, one_chip)).compile()
    name = "flash_prefill" + (f"_w{window}" if window else "")
    assert "tpu_custom_call" in compiled.as_text() and name in compiled.as_text()
    # the output and nothing that grows with L squared
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * L * hq * HEAD * 2


def _compiled_admission(cfg, spec, mesh, params, cache, L: int):
    """One slot's admission at bucket ``L`` as the batcher's program calls
    it, compiled in the chip's posture: where kernels are interpreted
    (here, by default) ``ops/moe_utils`` validates the combine's contract
    under a ``lax.cond`` whose other branch is a float32 scatter as wide as
    the sorted rows, which no chip compiles."""
    import dataclasses

    from triton_dist_tpu.models import decode

    pcfg = dataclasses.replace(cfg, seq=L)
    rep = NamedSharding(mesh, P())
    cs = spec.specs(cfg)

    def fn(p, c, prompt, mask, pick):
        return decode.prefill_cache(
            pcfg, p, c, prompt.reshape(-1), spec, spec.s_max, slot_mask=mask,
            pick=pick)

    with _chip_posture():
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(cfg.param_specs(), cs, P(), P(), P()),
            out_specs=(cs, P(), P()), check_vma=False),
            donate_argnums=(1,)).lower(
            params, cache, _struct((cfg.batch, L), jnp.int32, rep),
            _struct((cfg.batch,), jnp.bool_, rep),
            _struct((cfg.batch,), jnp.int32, rep)
        ).compile()


@pytest.fixture(scope="module")
def smallthinker(topo):
    """``(cfg, spec, mesh, params' and cache's shapes)`` of the
    benchmark's SmallThinker configuration on one described chip."""
    import sys

    perfbench = os.path.join(
        os.path.dirname(os.path.dirname(__file__)), "perfbench")
    if perfbench not in sys.path:
        sys.path.insert(0, perfbench)
    from harness import cells

    from triton_dist_tpu.models.decode import WindowPagedKVCacheSpec
    from triton_dist_tpu.models.window_moe import init_window_moe_params

    cell = cells.Cell(cells.benchmark(), "smallthinker-21b-a3b.doc-reason")
    adapter = cells.load_module("programs", cell.config["program"])
    cfg = adapter.model_config(cell.config, interpret=False)
    eng = cell.config["engine"]
    spec = WindowPagedKVCacheSpec(eng["s_max"], eng["page"], static_table=True)
    mesh = Mesh(np.array(topo.devices[:1]), (cfg.axis,))
    place = lambda shapes, specs: jax.tree.map(
        lambda x, s: _struct(x.shape, x.dtype, NamedSharding(mesh, s)),
        shapes, specs)
    params = place(
        jax.eval_shape(functools.partial(init_window_moe_params, cfg=cfg),
                       jax.random.PRNGKey(0)), cfg.param_specs())
    cache = place(jax.eval_shape(lambda: spec.init(cfg, 1)), spec.specs(cfg))
    return cfg, spec, mesh, params, cache


def test_smallthinker_step_compiles(smallthinker):
    """The decode step at the published widths: 32 slots, rings of 33
    pages, a full layer's table row of 128 pages, a group of 7; the
    cache aliased in and out, the window walk named for its window."""
    from triton_dist_tpu.models import decode

    cfg, spec, mesh, params, cache = smallthinker
    assert (spec.ring(cfg), cfg.n_q_heads // cfg.n_kv_heads) == (33, 7)
    assert cache["block_table"].shape == (1, 32, 128)
    assert cache["block_table_win"].shape == (1, 32, 33)
    rep = NamedSharding(mesh, P())
    cs = spec.specs(cfg)
    fn = jax.jit(jax.shard_map(
        lambda p, c, t, pos: decode.decode_step(
            cfg, p, c, t, pos, spec=spec, interpret=False),
        mesh=mesh, in_specs=(cfg.param_specs(), cs, P(), P()),
        out_specs=(P(), cs, P()), check_vma=False), donate_argnums=(1,))
    compiled = fn.lower(params, cache, _struct((32,), jnp.int32, rep),
                        _struct((32,), jnp.int32, rep)).compile()
    text = compiled.as_text()
    assert "paged_flash_decode_w4096" in text and "group_gemm" in text
    mem = compiled.memory_analysis()
    pools = sum(np.prod(cache[k].shape) * 2 for k in (
        "k_full", "v_full", "k_win", "v_win"))
    assert mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < 0.5e9


def test_smallthinker_admission_compiles_with_no_square_of_scores(smallthinker):
    """One slot's admission at bucket 8192 through the tiled kernel: both
    attention kinds, the last 4224 true rows landed in a ring of 33 pages;
    by the compiler's count the temporaries are under 2 GB (one layer's
    materialized scores would be 7.5 GB) and no array holds ``L x L`` or
    ``L x 2 window`` elements."""
    L = 8192
    compiled = _compiled_admission(*smallthinker, L)
    text = compiled.as_text()
    assert "flash_prefill_w4096" in text and "flash_prefill" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
    # no array of the program has two dimensions of L or more: neither
    # ``[.., L, L]`` scores nor ``[.., L, 2 x window]`` bands
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"\b(?:bf16|f32|s32)\[([\d,]+)\]", text)}
    assert not [s for s in shapes if sum(d >= L for d in s) >= 2]


def _latent_cell(topo, name: str):
    """``(cfg, spec, mesh, params' and cache's shapes)`` of one of the
    benchmark's latent-attention configurations on one described chip."""
    import sys

    perfbench = os.path.join(
        os.path.dirname(os.path.dirname(__file__)), "perfbench")
    if perfbench not in sys.path:
        sys.path.insert(0, perfbench)
    from harness import cells

    from triton_dist_tpu.models.decode import LatentPagedCacheSpec
    from triton_dist_tpu.models.mla_moe import init_mla_moe_params

    cell = cells.Cell(cells.benchmark(), name)
    adapter = cells.load_module("programs", cell.config["program"])
    cfg = adapter.model_config(cell.config, interpret=False)
    eng = cell.config["engine"]
    spec = LatentPagedCacheSpec(eng["s_max"], eng["page"], static_table=True)
    mesh = Mesh(np.array(topo.devices[:1]), (cfg.axis,))
    place = lambda shapes, specs: jax.tree.map(
        lambda x, s: _struct(x.shape, x.dtype, NamedSharding(mesh, s)),
        shapes, specs)
    params = place(
        jax.eval_shape(functools.partial(init_mla_moe_params, cfg=cfg),
                       jax.random.PRNGKey(0)), cfg.param_specs())
    cache = place(jax.eval_shape(lambda: spec.init(cfg, 1)), spec.specs(cfg))
    return cfg, spec, mesh, params, cache


def _compiled_step(cfg, spec, mesh, params, cache):
    from triton_dist_tpu.models import decode

    rep = NamedSharding(mesh, P())
    cs = spec.specs(cfg)
    fn = jax.jit(jax.shard_map(
        lambda p, c, t, pos: decode.decode_step(
            cfg, p, c, t, pos, spec=spec, interpret=False),
        mesh=mesh, in_specs=(cfg.param_specs(), cs, P(), P()),
        out_specs=(P(), cs, P()), check_vma=False), donate_argnums=(1,))
    return fn.lower(params, cache, _struct((cfg.batch,), jnp.int32, rep),
                    _struct((cfg.batch,), jnp.int32, rep)).compile()


def test_joyai_step_compiles_to_the_parents_program(topo):
    """The plain latent plan (JoyAI-LLM-Flash, the benchmark's cell) after
    the family learned a second attention kind: its decode step at the
    published widths, compiled for the described chip, holds the SAME
    INSTRUCTIONS as before, counted by opcode and result shape (names,
    metadata and the kernels' serialized bodies left out: those move with
    a line number). tests/golden/joyai_step.v5e_ops.json was written from
    the commit before PR 41 by this very reduction; regenerate it only
    with a PR that means to change JoyAI's step. PR 42 did, in the
    counters vector alone: it holds a fourth int32 (the sorted rows
    walked, a constant of the step), so the vector's own entries read
    ``s32[4]`` for ``s32[3]`` (its four pads, three adds and one fusion
    of the sum over layers) beside one more ``s32[1]`` constant; every
    other instruction is the parent's. PR 49 did again, in that vector
    alone: it holds a fifth int32 (the rows the combine gathered, a
    constant of the step), and the compiler builds the five as ONE
    ``concatenate s32[5]`` where it padded and added the four: ``add
    s32[4]`` x3, ``pad s32[4]`` x4 and ``fusion s32[4]`` x1 are gone,
    ``constant s32[1]`` 2 -> 3, ``parameter s32[1]`` 13 -> 9 and
    ``constant s32[]`` 369 -> 368; every other instruction is the
    parent's (the combine of a step is the ``topk``-gather form it was:
    the landed walk is a share's chunked admission's)."""
    import collections
    import json

    compiled = _compiled_step(*_latent_cell(topo, "joyai-llm-flash.reason"))
    ops = collections.Counter(
        m.group(2) + " " + m.group(1) for m in re.finditer(
            r"^\s*(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(",
            compiled.as_text(), re.M))
    golden = os.path.join(os.path.dirname(__file__), "golden",
                          "joyai_step.v5e_ops.json")
    with open(golden) as f:
        want = json.load(f)
    diff = {k: (want.get(k, 0), ops.get(k, 0))
            for k in set(want) | set(ops) if want.get(k, 0) != ops.get(k, 0)}
    assert not diff, diff


@pytest.fixture(scope="module")
def dots(topo):
    return _latent_cell(topo, "dots3-note-prev-ep8.doc-reason")


def test_dots_step_compiles(dots):
    """The sparse latent plan's step at the published widths: 32 slots,
    full layers of 128 heads on rows of 640 behind the indexer (64 x 128,
    top-2048 of a table row of 128 pages), window layers of 64 heads on
    rows of 1152 in rings of 6 pages; every pool aliased in and out, each
    kernel under its own name."""
    cfg, spec, mesh, params, cache = dots
    assert (spec.ring(cfg), cfg.latent_row, cfg.window_latent_row) == (
        6, 640, 1152)
    assert {k: v.shape for k, v in cache.items() if "lat" in k or k == "idx"
            } == {"lat": (2, 4096, 128, 640), "idx": (2, 4096, 128, 128),
                  "lat_win": (3, 192, 128, 1152)}
    compiled = _compiled_step(cfg, spec, mesh, params, cache)
    text = compiled.as_text()
    for name in ("index_score", "sparse_mla_decode", "ring_mla_decode",
                 "group_gemm"):
        assert name in text, name
    mem = compiled.memory_analysis()
    pools = sum(np.prod(cache[k].shape) * 2 for k in ("lat", "idx", "lat_win"))
    assert mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < 0.5e9


def test_dots_admission_compiles_with_no_square_of_float_scores(dots):
    """One slot's admission at bucket 8192: the indexer's scores a block
    of 1024 rows at a time, the selection as ``int8 [L, L]``, both tiled
    attentions (q/k of 192 and 256 against values of 128). No float32
    array holds ``L x L`` scores (one full layer's materialized scores of
    128 heads would be 34 GB), and the temporaries fit beside the
    weights."""
    L = 8192
    compiled = _compiled_admission(*dots, L)
    text = compiled.as_text()
    for name in ("index_score_prefill", "mla_flash_prefill_w513",
                 "mla_flash_prefill"):
        assert name in text, name
    # the routed experts walk the 545 sorted blocks a chunk of 72 at a
    # time inside one loop a layer (gated_experts.moe_mlp): gate|up exists
    # at a chunk's rows and never at the alignment's, and each chunk's
    # down GEMM writes its rows of the one result (8 chunks' rows) in place
    assert "bf16[9216,3072]" in text and "bf16[69760,3072]" not in text
    # three loops a layer hold that result: the pass that writes it and,
    # since PR 49, the combine's walk of the landed slots that reads it:
    # a loop over the 16 chunks of 512 tokens around a loop over a chunk's
    # trips, whose carry is the chunk's float32 sum
    loops = [line for line in text.splitlines()
             if " while(" in line and "bf16[73728,5120]" in line]
    assert len(loops) == 12
    assert sum("f32[16,512,5120]" in line for line in loops) == 4
    assert sum("f32[512,5120]" in line for line in loops) == 4
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 15e9
    # the selection leaves as int8; in float32 the scores exist a block of
    # rows at a time (the index queries happen to be [L, 64 x 128] bf16)
    # (so an ``[L, L]`` shape in the text may be those, inside a fusion:
    # what is held to is the block of scores and the temporaries' size)
    assert "s8[1,8192,8192]" in text and "f32[1024,8192]" in text
    assert mem.temp_size_in_bytes < 2.5e9


@pytest.fixture(scope="module")
def brumby(topo):
    """``(cfg, spec, mesh, params' and cache's shapes)`` of the
    benchmark's Brumby configuration on one described chip."""
    import sys

    perfbench = os.path.join(
        os.path.dirname(os.path.dirname(__file__)), "perfbench")
    if perfbench not in sys.path:
        sys.path.insert(0, perfbench)
    from harness import cells

    from triton_dist_tpu.models.decode import RetentionStateCacheSpec
    from triton_dist_tpu.models.retention import init_retention_params

    cell = cells.Cell(cells.benchmark(), "brumby-14b-base.doc-reason-b16")
    adapter = cells.load_module("programs", cell.config["program"])
    cfg = adapter.model_config(cell.config, interpret=False)
    eng = cell.config["engine"]
    spec = RetentionStateCacheSpec(eng["s_max"], eng["page"], static_table=True)
    mesh = Mesh(np.array(topo.devices[:1]), (cfg.axis,))
    place = lambda shapes, specs: jax.tree.map(
        lambda x, s: _struct(x.shape, x.dtype, NamedSharding(mesh, s)),
        shapes, specs)
    params = place(
        jax.eval_shape(functools.partial(init_retention_params, cfg=cfg),
                       jax.random.PRNGKey(0)), cfg.param_specs())
    cache = place(jax.eval_shape(lambda: spec.init(cfg, 1)), spec.specs(cfg))
    return cfg, spec, mesh, params, cache


def test_brumby_step_compiles_with_the_state_in_place(brumby):
    """The power-retention step at the published widths: 16 slots, 6
    layers, a kv head's state of 8704 x 128 float32 walked in row tiles;
    both pools (6.9 GB) aliased in and out, so that the step holds the
    state once, and the temporaries are a token's."""
    cfg, spec, mesh, params, cache = brumby
    assert cache["s"].shape == (6, 2, 16, 8, 8704, 128)
    compiled = _compiled_step(cfg, spec, mesh, params, cache)
    text = compiled.as_text()
    assert text.count("retention_update") >= cfg.n_layers
    mem = compiled.memory_analysis()
    pools = sum(int(np.prod(x.shape)) * 4 for x in cache.values())
    assert pools == cfg.state_bytes() and mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < 0.2e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9


def test_brumby_admission_compiles_at_bucket_8192_beside_the_state(brumby):
    """One slot's admission at bucket 8192 through the chunked kernel (16
    chunks of 512 a kv head, the state resident): no array holds ``L x L``
    weights, and the temporaries fit beside the weights and the state."""
    L = 8192
    compiled = _compiled_admission(*brumby, L)
    text = compiled.as_text()
    assert "retention_prefill" in text
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"\b(?:bf16|f32|s32)\[([\d,]+)\]", text)}
    # (the MLP's [L, 17408] and [L, 34816] are wider than L and are rows)
    assert not [s for s in shapes if sum(d == L for d in s) >= 2]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= brumby[0].state_bytes()
    print("brumby admission: temporaries", mem.temp_size_in_bytes,
          "arguments", mem.argument_size_in_bytes)
    assert mem.temp_size_in_bytes < 1.5e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9


@pytest.fixture(scope="module")
def granite(topo):
    """``(cfg, spec, mesh, params' and cache's shapes)`` of the
    benchmark's Granite-4.0-H configuration on one described chip."""
    import sys

    perfbench = os.path.join(
        os.path.dirname(os.path.dirname(__file__)), "perfbench")
    if perfbench not in sys.path:
        sys.path.insert(0, perfbench)
    from harness import cells

    from triton_dist_tpu.models.decode import StatePagedKVCacheSpec
    from triton_dist_tpu.models.ssm_hybrid import init_ssm_hybrid_params

    cell = cells.Cell(cells.benchmark(), "granite-4.0-h-small-ep4.doc-reason")
    adapter = cells.load_module("programs", cell.config["program"])
    cfg = adapter.model_config(cell.config, interpret=False)
    eng = cell.config["engine"]
    spec = StatePagedKVCacheSpec(eng["s_max"], eng["page"], static_table=True)
    mesh = Mesh(np.array(topo.devices[:1]), (cfg.axis,))
    place = lambda shapes, specs: jax.tree.map(
        lambda x, s: _struct(x.shape, x.dtype, NamedSharding(mesh, s)),
        shapes, specs)
    params = place(
        jax.eval_shape(functools.partial(init_ssm_hybrid_params, cfg=cfg),
                       jax.random.PRNGKey(0)), cfg.param_specs())
    cache = place(jax.eval_shape(lambda: spec.init(cfg, 1)), spec.specs(cfg))
    return cfg, spec, mesh, params, cache


def test_granite_step_compiles_with_the_state_and_the_ring_in_place(granite):
    """The Mamba-2 / expert step at the published widths: 32 slots, nine
    state-space layers whose state ``[128, 8192]`` float32 is walked in
    lane blocks and whose convolution ring is 8448 channels wide (no
    multiple of the ring kernel's 1024-channel unit: one block holds a
    layer's ring whole, cut out of the pool for the call), one attention
    layer on pages, 18 held experts of 768; every pool aliased in and out;
    no ``[N, d]`` decay of a slot is built."""
    cfg, spec, mesh, params, cache = granite
    assert cache["ssm"].shape == (9, 2, 32, 128, 8192)
    assert cache["conv"].shape == (9, 4, 32, 8448)
    assert cache["k"].shape == (1, 32 * 128, 8, 128, 128)
    compiled = _compiled_step(cfg, spec, mesh, params, cache)
    text = compiled.as_text()
    for kernel, calls in (("ssd_state_update", 9), ("conv_ring_step", 9),
                          ("paged_flash_decode", 1), ("group_gemm", 20)):
        assert text.count(kernel) >= calls, kernel
    assert "selective_state_update" not in text
    assert "f32[32,128,8192]" not in text
    mem = compiled.memory_analysis()
    pools = sum(int(np.prod(cache[k].shape)) * cache[k].dtype.itemsize
                for k in ("ssm", "conv", "k", "v"))
    assert mem.alias_size_in_bytes >= pools
    print("granite step: temporaries", mem.temp_size_in_bytes,
          "arguments", mem.argument_size_in_bytes)
    assert mem.temp_size_in_bytes < 0.3e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9


def test_granite_admission_compiles_in_the_chunked_form(granite):
    """One slot's admission at bucket 8192: the recurrence through the
    chunked kernel (32 chunks of 256, the state resident), attention
    through the tiled kernel; no token-by-token scan, no ``L x L`` array,
    and the temporaries fit beside the weights, the state and the pages."""
    L = 8192
    compiled = _compiled_admission(*granite, L)
    text = compiled.as_text()
    assert text.count("ssd_chunk_scan") >= 9 and "flash_prefill" in text
    assert "selective_scan" not in text
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"\b(?:bf16|f32|s32)\[([\d,]+)\]", text)}
    # ([L, 8192] is a prompt's rows at the inner width, not L x L)
    assert not [s for s in shapes if sum(d == L for d in s) >= 3]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= granite[0].state_bytes()
    print("granite admission: temporaries", mem.temp_size_in_bytes,
          "arguments", mem.argument_size_in_bytes)
    assert mem.temp_size_in_bytes < 4.5e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
