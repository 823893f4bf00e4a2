"""Fused GEMM-ReduceScatter — TP row-parallel forward
(≙ reference ``kernels/nvidia/gemm_reduce_scatter.py``, 561 LoC).

The reference runs a producer GEMM whose tiles *notify* per-output-rank
counters (``dl.notify`` on the last tile of a rank's rows,
gemm_reduce_scatter.py:224-235) with a rank+1-first threadblock swizzle
(:190-200) so communication for remote ranks starts as early as possible,
while a consumer reduce-scatter pipeline drains finished chunks on separate
high-priority streams (reduce_scatter.py:863).

TPU-native re-design: one fused Pallas kernel per PE; the tile swizzle
becomes the *chunk emission order* of the kernel's outer loop, and the
notify/consumer machinery collapses into the data-coupled receive semaphore
of each one-sided put. Two strategies (auto-selected like the standalone
reduce-scatter):

- ``scatter`` — produce the partial chunk destined for PE ``me+d`` in
  increasing-``d`` order (own chunk LAST — exactly the reference's
  rank+1-first swizzle) and push each chunk to its owner the moment its
  GEMM finishes; the ICI DMA overlaps the next chunk's MXU work. The final
  own-chunk GEMM fuses the n-way reduction of all landed chunks into its
  epilogue, so the reduce costs no extra HBM round-trip.
- ``ring`` — bandwidth-optimal fused ring reduce-scatter: step ``s``
  produces partial chunk ``me-1-s``, fused-adds the partially-reduced chunk
  that arrived from the left during step ``s-1``, and forwards it right;
  the last step's add lands directly in ``out``. Per-hop carry is in
  ``out_dtype`` (one round-off per hop, like any ring reduce).

Used for TP row-parallel layers: A is ``[M, k_loc]`` (K-sharded
activations, e.g. the output of a column-parallel layer), B is
``[k_loc, N]``; every PE gets its M-chunk of the fully-reduced C.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu import resilience
from triton_dist_tpu.autotuner import contextual_autotune
from triton_dist_tpu.ops.common import (
    GemmTile,
    dist_pallas_call,
    gemm_add_pipeline,
    gemm_chunk_spans,
    gemm_only,
    gemm_tile,
    jit_shard_map,
)
from triton_dist_tpu.ops.reduce_scatter import get_auto_reduce_scatter_method
from triton_dist_tpu.shmem import device as shmem
from triton_dist_tpu.utils import pick_block
from triton_dist_tpu.utils import axis_size as _axis_size


def _gemm_rs_xla(
    a: jax.Array, b: jax.Array, *, axis="tp", out_dtype=None, **_
) -> jax.Array:
    """The golden slow path (the same program every fused method is tested
    against): XLA's dot + psum-scatter, single- or multi-axis."""
    axes = tuple(axis) if isinstance(axis, (tuple, list)) else axis
    out_dtype = out_dtype or a.dtype
    return jax.lax.psum_scatter(
        jnp.dot(a, b, preferred_element_type=out_dtype), axes, tiled=True
    )


@dataclasses.dataclass(frozen=True)
class GemmRSConfig:
    """Tunables (≙ the GEMM tile knobs of the reference contexts,
    gemm_reduce_scatter.py:42-86; stream/buffer plumbing is subsumed by the
    fused kernel).

    The block fields carry no default tile: left unset (``config=None``,
    which is what every served call passes, or a config that sets
    ``chunks_per_shard`` alone) the tile is ``ops.common.gemm_tile``'s,
    a function of the GEMM's shape and a VMEM budget. Set (all three: the
    autotuner's candidates, a test's tiny tile) they are honoured, each
    shrunk to a divisor of its dimension."""

    block_m: int | None = None
    block_n: int | None = None
    block_k: int | None = None
    # block_m=0: world-1 XLA-native sentinel (see AGGemmConfig) — the
    # no-comm degenerate case goes to jnp.dot; raises at n>1.
    # Ring-step payload granularity (ISSUE 3): > 1 splits each ring hop's
    # partial chunk into that many per-chunk DMAs produced/added/forwarded
    # independently; 1 is the legacy shard-granular schedule, bit for bit.
    # Ring method only (the scatter method's puts are single-hop).
    chunks_per_shard: int = 1


def _gemm_rs_scatter_kernel(
    a_ref, b_ref, out_ref, send_buf, recv_buf, acc_ref, acc_own_ref,
    send_sems, recv_sems,
    *, axis: str, n: int, tile: GemmTile, tile_own: GemmTile, out_dtype,
):
    me = shmem.my_pe(axis)
    m_tot, k_loc = a_ref.shape
    n_dim = b_ref.shape[1]
    m_loc = m_tot // n
    gemm = gemm_add_pipeline(
        *tile[:3], m_loc, n_dim, k_loc, acc_ref, out_dtype, 0
    )
    # the own chunk's adds cost VMEM the remote chunks' pipeline does not
    # pay: its tile is its own, from the same rule
    gemm_reduce = gemm_add_pipeline(
        *tile_own[:3], m_loc, n_dim, k_loc, acc_own_ref, out_dtype, n - 1
    )

    # race shaking (no-op unless config.debug_comm_delay)
    shmem.comm_jitter(axis, salt=10)
    # All PEs must be inside the kernel before any chunk may land in their
    # slots (≙ the barrier before the scatter stage, reduce_scatter.py:604).
    shmem.barrier_all(axis)

    # Remote chunks first, own chunk last (≙ rank+1-first swizzle,
    # gemm_reduce_scatter.py:190-200). Receiver slot d-1 holds the chunk
    # from PE me-d — distinct per sender by symmetry.
    descs = []
    for d in range(1, n):
        dst = jax.lax.rem(me + d, n)
        slot = (d - 1) % 2
        if d >= 3:
            descs[d - 3].wait_send()  # send_buf slot free again
        gemm(a_ref.at[pl.ds(dst * m_loc, m_loc)], b_ref, send_buf.at[slot])
        descs.append(
            shmem.putmem_nbi_block(
                recv_buf.at[d - 1], send_buf.at[slot], dst, axis,
                send_sems.at[d - 1], recv_sems.at[d - 1],
            )
        )
    # Symmetric SPMD: our descriptors' recv side counts the equal-sized
    # incoming chunks, so this waits for all n-1 arrivals.
    for desc in descs:
        desc.wait_recv()
    # Own chunk's GEMM with the n-way reduction fused into its epilogue.
    gemm_reduce(
        a_ref.at[pl.ds(me * m_loc, m_loc)], b_ref,
        *(recv_buf.at[d] for d in range(n - 1)), out_ref,
    )
    shmem.quiet(*descs)


def _gemm_rs_ring_kernel(
    a_ref, b_ref, out_ref, comp_buf, recv_buf, acc_ref, send_sems, recv_sems,
    *, axis: str, n: int, tile: GemmTile, out_dtype,
):
    me = shmem.my_pe(axis)
    m_tot, k_loc = a_ref.shape
    n_dim = b_ref.shape[1]
    m_loc = m_tot // n
    bm, bn, bk = tile[:3]
    gemm = gemm_add_pipeline(bm, bn, bk, m_loc, n_dim, k_loc, acc_ref, out_dtype, 0)
    gemm_add = gemm_add_pipeline(bm, bn, bk, m_loc, n_dim, k_loc, acc_ref, out_dtype, 1)

    shmem.comm_jitter(axis, salt=11)
    shmem.barrier_all(axis)
    right = jax.lax.rem(me + 1, n)

    # Step s: produce partial chunk (me-1-s), fused-add the partial that
    # landed from the left during step s-1, forward right. After n-1 hops
    # the fully-reduced own chunk lands in out_ref.
    descs = []
    for s in range(n):
        c = pl.ds(jax.lax.rem(me - 1 - s + 2 * n, n) * m_loc, m_loc)
        target = out_ref if s == n - 1 else comp_buf.at[s % 2]
        if 2 <= s < n - 1:
            descs[s - 2].wait_send()  # comp_buf slot s%2 free again
        if s == 0:
            gemm(a_ref.at[c], b_ref, target)
        else:
            descs[s - 1].wait_recv()  # partial chunk landed in recv_buf[s-1]
            gemm_add(a_ref.at[c], b_ref, recv_buf.at[s - 1], target)
        if s < n - 1:
            descs.append(
                shmem.putmem_nbi_block(
                    recv_buf.at[s], target, right, axis,
                    send_sems.at[s], recv_sems.at[s],
                )
            )
    shmem.quiet(*descs)


def _gemm_rs_ring_chunked_kernel(
    a_ref, b_ref, out_ref, comp_buf, recv_buf, acc_ref, send_sems, recv_sems,
    sig_sems, *, axis: str, n: int, tile: GemmTile, out_dtype, spans,
):
    """Chunk-granular fused ring GEMM-RS (ISSUE 3 tentpole): step ``s``
    produces, fused-adds, and forwards its partial chunk in ``len(spans)``
    independent sub-chunks — chunk ``j``'s MXU work runs while chunk ``j+1``
    of the incoming partial is still in flight, so each hop exposes one
    *chunk* of ICI latency instead of one m_loc-row shard. chunk=1
    dispatches to :func:`_gemm_rs_ring_kernel` (bit-identical legacy)."""
    me = shmem.my_pe(axis)
    m_tot, k_loc = a_ref.shape
    n_dim = b_ref.shape[1]
    m_loc = m_tot // n
    bn, bk = tile.bn, tile.bk
    bms = [pick_block(rows, tile.bm) for _, rows in spans]
    bm_max = max(bms)
    gemms, gemm_adds = [], []
    for (_, rows), bm_j in zip(spans, bms):
        acc_j = acc_ref if bm_j == bm_max else acc_ref.at[pl.ds(0, bm_j), :]
        gemms.append(
            gemm_add_pipeline(bm_j, bn, bk, rows, n_dim, k_loc, acc_j, out_dtype, 0)
        )
        gemm_adds.append(
            gemm_add_pipeline(bm_j, bn, bk, rows, n_dim, k_loc, acc_j, out_dtype, 1)
        )

    shmem.comm_jitter(axis, salt=11)
    shmem.barrier_all(axis)
    right = jax.lax.rem(me + 1, n)

    # Step s, chunk j: produce partial rows of chunk (me-1-s), fused-add
    # the partially-reduced rows that landed from the left during step s-1,
    # forward them right — all at chunk granularity.
    descs = []
    for s in range(n):
        cbase = jax.lax.rem(me - 1 - s + 2 * n, n) * m_loc
        handles = []
        for j, (off, rows) in enumerate(spans):
            sl_a = pl.ds(cbase + off, rows)
            target = (
                out_ref.at[pl.ds(off, rows)] if s == n - 1
                else comp_buf.at[s % 2, pl.ds(off, rows)]
            )
            if 2 <= s < n - 1:
                descs[s - 2].wait_send_chunk(j)  # comp_buf rows free again
            if s == 0:
                gemms[j](a_ref.at[sl_a], b_ref, target)
            else:
                descs[s - 1].wait_recv_chunk(j)  # partial chunk j landed
                gemm_adds[j](
                    a_ref.at[sl_a], b_ref,
                    recv_buf.at[s - 1, pl.ds(off, rows)], target,
                )
            if s < n - 1:
                handles.append(
                    shmem.putmem_signal2_nbi_block(
                        recv_buf.at[s, pl.ds(off, rows)], target, right, axis,
                        send_sems.at[s, j], recv_sems.at[s, j],
                        sig_sems.at[s, j], canary=True,
                    )
                )
        if handles:
            # landing view (ISSUE 8 canary): SPMD symmetry — the left
            # neighbor's step-s partial lands in OUR recv_buf[s] at the
            # same span coordinates this put addressed on the right
            descs.append(shmem.ChunkedPutHandle(
                handles,
                recv_at=lambda off, rows, s=s: recv_buf.at[
                    s, pl.ds(off, rows)
                ],
                spans=spans,
            ))
    shmem.quiet(*descs)


def _gemm_rs_2d(a, b, *, axes, method, cfg, out_dtype, interpret):
    """Hierarchical GEMM-RS over two mesh axes ``(outer, inner)``
    (≙ the reference's producer GEMM + 2-D reduce-scatter pipeline,
    reduce_scatter.py:525-637): the fused GEMM-RS runs over the fast `inner`
    axis with A's chunk layout transposed so inner peer i ends up owning
    slab ``S_i = concat_o'(chunk (o', i))`` of the product, already
    inner-reduced; a standalone reduce-scatter then finishes over `outer`.
    Every byte crosses the slow axis once, n_i-fold pre-reduced."""
    from triton_dist_tpu.ops.reduce_scatter import reduce_scatter

    outer, inner = axes
    n_o = int(jax.lax.axis_size(outer))
    n_i = int(jax.lax.axis_size(inner))
    if n_o == 1:
        return gemm_rs(a, b, axis=inner, method=method, config=cfg,
                       out_dtype=out_dtype, interpret=interpret)
    if n_i == 1:
        return gemm_rs(a, b, axis=outer, method=method, config=cfg,
                       out_dtype=out_dtype, interpret=interpret)
    m_tot, k_loc = a.shape
    n = n_o * n_i
    assert m_tot % n == 0, (m_tot, n)
    m_loc = m_tot // n
    a_perm = a.reshape(n_o, n_i, m_loc, k_loc).swapaxes(0, 1).reshape(m_tot, k_loc)
    part = gemm_rs(
        a_perm, b, axis=inner, method=method, config=cfg,
        out_dtype=out_dtype, interpret=interpret,
    )  # [n_o*m_loc, N] = S_me_i's product, summed over the inner group
    # gemm_rs and the standalone reduce_scatter use different method
    # vocabularies ("scatter" vs "scatter_reduce")
    rs_method = {"scatter": "scatter_reduce"}.get(method, method)
    return reduce_scatter(part, axis=outer, method=rs_method, interpret=interpret)


def gemm_rs(
    a: jax.Array,
    b: jax.Array,
    *,
    axis: str = "tp",
    method: str = "auto",
    config: GemmRSConfig | None = None,
    out_dtype: Any = None,
    interpret: Any = None,
    devices: Any = None,
) -> jax.Array:
    """Overlapped ``psum_scatter(a @ b)`` (call inside ``jax.shard_map``).

    a: ``[M, k_loc]`` — K-sharded activations on this PE (M = n * m_loc).
    b: ``[k_loc, N]`` — K-shard of the weight (row-parallel).
    Returns ``[m_loc, N]`` — this PE's M-chunk of the fully-reduced product.
    Golden: ``jax.lax.psum_scatter(a @ b, axis, tiled=True)``
    (≙ ``gemm_rs_op``, reference gemm_reduce_scatter.py:498) — served
    automatically when the fused kernel cannot run in this environment
    (resilience layer, docs/resilience.md).
    """
    return resilience.guarded_call(
        "gemm_rs",
        _gemm_rs_fused,
        _gemm_rs_xla,
        a, b, axis=axis, method=method, config=config, out_dtype=out_dtype,
        interpret=interpret, devices=devices,
    )


def _gemm_rs_fused(
    a: jax.Array,
    b: jax.Array,
    *,
    axis: str = "tp",
    method: str = "auto",
    config: GemmRSConfig | None = None,
    out_dtype: Any = None,
    interpret: Any = None,
    devices: Any = None,
) -> jax.Array:
    cfg = config or GemmRSConfig()
    out_dtype = out_dtype or a.dtype
    from triton_dist_tpu.parallel.topology import is_dcn_axis_name as _is_dcn

    if isinstance(axis, (tuple, list)):
        if len(axis) == 1:
            axis = axis[0]
        else:
            assert len(axis) == 2, f"at most 2 axes supported, got {axis}"
            outer_ax, inner_ax = axis
            if _is_dcn(inner_ax) and not _is_dcn(outer_ax):
                # Tuple (ici, dcn): transport order and tuple order agree
                # for free — a's outer-major block layout already groups
                # each ICI slab's blocks contiguously, so the fused ICI
                # GEMM-RS runs DIRECTLY (no swizzle; the dcn-OUTER case
                # below is the one needing the inner-major re-grouping),
                # pre-reducing every byte before the DCN hop's XLA
                # psum-scatter.
                from triton_dist_tpu.ops.reduce_scatter import reduce_scatter

                part = gemm_rs(
                    a, b, axis=outer_ax, method=method, config=config,
                    out_dtype=out_dtype, interpret=interpret,
                )
                return reduce_scatter(part, axis=inner_ax, interpret=interpret)
            if _is_dcn(outer_ax):
                # a slice-crossing axis (either position): fused GEMM-RS on
                # the inner hop first (pre-reducing every byte n_i-fold
                # before the outer hop), then a reduce-scatter on the outer
                # hop — both recursive calls route per-axis, so a DCN hop
                # lowers to XLA's psum-scatter and an ICI hop keeps the
                # fused kernels (≙ the reference's inter-node P2P stage
                # after the intra-node RS pipeline,
                # reduce_scatter.py:525-560). Row layout: chunk (o, i) must
                # end at outer-rank o, inner-rank i — the inner RS keeps
                # rows [i*n_o*m + o*m, ...), so pre-swizzle a to slab-major
                # (i, o) order as the N-D reduce_scatter does.
                from triton_dist_tpu.ops.reduce_scatter import reduce_scatter

                n_o = int(jax.lax.axis_size(outer_ax))
                n_i = int(jax.lax.axis_size(inner_ax))
                m_tot0 = a.shape[0]
                m0 = m_tot0 // (n_o * n_i)
                at = (
                    a.reshape(n_o, n_i, m0, a.shape[1])
                    .swapaxes(0, 1)
                    .reshape(m_tot0, a.shape[1])
                )
                part = gemm_rs(
                    at, b, axis=inner_ax, method=method, config=config,
                    out_dtype=out_dtype, interpret=interpret,
                )  # [n_o*m0, N] pre-reduced over the inner axis
                return reduce_scatter(part, axis=outer_ax, interpret=interpret)
            return _gemm_rs_2d(
                a, b, axes=tuple(axis), method=method, cfg=cfg,
                out_dtype=out_dtype, interpret=interpret,
            )
    n = _axis_size(axis)
    m_tot, k_loc = a.shape
    n_dim = b.shape[1]
    if n > 1 and _is_dcn(axis):
        # a purely-DCN axis: no ICI for the fused producer — XLA's
        # dot + psum-scatter owns the DCN transport
        return jax.lax.psum_scatter(
            jnp.dot(a, b, preferred_element_type=out_dtype), axis, tiled=True
        )
    if cfg.block_m == 0:
        if n != 1:
            raise ValueError("GemmRSConfig(block_m=0) (XLA dot) is world-1 only")
        return jnp.dot(a, b, preferred_element_type=out_dtype)
    if n == 1:
        # World-1 is a plain matmul; run it through the same tuned MXU
        # pipeline the fused kernels use (beats the XLA dot at bench shapes).
        return gemm_only(
            a, b, cfg=cfg, out_dtype=out_dtype, name="gemm_rs", interpret=interpret
        )
    assert m_tot % n == 0, (m_tot, n)
    m_loc = m_tot // n
    if method == "auto":
        method = get_auto_reduce_scatter_method(
            m_loc * n_dim * jnp.dtype(out_dtype).itemsize, n, devices
        )
    # accept the standalone reduce-scatter's method name as an alias
    method = {"scatter_reduce": "scatter"}.get(method, method)
    if method not in ("scatter", "ring"):
        raise ValueError(f"unknown gemm_rs method: {method!r} (want scatter|ring)")
    n_steps = n - 1
    sem_shapes = [
        pltpu.SemaphoreType.DMA((n_steps,)),
        pltpu.SemaphoreType.DMA((n_steps,)),
    ]
    blocks = functools.partial(
        gemm_tile, cfg, m_loc, n_dim, k_loc, in_dtype=a.dtype,
        out_dtype=out_dtype,
    )
    if method == "scatter":
        # (the scatter method's puts are single-hop — chunking buys no
        # cross-hop pipelining there)
        tile, tile_own = blocks(n_adds=0), blocks(n_adds=n_steps)
        kern = functools.partial(
            _gemm_rs_scatter_kernel, axis=axis, n=n, tile=tile,
            tile_own=tile_own, out_dtype=out_dtype,
        )
        accs = [(tile.bm, tile.bn), (tile_own.bm, tile_own.bn)]
        # both accumulators live for the whole kernel, one pipeline at a time
        vmem = max(
            tile.vmem_limit_bytes + 4 * tile_own.bm * tile_own.bn,
            tile_own.vmem_limit_bytes + 4 * tile.bm * tile.bn,
        )
        tag = tile.tag + (tile_own.tag if tile_own[:3] != tile[:3] else "")
    else:
        # one tile for the ring's steps: all but the first fuse one add
        tile = blocks(n_adds=1)
        spans, chunk_tile = gemm_chunk_spans(cfg, tile, m_loc)
        kern = functools.partial(
            _gemm_rs_ring_kernel, axis=axis, n=n, tile=tile, out_dtype=out_dtype
        )
        acc_bm = tile.bm
        if len(spans) > 1:
            # chunk-granular ring
            kern = functools.partial(
                _gemm_rs_ring_chunked_kernel, axis=axis, n=n, tile=chunk_tile,
                out_dtype=out_dtype, spans=spans,
            )
            acc_bm = max(pick_block(rows, chunk_tile.bm) for _, rows in spans)
            sem_shapes = [
                pltpu.SemaphoreType.DMA((n_steps, len(spans))),
                pltpu.SemaphoreType.DMA((n_steps, len(spans))),
                pltpu.SemaphoreType.REGULAR((n_steps, len(spans))),
            ]
        accs = [(acc_bm, tile.bn)]
        vmem = tile.vmem_limit_bytes
        tag = tile.tag
    outs = dist_pallas_call(
        kern,
        name=f"gemm_rs_{method}",
        trace_tag=tag,
        vmem_limit_bytes=vmem,
        out_shape=(
            jax.ShapeDtypeStruct((m_loc, n_dim), out_dtype),
            # Workspace as outputs: how a kernel gets private HBM, and the
            # interpreter's emit_pipeline only takes kernel-arg HBM refs.
            jax.ShapeDtypeStruct((2, m_loc, n_dim), out_dtype),        # send/comp
            jax.ShapeDtypeStruct((n_steps, m_loc, n_dim), out_dtype),  # landing
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=tuple(pl.BlockSpec(memory_space=pl.ANY) for _ in range(3)),
        scratch_shapes=[
            *(pltpu.VMEM(acc, jnp.float32) for acc in accs),
            *sem_shapes,
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * m_tot * n_dim * k_loc,
            bytes_accessed=(m_tot * k_loc + k_loc * n_dim) * a.dtype.itemsize
            + (m_tot + 3 * n_steps * m_loc) * n_dim * jnp.dtype(out_dtype).itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
    )(a, b)
    return outs[0]


def _gemm_rs_op_xla(
    a: jax.Array,
    b: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "tp",
    **_,
) -> jax.Array:
    """Op-level golden: the same shard_map entry serving XLA's
    dot + psum-scatter."""
    return jit_shard_map(
        functools.partial(_gemm_rs_xla, axis=axis),
        mesh, (P(None, axis), P(axis, None)), P(axis, None),
        key=("gemm_rs_xla", axis),
    )(a, b)


def gemm_rs_op(
    a: jax.Array,
    b: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "tp",
    method: str = "auto",
    config: GemmRSConfig | None = None,
    interpret: Any = None,
) -> jax.Array:
    """Host-level entry (≙ ``gemm_rs_op``, reference
    gemm_reduce_scatter.py:498): `a` sharded on dim 1 (K), `b` sharded on
    dim 0 (K); the reduced result comes back sharded on dim 0 (M)."""
    from triton_dist_tpu.parallel import topology

    if mesh.size == 1 and config is not None and config.block_m == 0:
        # world-1 XLA-dot sentinel: bypass shard_map entirely (see
        # ag_gemm_op)
        return jnp.dot(a, b, preferred_element_type=a.dtype)
    fn = functools.partial(
        gemm_rs, axis=axis, method=method, config=config, interpret=interpret,
        devices=topology.axis_devices(mesh, axis),
    )
    return jit_shard_map(
        fn, mesh, (P(None, axis), P(axis, None)), P(axis, None),
        key=("gemm_rs", axis, method, config, str(interpret)),
    )(a, b)


# ≙ the reference's tune space for gemm_rs (gemm_reduce_scatter.py contexts);
# block_m tiles the per-destination M-chunk, which is M/n — smaller than the
# AG-GEMM tiles for the same problem.
# Only `gemm_rs_op` called WITHOUT a config reads this list (the autotuner's
# sweep of the whole op, docs/autotuner.md). The served path calls `gemm_rs`
# with config=None and never comes here: its tile is `ops.common.gemm_tile`'s
# rule, from the call's shape. Each candidate below is an explicit config
# and runs as written.
# FIRST entry = best-known config (applied sweep-free under
# TDT_AUTOTUNE_POLICY=cached_or_first): the swept winner at the bench
# shape M=8192 K=14336 N=4096.
GEMM_RS_TUNE_SPACE = (
    GemmRSConfig(0, 0, 0),  # world-1 XLA dot (raises → skipped at n>1);
    # measured v5e world-1: XLA 199 TFLOPS vs best Pallas chunking 176 at
    # M=8192 K=14336 N=4096 — this shape's B-panel restreaming favors XLA
    GemmRSConfig(512, 2048, 1024),
    GemmRSConfig(256, 1024, 512),
    GemmRSConfig(512, 1024, 512),
    GemmRSConfig(256, 2048, 512),
    GemmRSConfig(512, 2048, 512),
    GemmRSConfig(1024, 2048, 1024),
    GemmRSConfig(512, 4096, 2048),
    GemmRSConfig(128, 1024, 512),
    # chunks_per_shard axis (ISSUE 3): chunk-granular ring staging over the
    # best-known tiles — after every chunk=1 candidate so the sweep-free
    # walks never apply a chunked schedule untimed (see AG_GEMM_TUNE_SPACE)
    GemmRSConfig(512, 2048, 1024, chunks_per_shard=2),
    GemmRSConfig(512, 2048, 1024, chunks_per_shard=4),
    GemmRSConfig(256, 1024, 512, chunks_per_shard=4),
)

gemm_rs_op = contextual_autotune(GEMM_RS_TUNE_SPACE, name="gemm_rs")(gemm_rs_op)
# guard OUTSIDE the autotuner: the sweep still prices failing candidates;
# only a failure of the whole tuned entry degrades to the XLA golden
gemm_rs_op = resilience.guard_op("gemm_rs_op", _gemm_rs_op_xla)(gemm_rs_op)
