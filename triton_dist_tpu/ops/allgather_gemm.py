"""Fused AllGather-GEMM — the flagship overlapped op
(≙ reference ``kernels/nvidia/allgather_gemm.py``, 748 LoC).

The reference splits the op across CUDA streams: cp-engine producers push
shards into a symmetric workspace while a persistent consumer GEMM kernel
spins per-M-tile on readiness flags (``dl.wait`` + ``dl.consume_token``,
allgather_gemm.py:226-227) with a rank-first tile swizzle (:206-219).

TPU-native re-design: one fused Pallas kernel per PE. The ring transfer of
the next shard rides the ICI DMA engines *while* the MXU multiplies the
current shard through an inner ``emit_pipeline`` (HBM→VMEM double-buffered
matmul). The reference's tile swizzle becomes the ring schedule itself:
step s computes shard ``(me - s) % n``, which is exactly "start at own rank,
walk in ring-arrival order" — compute order equals arrival order, so there
is no wait bubble after the first hop.

    step 0:  compute own shard       | send own shard to right neighbor
    step s:  wait shard (me-s)       | forward it right | MXU on it

Used for TP column-parallel layers: A is sharded on M (tokens), B on N
(features); every PE gets the full gathered A and its N-shard of C.
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
from triton_dist_tpu.shmem import device as shmem
from triton_dist_tpu.utils import pick_block as _pick_block
from triton_dist_tpu.utils import axis_size as _axis_size


@dataclasses.dataclass(frozen=True)
class AGGemmConfig:
    """Tunables (≙ ``AllGatherGEMMTensorParallelContext``,
    reference allgather_gemm.py:407-489 — minus the stream/workspace
    plumbing, which the fused kernel does not need).

    The block fields carry no default tile: left unset (``config=None``,
    which is what every served call passes, or a config that sets
    ``chunks_per_shard`` alone) the tile is ``ops.common.gemm_tile``'s,
    a function of the GEMM's shape and a VMEM budget. Set (all three: the
    autotuner's candidates, a test's tiny tile) they are honoured, each
    shrunk to a divisor of its dimension."""

    block_m: int | None = None
    block_n: int | None = None
    block_k: int | None = None
    # block_m=0: world-1 XLA-native sentinel — dispatch the degenerate
    # no-comm case to jnp.dot (XLA's matmul), a first-class autotune
    # candidate. Non-viable (raises) at n>1, where the fused ring kernel
    # is the whole point.
    # Ring-step payload granularity (ISSUE 3): > 1 splits each shard into
    # that many per-chunk DMAs, the MXU computing on chunk j while chunk
    # j+1 is in flight; 1 reproduces the legacy shard-granular schedule
    # bit for bit (the tuner's no-regression anchor).
    chunks_per_shard: int = 1


def _ag_gemm_kernel(
    a_ref, b_ref, out_ref, ag_ref, acc_ref, copy_sem, send_sems, recv_sems,
    *, axis: str, n: int, tile: GemmTile, out_dtype,
):
    me = shmem.my_pe(axis)
    m_loc, k_dim = a_ref.shape
    n_loc = b_ref.shape[1]
    bm, bn, bk = tile[:3]

    local = pltpu.make_async_copy(a_ref, ag_ref.at[pl.ds(me * m_loc, m_loc)], copy_sem)
    local.start()
    local.wait()
    # race shaking (no-op unless config.debug_comm_delay)
    shmem.comm_jitter(axis, salt=8)
    shmem.barrier_all(axis)

    right = jax.lax.rem(me + 1, n)
    pipeline = gemm_add_pipeline(bm, bn, bk, m_loc, n_loc, k_dim, acc_ref, out_dtype)

    descs = []
    for s in range(n):
        c = jax.lax.rem(me - s + 2 * n, n)
        if s > 0:
            descs[s - 1].wait_recv()  # shard c landed during step s-1
        sl = pl.ds(c * m_loc, m_loc)
        if s < n - 1:
            # Forward shard c around the ring *before* computing on it: the
            # ICI transfer overlaps the MXU work below (≙ producer stream).
            descs.append(
                shmem.putmem_nbi_block(
                    ag_ref.at[sl], ag_ref.at[sl], right, axis,
                    send_sems.at[s], recv_sems.at[s],
                )
            )
        pipeline(ag_ref.at[sl], b_ref, out_ref.at[sl])
    shmem.quiet(*descs)


def _ag_gemm_chunked_kernel(
    a_ref, b_ref, out_ref, ag_ref, acc_ref, copy_sem, send_sems, recv_sems,
    sig_sems, *, axis: str, n: int, tile: GemmTile, out_dtype, spans,
):
    """Chunk-granular fused AG-GEMM (ISSUE 3 tentpole): step ``s`` waits,
    forwards, and COMPUTES shard ``me-s`` chunk by chunk — the MXU runs on
    chunk ``j`` while chunk ``j+1`` is still crossing the ICI, restoring the
    reference's per-M-tile progress (``dl.wait``/``dl.consume_token``,
    allgather_gemm.py:226-227) that the shard-granular port collapsed.
    chunk=1 dispatches to :func:`_ag_gemm_kernel` (bit-identical legacy)."""
    me = shmem.my_pe(axis)
    m_loc, k_dim = a_ref.shape
    n_loc = b_ref.shape[1]
    bn, bk = tile.bn, tile.bk
    # one pipeline per distinct chunk row-count (non-divisor spans differ by
    # one row); the f32 accumulator scratch is sized for the largest chunk
    # tile and sliced only for the smaller ones
    bms = [_pick_block(rows, tile.bm) for _, rows in spans]
    bm_max = max(bms)
    pipes = []
    for (_, rows), bm_j in zip(spans, bms):
        acc_j = acc_ref if bm_j == bm_max else acc_ref.at[pl.ds(0, bm_j), :]
        pipes.append(
            gemm_add_pipeline(bm_j, bn, bk, rows, n_loc, k_dim, acc_j, out_dtype)
        )

    local = pltpu.make_async_copy(a_ref, ag_ref.at[pl.ds(me * m_loc, m_loc)], copy_sem)
    local.start()
    local.wait()
    shmem.comm_jitter(axis, salt=8)
    shmem.barrier_all(axis)

    right = jax.lax.rem(me + 1, n)
    descs = []
    for s in range(n):
        c = jax.lax.rem(me - s + 2 * n, n)
        base = c * m_loc
        # the put issued at step s is consumed at step s+1, when the left
        # neighbor's step-s send — shard (me-1-s) mod n — has landed: that
        # shard's rows are the landing view (ISSUE 8 canary; the same
        # arithmetic as the chunked ring allgather's base_in)
        base_in = jax.lax.rem(me - 1 - s + 2 * n, n) * m_loc
        handles = []
        for j, (off, rows) in enumerate(spans):
            if s > 0:
                descs[s - 1].wait_recv_chunk(j)  # chunk j of shard c landed
            sl = pl.ds(base + off, rows)
            if s < n - 1:
                # forward chunk j before computing on it: its ICI hop rides
                # under this chunk's (and later chunks') MXU work
                handles.append(
                    shmem.putmem_signal2_nbi_block(
                        ag_ref.at[sl], ag_ref.at[sl], right, axis,
                        send_sems.at[s, j], recv_sems.at[s, j],
                        sig_sems.at[s, j], canary=True,
                    )
                )
            pipes[j](ag_ref.at[sl], b_ref, out_ref.at[sl])
        if handles:
            descs.append(shmem.ChunkedPutHandle(
                handles,
                recv_at=lambda off, rows, b=base_in: ag_ref.at[
                    pl.ds(b + off, rows)
                ],
                spans=spans,
            ))
    shmem.quiet(*descs)


def _ag_gemm_2d_kernel(
    a_ref, b_ref, out_ref, ag_ref, acc_ref, copy_sem, in_send, in_recv,
    out_send, out_recv, *, outer: str, inner: str, n_o: int, n_i: int,
    tile: GemmTile, out_dtype,
):
    """Fused hierarchical AG-GEMM over two mesh axes: the 2-D ring allgather
    (see ops/allgather._ring_2d_kernel) with an MXU pipeline consuming every
    chunk the moment it is locally available — compute order = 2-D arrival
    order, the multi-axis generalization of the 1-D swizzle (≙ the
    reference's node-shifted tile swizzle, allgather_gemm.py:206-219)."""
    me_i = shmem.my_pe(inner)
    me_o = shmem.my_pe(outer)
    m_loc, k_dim = a_ref.shape
    n_loc = b_ref.shape[1]
    pipeline = gemm_add_pipeline(
        *tile[:3], m_loc, n_loc, k_dim, acc_ref, out_dtype
    )

    def slot(o, i):
        return pl.ds((o * n_i + i) * m_loc, m_loc)

    local = pltpu.make_async_copy(a_ref, ag_ref.at[slot(me_o, me_i)], copy_sem)
    local.start()
    local.wait()
    shmem.comm_jitter((outer, inner), salt=9)
    shmem.barrier_all((outer, inner))

    right_i = jax.lax.rem(me_i + 1, n_i)
    down_o = jax.lax.rem(me_o + 1, n_o)
    descs_i = []
    descs_o = [[None] * n_i for _ in range(n_o - 1)]

    for s in range(n_i):
        c = jax.lax.rem(me_i - s + n_i, n_i)
        if s > 0:
            descs_i[s - 1].wait_recv()
        sl = slot(me_o, c)
        if s < n_i - 1:
            descs_i.append(
                shmem.putmem_nbi_block(
                    ag_ref.at[sl], ag_ref.at[sl], right_i, inner,
                    in_send.at[s], in_recv.at[s],
                )
            )
        if n_o > 1:
            descs_o[0][s] = shmem.putmem_nbi_block(
                ag_ref.at[sl], ag_ref.at[sl], down_o, outer,
                out_send.at[0, s], out_recv.at[0, s],
            )
        # both forwards are in flight: the MXU overlaps them
        pipeline(ag_ref.at[sl], b_ref, out_ref.at[sl])

    for t in range(1, n_o):
        row = jax.lax.rem(me_o - t + n_o, n_o)
        for s in range(n_i):
            c = jax.lax.rem(me_i - s + n_i, n_i)
            descs_o[t - 1][s].wait_recv()
            sl = slot(row, c)
            if t < n_o - 1:
                descs_o[t][s] = shmem.putmem_nbi_block(
                    ag_ref.at[sl], ag_ref.at[sl], down_o, outer,
                    out_send.at[t, s], out_recv.at[t, s],
                )
            pipeline(ag_ref.at[sl], b_ref, out_ref.at[sl])
    shmem.quiet(*descs_i, *(d for row_d in descs_o for d in row_d if d is not None))


def _ag_gemm_2d(a, b, *, axes, cfg, gather_output, out_dtype, interpret):
    outer, inner = axes
    n_o = int(jax.lax.axis_size(outer))
    n_i = int(jax.lax.axis_size(inner))
    n = n_o * n_i
    m_loc, k_dim = a.shape
    n_loc = b.shape[1]
    tile = gemm_tile(
        cfg, m_loc, n_loc, k_dim, in_dtype=a.dtype, out_dtype=out_dtype
    )
    out, ag = dist_pallas_call(
        functools.partial(
            _ag_gemm_2d_kernel, outer=outer, inner=inner, n_o=n_o, n_i=n_i,
            tile=tile, out_dtype=out_dtype,
        ),
        name="ag_gemm_2d",
        trace_tag=tile.tag,
        vmem_limit_bytes=tile.vmem_limit_bytes,
        out_shape=(
            jax.ShapeDtypeStruct((n * m_loc, n_loc), out_dtype),
            jax.ShapeDtypeStruct((n * m_loc, k_dim), a.dtype),
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ),
        scratch_shapes=[
            pltpu.VMEM((tile.bm, tile.bn), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((max(n_i - 1, 1),)),
            pltpu.SemaphoreType.DMA((max(n_i - 1, 1),)),
            pltpu.SemaphoreType.DMA((max(n_o - 1, 1), n_i)),
            pltpu.SemaphoreType.DMA((max(n_o - 1, 1), n_i)),
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * n * m_loc * n_loc * k_dim,
            bytes_accessed=(n * m_loc * k_dim + k_dim * n_loc + n * m_loc * n_loc) * a.dtype.itemsize,
            transcendentals=0,
        ),
        uses_barrier=True,
        interpret=interpret,
    )(a, b)
    return (out, ag) if gather_output else out


def _ag_gemm_xla(
    a: jax.Array, b: jax.Array, *, axis="tp", gather_output=False,
    out_dtype=None, **_
):
    """The golden slow path (the program every fused method is tested
    against): XLA's all-gather + dot, single- or multi-axis."""
    axes = tuple(axis) if isinstance(axis, (tuple, list)) else axis
    out_dtype = out_dtype or a.dtype
    ag = jax.lax.all_gather(a, axes, axis=0, tiled=True)
    out = jnp.dot(ag, b, preferred_element_type=out_dtype)
    return (out, ag) if gather_output else out


def ag_gemm(
    a: jax.Array,
    b: jax.Array,
    *,
    axis: str = "tp",
    config: AGGemmConfig | None = None,
    gather_output: bool = False,
    out_dtype: Any = None,
    interpret: Any = None,
):
    """Overlapped ``all_gather(a) @ b`` (call inside ``jax.shard_map``).

    a: ``[m_loc, K]`` — M-sharded activations on this PE.
    b: ``[K, n_loc]`` — N-shard of the weight (column-parallel).
    Returns ``[n*m_loc, n_loc]`` (plus the gathered ``[n*m_loc, K]`` A if
    `gather_output`, ≙ the reference returning its AG workspace for reuse).
    Golden: ``jax.lax.all_gather(a, axis, tiled=True) @ b`` — served
    automatically when the fused kernel cannot run in this environment
    (resilience layer, docs/resilience.md; the same guard every other op
    family carries).
    """
    from triton_dist_tpu import resilience

    return resilience.guarded_call(
        "ag_gemm",
        _ag_gemm_fused,
        _ag_gemm_xla,
        a, b, axis=axis, config=config, gather_output=gather_output,
        out_dtype=out_dtype, interpret=interpret,
    )


def _ag_gemm_fused(
    a: jax.Array,
    b: jax.Array,
    *,
    axis: str = "tp",
    config: AGGemmConfig | None = None,
    gather_output: bool = False,
    out_dtype: Any = None,
    interpret: Any = None,
):
    cfg = config or AGGemmConfig()
    out_dtype = out_dtype or a.dtype
    if cfg.block_m == 0:
        # before the 2-D dispatch: _pick_block(m, 0) would ZeroDivide there
        names = axis if isinstance(axis, (tuple, list)) else (axis,)
        n_tot = 1
        for ax in names:
            n_tot *= int(jax.lax.axis_size(ax))
        if n_tot != 1:
            raise ValueError("AGGemmConfig(block_m=0) (XLA dot) is world-1 only")
        out = jnp.dot(a, b, preferred_element_type=out_dtype)
        return (out, a) if gather_output else out
    from triton_dist_tpu.parallel.topology import is_dcn_axis_name as _is_dcn

    if isinstance(axis, (tuple, list)):
        if len(axis) == 1:
            axis = axis[0]
        else:
            assert len(axis) == 2, f"at most 2 axes supported, got {axis}"
            outer_ax, inner_ax = axis
            if _is_dcn(inner_ax) and not _is_dcn(outer_ax):
                # DCN in the INNER slot: composition order must follow the
                # TRANSPORT (fused compute on ICI, outputs shared across
                # DCN), not the tuple order — otherwise the single-axis
                # DCN fallback would gather A across DCN and n_dcn-plicate
                # the FLOPs. AG over (a0, a1) is AG over (a1, a0) with the
                # result's (n_i, n_o) block grid transposed, so route
                # through the efficient DCN-outer branch and fix the row
                # order locally.
                n_o = int(jax.lax.axis_size(outer_ax))
                n_i = int(jax.lax.axis_size(inner_ax))

                def _swap(y):
                    blk = y.shape[0] // (n_o * n_i)
                    return (
                        y.reshape(n_i, n_o, blk, *y.shape[1:])
                        .swapaxes(0, 1)
                        .reshape(y.shape)
                    )

                res = ag_gemm(
                    a, b, axis=(inner_ax, outer_ax), config=config,
                    gather_output=gather_output, out_dtype=out_dtype,
                    interpret=interpret,
                )
                if gather_output:
                    return _swap(res[0]), _swap(res[1])
                return _swap(res)
            if _is_dcn(outer_ax):
                # slice-crossing outer axis: keep the fused ring on the
                # ICI inner axis and gather COMPUTED OUTPUT rows across
                # DCN — each group computes its own rows once (vs
                # gathering A, which would n_o-plicate the FLOPs; ≙ the
                # reference's 2-D internode AG staging its cross-node hop
                # separately, allgather.py:291-375). Both recursive calls
                # route per-axis (a both-DCN tuple lowers everything to
                # XLA).
                from triton_dist_tpu.ops.allgather import all_gather

                res = ag_gemm(
                    a, b, axis=inner_ax, config=config,
                    gather_output=gather_output, out_dtype=out_dtype,
                    interpret=interpret,
                )
                y, ag = res if gather_output else (res, None)
                out = all_gather(y, axis=outer_ax, interpret=interpret)
                if gather_output:
                    return out, all_gather(ag, axis=outer_ax, interpret=interpret)
                return out
            return _ag_gemm_2d(
                a, b, axes=tuple(axis), cfg=cfg, gather_output=gather_output,
                out_dtype=out_dtype, interpret=interpret,
            )
    n = _axis_size(axis)
    m_loc, k_dim = a.shape
    n_loc = b.shape[1]
    if n > 1 and _is_dcn(axis):
        # a purely-DCN TP axis: no ICI for the fused ring — lower to XLA's
        # all-gather + dot and let its scheduler overlap the DCN transfer
        ag = jax.lax.all_gather(a, axis, tiled=True)
        out = jnp.dot(ag, b, preferred_element_type=out_dtype)
        return (out, ag) if gather_output else out
    tile = gemm_tile(
        cfg, m_loc, n_loc, k_dim, in_dtype=a.dtype, out_dtype=out_dtype
    )
    # lane-aligned column blocks are what the rule always wants of a column
    # count past one lane, and what an explicit config names
    lanes = n_loc > 128 if cfg.block_n is None else (
        cfg.block_n % 128 == 0 and tile.bn != n_loc
    )
    if lanes and tile.bn % 128 and n_loc % 512:
        # ... but no divisor of the column count is a multiple of 128
        # (the rule then holds the whole dimension; an explicit block fell
        # below a lane by halving): Mosaic refuses a proper slice that is
        # not a multiple of 128 ("Slice shape along dimension 1 must be
        # aligned to tiling (128)" — Llama-3's vocab shard at TP=4,
        # 32064 = 64 x 501). Pad B's columns to the next multiple of 512
        # — one weight-shard copy per call, <2% extra MXU work — and slice
        # the product back; the padded call's tile is the rule's again.
        pad = -n_loc % 512
        res = _ag_gemm_fused(
            a, jnp.pad(b, ((0, 0), (0, pad))), axis=axis, config=config,
            gather_output=gather_output, out_dtype=out_dtype,
            interpret=interpret,
        )
        if gather_output:
            return res[0][:, :n_loc], res[1]
        return res[:, :n_loc]
    if n == 1:
        # World-1 degenerates to a plain MXU matmul: routing A through the
        # gather workspace would cost an extra HBM round-trip of the whole
        # activation (measured ~3% at the M=8192 bench shape) for nothing.
        out = gemm_only(
            a, b, cfg=cfg, out_dtype=out_dtype, name="ag_gemm", interpret=interpret
        )
        return (out, a) if gather_output else out
    spans, chunk_tile = gemm_chunk_spans(cfg, tile, m_loc)
    n_steps = max(n - 1, 1)
    if len(spans) > 1:
        kernel = functools.partial(
            _ag_gemm_chunked_kernel, axis=axis, n=n, tile=chunk_tile,
            out_dtype=out_dtype, spans=spans,
        )
        bm_acc = max(_pick_block(rows, chunk_tile.bm) for _, rows in spans)
        sem_shapes = [
            pltpu.SemaphoreType.DMA((n_steps, len(spans))),
            pltpu.SemaphoreType.DMA((n_steps, len(spans))),
            pltpu.SemaphoreType.REGULAR((n_steps, len(spans))),
        ]
    else:
        kernel = functools.partial(
            _ag_gemm_kernel, axis=axis, n=n, tile=tile, out_dtype=out_dtype
        )
        bm_acc = tile.bm
        sem_shapes = [
            pltpu.SemaphoreType.DMA((n_steps,)),
            pltpu.SemaphoreType.DMA((n_steps,)),
        ]
    out, ag = dist_pallas_call(
        kernel,
        name="ag_gemm",
        trace_tag=tile.tag,
        vmem_limit_bytes=tile.vmem_limit_bytes,
        out_shape=(
            jax.ShapeDtypeStruct((n * m_loc, n_loc), out_dtype),
            jax.ShapeDtypeStruct((n * m_loc, k_dim), a.dtype),
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ),
        scratch_shapes=[
            pltpu.VMEM((bm_acc, tile.bn), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
            *sem_shapes,
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * n * m_loc * n_loc * k_dim,
            bytes_accessed=(n * m_loc * k_dim + k_dim * n_loc + n * m_loc * n_loc) * a.dtype.itemsize,
            transcendentals=0,
        ),
        uses_barrier=n > 1,
        interpret=interpret,
    )(a, b)
    return (out, ag) if gather_output else out


def ag_gemm_op(
    a: jax.Array,
    b: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "tp",
    config: AGGemmConfig | None = None,
    interpret: Any = None,
) -> jax.Array:
    """Host-level entry (≙ ``ag_gemm``, reference allgather_gemm.py:539):
    `a` sharded on dim 0, `b` sharded on dim 1, result replicated on M and
    sharded on N."""
    if mesh.size == 1 and config is not None and config.block_m == 0:
        # world-1 XLA-dot sentinel: no SPMD machinery at all — the fused
        # entry IS the best XLA program, with zero wrapper overhead
        return jnp.dot(a, b, preferred_element_type=a.dtype)
    fn = functools.partial(ag_gemm, axis=axis, config=config, interpret=interpret)
    return jit_shard_map(
        fn, mesh, (P(axis, None), P(None, axis)), P(None, axis),
        key=("ag_gemm", axis, config, str(interpret)),
    )(a, b)


# Candidate space for the contextual autotuner (≙ the reference's
# triton.Config spaces, allgather_gemm.py:386-404). Swept per input
# signature the first time `ag_gemm_op` is called without an explicit
# config; `pick_block` shrinks oversized tiles, so large-tile candidates
# degrade gracefully on small shards. (The served path calls `ag_gemm`
# with config=None and never reads this list: its tile is
# `ops.common.gemm_tile`'s rule. Each candidate here is an explicit config
# and runs as written.) Candidate ORDER is preference order
# (the sweep's order-margin walk and the first-viable policy both honor
# it): the world-1 XLA-dot sentinel leads — honest paired timing on v5e
# showed XLA's matmul at parity-or-better with the best Pallas chunking
# at the M=8192 bench shape (~188-190 TFLOPS; an earlier 199-vs-188
# reading predated full-output consumption and was DCE-inflated) — and
# (1024, 2048, 1024) is the best-known ring-kernel config at n>1.
AG_GEMM_TUNE_SPACE = (
    # world-1 XLA-dot sentinel LEADS (raises → skipped at n>1, where the
    # cached_or_first policy falls through to the ring kernel below)
    AGGemmConfig(0, 0, 0),
    AGGemmConfig(1024, 2048, 1024),
    AGGemmConfig(512, 2048, 512),
    AGGemmConfig(512, 2048, 1024),
    AGGemmConfig(512, 2048, 2048),
    AGGemmConfig(512, 1024, 512),
    AGGemmConfig(256, 1024, 512),
    # chunks_per_shard axis (ISSUE 3): chunk-granular ring overlap over the
    # best-known tiles. Listed AFTER every chunk=1 candidate, so the
    # sweep-free walks (cached_or_first / interpreter) can never pick a
    # chunked schedule untimed, and a sweep only crowns one that beats the
    # legacy leader by the paired-confirmation margin — the tuner cannot
    # regress below today's schedule by construction.
    AGGemmConfig(1024, 2048, 1024, chunks_per_shard=2),
    AGGemmConfig(1024, 2048, 1024, chunks_per_shard=4),
    AGGemmConfig(512, 2048, 512, chunks_per_shard=4),
    AGGemmConfig(512, 2048, 1024, chunks_per_shard=8),
)

ag_gemm_op = contextual_autotune(AG_GEMM_TUNE_SPACE, name="ag_gemm")(ag_gemm_op)
