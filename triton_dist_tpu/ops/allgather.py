"""AllGather kernel family (≙ reference ``kernels/nvidia/allgather.py``, 591 LoC).

The reference ships cp-engine push/pull, 1-D ring, NUMA-aware 2-D ring, and
inter-node variants, selected by ``get_auto_all_gather_method``
(allgather.py:44-69). The TPU-native set:

- ``ring_1d``        — unidirectional neighbor ring over ICI (≙ ring push
                       :138); bandwidth-optimal for ≥2 chips, n-1 hops.
- ``ring_bidir``     — bidirectional ring: both ICI directions carry
                       traffic, halving latency (the TPU analogue of the
                       reference's 2-D NUMA ring :194 — both exist to use
                       more links simultaneously).
- ``full_mesh_push`` — every PE puts its shard directly to every peer
                       (≙ full-mesh push :79). On TPU non-neighbor RDMA is
                       hardware-routed; best for small latency-bound sizes.

Pull variants (:104) are impossible on TPU (no remote loads — see
``shmem.device.getmem_nbi_block``) and are covered by push symmetry.
All kernels are HBM-resident: chunks move HBM→HBM over ICI without staging
through VMEM, so arbitrarily large gathers work.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu import resilience
from triton_dist_tpu.ops.common import chunk_schedule, dist_pallas_call, jit_shard_map
from triton_dist_tpu.parallel import topology
from triton_dist_tpu.shmem import device as shmem
from triton_dist_tpu.utils import axis_size as _axis_size


def _all_gather_xla(x: jax.Array, *, axis="tp", **_) -> jax.Array:
    """The golden slow path (the same program every fused method is tested
    against): XLA's all-gather, single- or multi-axis."""
    axes = tuple(axis) if isinstance(axis, (tuple, list)) else axis
    return jax.lax.all_gather(x, axes, tiled=True)


def _is_dcn(axis) -> bool:
    """Whether this mesh axis crosses TPU slice boundaries (DCN, not ICI):
    declared via ``config.dcn_axes`` or auto-detected at mesh creation."""
    return topology.is_dcn_axis_name(axis)


def get_auto_all_gather_method(
    chunk_bytes: int, n_pes: int, devices: Any = None
) -> str:
    """Topology/size-based method choice (≙ ``get_auto_all_gather_method``,
    reference allgather.py:44-69, which keys on NVLink-fullmesh/NUMA).
    `devices` — the mesh-axis devices (``topology.axis_devices``) — enables
    physical wrap detection from their torus coords."""
    from triton_dist_tpu.perf_model import direct_vs_ring_crossover_bytes

    if n_pes <= 2:
        return "ring_1d"
    if not topology.has_wraparound(n_pes, devices):
        # a line topology: a ring's wrap hop would route the long way
        return "full_mesh_push"
    # model-driven crossover (ring SOL vs routed-put SOL; tracks ICI BW)
    if chunk_bytes <= direct_vs_ring_crossover_bytes(n_pes):
        return "full_mesh_push"
    return "ring_bidir"


def _ring_1d_kernel(x_ref, out_ref, copy_sem, send_sems, recv_sems, *, axis: str, n: int):
    me = shmem.my_pe(axis)
    m = x_ref.shape[0]
    # Local shard into its slot, then barrier so every PE's out buffer is
    # live before remote writes land (≙ local_copy_and_barrier_all,
    # reference allgather_gemm.py:100-116).
    local = pltpu.make_async_copy(x_ref, out_ref.at[pl.ds(me * m, m)], copy_sem)
    local.start()
    local.wait()
    # race shaking (no-op unless config.debug_comm_delay): per-PE skew of
    # barrier entry + DMA issue
    shmem.comm_jitter(axis, salt=1)
    shmem.barrier_all(axis)
    right = jax.lax.rem(me + 1, n)
    descs = []
    for s in range(n - 1):
        c = jax.lax.rem(me - s + n, n)
        if s > 0:
            descs[s - 1].wait_recv()  # chunk c arrived during step s-1
        sl = pl.ds(c * m, m)
        descs.append(
            shmem.putmem_nbi_block(
                out_ref.at[sl], out_ref.at[sl], right, axis, send_sems.at[s], recv_sems.at[s]
            )
        )
    descs[-1].wait_recv()
    shmem.quiet(*descs)


def _ring_bidir_kernel(
    x_ref, out_ref, copy_sem, send_r, recv_r, send_l, recv_l, *, axis: str, n: int
):
    me = shmem.my_pe(axis)
    m = x_ref.shape[0]
    local = pltpu.make_async_copy(x_ref, out_ref.at[pl.ds(me * m, m)], copy_sem)
    local.start()
    local.wait()
    shmem.comm_jitter(axis, salt=2)
    shmem.barrier_all(axis)
    right = jax.lax.rem(me + 1, n)
    left = jax.lax.rem(me - 1 + n, n)
    steps_r = (n - 1 + 1) // 2  # chunks travelling rightward
    steps_l = (n - 1) // 2      # chunks travelling leftward
    descs_r, descs_l = [], []
    for s in range(max(steps_r, steps_l)):
        if s < steps_r:
            c = jax.lax.rem(me - s + n, n)
            if s > 0:
                descs_r[s - 1].wait_recv()
            sl = pl.ds(c * m, m)
            descs_r.append(
                shmem.putmem_nbi_block(
                    out_ref.at[sl], out_ref.at[sl], right, axis, send_r.at[s], recv_r.at[s]
                )
            )
        if s < steps_l:
            c = jax.lax.rem(me + s, n)
            if s > 0:
                descs_l[s - 1].wait_recv()
            sl = pl.ds(c * m, m)
            descs_l.append(
                shmem.putmem_nbi_block(
                    out_ref.at[sl], out_ref.at[sl], left, axis, send_l.at[s], recv_l.at[s]
                )
            )
    descs_r[-1].wait_recv()
    if descs_l:
        descs_l[-1].wait_recv()
    shmem.quiet(*descs_r, *descs_l)


def _ring_1d_chunked_kernel(
    x_ref, out_ref, copy_sem, send_sems, recv_sems, sig_sems,
    *, axis: str, n: int, spans,
):
    """Chunk-granular 1-D ring (ISSUE 3 tentpole): each ring-step shard is
    `len(spans)` independent chunk DMAs, and step ``s`` forwards chunk ``j``
    the moment chunk ``j`` of step ``s-1`` lands — so the per-hop exposed
    latency is one *chunk*, not one shard (wormhole pipelining; the chunk=1
    schedule is exactly :func:`_ring_1d_kernel` and is dispatched there)."""
    me = shmem.my_pe(axis)
    m = x_ref.shape[0]
    local = pltpu.make_async_copy(x_ref, out_ref.at[pl.ds(me * m, m)], copy_sem)
    local.start()
    local.wait()
    shmem.comm_jitter(axis, salt=1)
    shmem.barrier_all(axis)
    right = jax.lax.rem(me + 1, n)
    descs = []
    for s in range(n - 1):
        c = jax.lax.rem(me - s + n, n)
        base = c * m
        # step s's INCOMING chunk is the left neighbor's send: shard
        # (me-1-s) mod n — the landing view for payload integrity
        # (canary checksums + payload-fault injection, ISSUE 8)
        base_in = jax.lax.rem(me - 1 - s + 2 * n, n) * m
        ready = None
        if s > 0:
            prev = descs[s - 1]
            ready = prev.wait_recv_chunk  # chunk j arrived during step s-1
        descs.append(
            shmem.putmem_signal_chunked_nbi_block(
                lambda off, rows, base=base: out_ref.at[pl.ds(base + off, rows)],
                lambda off, rows, base=base: out_ref.at[pl.ds(base + off, rows)],
                right, axis,
                lambda j, s=s: send_sems.at[s, j],
                lambda j, s=s: recv_sems.at[s, j],
                lambda j, s=s: sig_sems.at[s, j],
                spans, ready=ready,
                recv_view=lambda off, rows, b=base_in: out_ref.at[
                    pl.ds(b + off, rows)
                ],
            )
        )
    descs[-1].wait_recv()
    shmem.quiet(*descs)


def _ring_bidir_chunked_kernel(
    x_ref, out_ref, copy_sem, send_r, recv_r, sig_r, send_l, recv_l, sig_l,
    *, axis: str, n: int, spans,
):
    """Chunk-granular bidirectional ring: both directions run the chunked
    forward-on-arrival schedule of :func:`_ring_1d_chunked_kernel`."""
    me = shmem.my_pe(axis)
    m = x_ref.shape[0]
    local = pltpu.make_async_copy(x_ref, out_ref.at[pl.ds(me * m, m)], copy_sem)
    local.start()
    local.wait()
    shmem.comm_jitter(axis, salt=2)
    shmem.barrier_all(axis)
    right = jax.lax.rem(me + 1, n)
    left = jax.lax.rem(me - 1 + n, n)
    steps_r = (n - 1 + 1) // 2
    steps_l = (n - 1) // 2
    descs_r, descs_l = [], []
    for s in range(max(steps_r, steps_l)):
        if s < steps_r:
            c = jax.lax.rem(me - s + n, n)
            base = c * m
            # incoming right-moving chunk: the left neighbor's step-s
            # send, shard (me-1-s) mod n (landing view, ISSUE 8)
            base_in = jax.lax.rem(me - 1 - s + 2 * n, n) * m
            ready = descs_r[s - 1].wait_recv_chunk if s > 0 else None
            descs_r.append(
                shmem.putmem_signal_chunked_nbi_block(
                    lambda off, rows, base=base: out_ref.at[pl.ds(base + off, rows)],
                    lambda off, rows, base=base: out_ref.at[pl.ds(base + off, rows)],
                    right, axis,
                    lambda j, s=s: send_r.at[s, j],
                    lambda j, s=s: recv_r.at[s, j],
                    lambda j, s=s: sig_r.at[s, j],
                    spans, ready=ready,
                    recv_view=lambda off, rows, b=base_in: out_ref.at[
                        pl.ds(b + off, rows)
                    ],
                )
            )
        if s < steps_l:
            c = jax.lax.rem(me + s, n)
            base = c * m
            # incoming left-moving chunk: the right neighbor's step-s
            # send, shard (me+1+s) mod n (landing view, ISSUE 8)
            base_in = jax.lax.rem(me + 1 + s, n) * m
            ready = descs_l[s - 1].wait_recv_chunk if s > 0 else None
            descs_l.append(
                shmem.putmem_signal_chunked_nbi_block(
                    lambda off, rows, base=base: out_ref.at[pl.ds(base + off, rows)],
                    lambda off, rows, base=base: out_ref.at[pl.ds(base + off, rows)],
                    left, axis,
                    lambda j, s=s: send_l.at[s, j],
                    lambda j, s=s: recv_l.at[s, j],
                    lambda j, s=s: sig_l.at[s, j],
                    spans, ready=ready,
                    recv_view=lambda off, rows, b=base_in: out_ref.at[
                        pl.ds(b + off, rows)
                    ],
                )
            )
    descs_r[-1].wait_recv()
    if descs_l:
        descs_l[-1].wait_recv()
    shmem.quiet(*descs_r, *descs_l)


def _full_mesh_push_kernel(x_ref, out_ref, copy_sem, send_sems, recv_sems, *, axis: str, n: int):
    me = shmem.my_pe(axis)
    m = x_ref.shape[0]
    local = pltpu.make_async_copy(x_ref, out_ref.at[pl.ds(me * m, m)], copy_sem)
    local.start()
    local.wait()
    shmem.comm_jitter(axis, salt=3)
    shmem.barrier_all(axis)
    my_sl = pl.ds(me * m, m)
    descs = []
    for d in range(1, n):
        dst = jax.lax.rem(me + d, n)
        descs.append(
            shmem.putmem_nbi_block(
                out_ref.at[my_sl], out_ref.at[my_sl], dst, axis,
                send_sems.at[d - 1], recv_sems.at[d - 1],
            )
        )
    # Symmetric SPMD: peer (me - d) sends me an equal-sized chunk tracked by
    # my recv_sems[d-1], so waiting on our own descriptors waits for all
    # incoming chunks too.
    for desc in descs:
        desc.wait_recv()
    shmem.quiet(*descs)


def _ring_2d_kernel(
    x_ref, out_ref, copy_sem, in_send, in_recv, out_send, out_recv,
    *, outer: str, inner: str, n_o: int, n_i: int,
):
    """Fused hierarchical 2-D ring allgather (≙ the reference's NUMA-aware /
    inter-node 2-D rings, allgather.py:194,291 and the device 2-D
    dissemination producer :377): an inner-axis ring gathers this PE's row
    while every chunk is forwarded along the outer axis the moment it lands,
    so outer-axis hops ride the ICI concurrently with inner-axis hops —
    per-segment pipelining, not phase-staged.

    Global slot layout matches ``jax.lax.all_gather(x, (outer, inner))``:
    chunk of PE (o, i) at rows ``[(o*n_i+i)*m, +m)``.

    Outer-round semantics: round ``t`` carries row ``me_o - t``; senders and
    receivers agree on the (t, s) semaphore slot because all PEs of an outer
    ring share the same inner coordinate (chunk order ``c = me_i - s``).
    """
    me_i = shmem.my_pe(inner)
    me_o = shmem.my_pe(outer)
    m = x_ref.shape[0]

    def slot(o, i):
        return pl.ds((o * n_i + i) * m, m)

    local = pltpu.make_async_copy(x_ref, out_ref.at[slot(me_o, me_i)], copy_sem)
    local.start()
    local.wait()
    shmem.comm_jitter((outer, inner), salt=4)
    shmem.barrier_all((outer, inner))

    right_i = jax.lax.rem(me_i + 1, n_i)
    down_o = jax.lax.rem(me_o + 1, n_o)
    descs_i = []
    descs_o = [[None] * n_i for _ in range(n_o - 1)]

    # Inner ring over own row; each chunk is forwarded outer-wards (round 0)
    # as soon as it is locally available.
    for s in range(n_i):
        c = jax.lax.rem(me_i - s + n_i, n_i)
        if s > 0:
            descs_i[s - 1].wait_recv()  # chunk (me_o, c) landed during s-1
        sl = slot(me_o, c)
        if s < n_i - 1:
            descs_i.append(
                shmem.putmem_nbi_block(
                    out_ref.at[sl], out_ref.at[sl], right_i, inner,
                    in_send.at[s], in_recv.at[s],
                )
            )
        if n_o > 1:
            descs_o[0][s] = shmem.putmem_nbi_block(
                out_ref.at[sl], out_ref.at[sl], down_o, outer,
                out_send.at[0, s], out_recv.at[0, s],
            )

    # Outer forwarding rounds: round t receives row me_o - t chunk by chunk
    # and (except the last round) forwards each chunk onward immediately.
    for t in range(1, n_o):
        row = jax.lax.rem(me_o - t + n_o, n_o)
        for s in range(n_i):
            c = jax.lax.rem(me_i - s + n_i, n_i)
            descs_o[t - 1][s].wait_recv()  # chunk (row, c) landed
            if t < n_o - 1:
                sl = slot(row, c)
                descs_o[t][s] = shmem.putmem_nbi_block(
                    out_ref.at[sl], out_ref.at[sl], down_o, outer,
                    out_send.at[t, s], out_recv.at[t, s],
                )
    shmem.quiet(*descs_i, *(d for row_d in descs_o for d in row_d if d is not None))


_KERNELS = {
    "ring_1d": (_ring_1d_kernel, 1),
    "ring_bidir": (_ring_bidir_kernel, 2),
    "full_mesh_push": (_full_mesh_push_kernel, 1),
}

# chunk-granular variants (ISSUE 3): ring methods only — full_mesh_push is
# a single hardware-routed hop per peer, so chunking buys no cross-hop
# pipelining there (chunks_per_shard is ignored for it, as for DCN/XLA
# fallbacks)
_CHUNKED_KERNELS = {
    "ring_1d": (_ring_1d_chunked_kernel, 1),
    "ring_bidir": (_ring_bidir_chunked_kernel, 2),
}


def all_gather_2d(
    x: jax.Array,
    *,
    axes: tuple[str, str],
    interpret: Any = None,
) -> jax.Array:
    return resilience.guarded_call(
        "all_gather_2d",
        _all_gather_2d_fused,
        functools.partial(_all_gather_xla, axis=tuple(axes)),
        x, axes=axes, interpret=interpret,
    )


def _all_gather_2d_fused(
    x: jax.Array,
    *,
    axes: tuple[str, str],
    interpret: Any = None,
) -> jax.Array:
    """Hierarchical allgather over two mesh axes ``(outer, inner)`` — the
    multi-axis composition VERDICT r1 called for (≙ 2-D rings, reference
    allgather.py:194,291). Call inside ``jax.shard_map``; golden:
    ``jax.lax.all_gather(x, axes, tiled=True)``.

    Map `inner` to the fastest/most-wraparound-rich ICI axis and `outer` to
    the slower axis (second torus dim, or the DCN axis of a multi-slice
    mesh): the inner ring then carries n_i-1 small hops while outer hops
    stream concurrently."""
    outer, inner = axes
    n_o = _axis_size((outer))
    n_i = _axis_size((inner))
    if n_o == 1:
        return all_gather(x, axis=inner, interpret=interpret)
    if n_i == 1:
        return all_gather(x, axis=outer, interpret=interpret)
    orig_shape = x.shape
    if x.ndim == 1:
        x = x.reshape(x.shape[0], 1)
    m = x.shape[0]
    out_shape = (n_o * n_i * m, *x.shape[1:])
    out = dist_pallas_call(
        functools.partial(
            _ring_2d_kernel, outer=outer, inner=inner, n_o=n_o, n_i=n_i
        ),
        name="all_gather_ring_2d",
        out_shape=jax.ShapeDtypeStruct(out_shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((max(n_i - 1, 1),)),
            pltpu.SemaphoreType.DMA((max(n_i - 1, 1),)),
            pltpu.SemaphoreType.DMA((n_o - 1, n_i)),
            pltpu.SemaphoreType.DMA((n_o - 1, n_i)),
        ],
        interpret=interpret,
    )(x)
    if len(orig_shape) == 1:
        out = out.reshape(out_shape[0])
    return out


def all_gather(x: jax.Array, *, axis: str = "tp", method: str = "auto", interpret: Any = None, devices: Any = None, chunks_per_shard: int = 1) -> jax.Array:
    """Gather shards along mesh `axis` (call inside ``jax.shard_map``).

    `x` is this PE's shard ``(m, ...)``; returns ``(n*m, ...)`` with shard i
    at rows ``[i*m, (i+1)*m)``. Golden reference:
    ``jax.lax.all_gather(x, axis, tiled=True)`` — served automatically when
    the fused kernel cannot run in this environment (resilience layer,
    docs/resilience.md).

    ``chunks_per_shard > 1`` splits every ring-step payload into that many
    per-chunk DMAs forwarded the moment each lands (chunk-granular overlap,
    ISSUE 3); 1 (default) is the legacy shard-granular schedule, bit for
    bit. Ring methods only — ignored by full_mesh_push and the DCN/XLA
    paths.
    """
    return resilience.guarded_call(
        "all_gather",
        _all_gather_fused,
        _all_gather_xla,
        x, axis=axis, method=method, interpret=interpret, devices=devices,
        chunks_per_shard=chunks_per_shard,
    )


def _all_gather_fused(x: jax.Array, *, axis: str = "tp", method: str = "auto", interpret: Any = None, devices: Any = None, chunks_per_shard: int = 1) -> jax.Array:
    if isinstance(axis, (tuple, list)):
        if len(axis) == 1:
            axis = axis[0]
        elif method != "auto":
            raise ValueError(
                f"multi-axis all_gather always uses the ring hierarchy; got "
                f"method={method!r} (only 'auto' is valid with >1 axis)"
            )
        else:
            # N-D (≙ the reference's 3-D node×numa×gpu push hierarchy,
            # low_latency_allgather.py:401): fused 2-D ring over the two
            # INNERMOST axes, then staged gathers outward — each outer hop
            # streams a block the inner hierarchy already assembled, and
            # the outermost-major concat order matches
            # jax.lax.all_gather(x, axes, tiled=True). A DCN axis (slice
            # boundary: no ICI path, remote DMA cannot reach — see
            # config.dcn_axes) is never fused into the 2-D ring; it peels
            # off to the single-axis path below, which lowers it to the
            # XLA collective (≙ the reference's internode
            # nvshmemx_putmem_signal stage, allgather.py:291-375 — here
            # XLA owns the DCN transport).
            axes = tuple(axis)
            if len(axes) >= 2 and not _is_dcn(axes[-1]) and not _is_dcn(axes[-2]):
                # the fused 2-D ring keeps shard granularity (its inner ring
                # already pipelines per-segment across the outer axis)
                out = all_gather_2d(x, axes=axes[-2:], interpret=interpret)
                rest = axes[:-2]
            else:
                out = all_gather(
                    x, axis=axes[-1], interpret=interpret,
                    chunks_per_shard=chunks_per_shard,
                )
                rest = axes[:-1]
            for a in reversed(rest):
                out = all_gather(
                    out, axis=a, interpret=interpret,
                    chunks_per_shard=chunks_per_shard,
                )
            return out
    n = _axis_size((axis))
    if n == 1:
        return x
    if _is_dcn(axis):
        # slice-crossing axis: XLA's all-gather rides DCN; the fused
        # remote-DMA kernels are ICI-only by construction
        return jax.lax.all_gather(x, axis, tiled=True)
    orig_shape = x.shape
    if x.ndim == 1:
        x = x.reshape(x.shape[0], 1)
    if method == "auto":
        method = get_auto_all_gather_method(
            x.size * x.dtype.itemsize, n, devices
        )
    m = x.shape[0]
    # every PE's slot starts at me*m rows of the HBM output: keep m a
    # whole number of (8 x 32-bit) sublane tiles, or the dynamic slot
    # offset is misaligned to the tiled layout (the flash-decode combine's
    # payload is b*hq + ceil(b*hq/d) rows — 258 at Llama-8B widths)
    tile = 8 * max(1, 4 // x.dtype.itemsize)
    if m % tile:
        m_pad = -(-m // tile) * tile
        out = _all_gather_fused(
            jnp.pad(x, ((0, m_pad - m),) + ((0, 0),) * (x.ndim - 1)),
            axis=axis, method=method, interpret=interpret, devices=devices,
            chunks_per_shard=chunks_per_shard,
        )
        out = out.reshape(n, m_pad, *x.shape[1:])[:, :m]
        return out.reshape((n * orig_shape[0],) + tuple(orig_shape[1:]))
    out_shape = (n * m, *x.shape[1:])
    n_steps = max(1, n - 1)
    chunks = max(1, int(chunks_per_shard))
    spans = chunk_schedule(m, chunks)
    if len(spans) > 1 and method in _CHUNKED_KERNELS:
        kernel_fn, n_sem_pairs = _CHUNKED_KERNELS[method]
        kernel = functools.partial(kernel_fn, axis=axis, n=n, spans=spans)
        name = f"all_gather_{method}"  # same family: never runs concurrently
        # per-(step, chunk) DMA sem pairs + the pure chunk-signal slots
        # (REGULAR; only exercised under an armed watchdog — see
        # shmem.putmem_signal_chunked_nbi_block)
        scratch = [pltpu.SemaphoreType.DMA(())]
        for _ in range(n_sem_pairs):
            scratch += [
                pltpu.SemaphoreType.DMA((n_steps, len(spans))),
                pltpu.SemaphoreType.DMA((n_steps, len(spans))),
                pltpu.SemaphoreType.REGULAR((n_steps, len(spans))),
            ]
    else:
        kernel_fn, n_sem_pairs = _KERNELS[method]
        kernel = functools.partial(kernel_fn, axis=axis, n=n)
        name = f"all_gather_{method}"
        scratch = [pltpu.SemaphoreType.DMA(())]
        for _ in range(n_sem_pairs):
            scratch += [pltpu.SemaphoreType.DMA((n_steps,)), pltpu.SemaphoreType.DMA((n_steps,))]
    out = dist_pallas_call(
        kernel,
        name=name,
        out_shape=jax.ShapeDtypeStruct(out_shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=scratch,
        interpret=interpret,
    )(x)
    if len(orig_shape) == 1:
        out = out.reshape(n * orig_shape[0])
    return out


def _all_gather_op_xla(
    x: jax.Array, mesh: Mesh, *, axis: str = "tp", **_
) -> jax.Array:
    """Op-level golden: the same shard_map entry serving XLA's all-gather."""
    in_spec = P(axis, *([None] * (x.ndim - 1)))
    out_spec = P(*([None] * x.ndim))
    return jit_shard_map(
        functools.partial(_all_gather_xla, axis=axis), mesh, in_spec, out_spec,
        key=("all_gather_xla", axis),
    )(x)


@resilience.guard_op("all_gather_op", _all_gather_op_xla)
def all_gather_op(
    x: jax.Array, mesh: Mesh, *, axis: str = "tp", method: str = "auto", interpret: Any = None, chunks_per_shard: int = 1
) -> jax.Array:
    """Convenience wrapper applying shard_map over `mesh` for a global array
    sharded on dim 0 (≙ the host-level ``ag_gemm``-style entry points)."""
    fn = functools.partial(
        all_gather, axis=axis, method=method, interpret=interpret,
        devices=topology.axis_devices(mesh, axis),
        chunks_per_shard=chunks_per_shard,
    )
    in_spec = P(axis, *([None] * (x.ndim - 1)))
    out_spec = P(*([None] * x.ndim))
    return jit_shard_map(
        fn, mesh, in_spec, out_spec,
        key=("all_gather", axis, method, str(interpret), chunks_per_shard),
    )(x)
