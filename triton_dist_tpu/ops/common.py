"""Shared kernel-building helpers (≙ reference ``kernels/nvidia/common_ops.py``).

The reference's common_ops holds device barrier kernels and host
stream-signal wrappers (``wait_eq``/``set_signal`` over cuStreamWriteValue,
:196-229). On TPU the host cannot poke device memory mid-program, so the
surviving pieces are: a standalone barrier kernel, collective-id management,
and the ``dist_pallas_call`` wrapper that all distributed kernels use.
"""

from __future__ import annotations

import functools
import itertools
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from triton_dist_tpu import config as tdt_config
from triton_dist_tpu.shmem import device as shmem



def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


_collective_id_counter = itertools.count(1)
_collective_ids: dict[str, int] = {}


def collective_id_for(name: str) -> int:
    """Stable collective_id per kernel family (barrier semaphores of
    concurrently-running kernels must not collide). Mosaic supports a small
    fixed pool of collective ids; running out is an error rather than a
    silent wrap that would alias two families' barrier semaphores."""
    if name not in _collective_ids:
        next_id = next(_collective_id_counter)
        if next_id >= 32:
            raise RuntimeError(
                f"out of collective_ids (31 kernel families in use) while "
                f"registering {name!r}; reuse an existing family name in "
                f"dist_pallas_call(name=...) for kernels that never run "
                f"concurrently"
            )
        _collective_ids[name] = next_id
    return _collective_ids[name]


def dist_pallas_call(
    kernel,
    *,
    name: str,
    out_shape: Any,
    in_specs: Sequence[pl.BlockSpec] | None = None,
    out_specs: Any = None,
    grid: tuple[int, ...] | None = None,
    grid_spec: Any = None,
    scratch_shapes: Sequence[Any] = (),
    cost_estimate: pl.CostEstimate | None = None,
    vmem_limit_bytes: int | None = None,
    interpret: Any = None,
    dimension_semantics: tuple[str, ...] | None = None,
    input_output_aliases: dict[int, int] | None = None,
    uses_barrier: bool = True,
    trace_tag: str = "",
):
    """pallas_call with the invariants every distributed kernel needs:
    side effects on (remote DMAs must not be DCE'd), a collective_id for the
    barrier semaphore, and config-resolved interpret mode.

    `trace_tag` is appended to the kernel's name in the lowered program and
    so in a device trace (the GEMM families put their tile there,
    :class:`GemmTile`); `name` alone stays the family every registry keys
    on (collective ids, watchdog sites, telemetry).

    `uses_barrier` must be False for degenerate single-PE calls: Mosaic
    rejects a collective_id on kernels that never touch the barrier
    semaphore.

    Resilience plumbing (zero-cost unless armed, docs/resilience.md): when
    ``config.timeout_iters > 0`` every kernel gains one extra
    ``int32[DIAG_LEN]`` SMEM output — the watchdog's diagnostic buffer —
    and its body is traced inside a ``watchdog.kernel_scope`` so the SHMEM
    wait primitives become bounded without any kernel changing its
    signature; the traced diag output is stripped from the caller-visible
    result and offered to the ambient ``jit_shard_map`` collection. An
    armed ``config.fault_plan`` opens the scope too (the signal-chaos
    injector needs the family/site bookkeeping) but adds no output."""
    from triton_dist_tpu import obs as _obs
    from triton_dist_tpu.obs import telemetry as _obs_telem
    from triton_dist_tpu.resilience import faults as _faults
    from triton_dist_tpu.resilience import records as _records
    from triton_dist_tpu.resilience import watchdog as _watchdog

    params: dict[str, Any] = dict(has_side_effects=True)
    if uses_barrier:
        params["collective_id"] = collective_id_for(name)
    if vmem_limit_bytes is not None:
        params["vmem_limit_bytes"] = vmem_limit_bytes
    if dimension_semantics is not None:
        params["dimension_semantics"] = dimension_semantics

    cfg = tdt_config.get_config()
    arm_diag = int(cfg.timeout_iters) > 0
    # wait-telemetry tier (ISSUE 9): one more SMEM output recording every
    # bounded wait site's observed spin count — success path included.
    # Requires the armed watchdog (the bounded waits are where the spin
    # count exists); without it the obs request is silently inert, the
    # chunk-signal discipline. Inside a jit_shard_map trace the decision
    # FOLLOWS the collecting scope (telem_wanted — the program being
    # built either consumes the buffer or it doesn't; reading config here
    # could disagree with the program's cache key if obs flipped between
    # wrap and first trace); outside one, config decides (the buffer is
    # dropped there anyway — no host boundary, no decode).
    wanted = _watchdog.telem_wanted()
    arm_telem = arm_diag and (
        _obs.wait_stats_enabled() if wanted is None else wanted
    )
    # a spent (healed) fault plan no longer needs the injector scope
    arm_scope = arm_diag or (
        cfg.fault_plan is not None and not _faults.plan_spent()
    )
    if arm_diag and params.get("dimension_semantics") is not None:
        # megacore chips split 'parallel' grid dims across two TensorCores;
        # the armed diag protocol (zero-init on grid step (0,…,0),
        # first-record-wins, fast-fail budget chaining) relies on in-order
        # execution on ONE core — a watchdogged run trades the parallel
        # split for a sound protocol (diagnostic posture, not a fast path)
        params["dimension_semantics"] = tuple(
            "arbitrary" for _ in params["dimension_semantics"]
        )

    single_out = not isinstance(out_shape, (tuple, list))
    out_shapes = [out_shape] if single_out else list(out_shape)
    n_user_outs = len(out_shapes)
    n_scratch = len(scratch_shapes)
    grid_dims = 0
    if grid_spec is not None:
        n_scratch += len(grid_spec.scratch_shapes)
        grid_dims = len(grid_spec.grid)
    elif grid is not None:
        grid_dims = len(grid)

    n_extra = (2 if arm_telem else 1) if arm_diag else 0
    if arm_diag:
        # the diagnostic buffer (and, when the obs layer arms wait_stats,
        # the telemetry buffer after it): unblocked SMEM, last outputs, so
        # existing input/output aliases and ref positions stay untouched
        out_shapes.append(jax.ShapeDtypeStruct((_records.DIAG_LEN,), jnp.int32))
        if arm_telem:
            out_shapes.append(
                jax.ShapeDtypeStruct((_obs_telem.TELEM_LEN,), jnp.int32)
            )
        extra_specs = tuple(
            pl.BlockSpec(memory_space=pltpu.SMEM) for _ in range(n_extra)
        )
        if grid_spec is not None:
            gs_outs = grid_spec.out_specs
            if not isinstance(gs_outs, (tuple, list)):
                gs_outs = (gs_outs,)
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=grid_spec.num_scalar_prefetch,
                grid=grid_spec.grid,
                in_specs=list(grid_spec.in_specs),
                out_specs=(*gs_outs, *extra_specs),
                scratch_shapes=list(grid_spec.scratch_shapes),
            )
        else:
            if out_specs is None:
                user_specs: tuple = tuple(pl.BlockSpec() for _ in range(n_user_outs))
            elif isinstance(out_specs, (tuple, list)):
                user_specs = tuple(out_specs)
            else:
                user_specs = (out_specs,)
            out_specs = (*user_specs, *extra_specs)

    body = kernel
    if arm_scope:
        def body(*refs):  # noqa: F811 — deliberate armed override
            diag_ref = telem_ref = None
            user_refs = refs
            if arm_diag:
                i = len(refs) - n_scratch - n_extra
                diag_ref = refs[i]
                if arm_telem:
                    telem_ref = refs[i + 1]
                user_refs = refs[:i] + refs[i + n_extra:]

                def _zero_diag():
                    for j in range(_records.DIAG_LEN):
                        diag_ref[j] = jnp.int32(0)
                    if telem_ref is not None:
                        for j in range(_obs_telem.TELEM_LEN):
                            telem_ref[j] = jnp.int32(0)
                        # the telemetry row self-describes its kernel
                        # family (gathered rows from different launches
                        # share one host-side decode)
                        telem_ref[_obs_telem.H_FAMILY] = jnp.int32(
                            _records.family_code_for(name)
                        )

                if grid_dims == 0:
                    _zero_diag()
                else:
                    # compiled outputs start uninitialized: clear once, on
                    # the first grid step (TPU grids execute in order)
                    first = pl.program_id(0) == 0
                    for d in range(1, grid_dims):
                        first = jnp.logical_and(first, pl.program_id(d) == 0)
                    pl.when(first)(_zero_diag)
            with _watchdog.kernel_scope(diag_ref, name, telem_ref=telem_ref):
                kernel(*user_refs)

    kwargs: dict[str, Any] = {}
    if grid_spec is not None:
        kwargs["grid_spec"] = grid_spec
    else:
        if grid is not None:
            kwargs["grid"] = grid
        if in_specs is not None:
            kwargs["in_specs"] = list(in_specs)
        if out_specs is not None:
            kwargs["out_specs"] = out_specs
    if input_output_aliases:
        kwargs["input_output_aliases"] = input_output_aliases
    call = pl.pallas_call(
        body,
        out_shape=tuple(out_shapes) if arm_diag else out_shape,
        scratch_shapes=list(scratch_shapes),
        compiler_params=pltpu.CompilerParams(**params),
        cost_estimate=cost_estimate,
        interpret=tdt_config.interpret_params() if interpret is None else interpret,
        name=name + trace_tag,
        **kwargs,
    )
    if not arm_diag:
        return call

    def invoke(*args):
        outs = call(*args)
        if arm_telem:
            *user, diag, telem = outs
        else:
            *user, diag = outs
            telem = None
        if not _watchdog.offer(diag, telem):
            # traced inside a USER-level shard_map, not jit_shard_map: no
            # host boundary will decode this diag and raise, so poison the
            # outputs in-trace — a timed-out launch must never hand back
            # plausible partial data (the telemetry is dropped for the
            # same reason: no host boundary, no decode)
            bad = diag[_records.F_STATUS] != _records.STATUS_OK
            user = [_watchdog.poison(u, bad) for u in user]
        return user[0] if single_out else tuple(user)

    return invoke


def chunk_schedule(
    rows: int, chunks: int, quantum: int = 1
) -> tuple[tuple[int, int], ...]:
    """Static ``(offset, rows)`` spans splitting a shard's `rows` into
    `chunks` contiguous near-equal chunks — the chunk-granular transfer
    schedule of the ring families (ISSUE 3; ≙ the per-M-tile readiness
    granularity of the reference's consumer GEMM, allgather_gemm.py:226).

    `quantum` > 1 aligns every span boundary to a multiple of it (the last
    chunk absorbs any sub-quantum tail): the GEMM families pass their MXU
    row tile here so a non-divisor chunk count can never hand
    ``pick_block`` an odd row count that collapses the tile toward 1 row —
    a silent orders-of-magnitude cliff. With the default quantum=1 counts
    balance to within one row; a request for more chunks than quanta
    clamps. Every PE computes the same spans from the same static shapes,
    so senders and receivers agree on per-chunk semaphore slots and byte
    counts by construction."""
    if rows < 1:
        raise ValueError(f"chunk_schedule: rows must be >= 1, got {rows}")
    if chunks < 1:
        raise ValueError(f"chunk_schedule: chunks must be >= 1, got {chunks}")
    quantum = max(1, min(int(quantum), rows))
    units = rows // quantum
    chunks = min(chunks, max(1, units))
    base, extra = divmod(units, chunks)
    spans, off = [], 0
    for j in range(chunks):
        sz = (base + (1 if j < extra else 0)) * quantum
        if j == chunks - 1:
            sz += rows - units * quantum  # sub-quantum tail
        spans.append((off, sz))
        off += sz
    return tuple(spans)


# ---------------------------------------------------------------------------
# Span-policy schedules (ISSUE 14): alternative span tilings/orderings the
# schedule synthesizer (triton_dist_tpu/synth/) enumerates and the static
# verifier proves. The emitter kernels consume the resulting spans
# UNCHANGED — a policy is purely a different (offset, rows) list. The math
# lives here (next to chunk_schedule, the kernel side's only dependency);
# the declarative policy space over it lives in synth/policies.py.
# ---------------------------------------------------------------------------

def span_window_schedule(
    rows: int, chunks: int, quantum: int = 1
) -> tuple[tuple[int, int], ...]:
    """Arrival-window span tiling (the synthesized ``window`` policy, AG
    side): contiguous ascending spans with geometrically GROWING sizes —
    the first chunk is as small as the quantum allows, each later chunk
    roughly doubles. The consumer's first wait (the exposed first-chunk
    bubble of ``perf_model.estimate_fused_ring_bubble_ms``) then covers
    only the smallest span's wire time, while the tail chunks keep DMA
    descriptor count bounded. ``chunks=1`` (or too few quanta) degrades to
    :func:`chunk_schedule`'s single span — the legacy protocol, bit for
    bit (the synthesizer's identity pin)."""
    if rows < 1:
        raise ValueError(f"span_window_schedule: rows must be >= 1, got {rows}")
    if chunks < 1:
        raise ValueError(
            f"span_window_schedule: chunks must be >= 1, got {chunks}"
        )
    quantum = max(1, min(int(quantum), rows))
    units = rows // quantum
    chunks = min(chunks, max(1, units))
    if chunks == 1:
        return chunk_schedule(rows, 1, quantum)
    # doubling weights 1, 2, 4, ... scaled into the unit budget; every
    # chunk keeps >= 1 unit, the LAST chunk absorbs the remainder (and the
    # sub-quantum tail) so sizes stay ascending
    weights = [1 << j for j in range(chunks)]
    total_w = sum(weights)
    sizes = [max(1, (units * w) // total_w) for w in weights[:-1]]
    head = sum(sizes)
    if head >= units:  # tiny unit budgets: fall back to near-equal spans
        return chunk_schedule(rows, chunks, quantum)
    sizes.append(units - head)
    spans, off = [], 0
    for j, sz_units in enumerate(sizes):
        sz = sz_units * quantum
        if j == chunks - 1:
            sz += rows - units * quantum  # sub-quantum tail
        spans.append((off, sz))
        off += sz
    return tuple(spans)


def span_interleave_schedule(
    rows: int, chunks: int, quantum: int = 1
) -> tuple[tuple[int, int], ...]:
    """Bidirectional chunk interleave (the synthesized ``interleave``
    policy, MoE combine side): the near-equal contiguous tiling of
    :func:`chunk_schedule` ISSUED alternately from both ends —
    ``c0, c_{k-1}, c1, c_{k-2}, …`` — so the landed slab grows inward from
    its first AND last rows. Per-chunk semaphore slots are positional
    (``sig_at(j)``), so issue order is free to permute: every PE computes
    the same permutation from the same static shapes and slot agreement
    holds exactly as for the contiguous order. Valid ONLY where the
    consumer drains chunks by slot index (the combine's
    ``wait_recv_chunk(j)`` loop); the AG gather-group arithmetic requires
    ascending contiguous coverage — :func:`resolve_spans` rejects the
    pairing. ``chunks=1`` is the legacy single span, bit for bit."""
    base = chunk_schedule(rows, chunks, quantum)
    if len(base) <= 2:
        return base
    order, lo, hi = [], 0, len(base) - 1
    while lo <= hi:
        order.append(lo)
        if hi != lo:
            order.append(hi)
        lo, hi = lo + 1, hi - 1
    return tuple(base[i] for i in order)


def span_torus2d_schedule(
    rows: int, chunks: int, quantum: int = 1, world: int = 1
) -> tuple[tuple[int, int], ...]:
    """2-D torus-aware span tiling (the synthesized ``torus2d`` policy):
    the chunk count adapts to the WORLD's most-square 2-D torus
    factorization (``parallel.topology.torus_factor``) — ``chunks ×
    inner_dim(world)`` contiguous near-equal spans, so each ring step
    forwards one span per inner-axis hop of the physical torus and the
    store-and-forward chain pipelines at the inner-ring granularity. On a
    world whose factorization is a line (inner dim 1 — e.g. world 2) the
    schedule degrades to :func:`chunk_schedule` at the caller's chunk
    count; with ``chunks=1`` there, that is the legacy single span — the
    identity pin."""
    from triton_dist_tpu.parallel.topology import torus_factor

    _, inner = torus_factor(max(1, int(world)))
    return chunk_schedule(rows, max(1, int(chunks)) * inner, quantum)


# Registry the overlap host entries dispatch on (GroupGemmConfig
# .span_policy). "contig" is the legacy schedule — the identity the
# emitter pin tests compare against. Each entry: (schedule_fn,
# needs_world, contiguous_ascending).
SPAN_POLICIES = {
    "contig": (chunk_schedule, False, True),
    "window": (span_window_schedule, False, True),
    "interleave": (span_interleave_schedule, False, False),
    "torus2d": (span_torus2d_schedule, True, True),
}


def validate_span_policy(policy: str, side: str) -> None:
    """The span-policy config fence: unknown names and side-invalid
    pairings raise with a named diagnosis. The overlap HOST entries call
    this BEFORE their ``guarded_call`` ladder — a policy misconfiguration
    is a config error that must fail loudly, not a kernel failure the
    guard may silently downgrade to the golden path."""
    try:
        _, _, ascending = SPAN_POLICIES[policy]
    except KeyError:
        raise ValueError(
            f"unknown span_policy {policy!r}; known: {sorted(SPAN_POLICIES)}"
        ) from None
    if side == "ag" and not ascending:
        raise ValueError(
            f"span_policy {policy!r} emits non-contiguous span order, which "
            f"the AG gather-group schedule cannot consume (its group "
            f"coverage is derived from ascending span offsets); valid "
            f"sides: moe_rs"
        )


def resolve_spans(
    rows: int, chunks: int, quantum: int, *, policy: str = "contig",
    world: int = 1, side: str = "moe_rs",
) -> tuple[tuple[int, int], ...]:
    """The span schedule for one overlap launch: dispatch
    ``GroupGemmConfig.span_policy`` to its schedule function.
    ``side="ag"`` (the AG-GroupGEMM ring) requires ascending contiguous
    spans — its gather-group arithmetic derives each span's compute
    coverage from the span offsets, and the last LIST entry absorbs the
    group tail — so order-permuting policies are rejected with a named
    diagnosis (the same validity rule ``synth/generate.py`` prunes on).
    ``policy="contig"`` is byte-for-byte :func:`chunk_schedule`."""
    validate_span_policy(policy, side)
    fn, needs_world, _ = SPAN_POLICIES[policy]
    if needs_world:
        return fn(rows, chunks, quantum, world)
    return fn(rows, chunks, quantum)


def gemm_add_pipeline(
    bm: int, bn: int, bk: int, m_dim: int, n_dim: int, k_dim: int,
    acc_ref, out_dtype, n_adds: int = 0,
):
    """Tiled ``O = A @ B (+ sum(adds))`` as an inner ``emit_pipeline``: f32
    VMEM accumulation over the k grid dim with the optional adds fused into
    the last-k epilogue. The shared MXU workhorse of the fused kernels
    (≙ the consumer/producer GEMM bodies of reference allgather_gemm.py:133
    and gemm_reduce_scatter.py:125). Add operands use a k-invariant index
    map, so Pallas fetches each of their tiles once."""
    n_k = k_dim // bk

    def body(a_blk, b_blk, *rest):
        o_blk = rest[-1]
        adds = rest[:-1]
        kk = pl.program_id(2)

        @pl.when(kk == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        acc_ref[:] += jnp.dot(a_blk[:], b_blk[:], preferred_element_type=jnp.float32)

        @pl.when(kk == n_k - 1)
        def _():
            acc = acc_ref[:]
            for r in adds:
                acc = acc + r[:].astype(jnp.float32)
            o_blk[:] = acc.astype(out_dtype)

    return pltpu.emit_pipeline(
        body,
        grid=(m_dim // bm, n_dim // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ]
        + [pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))] * n_adds,
        out_specs=[pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))],
    )


# VMEM one inner GEMM pipeline may ask of Mosaic (v5e holds 128 MiB; the
# compiler's own scoped default is 16 MiB). The sweep that chose it is in
# CHANGES.md (PR 43) and docs/autotuner.md "Where a served tile comes from".
GEMM_VMEM_BUDGET = 32 * 2**20
_MOSAIC_SCOPED_DEFAULT = 16 * 2**20
_MXU = 128


class GemmTile(NamedTuple):
    """One ``gemm_add_pipeline``'s blocks and the scoped VMEM they need."""

    bm: int
    bn: int
    bk: int
    vmem_limit_bytes: int

    @property
    def tag(self) -> str:
        """Suffix of the kernel's name in a trace (docs/observability.md);
        it ends in a letter so that a reader which strips an HLO
        instruction's trailing ``.<n>`` leaves it whole."""
        return f"_{self.bm}m{self.bn}n{self.bk}k"


def _tile_vmem_bytes(bm, bn, bk, n_adds, in_size, out_size) -> int:
    """What Mosaic allocates for one pipeline at this tile: the A, B and
    out tiles double-buffered, each add tile double-buffered and once more
    for the epilogue that sums it, the f32 accumulator, the copy of the A
    tile its dot makes (read off the compiler's own refusals at a described
    v5e: the scoped allocation is this sum + under 1 MiB), 2 MiB to spare."""
    return (
        2 * (bm * bk + bk * bn) * in_size
        + (2 + 3 * n_adds) * bm * bn * out_size
        + 4 * bm * bn
        + bm * bk * in_size
        + 2 * 2**20
    )


def _mxu_blocks(dim: int) -> tuple[int, ...]:
    """Block sizes the rule may give a dimension: its divisors that are
    multiples of the MXU's 128; the whole dimension where it has none (a
    whole-dimension block is legal at any size: test shapes, a row count
    of 8 or 64)."""
    return tuple(d for d in range(_MXU, dim + 1, _MXU) if dim % d == 0) or (dim,)


@functools.lru_cache(maxsize=1024)
def _largest_step(m, n, k, n_adds, in_size, out_size, budget) -> GemmTile:
    best = None
    for bm, bn, bk in itertools.product(*map(_mxu_blocks, (m, n, k))):
        need = _tile_vmem_bytes(bm, bn, bk, n_adds, in_size, out_size)
        fits = need <= budget
        # a tile that fits (else the smallest there is); the largest grid
        # step; among equals the fewest bytes streamed (A once a column
        # block, B once a row block)
        rank = (
            fits, 0 if fits else -need, bm * bn * bk,
            -(m * k * (n // bn) + k * n * (m // bm)),
        )
        if best is None or rank > best[0]:
            best = (rank, GemmTile(bm, bn, bk, need))
    return best[1]


def gemm_tile(
    cfg, m: int, n: int, k: int, *, n_adds: int = 0, in_dtype, out_dtype
) -> GemmTile:
    """THE place a fused GEMM's tile comes from: ``(bm, bn, bk)`` of the
    inner ``gemm_add_pipeline`` over ``[m, k] @ [k, n]`` with `n_adds` fused
    add operands, and the ``vmem_limit_bytes`` the kernel then asks for.

    A config whose block fields are unset (``config=None`` upstream: every
    served call) gets the rule: the largest grid step ``bm * bn * bk`` whose
    footprint (:func:`_tile_vmem_bytes`) fits :data:`GEMM_VMEM_BUDGET`,
    every block a divisor of its dimension and a multiple of the MXU's 128
    where the dimension has such a divisor (N = 3584 gives 1792 or 896,
    never 512 by halving), never more rows than there are. It follows the
    shape: the own chunk of ``gemm_rs``'s scatter kernel, whose three adds
    cost VMEM the remote chunks' pipeline does not pay, gets a smaller
    tile from the same call; a 128-row chunk gets a wide one.

    An explicit config (the autotuner's candidates, tests' tiny tiles) is
    honoured as before, each block shrunk to a divisor by ``pick_block``.
    Either way the limit is the footprint's, and never under Mosaic's own
    16 MiB default."""
    from triton_dist_tpu.utils import pick_block

    in_size = jnp.dtype(in_dtype).itemsize
    out_size = jnp.dtype(out_dtype).itemsize
    if cfg is not None and cfg.block_m is not None:
        if cfg.block_n is None or cfg.block_k is None:
            raise ValueError(f"{cfg}: set all three block fields or none")
        bm, bn, bk = (
            pick_block(m, cfg.block_m), pick_block(n, cfg.block_n),
            pick_block(k, cfg.block_k),
        )
        need = _tile_vmem_bytes(bm, bn, bk, n_adds, in_size, out_size)
    else:
        bm, bn, bk, need = _largest_step(
            m, n, k, n_adds, in_size, out_size, GEMM_VMEM_BUDGET
        )
    return GemmTile(bm, bn, bk, max(need, _MOSAIC_SCOPED_DEFAULT))


def gemm_chunk_spans(cfg, tile: GemmTile, m_loc: int):
    """``chunks_per_shard`` applied to a shard's rows: the chunk spans, and
    `tile` with the row block a chunk's rows shrink FROM (the cap an
    explicit config names, as before; else the rule's block). Span
    boundaries quantize to the MXU row tile a chunk of that size would
    pick, so chunking shrinks tiles predictably (m_loc/chunks) instead of
    collapsing them on odd row counts (see :func:`chunk_schedule`)."""
    from triton_dist_tpu.utils import pick_block

    chunks = max(1, int(cfg.chunks_per_shard))
    cap_m = tile.bm if cfg.block_m is None else cfg.block_m
    spans = chunk_schedule(
        m_loc, chunks,
        quantum=pick_block(m_loc, min(cap_m, max(1, m_loc // chunks))),
    )
    return spans, tile._replace(bm=cap_m)


def gemm_only(a, b, *, cfg, out_dtype, name: str, interpret=None):
    """Pure-MXU pipelined matmul — the world-1 degenerate path shared by the
    fused ops (same inner ``gemm_add_pipeline``, minus workspace and ring).
    `cfg` is the op's config or None (:func:`gemm_tile`); `name` keeps
    traces/profiles attributed to the real op."""
    m_loc, k_dim = a.shape
    n_loc = b.shape[1]
    tile = gemm_tile(
        cfg, m_loc, n_loc, k_dim, in_dtype=a.dtype, out_dtype=out_dtype
    )
    bm, bn, bk, vmem = tile

    def _kernel(a_ref, b_ref, out_ref, acc_ref):
        pipeline = gemm_add_pipeline(bm, bn, bk, m_loc, n_loc, k_dim, acc_ref, out_dtype)
        pipeline(a_ref, b_ref, out_ref)

    return dist_pallas_call(
        _kernel,
        name=name,
        trace_tag=tile.tag,
        out_shape=jax.ShapeDtypeStruct((m_loc, n_loc), out_dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * m_loc * n_loc * k_dim,
            bytes_accessed=(m_loc * k_dim + k_dim * n_loc + m_loc * n_loc) * a.dtype.itemsize,
            transcendentals=0,
        ),
        vmem_limit_bytes=vmem,
        uses_barrier=False,
        interpret=interpret,
    )(a, b)


_jit_cache: dict[Any, Any] = {}
# unarmed dispatch wrappers, keyed like _jit_cache: callers compare entry
# identity (tests pin f1 is f2 for the zero-overhead path), so the span
# wrapper must be as cached as the jitted program it fronts
_wrapper_cache: dict[Any, Any] = {}


def jit_shard_map(
    fn,
    mesh,
    in_specs,
    out_specs,
    *,
    key: Any,
    donate_argnums: tuple = (),
):
    """``jax.jit(jax.shard_map(fn, ...))`` cached across calls.

    ``jax.jit`` keys its cache on the callable's identity; building a fresh
    ``shard_map`` wrapper per invocation (what every ``*_op`` convenience
    entry naturally does) therefore retraces AND recompiles every call —
    seconds per call. `key` must capture everything
    that changes the traced program besides the mesh/specs (op name, config,
    method, static dims); argument shapes/dtypes are handled by jit itself.

    When the watchdog is armed (``config.timeout_iters > 0``) the traced fn
    runs inside a ``watchdog.collect`` scope: every ``dist_pallas_call`` it
    launches contributes its diagnostic buffer, the merged per-PE record
    rides back as one extra shard_map output (outputs are NaN-poisoned
    in-program on the PEs that tripped), and host-side a non-clean record
    raises :class:`resilience.DistTimeoutError` (or, with
    ``config.raise_on_timeout=False``, returns the poisoned outputs after
    recording the event in ``resilience.health``).
    """
    from triton_dist_tpu import config as _tdt_config
    from triton_dist_tpu import obs as _obs
    from triton_dist_tpu.obs import telemetry as _obs_telem
    from triton_dist_tpu.resilience import faults as _faults
    from triton_dist_tpu.resilience import guard as _guard
    from triton_dist_tpu.resilience import records as _records
    from triton_dist_tpu.resilience import watchdog as _watchdog

    cfg = _tdt_config.get_config()
    armed = int(cfg.timeout_iters) > 0
    # wait-telemetry tier (ISSUE 9): the traced program grows one more
    # gathered output, so the request is part of the program cache key
    ws = armed and _obs.wait_stats_enabled()
    family = key[0] if isinstance(key, tuple) and key and isinstance(key[0], str) else str(key)

    def _cache_key():
        cfg = _tdt_config.get_config()
        return (
            mesh, str(in_specs), str(out_specs), donate_argnums, key,
            # trace-time config that changes the kernel program (a cached
            # un-delayed program must not serve a race-shaking, watchdogged,
            # or fault-injected run, and vice versa). The fault-plan token
            # flips when a bounded plan's trigger budget is spent, so a
            # healed retry traces — and caches — the clean program.
            cfg.debug_comm_delay, cfg.timeout_iters, _faults.plan_token(), ws,
            # an explicit golden run traces different programs
            _guard.golden_active(),
        )

    def _resolve():
        cache_key = _cache_key()
        hit = _jit_cache.get(cache_key)
        if hit is None:
            if armed:
                def fn_diag(*args):
                    # want_telem rides the scope so the kernels traced
                    # inside arm their telemetry output to MATCH this
                    # program's output structure (see watchdog.collect)
                    with _watchdog.collect(want_telem=ws) as entries:
                        out = fn(*args)
                    diag = _watchdog.merge([d for d, _ in entries])
                    bad = diag[0, _records.F_STATUS] != _records.STATUS_OK
                    if ws:
                        telems = [t for _, t in entries if t is not None]
                        telem = (
                            jnp.stack(telems) if telems
                            else jnp.zeros(
                                (1, _obs_telem.TELEM_LEN), jnp.int32
                            )
                        )
                        return _watchdog.poison(out, bad), diag, telem
                    return _watchdog.poison(out, bad), diag

                diag_out_spec = PartitionSpec(tuple(mesh.axis_names), None)
                armed_out_specs = (
                    (out_specs, diag_out_spec, diag_out_spec) if ws
                    else (out_specs, diag_out_spec)
                )
                hit = jax.jit(
                    _shard_map(fn_diag, mesh, in_specs, armed_out_specs),
                    donate_argnums=donate_argnums,
                )
            else:
                hit = jax.jit(
                    _shard_map(fn, mesh, in_specs, out_specs),
                    donate_argnums=donate_argnums,
                )
            _jit_cache[cache_key] = hit
        return hit

    if not armed:
        # Cached wrapper, keyed like the program cache: unarmed entries
        # with the same key return the IDENTICAL callable (pinned in
        # tests/test_elastic.py). The program is resolved EAGERLY at wrap
        # time and frozen in the closure — exactly the pre-obs semantics:
        # a stored unarmed wrapper must never re-resolve under a config
        # that changed after wrap (re-reading _cache_key per call under a
        # later-armed watchdog would build the unarmed program and cache
        # it under the ARMED key, poisoning the shared program cache).
        # Per-call work is ONE obs.span_enabled() attribute read, so a
        # wrapper stored while obs was disarmed still emits jit spans
        # once obs is armed mid-process; `cached` reports whether this
        # wrapper has dispatched before (jax.jit traces lazily on the
        # first CALL, so that is the trace-vs-cached boundary).
        wrap_key = _cache_key()
        hit = _wrapper_cache.get(wrap_key)
        if hit is not None:
            return hit
        jitted = _resolve()
        state = {"warm": False}

        def unarmed_call(*args):
            if not _obs.span_enabled():
                state["warm"] = True
                return jitted(*args)
            cached = state["warm"]
            state["warm"] = True
            with _obs.span(f"jit:{family}", cat="jit", cached=cached,
                           armed=False):
                return jitted(*args)

        # the jitted program itself, for AOT inspection (chip_smoke.py
        # lowers it to look for the Pallas custom calls)
        unarmed_call.jitted = jitted
        _wrapper_cache[wrap_key] = unarmed_call
        return unarmed_call
    n_world = int(mesh.devices.size)
    # peer attribution is keyed by flattened device index; on a multi-axis
    # mesh the diag rows span the product world while records carry the PE
    # along one comm axis, so attribution only runs on 1-D worlds
    single_axis = mesh.devices.ndim == 1

    def _refuse(reason):
        # the family's collective semaphore state is undefined after an
        # earlier trip (even under raise_on_timeout=False, which raised
        # nothing): refuse the launch with a fallbackable error so an
        # enclosing guard serves the golden path — loud otherwise
        raise NotImplementedError(
            f"distributed kernel family {family!r} refused to launch: "
            f"{reason}; its collective semaphore may hold residue. "
            f"Guarded op entries serve the golden XLA path; see "
            f"docs/resilience.md."
        )

    def _raise_integrity(recs, noted=False):
        # per-chunk canary mismatches (ISSUE 8): corrupt data was
        # DETECTED — outputs arrive NaN-poisoned (the diag status gates
        # the same in-program poison as timeouts), the named PEs are
        # struck directly (victim == culprit under the landing-site
        # model), and the op raises IntegrityError REGARDLESS of
        # raise_on_timeout: poison-and-continue is a timeout posture;
        # silently continuing past known-corrupt data is what this layer
        # exists to prevent. No family pin either — the canary drains its
        # own credits, so there is no semaphore residue to protect.
        from triton_dist_tpu.resilience import elastic as _elastic
        from triton_dist_tpu.resilience import health
        from triton_dist_tpu.resilience import integrity as _integrity

        if not noted:  # mixed-launch callers recorded/struck these already
            health.record_integrity(family, records=recs)
            if _tdt_config.get_config().elastic and single_axis:
                _elastic.note_integrity_records(recs, n_world, family=family)
        err = _integrity.IntegrityError(
            family, _integrity.DET_CANARY, records=recs, world_size=n_world
        )
        err._tdt_recorded = True
        raise err

    def _launch(*args):
        """One resolved-program invocation, normalized to (out, diag):
        the wait-stats variant peels its telemetry output and folds the
        decoded per-site spin records into the obs registry (success and
        failure paths alike — a timed-out launch's surviving sites are
        exactly the attribution a stall question needs)."""
        if ws:
            out, diag, telem = _resolve()(*args)
            _obs_telem.record_decoded(_obs_telem.decode_telem(telem))
        else:
            out, diag = _resolve()(*args)
        return out, diag

    def call(*args):
        from triton_dist_tpu.resilience import health

        reason = health.short_circuited(family)
        if reason is not None:
            _refuse(reason)
        cfg = _tdt_config.get_config()
        policy = cfg.retry_policy
        if policy is None and not cfg.elastic:
            # pre-existing single-attempt path (retry/elastic disabled).
            # Resolved per call, not at wrap time: callers store these
            # wrappers (models/decode serving steps), and a stored wrapper
            # must pick up a healed fault plan's clean program
            out, diag = _launch(*args)
            if cfg.fault_plan is not None:
                _faults.note_launch()
            recs = _records.decode_diag(diag)  # forces the device sync
            if recs:
                to_recs = [r for r in recs if r["status"] != "integrity"]
                if not to_recs:
                    _raise_integrity(recs)
                int_recs = [r for r in recs if r["status"] == "integrity"]
                if int_recs:
                    # mixed launch: the timeout arc below is the louder
                    # event, but the corruption detections must still land
                    # in the registry (attribution strikes need the
                    # elastic path — not this branch, which runs with
                    # elastic disabled)
                    health.record_integrity(family, records=int_recs)
                health.record_timeout(family, to_recs)
                if _tdt_config.get_config().raise_on_timeout:
                    raise _records.DistTimeoutError(
                        family, to_recs, world_size=n_world
                    )
                if int_recs:
                    # poison-and-continue is a TIMEOUT posture only:
                    # detected corruption raises regardless, even when it
                    # co-occurred with a silent timeout
                    _raise_integrity(int_recs, noted=True)
            return out

        # elastic degraded-mode path: transient timeouts are retried with
        # backoff, every failed attempt feeds peer attribution, and
        # exhaustion records the timeout (quarantining the family) and
        # escalates — by which point a persistent straggler has collected
        # enough strikes to be PE-quarantined (docs/resilience.md)
        from triton_dist_tpu.resilience import elastic as _elastic
        from triton_dist_tpu.resilience import retry as _retry

        attempts = policy.max_attempts if policy is not None else 1
        delays = policy.delays(key=family) if policy is not None else ()
        slept = 0.0
        for attempt in range(attempts):
            out, diag = _launch(*args)
            if cfg.fault_plan is not None:
                _faults.note_launch()
            recs = _records.decode_diag(diag)
            if not recs:
                if attempt:
                    health.record_recovery(family, attempt)
                    # stamp the recovery onto the enclosing op:{family}
                    # guard span BY NAME (the guard layer's ladder-rung
                    # record, ISSUE 9) — the innermost open span here is
                    # our own jit:{family} dispatch span
                    _obs.tracer.annotate_span(
                        f"op:{family}", retries=attempt
                    )
                if cfg.elastic:
                    _elastic.note_clean_step(n_world)
                return out
            int_recs = [r for r in recs if r["status"] == "integrity"]
            if int_recs and len(int_recs) == len(recs):
                # pure canary corruption (no timeouts): retried in place
                # under the policy — sound even on compiled TPU, a canary
                # drains its own credits so no semaphore residue exists —
                # counted as integrity_retry (separate from the timeout
                # counters) with the named PEs struck per failed attempt;
                # exhaustion (or a donating entry, whose buffers died with
                # the first attempt) raises IntegrityError
                delay = (
                    delays[attempt] if attempt < len(delays) else 0.0
                )
                over_budget = (
                    policy is not None
                    and policy.total_delay_budget_s is not None
                    and slept + delay > policy.total_delay_budget_s
                )
                if (
                    attempt == attempts - 1 or donate_argnums or over_budget
                ):
                    _raise_integrity(int_recs)  # strikes the named PEs
                if cfg.elastic and single_axis:
                    _elastic.note_integrity_records(
                        int_recs, n_world, family=family
                    )
                health.record_integrity_retry(family, attempt + 1, delay)
                _retry.get_clock().sleep(delay)
                slept += delay
                continue
            # mixed records: the timeout arc below handles the louder
            # event over the timeout records only — but the corruption
            # detections still land in the registry and still strike
            # their named PEs (a persistently corrupt PE that co-occurs
            # with timeouts must not escape attribution)
            if int_recs:
                health.record_integrity(family, records=int_recs)
                if cfg.elastic and single_axis:
                    _elastic.note_integrity_records(
                        int_recs, n_world, family=family
                    )
            recs = [r for r in recs if r["status"] != "integrity"]
            if cfg.elastic and single_axis:
                _elastic.note_timeout_records(recs, n_world, family=family)
            last = attempt == attempts - 1
            if donate_argnums:
                # donated inputs are deleted by the first invocation; a
                # relaunch with the same tuple would read freed buffers.
                # Timeouts on donating entries escalate immediately —
                # host-level retries (ElasticStep) own re-materialization.
                last = True
            if not _tdt_config.interpreting():
                # compiled TPU: the family's collective semaphore may hold
                # residue after the trip (a straggler signal landing after
                # the in-kernel drain) — relaunching the fused kernel on it
                # could pass a wait early and serve stale buffers, so the
                # first trip escalates here. The pin below sends later
                # calls to the golden path, where host-level retries
                # (retry.call_with_retry / ElasticStep) remain safe.
                # Interpret mode rebuilds simulated semaphores per launch,
                # so in-place retry is sound there.
                last = True
            delay = 0.0 if last else delays[attempt]
            over_budget = (
                policy is not None
                and policy.total_delay_budget_s is not None
                and slept + delay > policy.total_delay_budget_s
            )
            if not last and not over_budget:
                health.record_retry(family, attempt + 1, delay, records=recs)
                _retry.get_clock().sleep(delay)
                slept += delay
                continue
            health.record_timeout(family, recs)
            # the elastic world is about to shrink (or already did): in
            # interpret mode the family pin record_timeout just made is
            # hardware-residue protection with nothing to protect — release
            # it so the rebuilt world runs the fused path, not the golden
            _elastic.maybe_release_family_pins()
            if cfg.raise_on_timeout:
                raise _records.DistTimeoutError(family, recs, world_size=n_world)
            if int_recs:
                # corruption raises regardless of the timeout posture —
                # these records were recorded/struck in the mixed handling
                _raise_integrity(int_recs, noted=True)
            return out

    def spanned_call(*args):
        # jit:{family} dispatch span (trace vs cached — the compile-cost
        # attribution ISSUE 9 asks of this boundary). Enablement checked
        # per call so stored wrappers pick up a mid-process arming; the
        # armed path legitimately re-resolves per call (healed fault
        # plans), so `cached` is read from the program cache itself.
        if not _obs.span_enabled():
            return call(*args)
        cached = _cache_key() in _jit_cache
        with _obs.span(f"jit:{family}", cat="jit", cached=cached,
                       armed=True):
            return call(*args)

    return spanned_call


def barrier_all_op(axis: str = "tp", interpret: Any = None) -> None:
    """Standalone device barrier over a mesh axis — call inside shard_map
    (≙ ``barrier_all_on_stream`` / ``barrier_all_intra_node_atomic_cas_block``,
    common_ops.py:87-193)."""

    def _kernel(out_ref):
        shmem.barrier_all(axis)
        out_ref[0] = jnp.int32(1)

    return dist_pallas_call(
        _kernel,
        name="barrier_all",
        out_shape=jax.ShapeDtypeStruct((1,), jnp.int32),
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        uses_barrier=int(jax.lax.axis_size(axis)) > 1,
        interpret=interpret,
    )()
