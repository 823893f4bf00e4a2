"""Block-aligned grouped GEMM for MoE expert compute
(≙ the grouped-GEMM halves of reference ``allgather_group_gemm.py:420``
``kernel_consumer_m_parallel_scatter_group_gemm`` and
``moe_reduce_rs.py:362`` ``kernel_producer_group_gemm_tp_scatter_input``).

Rows of `a` are pre-sorted by expert and padded so every ``block_m`` tile
belongs to one expert (see ``moe_utils.moe_align_block_size``); the owning
expert of each row-block arrives via scalar prefetch, steering the weight
BlockSpec's index_map — the TPU analogue of the reference reading its
device-side ``gather_index``/``expert_index`` tensors per tile; the MXU
pipeline is an ordinary tiled matmul whose B operand hops between experts.

Kernel bodies come from the pipeline emitter
(:mod:`triton_dist_tpu.ops.gg_pipeline`, ISSUE 7): operand format ×
tile validity × schedule as composable policies, the default tuple
bit-exact to the retired legacy kernels. Every public entry runs under
``resilience.guarded_call`` with a golden XLA implementation
(expert-sorted ``ragged_dot`` over the same padded layout) — the
degradation discipline every fused collective family carries (PR 1/6).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.ops.common import dist_pallas_call
from triton_dist_tpu.ops.gg_pipeline import (
    OperandFormat,
    make_group_gemm_dw_kernel,
    make_group_gemm_kernel,
)
from triton_dist_tpu.utils import pick_block


@dataclasses.dataclass(frozen=True)
class GroupGemmConfig:
    block_m: int = 128  # must equal the alignment block size
    block_n: int = 1024
    block_k: int = 512
    # Chunk-granular MoE overlap (ISSUE 4): the OVERLAPPED pipeline kernels
    # (ag_group_gemm_overlap ring + moe_reduce_rs_overlap combine pushes)
    # split each ring-step shard / combine slab into this many per-chunk
    # DMAs consumed the moment each lands. 1 (default) emits the legacy
    # shard-granular schedule bit for bit; the grid-based group_gemm and
    # the sequential compositions ignore it (nothing to chunk there).
    chunks_per_shard: int = 1
    # Ragged grouped GEMM (ISSUE 5, the MegaBlocks move): consume the
    # alignment's per-block (expert_id, valid_rows) map and spend MXU time
    # only on each block's live row panels (quantized to the 128-row MXU
    # tile), instead of computing every alignment pad row. Layout is
    # untouched — big block_m keeps amortizing the B-operand stream while
    # the pad tax (worst-case E·(block_m−1) rows the legacy grid always
    # computes) drops to the panel quantum. False (default) emits the
    # legacy padded schedule bit for bit.
    ragged: bool = False
    # w8 weights (ISSUE 7): quantize the expert bank to int8 + per-
    # (expert, out-column) f32 scales at the op boundary and stream HALF
    # the weight bytes through every grouped GEMM — including both fused
    # overlap pipelines, where the weight stream is the decode regime's
    # bound resource. On-the-fly quantize costs one bank read+write per
    # call, amortized over the pipelines' MANY weight-slab re-reads;
    # single-pass callers should feed pre-quantized pools through the
    # scale= operands instead. SERVING knob: forward-only; every backward
    # strips it (straight-through, ops.grads). False = bit-exact bf16.
    w8: bool = False
    # "pallas" (default) = the fused kernels above. "ragged_dot" = the XLA
    # sentinel (VERDICT r5 #1): the grouped GEMMs lower to
    # ``jax.lax.ragged_dot`` over the same padded layout — an in-tuner A/B
    # against XLA's own ragged kernel. Requires globally expert-sorted
    # blocks, so the MoE pipeline routes it through the sequential
    # composition.
    backend: str = "pallas"
    # Span-schedule policy of the OVERLAPPED pipelines (ISSUE 14): how the
    # per-ring-step shard / combine slab is tiled into chunk spans.
    # "contig" (default) is the legacy near-equal contiguous tiling of
    # ``ops.common.chunk_schedule``, bit for bit; the other names
    # ("window", "interleave", "torus2d" — ``ops.common.SPAN_POLICIES``)
    # are SYNTHESIZED schedules that enter tune spaces only after the
    # generate → prove → admit loop of ``triton_dist_tpu/synth`` proves
    # them credit-balanced and deadlock-free (docs/analysis.md). The grid
    # group_gemm and sequential compositions ignore it, like
    # chunks_per_shard.
    span_policy: str = "contig"
    # fp8 weights (ISSUE 19): quantize the expert bank to fp8_e4m3 + the
    # SAME per-(expert, out-column) f32 scale layout as w8 and stream the
    # weight bytes at quarter rate through every grouped GEMM — one rung
    # below w8 on the precision ladder, the remaining lever for the
    # still-sub-ceiling decode-shaped weight stream. Rides the w8 slot
    # structure verbatim (``OperandFormat.scaled``); exclusive with
    # ``w8``. SERVING knob like w8: forward-only, every backward strips
    # it. False = untouched. (Appended after span_policy so historical
    # positional constructions keep their meaning.)
    fp8: bool = False

    def __post_init__(self):
        if self.w8 and self.fp8:
            raise ValueError(
                "GroupGemmConfig: w8 and fp8 are exclusive operand formats"
            )


# The MXU row tile: live rows are quantized UP to this many before the
# ragged kernels skip a panel (a sub-128-row dot would waste the MXU's
# 128×128 systolic array anyway). Tests monkeypatch this to exercise
# panel skipping at interpreter-friendly block sizes.
_PANEL_ROWS = 128


def _panel_for(block_m: int) -> int:
    """Ragged row-panel size for a block: the largest power-of-2-shrinkable
    divisor of block_m at most the MXU row tile (shared picker semantics
    with the kernels' other block shapes)."""
    return pick_block(block_m, _PANEL_ROWS)


def quantize_expert_weights(b: jax.Array):
    """Per-(expert, out-column) absmax int8 quantization of expert weights
    ``[E, K, N]`` → ``(b_q int8, scale f32 [E, 1, N])`` for
    :func:`group_gemm_w8` / ``GroupGemmConfig(w8=True)``. Column
    granularity keeps the scale application a single row-broadcast multiply
    on the accumulator (the standard weight-only PTQ layout); ~0.2-0.5%
    RMS error on gaussian weights."""
    bf = b.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(bf), axis=1, keepdims=True) / 127.0, 1e-8)
    b_q = jnp.clip(jnp.round(bf / scale), -127, 127).astype(jnp.int8)
    return b_q, scale


# fp8_e4m3fn: the finite-max e4m3 variant every backend ships; 448 is its
# largest normal — the absmax maps onto it exactly as 127 does for int8.
FP8_DTYPE = jnp.float8_e4m3fn
_FP8_MAX = 448.0


def quantize_expert_weights_fp8(b: jax.Array):
    """Per-(expert, out-column) absmax fp8_e4m3 quantization of expert
    weights ``[E, K, N]`` → ``(b_q fp8, scale f32 [E, 1, N])`` for
    :func:`group_gemm_fp8` / ``GroupGemmConfig(fp8=True)`` — the int8
    quantizer's exact shape with 448 (the e4m3 max normal) in 127's seat
    and the rounding left to the dtype cast (e4m3 keeps a mantissa, so
    nearest-even beats pre-rounding). Scale layout is identical to
    :func:`quantize_expert_weights`, so every scale-fold site downstream
    is shared."""
    bf = b.astype(jnp.float32)
    scale = jnp.maximum(
        jnp.max(jnp.abs(bf), axis=1, keepdims=True) / _FP8_MAX, 1e-8
    )
    b_q = jnp.clip(bf / scale, -_FP8_MAX, _FP8_MAX).astype(FP8_DTYPE)
    return b_q, scale


def resolve_w8(b: jax.Array, scale: jax.Array | None, cfg: GroupGemmConfig):
    """The quantized-format config axes at an op boundary: with ``cfg.w8``
    or ``cfg.fp8`` and no caller scales, quantize the float bank on the
    fly; explicit ``scale`` (the pre-quantized serving path) wins.
    Returns ``(b, scale)``."""
    if scale is not None or not (cfg.w8 or getattr(cfg, "fp8", False)):
        return b, scale
    fp8 = getattr(cfg, "fp8", False)
    if not jnp.issubdtype(b.dtype, jnp.floating) or b.dtype == FP8_DTYPE:
        raise ValueError(
            f"GroupGemmConfig.{'fp8' if fp8 else 'w8'} with a pre-quantized "
            "weight bank needs the matching per-(expert, out-column) scale "
            "(pass scale=, from quantize_expert_weights"
            f"{'_fp8' if fp8 else ''})"
        )
    return (quantize_expert_weights_fp8 if fp8 else quantize_expert_weights)(b)


def _ragged_dot_group_gemm(
    a_sorted, b, expert_ids, *, scale, out_dtype, act_fn, n_exp, bm,
):
    """The XLA sentinel (``GroupGemmConfig.backend="ragged_dot"``):
    ``jax.lax.ragged_dot`` over the SAME padded block-aligned layout.
    Blocks must be globally expert-sorted (every in-repo global alignment
    is; the rank-major overlap layout is not — the pipeline routes the
    sentinel through the sequential composition). Pad rows are treated as
    real rows of their block's expert, exactly as the Pallas legacy kernel
    treats them, so outputs agree row for row on live rows."""
    ids = jnp.clip(expert_ids, 0, n_exp - 1)
    group_sizes = (jnp.bincount(ids, length=n_exp) * bm).astype(jnp.int32)
    a = a_sorted
    if act_fn is not None:
        a = act_fn(a.astype(jnp.float32)).astype(a_sorted.dtype)
    out = jax.lax.ragged_dot(
        a, b.astype(a.dtype) if scale is not None else b,
        group_sizes=group_sizes,
        preferred_element_type=jnp.float32,
    )
    if scale is not None:
        # per-row expert scale: rows of block i belong to expert ids[i]
        row_e = jnp.repeat(ids, bm)
        out = out * scale[row_e, 0, :]
    return out.astype(out_dtype)


def _group_gemm_xla(
    a_sorted, b, expert_ids, *, valid_rows, scale, ragged, bm, out_dtype,
    act_fn, into=None, **_,
):
    """The golden slow path (the program the kernel is tested against):
    globally expert-sort the blocks, one ``jax.lax.ragged_dot`` over the
    SAME padded layout, unsort — pad rows computed as real rows of their
    block's (clamped) expert, the w8 scale folded in f32 before the
    ragged dead-row mask, exactly the kernel contract. The sort/unsort
    (vs gathering a ``[nb, K, N]`` weight batch) keeps the fallback's
    memory at the bank size — degraded environments must not OOM."""
    n_exp = b.shape[0]
    nb = expert_ids.shape[0]
    ids = jnp.clip(expert_ids, 0, n_exp - 1)
    a = a_sorted
    if act_fn is not None:
        a = act_fn(a.astype(jnp.float32)).astype(a_sorted.dtype)
    order = jnp.argsort(ids, stable=True)
    inv = jnp.argsort(order)
    a3 = a.reshape(nb, bm, -1)
    group_sizes = (jnp.bincount(ids, length=n_exp) * bm).astype(jnp.int32)
    out = jax.lax.ragged_dot(
        a3[order].reshape(nb * bm, -1),
        b.astype(a.dtype) if scale is not None else b,
        group_sizes=group_sizes,
        preferred_element_type=jnp.float32,
    )
    if scale is not None:
        out = out * scale[jnp.repeat(ids[order], bm), 0, :]
    out = out.reshape(nb, bm, -1)[inv]
    if ragged:
        rows = jnp.arange(bm, dtype=jnp.int32)[None, :, None]
        out = jnp.where(rows < valid_rows[:, None, None], out, 0.0)
    out = out.reshape(nb * bm, -1).astype(out_dtype)
    if into is not None:
        buffer, first_block = into
        out = jax.lax.dynamic_update_slice_in_dim(
            buffer, out, first_block * bm, 0)
    return out


def dead_blocks_refetch_none(valid_rows: jax.Array) -> jax.Array:
    """:func:`group_gemm`'s ``a_blocks`` for a ragged alignment: each block
    its own, a block of no valid row the last live block before it (block
    0 before the first)."""
    blocks = jnp.arange(valid_rows.shape[0], dtype=jnp.int32)
    return jax.lax.cummax(jnp.where(valid_rows > 0, blocks, 0))


def _group_gemm_fused(
    a_sorted, b, expert_ids, *, valid_rows, scale, ragged, bm, out_dtype,
    act_fn, cfg, interpret, a_blocks=None, into=None,
):
    t_pad, k_dim = a_sorted.shape
    n_exp, _, n_dim = b.shape
    bn = pick_block(n_dim, cfg.block_n)
    bk = pick_block(k_dim, cfg.block_k)
    n_k = k_dim // bk
    # parallel dims must form a grid prefix: n-tiles first (megablox order)
    grid = (n_dim // bn, t_pad // bm, n_k)
    w8 = scale is not None
    out_shape = jax.ShapeDtypeStruct((t_pad, n_dim), out_dtype)
    aliases = None
    if ragged:
        # scalar-prefetched, in this order: e_ref, v_ref, then the index
        # maps' own. ``a``: the row block of A each block FETCHES (a dead
        # block reads no row of it, so it may name the block before's: an
        # unchanged block index costs no fetch, as ops/mla_decode.py's
        # page_map does past a sequence's length). ``first``: where in
        # ``into``'s buffer the result's first block goes.
        scalars = {"e": expert_ids, "v": valid_rows.astype(jnp.int32)}
        if a_blocks is not None:
            scalars["a"] = a_blocks.astype(jnp.int32)
        if into is not None:
            buffer, first_block = into
            scalars["first"] = jnp.asarray(first_block, jnp.int32).reshape(1)
        at = {name: n for n, name in enumerate(scalars)}
        a_row = (lambda i, s: s[at["a"]][i]) if "a" in at else (lambda i, s: i)
        o_row = ((lambda i, s: s[at["first"]][0] + i) if "first" in at
                 else (lambda i, s: i))
        in_specs = [
            pl.BlockSpec((bm, bk), lambda j, i, kk, *s: (a_row(i, s), kk)),
            pl.BlockSpec(
                (1, bk, bn), lambda j, i, kk, *s: (s[at["e"]][i], kk, j),
            ),
        ]
        args = [*scalars.values(), a_sorted, b]
        out_spec = pl.BlockSpec((bm, bn), lambda j, i, kk, *s: (o_row(i, s), j))
        if w8:
            in_specs.append(
                pl.BlockSpec(
                    (1, 1, bn), lambda j, i, kk, *s: (s[at["e"]][i], 0, j),
                )
            )
    else:
        in_specs = [
            pl.BlockSpec((bm, bk), lambda j, i, kk, e_ref: (i, kk)),
            pl.BlockSpec(
                (1, bk, bn), lambda j, i, kk, e_ref: (e_ref[i], kk, j)
            ),
        ]
        args = [expert_ids, a_sorted, b]
        out_spec = pl.BlockSpec((bm, bn), lambda j, i, kk, e_ref: (i, j))
        if w8:
            in_specs.append(
                pl.BlockSpec(
                    (1, 1, bn), lambda j, i, kk, e_ref: (e_ref[i], 0, j)
                )
            )
    fp8 = w8 and b.dtype == FP8_DTYPE  # format keyed off the BANK dtype
    if w8:
        args.append(scale.astype(jnp.float32))
        name = "group_gemm_fp8" if fp8 else "group_gemm_w8"
        w_bytes = n_exp * k_dim * n_dim  # int8/fp8: 1 byte
    else:
        name = "group_gemm"
        w_bytes = n_exp * k_dim * n_dim * b.dtype.itemsize
    kernel = make_group_gemm_kernel(
        n_k=n_k, out_dtype=out_dtype, act_fn=act_fn,
        fmt=OperandFormat(w8 and not fp8, fp8), ragged=ragged,
        panel=_panel_for(bm) if ragged else 0,
    )
    if into is not None:
        # the buffer is the output, aliased: blocks the grid does not
        # write keep what they held. It stays in HBM, and no ref of it
        # reaches the body
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        aliases = {len(args): 0}
        args.append(buffer)
        out_shape = jax.ShapeDtypeStruct(buffer.shape, buffer.dtype)
    if ragged and len(scalars) > 2:
        # the body knows e_ref, v_ref, a_ref, b_ref, [s_ref], o_ref, acc_ref
        body, n_own, at_buffer = kernel, len(scalars) - 2, 4 + w8

        def kernel(*refs):
            refs = list(refs)
            del refs[2:2 + n_own]
            if into is not None:
                del refs[at_buffer]
            body(*refs)

    return dist_pallas_call(
        kernel,
        name=name,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars) if ragged else 1,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * t_pad * k_dim * n_dim,
            bytes_accessed=(t_pad * k_dim + t_pad * n_dim)
            * a_sorted.dtype.itemsize + w_bytes,
            # the fused act_fn re-runs over every A tile once per n-tile
            transcendentals=(
                t_pad * k_dim * (n_dim // bn) if act_fn is not None else 0
            ),
        ),
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        input_output_aliases=aliases,
        uses_barrier=False,
        interpret=interpret,
    )(*args)


def group_gemm(
    a_sorted: jax.Array,
    b: jax.Array,
    expert_ids: jax.Array,
    *,
    valid_rows: jax.Array | None = None,
    scale: jax.Array | None = None,
    config: GroupGemmConfig | None = None,
    out_dtype: Any = None,
    act_fn: Any = None,
    interpret: Any = None,
    a_blocks: jax.Array | None = None,
    into: tuple[jax.Array, Any] | None = None,
) -> jax.Array:
    """``out[i*bm:(i+1)*bm] = a_sorted[i*bm:(i+1)*bm] @ b[expert_ids[i]]``.

    a_sorted: ``[t_pad, K]`` block-aligned rows; b: ``[E, K, N]``;
    expert_ids: ``[t_pad // block_m]`` int32 (runtime values — scalar
    prefetch). Returns ``[t_pad, N]``. Golden: the expert-sorted ragged_dot
    (served automatically when the kernel cannot build — resilience layer).

    ``act_fn`` (e.g. ``jax.nn.silu``) is applied to every A tile inside
    the kernel (f32, cast back to A's dtype) — the fused epilogue→
    producer form of ``group_gemm(act(a), ...)`` that deletes the
    standalone activation's full HBM pass over A; the redundant per-
    n-tile VPU recompute hides under the B-operand stream.

    With ``scale`` (``[E, 1, N]`` f32 from
    :func:`quantize_expert_weights`), `b` is an int8-quantized weight
    pool: B tiles upcast in-kernel, per-(expert, out-column) scales fold
    into the accumulator at the last K step. ``config.w8`` quantizes a
    float bank on the fly instead (:func:`resolve_w8`).

    With ``config.ragged`` (needs ``valid_rows`` — the alignment builders'
    per-block live-row map, ``moe_align_block_size(ragged=True)``) the
    kernel skips every dead 128-row panel instead of computing the
    alignment's worst-case pad rows (the ~25% MoE padding tax, VERDICT r5
    #1); dead rows come back exact zeros. ``ragged=False`` emits the
    legacy schedule bit for bit. ``a_blocks`` (``[t_pad // block_m]``
    int32, ragged only; :func:`dead_blocks_refetch_none` makes it) names
    the row block of `a_sorted` each block fetches: a block of no valid
    row reads none of it, and named after the last live block before it,
    it costs no fetch either. The result is the same with and without.
    ``into = (buffer [rows, N], first_block)`` (ragged only) writes the
    result over blocks ``[first_block, first_block + t_pad // block_m)``
    of ``buffer`` in place (the kernel's output IS the buffer, aliased;
    ``first_block`` may be traced) and returns the buffer: a pass that
    walks its sorted rows a run of blocks at a time builds one result
    with no copy.
    """
    from triton_dist_tpu import resilience

    cfg = config or GroupGemmConfig()
    t_pad = a_sorted.shape[0]
    n_exp = b.shape[0]
    out_dtype = out_dtype or a_sorted.dtype
    n_blocks = expert_ids.shape[0]
    assert t_pad % n_blocks == 0, (t_pad, n_blocks)
    bm = t_pad // n_blocks
    assert bm == cfg.block_m, (
        f"rows-per-block {bm} != config.block_m {cfg.block_m}: alignment and "
        f"GEMM must use the same block size"
    )
    b, scale = resolve_w8(b, scale, cfg)
    if cfg.backend == "ragged_dot":
        return _ragged_dot_group_gemm(
            a_sorted, b, expert_ids, scale=scale, out_dtype=out_dtype,
            act_fn=act_fn, n_exp=n_exp, bm=bm,
        )
    ragged = bool(cfg.ragged)
    if ragged and valid_rows is None:
        raise ValueError(
            "GroupGemmConfig.ragged needs the alignment's per-block "
            "valid_rows map — build it with moe_align_block_size(..., "
            "ragged=True) / moe_align_ranked(..., ragged=True)"
        )
    if scale is not None:
        assert scale.shape == (n_exp, 1, b.shape[2]), (scale.shape, b.shape)
    if (a_blocks is not None or into is not None) and not ragged:
        raise ValueError("a_blocks and into need GroupGemmConfig.ragged "
                         "(only a block of no valid row reads nothing of A)")
    if into is not None:
        assert into[0].shape[1] == b.shape[2] and into[0].dtype == out_dtype, (
            into[0].shape, into[0].dtype, b.shape, out_dtype)
    return resilience.guarded_call(
        "group_gemm",
        functools.partial(_group_gemm_fused, cfg=cfg, interpret=interpret),
        _group_gemm_xla,
        a_sorted, b, expert_ids, valid_rows=valid_rows, scale=scale,
        ragged=ragged, bm=bm, out_dtype=out_dtype, act_fn=act_fn,
        a_blocks=a_blocks, into=into,
    )


def group_gemm_w8(
    a_sorted: jax.Array,
    b_q: jax.Array,
    scale: jax.Array,
    expert_ids: jax.Array,
    *,
    valid_rows: jax.Array | None = None,
    config: GroupGemmConfig | None = None,
    out_dtype: Any = None,
    act_fn: Any = None,
    interpret: Any = None,
) -> jax.Array:
    """:func:`group_gemm` over int8-quantized expert weights (from
    :func:`quantize_expert_weights`): ``out[i·bm:(i+1)·bm] =
    (a_sorted[i·bm:(i+1)·bm] @ upcast(b_q[e])) · scale[e]``.

    The weight stream dominates grouped-GEMM HBM traffic at decode token
    counts (each expert's slab is read regardless of how few rows route
    to it), so int8 weights halve the bound resource. Thin alias of
    :func:`group_gemm` with the ``scale`` operand."""
    return group_gemm(
        a_sorted, b_q, expert_ids, valid_rows=valid_rows, scale=scale,
        config=config, out_dtype=out_dtype, act_fn=act_fn,
        interpret=interpret,
    )


def group_gemm_fp8(
    a_sorted: jax.Array,
    b_q: jax.Array,
    scale: jax.Array,
    expert_ids: jax.Array,
    *,
    valid_rows: jax.Array | None = None,
    config: GroupGemmConfig | None = None,
    out_dtype: Any = None,
    act_fn: Any = None,
    interpret: Any = None,
) -> jax.Array:
    """:func:`group_gemm` over fp8_e4m3-quantized expert weights (from
    :func:`quantize_expert_weights_fp8`) — :func:`group_gemm_w8`'s exact
    twin one precision rung down (ISSUE 19): the fp8 B tiles upcast
    in-kernel and the per-(expert, out-column) scales fold into the
    accumulator at the last K step, the shared ``OperandFormat.scaled``
    trace. Thin alias of :func:`group_gemm` with the ``scale`` operand;
    the format is keyed off the bank dtype."""
    return group_gemm(
        a_sorted, b_q, expert_ids, valid_rows=valid_rows, scale=scale,
        config=config, out_dtype=out_dtype, act_fn=act_fn,
        interpret=interpret,
    )


def _group_gemm_dw_xla(
    a_sorted, g_sorted, expert_ids, n_exp, *, valid_rows, ragged, bm, **_,
):
    """Golden dW: the scan of per-block AᵀG dots the fused kernel exists
    to replace — one ``[K, N]`` outer product per step accumulated onto
    the block's expert, so the fallback's working set is one tile, never
    a ``[nb, K, N]`` batch. Padded contract accumulates every row
    (callers pre-zero pad rows, as for the kernel); ragged zeroes each
    block's dead rows on A first — the kernel's in-kernel junk mask."""
    nb = expert_ids.shape[0]
    k_dim = a_sorted.shape[1]
    n_dim = g_sorted.shape[1]
    ids = jnp.clip(expert_ids, 0, n_exp - 1)
    a3 = a_sorted.reshape(nb, bm, k_dim).astype(jnp.float32)
    g3 = g_sorted.reshape(nb, bm, n_dim).astype(jnp.float32)
    if ragged:
        rows = jnp.arange(bm, dtype=jnp.int32)[None, :, None]
        a3 = jnp.where(rows < valid_rows[:, None, None], a3, 0.0)

    def step(acc, xs):
        a_b, g_b, e = xs
        return acc.at[e].add(
            jax.lax.dot_general(
                a_b, g_b, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        ), None

    acc0 = jnp.zeros((n_exp, k_dim, n_dim), jnp.float32)
    out, _ = jax.lax.scan(step, acc0, (a3, g3, ids))
    return out


def _group_gemm_dw_fused(
    a_sorted, g_sorted, expert_ids, n_exp, *, valid_rows, ragged, bm, cfg,
    interpret,
):
    t_pad, k_dim = a_sorted.shape
    n_dim = g_sorted.shape[1]
    n_blocks = expert_ids.shape[0]
    bk = pick_block(k_dim, cfg.block_k)
    bn = pick_block(n_dim, cfg.block_n)
    # i innermost: output-block visits for one (kk, nn) tile are grouped by
    # expert run; kk/nn never revisit a previously-left block
    grid = (k_dim // bk, n_dim // bn, n_blocks)
    if ragged:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (bm, bk), lambda kk, nn, i, e_ref, v_ref: (i, kk)
                ),
                pl.BlockSpec(
                    (bm, bn), lambda kk, nn, i, e_ref, v_ref: (i, nn)
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, bk, bn),
                lambda kk, nn, i, e_ref, v_ref: (e_ref[i], kk, nn),
            ),
            scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
        )
        args = (expert_ids, valid_rows.astype(jnp.int32), a_sorted, g_sorted)
    else:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda kk, nn, i, e_ref: (i, kk)),
                pl.BlockSpec((bm, bn), lambda kk, nn, i, e_ref: (i, nn)),
            ],
            out_specs=pl.BlockSpec(
                (1, bk, bn), lambda kk, nn, i, e_ref: (e_ref[i], kk, nn)
            ),
            scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
        )
        args = (expert_ids, a_sorted, g_sorted)
    kernel = make_group_gemm_dw_kernel(
        ragged=ragged, panel=_panel_for(bm) if ragged else 0
    )
    return dist_pallas_call(
        kernel,
        name="group_gemm_dw",
        out_shape=jax.ShapeDtypeStruct((n_exp, k_dim, n_dim), jnp.float32),
        grid_spec=grid_spec,
        cost_estimate=pl.CostEstimate(
            flops=2 * t_pad * k_dim * n_dim,
            bytes_accessed=(
                t_pad * (k_dim + n_dim) * a_sorted.dtype.itemsize
                + n_exp * k_dim * n_dim * 4
            ),
            transcendentals=0,
        ),
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        uses_barrier=False,
        interpret=interpret,
    )(*args)


def group_gemm_dw(
    a_sorted: jax.Array,
    g_sorted: jax.Array,
    expert_ids: jax.Array,
    n_exp: int,
    *,
    valid_rows: jax.Array | None = None,
    config: GroupGemmConfig | None = None,
    assume_sorted: bool = False,
    interpret: Any = None,
) -> jax.Array:
    """Transpose grouped GEMM: ``dW[e] = Σ_{blocks i of e} A_iᵀ @ G_i``
    (the expert-weight gradient of :func:`group_gemm`; ≙ the dW half the
    reference leaves to torch autograd — here a first-class MXU kernel
    instead of a scan of dots). No w8 axis: gradients accumulate against
    the full-precision bank (``ops.grads`` strips ``w8`` from every
    backward config).

    a_sorted ``[t_pad, K]``, g_sorted ``[t_pad, N]`` block-aligned rows in
    the SAME order; expert_ids ``[t_pad // block_m]``. Returns
    ``[n_exp, K, N]`` f32; experts with no rows come back exactly zero.

    The kernel's output-revisit accumulation needs each expert's blocks
    CONSECUTIVE in grid order, so blocks are grouped by expert up front —
    correctness insurance for arbitrary callers (the forward
    ``group_gemm`` is order-independent, so its VJP must be too). Callers
    whose ids come from ``moe_align_block_size`` (sorted by construction)
    pass ``assume_sorted=True`` to skip the two full-array permutation
    copies on the training hot path.
    """
    from triton_dist_tpu import resilience

    cfg = config or GroupGemmConfig()
    t_pad, k_dim = a_sorted.shape
    n_dim = g_sorted.shape[1]
    # enforce the id-range invariant here rather than by caller convention:
    # an out-of-range id would land its block's AᵀG in expert n_exp-1's dW
    # (the output index_map clamps) while the zero-row mask below counted it
    # as occupying a DIFFERENT bucket — clamping first keeps both consistent
    expert_ids = jnp.clip(expert_ids, 0, n_exp - 1)
    n_blocks = expert_ids.shape[0]
    assert t_pad % n_blocks == 0 and t_pad // n_blocks == cfg.block_m, (
        t_pad, n_blocks, cfg.block_m,
    )
    bm = cfg.block_m
    ragged = bool(cfg.ragged) and cfg.backend == "pallas"
    if ragged and valid_rows is None:
        raise ValueError(
            "GroupGemmConfig.ragged needs the alignment's per-block "
            "valid_rows map (moe_align_block_size(..., ragged=True))"
        )
    if not assume_sorted:
        order = jnp.argsort(expert_ids, stable=True)
        expert_ids = expert_ids[order]
        if ragged:
            valid_rows = valid_rows[order]
        a_sorted = a_sorted.reshape(n_blocks, bm, k_dim)[order].reshape(
            t_pad, k_dim
        )
        g_sorted = g_sorted.reshape(n_blocks, bm, n_dim)[order].reshape(
            t_pad, n_dim
        )
    out = resilience.guarded_call(
        "group_gemm_dw",
        functools.partial(_group_gemm_dw_fused, cfg=cfg, interpret=interpret),
        _group_gemm_dw_xla,
        a_sorted, g_sorted, expert_ids, n_exp, valid_rows=valid_rows,
        ragged=ragged, bm=bm,
    )
    # an expert with zero rows never has its output block visited — that
    # memory is undefined, not zero; mask it (where, not multiply: the
    # garbage may be NaN)
    counts = jnp.bincount(
        jnp.clip(expert_ids, 0, n_exp - 1), length=n_exp
    )
    return jnp.where(counts[:, None, None] > 0, out, 0.0)
