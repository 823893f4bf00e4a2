"""Distributed GQA flash-decode — sequence/context parallelism for decode
(≙ reference ``kernels/nvidia/flash_decode.py``, 1160 LoC, and the SP layer
``layers/nvidia/sp_flash_decode_layer.py``).

The reference pipeline (SURVEY.md §3.5): per-rank split-KV attention over the
local KV shard (``kernel_gqa_fwd_batch_decode_split_kv`` :130) → intra-rank
combine (:393) → LL-protocol allgather of (acc, lse) → inter-rank combine
with the numerically-stable online-softmax merge (:482-530).

TPU-native re-design:

- **split-KV + intra-rank combine collapse into one kernel.** GPU split-KV
  exists to fill idle SMs with independent KV spans; a TPU core executes the
  Pallas grid sequentially with a pipelined memory stream, so the idiomatic
  form is a single online-softmax pass over KV chunks (grid dim = chunk,
  carry (m, l, acc) in VMEM scratch). Nothing to combine intra-rank.
- **The LL protocol is unnecessary.** The reference packs payload+flag into
  8-byte words so receivers spin on data (low_latency_allgather.py:532-571);
  TPU remote DMAs carry data-coupled completion semaphores, so the plain
  ``full_mesh_push`` allgather (allgather.py) IS the low-latency path.
- **Inter-rank combine** keeps the reference's (acc‖lse) merge algebra —
  it is exactly blockwise/ring-attention math — expressed as XLA elementwise
  ops, which fuse into a single kernel without hand-writing one.

Layouts: q ``[batch, q_heads, head_dim]`` (one decode token per sequence),
KV cache ``[batch, kv_heads, seq, head_dim]`` with valid prefix ``kv_lens``
per sequence (contiguous cache; a paged variant would add a block-table via
scalar prefetch in the index_map, same kernel body).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
import re
from typing import Any

import jax
import jax.numpy as jnp

from triton_dist_tpu.utils import axis_size as _axis_size
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu import resilience
from triton_dist_tpu.autotuner import contextual_autotune
from triton_dist_tpu.ops.allgather import all_gather
from triton_dist_tpu.ops.common import dist_pallas_call, jit_shard_map
from triton_dist_tpu.parallel import topology
from triton_dist_tpu.utils import cdiv, pick_block

NEG_INF = float("-inf")

# fp8 KV cache (ISSUE 19): same payload dtype + absmax ceiling as the
# weight path's fp8_e4m3 format (ops/group_gemm.py) — the kernels are
# payload-dtype generic (the in-kernel bf16 upcast covers int8 AND fp8),
# so fp8 only changes the quantizer and the guard/kernel names.
FP8_KV_DTYPE = jnp.float8_e4m3fn
_FP8_KV_MAX = 448.0


def _scoped_vmem_limit_bytes() -> int:
    """XLA's per-kernel scoped-vmem stack limit: pipeline buffers + scratch
    of ONE pallas_call must fit this, regardless of how much physical VMEM
    the generation has — chip-measured r5: a 16.19 MB allocation is
    rejected with "limit 16.00M" on v5e while vmem_bytes() reports 128 MB.

    Deployments override the limit with ``--xla_tpu_scoped_vmem_limit_kib``
    (in XLA_FLAGS or LIBTPU_INIT_ARGS) or ``TDT_SCOPED_VMEM_LIMIT_KIB``;
    the grid auto-selection must respect that, not a baked-in constant —
    read it per call (flags can be set after import), 16 MiB fallback."""
    kib = os.environ.get("TDT_SCOPED_VMEM_LIMIT_KIB")
    if kib is None:
        for var in ("XLA_FLAGS", "LIBTPU_INIT_ARGS"):
            m = re.search(
                r"--xla_tpu_scoped_vmem_limit_kib=(\d+)",
                os.environ.get(var, ""),
            )
            if m:
                kib = m.group(1)
                break
    return int(kib) * 1024 if kib is not None else 16 * 2**20

# Per-step attention span both paged grids aim for when auto-picking
# pages_per_step: the contiguous sweep's winning block_s on chip (r5) —
# smaller spans pay the per-tile mask/max/exp/sum fixed costs too often.
_TARGET_SPAN = 4096
# Narrowest span at which the paged decode's fused-heads grid is preferred
# over a per-head grid that affords a wider one (chip, PR 38: see
# _paged_flash_decode_fused).
_FUSED_MIN_SPAN = 512


def _auto_pages_per_step(
    slab: int, page_size: int, max_pages: int, resident: int = 0,
) -> int:
    """Page slots per grid step for a paged decode/verify grid whose
    per-page K or V slab is ``slab`` bytes: enough slots to reach the
    target span (at least one when a single page already exceeds it),
    bounded by the table width and by what the double-buffered K+V
    pipeline (4·slab·P) affords under the scoped-VMEM budget after
    ``resident`` bytes (q/out/lse blocks + scratch accumulators the
    grid holds across the whole pass — the verify grids' rows make
    these significant). Returns 0 when not even one slot fits — the
    caller must prefer the other grid.

    Prefers the largest P ≤ the cap that DIVIDES the table width (down
    to cap/2): a non-divisor pads the last step with clamped duplicate
    page fetches — dead DMAs the length mask discards (chip r5: the
    quant fused grid measured 247 µs at the cap P=12 over a 32-page
    table vs 193 at the divisor P=8)."""
    cap = min(
        max(1, _TARGET_SPAN // page_size), max_pages,
        max(0, _fused_slab_vmem_budget() - resident) // (4 * slab),
    )
    for p in range(cap, max(1, cap // 2) - 1, -1):
        if max_pages % p == 0:
            return p
    return cap


def _fused_slab_vmem_budget() -> int:
    """fuse_heads auto-guard: the fused paged kernel's double-buffered K+V
    page slabs must fit this conservative VMEM slice (see
    :func:`paged_flash_decode`). Bounded by BOTH the generation's VMEM
    (half of it — accumulators, q, outs and the compiler's own scratch
    share the rest) and XLA's scoped-vmem stack limit less a 2 MiB
    allowance for those residents. Derived from the topology table (not
    a constant) so a generation with smaller VMEM auto-selects the
    per-head grid instead of failing to compile."""
    return min(
        topology.vmem_bytes() // 2, _scoped_vmem_limit_bytes() - 2 * 2**20
    )


@dataclasses.dataclass(frozen=True)
class FlashDecodeConfig:
    """Tunables (≙ the reference's split-KV block knobs).

    ``block_s=0`` selects the XLA-native formulation instead of the Pallas
    kernel: the same masked softmax-attention program XLA compiles into a
    fused HBM-bandwidth-bound loop. It is a first-class tuning candidate —
    on chips where XLA's fusion already sits at the memory wall (measured
    v5e: XLA 344 µs vs Pallas 460 µs at b=8 hq=64 s=8192) the idiomatic
    TPU answer is to let XLA have the contiguous bf16 case; the Pallas
    kernel remains the only path for paged and int8-quantized caches.

    ``fuse_heads=True`` moves the kv-head loop INSIDE the kernel: the grid
    drops from (b, h_kv, chunks) to (b, chunks) and each step streams one
    K slab + one V slab covering every kv head. At decode shapes the
    per-step work is tiny (the GQA matmuls pad their handful of q rows up
    to the MXU's 128), so the h_kv-fold reduction in grid steps — fewer
    fixed per-step costs, h_kv-fold larger DMA transfers — is what moves
    a kernel sitting below the HBM wall toward it.

    ``soft_cap`` (> 0) applies the logit soft-cap of the reference's
    split-KV kernel (flash_decode.py:103-107; Gemma-2-family models):
    ``s = soft_cap * tanh(s / soft_cap)`` on the SCALED scores before
    masking, identically on every path (Pallas per-head / fused-heads /
    paged / int8 and the XLA goldens, decode AND verify) so the SP merge
    and the golden fallbacks stay exact twins. 0.0 (default) = disabled —
    bit-identical to the pre-knob kernels."""

    block_s: int = 2048  # KV chunk per online-softmax step; 0 = XLA-native
    fuse_heads: bool = False  # kv-head loop inside the kernel body
    soft_cap: float = 0.0  # logit soft-cap; 0 = off


def _kernel_head_dim(d: int) -> int:
    """The head dim the Pallas kernels run at. Power-of-2 dims pass
    through unchanged (today's shapes); a NON-power-of-2 head dim — the
    reference handles these with a BLOCK_DMODEL + BLOCK_DPE tail split
    (flash_decode.py:155-190) — is zero-padded up to the next power of
    two at the host boundary and the output sliced back. Zero d-columns
    are exact: padded q·k terms add 0 to every score and padded v columns
    produce 0 output columns that the slice discards, so (out, lse) are
    bit-identical to the unpadded math. ``scale`` always uses the TRUE
    head dim. The XLA-native goldens take any d natively — they are the
    CPU reference the padded kernels are pinned against."""
    if d < 1:
        raise ValueError(f"head dim must be >= 1, got {d}")
    p = 1
    while p < d:
        p <<= 1
    return p


def _pad_head_dim(x, d_pad: int):
    """Zero-pad the trailing (head) dim of ``x`` up to ``d_pad``."""
    d = x.shape[-1]
    if d == d_pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, d_pad - d)])


def _online_softmax_step(
    q, k_b, v_b, ks_row, vs_row, chunk_start, kv_len, scale,
    m_prev, l_prev, acc_prev, soft_cap=0.0, kv_lo=None, bias=None,
):
    """One KV-chunk update of one head's online-softmax carry; the single
    source of the decode math for the per-head AND fused-heads kernels.
    Returns ``(m_new, l_new, acc_new)``.

    Both matmuls run in the cache dtype (bf16 MXU fast path, f32
    accumulate); the f32-upcast variant costs a full VPU pass over
    every K/V tile and measured 25% slower than the HBM-bandwidth
    wall this kernel otherwise sits on. ``ks_row``/``vs_row`` are None on
    the plain path; when present (int8 cache) the K/V tiles upcast to bf16
    (riding under the halved DMA time) and the per-position row scales
    fold into the scores / probabilities. ``soft_cap`` > 0 (a static
    Python float — the branch resolves at trace time) squashes the scaled
    scores through ``soft_cap * tanh(s / soft_cap)`` BEFORE the length
    mask, after any int8 dequant scale — the reference's logit soft-cap,
    in the one place all five kernel paths share. ``kv_lo`` (window
    layers; None adds no op) also masks the positions below it; ``bias``
    (``[1, sc]`` float32, 0 or ``-inf`` a position; None adds no op) masks
    the positions a sparse selection left out. Leading
    dims of ``q``, the tiles and the carry (the paged decode's kv heads)
    are batch dims of both matmuls: one body for every head of a step."""
    if ks_row is not None:
        k_b = k_b.astype(jnp.bfloat16)
        v_b = v_b.astype(jnp.bfloat16)
    lead = q.ndim - 2                 # leading (head) dims ride as batch
    batch = (tuple(range(lead)),) * 2
    s = jax.lax.dot_general(                            # [.., g, sc]
        q, k_b, (((lead + 1,), (lead + 1,)), batch),
        preferred_element_type=jnp.float32,
    ) * (scale if ks_row is None else ks_row * scale)
    if soft_cap:
        s = soft_cap * jnp.tanh(s / soft_cap)
    span = chunk_start + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, lead + 1)
    s = jnp.where(span < kv_len, s, NEG_INF)
    if kv_lo is not None:
        s = jnp.where(span >= kv_lo, s, NEG_INF)
    if bias is not None:
        s = s + bias
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # all-masked rows keep m_new == -inf: subtract a clamped copy so the
    # update is exp(-inf) = 0, not exp(-inf - -inf) = NaN. The verify
    # kernel hits this (per-ROW lengths — a zero-length row shares its
    # grid step with live rows); the single-position kernels' chunk gate
    # merely made it unreachable.
    m_safe = jnp.maximum(m_new, -1e30)
    alpha = jnp.exp(m_prev - m_safe)
    p = jnp.exp(s - m_safe)                             # [.., g, sc]
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = p if vs_row is None else p * vs_row
    acc_new = acc_prev * alpha + jax.lax.dot_general(
        pv.astype(v_b.dtype), v_b, (((lead + 1,), (lead,)), batch),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


def _finalize_softmax(m, l, acc):
    """(out, lse) from a finished carry. kv_len == 0 → l == 0: emit out=0,
    lse=-inf (weight 0 in the SP merge)."""
    out = jnp.where(l > 0, acc / jnp.maximum(l, 1e-30), 0.0)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return out, lse


def _flash_decode_body(
    kv_lens_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, out_ref, lse_ref,
    m_scr, l_scr, acc_scr, *, n_chunks: int, block_s: int, scale: float,
    soft_cap: float = 0.0,
):
    """Per-head online-softmax decode body: grid (b, h_kv, chunk)."""
    b_i = pl.program_id(0)
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    kv_len = kv_lens_ref[b_i]

    @pl.when(c * block_s < kv_len)
    def _():
        m_scr[:], l_scr[:], acc_scr[:] = _online_softmax_step(
            q_ref[0, 0], k_ref[0, 0], v_ref[0, 0],
            None if ks_ref is None else ks_ref[0, 0],
            None if vs_ref is None else vs_ref[0, 0],
            c * block_s, kv_len, scale, m_scr[:], l_scr[:], acc_scr[:],
            soft_cap,
        )

    @pl.when(c == n_chunks - 1)
    def _():
        out_ref[0, 0], lse_ref[0, 0] = _finalize_softmax(
            m_scr[:], l_scr[:], acc_scr[:]
        )


def _flash_decode_kernel(
    kv_lens_ref, q_ref, k_ref, v_ref, out_ref, lse_ref, m_scr, l_scr, acc_scr,
    **kw,
):
    _flash_decode_body(
        kv_lens_ref, q_ref, k_ref, v_ref, None, None, out_ref, lse_ref,
        m_scr, l_scr, acc_scr, **kw,
    )


def _fused_heads_core(
    c, gate_len, row_len, q_ref, k_ref, v_ref, ks_ref, vs_ref, out_ref,
    lse_ref, m_scr, l_scr, acc_scr,
    *, n_chunks: int, block_s: int, scale: float, h_kv: int,
    soft_cap: float = 0.0,
):
    """Shared ``fuse_heads`` skeleton (decode AND verify): all kv heads of
    the chunk arrive in ONE K slab + ONE V slab, the head loop unrolls
    inside the step, scratches carry a leading h_kv dim. ``gate_len``
    (scalar) skips whole chunks; ``row_len`` (scalar for decode, a
    per-row column for verify) masks inside the step."""
    @pl.when(c == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(c * block_s < gate_len)
    def _():
        for j in range(h_kv):  # static unroll over the slab's heads
            m_scr[j], l_scr[j], acc_scr[j] = _online_softmax_step(
                q_ref[0, j], k_ref[0, j], v_ref[0, j],
                None if ks_ref is None else ks_ref[0, j],
                None if vs_ref is None else vs_ref[0, j],
                c * block_s, row_len, scale,
                m_scr[j], l_scr[j], acc_scr[j], soft_cap,
            )

    @pl.when(c == n_chunks - 1)
    def _():
        out_ref[0], lse_ref[0] = _finalize_softmax(
            m_scr[:], l_scr[:], acc_scr[:]
        )


def _flash_decode_fused_heads_body(
    kv_lens_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, out_ref, lse_ref,
    m_scr, l_scr, acc_scr, **kw,
):
    kv_len = kv_lens_ref[pl.program_id(0)]
    _fused_heads_core(
        pl.program_id(1), kv_len, kv_len, q_ref, k_ref, v_ref, ks_ref,
        vs_ref, out_ref, lse_ref, m_scr, l_scr, acc_scr, **kw,
    )


def _flash_decode_fused_heads_kernel(
    kv_lens_ref, q_ref, k_ref, v_ref, out_ref, lse_ref, m_scr, l_scr, acc_scr,
    **kw,
):
    _flash_decode_fused_heads_body(
        kv_lens_ref, q_ref, k_ref, v_ref, None, None, out_ref, lse_ref,
        m_scr, l_scr, acc_scr, **kw,
    )


def _flash_decode_fused_heads_quant_kernel(*refs, **kw):
    _flash_decode_fused_heads_body(*refs, **kw)


def flash_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_lens: jax.Array,
    *,
    config: FlashDecodeConfig | None = None,
    return_lse: bool = False,
    interpret: Any = None,
):
    """Single-device GQA batch decode (≙ ``gqa_fwd_batch_decode_intra_rank``,
    reference flash_decode.py:763).

    q: ``[b, q_heads, d]``; k, v: ``[b, kv_heads, s, d]``; kv_lens: ``[b]``
    int32 valid prefix lengths. Returns f32 ``[b, q_heads, d]`` (and the
    per-head log-sum-exp ``[b, q_heads]`` if `return_lse` — the partial pair
    the SP merge consumes).
    """
    return _decode_call(
        q, k, v, None, kv_lens, config=config, return_lse=return_lse,
        interpret=interpret,
    )


def _xla_decode(q, k, v, kv_lens, *, return_lse, soft_cap=0.0, kv_lo=None):
    """XLA-native GQA decode (``FlashDecodeConfig(block_s=0)``): a masked
    softmax attention XLA fuses into one HBM-bound loop. f32 score/prob
    math matches the Pallas kernel's accumulation precision; the (out, lse)
    contract is identical, so the SP combine consumes either path. Takes
    any head dim natively (no tile padding) — the CPU golden for the
    kernels' non-power-of-2 head-dim padding; ``soft_cap`` applies the
    same pre-mask logit squash as :func:`_online_softmax_step`; ``kv_lo
    [b]`` (window layers) also masks the positions below it."""
    b, hq, d = q.shape
    _, h_kv, s_len, _ = k.shape
    g = hq // h_kv
    q4 = q.reshape(b, h_kv, g, d).astype(jnp.float32)
    s = jnp.einsum(
        "bhgd,bhsd->bhgs", q4, k.astype(jnp.float32)
    ) / math.sqrt(d)
    if soft_cap:
        s = soft_cap * jnp.tanh(s / soft_cap)
    span = jnp.arange(s_len, dtype=jnp.int32)
    s = jnp.where(span[None, None, None, :] < kv_lens[:, None, None, None], s, NEG_INF)
    if kv_lo is not None:
        s = jnp.where(
            span[None, None, None, :] >= kv_lo[:, None, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m_safe = jnp.maximum(m, -1e30)  # kv_len==0 rows: avoid inf-inf
    p = jnp.exp(s - m_safe)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhgs,bhsd->bhgd", p, v.astype(jnp.float32))
    out = (out / jnp.maximum(l, 1e-30)).reshape(b, hq, d)
    out = jnp.where(l.reshape(b, hq, 1) > 0, out, 0.0)
    if not return_lse:
        return out
    lse = (m_safe + jnp.log(jnp.maximum(l, 1e-30))).reshape(b, hq)
    lse = jnp.where(l.reshape(b, hq) > 0, lse, NEG_INF)
    return out, lse


def _decode_call(q, k, v, scales, kv_lens, *, config, return_lse, interpret):
    """Shared host-side builder for the plain and int8 decode paths; the
    only deltas are the two optional scale operands and the q dtype.

    The bf16 path degrades to :func:`_xla_decode` when the Pallas kernel
    cannot run in this environment (resilience layer, docs/resilience.md);
    int8 caches have no golden slow path, so their failures stay loud."""
    cfg = config or FlashDecodeConfig()
    if cfg.block_s == 0:
        if scales is not None:
            raise ValueError(
                "block_s=0 (XLA-native) supports only the contiguous bf16 "
                "cache; int8/fp8/paged caches need the Pallas kernel"
            )
        return _xla_decode(
            q, k, v, kv_lens.astype(jnp.int32), return_lse=return_lse,
            soft_cap=cfg.soft_cap,
        )
    if scales is None:
        family = "flash_decode"
    else:
        family = (
            "flash_decode_fp8" if k.dtype == FP8_KV_DTYPE
            else "flash_decode_quant"
        )
    return resilience.guarded_call(
        family,
        lambda: _decode_call_fused(
            q, k, v, scales, kv_lens, cfg=cfg, return_lse=return_lse,
            interpret=interpret,
        ),
        None if scales is not None else (
            lambda: _xla_decode(
                q, k, v, kv_lens.astype(jnp.int32), return_lse=return_lse,
                soft_cap=cfg.soft_cap,
            )
        ),
    )


def _decode_call_fused(q, k, v, scales, kv_lens, *, cfg, return_lse, interpret):
    b, hq, d = q.shape
    _, h_kv, s_len, _ = k.shape
    assert hq % h_kv == 0, (hq, h_kv)
    g = hq // h_kv
    sc = pick_block(s_len, cfg.block_s)
    n_chunks = s_len // sc
    scale = 1.0 / math.sqrt(d)  # the TRUE head dim, before any padding
    d_out, d = d, _kernel_head_dim(d)
    if d != d_out:  # non-pow-2 head dim: zero-pad, slice the output back
        q, k, v = (_pad_head_dim(x, d) for x in (q, k, v))
    # the kernel's matmuls run in the cache dtype (bf16 MXU fast path);
    # mixed-precision callers get their q silently matched to the cache —
    # int8 caches upcast in-kernel, so their q rides bf16
    q4 = q.reshape(b, h_kv, g, d).astype(
        jnp.bfloat16 if scales is not None else k.dtype
    )
    args = [kv_lens.astype(jnp.int32), q4, k, v]
    fp8 = scales is not None and k.dtype == FP8_KV_DTYPE
    if scales is None:
        kv_bytes = 2 * b * h_kv * s_len * d * k.dtype.itemsize
    else:
        args += [scales[0].astype(jnp.float32), scales[1].astype(jnp.float32)]
        kv_bytes = 2 * b * h_kv * s_len * (d + 4)  # 1B payload + f32 scale
    cost = pl.CostEstimate(
        flops=4 * b * hq * s_len * d,
        bytes_accessed=kv_bytes,
        transcendentals=b * hq * s_len,
    )
    if cfg.fuse_heads:
        # grid (b, chunk): each step's K/V slab spans every kv head — h_kv×
        # fewer grid steps and h_kv× larger DMAs (see FlashDecodeConfig)
        grid = (b, n_chunks)
        in_specs = [
            pl.BlockSpec(memory_space=pltpu.SMEM),  # kv_lens
            pl.BlockSpec((1, h_kv, g, d), lambda i, c: (i, 0, 0, 0)),
            pl.BlockSpec((1, h_kv, sc, d), lambda i, c: (i, 0, c, 0)),
            pl.BlockSpec((1, h_kv, sc, d), lambda i, c: (i, 0, c, 0)),
        ]
        if scales is None:
            name, kernel = "flash_decode_fh", _flash_decode_fused_heads_kernel
        else:
            name = "flash_decode_fh_fp8" if fp8 else "flash_decode_fh_quant"
            kernel = _flash_decode_fused_heads_quant_kernel
            scale_spec = pl.BlockSpec(
                (1, h_kv, 1, sc), lambda i, c: (i, 0, 0, c)
            )
            in_specs += [scale_spec, scale_spec]
        out, lse = dist_pallas_call(
            functools.partial(
                kernel, n_chunks=n_chunks, block_s=sc, scale=scale, h_kv=h_kv,
                soft_cap=cfg.soft_cap,
            ),
            name=name,
            grid=grid,
            out_shape=(
                jax.ShapeDtypeStruct((b, h_kv, g, d), jnp.float32),
                jax.ShapeDtypeStruct((b, h_kv, g, 1), jnp.float32),
            ),
            in_specs=in_specs,
            out_specs=(
                pl.BlockSpec((1, h_kv, g, d), lambda i, c: (i, 0, 0, 0)),
                pl.BlockSpec((1, h_kv, g, 1), lambda i, c: (i, 0, 0, 0)),
            ),
            scratch_shapes=[
                pltpu.VMEM((h_kv, g, 1), jnp.float32),
                pltpu.VMEM((h_kv, g, 1), jnp.float32),
                pltpu.VMEM((h_kv, g, d), jnp.float32),
            ],
            cost_estimate=cost,
            dimension_semantics=("parallel", "arbitrary"),
            uses_barrier=False,
            interpret=interpret,
        )(*args)
        out = out.reshape(b, hq, d)[..., :d_out]
        lse = lse.reshape(b, hq)
        return (out, lse) if return_lse else out
    grid = (b, h_kv, n_chunks)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),  # kv_lens
        pl.BlockSpec((1, 1, g, d), lambda i, j, c: (i, j, 0, 0)),
        pl.BlockSpec((1, 1, sc, d), lambda i, j, c: (i, j, c, 0)),
        pl.BlockSpec((1, 1, sc, d), lambda i, j, c: (i, j, c, 0)),
    ]
    if scales is None:
        name, kernel = "flash_decode", _flash_decode_kernel
    else:
        name = "flash_decode_fp8" if fp8 else "flash_decode_quant"
        kernel = _flash_decode_quant_kernel
        scale_spec = pl.BlockSpec((1, 1, 1, sc), lambda i, j, c: (i, j, 0, c))
        in_specs += [scale_spec, scale_spec]
    out, lse = dist_pallas_call(
        functools.partial(
            kernel, n_chunks=n_chunks, block_s=sc, scale=scale,
            soft_cap=cfg.soft_cap,
        ),
        name=name,
        grid=grid,
        out_shape=(
            jax.ShapeDtypeStruct((b, h_kv, g, d), jnp.float32),
            # 4-D with a unit minor dim: Mosaic wants the trailing block dims
            # to equal the array dims (g < 8 sublanes is fine when full).
            jax.ShapeDtypeStruct((b, h_kv, g, 1), jnp.float32),
        ),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, 1, g, d), lambda i, j, c: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda i, j, c: (i, j, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        cost_estimate=cost,
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        uses_barrier=False,
        interpret=interpret,
    )(*args)
    out = out.reshape(b, hq, d)[..., :d_out]
    lse = lse.reshape(b, hq)
    return (out, lse) if return_lse else out



def _flash_verify_body(
    max_lens_ref, lens_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, out_ref,
    lse_ref, m_scr, l_scr, acc_scr, *, n_chunks: int, block_s: int,
    scale: float, soft_cap: float = 0.0,
):
    """Multi-position (speculative-verify) decode body: grid
    (b, h_kv, chunk) exactly like :func:`_flash_decode_body`, but the q
    block carries ``S*g`` rows — S draft positions × the GQA group — and
    each ROW masks its own cache prefix via a per-row length column
    (``lens_ref``, VMEM). The per-sequence MAX length (SMEM) gates whole
    chunks. The S-fold wider score matmul is the point: the cache streams
    from HBM ONCE for all S draft positions, where S single-token decodes
    would stream it S times — and the MXU sees S*g rows instead of g.
    ``ks_ref``/``vs_ref`` are None on the plain path; when present
    (quantized cache) the per-position row scales fold exactly as in
    :func:`_flash_decode_body`."""
    b_i = pl.program_id(0)
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(c * block_s < max_lens_ref[b_i])
    def _():
        m_scr[:], l_scr[:], acc_scr[:] = _online_softmax_step(
            q_ref[0, 0], k_ref[0, 0], v_ref[0, 0],
            None if ks_ref is None else ks_ref[0, 0],
            None if vs_ref is None else vs_ref[0, 0],
            c * block_s, lens_ref[0, 0], scale,
            m_scr[:], l_scr[:], acc_scr[:], soft_cap,
        )

    @pl.when(c == n_chunks - 1)
    def _():
        out_ref[0, 0], lse_ref[0, 0] = _finalize_softmax(
            m_scr[:], l_scr[:], acc_scr[:]
        )


def _flash_verify_kernel(
    max_lens_ref, lens_ref, q_ref, k_ref, v_ref, out_ref, lse_ref,
    m_scr, l_scr, acc_scr, **kw,
):
    _flash_verify_body(
        max_lens_ref, lens_ref, q_ref, k_ref, v_ref, None, None, out_ref,
        lse_ref, m_scr, l_scr, acc_scr, **kw,
    )


def _flash_verify_quant_kernel(*refs, **kw):
    _flash_verify_body(*refs, **kw)


def _xla_verify(q, k, v, kv_lens, *, return_lse, soft_cap=0.0):
    """XLA-native multi-position decode (block_s=0 sentinel + golden):
    per-(sequence, position) prefix masks over one einsum. Any head dim,
    same ``soft_cap`` contract as :func:`_xla_decode`."""
    b, S, hq, d = q.shape
    _, h_kv, s_len, _ = k.shape
    g = hq // h_kv
    q5 = q.reshape(b, S, h_kv, g, d).astype(jnp.float32)
    s = jnp.einsum(
        "bshgd,bhtd->bshgt", q5, k.astype(jnp.float32)
    ) / math.sqrt(d)
    if soft_cap:
        s = soft_cap * jnp.tanh(s / soft_cap)
    span = jnp.arange(s_len, dtype=jnp.int32)
    mask = span[None, None, :] < kv_lens[:, :, None]       # [b, S, t]
    s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m_safe = jnp.maximum(m, -1e30)
    p = jnp.exp(s - m_safe)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bshgt,bhtd->bshgd", p, v.astype(jnp.float32))
    out = (out / jnp.maximum(l, 1e-30)).reshape(b, S, hq, d)
    out = jnp.where(l.reshape(b, S, hq, 1) > 0, out, 0.0)
    if not return_lse:
        return out
    lse = (m_safe + jnp.log(jnp.maximum(l, 1e-30))).reshape(b, S, hq)
    lse = jnp.where(l.reshape(b, S, hq) > 0, lse, NEG_INF)
    return out, lse


def flash_verify(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_lens: jax.Array,
    *,
    config: FlashDecodeConfig | None = None,
    return_lse: bool = False,
    interpret: Any = None,
):
    """Multi-position GQA decode — the speculative-decoding VERIFY
    attention (beyond the reference, whose serving surface stops at
    single-token decode): score S draft positions of every sequence in
    ONE pass over the cache.

    q: ``[b, S, q_heads, d]`` (position i = draft token i); k, v:
    ``[b, kv_heads, s, d]`` with the S draft tokens' own k/v ALREADY
    WRITTEN; kv_lens: ``[b, S]`` int32 — row (b, i) attends cache
    positions ``< kv_lens[b, i]`` (the verify caller passes
    ``pos0+i+1``: its prefix plus draft tokens ``<= i`` — causal within
    the chunk via the cache). Returns f32 ``[b, S, q_heads, d]`` (+
    ``lse [b, S, q_heads]``)."""
    cfg = config or FlashDecodeConfig()
    assert q.shape[2] % k.shape[1] == 0, (q.shape, k.shape)
    kv_lens = kv_lens.astype(jnp.int32)
    if cfg.block_s == 0:
        return _xla_verify(
            q, k, v, kv_lens, return_lse=return_lse, soft_cap=cfg.soft_cap
        )
    return resilience.guarded_call(
        "flash_verify",
        lambda: _flash_verify_fused(
            q, k, v, kv_lens, cfg=cfg, return_lse=return_lse,
            interpret=interpret,
        ),
        lambda: _xla_verify(
            q, k, v, kv_lens, return_lse=return_lse, soft_cap=cfg.soft_cap
        ),
    )


def _flash_verify_fused(q, k, v, kv_lens, *, cfg, return_lse, interpret,
                        scales=None):
    b, S, hq, d = q.shape
    _, h_kv, s_len, _ = k.shape
    g = hq // h_kv
    sc = pick_block(s_len, cfg.block_s)
    n_chunks = s_len // sc
    rows = S * g
    scale = 1.0 / math.sqrt(d)  # the TRUE head dim, before any padding
    d_out, d = d, _kernel_head_dim(d)
    if d != d_out:
        q, k, v = (_pad_head_dim(x, d) for x in (q, k, v))
    # quantized caches upcast in-kernel, so their q rides bf16 (the same
    # contract as _decode_call_fused)
    q5 = (
        q.reshape(b, S, h_kv, g, d)
        .swapaxes(1, 2)
        .reshape(b, h_kv, rows, d)
        .astype(jnp.bfloat16 if scales is not None else k.dtype)
    )
    # per-row length column: row s*g + j masks with kv_lens[b, s]
    lens_rows = jnp.repeat(kv_lens, g, axis=1).reshape(b, 1, rows, 1)
    max_lens = jnp.max(kv_lens, axis=1)
    cost = pl.CostEstimate(
        flops=4 * b * S * hq * s_len * d,
        bytes_accessed=2 * b * h_kv * s_len * (
            (d + 4) if scales is not None else d * k.dtype.itemsize
        ),
        transcendentals=b * S * hq * s_len,
    )
    args = [max_lens, lens_rows, q5, k, v]
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),  # max_lens (chunk gate)
        pl.BlockSpec((1, 1, rows, 1), lambda i, j, c: (i, 0, 0, 0)),
        pl.BlockSpec((1, 1, rows, d), lambda i, j, c: (i, j, 0, 0)),
        pl.BlockSpec((1, 1, sc, d), lambda i, j, c: (i, j, c, 0)),
        pl.BlockSpec((1, 1, sc, d), lambda i, j, c: (i, j, c, 0)),
    ]
    if scales is None:
        name, kernel = "flash_verify", _flash_verify_kernel
    else:
        name = (
            "flash_verify_fp8" if k.dtype == FP8_KV_DTYPE
            else "flash_verify_quant"
        )
        kernel = _flash_verify_quant_kernel
        args += [scales[0].astype(jnp.float32), scales[1].astype(jnp.float32)]
        scale_spec = pl.BlockSpec((1, 1, 1, sc), lambda i, j, c: (i, j, 0, c))
        in_specs += [scale_spec, scale_spec]
    out, lse = dist_pallas_call(
        functools.partial(
            kernel, n_chunks=n_chunks, block_s=sc,
            scale=scale, soft_cap=cfg.soft_cap,
        ),
        name=name,
        grid=(b, h_kv, n_chunks),
        out_shape=(
            jax.ShapeDtypeStruct((b, h_kv, rows, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h_kv, rows, 1), jnp.float32),
        ),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, 1, rows, d), lambda i, j, c: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, rows, 1), lambda i, j, c: (i, j, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
        cost_estimate=cost,
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        uses_barrier=False,
        interpret=interpret,
    )(*args)
    out = (
        out.reshape(b, h_kv, S, g, d).swapaxes(1, 2)
        .reshape(b, S, hq, d)[..., :d_out]
    )
    lse = lse.reshape(b, h_kv, S, g).swapaxes(1, 2).reshape(b, S, hq)
    return (out, lse) if return_lse else out


def flash_verify_distributed(
    q: jax.Array,
    k_shard: jax.Array,
    v_shard: jax.Array,
    lens_shard: jax.Array,
    *,
    axis: str = "tp",
    config: FlashDecodeConfig | None = None,
    ag_method: str = "full_mesh_push",
    interpret: Any = None,
) -> jax.Array:
    """SP form of :func:`flash_verify` (call inside ``jax.shard_map``):
    per-shard multi-position partials, then the same (out ‖ lse)
    allgather-merge the single-token SP decode rides — the S dim folds
    into the payload's row dim."""
    out, lse = flash_verify(
        q, k_shard, v_shard, lens_shard,
        config=config, return_lse=True, interpret=interpret,
    )
    b, S, hq, d = out.shape
    merged = _sp_allgather_combine(
        out.reshape(b * S, hq, d), lse.reshape(b * S, hq), axis, ag_method,
        interpret,
    )
    return merged.reshape(b, S, hq, d)


def _paged_to_contiguous(pages, block_table):
    """Gather a paged pool back into per-sequence contiguous caches:
    ``[n_pages, h_kv, page, d]`` + ``[b, max_pages]`` →
    ``[b, h_kv, max_pages*page, d]`` — a pure XLA gather, so the paged
    entries get a golden slow path with the identical masking contract
    (positions past ``kv_lens`` are masked either way)."""
    b, max_pages = block_table.shape
    x = pages[block_table.astype(jnp.int32)]  # [b, max_pages, h_kv, pg, d]
    _, _, h_kv, page, d = x.shape
    return x.swapaxes(1, 2).reshape(b, h_kv, max_pages * page, d)


def _xla_paged_decode(q, k_pages, v_pages, kv_lens, block_table, *,
                      return_lse=False, soft_cap=0.0):
    """Golden slow path for the paged decode: block-table gather to a
    contiguous cache + the XLA-native masked attention."""
    return _xla_decode(
        q, _paged_to_contiguous(k_pages, block_table),
        _paged_to_contiguous(v_pages, block_table),
        kv_lens, return_lse=return_lse, soft_cap=soft_cap,
    )


def _window_pages(window: int, page_size: int, max_pages: int) -> int:
    """Pages that can hold ``window`` consecutive positions (one more
    than the window's own where it starts inside a page), never more
    than the table is wide."""
    return min(cdiv(window, page_size) + 1, max_pages)


def _xla_paged_window_decode(q, k_pages, v_pages, kv_lens, block_table, *,
                             window, return_lse=False, soft_cap=0.0):
    """Golden slow path of the WINDOW form (the CPU tests' twin; on the
    chip a fallback is a health flip): gather the pages that hold ``[kv_len
    - window, kv_len)`` through the table (column = logical page modulo
    the table's width) into a contiguous span that starts at the window's
    first page, and mask by position within it."""
    page = k_pages.shape[2]
    max_pages = block_table.shape[1]
    lo = jnp.maximum(kv_lens - window, 0)                      # [b]
    first = lo // page
    logical = first[:, None] + jnp.arange(
        _window_pages(window, page, max_pages))[None, :]
    table = jnp.take_along_axis(
        block_table.astype(jnp.int32), logical % max_pages, axis=1)
    base = first * page
    return _xla_decode(
        q, _paged_to_contiguous(k_pages, table),
        _paged_to_contiguous(v_pages, table), kv_lens - base,
        return_lse=return_lse, soft_cap=soft_cap, kv_lo=lo - base,
    )


def _xla_paged_verify(q, k_pages, v_pages, kv_lens, block_table, *,
                      return_lse=False, soft_cap=0.0):
    """Golden slow path for the paged multi-position verify."""
    return _xla_verify(
        q, _paged_to_contiguous(k_pages, block_table),
        _paged_to_contiguous(v_pages, block_table),
        kv_lens, return_lse=return_lse, soft_cap=soft_cap,
    )


def _paged_flash_verify_kernel(
    max_lens_ref, bt_ref, lens_ref, q_ref, *rest,
    n_steps: int, pages_per_step: int, page_size: int, scale: float,
    h_kv: int, chunk_dim: int, soft_cap: float = 0.0,
):
    """Paged verify over ``pages_per_step`` pages concatenated into one
    [rows, P·page] span per step (same r5 chip finding as
    :func:`_paged_flash_decode_kernel`, whose shared-body shape this
    mirrors: fused grid = pool ``h_kv`` + ``chunk_dim=1``, per-head
    grid = the ``h_kv=1, chunk_dim=2`` instance). The per-sequence max
    length gates whole steps; the per-row length column masks inside
    the span. Clamped duplicate tail slots are length-masked: their
    span positions are >= max_pages*page >= every row length."""
    del bt_ref
    P = pages_per_step
    kv_refs = rest[: 2 * P]
    out_ref, lse_ref, m_scr, l_scr, acc_scr = rest[2 * P :]
    c = pl.program_id(chunk_dim)

    @pl.when(c == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(c * P * page_size < max_lens_ref[pl.program_id(0)])
    def _():
        for j in range(h_kv):  # static unroll over the slab's heads
            k_cat = jnp.concatenate(
                [kv_refs[2 * p][0, j] for p in range(P)], axis=0
            ) if P > 1 else kv_refs[0][0, j]
            v_cat = jnp.concatenate(
                [kv_refs[2 * p + 1][0, j] for p in range(P)], axis=0
            ) if P > 1 else kv_refs[1][0, j]
            m_scr[j], l_scr[j], acc_scr[j] = _online_softmax_step(
                q_ref[0, j], k_cat, v_cat, None, None,
                c * P * page_size, lens_ref[0, 0], scale,
                m_scr[j], l_scr[j], acc_scr[j], soft_cap,
            )

    @pl.when(c == n_steps - 1)
    def _():
        out_ref[0], lse_ref[0] = _finalize_softmax(
            m_scr[:], l_scr[:], acc_scr[:]
        )


def paged_flash_verify(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    kv_lens: jax.Array,
    block_table: jax.Array,
    *,
    fuse_heads: bool | None = None,
    pages_per_step: int | None = None,
    soft_cap: float = 0.0,
    return_lse: bool = False,
    interpret: Any = None,
):
    """Multi-position decode over a PAGED cache — :func:`flash_verify`
    with the block-table indirection of :func:`paged_flash_decode`: q
    ``[b, S, q_heads, d]``, kv_lens ``[b, S]`` per-row prefix lengths,
    pages/table as in the paged decode (the S chunk positions' k/v
    already written into their pages). ``fuse_heads`` /
    ``pages_per_step`` (None = the same span-driven auto as
    :func:`paged_flash_decode`, with the verify rows' larger
    q/out/accumulator residents counted against the VMEM budget);
    ``soft_cap`` as in :class:`FlashDecodeConfig` (the paged entries take
    it directly — their knobs are kwargs, not a config).
    Degrades to the gather-reconstructed :func:`_xla_paged_verify` golden
    when the Pallas kernel cannot run in this environment (resilience
    layer, docs/resilience.md)."""
    assert q.shape[2] % k_pages.shape[1] == 0, (q.shape, k_pages.shape)
    kv_lens = kv_lens.astype(jnp.int32)
    return resilience.guarded_call(
        "paged_flash_verify",
        lambda: _paged_flash_verify_fused(
            q, k_pages, v_pages, kv_lens, block_table,
            fuse_heads=fuse_heads, pages_per_step=pages_per_step,
            soft_cap=soft_cap, return_lse=return_lse, interpret=interpret,
        ),
        lambda: _xla_paged_verify(
            q, k_pages, v_pages, kv_lens, block_table,
            return_lse=return_lse, soft_cap=soft_cap,
        ),
    )


def _paged_flash_verify_fused(
    q, k_pages, v_pages, kv_lens, block_table, *,
    fuse_heads, pages_per_step, soft_cap, return_lse, interpret,
):
    b, S, hq, d = q.shape
    n_pages, h_kv, page_size, _ = k_pages.shape
    g = hq // h_kv
    rows = S * g
    max_pages = block_table.shape[1]
    scale = 1.0 / math.sqrt(d)  # the TRUE head dim, before any padding
    d_out, d = d, _kernel_head_dim(d)
    if d != d_out:  # pad the q and the page pools; slice the output back
        q, k_pages, v_pages = (
            _pad_head_dim(x, d) for x in (q, k_pages, v_pages)
        )
    # per-head-grid resident bytes (q block in the cache dtype, f32
    # out/lse blocks, f32 m/l/acc scratches); the fused grid holds h_kv×
    slab_h = page_size * d * k_pages.dtype.itemsize
    res_h = rows * (
        d * k_pages.dtype.itemsize + (d + 1) * 4 + (d + 2) * 4
    )
    p_f = _auto_pages_per_step(
        h_kv * slab_h, page_size, max_pages, resident=h_kv * res_h
    )
    p_h = _auto_pages_per_step(slab_h, page_size, max_pages, resident=res_h)
    if fuse_heads is None:
        fuse_heads = p_f >= 1 and p_f >= p_h
    if pages_per_step is None and (p_f if fuse_heads else p_h) == 0:
        # the SELECTED grid (auto never picks a dead grid while the other
        # lives, but an explicit fuse_heads can force one) affords not even
        # ONE page slot: without this check the forced pages_per_step=1
        # dies deep inside Mosaic compilation with an allocation error
        # naming none of these numbers
        raise ValueError(
            f"paged_flash_verify: the selected "
            f"{'fused' if fuse_heads else 'per-head'} grid affords no "
            f"single page slot under the scoped-VMEM budget — "
            f"rows=S*g={rows} (S={S}, g={g}), page_size={page_size}, "
            f"head_dim={d}, h_kv={h_kv}: residents "
            f"{(h_kv * res_h) if fuse_heads else res_h} B + one "
            f"double-buffered K+V page slot "
            f"{4 * ((h_kv * slab_h) if fuse_heads else slab_h)} B exceed "
            f"the {_fused_slab_vmem_budget()} B budget "
            f"(--xla_tpu_scoped_vmem_limit_kib / TDT_SCOPED_VMEM_LIMIT_KIB "
            f"raises it). Reduce S or page_size, toggle fuse_heads, or use "
            f"flash_verify on a contiguous cache."
        )
    if pages_per_step is None:
        pages_per_step = max(1, p_f if fuse_heads else p_h)
    P = pages_per_step
    n_steps = cdiv(max_pages, P)
    q5 = (
        q.reshape(b, S, h_kv, g, d)
        .swapaxes(1, 2)
        .reshape(b, h_kv, rows, d)
        .astype(k_pages.dtype)
    )
    lens_rows = jnp.repeat(kv_lens, g, axis=1).reshape(b, 1, rows, 1)
    max_lens = jnp.max(kv_lens, axis=1)
    cost = pl.CostEstimate(
        flops=4 * b * S * hq * max_pages * page_size * d,
        bytes_accessed=(2 * b * h_kv * max_pages * page_size * d)
        * k_pages.dtype.itemsize,
        transcendentals=b * S * hq * max_pages * page_size,
    )
    if fuse_heads:
        def kv_index_map_fh_p(p):
            def index_map(i, c, max_lens_ref, bt_ref):
                return (
                    bt_ref[i, jnp.minimum(c * P + p, max_pages - 1)], 0, 0, 0,
                )
            return index_map

        page_spec = lambda p: pl.BlockSpec(
            (1, h_kv, page_size, d), kv_index_map_fh_p(p)
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_steps),
            in_specs=[
                pl.BlockSpec((1, 1, rows, 1), lambda i, c, *_: (i, 0, 0, 0)),
                pl.BlockSpec((1, h_kv, rows, d), lambda i, c, *_: (i, 0, 0, 0)),
                *(page_spec(p) for p in range(P) for _ in (0, 1)),
            ],
            out_specs=(
                pl.BlockSpec((1, h_kv, rows, d), lambda i, c, *_: (i, 0, 0, 0)),
                pl.BlockSpec((1, h_kv, rows, 1), lambda i, c, *_: (i, 0, 0, 0)),
            ),
            scratch_shapes=[
                pltpu.VMEM((h_kv, rows, 1), jnp.float32),
                pltpu.VMEM((h_kv, rows, 1), jnp.float32),
                pltpu.VMEM((h_kv, rows, d), jnp.float32),
            ],
        )
        out, lse = dist_pallas_call(
            functools.partial(
                _paged_flash_verify_kernel,
                n_steps=n_steps, pages_per_step=P, page_size=page_size,
                scale=scale, h_kv=h_kv, chunk_dim=1, soft_cap=soft_cap,
            ),
            name="paged_flash_verify_fh",
            grid_spec=grid_spec,
            out_shape=(
                jax.ShapeDtypeStruct((b, h_kv, rows, d), jnp.float32),
                jax.ShapeDtypeStruct((b, h_kv, rows, 1), jnp.float32),
            ),
            cost_estimate=cost,
            dimension_semantics=("parallel", "arbitrary"),
            uses_barrier=False,
            interpret=interpret,
        )(
            max_lens, block_table.astype(jnp.int32), lens_rows, q5,
            *(kv for _ in range(P) for kv in (k_pages, v_pages)),
        )
        out = (
            out.reshape(b, h_kv, S, g, d).swapaxes(1, 2)
            .reshape(b, S, hq, d)[..., :d_out]
        )
        lse = lse.reshape(b, h_kv, S, g).swapaxes(1, 2).reshape(b, S, hq)
        return (out, lse) if return_lse else out

    def kv_index_map_p(p):
        def index_map(i, j, c, max_lens_ref, bt_ref):
            return (bt_ref[i, jnp.minimum(c * P + p, max_pages - 1)], j, 0, 0)
        return index_map

    page_spec = lambda p: pl.BlockSpec(
        (1, 1, page_size, d), kv_index_map_p(p)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h_kv, n_steps),
        in_specs=[
            pl.BlockSpec((1, 1, rows, 1), lambda i, j, c, *_: (i, 0, 0, 0)),
            pl.BlockSpec((1, 1, rows, d), lambda i, j, c, *_: (i, j, 0, 0)),
            *(page_spec(p) for p in range(P) for _ in (0, 1)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, rows, d), lambda i, j, c, *_: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, rows, 1), lambda i, j, c, *_: (i, j, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((1, rows, 1), jnp.float32),
            pltpu.VMEM((1, rows, 1), jnp.float32),
            pltpu.VMEM((1, rows, d), jnp.float32),
        ],
    )
    # the shared body's h_kv=1 instance (leading head dim on scratches)
    out, lse = dist_pallas_call(
        functools.partial(
            _paged_flash_verify_kernel,
            n_steps=n_steps, pages_per_step=P, page_size=page_size,
            scale=scale, h_kv=1, chunk_dim=2, soft_cap=soft_cap,
        ),
        name="paged_flash_verify",
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((b, h_kv, rows, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h_kv, rows, 1), jnp.float32),
        ),
        cost_estimate=cost,
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        uses_barrier=False,
        interpret=interpret,
    )(
        max_lens, block_table.astype(jnp.int32), lens_rows, q5,
        *(kv for _ in range(P) for kv in (k_pages, v_pages)),
    )
    out = (
        out.reshape(b, h_kv, S, g, d).swapaxes(1, 2)
        .reshape(b, S, hq, d)[..., :d_out]
    )
    lse = lse.reshape(b, h_kv, S, g).swapaxes(1, 2).reshape(b, S, hq)
    return (out, lse) if return_lse else out


def paged_flash_verify_distributed(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    lens_shard: jax.Array,
    block_table: jax.Array,
    *,
    axis: str = "tp",
    fuse_heads: bool | None = None,
    pages_per_step: int | None = None,
    soft_cap: float = 0.0,
    ag_method: str = "full_mesh_push",
    interpret: Any = None,
) -> jax.Array:
    """SP form of :func:`paged_flash_verify` (call inside shard_map):
    per-shard multi-position partials over each PE's page pool, merged by
    the shared (out ‖ lse) allgather tail."""
    out, lse = paged_flash_verify(
        q, k_pages, v_pages, lens_shard, block_table,
        fuse_heads=fuse_heads, pages_per_step=pages_per_step,
        soft_cap=soft_cap, return_lse=True, interpret=interpret,
    )
    b, S, hq, d = out.shape
    merged = _sp_allgather_combine(
        out.reshape(b * S, hq, d), lse.reshape(b * S, hq), axis, ag_method,
        interpret,
    )
    return merged.reshape(b, S, hq, d)


def _ranged_local_lens(pos0, S, axis, s_shard):
    """Per-(sequence, range-row) valid prefix in THIS PE's sequence shard
    for a suffix-only ranged prefill: row i of the range attends global
    positions ``<= pos0 + i`` — exact causal masking across the range
    boundary — and this PE covers ``[me*s_shard, (me+1)*s_shard)``."""
    me = jax.lax.axis_index(axis)
    pos_mat = (
        jnp.asarray(pos0, jnp.int32).reshape(-1, 1)
        + jnp.arange(S, dtype=jnp.int32)[None, :]
    )                                                      # [b, S]
    return jnp.clip(pos_mat + 1 - me * s_shard, 0, s_shard).astype(jnp.int32)


def flash_ranged_prefill_distributed(
    q: jax.Array,
    k_shard: jax.Array,
    v_shard: jax.Array,
    pos0: jax.Array,
    *,
    axis: str = "tp",
    config: FlashDecodeConfig | None = None,
    ag_method: str = "full_mesh_push",
    interpret: Any = None,
) -> jax.Array:
    """Suffix-only RANGED prefill over a contiguous SP cache (call inside
    ``jax.shard_map``) — the flash family's attend-to-prior-cache prefill
    (ROADMAP #2): q carries a prompt RANGE's rows ``[b, S, q_heads, d]``
    at global positions ``pos0 .. pos0+S-1`` whose own k/v are ALREADY
    WRITTEN into the shard; row i attends every landed position
    ``<= pos0+i``. The per-row prefix lengths are derived from ``pos0``
    here and the multi-position verify attention runs unchanged, so
    composing consecutive ranges is bit-identical to one whole-prompt
    pass: every row's mask names the same global prefix either way."""
    S = q.shape[1]
    lens = _ranged_local_lens(pos0, S, axis, k_shard.shape[2])
    return flash_verify_distributed(
        q, k_shard, v_shard, lens,
        axis=axis, config=config, ag_method=ag_method, interpret=interpret,
    )


def paged_flash_ranged_prefill_distributed(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    pos0: jax.Array,
    block_table: jax.Array,
    *,
    axis: str = "tp",
    fuse_heads: bool | None = None,
    pages_per_step: int | None = None,
    soft_cap: float = 0.0,
    ag_method: str = "full_mesh_push",
    interpret: Any = None,
) -> jax.Array:
    """Paged twin of :func:`flash_ranged_prefill_distributed`: the same
    suffix-only ranged prefill over each PE's page POOL, with the range's
    prior pages named by ``block_table`` (the reference's block-table
    indirection) — per-row lengths from ``pos0``, then the paged
    multi-position verify."""
    S = q.shape[1]
    s_shard = block_table.shape[1] * k_pages.shape[2]
    lens = _ranged_local_lens(pos0, S, axis, s_shard)
    return paged_flash_verify_distributed(
        q, k_pages, v_pages, lens, block_table,
        axis=axis, fuse_heads=fuse_heads, pages_per_step=pages_per_step,
        soft_cap=soft_cap, ag_method=ag_method, interpret=interpret,
    )


def quantize_kv(k: jax.Array, v: jax.Array):
    """Per-(batch, head, position) absmax int8 quantization of a KV cache
    (k, v ``[b, h_kv, s, d]``) → ``(k_q, v_q, k_scale, v_scale)`` with
    int8 payloads and ``[b, h_kv, 1, s]`` f32 row scales (scale layout is
    lane-major so the kernel broadcasts it over the head group without a
    relayout). Halves the decode kernel's HBM traffic — the resource it is
    bound by — at ~0.4% RMS error per row."""

    def q1(x):
        xf = x.astype(jnp.float32)
        s = jnp.max(jnp.abs(xf), axis=-1) / 127.0            # [b, h, s]
        s = jnp.maximum(s, 1e-8)
        xq = jnp.clip(jnp.round(xf / s[..., None]), -127, 127).astype(jnp.int8)
        return xq, s[:, :, None, :]                          # [b, h, 1, s]

    k_q, k_s = q1(k)
    v_q, v_s = q1(v)
    return k_q, v_q, k_s, v_s


def _flash_decode_quant_kernel(*refs, **kw):
    _flash_decode_body(*refs, **kw)


def flash_decode_quant(
    q: jax.Array,
    k_q: jax.Array,
    v_q: jax.Array,
    k_scale: jax.Array,
    v_scale: jax.Array,
    kv_lens: jax.Array,
    *,
    config: FlashDecodeConfig | None = None,
    return_lse: bool = False,
    interpret: Any = None,
):
    """GQA batch decode over an int8-quantized KV cache (from
    :func:`quantize_kv`) — same contract as :func:`flash_decode`, half the
    HBM traffic, with one precision delta: `q` is cast to bfloat16 for the
    MXU fast path (the int8 cache upcasts to bf16 in-kernel), so f32
    queries lose precision here that the plain path would keep. Composes
    with the SP merge via ``return_lse``."""
    return _decode_call(
        q, k_q, v_q, (k_scale, v_scale), kv_lens, config=config,
        return_lse=return_lse, interpret=interpret,
    )


def flash_decode_quant_distributed(
    q: jax.Array,
    k_q: jax.Array,
    v_q: jax.Array,
    k_scale: jax.Array,
    v_scale: jax.Array,
    kv_lens_shard: jax.Array,
    *,
    axis: str = "tp",
    config: FlashDecodeConfig | None = None,
    ag_method: str = "full_mesh_push",
    interpret: Any = None,
) -> jax.Array:
    """SP/CP decode over an int8 KV cache: per-shard quantized partials,
    standard (out, lse) merge."""
    out, lse = flash_decode_quant(
        q, k_q, v_q, k_scale, v_scale, kv_lens_shard,
        config=config, return_lse=True, interpret=interpret,
    )
    return _sp_allgather_combine(out, lse, axis, ag_method, interpret)


def quantize_kv_fp8(k: jax.Array, v: jax.Array):
    """fp8_e4m3 twin of :func:`quantize_kv` (ISSUE 19): per-(batch, head,
    position) absmax rows at the e4m3 ceiling (448) instead of int8's 127,
    same ``[b, h_kv, 1, s]`` f32 scale layout. The payload is 1 byte like
    int8 — the traffic win over int8 is on the WIRE and weight paths; here
    fp8 trades int8's uniform 8-bit grid for e4m3's tapered one (denser
    near zero, where attention logits live)."""

    def q1(x):
        xf = x.astype(jnp.float32)
        s = jnp.max(jnp.abs(xf), axis=-1) / _FP8_KV_MAX       # [b, h, s]
        s = jnp.maximum(s, 1e-8)
        xq = jnp.clip(xf / s[..., None], -_FP8_KV_MAX, _FP8_KV_MAX).astype(
            FP8_KV_DTYPE
        )
        return xq, s[:, :, None, :]                           # [b, h, 1, s]

    k_q, k_s = q1(k)
    v_q, v_s = q1(v)
    return k_q, v_q, k_s, v_s


def flash_decode_fp8(
    q: jax.Array,
    k_q: jax.Array,
    v_q: jax.Array,
    k_scale: jax.Array,
    v_scale: jax.Array,
    kv_lens: jax.Array,
    *,
    config: FlashDecodeConfig | None = None,
    return_lse: bool = False,
    interpret: Any = None,
):
    """GQA batch decode over an fp8-quantized KV cache (from
    :func:`quantize_kv_fp8`) — the fp8 twin of :func:`flash_decode_quant`:
    the same upcast-in-kernel shape (fp8 tiles rise to bf16 under the
    halved DMA time, row scales fold into scores/probabilities), the same
    q→bf16 contract, ``soft_cap`` and non-pow-2 head dims ride through."""
    return _decode_call(
        q, k_q, v_q, (k_scale, v_scale), kv_lens, config=config,
        return_lse=return_lse, interpret=interpret,
    )


def flash_decode_fp8_distributed(
    q: jax.Array,
    k_q: jax.Array,
    v_q: jax.Array,
    k_scale: jax.Array,
    v_scale: jax.Array,
    kv_lens_shard: jax.Array,
    *,
    axis: str = "tp",
    config: FlashDecodeConfig | None = None,
    ag_method: str = "full_mesh_push",
    interpret: Any = None,
) -> jax.Array:
    """SP/CP decode over an fp8 KV cache: per-shard fp8 partials,
    standard (out, lse) merge — the fp8 twin of
    :func:`flash_decode_quant_distributed`."""
    out, lse = flash_decode_fp8(
        q, k_q, v_q, k_scale, v_scale, kv_lens_shard,
        config=config, return_lse=True, interpret=interpret,
    )
    return _sp_allgather_combine(out, lse, axis, ag_method, interpret)


def flash_verify_fp8(
    q: jax.Array,
    k_q: jax.Array,
    v_q: jax.Array,
    k_scale: jax.Array,
    v_scale: jax.Array,
    kv_lens: jax.Array,
    *,
    config: FlashDecodeConfig | None = None,
    return_lse: bool = False,
    interpret: Any = None,
):
    """Multi-position verify over an fp8 KV cache — :func:`flash_verify`
    with the decode family's quantized-cache contract (per-position row
    scales fold in-kernel, q rides bf16). Quantized caches have no golden
    slow path, so failures stay loud."""
    cfg = config or FlashDecodeConfig()
    assert q.shape[2] % k_q.shape[1] == 0, (q.shape, k_q.shape)
    kv_lens = kv_lens.astype(jnp.int32)
    if cfg.block_s == 0:
        raise ValueError(
            "block_s=0 (XLA-native) supports only the contiguous bf16 "
            "cache; fp8 caches need the Pallas kernel"
        )
    return resilience.guarded_call(
        "flash_verify_fp8",
        lambda: _flash_verify_fused(
            q, k_q, v_q, kv_lens, cfg=cfg, return_lse=return_lse,
            interpret=interpret, scales=(k_scale, v_scale),
        ),
        None,
    )


def flash_ranged_prefill_fp8_distributed(
    q: jax.Array,
    k_q_shard: jax.Array,
    v_q_shard: jax.Array,
    k_scale: jax.Array,
    v_scale: jax.Array,
    pos0: jax.Array,
    *,
    axis: str = "tp",
    config: FlashDecodeConfig | None = None,
    ag_method: str = "full_mesh_push",
    interpret: Any = None,
) -> jax.Array:
    """fp8 twin of :func:`flash_ranged_prefill_distributed`: suffix-only
    ranged prefill over a contiguous fp8 SP cache shard (call inside
    ``jax.shard_map``) — per-row prefix lengths from ``pos0``, the fp8
    multi-position verify, then the standard (out ‖ lse) merge."""
    S = q.shape[1]
    lens = _ranged_local_lens(pos0, S, axis, k_q_shard.shape[2])
    out, lse = flash_verify_fp8(
        q, k_q_shard, v_q_shard, k_scale, v_scale, lens,
        config=config, return_lse=True, interpret=interpret,
    )
    b, S, hq, d = out.shape
    merged = _sp_allgather_combine(
        out.reshape(b * S, hq, d), lse.reshape(b * S, hq), axis, ag_method,
        interpret,
    )
    return merged.reshape(b, S, hq, d)


def _live_pages(kv_len, page_size: int, n_slots: int, window: int | None):
    """``(first, n)``: the logical pages that hold the positions a row of
    length ``kv_len`` attends — ``[0, kv_len)``, or a window's ``[kv_len -
    window, kv_len)`` — run from page ``first`` for ``n`` pages: through the
    page of position ``kv_len - 1``, none for an empty row, never more than
    the ``n_slots`` the table (or the window) can hold."""
    # (the operands are >= 0: lax's truncating div is the floor's)
    last = jax.lax.div(jnp.maximum(kv_len - 1, 0), page_size)
    first = 0 if window is None else jax.lax.div(
        jnp.maximum(kv_len - window, 0), page_size)
    n = jnp.where(kv_len > 0, jnp.minimum(last - first + 1, n_slots), 0)
    return first, n


def _paged_flash_decode_kernel(
    kv_lens_ref, block_table_ref, q_ref, *rest,
    pages_per_step: int, page_size: int, scale: float, per_head: bool,
    n_slots: int, quant: bool = False, soft_cap: float = 0.0,
    window: int | None = None,
):
    """Paged decode of one (row, kv head) — per-head grid ``(b, h_kv)`` —
    or of one row's every kv head — fused grid ``(b,)`` — a grid step. ONE
    body for both: the buffers carry a head dim of 1 or of the pool's
    ``h_kv``.

    The page loop is HERE, and its trip count is data: the pools stay in
    HBM, and the step walks the row's LIVE pages (:func:`_live_pages`) in
    chunks of ``pages_per_step``, each page its own DMA into one of two
    VMEM buffers, the chunk one [g, P·page] online-softmax span (r5 chip
    finding: the span, not the page indirection, is the cost). While a
    chunk is multiplied the next one is in flight — the same row's, or,
    at a row's last chunk, the first chunk of the NEXT grid step, whose
    table row and length are in SMEM too — so only the call's first DMA
    is exposed. The grid therefore runs in order on one core (every
    dimension ``arbitrary``), and ``slot_ref`` carries the buffer the
    step's first chunk lands in from step to step.

    The last chunk of a row is multiplied over the narrowest of the spans
    P, P/2, P/4, ... that covers its live pages. That span's dead page
    slots (past the row's last live page) are not fetched: they hold
    whatever an earlier chunk left there — uninitialised VMEM, or ANOTHER
    row's page. The length mask discards their scores, but ``p·v``
    multiplies them by 0, and ``0 x NaN`` is NaN — so the V (and V-scale)
    slots ``[live, width)`` are zeroed before the multiply: nothing but
    the row's own pages ever reaches its product, and a request whose KV
    went non-finite stays that request's alone (``_poison_slot``'s
    containment, models/decode.py).

    ``quant``: int8 / fp8 pools — the per-position scale rows ride their
    own page DMAs and are concatenated exactly as
    :func:`flash_decode_quant` folds them. ``window``: the walk starts at
    the page of position ``kv_len - window`` (table column = logical page
    modulo the table's width: a ring), and the positions below the window
    are masked."""
    P = pages_per_step
    if quant:
        (k_hbm, v_hbm, ks_hbm, vs_hbm, out_ref, lse_ref, k_buf, v_buf,
         ks_buf, vs_buf, sems, slot_ref, m_scr, l_scr, acc_scr) = rest
    else:
        (k_hbm, v_hbm, out_ref, lse_ref, k_buf, v_buf, sems, slot_ref,
         m_scr, l_scr, acc_scr) = rest
        ks_hbm = vs_hbm = ks_buf = vs_buf = None
    b, max_pages = block_table_ref.shape
    i = pl.program_id(0)
    if per_head:
        j = pl.program_id(1)
        wrap = j == pl.num_programs(1) - 1
        nxt_i, nxt_j = jnp.where(wrap, i + 1, i), jnp.where(wrap, 0, j + 1)
        first_step = jnp.logical_and(i == 0, j == 0)
    else:
        j = nxt_j = None
        nxt_i, first_step = i + 1, i == 0
    kv_len = kv_lens_ref[i]
    kv_lo = None if window is None else jnp.maximum(kv_len - window, 0)
    first, n_live = _live_pages(kv_len, page_size, n_slots, window)
    n_chunks = jax.lax.div(n_live + P - 1, P)

    def chunk_dmas(then, slot, row, head, chunk, valid=True):
        """``then`` (start / wait) on each page DMA of ``row``'s ``chunk``
        into buffer ``slot``: its live pages, none where not ``valid``."""
        row_first, row_live = _live_pages(
            kv_lens_ref[row], page_size, n_slots, window)

        def one_page(p, carry):
            logical = row_first + chunk * P + p
            page = block_table_ref[row, logical if window is None
                                   else jax.lax.rem(logical, max_pages)]
            src = (page, pl.ds(head, 1)) if per_head else (page,)
            at = pl.ds(pl.multiple_of(p * page_size, page_size), page_size)
            for hbm, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                then(pltpu.make_async_copy(
                    hbm.at[src], buf.at[slot, :, at], sems.at[slot]))
            if quant:  # the scale rows of the page, whole tiles
                for hbm, buf in ((ks_hbm, ks_buf), (vs_hbm, vs_buf)):
                    then(pltpu.make_async_copy(
                        hbm.at[src], buf.at[slot, p], sems.at[slot]))
            return carry

        jax.lax.fori_loop(
            0, jnp.where(valid, jnp.clip(row_live - chunk * P, 0, P), 0),
            one_page, 0)

    start = lambda dma: dma.start()
    wait = lambda dma: dma.wait()
    # the next grid step's first chunk, where there is a next step
    nxt = (jnp.minimum(nxt_i, b - 1), nxt_j, 0)
    has_nxt = nxt_i < b

    @pl.when(first_step)
    def _():
        slot_ref[0] = 0
        chunk_dmas(start, 0, i, j, 0)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    slot0 = slot_ref[0]

    def multiply(slot, chunk, width: int):
        """The online-softmax step of the step's heads (a batch dim of its
        matmuls) over the first ``width`` page slots of buffer ``slot``."""
        span = pl.ds(0, width * page_size)
        if quant:  # per-position scale rows ride
            ks, vs = (
                jnp.concatenate(
                    [buf[slot, p] for p in range(width)], axis=-1
                ) if width > 1 else buf[slot, 0]
                for buf in (ks_buf, vs_buf))
        else:
            ks = vs = None
        m_scr[...], l_scr[...], acc_scr[...] = _online_softmax_step(
            q_ref[0], k_buf[slot, :, span], v_buf[slot, :, span], ks, vs,
            (first + chunk * P) * page_size, kv_len, scale,
            m_scr[...], l_scr[...], acc_scr[...], soft_cap, kv_lo,
        )

    def zero_v_page(slot, p, carry):
        """Page slot ``p`` of buffer ``slot`` leaves the product: stale
        VMEM never enters p·v (see the docstring)."""
        at = pl.ds(pl.multiple_of(p * page_size, page_size), page_size)
        v_buf[slot, :, at] = jnp.zeros(
            (v_buf.shape[1], page_size, v_buf.shape[3]), v_buf.dtype)
        if quant:
            vs_buf[slot, p] = jnp.zeros(vs_buf.shape[2:], vs_buf.dtype)
        return carry

    # the spans a chunk may be multiplied over: P, and its halves
    widths = sorted({cdiv(P, 1 << s) for s in range(P.bit_length())})

    def one_chunk(c, carry):
        slot = jax.lax.rem(slot0 + c, 2)
        more = c + 1 < n_chunks
        # what is multiplied next goes into the other buffer first: this
        # row's next chunk, or the next grid step's first
        after = [x if y is None else jnp.where(more, x, y)
                 for x, y in zip((i, j, c + 1), nxt)]
        chunk_dmas(start, 1 - slot, *after, jnp.logical_or(more, has_nxt))
        chunk_dmas(wait, slot, i, j, c)
        live = jnp.clip(n_live - c * P, 0, P)
        # the narrowest span that covers the live pages; the page slots it
        # holds past them were not fetched
        cover = jnp.int32(widths[0])
        for below, width in zip(widths, widths[1:]):
            cover = jnp.where(live > below, width, cover)
        jax.lax.fori_loop(
            live, cover, functools.partial(zero_v_page, slot), 0)
        for width in widths:
            pl.when(cover == width)(
                functools.partial(multiply, slot, c, width))
        return carry

    jax.lax.fori_loop(0, n_chunks, one_chunk, 0)

    @pl.when(n_chunks == 0)
    def _():  # an empty row hands the next step its first chunk itself
        chunk_dmas(start, slot0, *nxt, has_nxt)

    slot_ref[0] = jax.lax.rem(slot0 + n_chunks, 2)
    out_ref[0], lse_ref[0] = _finalize_softmax(
        m_scr[...], l_scr[...], acc_scr[...])


def paged_flash_decode(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    kv_lens: jax.Array,
    block_table: jax.Array,
    *,
    k_scales: jax.Array | None = None,
    v_scales: jax.Array | None = None,
    fuse_heads: bool | None = None,
    pages_per_step: int | None = None,
    soft_cap: float = 0.0,
    return_lse: bool = False,
    interpret: Any = None,
    window: int | None = None,
):
    """Single-device GQA batch decode over a PAGED KV cache
    (≙ the reference's paged decode, flash_decode.py:130-280: the KV cache
    is a pool of fixed-size pages; ``block_table[b, i]`` names the physical
    page holding sequence ``b``'s ``i``-th chunk).

    q: ``[b, q_heads, d]``; k_pages, v_pages: ``[n_pages, kv_heads,
    page_size, d]``; kv_lens: ``[b]`` int32; block_table: ``[b, max_pages]``
    int32 physical page ids (entries past a row's last live page are
    never read). Returns like :func:`flash_decode`.

    TPU-native form of the indirection: ``kv_lens`` and the block table
    ride scalar prefetch (SMEM), the pools stay in HBM, and the kernel
    itself copies pages into VMEM: the page loop runs INSIDE a grid step
    and ends at the page of position ``kv_len - 1`` (docs/serving.md "The
    walk's discipline"). **What is fetched:** the pages that hold a row's
    attended positions, each whole, each once a kv head (per-head grid)
    or once (fused grid): a table's width is capacity, never work; an
    empty row fetches nothing. Two buffers of ``pages_per_step`` page
    slots alternate, one multiplied while the other fills, and a step's
    last chunk overlaps the NEXT grid step's first, so the grid runs in
    order (every dimension ``arbitrary``). **The stale-buffer rule:** a
    page slot that was not fetched holds whatever was there; the length
    mask discards its scores, but ``p x v`` would multiply it by 0 and
    ``0 x NaN`` is NaN, and what was there may be ANOTHER row's page: so
    the dead V slots of the span a row's last chunk is multiplied over
    are zeroed before the multiply. Only a row's own pages reach its
    product (the positions of its own last page past ``kv_len`` included,
    as they always did).

    ``fuse_heads``: a page holds every kv head's slab, so the fused-heads
    grid (b,) fetches each physical page in ONE DMA and multiplies the
    heads as a batch dim of its matmuls; the per-head grid (b, h_kv)
    fetches page_size·d slices. Default (None) = auto: fused wherever one
    of its page slots fits the scoped-VMEM budget and its span reaches
    512, or all the per-head grid could span (the per-head grid's slabs
    are h_kv times smaller, so many-kv-head pools always compile). A grid step costs ~0.8 µs however little it
    walks (chip, PR 38: 64 steps of one page 50 µs, 8 fused steps 20), so
    fewer, larger steps win at every length measured. Pass True/False to
    pin.

    ``pages_per_step``: page slots of a buffer = physical pages
    CONCATENATED into one online-softmax span (each page still its own
    DMA, P in flight). None = auto: reach a 4096 span, bounded by the
    VMEM budget and the table width. The r5 span finding (on the old
    grids: per-head at span 4096 measured 347 µs where fused capped at
    1792 gave 392, and the span-256 grids 577) holds in this form at the
    long end — per-head over 14 live pages of 16: P = 16 104 µs, P = 4
    174, P = 2 246 (chip, PR 38: 8 rows, 8 kv heads, page 128) — and is
    met from the short end by the span ladder: a row's last chunk is
    multiplied over the narrowest of P, P/2, P/4, ... page slots that
    covers its live pages, so three live pages cost a 4-page span, not
    the buffer's.

    ``k_scales``/``v_scales`` (``[n_pages, kv_heads, 1, page_size]``
    f32, from :func:`quantize_kv_pages`): int8 page pools — the paged
    form of :func:`flash_decode_quant`'s per-position row scales. The
    payload DMAs stream at half the bytes (the resource decode is
    bound by) and the scales ride 2P extra page-slot fetches; this
    completes the serving cache matrix (contiguous/paged ×
    bf16/int8), which the reference's bf16-only paged decode lacks.

    ``window``: a row attends positions ``[kv_len - window, kv_len)``
    only, and only the pages that hold them are fetched: the walk starts
    at the range's first page and covers at most ``ceil(window / page) +
    1`` pages a row (never more than the table is wide).
    ``kv_lens`` counts TRUE positions; logical page ``i`` lies in table
    column ``i % max_pages``, so a table narrower than the sequence is a
    RING (``models/decode.py`` ``WindowPagedKVCacheSpec``) and a
    full-width one is read where it lies. The kernels carry the window
    in their names (``paged_flash_decode_w128_fh``). ``None``: the same
    walk from page 0.

    The bf16 pool degrades to the gather-reconstructed
    :func:`_xla_paged_decode` golden when the Pallas kernel cannot run in
    this environment (resilience layer, docs/resilience.md); int8 pools
    have no golden slow path, so their failures stay loud.
    """
    assert q.shape[1] % k_pages.shape[1] == 0, (q.shape, k_pages.shape)
    kv_lens = kv_lens.astype(jnp.int32)
    if window is not None:
        if k_scales is not None:
            raise NotImplementedError(
                "paged_flash_decode: a window over a quantized pool is not "
                "built")
        if window < 1:
            raise ValueError(f"window={window} must be >= 1")
    if k_scales is not None:
        family = (
            "paged_flash_decode_fp8" if k_pages.dtype == FP8_KV_DTYPE
            else "paged_flash_decode_q"
        )
        golden = None
    else:
        family = "paged_flash_decode"
        twin = _xla_paged_decode if window is None else functools.partial(
            _xla_paged_window_decode, window=window)
        golden = lambda: twin(
            q, k_pages, v_pages, kv_lens, block_table,
            return_lse=return_lse, soft_cap=soft_cap,
        )
    return resilience.guarded_call(
        family,
        lambda: _paged_flash_decode_fused(
            q, k_pages, v_pages, kv_lens, block_table,
            k_scales=k_scales, v_scales=v_scales, fuse_heads=fuse_heads,
            pages_per_step=pages_per_step, soft_cap=soft_cap,
            return_lse=return_lse, interpret=interpret, window=window,
        ),
        golden,
    )


def _paged_flash_decode_fused(
    q, k_pages, v_pages, kv_lens, block_table, *,
    k_scales, v_scales, fuse_heads, pages_per_step, soft_cap, return_lse,
    interpret, window=None,
):
    b, hq, d = q.shape
    n_pages, h_kv, page_size, _ = k_pages.shape
    g = hq // h_kv
    max_pages = block_table.shape[1]
    quant = k_scales is not None
    # page slots a row's walk can cover: the whole table row, or the pages
    # that can hold a window's positions
    n_slots = max_pages if window is None else _window_pages(
        window, page_size, max_pages)
    tag = "" if window is None else f"_w{window}"
    d_out = d
    scale = 1.0 / math.sqrt(d)  # the TRUE head dim, before any padding
    d = _kernel_head_dim(d)
    if d != d_out:  # pad the q and the page pools; slice the output back
        q, k_pages, v_pages = (
            _pad_head_dim(x, d) for x in (q, k_pages, v_pages)
        )
    if quant:
        assert v_scales is not None
        assert k_scales.shape == (n_pages, h_kv, 1, page_size), k_scales.shape
        assert v_scales.shape == k_scales.shape, (v_scales.shape, k_scales.shape)
    # what a page slot of one kv head takes of VMEM, as a K or V slab of
    # the two double buffers (4 of them a slot): int8 / fp8 pools hold half
    # the payload bytes, plus an f32 scale row (a [1, page] row fills 8
    # sublanes) and the bf16 copy the multiply makes of one buffer
    slab_h = page_size * (
        d * k_pages.dtype.itemsize + ((32 + d) if quant else 0)
    )
    slab_f = h_kv * slab_h
    p_f = _auto_pages_per_step(slab_f, page_size, n_slots)
    p_h = _auto_pages_per_step(slab_h, page_size, n_slots)
    if fuse_heads is None:
        # the fused grid wherever one of its page slots fits the budget
        # and its span is not a sliver: a grid step costs ~0.8 us however
        # little it walks (the DMA latency one step of lookahead cannot
        # hide), and the fused grid has h_kv times fewer of them, each
        # page ONE DMA for all heads. v5e, 8 rows x 8 kv heads, 3 / 8 / 14
        # live pages of 16 (PR 38): fused at P = 4 or 8 30 / 58 / 93 us,
        # per-head at P = 16 57 / 76 / 104; fused at P = 2 (a 256 span,
        # heads unrolled) 45 / 88 / 142: below _FUSED_MIN_SPAN the
        # per-head grid, whose slabs afford the wider span, wins the long
        # end. int8 / fp8 pools (half the payload bytes, per-page scale
        # fetches) go DMA-issue-bound per head (chip r5: 478 us against
        # fused 218, on the old grids; not measured again on this form: no
        # cell runs a quantized pool): fused whenever a slot fits. A walk
        # narrower than _FUSED_MIN_SPAN altogether (a window layer's 2
        # page slots, a 4-page table of small pages) is fused when it is
        # all the per-head grid could span too. Many-kv-head pools still
        # never fail to compile: per-head slabs are h_kv times smaller.
        fuse_heads = p_f >= 1 and (
            quant
            or p_f * page_size >= min(_FUSED_MIN_SPAN, p_h * page_size))
    if pages_per_step is None and (p_f if fuse_heads else p_h) == 0:
        # the SELECTED grid (auto never picks a dead grid while the other
        # lives, but an explicit fuse_heads can force one) affords not even
        # ONE page slot: without this check the forced pages_per_step=1
        # dies deep inside Mosaic compilation with an allocation error
        # naming none of these numbers
        raise ValueError(
            f"paged_flash_decode: the selected "
            f"{'fused' if fuse_heads else 'per-head'} grid affords no "
            f"single page slot under the scoped-VMEM budget — "
            f"page_size={page_size}, head_dim={d}, h_kv={h_kv}: one "
            f"double-buffered K+V page slot "
            f"{4 * (slab_f if fuse_heads else slab_h)} B exceeds the "
            f"{_fused_slab_vmem_budget()} B budget "
            f"(--xla_tpu_scoped_vmem_limit_kib / TDT_SCOPED_VMEM_LIMIT_KIB "
            f"raises it). Reduce page_size, toggle fuse_heads, or use "
            f"flash_decode on a contiguous cache."
        )
    # match q to the pool's COMPUTE dtype (int8 pools upcast to bf16 in
    # the kernel — the same contract as flash_decode_quant)
    q4 = q.reshape(b, h_kv, g, d).astype(
        jnp.bfloat16 if quant else k_pages.dtype
    )
    # an upper bound: what a call reads and multiplies follows kv_lens
    cost = pl.CostEstimate(
        flops=4 * b * hq * n_slots * page_size * d,
        bytes_accessed=(2 * b * h_kv * n_slots * page_size)
        * (d * k_pages.dtype.itemsize + (4 if quant else 0)),
        transcendentals=b * hq * n_slots * page_size,
    )
    if pages_per_step is None:
        pages_per_step = max(1, p_f if fuse_heads else p_h)
    P = min(pages_per_step, n_slots)
    # kv heads a grid step holds; the per-head grid is the 1-head instance
    # of the same body (a leading head dim of 1 on blocks and buffers)
    heads = h_kv if fuse_heads else 1
    if fuse_heads:
        grid = (b,)
        block = lambda i, *_: (i, 0, 0, 0)
    else:
        grid = (b, h_kv)
        block = lambda i, j, *_: (i, j, 0, 0)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, heads, g, d), block),
            *([in_hbm] * (4 if quant else 2)),
        ],
        out_specs=(
            pl.BlockSpec((1, heads, g, d), block),
            pl.BlockSpec((1, heads, g, 1), block),
        ),
        scratch_shapes=[
            # two buffers of P page slots: one multiplied, one in flight
            *[pltpu.VMEM((2, heads, P * page_size, d), k_pages.dtype)] * 2,
            *[pltpu.VMEM((2, P, heads, 1, page_size), jnp.float32)]
            * (2 if quant else 0),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((heads, g, 1), jnp.float32),
            pltpu.VMEM((heads, g, 1), jnp.float32),
            pltpu.VMEM((heads, g, d), jnp.float32),
        ],
    )
    name = "paged_flash_decode_q" if quant else f"paged_flash_decode{tag}"
    out, lse = dist_pallas_call(
        functools.partial(
            _paged_flash_decode_kernel,
            pages_per_step=P, page_size=page_size, scale=scale,
            per_head=not fuse_heads, n_slots=n_slots, quant=quant,
            soft_cap=soft_cap, window=window,
        ),
        name=name + ("_fh" if fuse_heads else ""),
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((b, h_kv, g, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h_kv, g, 1), jnp.float32),
        ),
        cost_estimate=cost,
        # in order, on one core: a step starts the next step's first DMAs
        dimension_semantics=("arbitrary",) * len(grid),
        uses_barrier=False,
        interpret=interpret,
    )(
        kv_lens, block_table.astype(jnp.int32), q4, k_pages, v_pages,
        *((k_scales, v_scales) if quant else ()),
    )
    out = out.reshape(b, hq, d)[..., :d_out]
    lse = lse.reshape(b, hq)
    return (out, lse) if return_lse else out


def quantize_kv_pages(k_pages: jax.Array, v_pages: jax.Array):
    """Per-(page, head, position) absmax int8 quantization of a paged KV
    pool (k_pages, v_pages ``[n_pages, h_kv, page, d]``) →
    ``(k_q, v_q, k_scale, v_scale)`` with int8 payloads and
    ``[n_pages, h_kv, 1, page]`` f32 row scales — the paged layout of
    :func:`quantize_kv`'s scales. The math is dimension-agnostic over
    the leading axis, so this IS :func:`quantize_kv` applied to the
    pool (one implementation: a fix to the shared quantization cannot
    diverge the two cache layouts). Feed to :func:`paged_flash_decode`
    via ``k_scales``/``v_scales``."""
    return quantize_kv(k_pages, v_pages)


def paged_flash_decode_quant(
    q: jax.Array,
    k_pages_q: jax.Array,
    v_pages_q: jax.Array,
    k_scales: jax.Array,
    v_scales: jax.Array,
    kv_lens: jax.Array,
    block_table: jax.Array,
    **kw,
):
    """int8-pool paged decode (:func:`flash_decode_quant` × the paged
    layout — the last cell of the serving cache matrix): thin alias of
    :func:`paged_flash_decode` with the scale pools attached; argument
    order mirrors the contiguous quant entry."""
    return paged_flash_decode(
        q, k_pages_q, v_pages_q, kv_lens, block_table,
        k_scales=k_scales, v_scales=v_scales, **kw,
    )


def quantize_kv_pages_fp8(k_pages: jax.Array, v_pages: jax.Array):
    """fp8 twin of :func:`quantize_kv_pages` — :func:`quantize_kv_fp8`
    applied to the page pool (one implementation, two cache layouts)."""
    return quantize_kv_fp8(k_pages, v_pages)


def paged_flash_decode_fp8(
    q: jax.Array,
    k_pages_q: jax.Array,
    v_pages_q: jax.Array,
    k_scales: jax.Array,
    v_scales: jax.Array,
    kv_lens: jax.Array,
    block_table: jax.Array,
    **kw,
):
    """fp8-pool paged decode (:func:`flash_decode_fp8` × the paged
    layout): thin alias of :func:`paged_flash_decode` with the fp8 scale
    pools attached; argument order mirrors the contiguous fp8 entry."""
    return paged_flash_decode(
        q, k_pages_q, v_pages_q, kv_lens, block_table,
        k_scales=k_scales, v_scales=v_scales, **kw,
    )


def paged_flash_decode_distributed(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    kv_lens_shard: jax.Array,
    block_table: jax.Array,
    *,
    axis: str = "tp",
    fuse_heads: bool | None = None,
    pages_per_step: int | None = None,
    soft_cap: float = 0.0,
    ag_method: str = "full_mesh_push",
    interpret: Any = None,
) -> jax.Array:
    """SP/CP decode over a paged, sequence-sharded KV cache: each PE holds
    its own page pool + block table covering its sequence shard (the paged
    analogue of :func:`flash_decode_distributed`; ≙ the reference SP layer,
    which is paged end-to-end: sp_flash_decode_layer.py:78).
    ``fuse_heads`` / ``pages_per_step`` as in :func:`paged_flash_decode`
    (None = span-driven auto)."""
    out, lse = paged_flash_decode(
        q, k_pages, v_pages, kv_lens_shard, block_table,
        fuse_heads=fuse_heads, pages_per_step=pages_per_step,
        soft_cap=soft_cap, return_lse=True, interpret=interpret,
    )
    return _sp_allgather_combine(out, lse, axis, ag_method, interpret)


def combine_partials(outs: jax.Array, lses: jax.Array) -> jax.Array:
    """Numerically-stable online-softmax merge of partial attention results
    (≙ ``kernel_inter_rank_gqa_fwd_batch_decode_combine_kv``, reference
    flash_decode.py:482-530: ``acc *= exp(m - m_new) ...``).

    outs: ``[n, b, hq, d]`` partial (normalized) outputs; lses: ``[n, b, hq]``
    their log-sum-exps. Returns the exact full-attention result ``[b, hq, d]``.
    """
    m = jnp.max(lses, axis=0)                            # [b, hq]
    # ranks with no KV carry lse=-inf → weight 0; all -inf → output 0
    w = jnp.where(
        jnp.isfinite(lses), jnp.exp(lses - jnp.maximum(m, -1e30)), 0.0
    )                                                    # [n, b, hq]
    denom = jnp.maximum(jnp.sum(w, axis=0), 1e-30)       # [b, hq]
    return jnp.einsum("nbh,nbhd->bhd", w, outs) / denom[..., None]


def flash_decode_distributed(
    q: jax.Array,
    k_shard: jax.Array,
    v_shard: jax.Array,
    kv_lens_shard: jax.Array,
    *,
    axis: str = "tp",
    config: FlashDecodeConfig | None = None,
    ag_method: str = "full_mesh_push",
    interpret: Any = None,
) -> jax.Array:
    """SP/CP decode over a KV-sharded cache (call inside ``jax.shard_map``;
    ≙ ``SpGQAFlashDecodeAttention.forward``, sp_flash_decode_layer.py:78).

    Every PE holds the full q and a sequence-shard of the KV cache
    (``kv_lens_shard`` = #valid positions in the LOCAL shard). Local partial
    attention → low-latency allgather of the (out ‖ lse) payload → merge.
    Golden: single-device flash decode over the concatenated cache.
    """
    out, lse = flash_decode(
        q, k_shard, v_shard, kv_lens_shard,
        config=config, return_lse=True, interpret=interpret,
    )
    return _sp_allgather_combine(out, lse, axis, ag_method, interpret)


def _sp_allgather_combine(out, lse, axis, ag_method, interpret) -> jax.Array:
    """Shared SP tail: allgather each PE's (out ‖ lse) payload and merge.

    One flat payload per PE (≙ the staged symm ag_buffer copy,
    sp_flash_decode_layer.py:134-137): [b*hq, d] out rows, then the b*hq
    lse scalars packed densely into ceil(b*hq/d) extra rows.
    """
    n = _axis_size(axis)
    if n == 1:
        return out
    b, hq, d = out.shape
    rows = b * hq
    lse_rows = -(-rows // d)
    lse_packed = jnp.pad(lse.reshape(-1), (0, lse_rows * d - rows)).reshape(lse_rows, d)
    payload = jnp.concatenate([out.reshape(rows, d), lse_packed])
    gathered = all_gather(payload, axis=axis, method=ag_method, interpret=interpret)
    gathered = gathered.reshape(n, rows + lse_rows, d)
    outs = gathered[:, :rows, :].reshape(n, b, hq, d)
    lses = gathered[:, rows:, :].reshape(n, lse_rows * d)[:, :rows].reshape(n, b, hq)
    return combine_partials(outs, lses)


def flash_decode_op(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_lens: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "tp",
    config: FlashDecodeConfig | None = None,
    interpret: Any = None,
) -> jax.Array:
    """Host-level SP entry: `k`/`v` ``[b, h_kv, S, d]`` sharded on the
    sequence dim over `axis`, `q`/`kv_lens` replicated (global lengths).
    Each PE derives its local valid length from the global one."""
    n = mesh.shape[axis]
    s_shard = k.shape[2] // n
    if n == 1 and config is not None and config.block_s == 0:
        # world-1 XLA-native sentinel: no SPMD machinery (see ag_gemm_op)
        return _xla_decode(
            q, k, v, kv_lens.astype(jnp.int32), return_lse=False,
            soft_cap=config.soft_cap,
        )

    def fn(q, k_s, v_s, kv_lens):
        me = jax.lax.axis_index(axis)
        local_lens = jnp.clip(kv_lens - me * s_shard, 0, s_shard)
        return flash_decode_distributed(
            q, k_s, v_s, local_lens, axis=axis, config=config, interpret=interpret
        )

    return jit_shard_map(
        fn, mesh,
        (
            P(None, None, None),
            P(None, None, axis, None),
            P(None, None, axis, None),
            P(None),
        ),
        P(None, None, None),
        key=("flash_decode", axis, config, s_shard, str(interpret)),
    )(q, k, v, kv_lens.astype(jnp.int32))


# KV-chunk tune space (≙ the reference's split-KV block sweep); larger
# chunks amortize per-grid-step overhead, smaller ones win on short
# caches. FIRST entry = best-known for the long-cache bench shape
# (applied sweep-free under cached_or_first): the per-head Pallas kernel
# at block_s=4096, which RETIRED the XLA sentinel on chip in the r5
# sweep (359.5 µs vs the sentinel's ~374, vs_baseline 1.04 — the span
# finding: wide per-step softmax spans win; the r3-era "XLA fusion wins"
# measurement was against span-512 chunkings). The sentinel stays as
# the second candidate for shapes where XLA's one-fusion form still
# wins (short caches). Fused-heads chunkings above span 1024 exceed the
# 16 MiB scoped-VMEM stack at h_kv=8 and fail candidate compilation —
# the sweep prices that in by falling through; they remain for
# few-kv-head shapes where their one-DMA-per-chunk slabs fit.
FLASH_DECODE_TUNE_SPACE = (
    FlashDecodeConfig(block_s=4096),
    FlashDecodeConfig(block_s=0),
    FlashDecodeConfig(block_s=8192),
    FlashDecodeConfig(block_s=2048),
    FlashDecodeConfig(block_s=1024),
    FlashDecodeConfig(block_s=512),
    FlashDecodeConfig(block_s=2048, fuse_heads=True),
    FlashDecodeConfig(block_s=1024, fuse_heads=True),
    FlashDecodeConfig(block_s=4096, fuse_heads=True),
    FlashDecodeConfig(block_s=512, fuse_heads=True),
)


def _fd_effective_block(cfg, q, k, v, kv_lens, mesh, *, axis="tp", **_):
    """Configs whose block clamps to the same per-shard chunk are the same
    kernel — time one (pick_block caps block_s at the local KV length)."""
    if cfg.block_s == 0:
        return 0  # XLA-native path: its own kernel
    return (
        pick_block(k.shape[2] // mesh.shape[axis], cfg.block_s),
        cfg.fuse_heads,
    )


def _flash_decode_op_xla(q, k, v, kv_lens, mesh, *, config=None, **_):
    """Op-level golden: the XLA-native masked attention over the full
    cache — no SPMD machinery at all (jit shards the einsums under the
    arrays' placement), so it survives any topology the fused SP
    pipeline cannot. Honors the config's ``soft_cap`` — the golden must
    compute the same capped logits as the kernel it stands in for."""
    del mesh
    return _xla_decode(
        q, k, v, kv_lens.astype(jnp.int32), return_lse=False,
        soft_cap=config.soft_cap if config is not None else 0.0,
    )


flash_decode_op = contextual_autotune(
    FLASH_DECODE_TUNE_SPACE, name="flash_decode", dedupe=_fd_effective_block
)(flash_decode_op)
# guard OUTSIDE the autotuner: the sweep still prices failing candidates;
# only a failure of the whole tuned entry degrades to the XLA golden
flash_decode_op = resilience.guard_op("flash_decode_op", _flash_decode_op_xla)(
    flash_decode_op
)
