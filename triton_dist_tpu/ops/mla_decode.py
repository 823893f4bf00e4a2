"""Absorbed latent-attention (MLA) decode over a paged LATENT cache.

The cache holds ONE row a token and layer, shared by every head: the
normed kv latent ``c_kv`` (``d_v`` values), the rotated shared key ``k_r``
and zero padding up to a whole number of 128-lane tiles (``LATENT_ROW``
picks the width). With the up-projection absorbed into the query
(``q_lat[h] = q_n[h] @ W_kvb,k[h]^T``) decode is multi-QUERY attention
over that row: scores are one dot of ``[q_lat | q_r | 0]`` with the row,
the output is ``p @ row[:, :d_v]`` — keys of width ``row`` and values of
width ``d_v`` read from the SAME tile, which is what
``ops/flash_decode.py``'s one-``d`` kernels cannot express.

The pool is the whole cache leaf ``[n_layers, n_pages, page, row]``; the
layer is a static index of the page BlockSpec, so no per-layer slice of
the pool is ever materialized. Physical pages come from the same block
table as the k/v paged cache (scalar prefetch steers the fetches).

Three calls share the one kernel body, each under its own name in a
device trace:

- :func:`mla_paged_decode` (``mla_paged_decode``): a slot's whole table
  row, every position below its length.
- the same with ``window=`` (``ring_mla_decode``): the table is a RING
  (column = logical page modulo its width, ``models/decode.py``); the walk
  starts at the page of position ``kv_len - window``, covers at most
  ``ceil(window / page) + 1`` pages whatever the context, and the
  positions below the window are masked.
- :func:`sparse_mla_decode` (``sparse_mla_decode``): the table row's
  LIVE pages where they lie, with ``keep [b, s]``: the positions an index
  selected (``ops/sparse_index.py``); the others are masked out of the
  softmax as the positions past the length are. No row is gathered: a
  row of 1280 B gathered by XLA costs 15 ns and the sort that orders the
  gather 6 more, the walk 4.6 ns a row in place, so the walk is the
  shorter way while a slot holds under ~7.6 rows for each one selected
  (15.5k rows at top-2048: PERF.md section 6, PR 41).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu import resilience
from triton_dist_tpu.ops.common import dist_pallas_call
from triton_dist_tpu.ops.flash_decode import (
    NEG_INF, _finalize_softmax, _online_softmax_step,
)
from triton_dist_tpu.utils import cdiv, round_up

LANE = 128
# the names the kernel carries in a device trace (perfbench reads them)
KERNEL_NAME = "mla_paged_decode"
RING_KERNEL_NAME = "ring_mla_decode"
SPARSE_KERNEL_NAME = "sparse_mla_decode"


def latent_row(d_latent: int, d_rope: int) -> int:
    """Stored width of one latent row: ``c_kv | k_r`` padded to whole lane
    tiles (512 + 64 -> 640: 576 is 4.5 tiles, and a half tile at the end
    of a row makes every page DMA and the score dot ragged)."""
    return round_up(d_latent + d_rope, LANE)


def _mla_decode_kernel(
    kv_lens_ref, bt_ref, q_ref, *rest,
    n_steps: int, pages_per_step: int, page_size: int, scale: float,
    d_v: int, window: int | None = None, masked: bool = False,
):
    """Grid ``(sequence, chunk)``; ``pages_per_step`` pages concatenated
    into one online-softmax span per step, as the k/v paged kernel does.
    ``window``: chunk 0 starts at the page of position ``kv_len - window``
    (the index map names the pages) and the positions below the
    window are masked. ``masked``: after the pages comes the chunk's
    ``[1, 1, span]`` float32 bias by position (0 keeps, ``-inf`` drops)."""
    del bt_ref
    P = pages_per_step
    page_refs = rest[:P]
    bias_ref = rest[P] if masked else None
    out_ref, m_scr, l_scr, acc_scr = rest[P + masked:]
    c = pl.program_id(1)
    kv_len = kv_lens_ref[pl.program_id(0)]
    if window is None:
        kv_lo, base = None, c * P * page_size
    else:   # the chunk's first position counts from the window's first page
        kv_lo = jnp.maximum(kv_len - window, 0)
        base = (jax.lax.div(kv_lo, page_size) + c * P) * page_size

    @pl.when(c == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(base < kv_len)
    def _():
        rows = (
            jnp.concatenate([r[0, 0] for r in page_refs], axis=0)
            if P > 1 else page_refs[0][0, 0]
        )                                               # [P*page, row]
        m_scr[:], l_scr[:], acc_scr[:] = _online_softmax_step(
            q_ref[0], rows, rows[:, :d_v], None, None,
            base, kv_len, scale,
            m_scr[:], l_scr[:], acc_scr[:], kv_lo=kv_lo,
            bias=None if bias_ref is None else bias_ref[0],
        )

    @pl.when(c == n_steps - 1)
    def _():
        out_ref[0], _ = _finalize_softmax(m_scr[:], l_scr[:], acc_scr[:])


def _xla_mla_decode(q, pool, li, kv_lens, block_table, *, d_v, scale,
                    window=None, keep=None):
    """Golden slow path: gather the sequence's pages and attend in XLA.
    With ``window`` the table is a ring: each gathered row's POSITION is
    rebuilt from its ring address (the last position below the length
    that lives there) and masked to the window. ``keep [b, span]`` bool
    masks the positions an index did not select."""
    b, max_pages = block_table.shape
    span = max_pages * pool.shape[2]
    rows = pool[li][block_table]                  # [b, max_pages, page, row]
    rows = rows.reshape(b, span, -1).astype(jnp.float32)
    lens = kv_lens[:, None]
    pos = jnp.arange(span, dtype=jnp.int32)[None, :]
    if window is None:
        live = pos < lens
    else:
        pos = lens - 1 - (lens - 1 - pos) % span
        live = (pos >= jnp.maximum(lens - window, 0)) & (pos >= 0)
    if keep is not None:
        live = live & keep
    s = jnp.einsum("bhr,btr->bht", q.astype(jnp.float32), rows) * scale
    s = jnp.where(live[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(kv_lens[:, None, None] > 0, p, 0.0)
    return jnp.einsum("bht,btv->bhv", p, rows[..., :d_v])


def _mla_decode_fused(
    q, pool, li, kv_lens, block_table, *, d_v, scale, pages_per_step,
    interpret, window=None, keep=None, name=KERNEL_NAME,
):
    b, hq, row = q.shape
    _, _, page_size, _ = pool.shape
    max_pages = block_table.shape[1]
    P = pages_per_step
    # the pages a row can expose: its table row, or what holds a window
    n_slots = max_pages if window is None else min(
        cdiv(window, page_size) + 1, max_pages)
    n_steps = cdiv(n_slots, P)

    def page_map(p):
        def index_map(i, c, kv_lens_ref, bt_ref):
            # chunks past the live length re-name the last live page: an
            # unchanged block index costs no fetch, and the length mask
            # (or the chunk gate) keeps the duplicate out of the softmax
            last = jnp.maximum(kv_lens_ref[i] - 1, 0) // page_size
            if window is None:
                j = jnp.minimum(jnp.minimum(c * P + p, last), max_pages - 1)
            else:   # from the window's first page, at its ring column
                first = jnp.maximum(kv_lens_ref[i] - window, 0) // page_size
                j = jnp.minimum(first + c * P + p, last) % max_pages
            return (li, bt_ref[i, j], 0, 0)
        return index_map

    def bias_map(i, c, kv_lens_ref, bt_ref):
        # as the pages: a chunk past the live length names the last live one
        last = jnp.maximum(kv_lens_ref[i] - 1, 0) // (P * page_size)
        return (i, 0, jnp.minimum(c, last))

    masked = keep is not None
    bias = () if not masked else (
        jnp.where(keep, 0.0, NEG_INF).astype(jnp.float32)[:, None, :],)
    out = dist_pallas_call(
        functools.partial(
            _mla_decode_kernel, n_steps=n_steps, pages_per_step=P,
            page_size=page_size, scale=scale, d_v=d_v, window=window,
            masked=masked,
        ),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_steps),
            in_specs=[
                pl.BlockSpec((1, hq, row), lambda i, c, *_: (i, 0, 0)),
                *(pl.BlockSpec((1, 1, page_size, row), page_map(p))
                  for p in range(P)),
                *(pl.BlockSpec((1, 1, P * page_size), bias_map)
                  for _ in bias),
            ],
            out_specs=pl.BlockSpec((1, hq, d_v), lambda i, c, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hq, 1), jnp.float32),
                pltpu.VMEM((hq, 1), jnp.float32),
                pltpu.VMEM((hq, d_v), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, d_v), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * hq * n_slots * page_size * (row + d_v),
            bytes_accessed=b * n_slots * page_size * row
            * pool.dtype.itemsize,
            transcendentals=b * hq * n_slots * page_size,
        ),
        dimension_semantics=("parallel", "arbitrary"),
        uses_barrier=False,
        interpret=interpret,
    )(
        kv_lens, block_table.astype(jnp.int32), q.astype(pool.dtype),
        *(pool for _ in range(P)), *bias,
    )
    return out


def _pages_per_step(block_table) -> int:
    """4 pages a step over a table row: a page of 128 rows is 160 KB, two
    grid steps' overhead in DMA time, so wider spans pay until dead pages
    are fetched for short contexts."""
    return max(p for p in (1, 2, 4) if block_table.shape[1] % p == 0)


def mla_paged_decode(
    q: jax.Array,
    pool: jax.Array,
    li: int,
    kv_lens: jax.Array,
    block_table: jax.Array,
    *,
    d_v: int,
    scale: float,
    window: int | None = None,
    interpret: Any = None,
) -> jax.Array:
    """Absorbed MLA decode for one layer.

    q: ``[b, heads, row]`` = ``[q_lat | q_r | 0]`` per head; pool:
    ``[n_layers, n_pages, page, row]`` (rows ``[c_kv | k_r | 0]``); ``li``
    the (static) layer; kv_lens ``[b]`` int32; block_table ``[b,
    max_pages]``. ``scale`` is the model's softmax scale
    (``1/sqrt(qk_head_dim)`` of the EXPANDED form, not of ``row``).
    Returns the latent output ``[b, heads, d_v]`` float32; the caller
    applies ``W_kvb,v``. ``window``: the table is a ring and a row attends
    ``[kv_len - window, kv_len)`` (the module's docstring).
    """
    kv_lens = kv_lens.astype(jnp.int32)
    if window is None:
        name, pages_per_step = KERNEL_NAME, _pages_per_step(block_table)
    elif window < 1:
        raise ValueError(f"window={window} must be >= 1")
    else:
        # a ring's live pages are few (window 513, page 128: at most 5 of
        # 6): two steps of three pages, not six of one, halve the steps'
        # overhead beside each page's 295 KB
        n_slots = min(cdiv(window, pool.shape[2]) + 1, block_table.shape[1])
        name, pages_per_step = RING_KERNEL_NAME, max(
            p for p in (1, 2, 3) if n_slots % p == 0)
    return resilience.guarded_call(
        name,
        lambda: _mla_decode_fused(
            q, pool, li, kv_lens, block_table, d_v=d_v, scale=scale,
            pages_per_step=pages_per_step, interpret=interpret,
            window=window, name=name,
        ),
        lambda: _xla_mla_decode(
            q, pool, li, kv_lens, block_table, d_v=d_v, scale=scale,
            window=window,
        ),
    )


def sparse_mla_decode(
    q: jax.Array, pool: jax.Array, li: int, kv_lens: jax.Array,
    block_table: jax.Array, keep: jax.Array, *, d_v: int, scale: float,
    interpret: Any = None,
) -> jax.Array:
    """Absorbed MLA decode over SELECTED rows: :func:`mla_paged_decode`'s
    operands and ``keep [b, pages a slot * page]`` bool, true at the
    positions a row attends (``ops.sparse_index.topk_mask`` of its index
    scores; nothing at or past ``kv_lens`` is read whatever it says). The
    live pages are walked where they lie and the unselected rows masked.
    ``[b, heads, d_v]`` float32."""
    kv_lens = kv_lens.astype(jnp.int32)
    if keep.shape != (q.shape[0], block_table.shape[1] * pool.shape[2]):
        raise ValueError(f"keep {keep.shape}: not a flag a position of "
                         f"{block_table.shape} pages of {pool.shape[2]}")
    return resilience.guarded_call(
        SPARSE_KERNEL_NAME,
        lambda: _mla_decode_fused(
            q, pool, li, kv_lens, block_table, d_v=d_v, scale=scale,
            pages_per_step=_pages_per_step(block_table), keep=keep,
            interpret=interpret, name=SPARSE_KERNEL_NAME,
        ),
        lambda: _xla_mla_decode(q, pool, li, kv_lens, block_table, d_v=d_v,
                                scale=scale, keep=keep),
    )
