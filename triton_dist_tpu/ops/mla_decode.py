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
# the name the kernel carries in a device trace (perfbench reads it)
KERNEL_NAME = "mla_paged_decode"


def latent_row(d_latent: int, d_rope: int) -> int:
    """Stored width of one latent row: ``c_kv | k_r`` padded to whole lane
    tiles (512 + 64 -> 640: 576 is 4.5 tiles, and a half tile at the end
    of a row makes every page DMA and the score dot ragged)."""
    return round_up(d_latent + d_rope, LANE)


def _mla_decode_kernel(
    kv_lens_ref, bt_ref, q_ref, *rest,
    n_steps: int, pages_per_step: int, page_size: int, scale: float,
    d_v: int,
):
    """Grid ``(sequence, chunk)``; ``pages_per_step`` pages concatenated
    into one online-softmax span per step, as the k/v paged kernel does."""
    del bt_ref
    P = pages_per_step
    page_refs = rest[:P]
    out_ref, m_scr, l_scr, acc_scr = rest[P:]
    c = pl.program_id(1)
    kv_len = kv_lens_ref[pl.program_id(0)]

    @pl.when(c == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(c * P * page_size < kv_len)
    def _():
        rows = (
            jnp.concatenate([r[0, 0] for r in page_refs], axis=0)
            if P > 1 else page_refs[0][0, 0]
        )                                               # [P*page, row]
        m_scr[:], l_scr[:], acc_scr[:] = _online_softmax_step(
            q_ref[0], rows, rows[:, :d_v], None, None,
            c * P * page_size, kv_len, scale,
            m_scr[:], l_scr[:], acc_scr[:],
        )

    @pl.when(c == n_steps - 1)
    def _():
        out_ref[0], _ = _finalize_softmax(m_scr[:], l_scr[:], acc_scr[:])


def _xla_mla_decode(q, pool, li, kv_lens, block_table, *, d_v, scale):
    """Golden slow path: gather the sequence's pages and attend in XLA."""
    b, max_pages = block_table.shape
    page = pool.shape[2]
    rows = pool[li][block_table]                  # [b, max_pages, page, row]
    rows = rows.reshape(b, max_pages * page, -1).astype(jnp.float32)
    s = jnp.einsum("bhr,btr->bht", q.astype(jnp.float32), rows) * scale
    live = jnp.arange(max_pages * page)[None, None, :] < kv_lens[:, None, None]
    s = jnp.where(live, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(kv_lens[:, None, None] > 0, p, 0.0)
    return jnp.einsum("bht,btv->bhv", p, rows[..., :d_v])


def _mla_decode_fused(
    q, pool, li, kv_lens, block_table, *, d_v, scale, pages_per_step,
    interpret,
):
    b, hq, row = q.shape
    _, _, page_size, _ = pool.shape
    max_pages = block_table.shape[1]
    P = pages_per_step
    n_steps = cdiv(max_pages, P)

    def page_map(p):
        def index_map(i, c, kv_lens_ref, bt_ref):
            # chunks past the live length re-name the last live page: an
            # unchanged block index costs no fetch, and the length mask
            # (or the chunk gate) keeps the duplicate out of the softmax
            last = jnp.maximum(kv_lens_ref[i] - 1, 0) // page_size
            j = jnp.minimum(jnp.minimum(c * P + p, last), max_pages - 1)
            return (li, bt_ref[i, j], 0, 0)
        return index_map

    out = dist_pallas_call(
        functools.partial(
            _mla_decode_kernel, n_steps=n_steps, pages_per_step=P,
            page_size=page_size, scale=scale, d_v=d_v,
        ),
        name=KERNEL_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_steps),
            in_specs=[
                pl.BlockSpec((1, hq, row), lambda i, c, *_: (i, 0, 0)),
                *(pl.BlockSpec((1, 1, page_size, row), page_map(p))
                  for p in range(P)),
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
            flops=2 * b * hq * max_pages * page_size * (row + d_v),
            bytes_accessed=b * max_pages * page_size * row
            * pool.dtype.itemsize,
            transcendentals=b * hq * max_pages * page_size,
        ),
        dimension_semantics=("parallel", "arbitrary"),
        uses_barrier=False,
        interpret=interpret,
    )(
        kv_lens, block_table.astype(jnp.int32), q.astype(pool.dtype),
        *(pool for _ in range(P)),
    )
    return out


def mla_paged_decode(
    q: jax.Array,
    pool: jax.Array,
    li: int,
    kv_lens: jax.Array,
    block_table: jax.Array,
    *,
    d_v: int,
    scale: float,
    interpret: Any = None,
) -> jax.Array:
    """Absorbed MLA decode for one layer.

    q: ``[b, heads, row]`` = ``[q_lat | q_r | 0]`` per head; pool:
    ``[n_layers, n_pages, page, row]`` (rows ``[c_kv | k_r | 0]``); ``li``
    the (static) layer; kv_lens ``[b]`` int32; block_table ``[b,
    max_pages]``. ``scale`` is the model's softmax scale
    (``1/sqrt(qk_head_dim)`` of the EXPANDED form, not of ``row``).
    Returns the latent output ``[b, heads, d_v]`` float32; the caller
    applies ``W_kvb,v``.
    """
    kv_lens = kv_lens.astype(jnp.int32)
    max_pages = block_table.shape[1]
    # 4 pages a step: a page of 128 rows is 160 KB, two grid steps'
    # overhead in DMA time, so wider spans pay until dead pages are
    # fetched for short contexts
    pages_per_step = max(p for p in (1, 2, 4) if max_pages % p == 0)
    return resilience.guarded_call(
        KERNEL_NAME,
        lambda: _mla_decode_fused(
            q, pool, li, kv_lens, block_table, d_v=d_v, scale=scale,
            pages_per_step=pages_per_step, interpret=interpret,
        ),
        lambda: _xla_mla_decode(
            q, pool, li, kv_lens, block_table, d_v=d_v, scale=scale,
        ),
    )
