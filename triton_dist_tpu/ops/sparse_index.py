"""A learned sparse-attention INDEXER's scores and selection (the
DeepSeek-V3.2 form): which cached positions a full-attention layer reads.

``I(t, j) = sum_g w_g(t) * relu(q_g(t) . k(j)) * scale`` for ``j <= t``:
``G`` index heads of width ``d``, ONE index key a token (its own small
pool, ``models/decode.py``), a weight a head from the token's own row. A
row attends the ``topk`` positions of largest ``I(t, .)``, every ``j <= t``
while ``t < topk``, a tie to the lower position: the set ``jax.lax.top_k``
picks on the float32 scores.

- a STEP: :func:`index_scores_paged` (kernel ``index_score``) walks each
  slot's live pages of the index-key pool where it lies, ``pages_per_step``
  pages a grid step (an index page is 32 KB: the step's overhead, not its
  bytes, is the cost), and writes ``[b, s_max]`` float32 scores, ``-inf``
  at and past a slot's length; :func:`topk_mask` of them is the step's
  selection, a flag a position (``ops.mla_decode.sparse_mla_decode`` reads
  the latent rows' live pages under it: no sort, no gather).
- an ADMISSION: :func:`selection_mask` scores a block of query rows
  against every key (kernel ``index_score_prefill``: key blocks above the
  diagonal are not multiplied) and keeps, a row, its ``topk`` largest
  (:func:`topk_mask`). One block of rows is in float32 at a time; what
  leaves is ``int8``.
- :func:`topk_mask`: the ``topk``-th largest score of a row found by
  bisection on the scores' bits (32 counting passes, no sort), ties at it
  kept from the lowest position up.
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

NEG_INF = float("-inf")
# the names the kernels carry in a device trace (perfbench reads them)
KERNEL_NAME = "index_score"
PREFILL_KERNEL_NAME = "index_score_prefill"
# query rows whose float32 scores against every key exist at one time
MASK_ROWS = 1024
BLOCK_Q, BLOCK_K = 256, 512


# -- a step: scores of each slot's live rows ------------------------------------

def _index_score_kernel(kv_lens_ref, bt_ref, q_ref, w_ref, *rest, P: int,
                        page_size: int, scale: float):
    """Grid ``(slot, chunk)``: ``P`` pages of index keys against the
    slot's ``[G, d]`` index queries, weighted and summed over the heads."""
    del bt_ref
    page_refs, out_ref = rest[:P], rest[P]
    c = pl.program_id(1)
    kv_len = kv_lens_ref[pl.program_id(0)]
    base = c * P * page_size

    @pl.when(base < kv_len)
    def _():
        keys = (jnp.concatenate([r[0, 0] for r in page_refs], axis=0)
                if P > 1 else page_refs[0][0, 0])           # [P*page, d]
        s = jax.lax.dot_general(                            # [G, P*page]
            q_ref[0], keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = jnp.sum(jnp.maximum(s, 0.0) * w_ref[0], axis=0,
                    keepdims=True) * scale
        pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        out_ref[0] = jnp.where(pos < kv_len, s, NEG_INF)

    @pl.when(base >= kv_len)
    def _():
        out_ref[0] = jnp.full(out_ref.shape[1:], NEG_INF, out_ref.dtype)


def _xla_index_scores(q, w, pool, li, kv_lens, block_table, *, scale):
    """The plain twin: every page of the table row gathered."""
    b, max_pages = block_table.shape
    keys = pool[li][block_table].reshape(b, max_pages * pool.shape[2], -1)
    s = jnp.einsum("bgd,btd->bgt", q.astype(jnp.float32),
                   keys.astype(jnp.float32))
    s = jnp.einsum("bgt,bg->bt", jnp.maximum(s, 0.0), w) * scale
    live = jnp.arange(s.shape[1])[None, :] < kv_lens[:, None]
    return jnp.where(live, s, NEG_INF)


def _index_scores_fused(q, w, pool, li, kv_lens, block_table, *, scale,
                        interpret):
    b, g, d = q.shape
    page_size = pool.shape[2]
    max_pages = block_table.shape[1]
    P = max(p for p in (1, 2, 4, 8, 16) if max_pages % p == 0)
    n_steps = max_pages // P

    def page_map(p):
        def index_map(i, c, kv_lens_ref, bt_ref):
            # chunks past the live length re-name the last live page: an
            # unchanged block index costs no fetch
            last = jnp.maximum(kv_lens_ref[i] - 1, 0) // page_size
            return (li, bt_ref[i, jnp.minimum(c * P + p, last)], 0, 0)
        return index_map

    rows = max_pages * page_size
    out = dist_pallas_call(
        functools.partial(_index_score_kernel, P=P, page_size=page_size,
                          scale=scale),
        name=KERNEL_NAME,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_steps),
            in_specs=[
                pl.BlockSpec((1, g, d), lambda i, c, *_: (i, 0, 0)),
                pl.BlockSpec((1, g, 1), lambda i, c, *_: (i, 0, 0)),
                *(pl.BlockSpec((1, 1, page_size, d), page_map(p))
                  for p in range(P)),
            ],
            out_specs=pl.BlockSpec((1, 1, P * page_size),
                                   lambda i, c, *_: (i, 0, c)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, 1, rows), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * g * rows * d,
            bytes_accessed=b * rows * (d * pool.dtype.itemsize + 4),
            transcendentals=0),
        dimension_semantics=("parallel", "arbitrary"),
        uses_barrier=False,
        interpret=interpret,
    )(kv_lens, block_table.astype(jnp.int32), q.astype(pool.dtype),
      w.astype(jnp.float32)[..., None], *(pool for _ in range(P)))
    return out[:, 0]


def index_scores_paged(
    q: jax.Array, w: jax.Array, pool: jax.Array, li: int,
    kv_lens: jax.Array, block_table: jax.Array, *, scale: float,
    interpret: Any = None,
) -> jax.Array:
    """A step's index scores: ``q [b, G, d]`` (each slot's index queries),
    ``w [b, G]`` float32, ``pool [n_layers, n_pages, page, d]`` the index
    keys (``li`` the static layer), ``kv_lens [b]``, ``block_table [b,
    pages a slot]``. ``[b, pages a slot * page]`` float32: ``I(pos, j)`` at
    ``j < kv_len``, ``-inf`` elsewhere."""
    kv_lens = kv_lens.astype(jnp.int32)
    return resilience.guarded_call(
        KERNEL_NAME,
        lambda: _index_scores_fused(q, w, pool, li, kv_lens, block_table,
                                    scale=scale, interpret=interpret),
        lambda: _xla_index_scores(q, w, pool, li, kv_lens, block_table,
                                  scale=scale),
    )


# -- an admission: which keys each row of a prompt keeps ---------------------------

def _index_prefill_kernel(row0_ref, q_ref, w_ref, k_ref, o_ref, *, g: int,
                          d: int, bq: int, bk: int, scale: float):
    """Grid ``(query block, key block)`` of one block of rows that starts
    at position ``row0``: the heads one at a time, each ``[bq, d] x [d,
    bk]``, ReLU, weighted by the row's own weight of the head."""
    q0 = row0_ref[0] + pl.program_id(0) * bq
    k0 = pl.program_id(1) * bk

    @pl.when(k0 <= q0 + bq - 1)
    def _():
        keys = k_ref[...]
        w = w_ref[...]
        acc = jnp.zeros((bq, bk), jnp.float32)
        for h in range(g):
            s = jax.lax.dot_general(
                q_ref[:, pl.ds(h * d, d)], keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc = acc + jnp.maximum(s, 0.0) * w[:, h:h + 1]
        pos = q0 + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        key = k0 + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
        o_ref[...] = jnp.where(key <= pos, acc * scale, NEG_INF)

    @pl.when(k0 > q0 + bq - 1)
    def _():
        o_ref[...] = jnp.full(o_ref.shape, NEG_INF, o_ref.dtype)


def _xla_prefill_scores(q, w, k, row0, *, g, scale):
    r, L = q.shape[0], k.shape[0]
    s = jnp.einsum("rgd,td->rgt", q.reshape(r, g, -1).astype(jnp.float32),
                   k.astype(jnp.float32))
    s = jnp.einsum("rgt,rg->rt", jnp.maximum(s, 0.0), w) * scale
    ok = jnp.arange(L)[None, :] <= row0 + jnp.arange(r)[:, None]
    return jnp.where(ok, s, NEG_INF)


def prefill_scores(q, w, k, row0, *, g: int, scale: float,
                   interpret: Any = None) -> jax.Array:
    """Index scores of ``r`` consecutive query rows (positions ``row0 ..``)
    against all ``L`` keys: ``q [r, g * d]``, ``w [r, g]`` float32, ``k
    [L, d]`` -> ``[r, L]`` float32, ``-inf`` above the diagonal. ``r`` and
    ``L`` are whole blocks (the caller pads)."""
    r, L = q.shape[0], k.shape[0]
    d = k.shape[1]
    bq, bk = min(BLOCK_Q, r), min(BLOCK_K, L)
    if r % bq or L % bk:
        raise ValueError(f"{r} rows x {L} keys: not whole blocks of "
                         f"{bq} x {bk}")
    row0 = jnp.asarray(row0, jnp.int32).reshape(1)

    def fused():
        return dist_pallas_call(
            functools.partial(_index_prefill_kernel, g=g, d=d, bq=bq, bk=bk,
                              scale=scale),
            name=PREFILL_KERNEL_NAME,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(r // bq, L // bk),
                in_specs=[
                    pl.BlockSpec((bq, g * d), lambda i, j, *_: (i, 0)),
                    pl.BlockSpec((bq, g), lambda i, j, *_: (i, 0)),
                    pl.BlockSpec((bk, d), lambda i, j, *_: (j, 0)),
                ],
                out_specs=pl.BlockSpec((bq, bk), lambda i, j, *_: (i, j)),
            ),
            out_shape=jax.ShapeDtypeStruct((r, L), jnp.float32),
            cost_estimate=pl.CostEstimate(
                flops=2 * r * L * g * d, transcendentals=0,
                bytes_accessed=q.size * q.dtype.itemsize + 4 * r * L),
            dimension_semantics=("parallel", "parallel"),
            uses_barrier=False,
            interpret=interpret,
        )(row0, q, w.astype(jnp.float32), k.astype(q.dtype))

    return resilience.guarded_call(
        PREFILL_KERNEL_NAME, fused,
        lambda: _xla_prefill_scores(q, w, k, row0[0], g=g, scale=scale))


def _sortable(x: jax.Array) -> jax.Array:
    """float32 -> uint32 whose unsigned order is the floats' TOTAL order
    (-0.0 below 0.0), the order ``jax.lax.top_k`` sorts by."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    flip = jnp.where(bits >> 31 == 1, jnp.uint32(0xFFFFFFFF),
                     jnp.uint32(0x80000000))
    return bits ^ flip


def topk_mask(scores: jax.Array, topk: int) -> jax.Array:
    """``[r, L]`` bool: a row's ``topk`` largest FINITE scores (all of them
    where it has no more), a tie at the ``topk``-th kept from the lowest
    position up: the set ``jax.lax.top_k`` picks. The ``topk``-th largest
    is built a bit at a time (the largest ``T`` with ``count(key >= T) >=
    topk``): 32 counting passes, no sort."""
    key = _sortable(scores)

    def one_bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        n = jnp.sum(key >= cand[:, None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= topk, cand, t)

    t = jax.lax.fori_loop(0, 32, one_bit,
                          jnp.zeros(scores.shape[:1], jnp.uint32))
    above = key > t[:, None]
    at = key == t[:, None]
    room = topk - jnp.sum(above, axis=-1, dtype=jnp.int32)
    keep = above | (at & (jnp.cumsum(at, axis=-1, dtype=jnp.int32)
                          <= room[:, None]))
    return keep & jnp.isfinite(scores)


def selection_mask(q, w, k, topk: int, *, g: int, scale: float,
                   interpret: Any = None) -> jax.Array:
    """An admission's selection: ``q [L, g * d]`` index queries, ``w [L,
    g]``, ``k [L, d]`` index keys of ONE sequence -> ``[L, L]`` int8, 1
    where row ``t`` attends key ``j`` (``j <= t`` among its ``topk``
    largest ``I(t, .)``). Rows go ``MASK_ROWS`` at a time: their float32
    scores exist for one block only."""
    L = q.shape[0]
    rows = min(MASK_ROWS, L)
    if L % rows:
        raise ValueError(f"{L} rows: not whole blocks of {rows}")

    def one_block(i):
        r0 = i * rows
        s = prefill_scores(
            jax.lax.dynamic_slice_in_dim(q, r0, rows),
            jax.lax.dynamic_slice_in_dim(w, r0, rows), k, r0, g=g,
            scale=scale, interpret=interpret)
        return topk_mask(s, topk).astype(jnp.int8)

    if rows == L:
        return one_block(0)
    out = jax.lax.map(one_block, jnp.arange(L // rows, dtype=jnp.int32))
    return out.reshape(L, L)
