"""Tiled causal prefill attention with an online softmax: no ``[L, L]``
scores, an optional sliding window, grouped query heads, a value width of
its own, an optional per-row SELECTION of keys.

``flash_prefill(q [n, L, hq, d], k, v [n, L, h_kv, d], lens [n], window)
-> [n, L, hq * d]``: position ``p`` of sequence ``i`` attends ``max(0, p -
window + 1) .. p`` (``0 .. p`` without a window); rows at or past
``lens[i]`` are padding, their output is finite and means nothing.

One grid step is one block of ``block_q`` queries of one kv head of one
sequence. The head's ``g = hq / h_kv`` query heads are stacked into ONE
``[g * block_q, d]`` operand (row ``h * block_q + r`` = query head ``h``,
query ``r``), so a key block is multiplied once for the whole group. The
key loop is INSIDE the step and its bounds are data: k and v stay in HBM
and the step copies the blocks ``kb_lo .. kb_hi`` that hold the keys its
true queries can see, one block in flight while the last is multiplied
(two VMEM buffers). A block wholly above the diagonal, below the window or
past ``lens`` is neither fetched nor multiplied (the walk is bounded; no
index map is clamped into a re-fetch: PERF.md section 6, PR 38), and a
query block wholly past ``lens`` walks nothing and writes zeros. Only EDGE
blocks (those the diagonal or the window's lower edge crosses) build a
mask; the blocks between them take the unmasked body. bf16 operands,
float32 scores, sums and accumulator. q is scaled by ``1 / sqrt(d)`` once,
in the operand.

``v`` may be narrower or wider than ``q`` and ``k`` (latent attention in
its expanded form: q/k of 192 or 256, values of 128): the value buffers,
the accumulator and the output take ``v``'s width. ``keep [n, L, L]`` int8
(``ops/sparse_index.selection_mask``) lets row ``t`` see key ``j`` only
where ``keep[t, j] != 0``: the block of it that meets a key block rides
that block's DMAs, every block takes the masked body, and a key block in
which a row keeps nothing still costs its multiply (the walk's bounds are
the causal ones).

What a call costs: :func:`blocks_walked` (host arithmetic, the kernel's own
bounds) against the causal square's.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu import resilience
from triton_dist_tpu.ops.common import dist_pallas_call
from triton_dist_tpu.utils import cdiv

NEG_INF = float("-inf")
LANES = 128
# rows of the stacked query operand a step aims at, and keys a block
# (v5e, PR 39: scores [1024, 512] f32 are 2 MB of the 16 MiB scoped VMEM)
TARGET_ROWS = 1024
BLOCK_K = 512


def default_blocks(L: int, g: int) -> tuple[int, int]:
    """``(block_q, block_k)`` for sequences of ``L`` and a group of ``g``:
    powers of two from 128 up, ``g * block_q`` near ``TARGET_ROWS``, never
    more than ``L`` rounded up to 128."""
    cap = cdiv(L, LANES) * LANES
    bq = LANES
    while bq * 2 * g <= TARGET_ROWS:
        bq *= 2
    fit = lambda b: min(b, 1 << (cap - 1).bit_length())
    return fit(bq), fit(BLOCK_K)


def _key_blocks(q0, length, bq: int, bk: int, window: int | None):
    """``(first key block, blocks)`` a query block that starts at ``q0``
    walks in a sequence of ``length`` true positions: through the block of
    its last true query, from the block of its first query's window. The
    ONE statement of the bounds: the kernel and :func:`blocks_walked`."""
    lib = np if isinstance(q0, np.ndarray) else jnp
    last = lib.minimum(q0 + bq, length) - 1
    lo = 0 if window is None else lib.maximum(q0 - window + 1, 0) // bk
    return lo, lib.where(q0 < length, last // bk - lo + 1, 0)


def blocks_walked(lens, L: int, g: int,
                  window: int | None) -> tuple[int, int]:
    """``(live, square)`` key blocks of one call a kv head at the default
    block sizes: what the kernel walks for sequences of true lengths
    ``lens`` padded to ``L``, and what the causal square of ``L`` holds."""
    bq, bk = default_blocks(L, g)
    q0 = np.arange(cdiv(L, bq), dtype=np.int64)[None, :] * bq
    lens = np.asarray(lens, np.int64).reshape(-1, 1)
    live = _key_blocks(q0, lens, bq, bk, window)[1].sum()
    square = _key_blocks(q0, np.int64(L), bq, bk, None)[1].sum() * len(lens)
    return int(live), int(square)


def _flash_prefill_kernel(
    lens_ref, q_ref, k_hbm, v_hbm, *rest, bq: int, bk: int, g: int, d: int,
    scale: float, window: int | None, dv: int, selected: bool,
):
    if selected:
        (keep_hbm, o_ref, k_buf, v_buf, keep_buf, sems, q_scr, m_scr, l_scr,
         acc_scr) = rest
    else:
        o_ref, k_buf, v_buf, sems, q_scr, m_scr, l_scr, acc_scr = rest
    i, j, qb = (pl.program_id(a) for a in range(3))
    q0 = qb * bq
    kb_lo, n_blocks = _key_blocks(q0, lens_ref[i], bq, bk, window)

    def copies(kb, slot):
        rows = pl.ds(pl.multiple_of(kb * bk, bk), bk)
        cols = pl.ds(pl.multiple_of(j * d, d), d)
        v_cols = cols if dv == d else pl.ds(pl.multiple_of(j * dv, dv), dv)
        out = [pltpu.make_async_copy(
            hbm.at[i, rows, at], buf.at[slot], sems.at[t, slot])
            for t, (hbm, buf, at) in enumerate(
                ((k_hbm, k_buf, cols), (v_hbm, v_buf, v_cols)))]
        if selected:  # the rows' selection among this block's keys
            out.append(pltpu.make_async_copy(
                keep_hbm.at[i, pl.ds(pl.multiple_of(q0, bq), bq), rows],
                keep_buf.at[slot], sems.at[2, slot]))
        return out

    @pl.when(n_blocks > 0)
    def _():
        for dma in copies(kb_lo, 0):
            dma.start()

    for h in range(g):  # the group's heads, stacked on the rows
        q_scr[pl.ds(h * bq, bq), :] = (
            q_ref[0, :, pl.ds(h * d, d)].astype(jnp.float32) * scale
        ).astype(q_scr.dtype)
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def multiply(slot, k0, masked: bool):
        s = jax.lax.dot_general(                         # [g * bq, bk]
            q_scr[...], k_buf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if masked:
            pos = q0 + jax.lax.rem(
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 0), bq)
            key = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            ok = key <= pos
            if window is not None:
                ok = jnp.logical_and(ok, key > pos - window)
            if selected:
                ok = jnp.logical_and(
                    ok, keep_buf[slot].astype(jnp.int32) != 0)
            s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a row that has seen no key yet keeps m == -inf: subtract a
        # clamped copy, so that its update is exp(-inf) = 0 and not NaN
        m_safe = jnp.maximum(m_new, -1e30) if masked else m_new
        alpha = jnp.exp(m_prev - m_safe)
        p = jnp.exp(s - m_safe)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p.astype(v_buf.dtype), v_buf[slot],
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    def one_block(c, carry):
        slot = jax.lax.rem(c, 2)
        kb = kb_lo + c

        @pl.when(c + 1 < n_blocks)
        def _():
            for dma in copies(kb + 1, 1 - slot):
                dma.start()

        for dma in copies(kb, slot):
            dma.wait()
        k0 = kb * bk
        # the diagonal crosses the block, or the window's lower edge does
        if selected:  # every block is masked by the rows' selection
            multiply(slot, k0, True)
            return carry
        edge = k0 + bk - 1 > q0
        if window is not None:
            edge = jnp.logical_or(edge, k0 < q0 + bq - window)
        pl.when(edge)(functools.partial(multiply, slot, k0, True))
        pl.when(jnp.logical_not(edge))(
            functools.partial(multiply, slot, k0, False))
        return carry

    jax.lax.fori_loop(0, n_blocks, one_block, 0)
    l = l_scr[...]
    out = jnp.where(l > 0, acc_scr[...] / jnp.maximum(l, 1e-30), 0.0)
    for h in range(g):
        o_ref[0, :, pl.ds(h * dv, dv)] = out[h * bq:(h + 1) * bq].astype(
            o_ref.dtype)


def xla_flash_prefill(q, k, v, lens, window: int | None = None, keep=None):
    """The plain twin: explicit ``[L, L]`` scores in float32 (the golden
    of the resilience layer and of the tests; small sizes only)."""
    del lens  # rows past a length are padding: any finite value serves
    n, L, hq, d = q.shape
    h_kv, dv = k.shape[2], v.shape[3]
    f32 = jnp.float32
    qg = q.reshape(n, L, h_kv, hq // h_kv, d).astype(f32)
    s = jnp.einsum("nqhgd,nkhd->nhgqk", qg, k.astype(f32)) / math.sqrt(d)
    qp, kp = jnp.arange(L)[:, None], jnp.arange(L)[None, :]
    ok = kp <= qp
    if window is not None:
        ok = ok & (kp > qp - window)
    if keep is not None:
        ok = ok[None, None, None] & (keep != 0)[:, None, None]
    s = jnp.where(ok, s, NEG_INF)
    o = jnp.einsum("nhgqk,nkhd->nqhgd", jax.nn.softmax(s, -1), v.astype(f32))
    return o.reshape(n, L, hq * dv).astype(q.dtype)


def flash_prefill(
    q: jax.Array, k: jax.Array, v: jax.Array, lens: jax.Array, *,
    window: int | None = None, block_q: int | None = None,
    block_k: int | None = None, interpret: Any = None,
    keep: jax.Array | None = None, name: str = "flash_prefill",
) -> jax.Array:
    """Causal (optionally windowed) grouped-query attention of whole
    prompts, tiled: see the module's docstring. ``lens [n]`` int32 counts
    each sequence's TRUE positions (it bounds the walk; it masks nothing a
    true query can see). ``block_q`` / ``block_k`` default to
    :func:`default_blocks`; ``L`` is padded to a multiple of both. The
    kernel carries the window in its name (``flash_prefill_w4096``) after
    ``name`` (a family whose trace should tell its calls apart gives its
    own). ``v [n, L, h_kv, d_v]`` may have a width of its own: ``[n, L, hq
    * d_v]`` comes back. ``keep [n, L, L]`` int8 (one query head a kv head
    only) masks row ``t`` to the keys with ``keep[t, j] != 0``. Degrades
    to :func:`xla_flash_prefill` where the kernel cannot run and the
    resilience layer allows it."""
    if (q.shape[2] % k.shape[2] or k.shape[:3] != v.shape[:3]
            or q.shape[3] != k.shape[3]):
        raise ValueError(
            f"q {q.shape} is no whole group of query heads a kv head of "
            f"k {k.shape}, v {v.shape}")
    if window is not None and window < 1:
        raise ValueError(f"window={window} must be >= 1")
    if keep is not None and (q.shape[2] != k.shape[2] or window is not None):
        raise ValueError("a selection of keys goes with one query head a "
                         "kv head and no window")
    lens = lens.astype(jnp.int32)
    return resilience.guarded_call(
        name,
        lambda: _flash_prefill(q, k, v, lens, window, block_q, block_k,
                               interpret, keep, name),
        lambda: xla_flash_prefill(q, k, v, lens, window, keep),
    )


def _flash_prefill(q, k, v, lens, window, block_q, block_k, interpret,
                   keep=None, name="flash_prefill"):
    n, L, hq, d_true = q.shape
    h_kv, dv_true = k.shape[2], v.shape[3]
    g = hq // h_kv
    bq, bk = default_blocks(L, g)
    bq, bk = block_q or bq, block_k or bk
    # a kv head's columns are a lane-aligned block of the flattened row
    d = cdiv(d_true, LANES) * LANES
    dv = cdiv(dv_true, LANES) * LANES
    step = bq * bk // math.gcd(bq, bk)
    Lp = cdiv(L, step) * step

    def flat(x, width):
        pad = ((0, 0), (0, Lp - L), (0, 0), (0, width - x.shape[3]))
        return (jnp.pad(x, pad) if Lp != L or width != x.shape[3] else x
                ).reshape(n, Lp, -1)

    q2, k2, v2 = flat(q, d), flat(k, d), flat(v, dv)
    block = pl.BlockSpec((1, bq, g * d), lambda i, j, qb, *_: (i, qb, j))
    out_block = pl.BlockSpec(
        (1, bq, g * dv), lambda i, j, qb, *_: (i, qb, j))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    rows = g * bq
    selected = keep is not None
    if selected and Lp != L:
        keep = jnp.pad(keep, ((0, 0), (0, Lp - L), (0, Lp - L)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n, h_kv, Lp // bq),
        in_specs=[block, in_hbm, in_hbm] + [in_hbm] * selected,
        out_specs=out_block,
        scratch_shapes=[
            pltpu.VMEM((2, bk, d), k.dtype),
            pltpu.VMEM((2, bk, dv), v.dtype),
            *([pltpu.VMEM((2, bq, bk), jnp.int8)] * selected),
            pltpu.SemaphoreType.DMA((2 + selected, 2)),
            pltpu.VMEM((rows, d), k.dtype),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, dv), jnp.float32),
        ],
    )
    # an upper bound (the causal square): what a call walks follows lens
    pairs = n * hq * Lp * Lp // 2
    cost = pl.CostEstimate(
        flops=2 * pairs * (d + dv), transcendentals=pairs,
        bytes_accessed=q.dtype.itemsize * (
            q2.size + n * Lp * hq * dv + k2.size + v2.size))
    tag = "" if window is None else f"_w{window}"
    out = dist_pallas_call(
        functools.partial(
            _flash_prefill_kernel, bq=bq, bk=bk, g=g, d=d,
            scale=1.0 / math.sqrt(d_true), window=window, dv=dv,
            selected=selected),
        name=f"{name}{tag}",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, Lp, hq * dv), q.dtype),
        cost_estimate=cost,
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        uses_barrier=False,
        interpret=interpret,
    )(lens, q2, k2, v2, *((keep.astype(jnp.int8),) if selected else ()))
    if Lp != L or dv != dv_true:
        out = out.reshape(n, Lp, hq, dv)[:, :L, :, :dv_true]
    return out.reshape(n, L, hq * dv_true)
