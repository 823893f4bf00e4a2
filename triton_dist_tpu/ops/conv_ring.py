"""One token of a causal depthwise convolution for every slot of a batch,
in place in the ring that holds each slot's last inputs.

    y[i] = bias + sum_{j<K} w[j] * u_i[pos_i - K + 1 + j]     (u = 0 before 0)

``K`` taps (the last is the newest input), ``d`` independent channels. The
RING ``[layers, K, slots, d]`` float32 is keyed by position: row ``p % K``
of slot ``i`` holds ``u_i[p]`` for the last ``p`` of that residue
(``models/decode.py`` ``StatePagedKVCacheSpec``). A step at ``pos`` reads
the ``K - 1`` rows of the positions before it and writes row ``pos % K``,
never a row it reads: run again on the same inputs it leaves the same
ring. A row of a position before 0 reads as ZERO by a select, never by a
product: what a finished request left there may not be finite.

:func:`conv_ring_step` (``conv_ring_step`` in a device trace) is one
kernel a layer: the grid walks BLOCKS OF CHANNELS with every slot in a
block, each block of the layer's ring read once and written back whole
with row ``pos % K`` of each slot replaced (the other rows are rewritten
with themselves), the pool aliased in and out. The positions are a small
VECTOR operand ``[slots, 1]`` (the row masks are computed on the vector
unit: nothing of them is copied to scalar memory). The taps and the bias
come AS STORED, pinned to HBM, and are widened inside: no XLA fusion
stands between a ``[d]`` leaf and its only reader, and the compiler
prefetches neither (``ops/per_layer.py``). The layer is a prefetched
scalar, so a program's calls share one trace and one lowering.

It has an XLA twin (the resilience layer's golden; the unit tests' second
opinion).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu import resilience
from triton_dist_tpu.ops.common import dist_pallas_call
from triton_dist_tpu.ops.per_layer import in_hbm, layer_index, traced_once

# the name the kernel carries in a device trace
CONV_KERNEL = "conv_ring_step"
# bytes of blocks a grid step may hold in VMEM, double-buffered: half of
# the 16 MiB Mosaic gives a kernel unasked
VMEM_BLOCKS = 8 * 2**20
# a 1-D operand (the bias, as stored) is tiled by 1024 elements: a block of
# it is a multiple of that, or the whole
CHANNEL_UNIT = 1024


def channel_block(d: int, slots: int, taps: int) -> int:
    """Channels a grid step holds: the widest multiple of
    :data:`CHANNEL_UNIT` that divides ``d`` and whose blocks (the ring's in
    and out, ``u`` and ``y``, each twice for the pipeline) fit
    :data:`VMEM_BLOCKS`; all of ``d`` where it is no such multiple."""
    if d % CHANNEL_UNIT:
        return d
    units = d // CHANNEL_UNIT
    fit = VMEM_BLOCKS // (2 * 4 * slots * (2 * taps + 2) * CHANNEL_UNIT)
    return CHANNEL_UNIT * max(
        n for n in range(1, units + 1) if units % n == 0 and n <= max(fit, 1))


def _row_codes(pos, taps: int) -> list:
    """What each ring row is to a step at ``pos [slots, 1]`` (never
    negative): by row, ``[slots, 1]`` int32 holding 0 for the row this
    step writes (``pos % taps``), ``a`` in ``1..taps - 1`` for the input
    ``a`` positions back (tap ``taps - 1 - a``) and ``taps`` where that
    position lies before 0."""
    q = lax.rem(pos, taps)
    codes = []
    for r in range(taps):
        age = q - r
        age = lax.select(age < 0, age + taps, age)
        codes.append(lax.select(age > pos, jnp.full_like(age, taps), age))
    return codes


def _conv_kernel(li_ref, pos_ref, u_ref, w_ref, b_ref, ring_ref, y_ref,
                 ring_out_ref):
    """Grid ``(channel block,)``: ``ring_ref [K, slots, db]`` is the layer's
    block, ``ring_out_ref`` the same block of the same buffer. The masks
    are compared at the block's width from ONE broadcast integer a row
    (``lax.select``, no ``jnp.where``): a broadcast of booleans costs
    Mosaic's lowering a traced helper each, sixteen a body."""
    del li_ref                          # the index maps' (the ring's layer)
    taps, slots, db = ring_ref.shape
    u = u_ref[:]
    w = w_ref[:].astype(jnp.float32)
    w = [w[t:t + 1] for t in range(taps)]
    out = b_ref[:].astype(jnp.float32)[None] + w[taps - 1] * u
    zero = jnp.zeros((slots, db), jnp.float32)
    for r, code in enumerate(_row_codes(pos_ref[:], taps)):
        row = ring_ref[r]
        code = lax.broadcast_in_dim(code, (slots, db), (0, 1))
        for a in range(1, taps):
            # a select, not a product: a stale row may hold anything
            out = out + lax.select(code == a, w[taps - 1 - a] * row, zero)
        ring_out_ref[r] = lax.select(code == 0, u, row)
    y_ref[:] = out


def _xla_conv_ring_step(pool, li, u, pos, w, bias):
    taps = pool.shape[1]
    w, ring = w.astype(jnp.float32), pool[li]               # [K, slots, d]
    out = bias.astype(jnp.float32) + w[taps - 1] * u
    for r in range(taps):
        # ring row r holds the input of the last position == r (mod K):
        # tap j of this step, unless it is the row this step writes
        j = (r - pos + taps - 1) % taps
        live = (j < taps - 1) & (pos - (taps - 1) + j >= 0)
        tap = jnp.take(w, jnp.minimum(j, taps - 2), axis=0)     # [slots, d]
        out = out + jnp.where(live[:, None], tap * ring[r], 0.0)
    slots = jnp.arange(u.shape[0])
    return out, pool.at[li, pos % taps, slots].set(u, mode="drop")


@traced_once
def _conv_ring_fused(li, pool, u, pos, w, bias, *, interpret):
    _, taps, slots, d = pool.shape
    db = channel_block(d, slots, taps)
    rows = pl.BlockSpec((slots, db), lambda i, li: (0, i))
    ring = pl.BlockSpec((None, taps, slots, db),
                        lambda i, li: (li[0], 0, 0, i))
    y, pool = dist_pallas_call(
        _conv_kernel,
        name=CONV_KERNEL,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(d // db,),
            in_specs=[pl.BlockSpec((slots, 1), lambda i, li: (0, 0)), rows,
                      pl.BlockSpec((taps, db), lambda i, li: (0, i)),
                      pl.BlockSpec((db,), lambda i, li: (i,)), ring],
            out_specs=(rows, ring),
        ),
        out_shape=(jax.ShapeDtypeStruct((slots, d), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)),
        # the ring is updated where it lies: operand 5 (the prefetched
        # layer counts) is output 1
        input_output_aliases={5: 1},
        cost_estimate=pl.CostEstimate(
            flops=4 * taps * slots * d, transcendentals=0,
            bytes_accessed=4 * slots * d * (2 * taps + 2)),
        dimension_semantics=("parallel",),
        uses_barrier=False,
        interpret=interpret,
    )(li, pos[:, None], u, in_hbm(w, interpret), in_hbm(bias, interpret),
      pool)
    return y, pool


def conv_ring_step(pool, li: int, u, pos, w, bias, *, interpret: Any = None):
    """Every slot's convolution one token on, in the ring. ``pool [layers,
    K, slots, d]`` float32, ``li`` the layer, ``u [slots, d]`` the
    input at ``pos [slots]``, ``w [K, d]`` tap-major and ``bias [d]`` in the
    dtype they are stored in -> ``(y [slots, d] float32, pool)`` with row
    ``pos % K`` of each slot holding ``u``."""
    u, pos = u.astype(jnp.float32), pos.astype(jnp.int32)
    return resilience.guarded_call(
        CONV_KERNEL,
        lambda: _conv_ring_fused(layer_index(li), pool, u, pos, w, bias,
                                 interpret=interpret),
        lambda: _xla_conv_ring_step(pool, li, u, pos, w, bias),
    )
