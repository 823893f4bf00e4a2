"""The selective state-space recurrence (Mamba-1) as two kernels: the scan
over a prompt and the one-token update of a decode step.

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * c_t) * B_t
    y_t = sum_n h_t[n] * C_t[n] + D * c_t

``d`` independent channels, ``N`` states a channel; ``c`` is the
convolved, activated input, ``dt`` the (softplus'd) step, ``B`` and ``C``
the token's input and output maps, ``A`` negative. Everything is float32:
the state is a sum over hundreds of steps.

LAYOUT. A state is held ``[N, d]``, channels on the lanes: ``[d, N]`` with
``N`` = 16 would fill an eighth of every 128-lane tile, in HBM and in
VMEM alike. ``A`` comes the same way (``[N, d]``), a token's ``B`` / ``C``
as a column ``[N, 1]`` that broadcasts along the lanes.

- :func:`selective_scan` (``selective_scan`` in a device trace): one
  sequence ``[L, d]``; the grid walks blocks of channels and, inside them,
  chunks of time with the state resident in VMEM. ``[L, d, N]`` never
  exists. A position whose ``dt`` is 0 leaves the state as it was
  (``exp(0) = 1``, ``0 * c = 0``): that is how a caller stops the scan at a
  prompt's true length inside a padded bucket.
- :func:`selective_state_update` (``selective_state_update``): every slot
  of a batch one token on, in place in the pool ``[layers, 2, slots, N,
  d]``, whose axis of 2 is keyed by position (``models/decode.py``
  ``StatePagedKVCacheSpec``): slot ``i`` at ``pos[i]`` READS the row of
  ``pos[i] - 1`` and WRITES the row of ``pos[i]``, the other, so running
  the update again from the same inputs reads the same state and writes
  the same result. A slot at position 0 reads zeros instead: a sequence's
  first token. ``pos`` is the ONE vector of the slots the kernel is
  handed in scalar memory (the layer, a scalar, is the other prefetched
  operand: a program's 26 calls share one trace, ``ops/per_layer.py``);
  the step's bias and ``D`` come as they are stored and are widened
  inside (``dt = softplus(dt_in + b_dt)`` is the kernel's prologue): no
  XLA fusion reads a ``[d]`` leaf on the way. The token's ``c``, ``dt_in``
  and ``y`` are whole ``[slots, d]`` blocks a slot's row is read from and
  written to: a block of one row would need them re-laid ``[slots, 1,
  d]``, three copies a layer.

Each has an XLA twin (the resilience layer's golden; the unit tests'
second opinion).
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
from triton_dist_tpu.ops.per_layer import in_hbm, layer_index, traced_once
from triton_dist_tpu.utils import round_up

# the names the kernels carry in a device trace (perfbench reads them)
SCAN_KERNEL = "selective_scan"
UPDATE_KERNEL = "selective_state_update"
# time steps a grid step of the scan walks, and channels a block holds: a
# block's state [16, 512] is 8 vector registers, carried through the loop
TIME_CHUNK = 64
CHANNEL_BLOCK = 512
# steps unrolled in the loop's body: y is stored a whole sublane tile at once
UNROLL = 8


def _advance(h, a, dt, x, b_col, c_col, d_row):
    """One token: ``h [N, d]``, ``dt``, ``x``, ``d_row [1, d]``, ``b_col``,
    ``c_col [N, 1]`` -> ``(h, y [1, d])``."""
    h = jnp.exp(dt * a) * h + (dt * x) * b_col
    return h, jnp.sum(h * c_col, axis=0, keepdims=True) + d_row * x


# -- the scan over a prompt --------------------------------------------------------

def _scan_kernel(c_ref, dt_ref, b_ref, cm_ref, a_ref, d_ref, h0_ref, y_ref,
                 h_ref, *, chunk: int):
    """Grid ``(channel block, time chunk)``; ``h_ref`` (the output block,
    resident across the time axis) carries the state between chunks."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        h_ref[:] = h0_ref[:]

    a, d_row = a_ref[:], d_ref[:]

    def body(i, h):
        t0 = pl.multiple_of(i * UNROLL, UNROLL)
        x8, dt8 = c_ref[pl.ds(t0, UNROLL), :], dt_ref[pl.ds(t0, UNROLL), :]
        b8, c8 = b_ref[pl.ds(t0, UNROLL)], cm_ref[pl.ds(t0, UNROLL)]
        rows = []
        for k in range(UNROLL):
            h, y = _advance(h, a, dt8[k:k + 1], x8[k:k + 1], b8[k], c8[k],
                            d_row)
            rows.append(y)
        y_ref[pl.ds(t0, UNROLL), :] = jnp.concatenate(rows, axis=0)
        return h

    h_ref[:] = jax.lax.fori_loop(0, chunk // UNROLL, body, h_ref[:])


def _xla_selective_scan(c, dt, b, cm, a, d_skip, h0):
    def step(h, xs):
        x, dt_t, b_t, c_t = xs
        h, y = _advance(h, a, dt_t[None], x[None], b_t[:, None], c_t[:, None],
                        d_skip[None])
        return h, y[0]

    h, y = jax.lax.scan(step, h0, (c, dt, b, cm))
    return y, h


def _selective_scan_fused(c, dt, b, cm, a, d_skip, h0, *, interpret):
    L, d = c.shape
    n = a.shape[0]
    chunk = min(TIME_CHUNK, round_up(L, UNROLL))
    lp = round_up(L, chunk)
    db = CHANNEL_BLOCK if d % CHANNEL_BLOCK == 0 else d
    # padded positions: dt = 0, the state stays
    c, dt, b, cm = (jnp.pad(x, ((0, lp - L), (0, 0))) for x in (c, dt, b, cm))
    rows = pl.BlockSpec((chunk, db), lambda i, t: (t, i))
    cols = pl.BlockSpec((chunk, n, 1), lambda i, t: (t, 0, 0))
    state = pl.BlockSpec((n, db), lambda i, t: (0, i))
    y, h = dist_pallas_call(
        functools.partial(_scan_kernel, chunk=chunk),
        name=SCAN_KERNEL,
        grid=(d // db, lp // chunk),
        in_specs=[rows, rows, cols, cols, state,
                  pl.BlockSpec((1, db), lambda i, t: (0, i)), state],
        out_specs=(rows, state),
        out_shape=(jax.ShapeDtypeStruct((lp, d), jnp.float32),
                   jax.ShapeDtypeStruct((n, d), jnp.float32)),
        cost_estimate=pl.CostEstimate(
            flops=7 * lp * d * n, transcendentals=lp * d * n,
            bytes_accessed=4 * (3 * lp * d + 2 * lp * n + 3 * n * d)),
        dimension_semantics=("parallel", "arbitrary"),
        uses_barrier=False,
        interpret=interpret,
    )(c, dt, b[..., None], cm[..., None], a, d_skip[None], h0)
    return y[:L], h


def selective_scan(c, dt, b, cm, a, d_skip, h0, *, interpret: Any = None):
    """One sequence through the recurrence. ``c, dt [L, d]``, ``b, cm [L,
    N]``, ``a [N, d]``, ``d_skip [d]``, ``h0 [N, d]``, float32 ->
    ``(y [L, d], h_L [N, d])``."""
    args = tuple(x.astype(jnp.float32) for x in (c, dt, b, cm, a, d_skip, h0))
    return resilience.guarded_call(
        SCAN_KERNEL,
        lambda: _selective_scan_fused(*args, interpret=interpret),
        lambda: _xla_selective_scan(*args),
    )


# -- one token of every slot, in the pool ---------------------------------------

def _update_kernel(li_ref, pos_ref, c_ref, dt_ref, bdt_ref, b_ref, cm_ref,
                   a_ref, d_ref, h_in_ref, y_ref, h_out_ref):
    """Grid ``(slot,)``: the slot's state comes from the pool's row of
    ``pos[i] - 1`` and goes to the row of ``pos[i]`` (the block index maps
    say which)."""
    del li_ref                          # the index maps' (the pool's layer)
    i = pl.program_id(0)
    h = h_in_ref[0, 0, 0]
    # a select, not a product: what a finished request left may not be finite
    h = jnp.where(jnp.broadcast_to(pos_ref[i], h.shape) == 0, 0.0, h)
    row = pl.ds(i, 1)
    dt = jax.nn.softplus(
        dt_ref[row, :] + bdt_ref[:].astype(jnp.float32)[None])
    h, y = _advance(h, a_ref[:], dt, c_ref[row, :], b_ref[i], cm_ref[i],
                    d_ref[:].astype(jnp.float32)[None])
    h_out_ref[0, 0, 0] = h
    y_ref[row, :] = y


def _xla_state_update(pool, li, pos, c, dt_in, b_dt, b, cm, a, d_skip):
    slots = jnp.arange(c.shape[0])
    dt = jax.nn.softplus(dt_in + b_dt.astype(jnp.float32))
    h = jnp.where((pos == 0)[:, None, None], 0.0,
                  pool[li, (pos + 1) % 2, slots])
    h = (jnp.exp(dt[:, None, :] * a[None]) * h
         + (dt * c)[:, None, :] * b[:, :, None])
    y = jnp.einsum("bnd,bn->bd", h, cm) + d_skip.astype(jnp.float32) * c
    return y, pool.at[li, pos % 2, slots].set(h)


@traced_once
def _state_update_fused(li, pool, pos, c, dt_in, b_dt, b, cm, a, d_skip, *,
                        interpret):
    slots, d = c.shape
    n = a.shape[0]
    whole = lambda shape: pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))
    block = (1, 1, 1, n, d)
    y, pool = dist_pallas_call(
        _update_kernel,
        name=UPDATE_KERNEL,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots,),
            in_specs=[
                whole((slots, d)), whole((slots, d)), whole((d,)),
                whole((slots, n, 1)), whole((slots, n, 1)), whole((n, d)),
                whole((d,)),
                pl.BlockSpec(
                    block, lambda i, li, p: (li[0], (p[i] + 1) % 2, i, 0, 0)),
            ],
            out_specs=(
                whole((slots, d)),
                pl.BlockSpec(
                    block, lambda i, li, p: (li[0], p[i] % 2, i, 0, 0)),
            ),
        ),
        out_shape=(jax.ShapeDtypeStruct((slots, d), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)),
        # the pool is updated where it lies: operand 9 (the two prefetched
        # vectors count) is output 1
        input_output_aliases={9: 1},
        cost_estimate=pl.CostEstimate(
            flops=7 * slots * d * n, transcendentals=slots * d * (n + 2),
            bytes_accessed=4 * slots * (2 * n * d + 3 * d + 2 * n)),
        dimension_semantics=("arbitrary",),
        uses_barrier=False,
        interpret=interpret,
    )(li, pos, c, dt_in, in_hbm(b_dt, interpret),
      b[..., None], cm[..., None], a, in_hbm(d_skip, interpret), pool)
    return y, pool


def selective_state_update(pool, li: int, pos, c, dt_in, b_dt, b, cm, a,
                           d_skip, *, interpret: Any = None):
    """Every slot one token on, in the pool. ``pool [layers, 2, slots, N,
    d]`` float32, ``li`` the layer, ``pos [slots]`` each slot's
    position: its state is READ from row ``(pos - 1) % 2`` of the axis of 2
    (zeros at position 0) and written to row ``pos % 2``; ``c, dt_in
    [slots, d]``, the step before its bias and softplus, ``b, cm [slots,
    N]``, ``a [N, d]`` float32; ``b_dt, d_skip [d]`` in the dtype they are
    stored in -> ``(y [slots, d], pool)``."""
    pos = pos.astype(jnp.int32)
    c, dt_in, b, cm, a = (x.astype(jnp.float32) for x in (c, dt_in, b, cm, a))
    args = (pos, c, dt_in, b_dt, b, cm, a, d_skip)
    return resilience.guarded_call(
        UPDATE_KERNEL,
        lambda: _state_update_fused(layer_index(li), pool, *args,
                                    interpret=interpret),
        lambda: _xla_state_update(pool, li, *args),
    )
