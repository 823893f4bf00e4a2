"""The state-space recurrence with a MATRIX state and ONE decay a head
(Mamba-2, "SSD") as two kernels: the one-token update of a decode step and
the chunked pass over a prompt.

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t        [P, N] a head
    y_t = h_t C_t + D x_t

``H`` heads of ``P`` channels (``d = H P``), ``N`` states a channel; ``A``
(negative), ``D`` and the step ``dt`` are ONE scalar a head, ``B`` and ``C``
one vector ``[N]`` a token for all heads (one group). Everything the state
touches is float32: it is a sum over thousands of tokens.

LAYOUT. A state is held ``[N, d]``, the heads' channels side by side on the
lanes (``ops/selective_scan.py``'s layout, whose per-channel decay ``[N,
d]`` is here a ROW: a head's decay is the same for its ``P`` lanes and for
every ``n``). No ``[N, d]`` decay is built or read, and a step takes ``H``
exponentials a slot, not ``N d``.

- :func:`ssd_state_update` (``ssd_state_update`` in a device trace): every
  slot one token on, in place in the pool ``[layers, 2, slots, N, d]``,
  whose axis of 2 is keyed by position as ``selective_state_update``'s is:
  slot ``i`` at ``pos[i]`` READS the row of ``pos[i] - 1`` (zeros at
  position 0) and WRITES the row of ``pos[i]``, so the step is repeatable.
  The grid walks ``(block of lanes, slot)``: a slot's state (4 MB at ``N``
  = 128, ``d`` = 8192) goes through VMEM in blocks read once and written
  once; the token's rows (``dt x``, the decay) are whole ``[slots, block]``
  operands that stay while the slots go by. ``B`` and ``C`` are laid along
  the sublanes once a grid step, by a transpose.
- :func:`ssd_chunk_scan` (``ssd_chunk_scan``): one sequence from an empty
  state, ``chunk`` rows at a time in the DUAL form. With ``s_t`` the sum of
  ``dt A`` inside the chunk up to ``t``:

      Y[t] = sum_{r<=t} exp(s_t - s_r) (C_t . B_r) dt_r x_r
             + exp(s_t) C_t . H_prev  + D x_t
      H_next = exp(s_Q) H_prev + sum_r exp(s_Q - s_r) dt_r x_r (x) B_r

  ``C B^T [Q, Q]`` is one product a chunk for all heads; the mask
  ``exp(s_t - s_r)`` is a head's. The grid walks ``(block of lanes,
  chunk)`` with the block's state resident in VMEM for the whole sequence;
  the MXU is fed ``x``'s dtype (the carried float32 state as its two
  halves), sums and the state stay float32. Two heads of 64 channels share
  a 128-lane tile: each head's ``[Q, Q]`` product multiplies the tile with
  the other head's lanes zeroed (the MXU is 128 wide either way), and what
  is linear in the head (the read of the carried state, its update) is ONE
  product a tile with the head's factor selected a lane. A row past the
  sequence's end has ``dt = 0``: it leaves the state as it was; chunks
  wholly past it are not walked (the live chunks' count is a prefetched
  scalar: a dead chunk's grid step fetches nothing, multiplies nothing
  and writes zeros for its rows of ``y``).

Each has an XLA twin (the resilience layer's golden; the unit tests'
second opinion): the update as it is written, the scan in the same dual
form as plain ``einsum``s.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu import resilience
from triton_dist_tpu.ops.common import dist_pallas_call
from triton_dist_tpu.ops.per_layer import layer_index, traced_once
from triton_dist_tpu.utils import round_up

# the names the kernels carry in a device trace (perfbench reads them)
UPDATE_KERNEL = "ssd_state_update"
SCAN_KERNEL = "ssd_chunk_scan"
# bytes of state a grid step of the update may hold in VMEM: its block in
# and out, each twice for the pipeline, inside Mosaic's 16 MiB
UPDATE_VMEM_BLOCKS = 4 * 2**20
# lanes a grid step of the scan holds (whole heads; 8 heads of 64)
SCAN_LANES = 512
SCAN_VMEM = 48 * 2**20
LANES = 128
_F32 = jnp.float32


def lane_tile(d: int, head_dim: int) -> int:
    """Lanes the kernels work on at once: whole heads in one 128-lane tile
    (a wider head is its own tile); all of ``d`` where it is narrower than
    a tile (toy sizes)."""
    if d % head_dim:
        raise ValueError(f"d={d} is not whole heads of {head_dim}")
    if head_dim >= LANES:
        return head_dim
    if d % LANES == 0 and LANES % head_dim == 0:
        return LANES
    return d


def _lane_block(d: int, tile: int, most: int) -> int:
    """The widest block of whole tiles that divides ``d`` and is at most
    ``most`` lanes (one tile at least)."""
    tiles = d // tile
    return tile * max(n for n in range(1, tiles + 1)
                      if tiles % n == 0 and n * tile <= max(most, tile))


def _per_lane(x, head_dim: int):
    """``x [..., H]`` (one value a head) on every lane of its head:
    ``[..., H * head_dim]``."""
    return jnp.repeat(x, head_dim, axis=-1)


# -- one token of every slot, in the pool ---------------------------------------

def _update_kernel(li_ref, pos_ref, dtx_ref, dec_ref, b_ref, cm_ref,
                   h_in_ref, y_ref, h_out_ref):
    """Grid ``(lane block, slot)``: the slot's block of state comes from
    the pool's row of ``pos[i] - 1`` and goes to the row of ``pos[i]`` (the
    block index maps say which). The token's rows come ``[slots, tiles,
    tile]``: a slot's is then a whole ``[tiles, tile]`` value whose row
    ``t`` lies over tile ``t`` of the state."""
    del li_ref                          # the index maps' (the pool's layer)
    i = pl.program_id(1)
    n = h_in_ref.shape[-2]
    tiles, tile = dtx_ref.shape[1:]
    row = pl.ds(i, 1)
    # the token's B and C along the sublanes, on every lane of a tile
    b_col = jnp.broadcast_to(b_ref[row, :], (tile, n)).T
    c_col = jnp.broadcast_to(cm_ref[row, :], (tile, n)).T
    first = jnp.broadcast_to(pos_ref[i], (n, tile)) == 0
    dec, dtx, ys = dec_ref[i], dtx_ref[i], []
    for t in range(tiles):
        lanes = pl.ds(t * tile, tile)
        # a select, not a product: what a finished request left may not be
        # finite
        h = jnp.where(first, 0.0, h_in_ref[0, 0, 0, :, lanes])
        h = dec[t:t + 1] * h + b_col * dtx[t:t + 1]
        h_out_ref[0, 0, 0, :, lanes] = h
        ys.append(jnp.sum(h * c_col, axis=0, keepdims=True))
    y_ref[i] = jnp.concatenate(ys, axis=0)


def _step_rows(x, dt_in, dt_bias, a, head_dim: int):
    """A step's rows on every lane, float32: ``(dt x [b, d], decay [b,
    d])`` with ``dt = softplus(dt_in + dt_bias)`` and ``decay = exp(dt A)``
    computed a HEAD (``[b, H]``)."""
    dt = jax.nn.softplus(dt_in + dt_bias.astype(_F32))
    decay = jnp.exp(dt * a)
    return _per_lane(dt, head_dim) * x, _per_lane(decay, head_dim)


def _xla_update(pool, li, pos, dtx, dec, b, cm):
    slots = jnp.arange(dtx.shape[0])
    h = jnp.where((pos == 0)[:, None, None], 0.0,
                  pool[li, (pos + 1) % 2, slots])
    h = dec[:, None, :] * h + b[:, :, None] * dtx[:, None, :]
    y = jnp.einsum("bnd,bn->bd", h, cm)
    return y, pool.at[li, pos % 2, slots].set(h)


@traced_once
def _update_fused(li, pool, pos, x, dt_in, dt_bias, a, b, cm, *, interpret):
    slots, d = x.shape
    n = b.shape[1]
    head_dim = d // a.shape[0]
    dtx, dec = _step_rows(x, dt_in, dt_bias, a, head_dim)
    tile = lane_tile(d, head_dim)
    db = _lane_block(d, tile, UPDATE_VMEM_BLOCKS // (4 * 4 * n))
    tiled = lambda v: v.reshape(slots, d // tile, tile)
    rows = pl.BlockSpec((slots, db // tile, tile), lambda j, i, *_: (0, j, 0))
    token = pl.BlockSpec((slots, n), lambda j, i, *_: (0, 0))
    block = (1, 1, 1, n, db)
    y, pool = dist_pallas_call(
        _update_kernel,
        name=UPDATE_KERNEL,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(d // db, slots),
            in_specs=[
                rows, rows, token, token,
                pl.BlockSpec(
                    block, lambda j, i, li, p: (li[0], (p[i] + 1) % 2, i, 0, j)),
            ],
            out_specs=(
                rows,
                pl.BlockSpec(
                    block, lambda j, i, li, p: (li[0], p[i] % 2, i, 0, j)),
            ),
        ),
        out_shape=(jax.ShapeDtypeStruct((slots, d // tile, tile), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)),
        # the pool is updated where it lies: operand 6 (the two prefetched
        # vectors count) is output 1
        input_output_aliases={6: 1},
        cost_estimate=pl.CostEstimate(
            flops=5 * slots * d * n, transcendentals=0,
            bytes_accessed=4 * slots * (2 * n * d + 3 * d + 2 * n)),
        dimension_semantics=("arbitrary", "arbitrary"),
        uses_barrier=False,
        interpret=interpret,
    )(li, pos, tiled(dtx), tiled(dec), b, cm, pool)
    return y.reshape(slots, d), pool


def ssd_state_update(pool, li: int, pos, x, dt_in, dt_bias, a, b, cm, d_skip,
                     *, interpret: Any = None):
    """Every slot one token on, in the pool. ``pool [layers, 2, slots, N,
    d]`` float32, ``li`` the layer, ``pos [slots]`` each slot's position:
    its state is READ from row ``(pos - 1) % 2`` of the axis of 2 (zeros at
    position 0) and written to row ``pos % 2``. ``x [slots, d]`` the
    convolved, activated input; ``dt_in [slots, H]`` the step before its
    bias and softplus; ``dt_bias, a, d_skip [H]`` (``a`` negative, float32;
    the other two in the dtype they are stored in); ``b, cm [slots, N]`` ->
    ``(y [slots, d] float32, pool)``."""
    head_dim = x.shape[1] // a.shape[0]
    pos = pos.astype(jnp.int32)
    x, dt_in, a, b, cm = (v.astype(_F32) for v in (x, dt_in, a, b, cm))
    y, pool = resilience.guarded_call(
        UPDATE_KERNEL,
        lambda: _update_fused(layer_index(li), pool, pos, x, dt_in, dt_bias,
                              a, b, cm, interpret=interpret),
        lambda: _xla_update(pool, li, pos,
                            *_step_rows(x, dt_in, dt_bias, a, head_dim), b, cm),
    )
    return y + _per_lane(d_skip.astype(_F32), head_dim) * x, pool


# -- the chunked pass over a prompt ------------------------------------------------

def _scan_kernel(live_ref, x_ref, xdt_ref, b_ref, cm_ref, srow_ref, sall_ref,
                 eall_ref, send_ref, drow_ref, y_ref, h_ref, *, head_dim: int,
                 tile: int):
    """Grid ``(lane block, chunk)``; ``h_ref [N, db]`` (the output block,
    resident across the chunks) carries the state. ``sall [Q, H]`` is every
    head's cumulative ``dt A`` inside the chunk and ``eall`` what is left
    of it to the chunk's end, a head a lane (a head's COLUMN is selected
    and summed out of it: a ``[.., Q, 1]`` operand would lie in HBM 128
    times its size); ``srow [heads, 1, Q]`` the block's heads' sums as
    rows; ``send [1, 1, db]`` the whole chunk's on every lane of its
    head."""
    Q, db = x_ref.shape
    mm = x_ref.dtype                    # what the MXU is fed
    heads = tile // head_dim            # in one tile
    first_head = pl.program_id(0) * (db // head_dim)

    @pl.when(pl.program_id(1) == 0)
    def _():
        h_ref[:] = jnp.zeros(h_ref.shape, _F32)

    def read(cm, h_old):
        """``C H`` ``[Q, tile]``; a float32 state is fed to a bf16 MXU as
        its two halves."""
        dot = lambda h: jnp.dot(cm, h, preferred_element_type=_F32)
        if mm == _F32:
            return dot(h_old)
        hi = h_old.astype(mm)
        return dot(hi) + dot((h_old - hi.astype(_F32)).astype(mm))

    @pl.when(pl.program_id(1) >= live_ref[0])
    def _():
        y_ref[:] = jnp.zeros(y_ref.shape, y_ref.dtype)

    @pl.when(pl.program_id(1) < live_ref[0])
    def _():
        b, cm = b_ref[:], cm_ref[:]
        # [t, r]: one product a chunk for every head
        g = lax.dot_general(cm, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=_F32)
        t_id = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        r_id = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
        causal = r_id <= t_id
        lane_head = lax.broadcasted_iota(jnp.int32, (Q, tile), 1) // head_dim
        s_all, e_all = sall_ref[:], eall_ref[:]
        head_id = lax.broadcasted_iota(jnp.int32, s_all.shape, 1)

        def column(v, head):
            """``v [Q, H]`` -> its column ``head`` ``[Q, 1]``."""
            return jnp.sum(jnp.where(head_id == head, v, 0.0), axis=1,
                           keepdims=True)

        def one_tile(ti, carry):
            lanes = pl.ds(pl.multiple_of(ti * tile, tile), tile)
            xdt = xdt_ref[:, lanes]
            h_old = h_ref[:, lanes]
            intra = jnp.zeros((Q, tile), _F32)
            s_own = jnp.zeros((Q, tile), _F32)
            e_own = jnp.zeros((Q, tile), _F32)
            for k in range(heads):
                hd = ti * heads + k
                s_col = column(s_all, first_head + hd)
                # every exponent <= 0 under the mask
                decay = jnp.exp(jnp.where(causal, s_col - srow_ref[hd], -1e30))
                mine = lane_head == k
                intra += jnp.dot(
                    (g * decay).astype(mm),
                    xdt if heads == 1 else jnp.where(mine, xdt, 0),
                    preferred_element_type=_F32)
                s_own = jnp.where(mine, s_col, s_own)
                e_own = jnp.where(mine, column(e_all, first_head + hd), e_own)
            y = (intra + jnp.exp(s_own) * read(cm, h_old)
                 + drow_ref[:, lanes] * x_ref[:, lanes].astype(_F32))
            y_ref[:, lanes] = y.astype(y_ref.dtype)
            xw = (xdt.astype(_F32) * jnp.exp(e_own)).astype(mm)
            h_ref[:, lanes] = jnp.exp(send_ref[0, :, lanes]) * h_old + \
                lax.dot_general(b, xw, (((0,), (0,)), ((), ())),
                                preferred_element_type=_F32)
            return carry

        lax.fori_loop(0, db // tile, one_tile, 0)


def _chunk_sums(dt, a, chunk: int):
    """``dt [Lp, H]`` (whole chunks), ``a [H]`` -> ``s [Lp, H]``, the sum
    of ``dt A`` inside each chunk up to and with each row."""
    n = dt.shape[0] // chunk
    return jnp.cumsum((dt * a).reshape(n, chunk, -1), axis=1).reshape(dt.shape)


def _pad_rows(x, rows: int):
    return jnp.pad(x, ((0, rows - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))


def _xla_chunk_scan(x, dt, a, b, cm, d_skip, chunk: int):
    """The dual form as it is written, float32."""
    L, d = x.shape
    H, N = a.shape[0], b.shape[1]
    P = d // H
    lp = round_up(L, chunk)
    n = lp // chunk
    x, dt, b, cm = (_pad_rows(v.astype(_F32), lp) for v in (x, dt, b, cm))
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(h, xs):
        x, dt, b, cm = xs                       # [Q, H, P], [Q, H], [Q, N] x2
        s = jnp.cumsum(dt * a, axis=0)          # [Q, H]
        decay = jnp.exp(jnp.where(
            causal[..., None], s[:, None] - s[None, :], -jnp.inf))
        g = jnp.einsum("tn,rn->tr", cm, b)
        xdt = x * dt[..., None]
        y = (jnp.einsum("tr,trh,rhp->thp", g, decay, xdt)
             + jnp.exp(s)[..., None] * jnp.einsum("tn,nhp->thp", cm, h)
             + d_skip[:, None] * x)
        h = jnp.exp(s[-1])[None, :, None] * h + jnp.einsum(
            "rn,rhp->nhp", b, xdt * jnp.exp(s[-1] - s)[..., None])
        return h, y

    h, y = lax.scan(one, jnp.zeros((N, H, P), _F32),
                    (x.reshape(n, chunk, H, P), dt.reshape(n, chunk, H),
                     b.reshape(n, chunk, N), cm.reshape(n, chunk, N)))
    return y.reshape(lp, d)[:L], h.reshape(N, d)


@functools.lru_cache(maxsize=None)
def _chunk_scan_of(chunk: int):
    """The fused scan at one chunk length (a static of the kernel), traced
    once a shape."""
    @traced_once
    def ssd_chunk_scan_fused(*args, interpret):
        return _chunk_scan_fused(*args, chunk=chunk, interpret=interpret)

    return ssd_chunk_scan_fused


def _chunk_scan_fused(x, dt, a, b, cm, d_skip, live, *, chunk, interpret):
    L, d = x.shape
    H, N = a.shape[0], b.shape[1]
    P = d // H
    mm = x.dtype
    tile = lane_tile(d, P)
    db = _lane_block(d, tile, SCAN_LANES)
    hb = db // P
    Q = chunk
    lp = round_up(L, Q)
    n = lp // Q
    x, dt, b, cm = (_pad_rows(v, lp) for v in (x, dt, b, cm))
    s = _chunk_sums(dt, a, Q)                               # [lp, H]
    s_end = s.reshape(n, Q, H)[:, -1]                       # [n, H]
    to_end = (s_end[:, None] - s.reshape(n, Q, H)).reshape(lp, H)
    xdt = (x.astype(_F32) * _per_lane(dt, P)).astype(mm)
    # a chunk's rows by its own index while it is live; a dead chunk names
    # the last live one's blocks again: nothing is fetched for it
    at = lambda c, live: jnp.minimum(c, live[0] - 1)
    rows = pl.BlockSpec((Q, db), lambda j, c, live: (at(c, live), j))
    token = pl.BlockSpec((Q, N), lambda j, c, live: (at(c, live), 0))
    heads = pl.BlockSpec((Q, H), lambda j, c, live: (at(c, live), 0))
    y, h = dist_pallas_call(
        functools.partial(_scan_kernel, head_dim=P, tile=tile),
        name=SCAN_KERNEL,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(d // db, n),
            in_specs=[
                rows, rows, token, token,
                pl.BlockSpec((hb, 1, Q), lambda j, c, live: (j, 0, at(c, live))),
                heads, heads,
                pl.BlockSpec((1, 1, db), lambda j, c, live: (at(c, live), 0, j)),
                pl.BlockSpec((1, db), lambda j, c, live: (0, j)),
            ],
            out_specs=(pl.BlockSpec((Q, db), lambda j, c, live: (c, j)),
                       pl.BlockSpec((N, db), lambda j, c, live: (0, j))),
        ),
        out_shape=(jax.ShapeDtypeStruct((lp, d), mm),
                   jax.ShapeDtypeStruct((N, d), _F32)),
        cost_estimate=pl.CostEstimate(
            flops=2 * lp * (d * (Q + 3 * N) + Q * N * (d // db)),
            transcendentals=lp * (H * Q + 3 * d),
            bytes_accessed=lp * d * 3 * x.dtype.itemsize + 4 * N * d),
        vmem_limit_bytes=SCAN_VMEM,
        dimension_semantics=("parallel", "arbitrary"),
        uses_barrier=False,
        interpret=interpret,
    )(live, x, xdt, b.astype(mm), cm.astype(mm),
      s.T[:, None, :], s, to_end,
      _per_lane(s_end, P)[:, None, :], _per_lane(d_skip, P)[None])
    return y[:L], h


def ssd_chunk_scan(x, dt, a, b, cm, d_skip, length=None, *, chunk: int,
                   interpret: Any = None):
    """One sequence through the recurrence in the chunked dual form, from
    an empty state. ``x [L, d]`` (the MXU is fed its dtype, and ``y`` comes
    in it), ``dt [L, H]`` float32 AFTER its softplus and 0 at every row
    past the sequence's end, ``a [H]`` negative, ``b, cm [L, N]``, ``d_skip
    [H]``; ``length`` (a traced scalar, default ``L``) is the sequence's
    true length: chunks wholly past it are not walked, and their rows of
    ``y`` are zeros -> ``(y [L, d], h [N, d])``, the float32 state
    after the last true row."""
    L = x.shape[0]
    dt, a, d_skip = (v.astype(_F32) for v in (dt, a, d_skip))
    length = L if length is None else length
    live = jnp.clip(-(-jnp.asarray(length, jnp.int32) // chunk), 1,
                    -(-L // chunk)).reshape(1)

    def twin():
        y, h = _xla_chunk_scan(x, dt, a, b, cm, d_skip, chunk)
        return y.astype(x.dtype), h

    return resilience.guarded_call(
        SCAN_KERNEL,
        lambda: _chunk_scan_of(chunk)(x, dt, a, b, cm, d_skip, live,
                                      interpret=interpret),
        twin)
