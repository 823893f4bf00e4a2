"""Power retention of degree 2 (Manifest AI's linear attention, arXiv
2507.04239) as two kernels: the one-token update of a decode step and the
chunked pass over a prompt.

    a_ij = (s q_i . k_j)^2 exp(G_i - G_j)      (j <= i, G = cumsum log g)
    y_i  = sum_j a_ij v_j / (sum_j a_ij + eps)

is, with ``phi(x) . phi(y) = (x . y)^2``, the recurrence that is served:

    S_t = g_t S_{t-1} + phi(k_t) v_t^T        [D, d] a kv head
    Z_t = g_t Z_{t-1} + k_t k_t^T             [d, d]
    y_t = phi(s q_t)^T S_t / (s^2 q_t^T Z_t q_t + eps)

``s = d ** -0.5``; a group of ``g`` query heads reads one kv head's state.
Everything the state touches is float32: it is a sum over thousands of
tokens.

LAYOUT. ``phi`` is the symmetric square in TILED form (:func:`pair_table`):
the ``d`` features in blocks of 8, one PAIR of blocks ``(bi, jb)``, ``jb >=
bi``, 64 rows ``(i', j') -> x[8 bi + i'] x[8 jb + j']``, weighted 1 where
``jb == bi`` (the block holds ``(i, j)`` and ``(j, i)``) and ``sqrt 2``
elsewhere. ``D = 64 * nb (nb + 1) / 2`` rows, ``nb = d / 8``: 8704 at ``d =
128`` against the 8256 distinct products, 5% more rows for whole ``[8,
128]`` tiles whose ``x_i`` is ONE value a tile and whose ``x_j`` are the 8
sublanes. ``S`` is held ``[D, d]``, the value's ``d`` on the lanes. The
normaliser's state ``z = sum phi(k)`` is held as the matrix ``Z = sum k
k^T`` it is a re-ordering of (``phi(q) . z = q^T Z q``): ``d^2`` values for
``D``, 0.4% of ``S``, and it needs no row order at all.

- :func:`retention_update` (``retention_update`` in a device trace): every
  slot one token on, in place in the pools ``S [layers, 2, slots, h_kv, D,
  d]`` and ``Z [layers, 2, slots, h_kv, d, d]``, whose axis of 2 is keyed
  by position as ``selective_state_update``'s is: slot ``i`` at ``pos[i]``
  READS the row of ``pos[i] - 1`` (zeros at position 0) and WRITES the row
  of ``pos[i]``, so the step is repeatable. The grid walks ``(slot, kv
  head, row tile)``: a kv head's ``S`` (4.5 MB) is walked in tiles VMEM
  holds twice over, read once and written once, the group's query heads
  all reading the tile while it is there. ``phi`` is never built: a tile's
  update is ``k_i * (k_j v^T)`` and its read ``q_i * (q_j . S)``, from the
  128-wide vectors (laid along the sublanes once a grid step, by a
  transpose).
- :func:`retention_prefill` (``retention_prefill``): one sequence, chunks
  of :func:`chunk_len` rows. Inside a chunk the masked ``(Q K^T)^2``
  product with its decays; across chunks the query of the carried state
  and its gated update, the state resident in VMEM for the whole sequence.
  The kernel works feature-major (``[d, tokens]``): ``phi(X)^T`` tiles are
  then ``x_i``'s row times ``x_j``'s 8 rows, the same pairs as the step's.
  A padded row has ``log g = 0`` and ``k = 0``: it leaves ``S``, ``Z`` as
  they were, which is how a caller stops the scan at a prompt's true
  length inside a padded bucket.

Each has an XLA twin (the resilience layer's golden; the unit tests'
second opinion), which builds ``phi`` (:func:`phi`) as it is written.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu import resilience
from triton_dist_tpu.ops.common import dist_pallas_call
from triton_dist_tpu.ops.per_layer import layer_index, traced_once
from triton_dist_tpu.utils import round_up

# the names the kernels carry in a device trace (perfbench reads them)
UPDATE_KERNEL = "retention_update"
PREFILL_KERNEL = "retention_prefill"
EPS = 1e-6
BLOCK = 8                     # features a block of the tiled phi: a sublane tile
PAIR_ROWS = BLOCK * BLOCK
# bytes of S a grid step of the update may hold in VMEM: its tile in and
# out, each twice for the pipeline, inside half of Mosaic's 16 MiB
UPDATE_VMEM_BLOCKS = 8 * 2**20
# rows of S a product of the prefill walks at once (whole pairs)
PREFILL_ROWS = 512
PREFILL_VMEM = 48 * 2**20
HI = lax.Precision.HIGHEST
_F32 = jnp.float32


# -- the tiled symmetric square -------------------------------------------------

@functools.lru_cache(maxsize=None)
def pair_table(d: int) -> tuple[np.ndarray, np.ndarray]:
    """``(bi, jb)`` of every pair of 8-feature blocks with ``jb >= bi``,
    ``bi``-major: the order of the state's 64-row groups."""
    if d % BLOCK:
        raise ValueError(f"head_dim={d} is not whole blocks of {BLOCK}")
    nb = d // BLOCK
    pairs = [(bi, jb) for bi in range(nb) for jb in range(bi, nb)]
    bi, jb = (np.array(x, np.int32) for x in zip(*pairs))
    return bi, jb


def state_rows(d: int) -> int:
    """``D``: rows of a kv head's state at head width ``d``."""
    return PAIR_ROWS * len(pair_table(d)[0])


@functools.lru_cache(maxsize=None)
def _phi_index(d: int):
    """Row ``r`` of the state is ``coef[r] * x[i[r]] * x[j[r]]``."""
    bi, jb = pair_table(d)
    ii, jj = np.divmod(np.arange(PAIR_ROWS), BLOCK)
    i = (bi[:, None] * BLOCK + ii[None]).reshape(-1)
    j = (jb[:, None] * BLOCK + jj[None]).reshape(-1)
    coef = np.repeat(np.where(bi == jb, 1.0, np.sqrt(2.0)), PAIR_ROWS)
    return i, j, coef.astype(np.float32)


def phi(x):
    """``x [..., d]`` -> ``[..., D]`` float32 with ``phi(x) . phi(y) = (x .
    y)^2``, in the state's row order."""
    i, j, coef = _phi_index(x.shape[-1])
    x = x.astype(_F32)
    return coef * x[..., i] * x[..., j]


def chunk_len(d: int) -> int:
    """Rows of a chunk of the prefill: four head widths, in whole 128-lane
    tiles and at most 512. A token's products inside a chunk cost ``4 C d``
    against the ``2 D d ~ d^3`` of its query of the state, a sixteenth at
    ``C = 4 d``; and the ``[C, C]`` float32 tiles a chunk holds (scores,
    decays) are 1 MiB each at ``d = 128``."""
    return min(512, round_up(4 * d, 128))


def _tile_pairs(n_pairs: int, most: int) -> int:
    """Pairs a tile of rows holds: the largest divisor of ``n_pairs`` that
    is at most ``most``."""
    return max(n for n in range(1, max(most, 1) + 1) if n_pairs % n == 0)


def _pair_coef(bi, jb):
    return jnp.where(bi == jb, 1.0, float(np.sqrt(2.0))).astype(_F32)


# -- one token of every slot, in the pools -----------------------------------------

def _update_kernel(li_ref, pos_ref, bi_ref, jb_ref, q_ref, kvg_ref, s_in_ref,
                   z_in_ref, y_ref, s_out_ref, z_out_ref, kb_ref, kv_ref,
                   qb_ref, den_ref, acc_ref, *, tile_pairs: int, eps: float):
    """Grid ``(slot, kv head, row tile)``. ``kb_ref [d, d]`` holds ``k_i``
    along row ``i`` (every lane), ``kv_ref`` the outer product ``k_j v``,
    ``qb_ref [g, d, d]`` each query head's ``q_i`` the same way: built at
    the head's first tile, read by every tile."""
    del li_ref                          # the index maps' (the pools' layer)
    slot, t = pl.program_id(0), pl.program_id(2)
    g, d = q_ref.shape[2], q_ref.shape[3]
    g_row = kvg_ref[0, 0, 2:3, :]                       # [1, d], g on every lane

    def fresh(x):
        # a select, not a product: what a finished request left may not be
        # finite
        return jnp.where(jnp.broadcast_to(pos_ref[slot], x.shape) == 0, 0.0, x)

    @pl.when(t == 0)
    def _():
        k_rows = jnp.broadcast_to(kvg_ref[0, 0, 0:1, :], (d, d))
        kb = k_rows.T
        kb_ref[:] = kb
        kv_ref[:] = kb * kvg_ref[0, 0, 1:2, :]
        z = g_row * fresh(z_in_ref[0, 0, 0, 0]) + kb * k_rows
        z_out_ref[0, 0, 0, 0] = z
        for h in range(g):
            q_rows = jnp.broadcast_to(q_ref[0, 0, h:h + 1, :], (d, d))
            qb = q_rows.T
            qb_ref[h] = qb
            den = jnp.sum(jnp.sum(qb * q_rows * z, axis=0, keepdims=True),
                          axis=1, keepdims=True)
            den_ref[h] = jnp.broadcast_to(den, (1, d))
        acc_ref[:] = jnp.zeros(acc_ref.shape, _F32)

    def pair(pp, carry):
        p = t * tile_pairs + pp
        bi, jb = bi_ref[p], jb_ref[p]
        c = _pair_coef(bi, jb)
        j0 = pl.multiple_of(jb * BLOCK, BLOCK)
        kvj = kv_ref[pl.ds(j0, BLOCK), :] * c
        accs = [jnp.zeros((BLOCK, d), _F32)] * g
        for ii in range(BLOCK):
            i = bi * BLOCK + ii
            r0 = pl.multiple_of(pp * PAIR_ROWS + ii * BLOCK, BLOCK)
            s = fresh(s_in_ref[0, 0, 0, 0, pl.ds(r0, BLOCK), :])
            s = g_row * s + kb_ref[pl.ds(i, 1), :] * kvj
            s_out_ref[0, 0, 0, 0, pl.ds(r0, BLOCK), :] = s
            accs = [a + qb_ref[h, pl.ds(i, 1), :] * s
                    for h, a in enumerate(accs)]
        for h in range(g):
            acc_ref[h] += (qb_ref[h, pl.ds(j0, BLOCK), :] * c) * accs[h]
        return carry

    lax.fori_loop(0, tile_pairs, pair, 0)

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        for h in range(g):
            num = jnp.sum(acc_ref[h], axis=0, keepdims=True)
            y_ref[0, 0, h:h + 1, :] = num / (den_ref[h] + eps)


def _xla_retention_update(s_pool, z_pool, li, pos, q, k, v, log_g):
    """The step as it is written: ``q [b, h_kv, g, d]`` (scaled), ``k, v
    [b, h_kv, d]``, ``log_g [b, h_kv]``, float32."""
    slots = jnp.arange(q.shape[0])
    first = (pos == 0)[:, None, None, None]
    s = jnp.where(first, 0.0, s_pool[li, (pos + 1) % 2, slots])
    z = jnp.where(first, 0.0, z_pool[li, (pos + 1) % 2, slots])
    gate = jnp.exp(log_g)[..., None, None]
    s = gate * s + phi(k)[..., None] * v[..., None, :]
    z = gate * z + k[..., None] * k[..., None, :]
    num = jnp.einsum("bhgr,bhrd->bhgd", phi(q), s, precision=HI)
    den = jnp.einsum("bhgi,bhij,bhgj->bhg", q, z, q, precision=HI)
    return (num / (den[..., None] + EPS),
            s_pool.at[li, pos % 2, slots].set(s),
            z_pool.at[li, pos % 2, slots].set(z))


@traced_once
def _update_fused(li, s_pool, z_pool, pos, q, kvg, *, interpret):
    b, h_kv, g, d = q.shape
    rows = s_pool.shape[4]
    bi, jb = pair_table(d)
    tile_pairs = _tile_pairs(
        len(bi), UPDATE_VMEM_BLOCKS // (4 * 4 * PAIR_ROWS * d))
    tile = tile_pairs * PAIR_ROWS
    read = lambda i, h, t, li, pos, *_: (li[0], (pos[i] + 1) % 2, i, h)
    write = lambda i, h, t, li, pos, *_: (li[0], pos[i] % 2, i, h)
    token = lambda rows: pl.BlockSpec((1, 1, rows, d),
                                      lambda i, h, t, *_: (i, h, 0, 0))
    s_block, z_block = (1, 1, 1, 1, tile, d), (1, 1, 1, 1, d, d)
    y, s_pool, z_pool = dist_pallas_call(
        functools.partial(_update_kernel, tile_pairs=tile_pairs, eps=EPS),
        name=UPDATE_KERNEL,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, h_kv, rows // tile),
            in_specs=[
                token(g), token(3),
                pl.BlockSpec(s_block, lambda i, h, t, *p: (*read(i, h, t, *p), t, 0)),
                pl.BlockSpec(z_block, lambda i, h, t, *p: (*read(i, h, t, *p), 0, 0)),
            ],
            out_specs=(
                token(g),
                pl.BlockSpec(s_block, lambda i, h, t, *p: (*write(i, h, t, *p), t, 0)),
                pl.BlockSpec(z_block, lambda i, h, t, *p: (*write(i, h, t, *p), 0, 0)),
            ),
            scratch_shapes=[
                pltpu.VMEM((d, d), _F32), pltpu.VMEM((d, d), _F32),
                pltpu.VMEM((g, d, d), _F32), pltpu.VMEM((g, 1, d), _F32),
                pltpu.VMEM((g, BLOCK, d), _F32),
            ],
        ),
        out_shape=(jax.ShapeDtypeStruct(q.shape, _F32),
                   jax.ShapeDtypeStruct(s_pool.shape, s_pool.dtype),
                   jax.ShapeDtypeStruct(z_pool.shape, z_pool.dtype)),
        # the pools are updated where they lie: operands 6 and 7 (the four
        # prefetched vectors count) are outputs 1 and 2
        input_output_aliases={6: 1, 7: 2},
        cost_estimate=pl.CostEstimate(
            flops=(4 + 2 * g) * b * h_kv * rows * d, transcendentals=0,
            bytes_accessed=4 * b * h_kv * (2 * (rows + d) * d + (2 * g + 3) * d)),
        dimension_semantics=("arbitrary",) * 3,
        uses_barrier=False,
        interpret=interpret,
    )(li, pos, jnp.asarray(bi), jnp.asarray(jb), q, kvg, s_pool, z_pool)
    return y, s_pool, z_pool


def _grouped(q, h_kv: int):
    """``q [..., h_q, d]`` -> ``[..., h_kv, g, d]``, float32, scaled by
    ``d ** -0.5``: query head ``h`` reads kv head ``h // g``."""
    *lead, h_q, d = q.shape
    return q.astype(_F32).reshape(*lead, h_kv, h_q // h_kv, d) * d ** -0.5


def retention_update(s_pool, z_pool, li: int, pos, q, k, v, log_g, *,
                     interpret: Any = None):
    """Every slot one token on, in the pools. ``s_pool [layers, 2, slots,
    h_kv, D, d]``, ``z_pool [layers, 2, slots, h_kv, d, d]`` float32, ``li``
    the layer, ``pos [slots]`` each slot's position: its state is READ from
    row ``(pos - 1) % 2`` of the axis of 2 (zeros at position 0) and
    written to row ``pos % 2``. ``q [slots, h_q, d]``, ``k, v [slots, h_kv,
    d]`` (normed and rotated, not scaled), ``log_g [slots, h_kv]`` ->
    ``(y [slots, h_q, d] float32, s_pool, z_pool)``."""
    b, h_q, d = q.shape
    h_kv = k.shape[1]
    pos = pos.astype(jnp.int32)
    qg = _grouped(q, h_kv)
    k, v, log_g = (x.astype(_F32) for x in (k, v, log_g))

    def fused():
        gate = jnp.broadcast_to(jnp.exp(log_g)[..., None], k.shape)
        return _update_fused(layer_index(li), s_pool, z_pool, pos, qg,
                             jnp.stack([k, v, gate], axis=2),
                             interpret=interpret)

    y, s_pool, z_pool = resilience.guarded_call(
        UPDATE_KERNEL, fused,
        lambda: _xla_retention_update(s_pool, z_pool, li, pos, qg, k, v, log_g))
    return y.reshape(b, h_q, d), s_pool, z_pool


# -- the chunked pass over a prompt ------------------------------------------------

def _prefill_kernel(bi_ref, jb_ref, qt_ref, kt_ref, kr_ref, vt_ref, vr_ref,
                    grow_ref, gcol_ref, gend_ref, yt_ref, s_ref, z_ref, x32_ref,
                    phi_ref, intra_ref, inter_ref, den_ref, *,
                    tile_pairs: int, eps: float):
    """Grid ``(kv head, chunk)``; ``s_ref [D, d]`` and ``z_ref [d, d]``
    (output blocks, resident across the chunks) carry the state. Feature-
    major: ``qt [g, d, C]``, ``kt, vt [d, C]``; ``kr, vr [C, d]`` are the
    same rows token-major; ``grow [1, C]`` / ``gcol [C, 1]`` the chunk's
    own cumulative ``log g`` and ``gend [1, d]`` its last value on every
    lane (Mosaic broadcasts along one axis at a time)."""
    g, d, C = qt_ref.shape[1:]
    rows = s_ref.shape[1]
    tile = tile_pairs * PAIR_ROWS
    mm = qt_ref.dtype                   # what the MXU is fed
    scale2 = 1.0 / d                    # s^2, on every a_ij alike

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[:] = jnp.zeros(s_ref.shape, _F32)
        z_ref[:] = jnp.zeros(z_ref.shape, _F32)

    grow, gcol = grow_ref[0], gcol_ref[0]
    gtot = gcol[C - 1:C, :]                             # [1, 1]
    from_start = jnp.exp(grow)                          # decay t0-1 -> i
    to_end_col, to_end_row = jnp.exp(gtot - gcol), jnp.exp(gtot - grow)
    keep = jnp.exp(gend_ref[0, 0])                      # [1, d]
    j_id = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    i_id = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # [j, i]: every exponent <= 0 under the mask
    decay = jnp.exp(jnp.where(j_id <= i_id, grow - gcol, -1e30))
    kr, vt, z_prev = kr_ref[0], vt_ref[0], z_ref[0]
    x32_ref[g] = kt_ref[0].astype(_F32)
    for h in range(g):
        qt = qt_ref[0, h]
        x32_ref[h] = qt.astype(_F32)
        sc = jnp.dot(kr, qt, preferred_element_type=_F32)       # [j, i]
        a = sc * sc * scale2 * decay
        intra_ref[h] = jnp.dot(vt, a.astype(mm), preferred_element_type=_F32)
        zq = jnp.dot(z_prev, x32_ref[h], precision=HI,
                     preferred_element_type=_F32)
        n_inter = jnp.sum(x32_ref[h] * zq, axis=0, keepdims=True) * scale2
        den_ref[h] = (jnp.sum(a, axis=0, keepdims=True)
                      + from_start * n_inter + eps)
        inter_ref[h] = jnp.zeros((d, C), _F32)
    vw = (vr_ref[0].astype(_F32) * to_end_col).astype(mm)

    def phi_rows(src: int, rt):
        """``phi(X)^T`` rows of tile ``rt`` of source ``src`` into
        ``phi_ref [tile, C]``."""
        def pair(pp, carry):
            p = rt * tile_pairs + pp
            bi, jb = bi_ref[p], jb_ref[p]
            j0 = pl.multiple_of(jb * BLOCK, BLOCK)
            xj = x32_ref[src, pl.ds(j0, BLOCK), :] * _pair_coef(bi, jb)
            for ii in range(BLOCK):
                r0 = pl.multiple_of(pp * PAIR_ROWS + ii * BLOCK, BLOCK)
                phi_ref[pl.ds(r0, BLOCK), :] = (
                    x32_ref[src, pl.ds(bi * BLOCK + ii, 1), :] * xj)
            return carry

        lax.fori_loop(0, tile_pairs, pair, 0)
        return phi_ref[:].astype(mm)

    def read(s_old, phi_q):
        """``S^T phi(Q)^T`` ``[d, C]``; a float32 state is fed to a bf16
        MXU as its two halves."""
        dot = lambda s: lax.dot_general(
            s, phi_q, (((0,), (0,)), ((), ())), preferred_element_type=_F32)
        if mm == _F32:
            return dot(s_old)
        hi = s_old.astype(mm)
        return dot(hi) + dot((s_old - hi.astype(_F32)).astype(mm))

    def row_tile(rt, carry):
        r0 = pl.multiple_of(rt * tile, BLOCK)
        s_old = s_ref[0, pl.ds(r0, tile), :]
        for h in range(g):
            inter_ref[h] += read(s_old, phi_rows(h, rt))
        s_ref[0, pl.ds(r0, tile), :] = keep * s_old + jnp.dot(
            phi_rows(g, rt), vw, preferred_element_type=_F32)
        return carry

    lax.fori_loop(0, rows // tile, row_tile, 0)
    kw = (x32_ref[g] * to_end_row).astype(mm)
    z_ref[0] = keep * z_prev + lax.dot_general(
        kw, kt_ref[0], (((1,), (1,)), ((), ())), preferred_element_type=_F32)
    for h in range(g):
        yt_ref[0, h] = ((intra_ref[h] + (from_start * scale2) * inter_ref[h])
                        / den_ref[h]).astype(yt_ref.dtype)


def _chunks(x, C: int):
    """``x [L, ...]`` zero-padded to whole chunks: ``[n, C, ...]``."""
    L = x.shape[0]
    x = jnp.pad(x, ((0, round_up(L, C) - L),) + ((0, 0),) * (x.ndim - 1))
    return x.reshape(-1, C, *x.shape[1:])


def _xla_retention_prefill(q, k, v, log_g):
    """The chunked form as it is written, float32, ``phi`` built."""
    L, h_q, d = q.shape
    h_kv = k.shape[1]
    C = chunk_len(d)
    qc = _chunks(_grouped(q, h_kv), C)
    kc, vc, gc = (_chunks(x.astype(_F32), C) for x in (k, v, log_g))
    causal = jnp.tril(jnp.ones((C, C), bool))

    def chunk(carry, xs):
        s, z = carry
        q, k, v, log_g = xs
        G = jnp.cumsum(log_g, axis=0)                   # [C, h]
        decay = jnp.exp(jnp.where(
            causal[..., None], G[:, None] - G[None, :], -jnp.inf))
        a = jnp.einsum("ihgd,jhd->ijhg", q, k, precision=HI) ** 2 \
            * decay[..., None]
        from_start = jnp.exp(G)[..., None]              # [C, h, 1]
        num = (jnp.einsum("ijhg,jhd->ihgd", a, v, precision=HI)
               + from_start[..., None] * jnp.einsum(
                   "ihgr,hrd->ihgd", phi(q), s, precision=HI))
        den = a.sum(1) + from_start * jnp.einsum(
            "ihgd,hde,ihge->ihg", q, z, q, precision=HI)
        to_end = jnp.exp(G[-1] - G)                     # [C, h]
        keep = jnp.exp(G[-1])[:, None, None]
        s = keep * s + jnp.einsum(
            "jhr,jhd->hrd", phi(k) * to_end[..., None], v, precision=HI)
        z = keep * z + jnp.einsum(
            "jhd,jhe->hde", k * to_end[..., None], k, precision=HI)
        return (s, z), num / (den[..., None] + EPS)

    zeros = lambda *shape: jnp.zeros((h_kv, *shape), _F32)
    (s, z), y = lax.scan(chunk, (zeros(state_rows(d), d), zeros(d, d)),
                         (qc, kc, vc, gc))
    return y.reshape(-1, h_q, d)[:L], s, z


@traced_once
def _prefill_fused(q, k, v, log_g, *, interpret):
    L, h_q, d = q.shape
    h_kv = k.shape[1]
    g = h_q // h_kv
    C = chunk_len(d)
    rows = state_rows(d)
    bi, jb = pair_table(d)
    tile_pairs = _tile_pairs(len(bi), PREFILL_ROWS // PAIR_ROWS)
    n = round_up(L, C) // C
    mm = q.dtype
    pad = lambda x: _chunks(x, C).reshape(n * C, *x.shape[1:])
    q, k, v = pad(q), pad(k.astype(mm)), pad(v.astype(mm))
    gc = jnp.cumsum(_chunks(log_g.astype(_F32), C), axis=1).reshape(n * C, h_kv)
    qt = q.reshape(n * C, h_kv, g, d).transpose(1, 2, 3, 0)     # [h, g, d, L]
    kr, vr = k.transpose(1, 0, 2), v.transpose(1, 0, 2)         # [h, L, d]
    kt, vt = k.transpose(1, 2, 0), v.transpose(1, 2, 0)         # [h, d, L]
    g_t = gc.T                                                  # [h, L]
    major = pl.BlockSpec((1, d, C), lambda h, c, *_: (h, 0, c))
    token = pl.BlockSpec((1, C, d), lambda h, c, *_: (h, c, 0))
    heads = pl.BlockSpec((1, g, d, C), lambda h, c, *_: (h, 0, 0, c))
    whole = lambda *shape: pl.BlockSpec(
        (1, *shape), lambda h, c, *_: (h,) + (0,) * len(shape))
    yt, s, z = dist_pallas_call(
        functools.partial(_prefill_kernel, tile_pairs=tile_pairs, eps=EPS),
        name=PREFILL_KERNEL,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(h_kv, n),
            in_specs=[
                heads, major, token, major, token,
                pl.BlockSpec((1, 1, C), lambda h, c, *_: (h, 0, c)),
                pl.BlockSpec((1, C, 1), lambda h, c, *_: (h, c, 0)),
                pl.BlockSpec((1, 1, 1, d), lambda h, c, *_: (h, c, 0, 0)),
            ],
            out_specs=(heads, whole(rows, d), whole(d, d)),
            scratch_shapes=[
                pltpu.VMEM((g + 1, d, C), _F32),
                pltpu.VMEM((tile_pairs * PAIR_ROWS, C), _F32),
                pltpu.VMEM((g, d, C), _F32), pltpu.VMEM((g, d, C), _F32),
                pltpu.VMEM((g, 1, C), _F32),
            ],
        ),
        out_shape=(jax.ShapeDtypeStruct((h_kv, g, d, n * C), mm),
                   jax.ShapeDtypeStruct((h_kv, rows, d), _F32),
                   jax.ShapeDtypeStruct((h_kv, d, d), _F32)),
        cost_estimate=pl.CostEstimate(
            flops=2 * n * C * d * (h_q * (2 * C + rows + 2 * d)
                                   + h_kv * (rows + d)),
            transcendentals=h_kv * n * C * (C + 4),
            bytes_accessed=n * C * d * (h_q * (q.dtype.itemsize + 4)
                                        + 4 * h_kv * q.dtype.itemsize)
            + 4 * h_kv * (rows + d) * d),
        vmem_limit_bytes=PREFILL_VMEM,
        dimension_semantics=("parallel", "arbitrary"),
        uses_barrier=False,
        interpret=interpret,
    )(jnp.asarray(bi), jnp.asarray(jb), qt, kt, kr, vt, vr,
      g_t[:, None, :], g_t[:, :, None],
      jnp.broadcast_to(g_t[:, C - 1::C, None, None], (h_kv, n, 1, d)))
    return yt.transpose(3, 0, 1, 2).reshape(n * C, h_q, d)[:L], s, z


def retention_prefill(q, k, v, log_g, *, interpret: Any = None):
    """One sequence through the chunked form from an empty state. ``q [L,
    h_q, d]``, ``k, v [L, h_kv, d]`` (normed and rotated, not scaled; the
    MXU is fed ``q``'s dtype, and ``y`` comes in it), ``log_g [L, h_kv]``
    float32; a row past the sequence's end has ``k = 0`` and ``log_g = 0``
    -> ``(y [L, h_q, d], S [h_kv, D, d], Z [h_kv, d, d])``, the float32
    state after the last row."""
    def twin():
        y, s, z = _xla_retention_prefill(q, k, v, log_g)
        return y.astype(q.dtype), s, z

    return resilience.guarded_call(
        PREFILL_KERNEL,
        lambda: _prefill_fused(q, k, v, log_g, interpret=interpret), twin)
