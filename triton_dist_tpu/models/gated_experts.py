"""The gated-expert MLP that the layer-plan families share
(``models/mla_moe.py``: latent attention; ``models/window_moe.py``: window
and full GQA): ONE place for the router, the routed experts through the
grouped GEMM, the shared expert, the leading dense gated MLP and the routing
counters. A family's config ``c`` brings ``hidden``, ``topk``,
``routed_scaling``, ``expert_ffn``, ``n_shared_experts``, ``held`` (first
expert, count held here), ``scoring`` and ``gate_act`` (both read from the
model's published config); where the norm and the residual go, and WHICH
ROWS the router reads, is the family's own.

- ``scoring == "sigmoid"``: ``s = sigmoid(r W_r)``; chosen = top-k of
  ``s + b``; ``w = s[chosen] / sum(s[chosen]) * routed_scaling``.
  ``scoring == "softmax"``: chosen = top-k of ``r W_r``, ``w`` = softmax
  over the chosen (no bias leaf). ``r`` are the rows the family routes on:
  the MLP's own input ``x`` (:func:`moe_mlp` alone), or rows of its
  choosing routed EARLIER (:func:`route_rows`, handed to :func:`moe_mlp` as
  ``routing``: a model whose router reads the layer's input before
  attention).
- ``y = sum_k w_k E_k(x) + E_shared(x)``, each ``E`` a gated MLP
  ``down(act(gate(x)) * up(x))``, ``act`` = ``gate_act`` (``silu`` |
  ``relu``). No token is dropped, there is no capacity. The routed part
  runs as two grouped GEMMs (``ops/group_gemm.py``) over the assignments
  sorted by expert (``ops/moe_utils.moe_align_block_size``), at decode and
  at prefill alike: only experts that were hit are read. Without shared
  experts (``n_shared_experts`` 0) there is no shared part and no leaf.
- ``held = (first, count)`` is the chip's share of the bank: the router
  still scores every expert, the layer computes the part of the result its
  own experts give, and nothing stands in for the others. The share that
  holds the bank's first expert adds the shared expert (one share of a
  layer does).
- :func:`admitted_rows` / :func:`last_rows`: the slots whose rows a
  prefill pass runs and where their logit rows go, for every layer-plan
  family (``models/ssm_hybrid.py`` asks them too): an admission runs the
  rows it admits.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from triton_dist_tpu.models.tp_transformer import unpack_gate_up
from triton_dist_tpu.obs.scopes import scope
from triton_dist_tpu.ops.group_gemm import (
    GroupGemmConfig, dead_blocks_refetch_none, group_gemm,
)
from triton_dist_tpu.ops.moe_utils import (
    combine_rows_gathered, gather_sorted_rows, moe_align_block_size,
    scatter_add_unsorted, select_experts,
)
from triton_dist_tpu.utils import axis_size as _axis_size

# counters a pass returns, summed over its expert layers (docs/observability.md)
MOE_STATS = ("experts_hit", "assignments", "expert_load_max",
             "sorted_rows_walked", "combine_rows_gathered")
# rows per grouped-GEMM block: small at decode, where a step's assignments
# spread over more experts than there are rows (chip, PR 28: 16-row blocks
# over the min(E, T) alignment 1.376 ms a layer, 32-row 1.410, 8-row 1.363)
DECODE_BLOCK_M = 16
PREFILL_BLOCK_M = 128
# the most one tile of an expert's matrix may take of VMEM (it is double
# buffered; the scoped limit is 16 MiB)
EXPERT_TILE_BYTES = 4 * 2**20
# a chunk of the sorted-row pass (_chunk_blocks): its gathered rows hold
# this many times the elements of the one expert a chunk's edge fetches
# twice (chip, PR 42, a layer's pass of 8192 rows at 1 / 2 / 4 / 8: dots3
# 16.88 / 17.11 / 17.23 / 18.30 ms, SmallThinker 10.33 / 10.11 / 10.38 /
# 10.29, JoyAI's 256 rows 5.36 / 5.34 / 5.32 / 5.48: a dead block inside
# the last chunk costs ~10 us, an edge less)
CHUNK_OVER_EXPERT = 2


def expert_bytes(params: dict) -> int:
    """Bytes of the routed expert banks in a parameter tree."""
    return sum(p[k].nbytes for p in params["layers"]
               for k in ("we_gate_up", "we_down") if k in p)


def require_one_shard(cfg, family: str,
                      missing: str = "the expert exchange across chips") -> None:
    n = _axis_size(cfg.axis)
    if n != 1:
        raise NotImplementedError(
            f"the {family} model serves on a one-device shard: axis "
            f"{cfg.axis!r} has {n} devices and {missing} is not built")


def admitted_rows(prompt, slot_mask, pick, b: int, L: int):
    """What a prefill pass of ``prompt [b*L]`` runs: ``(slots [n], tokens
    [n, L], pick [n])``. With a one-hot ``slot_mask [b]`` (an admission)
    the ONE slot it names: the other slots are mid-sequence, and a whole
    batch of buckets is ``b`` times the work; the slot is found here,
    inside the program, so one program a bucket serves every slot. Without
    a mask (``generate``) every slot. ``pick [b]`` is each prompt's last
    true position (default ``L - 1``). The mask must be one-hot:
    ``argmax`` of an empty one is slot 0."""
    if pick is None:
        pick = jnp.full((b,), L - 1, jnp.int32)
    slots = (jnp.arange(b, dtype=jnp.int32) if slot_mask is None
             else jnp.argmax(slot_mask)[None].astype(jnp.int32))
    pick = jnp.clip(pick, 0, L - 1)[slots]
    return slots, prompt.reshape(b, L)[slots], pick


def last_rows(rows, slots, b: int):
    """A pass's ``last [b, V]``: the logit ``rows [n, V]`` of the slots
    that ran, zeros elsewhere (the batcher reads the admitted slot's)."""
    return jnp.zeros((b, rows.shape[-1]), rows.dtype).at[slots].set(rows)


GATE_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}
SCORINGS = ("sigmoid", "softmax")


def _act(c, gate):
    """The gate's activation (``c.gate_act``), in float32."""
    return GATE_ACTS[c.gate_act](gate.astype(jnp.float32))


def gated_mlp(c, x, w_gate_up, w_down):
    """A gated MLP with gate | up stored as contiguous halves."""
    gu = x @ w_gate_up
    f = gu.shape[-1] // 2
    act = _act(c, gu[:, :f]).astype(x.dtype) * gu[:, f:]
    return act @ w_down


def dense_mlp(c, h, p):
    with scope("ffn/gate_up"):
        gu = h @ p["w_gate_up"]
    with scope("ffn/act"):
        gate, up = unpack_gate_up(gu, c)
        act = _act(c, gate).astype(h.dtype) * up
    with scope("ffn/down"):
        return act @ p["w_down"]


def route(c, h, p):
    """``(weights [m, topk] f32, ids [m, topk] int32)`` over the WHOLE
    bank, whatever share of it is held here. The choice bias is a leaf of
    the sigmoid-scored models only."""
    logits = h.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    return select_experts(
        logits, c.topk, scoring=c.scoring, bias=p.get("router_bias"),
        scale=c.routed_scaling,
    )


def _tile_n(k_dim: int, n_dim: int, itemsize: int) -> int:
    """Columns of one B tile ``[k_dim, .]`` of an expert's matrix: all
    ``n_dim`` of them where that fits ``EXPERT_TILE_BYTES`` (one tile = one
    expert's whole matrix), else halved until it does. The contraction is
    never split: consecutive blocks of one expert then re-use the tile in
    place, so an expert's weights stream once per GEMM however many blocks
    it fills (and a share's dead blocks, :func:`_align_share`, re-use the
    last held expert's tile where it lies)."""
    bn = n_dim
    while k_dim * bn * itemsize > EXPERT_TILE_BYTES and bn % 256 == 0:
        bn //= 2
    return bn


def _align_share(local, here, n_held: int, block_m: int):
    """The alignment of a SHARE's assignments: those to experts held
    elsewhere sort LAST, as a group of their own, into blocks of no valid
    row under the last held expert's name, so every live block lies before
    every dead one and ``num_tokens_post_pad`` is the live prefix (the held
    groups' padded rows). :func:`moe_mlp` walks that prefix a chunk at a
    time and stops: the rows past it go through no XLA pass and no kernel.
    The dead blocks inside the last chunk walked spend no MXU time, fetch
    no tile of B (the name is the block before's) and no block of A
    (``dead_blocks_refetch_none``), and write zeros; their grid steps are
    left. A pass of at most one chunk (a decode step) walks its whole
    alignment, and there a dead block still fetches its rows of A."""
    al = moe_align_block_size(
        jnp.where(here, local, n_held).reshape(-1), n_held + 1, block_m,
        ragged=True)
    away = al.expert_ids == n_held
    valid_rows = jnp.where(away, 0, al.valid_rows)
    return dataclasses.replace(
        al, expert_ids=jnp.minimum(al.expert_ids, n_held - 1),
        valid_rows=valid_rows,
        num_tokens_post_pad=jnp.sum(valid_rows > 0, dtype=jnp.int32) * block_m)


def routing_stats(local_ids, here, n_held: int, rows_walked=0,
                  rows_combined=0) -> jax.Array:
    """One layer's ``MOE_STATS`` int32: ``[experts hit, assignments,
    largest count on one expert]`` of its routing over the experts held
    here, the sorted rows its pass walked and the rows its combine
    gathered."""
    counts = jnp.zeros((n_held,), jnp.int32).at[local_ids.reshape(-1)].add(
        here.reshape(-1).astype(jnp.int32))
    return jnp.stack([jnp.sum(counts > 0), jnp.sum(counts), jnp.max(counts),
                      rows_walked, rows_combined]).astype(jnp.int32)


def no_stats() -> jax.Array:
    """A pass's ``MOE_STATS`` before its first expert layer."""
    return jnp.zeros((len(MOE_STATS),), jnp.int32)


def add_stats(stats, st):
    """A pass's counters with one more layer's: hit, assignments and the
    rows walked and gathered add over layers; the load is the largest
    seen."""
    return jnp.stack([stats[0] + st[0], stats[1] + st[1],
                      jnp.maximum(stats[2], st[2]), stats[3] + st[3],
                      stats[4] + st[4]])


def route_rows(c, rows, p, block_m: int):
    """The routing of ``rows [m, H]`` through layer ``p``'s router:
    ``(weights, local expert ids, held here?, alignment)``, what
    :func:`moe_mlp` takes as ``routing``. Inside ``scope("ffn")``; its ops
    are ``ffn/route``'s (scores and top-k, the alignment)."""
    first, n_held = c.held
    with scope("ffn/route"):
        w, ids = route(c, rows, p)
        local = ids - first
        here = (local >= 0) & (local < n_held)
        # an assignment to an expert held elsewhere keeps its row (shapes
        # are static) with weight 0: its part of the result is that other
        # chip's to add
        local = jnp.where(here, local, 0)
        w = jnp.where(here, w, 0.0)
        if n_held == c.n_experts:
            al = moe_align_block_size(
                local.reshape(-1), n_held, block_m, ragged=True)
        else:
            al = _align_share(local, here, n_held, block_m)
    return w, local, here, al


def _chunk_blocks(c, block_m: int) -> int:
    """Blocks in one chunk of the sorted-row pass, from the pass's own
    shapes. Two chunks that split an expert's run each fetch that expert's
    three matrices, ``3 x hidden x expert_ffn`` elements, which one call
    streams once; a chunk's gathered rows are ``rows x hidden``. So
    ``rows = CHUNK_OVER_EXPERT x 3 x expert_ffn`` holds the edge's second
    fetch to ``1 / CHUNK_OVER_EXPERT`` of ONE of the streams a chunk makes
    of its rows (it makes about ten: PERF.md section 5, dots3), and the
    hidden width cancels. A decode step's alignment (tens of blocks of
    ``DECODE_BLOCK_M`` rows) is well under one chunk at every width."""
    return max(1, CHUNK_OVER_EXPERT * 3 * c.expert_ffn // block_m)


def _chunks(al, chunk: int, sentinel: int):
    """``(alignment, chunks)``: the alignment with dead blocks appended up
    to a whole number of ``chunk``s (rows of no token, ``sentinel``, under
    the last block's expert: no fetch, no MXU time, no one's result)."""
    n_blocks = al.expert_ids.shape[0]
    n_chunks = -(-n_blocks // chunk)
    more = n_chunks * chunk - n_blocks
    if not more:
        return al, n_chunks
    return dataclasses.replace(
        al, sorted_token_ids=jnp.pad(
            al.sorted_token_ids, (0, more * al.block_m),
            constant_values=sentinel),
        expert_ids=jnp.pad(al.expert_ids, (0, more), mode="edge"),
        valid_rows=jnp.pad(al.valid_rows, (0, more))), n_chunks


def _blocks(al, start, n: int):
    """Blocks ``[start, start + n)`` of an alignment, as an alignment."""
    cut = lambda x, per: jax.lax.dynamic_slice_in_dim(x, start * per, n * per)
    return dataclasses.replace(
        al, sorted_token_ids=cut(al.sorted_token_ids, al.block_m),
        expert_ids=cut(al.expert_ids, 1), valid_rows=cut(al.valid_rows, 1))


def moe_mlp(c, h, p, block_m: int, interpret=None, routing=None):
    """Routed experts (the share held here) + the shared expert on rows
    ``h [m, H]``: ``(y [m, H], stats int32[5])``, the stats ``MOE_STATS``.
    ``routing`` is :func:`route_rows` of the rows the model's router
    reads, where those are not ``h`` (issued earlier in the pass); None
    routes on ``h``.

    The per-sorted-row work (the gather of sorted rows, gate|up, the
    activation, down) costs the rows that LANDED on the share, not the rows
    that were routed. Every live block of the alignment lies before every
    dead one, and their number is on the device, so an alignment of more
    than one chunk (:func:`_chunk_blocks`) is walked a chunk at a time up
    to its last live block and no further: the trip count is data, the
    program is one. Each live block goes through the same kernels with the
    same tiles as in one whole call, so the result is that call's bit for
    bit; the rows never walked are never written. ``sorted_rows_walked``
    says how far a pass went. An alignment of at most one chunk (every
    decode step) is walked whole, in straight-line calls. Each chunk's
    down GEMM writes its rows of the one result in place
    (``group_gemm(into=)``), and the alignment is padded with dead blocks
    to whole chunks, so no live block is walked twice.

    The weighted combine after it (``scatter_add_unsorted``) runs in one of
    two forms that follow from the same two shapes the pass was picked by.
    Behind a chunked pass over a SHARE (``written=here``) most slots name
    rows nobody wrote, and the combine walks the landed ones: a trip count
    that is data again, no unwritten row fetched, the float32 sum of each
    token's landed terms in ascending ``k`` as before, bit for bit. Behind
    a whole bank's pass or one of at most one chunk every slot holds a
    result, and the combine is ``topk`` gathers of every token's row, the
    right form when all landed. ``combine_rows_gathered`` says what either
    fetched.

    Inside ``scope("ffn")``: ``ffn/route`` is what a routed layer runs
    around its GEMMs (scores and top-k, the alignment, the gather of
    sorted rows, the weighted combine), ``ffn/experts`` the two grouped
    GEMMs and the activation between them (and, where the pass runs in
    chunks, the loop whole: each chunk's gather with them), ``ffn/shared``
    the shared expert."""
    m = h.shape[0]
    first, n_held = c.held
    w, local, here, al = routing or route_rows(c, h, p, block_m)
    # one B tile = one expert's whole gate (or up, or down) matrix where
    # VMEM has the room (_tile_n): an expert's weights stream once per
    # GEMM however many blocks it fills
    fe = c.expert_ffn
    size = p["we_gate_up"].dtype.itemsize
    gg_up = GroupGemmConfig(
        block_m=block_m, block_n=_tile_n(c.hidden, fe, size),
        block_k=c.hidden, ragged=True)
    gg_down = GroupGemmConfig(
        block_m=block_m, block_n=_tile_n(fe, c.hidden, size), block_k=fe,
        ragged=True)

    def experts(a, part, a_blocks=None, into=None):
        gu = group_gemm(a, p["we_gate_up"], part.expert_ids,
                        valid_rows=part.valid_rows, config=gg_up,
                        interpret=interpret, a_blocks=a_blocks)
        act = _act(c, gu[:, :fe]).astype(h.dtype) * gu[:, fe:]
        return group_gemm(act, p["we_down"], part.expert_ids,
                          valid_rows=part.valid_rows, config=gg_down,
                          interpret=interpret, a_blocks=a_blocks, into=into)

    n_blocks, chunk = al.expert_ids.shape[0], _chunk_blocks(c, block_m)
    if n_blocks <= chunk:
        with scope("ffn/route"):
            a = gather_sorted_rows(h, al, c.topk)
        with scope("ffn/experts"):
            y = experts(a, al)
        walked, written = n_blocks, None
    else:
        al, n_chunks = _chunks(al, chunk, here.size)

        def walk(i, y):
            part = _blocks(al, i * chunk, chunk)
            return experts(
                gather_sorted_rows(h, part, c.topk), part,
                dead_blocks_refetch_none(part.valid_rows), (y, i * chunk))

        trips = (al.num_tokens_post_pad // block_m + chunk - 1) // chunk
        with scope("ffn/experts"):
            y = jax.lax.fori_loop(0, trips, walk, jax.lax.empty(
                (n_chunks * chunk * block_m, c.hidden), h.dtype))
        walked = jnp.minimum(trips * chunk, n_blocks)
        # a whole bank's every assignment lies in the live prefix
        written = None if n_held == c.n_experts else here
    with scope("ffn/route"):
        out = scatter_add_unsorted(y, al, w, m, written=written)   # f32
    if first == 0 and c.n_shared_experts:
        with scope("ffn/shared"):
            out = out + gated_mlp(
                c, h, p["ws_gate_up"], p["ws_down"]).astype(jnp.float32)
    with scope("ffn/route"):
        stats = routing_stats(local, here, n_held, walked * block_m,
                              combine_rows_gathered(m, c.topk, written))
    return out.astype(h.dtype), stats
