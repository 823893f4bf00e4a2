"""MoE routing + token alignment utilities
(≙ reference ``select_experts``/``full_moe_align_block_size``
(moe_reduce_rs.py:87,180) and the C++ ``moe_ag_scatter_align_block_size``
CUDA kernel (csrc/lib/moe_utils.cu:36-356)).

The reference sorts token→expert assignments on device with a shared-memory
histogram + cumsum so every GEMM tile processes rows of a single expert,
padding each expert's segment to the tile size. The TPU-native form is a
fortiori simpler: XLA's sort/scan primitives fuse into a handful of kernels,
so the alignment is ~15 lines of jnp. (The reference's CUDA kernel is a
device-side necessity, not a design feature; the C++ host-side equivalent
for native tooling is part of the csrc/ build — see csrc/ when present.)

All shapes are static: the padded row count is the worst case
``T + min(E, T)*(block_m-1)`` rounded up, with sentinel rows marked by token id
``T`` (gathers clamp, epilogues mask).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from triton_dist_tpu.utils import round_up


def select_experts(
    logits: jax.Array,
    topk: int,
    *,
    scoring: str = "softmax",
    bias: jax.Array | None = None,
    scale: float = 1.0,
) -> tuple[jax.Array, jax.Array]:
    """Top-k routing (≙ ``select_experts``, moe_reduce_rs.py:180).

    logits: ``[tokens, E]``. Returns ``(weights [tokens, topk], ids
    [tokens, topk] int32)``. ``scoring="softmax"`` (default): softmax
    scores, renormalized over the chosen experts. ``scoring="sigmoid"``:
    each expert scored on its own. ``bias [E]`` enters the CHOICE only
    (top-k of ``score + bias``, the aux-loss-free balancing term); the
    weights are the unbiased scores of the chosen experts, normalized over
    them, times ``scale``.
    """
    x = logits.astype(jnp.float32)
    if scoring == "softmax":
        scores = jax.nn.softmax(x, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(x)
    else:
        raise ValueError(f"unknown scoring {scoring!r} (softmax, sigmoid)")
    if bias is None:
        weights, ids = jax.lax.top_k(scores, topk)
    else:
        _, ids = jax.lax.top_k(scores + bias.astype(jnp.float32), topk)
        weights = jnp.take_along_axis(scores, ids, axis=-1)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return weights, ids.astype(jnp.int32)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MoEAlignment:
    """Block-aligned token ordering for grouped GEMM.

    sorted_token_ids: ``[t_pad]`` int32 — flattened token-expert assignment
      index (``token*topk + k`` slot) per padded row; sentinel ``T`` for
      padding rows.
    expert_ids: ``[t_pad // block_m]`` int32 — owning expert of each row
      block (every block is single-expert by construction).
    num_tokens_post_pad: scalar int32 — valid padded rows (static shapes
      mean consumers still process all blocks; rows past this are padding).
    """

    sorted_token_ids: jax.Array
    expert_ids: jax.Array
    num_tokens_post_pad: jax.Array
    # Ragged mode (ISSUE 5): live rows per block — ``[t_pad // block_m]``
    # int32 in (0, block_m] for blocks inside an expert's segment, 0 for the
    # trailing worst-case blocks past every segment. Together with
    # expert_ids this is the scalar-prefetched per-block map
    # ``block → (expert_id, valid_rows)`` the ragged grouped-GEMM kernels
    # consume; None under the legacy (padded) contract.
    valid_rows: jax.Array | None = None

    @property
    def block_m(self) -> int:
        return self.sorted_token_ids.shape[0] // self.expert_ids.shape[0]


def moe_align_block_size(
    topk_ids: jax.Array, n_experts: int, block_m: int, *, ragged: bool = False
) -> MoEAlignment:
    """Sort token-expert assignments by expert and pad each expert segment
    to a multiple of `block_m` (≙ ``moe_ag_scatter_align_block_size``,
    csrc/lib/moe_utils.cu:36-356).

    topk_ids: ``[T]`` int32 flattened assignments (T = tokens * topk).

    ``ragged=True`` additionally emits the per-block ``valid_rows`` map
    (true live rows of each block — a tail block carries its real count
    instead of claiming the full ``block_m``), so a ragged-aware consumer
    can skip the pad rows' MXU work entirely. Layout and every other field
    are IDENTICAL to the legacy form: ragged changes what is computed, not
    where rows live, which is what lets every downstream consumer (gather,
    scatter, backward, the rank-major overlap layout) work unchanged.

    The padded rows are sized for the experts that CAN be hit: ``T``
    assignments touch at most ``min(E, T)`` experts, each of which pads by
    at most ``block_m - 1`` rows. With more experts than assignments
    (decode: 128 assignments over 256 experts) that halves the blocks the
    grouped GEMM walks; for ``E <= T`` it is ``T + E*(block_m-1)``.
    """
    t = topk_ids.shape[0]
    t_pad = round_up(t + min(n_experts, t) * (block_m - 1), block_m)
    counts = jnp.bincount(topk_ids, length=n_experts)
    padded_counts = ((counts + block_m - 1) // block_m) * block_m
    seg_starts = jnp.concatenate(
        [jnp.zeros(1, padded_counts.dtype), jnp.cumsum(padded_counts)[:-1]]
    )
    # stable sort by expert keeps original token order within an expert
    order = jnp.argsort(topk_ids, stable=True)  # [t] assignment indices
    expert_sorted = topk_ids[order]
    cum_counts = jnp.concatenate(
        [jnp.zeros(1, counts.dtype), jnp.cumsum(counts)[:-1]]
    )
    pos_in_expert = jnp.arange(t) - cum_counts[expert_sorted]
    target = seg_starts[expert_sorted] + pos_in_expert
    sorted_token_ids = jnp.full((t_pad,), t, jnp.int32).at[target].set(
        order.astype(jnp.int32)
    )
    block_starts = jnp.arange(t_pad // block_m) * block_m
    expert_ids = jnp.searchsorted(
        jnp.cumsum(padded_counts), block_starts, side="right"
    ).astype(jnp.int32)
    # blocks past all experts' segments keep a valid (clamped) expert id
    expert_ids = jnp.minimum(expert_ids, n_experts - 1)
    valid_rows = None
    if ragged:
        # live rows of block b: how far expert e's REAL rows reach into it
        # (0 for the worst-case trailing blocks — their clamped expert id
        # never owns them, so the whole block is dead)
        offs = block_starts.astype(jnp.int32) - seg_starts.astype(jnp.int32)[
            expert_ids
        ]
        valid_rows = jnp.clip(
            counts.astype(jnp.int32)[expert_ids] - offs, 0, block_m
        ).astype(jnp.int32)
    return MoEAlignment(
        sorted_token_ids=sorted_token_ids,
        expert_ids=expert_ids,
        num_tokens_post_pad=jnp.sum(padded_counts).astype(jnp.int32),
        valid_rows=valid_rows,
    )


def valid_rows_from_sorted(
    sorted_token_ids: jax.Array, block_m: int, sentinel: int
) -> jax.Array:
    """Reconstruct the ragged per-block ``valid_rows`` map from a sorted-id
    array whose pad rows carry ``sentinel`` (every in-repo alignment
    builder's convention). Valid rows are a prefix of each block by
    construction — real rows pack from the segment start, pad rows trail —
    so the per-block count IS the map. For externally-provided alignments
    (``moe_reduce_rs_op``) where the builder's map isn't in hand."""
    return jnp.sum(
        (sorted_token_ids.reshape(-1, block_m) < sentinel), axis=1
    ).astype(jnp.int32)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RankedAlignment:
    """Per-source-rank block alignment: rank-major, expert-minor.

    Each rank's ``m_loc * topk`` assignments are aligned *independently*
    (same construction as :func:`moe_align_block_size`, applied per rank),
    so every row block draws its tokens from exactly ONE rank's chunk. That
    locality is what lets the fused AG-GroupGEMM consume each chunk the
    moment its ring transfer lands, and the fused MoE-Reduce-RS push each
    destination rank's output as soon as its blocks finish — the TPU form
    of the reference's per-source-segment tile swizzle + per-rank notify
    counters (reference allgather_group_gemm.py:420-470,
    moe_reduce_rs.py:362). The price is per-rank instead of global padding
    (≤ ``E*(block_m-1)`` extra rows *per rank*); the overlap and the
    elimination of the materialized gather buy it back.

    local_ids: ``[n, t_pad_loc]`` int32 — rank-local flattened assignment
      index (``token*topk + k``), sentinel ``t_loc`` for padding rows.
    src_rows: ``[n, t_pad_loc]`` int32 — GLOBAL gathered-A row feeding each
      aligned row (``c*m_loc + token``); sentinel rows clamp to row 0 of
      their own chunk, which is always resident when that chunk is
      processed.
    expert_ids: ``[n, nb]`` int32 — owning expert of each row block.
    """

    local_ids: jax.Array
    src_rows: jax.Array
    expert_ids: jax.Array
    # ragged mode (ISSUE 5): ``[n, nb]`` live rows per (rank, block); None
    # under the legacy padded contract (see MoEAlignment.valid_rows)
    valid_rows: jax.Array | None = None

    @property
    def n_ranks(self) -> int:
        return self.local_ids.shape[0]

    @property
    def t_pad_loc(self) -> int:
        return self.local_ids.shape[1]

    @property
    def blocks_per_rank(self) -> int:
        return self.expert_ids.shape[1]

    @property
    def block_m(self) -> int:
        return self.t_pad_loc // self.blocks_per_rank

def ranked_global_view(al: RankedAlignment, m_loc: int, topk: int) -> MoEAlignment:
    """Express a rank-major :class:`RankedAlignment` as an ordinary global
    :class:`MoEAlignment` over the gathered token set, so every downstream
    consumer (``scatter_add_unsorted``, ``group_gemm`` backward, goldens)
    works unchanged: row ``(c, r)`` maps to global assignment
    ``c*m_loc*topk + local_ids[c, r]`` with the global sentinel
    ``n*m_loc*topk`` for padding rows.

    Two contract deltas vs :func:`moe_align_block_size` output: expert ids
    are sorted only *within* each rank segment (pass ``assume_sorted=False``
    to ``group_gemm_dw``), and because padding blocks are interleaved per
    rank segment there is no valid-prefix — ``num_tokens_post_pad`` is
    therefore the FULL padded length, so a consumer that truncates work at
    it conservatively processes everything (sentinel ids mask the padding
    rows, which every consumer must honor anyway)."""
    n, t_pad_loc = al.local_ids.shape
    t_loc = m_loc * topk
    c = jnp.arange(n, dtype=jnp.int32)[:, None]
    valid = al.local_ids < t_loc
    sorted_token_ids = jnp.where(
        valid, c * t_loc + al.local_ids, n * t_loc
    ).reshape(-1).astype(jnp.int32)
    return MoEAlignment(
        sorted_token_ids=sorted_token_ids,
        expert_ids=al.expert_ids.reshape(-1),
        num_tokens_post_pad=jnp.int32(n * t_pad_loc),
        valid_rows=(
            None if al.valid_rows is None else al.valid_rows.reshape(-1)
        ),
    )


def moe_align_ranked(
    ids_full: jax.Array, n_experts: int, block_m: int, m_loc: int,
    *, ragged: bool = False,
) -> RankedAlignment:
    """Align each rank's routing independently (see
    :class:`RankedAlignment`). ids_full: ``[n, m_loc*topk]`` int32 — the
    allgathered flattened top-k ids (tiny payload; ≙ the reference
    allgathering routing metadata ahead of the token data,
    allgather_group_gemm.py:272-330). ``ragged=True`` carries the
    per-(rank, block) ``valid_rows`` map through (see
    :func:`moe_align_block_size`)."""
    n, t_loc = ids_full.shape
    topk = t_loc // m_loc
    al = jax.vmap(
        lambda ids: moe_align_block_size(ids, n_experts, block_m, ragged=ragged)
    )(ids_full)
    token_of = jnp.clip(al.sorted_token_ids // topk, 0, m_loc - 1)
    valid = al.sorted_token_ids < t_loc
    c = jnp.arange(n, dtype=jnp.int32)[:, None]
    src_rows = c * m_loc + jnp.where(valid, token_of, 0)
    return RankedAlignment(
        local_ids=al.sorted_token_ids.astype(jnp.int32),
        src_rows=src_rows.astype(jnp.int32),
        expert_ids=al.expert_ids.astype(jnp.int32),
        valid_rows=(
            None if al.valid_rows is None
            else al.valid_rows.astype(jnp.int32)
        ),
    )


def ranked_scatter_meta(
    al: RankedAlignment, topk_weights_full: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Per-row combine metadata for the fused MoE-Reduce-RS: destination
    token WITHIN the row's own chunk and the routing weight (0 for sentinel
    rows). topk_weights_full: ``[n*m_loc, topk]`` gathered weights.
    Returns ``(dst_ids [n, nb, bm] int32, w_rows [n, nb, bm] f32)`` shaped
    for per-block VMEM slicing."""
    n, t_pad_loc = al.local_ids.shape
    topk = topk_weights_full.shape[1]
    m_loc = topk_weights_full.shape[0] // n
    t_loc = m_loc * topk
    valid = al.local_ids < t_loc
    local_tok = jnp.clip(al.local_ids // topk, 0, m_loc - 1)
    c = jnp.arange(n, dtype=jnp.int32)[:, None]
    glob_assign = jnp.clip(c * t_loc + al.local_ids, 0, n * t_loc - 1)
    w = jnp.where(
        valid, topk_weights_full.reshape(-1)[glob_assign], 0.0
    ).astype(jnp.float32)
    bm = al.block_m
    return (
        local_tok.astype(jnp.int32).reshape(n, -1, bm),
        w.reshape(n, -1, bm),
    )


def gather_sorted_rows(
    x: jax.Array, alignment: MoEAlignment, topk: int
) -> jax.Array:
    """Expand tokens into block-aligned grouped-GEMM rows: row ``r`` of the
    result is token ``sorted_token_ids[r] // topk`` (sentinels clamp to the
    last token; their outputs are masked on the way back)."""
    token_of_row = jnp.minimum(alignment.sorted_token_ids // topk, x.shape[0] - 1)
    return x[token_of_row]


# Tokens in one chunk of the landed walk (:func:`_landed_sum`): a trip
# gathers this many rows of `y_sorted` (chip, PR 49, a layer's combine at
# granite's / dots3's admission, 256 / 512 / 1024: 2.74 / 2.76 / 3.16 and
# 2.44 / 2.43 / 2.68 ms, against 6.86 / 6.88 for the whole form)
COMBINE_WALK_ROWS = 512


def _slot_sum(y_sorted, inv, w):
    """The combine's WHOLE form: one gather of all ``m`` token rows for
    each of the ``topk`` slots, weighted and added in ascending ``k`` in
    float32. ``inv [m, topk]`` is each slot's row of `y_sorted`."""
    # one row-gather per k slot: the obvious single [t, k, d] gather
    # measures 2.6x slower on chip (the 3-D intermediate's layout
    # defeats the streaming fusion); topk is small and static
    def term(k):
        return y_sorted[inv[:, k]].astype(jnp.float32) * w[:, k][:, None]

    out = term(0)
    for k in range(1, w.shape[1]):
        out = out + term(k)
    return out


def combine_rows_gathered(n_tokens: int, topk: int, written=None):
    """The rows :func:`scatter_add_unsorted` gathers for these arguments:
    ``topk x n_tokens`` in the whole form; on the landed walk its trips'
    rows of `y_sorted` (the landed slots, and for each ``j`` the rounding
    of the tokens that have a ``j``-th one up to whole chunks) and the
    ``n_tokens`` rows of the last gather."""
    if written is None:
        return topk * n_tokens
    rows = min(COMBINE_WALK_ROWS, n_tokens)
    landed = jnp.sum(written, axis=1, dtype=jnp.int32)
    reach = jnp.sum(landed[:, None] > jnp.arange(topk, dtype=jnp.int32),
                    axis=0, dtype=jnp.int32)
    return jnp.sum((reach + rows - 1) // rows) * rows + n_tokens


def _landed_sum(y_sorted, inv, w, written):
    """The combine's LANDED walk, :func:`_slot_sum` bit for bit where few
    of a token's slots are ``written``. Each token's landed slots move to
    the front in ascending ``k`` (a count along ``topk``, no sort over the
    assignments) and the tokens are ordered by how many they have, most
    first (one sort of ``m`` keys). A chunk of ``COMBINE_WALK_ROWS`` tokens
    of that order then needs as many trips as its FIRST token has landed
    slots, a number on the device: trip ``j`` gathers the chunk's ``j``-th
    landed rows of `y_sorted`, times their weight, into the chunk's float32
    sum (a trip count that is data: one program whatever landed). One last
    gather puts the sums back in token order. A slot that did not land
    names no row to fetch; it adds exactly 0.0 in the whole form and is
    left out here, which changes no bit (a sum starts at -0.0, the
    identity, where every slot landed, as the whole form starts at its
    first term; at +0.0, an unlanded slot's term, elsewhere)."""
    m, topk = w.shape
    rows = min(COMBINE_WALK_ROWS, m)
    m_pad = round_up(m, rows)
    landed = jnp.sum(written, axis=1, dtype=jnp.int32)
    # slot k of a token is its `place`-th landed one
    place = jnp.cumsum(written, axis=1, dtype=jnp.int32) - 1
    front = written[:, None, :] & (
        place[:, None, :] == jnp.arange(topk, dtype=jnp.int32)[None, :, None])
    order = jnp.argsort(-landed, stable=True)

    def chunked(x):     # [m, ...] by token -> [chunks, rows, ...] in `order`
        x = jnp.pad(x[order], [(0, m_pad - m)] + [(0, 0)] * (x.ndim - 1))
        return x.reshape(m_pad // rows, rows, *x.shape[1:])

    def fronted(x):     # [m, topk] by slot -> [chunks, topk, rows] by place
        x = jnp.sum(jnp.where(front, x[:, None, :], 0), axis=2)
        return chunked(x).transpose(0, 2, 1)

    def chunk_sum(chunk):
        inv_c, w_c, landed_c = chunk      # [topk, rows] x2, [rows]

        def add(j, acc):
            take = lambda x: jax.lax.dynamic_index_in_dim(x, j, keepdims=False)
            term = y_sorted[take(inv_c)].astype(jnp.float32)
            # a token with no j-th landed slot keeps its sum (a selection:
            # the row fetched for it is row 0, written whenever a trip
            # runs, and is never added)
            return jnp.where((landed_c > j)[:, None],
                             acc + term * take(w_c)[:, None], acc)

        start = jnp.where(landed_c == topk, -0.0, 0.0).astype(jnp.float32)
        return jax.lax.fori_loop(0, landed_c[0], add, jnp.broadcast_to(
            start[:, None], (rows, y_sorted.shape[1])))

    acc = jax.lax.map(chunk_sum, (fronted(inv), fronted(w), chunked(landed)))
    back = jnp.zeros((m,), jnp.int32).at[order].set(
        jnp.arange(m, dtype=jnp.int32), unique_indices=True)
    return acc.reshape(m_pad, -1)[back]


def scatter_add_unsorted(
    y_sorted: jax.Array,
    alignment: MoEAlignment,
    weights: jax.Array,
    n_tokens: int,
    *,
    assume_bijective: bool = True,
    written: jax.Array | None = None,
) -> jax.Array:
    """Inverse of :func:`gather_sorted_rows` with the top-k weighted
    reduction fused in (≙ the consumer topk-reduce, moe_reduce_rs.py:468):
    out[token] = Σ_k w[token,k] * y_sorted[row(token,k)].

    ``written [n_tokens, topk]`` bool says which slots' rows of `y_sorted`
    hold a result; the others' (a pass that stopped before them) are never
    read as numbers and add zero, whatever their weight: a selection, not
    a product with 0 (the unwritten row may hold anything).

    NOT a scatter by default: TPU serializes ``.at[].add()`` row scatters
    (measured 4.2 ms for the bench-shape combine — 10× its HBM traffic;
    the 19% pipeline overhead of r5's MFU decomposition). When the
    alignment is a bijection from the flat (token, k) slots to sorted
    rows — every slot placed exactly once, sentinel rows carrying
    ``n_tokens*topk``, which every in-repo alignment builder guarantees —
    a stable argsort of the slot ids IS the inverse permutation, and the
    combine becomes gather + weighted sum, both streaming ops (0.89 ms
    on chip).

    WHICH FORM RUNS, from the arguments alone: without ``written`` every
    slot holds a result and the combine is ``topk`` gathers of all
    ``n_tokens`` rows (:func:`_slot_sum`); with it the caller's pass
    stopped short, most slots name rows nobody wrote, and the combine
    walks the landed ones (:func:`_landed_sum`: the same float32 sum in
    the same order, bit for bit, and no unwritten row fetched).
    :func:`combine_rows_gathered` counts either's rows.

    ``assume_bijective`` is that CONTRACT, not a PRODUCTION runtime check
    (a traced guard + ``lax.cond`` costs ~1.1 ms — re-measured r5): pass
    ``False`` for capacity-style alignments that DROP slots (a dropped
    slot would shift every later token onto the wrong rows under the
    gather form) to get the masked-scatter semantics where dropped slots
    contribute zero.

    Under interpret/debug mode (``config.interpreting()``) the contract IS
    validated: the sorted slot ids must be exactly ``arange(t)`` followed
    by sentinels, and a violating alignment is routed to the masked-
    scatter path via ``lax.cond`` — a dropped slot then contributes zero
    instead of silently shifting every later token's rows (ADVICE r5 #1).
    The debug-tier cost never ships: compiled TPU runs keep the unguarded
    gather form."""
    from triton_dist_tpu import config as tdt_config

    topk = weights.shape[1]
    ids = alignment.sorted_token_ids  # [t_pad], sentinel = n_tokens*topk
    t = n_tokens * topk

    def masked_scatter(ids):
        valid = ids < t
        flat_w = jnp.where(
            valid, weights.reshape(-1)[jnp.clip(ids, 0, t - 1)], 0.0
        )
        if written is not None:
            valid &= written.reshape(-1)[jnp.clip(ids, 0, t - 1)]
        token_of_row = jnp.clip(ids // topk, 0, n_tokens - 1)
        contrib = y_sorted.astype(jnp.float32) * flat_w[:, None]
        return (
            jnp.zeros((n_tokens, y_sorted.shape[1]), jnp.float32)
            .at[token_of_row].add(jnp.where(valid[:, None], contrib, 0.0))
        )

    def bijective_gather(ids):
        inv = jnp.argsort(ids, stable=True)[:t].reshape(n_tokens, topk)
        w = weights.astype(jnp.float32)
        if written is None:
            return _slot_sum(y_sorted, inv, w)
        return _landed_sum(y_sorted, inv, w, written)

    if not assume_bijective:
        return masked_scatter(ids)
    if tdt_config.interpreting():
        sorted_ids = jnp.sort(ids)
        ok = jnp.all(sorted_ids[:t] == jnp.arange(t, dtype=sorted_ids.dtype))
        if ids.shape[0] > t:
            ok = jnp.logical_and(ok, jnp.all(sorted_ids[t:] == t))
        return jax.lax.cond(ok, bijective_gather, masked_scatter, ids)
    return bijective_gather(ids)
