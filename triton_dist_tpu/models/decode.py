"""Serving-side decode for the TP transformer: sequence-parallel KV cache
+ distributed flash decode (≙ the reference's serving story — its
`SpGQAFlashDecodeAttention` layer over `flash_decode.py`, scaled 1→32 GPUs
in README.md:193-195; here the same (partial, lse) merge rides the fused
allgather of ops/flash_decode.py).

Layout at decode time (one token per sequence per step):

- Activations are tiny (``[b, H]``) and REPLICATED — the Megatron AG/RS
  machinery is prefill-shaped; decode projections are plain TP
  (local columns / psum rows).
- The KV cache is SEQUENCE-SHARDED over the tp axis: PE ``i`` owns
  positions ``[i*s_shard, (i+1)*s_shard)`` of every layer's cache — the
  SP/CP decode scaling axis. Each step, the PE owning the current position
  appends the (head-complete) k/v; attention runs as per-shard
  flash-decode partials merged by log-sum-exp.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, ClassVar

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_dist_tpu.models.tp_transformer import (
    EPMoETransformerConfig,
    MoETransformerConfig,
    TransformerConfig,
    pack_gate_up,
    rmsnorm,
    rope,
    specs_for,
    unpack_gate_up,
)
from triton_dist_tpu.ops.flash_decode import (
    FlashDecodeConfig,
    flash_decode_distributed,
    paged_flash_decode,
    paged_flash_decode_distributed,
)
from triton_dist_tpu.obs.scopes import scope as _scope
from triton_dist_tpu.obs.tracer import NULL_SPAN, span as _span
from triton_dist_tpu.utils import axis_size as _axis_size


# Serving param specs are the model family's own (`specs_for`): dense,
# TP-MoE, flat EP-MoE, or hierarchical EP-MoE — where, on the 2-axis
# (ep_outer, axis) serving mesh, attention params come out TP over `axis`
# and replicated over `ep_outer` (each outer group serves its own batch
# slice — DP attention) while the expert banks shard over BOTH axes, the
# reference's multi-node deployment (ep_a2a_layer.py:41,
# test_ep_moe_inference.py). Pass the actual `params` so serving-quantized
# expert banks (quantize_moe_serving_params) resolve their scale-bearing
# spec tree.


def _outer_of(cfg) -> str | None:
    """The serving mesh's outer (node/slice) axis, or None on the flat
    1-axis deployment."""
    return getattr(cfg, "ep_outer", None)


def _outer_dims(cfg) -> tuple[int, int]:
    """(n_o, my_o) of the hierarchical deployment — (1, 0) when flat.
    Call inside shard_map."""
    o = _outer_of(cfg)
    if o is None:
        return 1, 0
    return _axis_size(o), jax.lax.axis_index(o)


def _mesh_outer(cfg, mesh: Mesh) -> int:
    """Outer-axis size of the serving mesh (host side). Validates that a
    hierarchical config actually got a 2-axis mesh."""
    o = _outer_of(cfg)
    if o is None:
        return 1
    if o not in mesh.shape:
        raise ValueError(
            f"hierarchical EP serving (ep_outer={o!r}) needs a mesh with "
            f"axes ({o!r}, {cfg.axis!r}); got {dict(mesh.shape)}"
        )
    return mesh.shape[o]


def _shard_of(s_max: int, n: int) -> int:
    """Per-PE sequence shard; positions >= (s_max//n)*n would be owned by
    no PE (their k/v would silently never land), so require even division."""
    if s_max % n != 0:
        raise ValueError(f"s_max={s_max} must divide evenly over {n} PEs")
    return s_max // n


def _local_lens(pos_b, me, s_shard):
    """Per-PE valid prefix per sequence: positions are global; this PE's
    shard covers ``[me*s_shard, (me+1)*s_shard)``."""
    return jnp.clip(pos_b + 1 - me * s_shard, 0, s_shard).astype(jnp.int32)


def _write_rows(cache, li, lead, slot, rows):
    """Scatter ``rows [..., h_kv, d]`` into layer ``li`` of a stacked
    cache at ``(lead[...], :, slot[...])``, in ONE op and in place: the
    page pool ``[n_layers, n_pool, h_kv, page, d]`` (``lead`` = page ids)
    or the contiguous ``[n_layers, b, h_kv, s, d]`` (``lead`` = the
    sequence). An index out of range drops its row: how a non-owner PE is
    gated (page ``n_pool``, offset ``s_shard``). Every leading dimension
    is indexed, the head too, so that the scatter's window is one ``[d]``
    row: with a window of ``[h_kv, d]`` XLA re-lays the WHOLE cache,
    window dimensions minor-most, before every scatter and back after."""
    heads = jnp.arange(cache.shape[2])
    return cache.at[li, lead[..., None], heads, slot[..., None]].set(
        rows.astype(cache.dtype), mode="drop")


def _pool_pages(pool):
    """The stacked page pool ``[n_layers, n_pool, ...]`` as ONE run of
    pages ``[n_layers * n_pool, ...]``: only the leading dimensions merge
    (the tiled trailing two are untouched), so this is a view, not a
    copy, and layer ``li``'s page ``p`` is page ``li * n_pool + p``."""
    return pool.reshape((-1,) + pool.shape[2:])


# -- the arithmetic of a RING of pages, once: the k/v kind of two lifetimes
# (WindowPagedKVCacheSpec) and the latent kind's window layers
# (LatentPagedCacheSpec) both keep a window layer's rows this way --------------

def ring_pages(window: int, page_size: int, s_max: int) -> int:
    """Pages of a slot's ring in a window layer: what holds ``window``
    consecutive positions and the page being written, never more than a
    whole sequence's."""
    return min(-(-window // page_size) + 1, s_max // page_size)


def _ring_table(b: int, ring: int) -> jax.Array:
    """``block_table_win [1, b, ring]``: each slot its own run of ring
    pages."""
    return (jnp.arange(b, dtype=jnp.int32)[:, None] * ring
            + jnp.arange(ring, dtype=jnp.int32)[None, :])[None]


def _step_address(bt, pos_b, page_size: int, s_max: int, n_pool: int,
                  ring: bool):
    """``(page ids [b], row in the page [b])`` where a step writes each
    slot's row of position ``pos_b``: the table column of the position's
    logical page, modulo the table's width on a ring. A slot at ``s_max``
    owns no page: its id is ``n_pool``, out of range, and the write
    drops."""
    col = pos_b // page_size
    if ring:
        col = col % bt.shape[1]
    page_ids = bt[jnp.arange(bt.shape[0]),
                  jnp.minimum(col, bt.shape[1] - 1)]
    safe_ids = jnp.where(pos_b < s_max, page_ids, n_pool)
    return safe_ids, pos_b % page_size


def _ring_pages_walked(lens: np.ndarray, window: int, page_size: int,
                       ring: int) -> np.ndarray:
    """Pages of ``[len - window, len)`` a slot, as the window kernels walk
    them (host arithmetic)."""
    return np.where(lens > 0, np.minimum(
        (lens - 1) // page_size
        - np.maximum(lens - window, 0) // page_size + 1, ring), 0)


def _ring_sources(lens, span: int, L: int):
    """``[n, span]``: the prompt row that ring address ``j`` holds after a
    prefill of ``lens [n]`` true positions padded to ``L``: the last
    position ``p < len`` with ``p % span == j`` (none yet: any row, the
    mask never reads it). Padding past a prompt's end would overwrite what
    its window still sees, so the count is from the TRUE length."""
    last = lens[:, None] - 1
    src = last - (last - jnp.arange(span, dtype=jnp.int32)) % span
    return jnp.clip(src, 0, L - 1)


@dataclasses.dataclass(frozen=True)
class KVCacheSpec:
    """Contiguous cache geometry: per layer ``[b, h_kv, s_max, d]`` sharded
    on dim 2. The spec object is also the cache STRATEGY: ``pre_step`` and
    ``update_and_attend`` are the only places decode touches the cache, so
    the paged variant below slots in without touching the decode loop."""

    s_max: int

    def init(self, cfg: TransformerConfig, n: int, n_o: int = 1) -> dict:
        _shard_of(self.s_max, n)
        if cfg.batch % n_o:
            raise ValueError(
                f"batch={cfg.batch} must divide over the {n_o} outer "
                f"(node) groups — each group owns a batch slice"
            )
        shape = (
            cfg.n_layers, cfg.batch, cfg.n_kv_heads, self.s_max, cfg.head_dim
        )
        return dict(k=jnp.zeros(shape, cfg.dtype), v=jnp.zeros(shape, cfg.dtype))

    def specs(self, cfg: TransformerConfig) -> dict:
        # batch over the outer (node) axis when hierarchical — each outer
        # group's attention serves only its own slots (DP attention);
        # sequence over the inner axis as always (SP decode)
        t, o = cfg.axis, _outer_of(cfg)
        return dict(k=P(None, o, None, t, None), v=P(None, o, None, t, None))

    def pre_step(self, cfg, cache: dict, pos, me, n: int) -> dict:
        return cache

    def update_and_attend(
        self, cfg, cache, li, k_new, v_new, q, pos_b, me, n,
        fd_config, interpret,
    ):
        """Owning PE appends each sequence's k/v at ITS position into the
        sequence shard, then SP flash-decode partials merge by
        log-sum-exp. ``pos_b [b]`` may be ragged (continuous batching)."""
        s_shard = _shard_of(self.s_max, n)
        # ownership gates the scatter INDICES: a non-owner's offset goes
        # out of range and its row drops (the paged pool's discipline)
        own_b = me == pos_b // s_shard                   # [b]
        safe_off = jnp.where(own_b, pos_b % s_shard, s_shard)
        bidx = jnp.arange(cfg.batch)
        with _scope("attn/kv_write"):
            cache = dict(
                cache,
                k=_write_rows(cache["k"], li, bidx, safe_off, k_new),
                v=_write_rows(cache["v"], li, bidx, safe_off, v_new),
            )
        with _scope("attn/decode"):
            attn = flash_decode_distributed(
                q.astype(cache["k"].dtype), cache["k"][li], cache["v"][li],
                _local_lens(pos_b, me, s_shard),
                axis=cfg.axis, config=fd_config, interpret=interpret,
            )
        return attn, cache

    def update_multi_and_attend(
        self, cfg, cache, li, k_new, v_new, q, pos0, me, n,
        fd_config, interpret,
    ):
        """Speculative-verify variant: append S consecutive positions per
        sequence (owner-gated per position — a chunk may straddle shard
        boundaries) and run the multi-row SP verify attention. k_new,
        v_new ``[b, S, h_kv, d]``; q ``[b, S, hq, d]``; pos0 ``[b]``.
        Returns ``(attn [b, S, hq, d] f32, cache)``."""
        from triton_dist_tpu.ops.flash_decode import (
            flash_ranged_prefill_distributed,
        )

        S = k_new.shape[1]
        s_shard = _shard_of(self.s_max, n)
        # ONE scatter for all (sequence, chunk-position) pairs: ownership
        # gates the INDICES — non-owner entries go out of range and drop
        # (the paged pool's discipline) — so the append costs one pass,
        # not S full-shard copies
        pos_mat = pos0[:, None] + jnp.arange(S, dtype=jnp.int32)  # [b, S]
        own = me == pos_mat // s_shard
        safe_off = jnp.where(own, pos_mat % s_shard, s_shard)     # OOB drop
        bmat = jnp.broadcast_to(
            jnp.arange(cfg.batch)[:, None], safe_off.shape
        )
        with _scope("attn/kv_write"):
            cache = dict(
                cache,
                k=_write_rows(cache["k"], li, bmat, safe_off, k_new),
                v=_write_rows(cache["v"], li, bmat, safe_off, v_new),
            )
        # row i attends global positions < pos0 + i + 1: the ranged entry
        # derives the per-(sequence, chunk-row) local prefix from pos0
        with _scope("attn/decode"):
            attn = flash_ranged_prefill_distributed(
                q.astype(cache["k"].dtype), cache["k"][li], cache["v"][li],
                pos0, axis=cfg.axis, config=fd_config, interpret=interpret,
            )
        return attn, cache


@dataclasses.dataclass(frozen=True)
class PagedKVCacheSpec:
    """Paged cache: each PE owns a page POOL covering its sequence shard
    plus a per-sequence block table (≙ the reference's paged serving cache,
    flash_decode.py:136,203 — vLLM-style). Pages are allocated at RUNTIME
    from a per-PE counter the first time a position lands in a new logical
    page, and the block-table indirection steers the kernel's page fetches
    via scalar prefetch (ops/flash_decode.paged_flash_decode).

    The pool stays where it lies (docs/serving.md "The pool's
    discipline"): one stacked array a tensor, written by one index-gated
    scatter a layer (``_write_rows``) and read by the kernels as the view
    ``_pool_pages`` with the table shifted to the layer's pages; no layer
    is sliced out or put back."""

    s_max: int
    page_size: int
    # static_table=True pre-assigns each sequence slot its own page range
    # at init and disables the runtime bump allocator — required for
    # CONTINUOUS batching, where slots reset mid-run (the bump counter
    # never reclaims, so re-admissions would run the pool out and the
    # out-of-range scatters would silently drop; ≙ vLLM restarting a
    # sequence with a fresh block list). The block-table indirection and
    # paged kernel path are identical either way.
    static_table: bool = False
    # extra (non-table-assigned) physical pages per PE. The prefix cache
    # (models/prefix_cache.py) reserves one as the SCRATCH page released
    # slots' table rows park on, so an idle slot's dummy decode step can
    # never scribble a page the allocator has re-issued. 0 = the layout
    # every pre-cache caller built, byte for byte.
    extra_pages: int = 0

    # what the pool holds: k and v of every kv head ("kv"), one latent row
    # a token (LatentPagedCacheSpec), or k/v pools of two page lifetimes
    # (WindowPagedKVCacheSpec); a config names its family's kind
    kind: ClassVar[str] = "kv"

    def attention_layers(self, cfg) -> int:
        """Layers whose k/v rows live in pages."""
        return cfg.n_layers

    def pages_walked(self, cfg, lens: np.ndarray) -> tuple[int, int]:
        """``(live, table)`` of one step over slots of lengths ``lens``
        (host arithmetic): the pages those lengths expose, which is what
        ``paged_flash_decode`` walks (docs/serving.md "The walk's
        discipline"), and the pages the table rows could name; over every
        PE's shard of a sequence."""
        layers = self.attention_layers(cfg)
        return (int((-(-lens // self.page_size)).sum()) * layers,
                lens.size * (self.s_max // self.page_size) * layers)

    def _geometry(self, cfg, n: int, n_o: int = 1) -> tuple[int, int]:
        s_shard = _shard_of(self.s_max, n)
        if s_shard % self.page_size != 0:
            # a non-dividing page size would let block_table gathers clamp
            # and silently overwrite page 0 — fail loudly like _shard_of
            raise ValueError(
                f"page_size={self.page_size} must divide the per-PE "
                f"sequence shard {s_shard}"
            )
        if cfg.batch % n_o:
            raise ValueError(
                f"batch={cfg.batch} must divide over the {n_o} outer "
                f"(node) groups — each group owns a batch slice"
            )
        pages_per_seq = s_shard // self.page_size
        # local pool: one PE covers its OUTER GROUP's batch slice × its
        # inner sequence shard
        return pages_per_seq, (cfg.batch // n_o) * pages_per_seq

    def _table(self, cfg, n: int, n_o: int = 1) -> tuple[int, jax.Array]:
        """``(pages in the whole pool, block table [w, b_att, pages a
        sequence])``: what every cache KIND shares."""
        pages_per_seq, n_pages = self._geometry(cfg, n, n_o)
        n_pages += self.extra_pages
        b_att = cfg.batch // n_o   # per-outer-group batch slice
        w = n_o * n                # total PEs
        if self.static_table:
            bt = jnp.broadcast_to(
                (
                    jnp.arange(b_att, dtype=jnp.int32)[:, None]
                    * pages_per_seq
                    + jnp.arange(pages_per_seq, dtype=jnp.int32)[None, :]
                ),
                (w, b_att, pages_per_seq),
            )
        else:
            bt = jnp.zeros((w, b_att, pages_per_seq), jnp.int32)
        return w * n_pages, bt

    def init(self, cfg: TransformerConfig, n: int, n_o: int = 1) -> dict:
        n_pool, bt = self._table(cfg, n, n_o)
        shape = (
            cfg.n_layers, n_pool, cfg.n_kv_heads, self.page_size,
            cfg.head_dim,
        )
        return dict(
            k=jnp.zeros(shape, cfg.dtype),
            v=jnp.zeros(shape, cfg.dtype),
            block_table=bt,
            n_alloc=jnp.zeros((bt.shape[0],), jnp.int32),
        )

    def specs(self, cfg: TransformerConfig) -> dict:
        # the pool / table / allocator are PER-PE over the whole mesh:
        # composite (outer, inner) sharding when hierarchical
        t, o = cfg.axis, _outer_of(cfg)
        pe = t if o is None else (o, t)
        return dict(
            k=P(None, pe, None, None, None), v=P(None, pe, None, None, None),
            block_table=P(pe, None, None), n_alloc=P(pe),
        )

    def pre_step(self, cfg, cache: dict, pos_b, me, n: int) -> dict:
        """Allocate a physical page per sequence when ITS position opens a
        new logical page on the owning PE (runs once per step — the table
        is shared by all layers, whose pools allocate in lockstep).
        Ragged ``pos_b``: needing sequences claim consecutive ids off the
        bump counter via an exclusive prefix sum."""
        if self.static_table:
            return cache
        s_shard = self.s_max // n
        off_b = pos_b % s_shard                          # [b]
        page_idx_b = off_b // self.page_size
        need_b = (me == pos_b // s_shard) & (off_b % self.page_size == 0)
        order = jnp.cumsum(need_b.astype(jnp.int32)) - need_b
        new_ids = cache["n_alloc"][0] + order.astype(jnp.int32)
        bidx = jnp.arange(cfg.batch)
        cur = cache["block_table"][0, bidx, page_idx_b]
        bt = cache["block_table"].at[0, bidx, page_idx_b].set(
            jnp.where(need_b, new_ids, cur)
        )
        n_alloc = cache["n_alloc"] + jnp.sum(need_b).astype(jnp.int32)
        return dict(cache, block_table=bt, n_alloc=n_alloc)

    def update_and_attend(
        self, cfg, cache, li, k_new, v_new, q, pos_b, me, n,
        fd_config, interpret,
    ):
        s_shard = _shard_of(self.s_max, n)
        off_b = pos_b % s_shard                          # [b]
        slot_b = off_b % self.page_size
        bidx = jnp.arange(cfg.batch)
        page_ids = cache["block_table"][0, bidx, off_b // self.page_size]
        # page-leading pool: ownership gates the scatter INDICES —
        # non-owner rows are sent out of range and dropped. (Gating the
        # VALUES instead would keep non-owner rows in the scatter, and a
        # non-owner whose table entry still holds the 0 default would
        # alias a real page: duplicate-index scatter order is
        # unspecified, so its stale write-back could clobber the owner's
        # k_new.)
        own_b = me == pos_b // s_shard                   # [b]
        n_pool = cache["k"].shape[1]
        safe_ids = jnp.where(own_b, page_ids, n_pool)    # OOB → dropped
        with _scope("attn/kv_write"):
            cache = dict(
                cache,
                k=_write_rows(cache["k"], li, safe_ids, slot_b, k_new),
                v=_write_rows(cache["v"], li, safe_ids, slot_b, v_new),
            )
        # the kernel reads its pages out of the WHOLE pool: the table is
        # shifted to this layer's run of pages
        with _scope("attn/decode"):
            attn = paged_flash_decode_distributed(
                q.astype(cache["k"].dtype),
                _pool_pages(cache["k"]), _pool_pages(cache["v"]),
                _local_lens(pos_b, me, s_shard),
                cache["block_table"][0] + li * n_pool,
                axis=cfg.axis, interpret=interpret,
            )
        return attn, cache

    def update_multi_and_attend(
        self, cfg, cache, li, k_new, v_new, q, pos0, me, n,
        fd_config, interpret,
    ):
        """Speculative-verify append on the page pool: all (sequence,
        chunk-position) pairs land in ONE scatter — ownership AND the
        static block table gate the indices (non-owner pairs go out of
        range and drop) — then the multi-row paged kernel attends via
        the same table. Static tables only: the bump allocator hands out
        pages one decode step at a time and cannot batch-claim a chunk
        that opens several pages."""
        from triton_dist_tpu.ops.flash_decode import (
            paged_flash_ranged_prefill_distributed,
        )

        if not self.static_table:
            raise NotImplementedError(
                "speculative verify on the paged cache needs "
                "static_table=True (pre-assigned page ranges)"
            )
        S = k_new.shape[1]
        s_shard = _shard_of(self.s_max, n)
        pos_mat = pos0[:, None] + jnp.arange(S, dtype=jnp.int32)  # [b, S]
        off_mat = pos_mat % s_shard
        own = me == pos_mat // s_shard
        bt = cache["block_table"][0]                       # [b, pps]
        page_ids = jnp.take_along_axis(
            bt, off_mat // self.page_size, axis=1
        )                                                  # [b, S]
        n_pool = cache["k"].shape[1]
        safe_ids = jnp.where(own, page_ids, n_pool)        # OOB → dropped
        slot = off_mat % self.page_size
        with _scope("attn/kv_write"):
            cache = dict(
                cache,
                k=_write_rows(cache["k"], li, safe_ids, slot, k_new),
                v=_write_rows(cache["v"], li, safe_ids, slot, v_new),
            )
        with _scope("attn/decode"):
            attn = paged_flash_ranged_prefill_distributed(
                q.astype(cache["k"].dtype),
                _pool_pages(cache["k"]), _pool_pages(cache["v"]),
                pos0, bt + li * n_pool, axis=cfg.axis, interpret=interpret,
            )
        return attn, cache


@dataclasses.dataclass(frozen=True)
class LatentPagedCacheSpec(PagedKVCacheSpec):
    """The paged cache's second KIND: one latent row a token and layer
    (``models/mla_moe.py``: normed kv latent | rotated shared key | pad,
    shared by every head) instead of ``k`` and ``v`` pools of ``n_kv_heads
    x head_dim``. The model's plan (``cfg.layer_types``) may name latent
    attention of two geometries, and the kind then keeps rows of two
    WIDTHS on two page LIFETIMES, and a third pool beside them:

    - full layers: ``lat [n_full_layers, n_pool, page, cfg.latent_row]``
      over ``s_max / page`` pages a slot, ``block_table``. A model whose
      every layer is full (no ``layer_types``) holds this pool alone, as
      the kind did before it learned the others.
    - window layers: ``lat_win [n_window_layers, b * ring, page,
      cfg.window_latent_row]`` over a RING of ``ceil(window / page) + 1``
      pages a slot, ``block_table_win``: position ``p`` lives in ring page
      ``(p // page) % ring`` and is overwritten ``ring * page`` positions
      later. The ring's arithmetic is the k/v window kind's
      (``ring_pages`` / ``_step_address`` / ``_ring_sources``).
    - a learned indexer's keys (``cfg.index_topk``): ``idx
      [n_full_layers, n_pool, page, cfg.index_head_dim]``, one a token a
      full layer, on the full layers' table.

    Block table, static page ranges and the drop-out-of-range write
    discipline are the k/v kind's; the model hands rows in
    (:meth:`write_and_attend` a step, :meth:`write_prompt` at prefill) and
    the kernels of ``ops/mla_decode.py`` / ``ops/sparse_index.py`` read the
    pools where they lie. Stale rows (a slot's last request, a ring's last
    lap, index keys past the new length) are hidden by the length and the
    window, never cleared. Static tables only (the batcher's)."""

    kind: ClassVar[str] = "latent"

    def ring(self, cfg) -> int:
        """Pages of a slot's ring in each window layer."""
        return ring_pages(cfg.window, self.page_size, self.s_max)

    def init(self, cfg, n: int, n_o: int = 1) -> dict:
        if not self.static_table:
            raise NotImplementedError(
                "the latent cache kind needs static_table=True")
        if n != 1 or n_o != 1:
            raise NotImplementedError(
                f"the latent cache kind lives on a one-device shard "
                f"(got {n_o} x {n} devices): a latent row is not sharded "
                f"over sequence or heads yet")
        n_pool, bt = self._table(cfg, n, n_o)
        kinds = cfg.attention_kinds
        n_full, n_win = kinds.count("full"), kinds.count("window")
        pool = lambda layers, pages, row: jnp.zeros(
            (layers, pages, self.page_size, row), cfg.dtype)
        out = dict(lat=pool(n_full, n_pool, cfg.latent_row), block_table=bt,
                   n_alloc=jnp.zeros((bt.shape[0],), jnp.int32))
        if n_win:
            if self.extra_pages:
                refuse_ring("the prefix cache (extra_pages: its scratch "
                            "page)")
            ring, b = self.ring(cfg), cfg.batch
            out.update(lat_win=pool(n_win, b * ring, cfg.window_latent_row),
                       block_table_win=_ring_table(b, ring))
        if cfg.index_topk:
            out.update(idx=pool(n_full, n_pool, cfg.index_head_dim))
        return out

    def specs(self, cfg) -> dict:
        kv = PagedKVCacheSpec.specs(self, cfg)
        lat = P(None, cfg.axis, None, None)
        out = dict(lat=lat, block_table=kv["block_table"],
                   n_alloc=kv["n_alloc"])
        if "window" in cfg.attention_kinds:
            out.update(lat_win=lat, block_table_win=kv["block_table"])
        if cfg.index_topk:
            out.update(idx=lat)
        return out

    def _refuse(self, what: str):
        raise NotImplementedError(
            f"{what} is not built for the latent cache kind "
            f"(LatentPagedCacheSpec): it reads k/v pools")

    def pages_walked(self, cfg, lens: np.ndarray) -> tuple[int, int]:
        """A plan of full layers with no indexer: ``mla_paged_decode``
        still walks the whole table row (PERF.md section 7), live is what
        it walks, the table. With an indexer a full layer walks its LIVE
        pages, in the index keys' pool and then in the latent rows' under
        the selection's mask (the rows it attends are ``selected_rows``, a
        counter of the pass); a window layer the pages of ``[len - window,
        len)`` in its ring."""
        kinds = cfg.attention_kinds
        n_full, n_win = kinds.count("full"), kinds.count("window")
        page = self.page_size
        table = lens.size * (self.s_max // page) * n_full
        full = int((-(-lens // page)).sum()) * n_full if cfg.index_topk \
            else table
        if not n_win:
            return full, table
        ring = self.ring(cfg)
        win = _ring_pages_walked(lens, cfg.window, page, ring)
        return (full + int(win.sum()) * n_win,
                table + lens.size * ring * n_win)

    def _pool_of(self, kind: str) -> tuple[str, str]:
        """``(latent pool, table)`` names of an attention kind."""
        return (("lat_win", "block_table_win") if kind == "window"
                else ("lat", "block_table"))

    def write_and_attend(self, cfg, cache, kind: str, ki: int, row, q, pos_b,
                         *, d_v: int, scale: float, index=None,
                         interpret=None):
        """One step of the ``ki``-th layer of its attention ``kind``: each
        slot's new latent ``row [b, row width]`` lands in its page (a full
        layer's page of the position, a window layer's ring page), then
        the absorbed queries ``q [b, heads, row width]`` read the kind's
        pool: a window layer ``[pos - window + 1, pos]`` through its ring;
        a full layer ``[0, pos]``, or, with ``index = (key [b, d_i],
        queries [b, G, d_i], weights [b, G])``, the ``cfg.index_topk``
        positions of largest index score: the slot's new index key lands
        beside its row, the slot's live keys are scored where they lie,
        and the latent rows' live pages are read where they lie with the
        unselected positions masked. ``(o_lat [b, heads, d_v] f32,
        cache)``."""
        from triton_dist_tpu.ops.mla_decode import (
            mla_paged_decode, sparse_mla_decode,
        )

        name, tn = self._pool_of(kind)
        bt = cache[tn][0]                                  # [b, pages a slot]
        n_pool = cache[name].shape[1]
        ids, slot = _step_address(
            bt, pos_b, self.page_size, self.s_max, n_pool, kind == "window")
        lens = jnp.clip(pos_b + 1, 0, self.s_max)
        with _scope("attn/kv_write"):
            pool = cache[name].at[ki, ids, slot].set(
                row.astype(cache[name].dtype), mode="drop")
            cache = dict(cache, **{name: pool})
        if index is None:
            with _scope("attn/decode"):
                return mla_paged_decode(
                    q, pool, ki, lens, bt, d_v=d_v, scale=scale,
                    window=cfg.window if kind == "window" else None,
                    interpret=interpret), cache
        from triton_dist_tpu.ops.sparse_index import (
            index_scores_paged, topk_mask,
        )

        key, q_idx, w_idx = index
        with _scope("attn/index"):
            idx = cache["idx"].at[ki, ids, slot].set(
                key.astype(cache["idx"].dtype), mode="drop")
            cache = dict(cache, idx=idx)
            scores = index_scores_paged(
                q_idx, w_idx, idx, ki, lens, bt, scale=cfg.index_scale,
                interpret=interpret)
            keep = topk_mask(scores, cfg.index_topk)
        with _scope("attn/decode"):
            return sparse_mla_decode(
                q, pool, ki, lens, bt, keep, d_v=d_v, scale=scale,
                interpret=interpret), cache

    def write_prompt(self, cache, kind: str, ki: int, rows, lens, slots,
                     index_keys=None):
        """Prefill's latent ``rows [n, L, row width]`` of the ``ki``-th
        layer of its kind for ``slots [n]``, as whole pages: a full
        layer's positions ``[0, L)`` into each slot's page range (with
        its ``index_keys [n, L, d_i]`` beside them); a window layer's LAST
        ``ring * page`` true positions (``lens [n]``) at their ring
        addresses. No other slot's pages are touched."""
        name, tn = self._pool_of(kind)
        n, L = rows.shape[:2]
        ps = self.page_size
        if kind == "window":
            ids = cache[tn][0][slots]                      # [n, ring]
            src = _ring_sources(lens, ids.shape[1] * ps, L)
            rows = jnp.take_along_axis(rows, src[:, :, None], axis=1)
        else:
            ids = cache[tn][0][slots, :-(-L // ps)]        # [n, pages of L]

        def put(pool, x):
            pad = ids.shape[1] * ps - x.shape[1]
            if pad:
                x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            return pool.at[ki, ids.reshape(-1)].set(
                x.reshape(-1, ps, x.shape[-1]).astype(pool.dtype))

        cache = dict(cache, **{name: put(cache[name], rows)})
        if index_keys is not None:
            cache = dict(cache, idx=put(cache["idx"], index_keys))
        return cache

    def update_and_attend(self, *a, **kw):
        self._refuse("the k/v decode step")

    def update_multi_and_attend(self, *a, **kw):
        self._refuse("speculative verify / ranged prefill")


@dataclasses.dataclass(frozen=True)
class WindowPagedKVCacheSpec(PagedKVCacheSpec):
    """The paged cache's third KIND: k/v pools of TWO page lifetimes in
    one allocator, for a model whose plan names window and full attention
    layers (``models/window_moe.py``; ``cfg.layer_types``, ``cfg.window``).

    - full layers: ``k_full, v_full [n_full_layers, n_pool, h_kv, page,
      d]`` over ``s_max / page`` pages a slot, ``block_table``, as the k/v
      kind's.
    - window layers: ``k_win, v_win [n_window_layers, n_ring_pool, ...]``
      over a RING of ``ceil(window / page) + 1`` pages a slot (never more
      than ``s_max / page``), ``block_table_win [., b, ring]``: position
      ``p`` lives in the slot's ring page ``(p // page) % ring`` and is
      overwritten ``ring * page`` positions later, when no window layer
      can see it any more.

    Same discipline as the k/v kind: static tables, one stacked array a
    tensor and kind, one index-gated scatter a layer (an index out of
    range drops its row), the kernels read the pool where it lies through
    a table shifted to the layer's pages. The model hands rows in
    (:meth:`write_and_attend` a step, :meth:`write_prompt` at prefill).

    A ring holds no position twice and forgets: what needs the WHOLE
    sequence in pages that stay refuses this kind by name
    (:func:`refuse_ring`): the prefix cache and a trie hit, ranged or
    chunked prefill, speculative verify, the disaggregated handoff, and
    any mesh wider than one device."""

    kind: ClassVar[str] = "kv_window"

    def ring(self, cfg) -> int:
        """Pages of a slot's ring in each window layer."""
        return ring_pages(cfg.window, self.page_size, self.s_max)

    def init(self, cfg, n: int, n_o: int = 1) -> dict:
        if not self.static_table:
            raise NotImplementedError(
                "the kv_window cache kind needs static_table=True")
        if n != 1 or n_o != 1:
            raise NotImplementedError(
                f"the kv_window cache kind lives on a one-device shard "
                f"(got {n_o} x {n} devices): a ring is not sharded over "
                f"sequence or heads yet")
        if self.extra_pages:
            refuse_ring("the prefix cache (extra_pages: its scratch page)")
        n_pool, bt = self._table(cfg, n, n_o)
        ring, b = self.ring(cfg), cfg.batch
        bt_win = _ring_table(b, ring)
        kinds = cfg.layer_types
        pool = lambda layers, pages: jnp.zeros(
            (layers, pages, cfg.n_kv_heads, self.page_size, cfg.head_dim),
            cfg.dtype)
        n_full, n_win = kinds.count("full"), kinds.count("window")
        return dict(
            k_full=pool(n_full, n_pool), v_full=pool(n_full, n_pool),
            k_win=pool(n_win, b * ring), v_win=pool(n_win, b * ring),
            block_table=bt, block_table_win=bt_win,
            n_alloc=jnp.zeros((bt.shape[0],), jnp.int32),
        )

    def specs(self, cfg) -> dict:
        kv = PagedKVCacheSpec.specs(self, cfg)
        return dict(
            k_full=kv["k"], v_full=kv["v"], k_win=kv["k"], v_win=kv["v"],
            block_table=kv["block_table"],
            block_table_win=kv["block_table"], n_alloc=kv["n_alloc"])

    def pages_walked(self, cfg, lens: np.ndarray) -> tuple[int, int]:
        page, ring = self.page_size, self.ring(cfg)
        n_full = cfg.layer_types.count("full")
        n_win = cfg.layer_types.count("window")
        full = -(-lens // page)
        win = _ring_pages_walked(lens, cfg.window, page, ring)
        return (int(full.sum()) * n_full + int(win.sum()) * n_win,
                lens.size * (self.s_max // page * n_full + ring * n_win))

    def _pool_of(self, kind: str) -> tuple[str, str, str]:
        """``(k pool, v pool, table)`` names of an attention kind."""
        return (("k_win", "v_win", "block_table_win") if kind == "window"
                else ("k_full", "v_full", "block_table"))

    def write_and_attend(self, cfg, cache, kind: str, ki: int, k_new, v_new,
                         q, pos_b, interpret):
        """One step of the ``ki``-th layer of its attention ``kind``: each
        slot's new row lands in its page (a full layer's page of the
        position, a window layer's ring page), then the kernel reads
        ``[0, pos]`` or the window's ``[pos - window + 1, pos]`` out of
        the kind's whole pool. ``(attn [b, hq, d] f32, cache)``."""
        kn, vn, tn = self._pool_of(kind)
        bt = cache[tn][0]                                  # [b, pages a slot]
        n_pool = cache[kn].shape[1]
        safe_ids, slot = _step_address(
            bt, pos_b, self.page_size, self.s_max, n_pool, kind == "window")
        with _scope("attn/kv_write"):
            cache = dict(
                cache,
                **{kn: _write_rows(cache[kn], ki, safe_ids, slot, k_new),
                   vn: _write_rows(cache[vn], ki, safe_ids, slot, v_new)})
        with _scope("attn/decode"):
            attn = paged_flash_decode(
                q.astype(cache[kn].dtype),
                _pool_pages(cache[kn]), _pool_pages(cache[vn]),
                jnp.clip(pos_b + 1, 0, self.s_max), bt + ki * n_pool,
                window=cfg.window if kind == "window" else None,
                interpret=interpret,
            )
        return attn, cache

    def write_prompt(self, cfg, cache, kind: str, ki: int, k, v, lens, slots):
        """Prefill's rows ``k, v [n, L, h_kv, d]`` of the ``ki``-th layer
        of its kind for ``slots [n]``, as whole pages: a full layer's
        positions ``[0, L)`` into each slot's page range; a window layer's
        LAST ``ring * page`` true positions (``lens [n]``: padding past a
        prompt's end would overwrite what its window still sees) at their
        ring addresses. No other slot's pages are touched."""
        kn, vn, tn = self._pool_of(kind)
        n, L = k.shape[:2]
        ps = self.page_size
        bt = cache[tn][0][slots]                           # [n, pages a slot]
        if kind == "window":
            src = _ring_sources(lens, bt.shape[1] * ps, L)[:, :, None, None]
            k, v = (jnp.take_along_axis(x, src, axis=1) for x in (k, v))
            ids = bt
        else:
            n_pages = -(-L // ps)
            if n_pages * ps != L:
                pad = ((0, 0), (0, n_pages * ps - L), (0, 0), (0, 0))
                k, v = jnp.pad(k, pad), jnp.pad(v, pad)
            ids = bt[:, :n_pages]
        as_pages = lambda x: jnp.swapaxes(
            x.reshape(n, -1, ps, *x.shape[2:]), 2, 3
        ).reshape(-1, x.shape[2], ps, x.shape[3])
        put = lambda pool, x: pool.at[ki, ids.reshape(-1)].set(
            as_pages(x).astype(pool.dtype))
        return dict(cache, **{kn: put(cache[kn], k), vn: put(cache[vn], v)})

    def update_and_attend(self, *a, **kw):
        raise NotImplementedError(
            "the dense family's decode step reads ONE k/v pool: a "
            "kv_window model walks its own plan (cfg.decode_step)")

    def update_multi_and_attend(self, *a, **kw):
        refuse_ring("speculative verify / ranged prefill")


def refuse_ring(what: str):
    raise NotImplementedError(
        f"{what} is not built for the kv_window cache kind "
        f"(WindowPagedKVCacheSpec): a window layer's pages are a ring that "
        f"forgets, and it needs every position of the sequence in pages "
        f"that stay")


@dataclasses.dataclass(frozen=True)
class StatePagedKVCacheSpec(PagedKVCacheSpec):
    """The paged cache's fourth KIND: pages and per-slot RECURRENT STATE
    side by side, for a model whose plan names state-space and attention
    layers (``models/ssm_hybrid.py``; ``cfg.layer_kinds``).

    - attention layers: ``k, v [n_attention_layers, n_pool, h_kv, page,
      d]`` and ``block_table``, as the k/v kind's, over those layers only.
    - state-space layers (``mamba`` or ``mamba2``, one kind a model),
      float32, a fixed size a slot whatever the context: ``ssm
      [n_state_layers, 2, slots, d_state, d_inner]`` (the recurrence's
      state, channels on the lanes: Mamba-2's heads lie side by side there)
      and ``conv [n_state_layers, d_conv, slots, cfg.conv_channels]`` (the
      convolution's last inputs: THE CONFIG says how wide the convolution
      is, ``d_inner`` for Mamba-1 and ``d_inner + 2 d_state`` for Mamba-2,
      whose ``B`` and ``C`` are convolved with ``x``).

    State is not paged, has no table and is not masked by a length: a
    stale row is not hidden, it is wrong. So both pools are RINGS KEYED BY
    POSITION, as a page row is: the state after position ``p`` lives in
    ``ssm[:, p % 2]``, the convolution's input at ``p`` in ``conv[:, p %
    d_conv]``. A step at ``pos`` reads ``ssm[(pos - 1) % 2]`` and the
    ``d_conv - 1`` rows before ``pos``, and writes ``ssm[pos % 2]`` and
    ``conv[pos % d_conv]``: never a row it reads. That is what makes the
    step REPEATABLE (``PAGED_CACHE_KINDS``). A slot's state is RESET by
    position too: a step at position 0 reads zeros, a convolution row of a
    position before 0 reads as zero, an admission by prefill writes the
    state after its last true token (``write_state``); nothing is cleared
    when a request leaves. A vacant slot's dummy step stands at position 0
    (``ContinuousBatcher._vacate``) and rewrites rows the next admission
    overwrites or never reads.

    What needs a sequence's state at MORE than its last position refuses
    this kind by name (:func:`refuse_state`): the prefix cache and a trie
    hit, ranged or chunked prefill, speculative verify, the disaggregated
    handoff; and any mesh wider than one device."""

    kind: ClassVar[str] = "kv_state"

    def init(self, cfg, n: int, n_o: int = 1) -> dict:
        _state_kind_serves(self, n, n_o)
        n_pool, bt = self._table(cfg, n, n_o)
        kinds, b = cfg.layer_kinds, cfg.batch
        n_attn = kinds.count("attention")
        n_mamba = len(kinds) - n_attn
        pool = lambda: jnp.zeros(
            (n_attn, n_pool, cfg.n_kv_heads, self.page_size, cfg.head_dim),
            cfg.dtype)
        return dict(
            k=pool(), v=pool(),
            ssm=jnp.zeros((n_mamba, 2, b, cfg.d_state, cfg.d_inner),
                          jnp.float32),
            conv=jnp.zeros((n_mamba, cfg.d_conv, b, cfg.conv_channels),
                           jnp.float32),
            block_table=bt,
            n_alloc=jnp.zeros((bt.shape[0],), jnp.int32),
        )

    def specs(self, cfg) -> dict:
        t = cfg.axis
        return dict(PagedKVCacheSpec.specs(self, cfg),
                    ssm=P(None, None, t, None, None),
                    conv=P(None, None, t, None))

    # -- the attention layers' pages ----------------------------------------

    def attention_layers(self, cfg) -> int:
        return cfg.layer_kinds.count("attention")

    def write_and_attend(self, cfg, cache, ki: int, k_new, v_new, q, pos_b,
                         interpret):
        """One step of the ``ki``-th attention layer: each slot's new row
        lands in the page of its position, then the kernel reads ``[0,
        pos]`` out of the whole pool. ``(attn [b, hq, d] f32, cache)``."""
        bt = cache["block_table"][0]                       # [b, pages a slot]
        n_pool = cache["k"].shape[1]
        col = jnp.minimum(pos_b // self.page_size, bt.shape[1] - 1)
        # a slot at s_max owns no page: its write drops
        ids = jnp.where(pos_b < self.s_max,
                        bt[jnp.arange(bt.shape[0]), col], n_pool)
        slot = pos_b % self.page_size
        with _scope("attn/kv_write"):
            cache = dict(cache,
                         k=_write_rows(cache["k"], ki, ids, slot, k_new),
                         v=_write_rows(cache["v"], ki, ids, slot, v_new))
        with _scope("attn/decode"):
            attn = paged_flash_decode(
                q.astype(cache["k"].dtype),
                _pool_pages(cache["k"]), _pool_pages(cache["v"]),
                jnp.clip(pos_b + 1, 0, self.s_max), bt + ki * n_pool,
                interpret=interpret,
            )
        return attn, cache

    def write_prompt(self, cache, ki: int, k, v, slots):
        """Prefill's rows ``k, v [n, L, h_kv, d]`` of the ``ki``-th
        attention layer into the page ranges of ``slots [n]``, as whole
        pages (rows past a prompt's end are junk no length exposes)."""
        n, L = k.shape[:2]
        ps = self.page_size
        n_pages = -(-L // ps)
        pad = ((0, 0), (0, n_pages * ps - L), (0, 0), (0, 0))
        ids = cache["block_table"][0][slots, :n_pages].reshape(-1)
        as_pages = lambda x: jnp.swapaxes(
            jnp.pad(x, pad).reshape(n, n_pages, ps, *x.shape[2:]), 2, 3
        ).reshape(-1, x.shape[2], ps, x.shape[3])
        put = lambda pool, x: pool.at[ki, ids].set(
            as_pages(x).astype(pool.dtype), mode="drop")
        return dict(cache, k=put(cache["k"], k), v=put(cache["v"], v))

    # -- the state-space layers' state ----------------------------------------

    def conv_step(self, cache, ki: int, u, pos_b, w, bias, interpret):
        """One step of the ``ki``-th state-space layer's causal depthwise
        convolution: ``u [b, d]`` is the input at ``pos_b``, ``w [d_conv,
        d]`` tap-major (the last tap is the newest input) and ``bias [d]``
        AS STORED (the kernel widens them). ``(bias + sum_j w[j] * u_{pos
        - d_conv + 1 + j}  [b, d] f32, cache)``; an input before position 0
        is zero, whatever its ring row holds.

        One kernel, in place in the ring (``ops/conv_ring.conv_ring_step``,
        whose XLA twin is the plain form of this step): ring row ``r``
        holds the input of the last position ``== r (mod d_conv)``, which
        is tap ``(r - pos + d_conv - 1) % d_conv`` of this step unless it
        is the row this step writes, ``pos % d_conv``; a row is masked by
        a select, not a product (a stale row may hold anything), and the
        step never reads the row it writes: run twice it leaves the same
        ring."""
        from triton_dist_tpu.ops.conv_ring import conv_ring_step

        with _scope("ssm/conv"):
            out, conv = conv_ring_step(cache["conv"], ki, u, pos_b, w, bias,
                                       interpret=interpret)
        return out, dict(cache, conv=conv)

    def state_step(self, cache, ki: int, c, dt_in, b_dt, b_in, c_out, a,
                   d_skip, pos_b, interpret):
        """One step of the ``ki``-th state-space layer's recurrence for
        every slot (``ops/selective_scan.selective_state_update``, handed
        ``b_dt`` and ``d_skip`` as stored): reads the state after ``pos -
        1`` (zeros at position 0), writes the state after ``pos``."""
        from triton_dist_tpu.ops.selective_scan import selective_state_update

        with _scope("ssm/scan"):
            y, ssm = selective_state_update(
                cache["ssm"], ki, pos_b, c, dt_in, b_dt, b_in, c_out, a,
                d_skip, interpret=interpret)
        return y, dict(cache, ssm=ssm)

    def head_state_step(self, cache, ki: int, x, dt_in, dt_bias, a, b_in,
                        c_out, d_skip, pos_b, interpret):
        """:meth:`state_step` for a layer whose decay is ONE scalar a head
        (Mamba-2; ``ops/ssd.ssd_state_update``, handed ``dt_bias`` and
        ``d_skip [heads]`` as stored): the same pool, the same parity."""
        from triton_dist_tpu.ops.ssd import ssd_state_update

        with _scope("ssm/scan"):
            y, ssm = ssd_state_update(
                cache["ssm"], ki, pos_b, x, dt_in, dt_bias, a, b_in, c_out,
                d_skip, interpret=interpret)
        return y, dict(cache, ssm=ssm)

    def write_state(self, cache, ki: int, slots, lens, u, h, first=None):
        """An admission's state of the ``ki``-th state-space layer for
        ``slots [n]`` whose prompts hold ``lens [n]`` true tokens: ``h [n,
        d_state, d]`` the recurrence's state after the LAST TRUE token,
        ``u [n, L, channels]`` the convolution's inputs, whose last ``d_conv``
        true rows go to their ring rows. No other slot's rows are
        touched. ``first [n]``: ``u`` holds the rows of positions ``first``
        on only (:meth:`tail`: a layer that keeps a whole prompt's inputs
        until the pass ends holds ``layers x L x channels`` floats)."""
        K = cache["conv"].shape[1]
        last = lens[:, None] - 1
        # ring row r <- the last position p < len with p % K == r (none yet:
        # any row; the step masks it by position)
        src = last - (last - jnp.arange(K, dtype=jnp.int32)) % K   # [n, K]
        if first is not None:
            src = src - first[:, None]
        rows = jnp.take_along_axis(
            u, jnp.clip(src, 0, u.shape[1] - 1)[:, :, None], axis=1)
        conv = cache["conv"].at[
            ki, jnp.arange(K)[None, :], slots[:, None]].set(rows)
        ssm = cache["ssm"].at[ki, (lens - 1) % 2, slots].set(h)
        return dict(cache, conv=conv, ssm=ssm)

    @staticmethod
    def tail(u, lens, taps: int):
        """The last ``taps`` true rows of ``u [n, L, channels]`` (from
        position 0 where a prompt is shorter) and the position of the first
        of them: what :meth:`write_state` reads of ``u``, as ``(rows [n,
        taps, channels], first [n])``."""
        first = jnp.maximum(lens - taps, 0)
        idx = first[:, None] + jnp.arange(taps, dtype=jnp.int32)
        rows = jnp.take_along_axis(
            u, jnp.minimum(idx, u.shape[1] - 1)[:, :, None], axis=1)
        return rows, first

    def update_and_attend(self, *a, **kw):
        raise NotImplementedError(
            "the dense family's decode step reads k/v pools of every layer: "
            "a kv_state model walks its own plan (cfg.decode_step)")

    def update_multi_and_attend(self, *a, **kw):
        refuse_state("speculative verify / ranged prefill")


@dataclasses.dataclass(frozen=True)
class RetentionStateCacheSpec(PagedKVCacheSpec):
    """The paged cache's fifth KIND, which has NO PAGES: per-slot MATRIX
    STATE alone, for a model whose every layer is a linear attention
    (``models/retention.py``). It is a kind of its own rather than
    ``StatePagedKVCacheSpec`` with empty k/v pools: that class is the two
    pools' side-by-side bookkeeping (tables, page writes, a convolution's
    ring), none of which a layer here has, and what the two share is the
    contract below and :func:`refuse_state`. ``page_size`` is taken (the
    batcher gives every paged kind one) and unused.

    Float32, a fixed size a slot whatever the context: ``s [n_layers, 2,
    slots, h_kv, D, d]`` (``D = cfg.state_rows``: ``phi(k) v^T`` summed) and
    ``z [n_layers, 2, slots, h_kv, d, d]`` (``k k^T`` summed, the
    normaliser). Both are KEYED BY POSITION as ``kv_state``'s recurrence
    is: the state after position ``p`` lives at ``[:, p % 2]``; a step at
    ``pos`` reads ``(pos - 1) % 2`` and writes ``pos % 2``, never the row it
    reads, which makes the step REPEATABLE (``PAGED_CACHE_KINDS``) at the
    price of the state twice. A step at position 0 reads zeros; an
    admission by prefill OVERWRITES the slot's ``s`` and ``z`` of its last
    true position WHOLE (``write_state``; the other parity is written by
    the slot's next step before any step reads it); nothing is cleared when
    a request leaves, and a vacated slot's stale state is never read.

    What needs a sequence's state at MORE than its last position refuses
    this kind by name (:func:`refuse_state`), as ``kv_state`` does."""

    kind: ClassVar[str] = "state"

    def init(self, cfg, n: int, n_o: int = 1) -> dict:
        _state_kind_serves(self, n, n_o)
        lead = (cfg.n_layers, 2, cfg.batch, cfg.n_kv_heads)
        d = cfg.head_dim
        return dict(s=jnp.zeros((*lead, cfg.state_rows, d), jnp.float32),
                    z=jnp.zeros((*lead, d, d), jnp.float32))

    def specs(self, cfg) -> dict:
        slots = P(None, None, cfg.axis, None, None, None)
        return dict(s=slots, z=slots)

    def attention_layers(self, cfg) -> int:
        return 0

    def state_step(self, cache, li: int, q, k, v, log_g, pos_b, interpret):
        """One step of layer ``li`` for every slot
        (``ops/retention.retention_update``): reads the state after ``pos -
        1`` (zeros at position 0), writes the state after ``pos``. ``(y [b,
        h_q, d] f32, cache)``."""
        from triton_dist_tpu.ops.retention import retention_update

        with _scope("retn/update"):
            y, s, z = retention_update(cache["s"], cache["z"], li, pos_b, q,
                                       k, v, log_g, interpret=interpret)
        return y, dict(cache, s=s, z=z)

    def write_state(self, cache, li: int, slots, lens, s, z):
        """An admission's state of layer ``li`` for ``slots [n]`` whose
        prompts hold ``lens [n]`` true tokens: ``s [n, h_kv, D, d]``, ``z
        [n, h_kv, d, d]`` after the LAST TRUE token. No other slot's rows
        are touched."""
        at = (li, (lens - 1) % 2, slots)
        return dict(cache, s=cache["s"].at[at].set(s),
                    z=cache["z"].at[at].set(z))

    def update_and_attend(self, *a, **kw):
        raise NotImplementedError(
            "the dense family's decode step reads k/v pools of every layer: "
            "a state model walks its own plan (cfg.decode_step)")

    def update_multi_and_attend(self, *a, **kw):
        refuse_state("speculative verify / ranged prefill", self.kind)


# the kinds whose slots hold a state after their last position only
STATE_CACHE_KINDS = (StatePagedKVCacheSpec.kind, RetentionStateCacheSpec.kind)


def _state_kind_serves(spec, n: int, n_o: int) -> None:
    """What ``init`` of a state kind checks before it builds its pools."""
    if not spec.static_table:
        raise NotImplementedError(
            f"the {spec.kind} cache kind needs static_table=True")
    if n != 1 or n_o != 1:
        raise NotImplementedError(
            f"the {spec.kind} cache kind lives on a one-device shard "
            f"(got {n_o} x {n} devices): a slot's state is not sharded yet")
    if spec.extra_pages:
        refuse_state("the prefix cache (extra_pages: its scratch page)",
                     spec.kind)


def refuse_state(what: str, kind: str = "kv_state"):
    raise NotImplementedError(
        f"{what} is not built for the {kind} cache kind "
        f"({PAGED_CACHE_KINDS[kind].__name__}): a slot's recurrent state is "
        f"the state after its LAST position only, and it needs the state "
        f"at a position inside the sequence (to share, to resume from, to "
        f"roll back to or to hand over)")


# a config's ``cache_kind`` -> the paged cache its family's passes use.
#
# THE CONTRACT every kind signs: A STEP IS REPEATABLE. ``decode_step`` run
# twice on the same ``(tok, pos)`` leaves the cache as running it once does,
# and a slot's step reads nothing that a step of the same slot at the same
# or a later position has written. The batcher's lookahead leans on it: a
# step sent ahead of a round that then admits a request is thrown away and
# run again (``ContinuousBatcher._round_inputs``) on the cache that the
# vain step already wrote to (it is donated). The page kinds keep the
# contract because a row is keyed by its position (the second run writes
# the same k/v rows again); ``kv_state`` keeps it by keying its state the
# same way: a parity axis of 2 on the recurrence's state and a ring of
# ``d_conv`` rows on the convolution's inputs, read at ``pos - 1`` and
# before, written at ``pos`` (``StatePagedKVCacheSpec``); ``state`` by the
# same parity axis on its matrix state, which is all it holds
# (``RetentionStateCacheSpec``).
PAGED_CACHE_KINDS = {
    spec.kind: spec for spec in (
        PagedKVCacheSpec, LatentPagedCacheSpec, WindowPagedKVCacheSpec,
        StatePagedKVCacheSpec, RetentionStateCacheSpec)}


def _decode_mlp(c, x, p, me, n, n_o, interpret):
    """Decode-shaped MLP residual on ``m`` replicated rows (``m`` =
    per-group batch for decode, batch × chunk for the speculative verify
    step): dense SwiGLU, all-experts-einsum TP-MoE, or EP dispatch over
    the a2a (flat and hierarchical). Returns the updated residual."""
    with _scope("ffn"):
        m = x.shape[0]
        h = rmsnorm(x, p["mlp_norm"], c.norm_eps)
        if isinstance(c, EPMoETransformerConfig):
            # EP serving decode (the reference's headline inference
            # configuration — its LL a2a IS decode-shaped EP dispatch,
            # README.md:87): each PE takes its row slice of the group's
            # replicated activations, dispatches over the EP transport to
            # the expert owners, and the combined shard all-gathers back.
            # HIERARCHICAL (ep_outer set): sources are every (outer, inner)
            # PE — the group's slice divides again over the inner axis —
            # and the two-phase dispatch (node-dedup over the slow axis,
            # expert scatter on the fast one) spans the whole mesh: the
            # reference's 4-node × 8-GPU serving shape
            # (test_ep_moe_inference.py) with DCN as the outer axis.
            from triton_dist_tpu.models.tp_transformer import ep_moe_apply

            if m % n:
                raise ValueError(
                    f"EP serving decode shards its rows over the "
                    f"{c.axis!r} axis: per-group rows={m} must divide "
                    f"evenly over {n} PEs"
                )
            m_loc = m // n
            h_loc = jax.lax.dynamic_slice_in_dim(h, me * m_loc, m_loc, 0)
            # per-(src, dest) slab worst case: a src PE holds m_loc rows,
            # each with topk assignments (flat) / at most one deduplicated
            # copy per destination node (hierarchical)
            y_loc = ep_moe_apply(
                c, h_loc, p,
                c.ep_max_m or (m_loc if n_o > 1 else m_loc * c.topk),
                interpret=interpret,
            )
            y = jax.lax.all_gather(y_loc, c.axis, axis=0, tiled=True)
            return x + y.astype(x.dtype)
        if isinstance(c, MoETransformerConfig):
            # decode-shaped MoE: at serving row counts every expert's F-shard
            # weights stream from HBM regardless (weight-bound), so computing
            # ALL experts with dense einsums + a one-hot topk combine is the
            # TPU-shaped move — no gather/sort on a [m, H] activation.
            # (Prefill-sized token counts go through the fused AG-GroupGEMM
            # pipeline instead.)
            from triton_dist_tpu.ops.moe_utils import select_experts

            logits = h.astype(jnp.float32) @ p["router"].astype(jnp.float32)
            tw, ids = select_experts(logits, c.topk)           # [m, topk]
            # int8 expert banks (quantize_moe_serving_params) read the int8
            # stream in the einsums — HALF the HBM bytes this weight-bound
            # step is made of — and the per-(e, col) scales apply AFTER the
            # contraction (exact: the scale is constant over the contracted
            # dim) in the f32 stages that already exist (gelu input /
            # combine), costing zero precision.
            quant = "w_up_scale" in p
            w_up = p["w_up"].astype(h.dtype) if quant else p["w_up"]
            w_down = p["w_down"].astype(x.dtype) if quant else p["w_down"]
            hE = jnp.einsum("bh,ehf->ebf", h, w_up)            # [E, m, F/n]
            hE = hE.astype(jnp.float32)
            if quant:
                hE = hE * p["w_up_scale"]                      # [E,1,F] bcasts
            act = jax.nn.gelu(hE).astype(x.dtype)
            yE = jnp.einsum("ebf,efh->ebh", act, w_down)
            yE = yE.astype(jnp.float32)
            if quant:
                yE = yE * p["w_down_scale"]
            wE = (
                jnp.zeros((m, c.n_experts), jnp.float32)
                .at[jnp.arange(m)[:, None], ids]
                .add(tw)
            )
            y = jnp.einsum("be,ebh->bh", wE, yE)  # yE already f32
            return x + jax.lax.psum(y.astype(x.dtype), c.axis)
        with _scope("ffn/gate_up"):
            gu = h @ p["w_gate_up"]
        with _scope("ffn/act"):
            gate, up = unpack_gate_up(gu, c)
            act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
        with _scope("ffn/down"):
            return x + jax.lax.psum(act @ p["w_down"], c.axis)


def decode_step(
    cfg: TransformerConfig,
    params: dict,
    cache: dict,
    tokens: jax.Array,   # [b] int32 — this step's input token per sequence
    pos: jax.Array,      # [] or [b] int32 — position (scalar = lockstep
                         # batch; vector = ragged/continuous batching)
    *,
    spec: KVCacheSpec | PagedKVCacheSpec,
    fd_config: FlashDecodeConfig | None = None,
    interpret: Any = None,
) -> tuple[jax.Array, dict]:
    """One decode step (call inside ``jax.shard_map``): returns
    ``(logits [b, vocab], new_cache)``. The cache layout and attention
    kernel come from `spec` (contiguous or paged).

    HIERARCHICAL deployment (``cfg.ep_outer`` set, 2-axis mesh): each
    outer group runs DP attention over ITS batch slice (cache batch dim
    outer-sharded), the EP MLP's two-phase dispatch spans the whole mesh,
    and the returned logits are re-gathered to the replicated ``[b,
    vocab]`` layout — the host scheduling loop is deployment-agnostic."""
    if cfg.own_passes:
        # a layer-plan family: (logits, cache, its pass_counters)
        return cfg.decode_step(
            params, cache, tokens, pos, spec=spec, interpret=interpret)
    n_o, my_o = _outer_dims(cfg)
    if cfg.batch % n_o:
        raise ValueError(
            f"batch={cfg.batch} must divide over the {n_o} outer groups"
        )
    b_att = cfg.batch // n_o
    # everything below this line is per-outer-group: c.batch is the
    # group's batch slice (identical to cfg on the flat deployment)
    c = dataclasses.replace(cfg, batch=b_att) if n_o > 1 else cfg
    n = _axis_size(c.axis)
    me = jax.lax.axis_index(c.axis)
    g = c.n_q_heads // c.n_kv_heads
    d = c.head_dim
    # the tiled head all_gather below needs whole kv groups per PE
    assert c.n_kv_heads % n == 0, (c.n_kv_heads, n)

    pos_g = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (cfg.batch,))
    if n_o > 1:
        tokens = jax.lax.dynamic_slice_in_dim(tokens, my_o * b_att, b_att, 0)
        pos_b = jax.lax.dynamic_slice_in_dim(pos_g, my_o * b_att, b_att, 0)
    else:
        pos_b = pos_g
    with _scope("head"):
        x = params["embed"][tokens]  # [b_att, H] replicated per group
    with _scope("attn"):
        cache = spec.pre_step(c, cache, pos_b, me, n)

    for li, p in enumerate(params["layers"]):
        # --- attention (SP flash decode over the sharded cache) ---
        with _scope("attn"):
            h = rmsnorm(x, p["attn_norm"], c.norm_eps)
            with _scope("attn/qkv"):
                qkv_loc = h @ p["wqkv"]                # [b, qkv/n] local
                # head-complete qkv: PE-major concat == kv-group-major (the
                # groups are sharded contiguously), so a tiled all_gather
                # restores the global group order
                qkv = jax.lax.all_gather(qkv_loc, c.axis, axis=1, tiled=True)
            qkv = qkv.reshape(c.batch, c.n_kv_heads, g + 2, d)
            q = qkv[:, :, :g, :].reshape(c.batch, 1, c.n_q_heads, d)
            k_new = qkv[:, :, g, :].reshape(c.batch, 1, c.n_kv_heads, d)
            v_new = qkv[:, :, g + 1, :]                     # [b, h_kv, d]
            # per-sequence rotary position (ragged decode): vmap over batch
            rope_b = jax.vmap(lambda xi, pi: rope(xi, pi, c.rope_theta))
            q = rope_b(q, pos_b[:, None])[:, 0]             # [b, hq, d]
            k_new = rope_b(k_new, pos_b[:, None])[:, 0]     # [b, h_kv, d]

            attn, cache = spec.update_and_attend(
                c, cache, li, k_new, v_new, q, pos_b, me, n, fd_config,
                interpret,
            )                                                # [b, hq, d] f32
            # row-parallel out-proj on the LOCAL head slice + psum
            with _scope("attn/out"):
                attn_loc = jax.lax.dynamic_slice_in_dim(
                    attn, me * (c.n_q_heads // n), c.n_q_heads // n, axis=1
                ).reshape(c.batch, -1).astype(x.dtype)
                x = x + jax.lax.psum(attn_loc @ p["wo"], c.axis)

        # --- MLP (shared row-wise helper: decode feeds [b, H] rows, the
        # speculative verify step feeds [b*S, H]) ---
        x = _decode_mlp(c, x, p, me, n, n_o, interpret)

    with _scope("head"):
        x = rmsnorm(x, params["final_norm"], c.norm_eps)
        logits_loc = x @ params["lm_head"]                   # [b_att, V/n]
        logits = jax.lax.all_gather(logits_loc, c.axis, axis=1, tiled=True)
        if n_o > 1:
            # back to the replicated [b, V] layout the host loop expects:
            # outer groups are batch-major, so a leading-dim gather restores
            # global slot order
            logits = jax.lax.all_gather(
                logits, _outer_of(cfg), axis=0, tiled=True
            )
    return logits, cache


def generate(
    cfg: TransformerConfig,
    params: dict,
    prompt: jax.Array,   # [b, prompt_len] int32
    n_steps: int,
    mesh: Mesh,
    *,
    s_max: int,
    page_size: int | None = None,
    fd_config: FlashDecodeConfig | None = None,
    prefill: bool = False,
    interpret: Any = None,
) -> jax.Array:
    """Greedy generation: process the prompt (cache warmup), then decode
    ``n_steps`` new tokens. Returns ``[b, n_steps]``.

    ``prefill=True`` runs the prompt through ONE full transformer forward
    (``prefill_cache`` — MXU-rate prompt processing, the serving-system
    prefill/decode split) instead of token-by-token, on either cache
    layout; ``b*prompt_len`` must divide over the axis.

    ``page_size`` switches the KV cache to the paged layout (page pool +
    block table; runtime page allocation, or static page ranges when
    composed with ``prefill=True`` — the batch page write needs them) —
    the serving-shaped configuration; default is the contiguous
    sequence-sharded cache. On the paged path the page IS the attention
    block, so ``fd_config`` (whose ``block_s`` tiles the contiguous
    kernel) is not accepted alongside ``page_size``.

    Hierarchical EP configs (``cfg.ep_outer`` set) need `mesh` to carry
    both axes ``(ep_outer, axis)``: batch and KV cache shard over the
    outer axis (DP attention per node group), sequence over the inner,
    and the MoE layer spans every device via the two-phase dispatch —
    the reference's multi-node serving deployment
    (test_ep_moe_inference.py). The host-side contract (replicated
    prompt in, [b, n_steps] tokens out) is deployment-independent.

    Host-level entry; jits ONE fused program that lax.scans decode_step
    over all positions (prompt phase ignores the model's predictions)."""
    b, prompt_len = prompt.shape
    assert b == cfg.batch
    if prompt_len + n_steps > s_max:
        # past s_max no PE owns the position: the k/v append would silently
        # drop and attention would read stale cache — fail loudly instead
        raise ValueError(
            f"prompt_len={prompt_len} + n_steps={n_steps} exceeds the KV "
            f"cache capacity s_max={s_max}"
        )
    if page_size and fd_config is not None:
        raise ValueError(
            "fd_config tiles the contiguous kernel; with page_size the page "
            "is the block — pass one or the other"
        )
    spec = (
        # prefill batch-writes whole page ranges, which needs the STATIC
        # table; plain paged decode keeps the runtime bump allocator
        PagedKVCacheSpec(s_max, page_size, static_table=prefill)
        if page_size else KVCacheSpec(s_max)
    )
    n = mesh.shape[cfg.axis]
    n_o = _mesh_outer(cfg, mesh)
    if prefill:
        if (b * prompt_len) % (n * n_o):
            raise ValueError(
                f"prefill needs b*prompt_len={b * prompt_len} divisible "
                f"over {n * n_o} PEs (the prompt shard is the model's "
                f"token shard)"
            )
    cache = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        spec.init(cfg, n, n_o), spec.specs(cfg),
    )
    step = functools.partial(
        decode_step, cfg, spec=spec, fd_config=fd_config, interpret=interpret,
    )

    def run(params, cache, prompt):
        def body(carry, i):
            cache, tok = carry
            logits, cache = step(params, cache, tok, i)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # within the prompt, the next input is the given token
            tok = jnp.where(i + 1 < prompt_len, prompt[:, jnp.minimum(i + 1, prompt_len - 1)], nxt)
            return (cache, tok), nxt

        (_, _), outs = jax.lax.scan(
            body, (cache, prompt[:, 0]), jnp.arange(prompt_len + n_steps - 1)
        )
        return outs  # [prompt_len + n_steps - 1, b]

    def run_prefill(params, cache, prompt):
        # per-group batch in the forward cfg: the model processes its
        # outer group's sequences only (the prompt shard is outer-major)
        pcfg = dataclasses.replace(
            cfg, seq=prompt_len, batch=b // n_o
        )
        prompt_loc = _prompt_shard(prompt, b, prompt_len, cfg)
        cache, last = prefill_cache(
            pcfg, params, cache, prompt_loc, spec, s_max
        )
        tok0 = jnp.argmax(last, axis=-1).astype(jnp.int32)

        def body(carry, i):
            cache, tok = carry
            logits, cache = step(params, cache, tok, prompt_len + i)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (cache, nxt), nxt

        (_, _), outs = jax.lax.scan(
            body, (cache, tok0), jnp.arange(n_steps - 1)
        )
        return jnp.concatenate([tok0[None], outs], axis=0)  # [n_steps, b]

    cache_specs = spec.specs(cfg)
    pspecs = specs_for(cfg, params)
    from triton_dist_tpu.ops.common import jit_shard_map

    out = jit_shard_map(
        run_prefill if prefill else run, mesh,
        (pspecs, cache_specs, P(None, None)),
        P(None, None),
        # the scan length and prompt split are baked into the trace
        key=(
            "generate", cfg, spec, fd_config, prefill, prompt_len, n_steps,
            str(interpret),
        ),
    )(
        jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, pspecs,
        ),
        cache, prompt,
    )
    if prefill:
        # n_steps=0: the scan is empty but tok0 was still concatenated —
        # slice keeps the [b, n_steps] contract identical to the
        # token-by-token path
        return out.T[:, :n_steps]       # [b, n_steps]
    return out[prompt_len - 1 :].T      # [b, n_steps]


@dataclasses.dataclass
class Request:
    """One generation request for :class:`ContinuousBatcher`.

    Sampling: greedy by default; ``temperature > 0`` samples from the
    softmax (optionally truncated to the ``top_k`` most likely tokens),
    reproducibly per request via ``seed`` — each slot owns an
    independent RNG, so a request's tokens do not depend on what shares
    the batch with it.

    ``rng`` (serving-engine internal) overrides the seed-derived RNG with
    a LIVE ``np.random.Generator``: a prefix-replayed request (serving
    engine rebuild, docs/serving.md) continues sampling exactly where the
    interrupted generation stopped instead of replaying draws its
    already-generated prompt suffix consumed."""

    prompt: list            # token ids, len >= 1
    max_new_tokens: int
    eos_id: int | None = None
    temperature: float = 0.0
    top_k: int | None = None
    seed: int | None = None
    uid: Any = None
    rng: Any = None

    def dist(self, logits) -> np.ndarray:
        """The sampling distribution over the vocab for a [vocab] f32
        logit row (float64 probs) — the exact computation :meth:`sample`
        draws from, factored out so speculative rejection sampling
        (serving/speculative.py) accepts against the SAME distribution
        plain serving samples from. Requires ``temperature > 0``."""
        z = logits.astype(np.float64) / self.temperature
        if self.top_k is not None:
            k = min(self.top_k, len(z))   # validated >= 1 at submit()
            keep = np.argpartition(z, -k)[-k:]   # EXACTLY k indices:
            mask = np.full_like(z, -np.inf)      # ties beyond k drop, so
            mask[keep] = z[keep]                 # top_k=1 stays greedy
            z = mask
        z -= z.max()
        probs = np.exp(z)
        probs /= probs.sum()
        return probs

    def sample(self, logits, rng) -> int:
        """Pick the next token from a [vocab] f32 logit row."""
        if self.temperature <= 0.0:
            return int(logits.argmax())
        probs = self.dist(logits)
        return int(rng.choice(len(probs), p=probs))


@dataclasses.dataclass
class _Ahead:
    """A decode step sent before the round it belongs to (lookahead): its
    inputs and outputs on the device, the cache epoch it left, and the
    ``tok`` / ``pos`` the host must hold for the step to be that round's."""

    tok_d: Any
    pos_d: Any
    logits: Any
    stats: Any
    epoch: int
    tok: Any = None
    pos: Any = None


class StepsExhaustedError(RuntimeError):
    """``ContinuousBatcher.run`` spent its step budget with work still in
    flight. Completed generations are NOT lost (ISSUE 6 satellite): the
    error names both rosters, and the finished results stay drainable via
    :meth:`ContinuousBatcher.drain_finished` — a wedged straggler request
    can never take already-finished neighbors down with it."""

    def __init__(self, max_steps: int, pending_uids, finished_uids):
        self.max_steps = int(max_steps)
        self.pending_uids = tuple(pending_uids)
        self.finished_uids = tuple(finished_uids)
        super().__init__(
            f"run(max_steps={max_steps}) exhausted with requests still "
            f"in flight: {list(self.pending_uids)}; "
            f"{len(self.finished_uids)} finished generation(s) "
            f"{list(self.finished_uids)} are retained — collect them with "
            f"drain_finished()"
        )


class ContinuousBatcher:
    """Continuous batching over the ragged decode step (beyond the
    reference — its serving surface stops at the decode kernel; this is
    the vLLM-shaped scheduler the kernel exists for).

    TPU-idiomatic split: ONE jitted SPMD step (static shapes, per-slot
    position vector) does all device work; the host only picks each
    slot's next token (prompt feed vs argmax), admits queued requests
    into free slots, and collects finished sequences between steps. Slots
    run RAGGED — a new request starts at position 0 while its neighbors
    are mid-generation; eviction is just the slot going idle (its stale
    cache is masked by the per-sequence ``kv_lens = pos+1`` and fully
    overwritten on re-admission).

        batcher = ContinuousBatcher(cfg, params, mesh, s_max=256)
        batcher.submit(Request([1, 2, 3], max_new_tokens=8))
        done = batcher.run()     # or step() in a serving loop
    """

    def __init__(
        self,
        cfg: TransformerConfig,
        params: dict,
        mesh: Mesh,
        *,
        s_max: int,
        page_size: int | None = None,
        fd_config: FlashDecodeConfig | None = None,
        prefill: bool = False,
        prefill_chunk_tokens: int | None = None,
        interpret: Any = None,
        prefix_cache: Any = None,
        lookahead: bool = True,
    ):
        self.cfg, self.mesh, self.s_max = cfg, mesh, s_max
        # lookahead ("read back one step late"): a round whose tokens
        # cannot change the schedule dispatches the NEXT step, fed on the
        # device, before it pulls its own tokens, so the host's half of a
        # round runs under the device's next step. _ahead_mask decides it
        # round by round from what the batcher observes, and the plain
        # round is what it falls back to. False: the plain round always,
        # op for op (the tests' reference).
        self.lookahead = bool(lookahead)
        self._ahead: _Ahead | None = None
        self._pass_stats = None  # the last program's counters (device)
        self._epoch = 0          # bumped by every new cache or params
        self.rounds_ahead = 0    # rounds whose step the round before sent
        self.ahead_discarded = 0
        n = mesh.shape[cfg.axis]
        n_o = _mesh_outer(cfg, mesh)
        self._n_o = n_o
        if page_size and fd_config is not None:
            raise ValueError(
                "fd_config tiles the contiguous kernel; with page_size the "
                "page is the block — pass one or the other"
            )
        # radix prefix cache (ISSUE 12): host-managed block table over the
        # paged pool; None = the pre-cache batcher, byte for byte
        self._px = None
        self._px_dirty = False
        self.struck: list[tuple[Any, str]] = []
        # what the model's family declares: counters its programs return
        # beside their logits, and the kind of paged cache they read
        self._counters = cfg.pass_counters
        if cfg.cache_kind != "kv":
            # the kinds a layer-plan family brings: one-device, paged,
            # whole-prompt admission
            refused = [
                name for name, on in (
                    ("prefix_cache", prefix_cache is not None),
                    ("prefill_chunk_tokens (ranged prefill)",
                     prefill_chunk_tokens is not None),
                    ("fd_config", fd_config is not None),
                    ("a contiguous cache (no page_size)", not page_size),
                    ("a mesh wider than one device", n * n_o != 1),
                ) if on
            ]
            if refused:
                raise NotImplementedError(
                    f"the {cfg.cache_kind} cache kind "
                    f"({PAGED_CACHE_KINDS[cfg.cache_kind].__name__}) does "
                    f"not support: {', '.join(refused)}")
        if prefix_cache is not None:
            prefix_cache.validate()
            if not page_size:
                raise ValueError(
                    "prefix_cache shares refcounted chains of PHYSICAL "
                    "pages — it needs the paged cache (pass page_size)"
                )
            # prefill=True composes (ISSUE 18): admission routes through
            # the suffix-only RANGED prefill (prefill_cache_ranged), whose
            # per-row causal mask attends the trie hit's already-landed
            # pages — the attend-to-prior-cache form the masked prefill
            # lacked. Every prefill admission (hit AND miss) rides it, so
            # a hit is bit-identical to its own miss by range composition.
            if n_o > 1:
                raise ValueError(
                    "prefix_cache supports flat (1-axis) serving meshes: "
                    "a hierarchical deployment shards the page pool per "
                    "outer batch group, so one trie cannot name pages "
                    "across groups"
                )
        # prefill + paged composes: the batcher's tables are STATIC
        # (pre-assigned page ranges), exactly what the paged prefill's
        # batch page write needs
        self.prefill = prefill
        if prefill_chunk_tokens is not None:
            if not prefill:
                raise ValueError(
                    "prefill_chunk_tokens bounds the ranged chunks of "
                    "MXU-rate admission — it needs prefill=True (token-fed "
                    "admission already interleaves one token per step)"
                )
            if prefill_chunk_tokens < 1:
                raise ValueError("prefill_chunk_tokens must be >= 1")
        self.prefill_chunk_tokens = prefill_chunk_tokens
        self._fd_config = fd_config
        self._interpret = interpret
        self._prefill_progs: dict[int, Any] = {}
        self._ranged_progs: dict[int, Any] = {}
        # chunked-prefill state: slot -> next unfed prompt position. A
        # parked slot sits at pos = s_max (owned by no PE: its dummy
        # decode writes drop) while bounded ranged chunks land between
        # decode steps.
        self._chunk: dict[int, int] = {}
        # cumulative REAL prompt tokens run through the MXU prefill paths
        # (bucket prefill + ranged chunks; pad positions excluded)
        self.prefill_tokens_total = 0
        # cumulative bucket-prefill PASSES: requests admitted through them
        # over this = requests a pass (``_admit`` fills a pass's rows)
        self.prefill_passes_total = 0
        # cumulative ROWS those passes ran through the model: over passes x
        # ``batch x bucket`` it is the share of a whole-batch pass's work
        # still done (the ``rows`` of the pass's span)
        self.prefill_rows_total = 0
        # The admission's discipline (docs/serving.md). The dense family's
        # bucket pass takes the queued requests of one bucket together:
        # rows never mix across the batch dim, a request's rows go through
        # the program they would have run alone. On ONE device that program
        # walks the pass's members, a trip a member (``_prefill_prog``: it
        # costs its members' rows); across devices it runs EVERY slot's
        # rows under a mask, whatever the group. A layer-plan family's pass
        # runs the ONE slot its one-hot mask names
        # (``gated_experts.admitted_rows``), and an expert exchange with a
        # set capacity (``ep_max_m``) drops rows by who else is in the
        # pass: both admit one request a pass.
        self._walks_members = not cfg.own_passes and n * n_o == 1
        self._fills_rows = not cfg.own_passes and (
            getattr(cfg, "ep_max_m", None) is None)
        # cumulative prefill WORK in swept query×key token-pairs: a bulk
        # bucket pass computes the dense padded bucket×bucket rectangle
        # (every query row against every key slot, mask applied after),
        # while a ranged chunk sweeps only its chunk_bucket×hi strip —
        # the asymmetry the serving engine's virtual_prefill_work_s
        # charge model bills (ISSUE 18)
        self.prefill_work_total = 0
        self.spec = (
            PAGED_CACHE_KINDS[cfg.cache_kind](
                s_max, page_size, static_table=True,
                extra_pages=1 if prefix_cache is not None else 0,
            )
            if page_size else KVCacheSpec(s_max)
        )
        self.cache = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            self.spec.init(cfg, n, n_o), self.spec.specs(cfg),
        )
        self.params = params
        step = functools.partial(
            decode_step, cfg, spec=self.spec, fd_config=fd_config,
            interpret=interpret,
        )
        # cache donated: a serving-sized cache is gigabytes and the old
        # buffer is dead the moment the step returns — without donation
        # every token pays a second full cache allocation + copy.
        # jit_shard_map (keyed cache) rather than raw jax.jit: re-creating
        # a batcher with the same geometry must not recompile the step
        # (jit keys on callable identity, and `step` is rebuilt per
        # instance)
        from triton_dist_tpu.ops.common import jit_shard_map

        self._step = self._keep_stats(jit_shard_map(
            step, mesh,
            (
                specs_for(cfg, params), self.spec.specs(cfg), P(None),
                P(None),
            ),
            (P(None, None), self.spec.specs(cfg)) + self._stats_specs(),
            key=("batcher_step", cfg, self.spec, fd_config, str(interpret)),
            donate_argnums=(1,),
        ))
        b = cfg.batch
        self.pos = np.zeros(b, np.int32)        # next write position per slot
        self.tok = np.zeros(b, np.int32)        # next input token per slot
        self.slot_req: list[Request | None] = [None] * b
        self.slot_rng: list[Any] = [None] * b
        self.slot_fed: list[int] = [0] * b      # prompt tokens already fed
        self.slot_out: list[list] = [[] for _ in range(b)]
        self.queue: list[Request] = []
        self.finished: list[tuple[Any, list]] = []
        self.rounds = 0     # decode rounds run (the `round` of their span)
        # poisoned requests (ISSUE 8): slots whose logit row went
        # non-finite under an armed config.integrity — evicted, never
        # finished; drained by the serving engine for typed rejection
        self.poisoned: list[tuple[Any, list, str]] = []
        if prefix_cache is not None:
            from triton_dist_tpu.models.prefix_cache import PagePrefixCache

            self._px = PagePrefixCache(
                prefix_cache, n_slots=b, page=page_size,
                pps_local=(s_max // n) // page_size, n_pes=n,
            )
            self._px_dirty = True   # park every row on scratch before step 1

    def _stats_specs(self) -> tuple:
        """Out-spec of the counters a program returns after its usual
        outputs (``cfg.pass_counters``); nothing where it declares none."""
        return (P(None),) if self._counters else ()

    def _keep_stats(self, prog):
        """A program that declares counters returns them last: keep them
        aside (``_pass_stats``, still on the device) so that every caller
        sees the usual pair. No counters: the program itself."""
        if not self._counters:
            return prog

        def call(*args):
            *out, self._pass_stats = prog(*args)
            return out

        return call

    @property
    def cache(self):
        return self._cache

    @cache.setter
    def cache(self, tree) -> None:
        # a step sent ahead is good only for the cache it was given
        self._cache, self._epoch = tree, self._epoch + 1

    def _pull_next(self, sp, logits, tok_d=None, pos_d=None) -> np.ndarray:
        """The round's one pull: each slot's best token; the step's
        counters, where it declares any, ride the same transfer (one int32
        vector) and land on the round's span. Given the round's inputs as
        they are on the device, and where :meth:`_ahead_mask` allows it,
        the next round's step goes out BEFORE this blocks."""
        if not self._counters:
            packed = jnp.argmax(logits, axis=-1)
        else:
            packed = _argmax_with(logits, self._pass_stats)
        live = None if tok_d is None else self._ahead_mask()
        if live is not None:
            packed.copy_to_host_async()   # queued before the step ahead
            self._send_ahead(packed, tok_d, pos_d, live)
        packed = np.asarray(packed, np.int32)
        if live is not None:
            # what the host will hold after this round if it went as the
            # mask foresaw: the next round checks before it believes
            self._ahead.tok = np.where(live, packed[: len(live)], self.tok)
            self._ahead.pos = self.pos + live
            sp.set("ahead", 1)
        if self._counters:
            self._set_counters(sp, packed[len(self.tok):])
        return packed[: len(self.tok)]

    def _ahead_mask(self) -> np.ndarray | None:
        """The live slots, if the next step may go out before this round's
        tokens are seen: every live slot takes its own best token and one
        more after it (greedy, past its prompt, no stop token, not at its
        last token), and nothing else reads the logits or moves pages."""
        from triton_dist_tpu.resilience import integrity as _integrity

        if (not self.lookahead or self._px is not None or self._chunk
                or _integrity.output_checks_enabled()):
            return None
        live = np.zeros(len(self.tok), bool)
        for i, req in enumerate(self.slot_req):
            if req is None:
                continue
            if (req.temperature > 0.0 or req.eos_id is not None
                    or self.slot_fed[i] < len(req.prompt)
                    or len(self.slot_out[i]) + 2 > req.max_new_tokens):
                return None
            live[i] = True
        return live if live.any() else None

    def _send_ahead(self, packed, tok_d, pos_d, live) -> None:
        tok_d, pos_d = _advance_on(self.mesh)(packed, tok_d, pos_d, live)
        logits, self.cache = self._step(self.params, self.cache, tok_d, pos_d)
        self._ahead = _Ahead(tok_d, pos_d, logits, self._pass_stats,
                             self._epoch)

    def _round_inputs(self):
        """``(tok, pos, logits)`` of the round about to run: its inputs on
        the device and, where the round before sent this step ahead and
        neither a slot nor the cache nor the weights moved since, the
        logits it already has (else ``None``: the step is still to run, on
        the cache the step sent in vain wrote to; every cache kind's step
        is repeatable, ``PAGED_CACHE_KINDS``)."""
        a, self._ahead = self._ahead, None
        if a is not None:
            if (a.epoch == self._epoch and np.array_equal(a.tok, self.tok)
                    and np.array_equal(a.pos, self.pos)):
                self.rounds_ahead += 1
                self._pass_stats = a.stats
                return a.tok_d, a.pos_d, a.logits
            self.ahead_discarded += 1
        if self.lookahead:
            # placed as the advance program places its outputs, so that the
            # step and the advance each see ONE type of input
            rep = NamedSharding(self.mesh, P(None))
            # copies: a backend may alias a host array it is handed (the
            # CPU's does, where the array lies on a 64-byte boundary), the
            # rounds write tok / pos in place, and the advance program may
            # still be queued when this round's pull returns
            return (jax.device_put(self.tok.copy(), rep),
                    jax.device_put(self.pos.copy(), rep), None)
        return jnp.asarray(self.tok), jnp.asarray(self.pos), None

    def _pull_last(self, sp, last, slots: list) -> np.ndarray:
        """An admission's pull: the logit rows of ``slots`` (and the
        pass's counters, where it declares any, onto the admission's
        span). A pass of a group pulls ``last`` whole (``[batch, vocab]``,
        a member's row at its slot, whether the program walked the members
        or ran every slot's rows), a transfer: rows taken on the device
        would be a program a group size, compiled when a group first has
        it."""
        if self._counters:
            self._set_counters(sp, np.asarray(self._pass_stats))
        if self._fills_rows:
            return np.asarray(last, np.float32)[slots]
        return np.asarray(last[slots[0]], np.float32)[None]

    @property
    def params(self) -> dict:
        return self._params

    @params.setter
    def params(self, tree: dict) -> None:
        """The ONE place the batcher takes parameters (construction, and a
        later ``batcher.params = tree`` under the same compiled programs):
        every leaf placed in its ``specs_for`` sharding, and a ``w_gate_up``
        or ``wqkv`` that arrives in its old public 3-D layout (``[H, F, 2]``,
        ``[H, n_kv, (g+2)*d]``) re-laid ONCE into the stored matrix, shard
        by shard, a layer at a time. A tree born in the program's layout
        passes untouched."""
        cfg, mesh = self.cfg, self.mesh
        # SHIMS: only perfbench's adapter still builds the 3-D leaves; they
        # go when it builds the stored layout (ROADMAP C14). Each PE
        # re-lays its own units / kv groups: no traffic
        shims = (
            ("w_gate_up", "relaid", "bytes",
             lambda w: pack_gate_up(w[..., 0], w[..., 1], cfg)),
            ("wqkv", "relaid_wqkv", "bytes_wqkv",
             lambda w: w.reshape(cfg.hidden, -1)),
        )
        with _span("tdt.batcher.take_params") as sp:
            tree = jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                tree, specs_for(cfg, tree),
            )
            for name, n_relaid, n_bytes, fn in shims:
                relay = jax.jit(jax.shard_map(
                    fn, mesh=mesh, in_specs=P(None, cfg.axis, None),
                    out_specs=P(None, cfg.axis),
                ))
                # the MoE trees carry no w_gate_up
                old = [p for p in tree["layers"]
                       if name in p and p[name].ndim == 3]
                sp.set(n_relaid, len(old))
                sp.set(n_bytes, sum(p[name].nbytes for p in old))
                for p in old:
                    p[name] = relay(p[name])
            for name, value in cfg.param_bytes(tree).items():
                sp.set(name, value)
        self._params, self._epoch = tree, self._epoch + 1

    def validate_request(self, req: Request) -> None:
        """Admissibility checks (shared with the serving engine, which
        validates at ENQUEUE time so a bad request is rejected loudly
        instead of failing deep inside a serve loop)."""
        if not req.prompt:
            raise ValueError("empty prompt (need at least one token)")
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if req.top_k is not None and req.top_k < 1:
            raise ValueError("top_k must be >= 1 (or None)")
        if len(req.prompt) + req.max_new_tokens > self.s_max:
            raise ValueError(
                f"prompt {len(req.prompt)} + max_new {req.max_new_tokens} "
                f"exceeds s_max={self.s_max}"
            )

    def submit(self, req: Request) -> None:
        self.validate_request(req)
        self.queue.append(req)

    def _prefill_prog(self, bucket: int):
        """Jitted prefill program for one padded prompt length (compiled
        once per bucket, whatever the group: buckets are powers of two so
        a serving mix of lengths stays at a handful of compilations).
        Across devices, ``fn(params, cache, prompt, mask, pick)``: every
        slot's rows in one masked pass. On one device,
        :func:`_member_walk`'s ``fn(params, cache, prompt, slots, pick,
        m)``: the pass's ``m`` members, a trip each."""
        if bucket in self._prefill_progs:
            return self._prefill_progs[bucket]
        cfg, mesh, spec, s_max = self.cfg, self.mesh, self.spec, self.s_max
        b = cfg.batch

        if self._walks_members:
            fn = _member_walk(cfg, spec, s_max, bucket)
            inputs = (P(None, None), P(None), P(None), P())
        else:
            pcfg = dataclasses.replace(cfg, seq=bucket, batch=b // self._n_o)
            inputs = (P(None, None), P(None), P(None))

            def fn(params, cache, prompt, mask, pick):
                prompt_loc = _prompt_shard(prompt, b, bucket, cfg)
                return prefill_cache(
                    pcfg, params, cache, prompt_loc, spec, s_max,
                    slot_mask=mask, pick=pick,
                )

        from triton_dist_tpu.ops.common import jit_shard_map

        prog = self._keep_stats(jit_shard_map(
            fn, mesh,
            (specs_for(cfg, self.params), spec.specs(cfg)) + inputs,
            (spec.specs(cfg), P(None, None)) + self._stats_specs(),
            key=("batcher_prefill", cfg, spec, s_max, bucket),
            donate_argnums=(1,),  # see self._step: the old cache is dead
        ))
        self._prefill_progs[bucket] = prog
        return prog

    def _bucket(self, length: int) -> int:
        n = self.mesh.shape[self.cfg.axis] * self._n_o
        bucket = 1
        while bucket < self.s_max and (
            bucket < length or (self.cfg.batch * bucket) % n
        ):
            bucket *= 2
        bucket = min(bucket, self.s_max)
        if bucket < length or (self.cfg.batch * bucket) % n:
            # e.g. an axis size with an odd prime factor that divides
            # neither batch nor any power-of-two bucket — no valid shard
            raise ValueError(
                f"no prefill bucket <= s_max={self.s_max} fits prompt "
                f"length {length} with b*bucket divisible over {n} PEs"
            )
        return bucket

    def _ranged_prog(self, bucket: int):
        """Jitted suffix-only ranged-prefill program for one padded chunk
        length (``prefill_cache_ranged`` — the verify forward): tokens
        ``[b, bucket]`` at per-slot start positions ``pos0``, attending
        already-landed KV. Non-target rows park at ``pos0 = s_max`` —
        owned by no PE, so their writes drop and their logits are
        ignored. No ``b*bucket`` divisibility constraint: the ranged
        forward gathers features, not tokens."""
        if bucket in self._ranged_progs:
            return self._ranged_progs[bucket]
        cfg, spec = self.cfg, self.spec

        def fn(params, cache, tokens, pos0):
            return prefill_cache_ranged(
                cfg, params, cache, tokens, pos0, spec=spec,
                fd_config=self._fd_config, interpret=self._interpret,
            )

        from triton_dist_tpu.ops.common import jit_shard_map

        prog = jit_shard_map(
            fn, self.mesh,
            (
                specs_for(cfg, self.params), spec.specs(cfg), P(None, None),
                P(None),
            ),
            (P(None, None, None), spec.specs(cfg)),
            key=(
                "batcher_ranged", cfg, spec, self._fd_config, bucket,
                str(self._interpret),
            ),
            donate_argnums=(1,),  # see self._step: the old cache is dead
        )
        self._ranged_progs[bucket] = prog
        return prog

    def _push_px_table(self) -> None:
        """Push the host-managed block table (admissions repointed rows at
        shared chains / fresh private pages, releases parked rows on
        scratch) — the only device-visible artifact of the whole
        prefix-cache layer. Must land before any device program whose
        paged scatter or attention reads the table."""
        self.cache = dict(
            self.cache,
            block_table=jax.device_put(
                jnp.asarray(self._px.table),
                NamedSharding(
                    self.mesh, self.spec.specs(self.cfg)["block_table"]
                ),
            ),
        )
        self._px_dirty = False

    def _ranged_pass(self, i: int, req: Request, lo: int, hi: int) -> None:
        """One suffix-only ranged-prefill pass for slot ``i`` over prompt
        positions ``[lo, hi)``. Pads to a power-of-two chunk bucket
        (compiled once per bucket, like ``_prefill_prog``); pad rows of
        the target slot write junk KV at positions ``>= hi``, which the
        next chunk / decode step overwrites before ``kv_lens`` ever
        exposes it (the documented dirty-cache discipline). When ``hi``
        reaches the prompt end, completes admission exactly like
        ``_admit_prefill`` — the first token samples from position
        ``L-1``'s row."""
        L = len(req.prompt)
        S = hi - lo
        bucket = 1
        while bucket < S:
            bucket *= 2
        with _span("tdt.batcher.ranged_pass", uid=str(req.uid), slot=i,
                   lo=lo, hi=hi, bucket=bucket):
            tokens = np.zeros((self.cfg.batch, bucket), np.int32)
            tokens[i, :S] = req.prompt[lo:hi]
            pos0 = np.full(self.cfg.batch, self.s_max, np.int32)  # parked rows
            pos0[i] = lo
            if self._px is not None and self._px_dirty:
                # the paged scatter and attention read the device table: an
                # acquire/publish that just repointed this slot's row must
                # land first
                self._push_px_table()
            logits, self.cache = self._ranged_prog(bucket)(
                self.params, self.cache, jnp.asarray(tokens), jnp.asarray(pos0)
            )
            self.prefill_tokens_total += S
            self.prefill_work_total += bucket * hi
            if self._px is not None:
                # publish-on-completion, batch form: every prompt page fully
                # covered by [0, hi) enters the trie now (its last position's
                # KV just landed) — the same gate the decode loop applies one
                # page at a time
                pg = self._px.page
                while True:
                    g = self._px.next_publish(i)
                    if (g + 1) * pg > hi or (g + 1) * pg > L:
                        break
                    if self._px.publish(i, g, req.prompt[g * pg:(g + 1) * pg]):
                        self._px_dirty = True
            if hi < L:
                return  # mid-prompt chunk: no token to sample yet
            self._first_token(i, req, np.asarray(logits[i, S - 1], np.float32))

    def _first_token(self, i: int, req: Request, last_i: np.ndarray) -> None:
        """What an MXU-rate admission ends with: slot ``i``'s first
        generated token, sampled from the logits of its prompt's last
        position, or the slot quarantined before a token exists."""
        from triton_dist_tpu.resilience import integrity as _integrity

        if _integrity.output_checks_enabled() and not np.isfinite(last_i).all():
            self._poison_slot(i, "non-finite prefill logits")
            return
        t0 = req.sample(last_i, self.slot_rng[i])
        self.slot_fed[i] = len(req.prompt)
        self.slot_out[i] = [t0]
        self.tok[i] = t0
        self.pos[i] = len(req.prompt)
        if len(self.slot_out[i]) >= req.max_new_tokens or (
            req.eos_id is not None and t0 == req.eos_id
        ):
            self.finished.append((req.uid, self.slot_out[i]))
            self._vacate(i)
            if self._px is not None:
                self._px.release(i)
                self._px_dirty = True

    def _admit_ranged(self, i: int, req: Request, lo: int) -> None:
        """Ranged admission: feed prompt positions ``[lo, L)`` — the
        divergent suffix past a trie hit, or the whole prompt — through
        the suffix-only ranged prefill: one pass, or parked into bounded
        chunks when ``prefill_chunk_tokens`` is armed and the suffix is
        longer (the chunks land between decode steps, so a long prompt
        cannot stall a decode-heavy batch)."""
        ct = self.prefill_chunk_tokens
        if ct is not None and len(req.prompt) - lo > ct:
            self._chunk[i] = lo
            self.pos[i] = self.s_max      # parked: owned by no PE
            self.tok[i] = 0
            self.slot_fed[i] = 0
            return
        self._ranged_pass(i, req, lo, len(req.prompt))

    def _prefill_inputs(self, slots: list, reqs: list, bucket: int) -> tuple:
        """The pass's inputs after params and cache, uploaded: the prompts
        of ``reqs`` padded to their bucket and the pick rows, with the
        group said the way ``_prefill_prog``'s program takes it. Across
        devices: each prompt in its slot's row, and the mask of ``slots``.
        On one device the group is DATA: the prompts packed in the first
        rows, ``slots`` and ``pick`` by member, and the members' count."""
        with _span("tdt.batcher.admit_prefill.build"):
            b = self.cfg.batch
            prompt = np.zeros((b, bucket), np.int32)
            # pad positions write junk KV beyond L-1, but decode overwrites
            # each position before kv_lens ever exposes it; the first
            # generated token comes from position L-1's logits (pick)
            pick = np.zeros(b, np.int32)
            rows = range(len(slots)) if self._walks_members else slots
            for r, req in zip(rows, reqs):
                prompt[r, :len(req.prompt)] = req.prompt
                pick[r] = len(req.prompt) - 1
            if self._walks_members:
                by_member = np.zeros(b, np.int32)
                by_member[:len(slots)] = slots
                return (jnp.asarray(prompt), jnp.asarray(by_member),
                        jnp.asarray(pick), jnp.asarray(np.int32(len(slots))))
            mask = np.zeros(b, bool)
            mask[slots] = True
            return jnp.asarray(prompt), jnp.asarray(mask), jnp.asarray(pick)

    def _admit_prefill(self, i, req) -> None:
        """MXU-rate admission: one full-forward pass writes the
        whole prompt's KV and yields the first generated token, for slot
        ``i``'s request ``req`` or, where both are lists (``_admit``: a
        family whose pass fills its rows), for every member of a group of
        one bucket. On one device the pass costs its members' rows (a trip
        a member); across devices it costs ``batch x bucket`` rows with one
        member or ``batch`` of them (``rows`` on the span)."""
        slots, reqs = ([i], [req]) if isinstance(req, Request) else (i, req)
        L = max(len(r.prompt) for r in reqs)
        bucket = self._bucket(L)
        with self._pass_span(slots, reqs, L, bucket) as sp:
            self._set_prefill_blocks(sp, L, bucket)
            args = self._prefill_inputs(slots, reqs, bucket)
            with _span("tdt.batcher.admit_prefill.dispatch"):
                self.cache, last = self._prefill_prog(bucket)(
                    self.params, self.cache, *args)
            self._count_pass(sp, len(reqs), bucket)
            with _span("tdt.batcher.admit_prefill.pull"):
                rows = self._pull_last(sp, last, slots)
            # a member at a time in slot order: its own finite check, its
            # slot's RNG, its instant finish
            for i, req, last_i in zip(slots, reqs, rows):
                self.prefill_tokens_total += len(req.prompt)
                self._first_token(i, req, last_i)

    def _count_pass(self, sp, admitted: int, bucket: int) -> None:
        """One bucket-prefill pass more on the cumulative counters, and on
        its span the ``rows`` it ran through the model: its members' on
        one device and for a layer-plan family, every slot's where the
        dense family's pass runs masked (across devices)."""
        self.prefill_passes_total += 1
        self.prefill_work_total += bucket * bucket
        whole = not (self._walks_members or self.cfg.own_passes)
        rows = (self.cfg.batch if whole else admitted) * bucket
        self.prefill_rows_total += rows
        sp.set("rows", rows)

    def _pass_span(self, slots: list, reqs: list, L: int, bucket: int):
        """The span of one bucket-prefill pass: ``admitted`` members, their
        uids (``|`` between them: a profiler annotation ends a value at a
        comma), the first one's slot, the longest prompt."""
        return _span("tdt.batcher.admit_prefill",
                     uid="|".join(str(r.uid) for r in reqs), slot=slots[0],
                     prompt_len=L, bucket=bucket, admitted=len(reqs))

    def _seat(self, i: int, req: Request) -> bool:
        """Hand free slot ``i`` to ``req`` and admit it, unless it takes
        the whole-batch bucket prefill (True: ``_admit`` runs that pass)."""
        self.slot_req[i] = req
        self.slot_out[i] = []
        # a live generator (prefix replay) continues sampling mid-stream;
        # otherwise each admission re-derives the slot RNG from the request
        # seed (the documented neighbor-independent sampling guarantee)
        self.slot_rng[i] = (
            req.rng if req.rng is not None
            else np.random.default_rng(req.seed)
        )
        if self.prefill and len(req.prompt) > 1:
            if self._px is not None:
                # px × fast prefill (ISSUE 18): the trie hit's pages are
                # the ranged pass's already-landed prior — only the
                # divergent suffix runs. The MISS path rides the same
                # ranged entry from lo=0, so hit ≡ miss bit for bit (range
                # composition), and both ≡ the token-fed px engine
                # (decode-chain equivalence).
                n_hit = self._px.acquire(i, req.prompt, req.max_new_tokens)
                self._px_dirty = True
                self._admit_ranged(i, req, n_hit)
            elif (self.prefill_chunk_tokens is not None
                  and len(req.prompt) > self.prefill_chunk_tokens):
                # chunked-prefill scheduling: park the slot; bounded ranged
                # chunks land between decode steps. Shorter prompts keep
                # the legacy bucket prefill byte for byte (the
                # armed-but-untriggered pin).
                self._admit_ranged(i, req, 0)
            else:
                return True
        elif self._px is not None:
            # longest-prefix match (ISSUE 12): every fully shared page is
            # skipped — the slot starts its feed at the first token whose
            # KV the trie does not already hold; the divergent page onward
            # is freshly claimed (CoW), so shared pages are never written
            n_hit = self._px.acquire(i, req.prompt, req.max_new_tokens)
            self._px_dirty = True
            self.pos[i] = n_hit
            self.tok[i] = req.prompt[n_hit]
            self.slot_fed[i] = n_hit + 1
        else:
            self.pos[i] = 0
            self.tok[i] = req.prompt[0]
            self.slot_fed[i] = 1
        return False

    def _by_bucket(self, waiting: list):
        """``(slot, request)`` pairs as ``(slots, requests)`` a prompt
        bucket, the buckets in the order of their first members."""
        groups: dict[int, tuple[list, list]] = {}
        for i, req in waiting:
            slots, reqs = groups.setdefault(
                self._bucket(len(req.prompt)), ([], []))
            slots.append(i)
            reqs.append(req)
        return groups.values()

    def _admit(self) -> None:
        if not self.queue:
            return
        with _span("tdt.batcher.admit", queued=len(self.queue)) as sp:
            # a prefill pass can free a slot it just filled (max_new_tokens=1
            # or instant EOS), so one sweep would leave that slot empty until
            # the next step even with queued work — sweep again while a slot
            # is free and a request is queued
            n_admitted = 0
            while self.queue and None in self.slot_req:
                waiting = []
                for i, r in enumerate(self.slot_req):
                    if r is None and self.queue:
                        req = self.queue.pop(0)
                        n_admitted += 1
                        if not self._seat(i, req):
                            continue
                        if self._fills_rows:
                            # the pass runs every slot's rows: it waits
                            # for the sweep's other requests of its bucket
                            waiting.append((i, req))
                        else:
                            self._admit_prefill(i, req)
                for group in self._by_bucket(waiting):
                    self._admit_prefill(*group)
            sp.set("admitted", n_admitted)

    @property
    def idle(self) -> bool:
        return not self.queue and all(r is None for r in self.slot_req)

    @property
    def n_free_slots(self) -> int:
        """Slots a new submission could claim without evicting anything:
        idle slots minus what the admission queue will absorb first."""
        free = sum(r is None for r in self.slot_req)
        return max(0, free - len(self.queue))

    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self.slot_req)

    @property
    def prefill_bucket_count(self) -> int:
        """Compiled masked-prefill programs held by the power-of-two
        bucket cache — the recompilation-storm observability gauge
        (ISSUE 6 satellite): a mixed-length workload must keep this within
        the log2 bucket bound, never one program per distinct length."""
        return len(self._prefill_progs)

    def drain_finished(self) -> list[tuple[Any, list]]:
        """Hand over (and clear) every finished ``(uid, tokens)`` — the
        public drain the serving engine uses between steps, and the reason
        a wedged straggler (``StepsExhaustedError``) can never lose
        completed neighbors."""
        out, self.finished = self.finished, []
        return out

    def drain_poisoned(self) -> list[tuple[Any, list, str]]:
        """Hand over (and clear) every poisoned ``(uid, tokens_before,
        reason)`` (ISSUE 8 per-request quarantine): requests whose logit
        row went non-finite under an armed ``config.integrity``. They were
        EVICTED, not finished — the serving engine typed-rejects them; a
        direct batcher user collects them here."""
        out, self.poisoned = self.poisoned, []
        return out

    def drain_struck(self) -> list[tuple[Any, str]]:
        """Hand over (and clear) every ``(uid, reason)`` evicted by a
        poisoned-shared-page strike (ISSUE 12): these requests read a page
        of the poisoned slot's chain, so their cache state is suspect —
        they were evicted WITHOUT a terminal state and must be
        resubmitted for a cold re-prefill (the serving engine restarts
        them from the original prompt, discarding tokens generated over
        the struck pages; a direct batcher user must resubmit them
        itself or they are lost)."""
        out, self.struck = self.struck, []
        return out

    def prefix_cache_stats(self) -> dict | None:
        """The prefix cache's counters + gauges (models/prefix_cache.py),
        or None when disarmed."""
        return None if self._px is None else self._px.stats()

    @property
    def prefix_cache(self):
        """The live :class:`~triton_dist_tpu.models.prefix_cache.
        PagePrefixCache` (tests / fault harnesses), or None."""
        return self._px

    def _vacate(self, i: int) -> None:
        """Slot ``i``'s request has left. The step advances every slot
        and is not told which are live, so the slot goes back to where a
        slot never used stands, token 0 at position 0: its dummy steps
        read one row and not the context the request held, every vacant
        row routes to the same experts, and what they write the next
        admission overwrites (with a prefix cache the row is on scratch
        by then)."""
        self.slot_req[i] = None
        self.tok[i] = 0
        self.pos[i] = 0

    def _poison_slot(self, i: int, reason: str) -> None:
        """Evict slot ``i``'s request as poisoned. Containment argument:
        decode rows never mix across the batch dim (attention is
        per-sequence, MLPs row-wise, collectives reduce feature/shard
        dims), so a NaN row is that request's alone; its garbage cache
        rows are masked by per-sequence ``kv_lens`` on eviction and fully
        overwritten on the slot's next admission — the documented
        eviction semantics, nothing new to clean."""
        from triton_dist_tpu.resilience import health

        req = self.slot_req[i]
        self.poisoned.append((req.uid, list(self.slot_out[i]), reason))
        self._vacate(i)
        self._chunk.pop(i, None)
        health.record_poisoned_request("continuous_batcher", req.uid, reason)
        if self._px is not None:
            # poisoned SHARED pages strike every reader (ISSUE 12): the
            # poisoned slot's whole chain is detached from the trie (no
            # future match can serve a possibly-corrupt page), and every
            # other slot reading any struck page is evicted for a cold
            # re-prefill — corrupt KV is never served, not even once more
            readers = self._px.release(i, strike=True)
            for j in readers:
                r = self.slot_req[j]
                self._px.release(j)
                self._vacate(j)
                self._chunk.pop(j, None)
                self.struck.append((
                    r.uid, f"shared prefix page struck: {reason}"
                ))
                health.record_prefix_strike(
                    "continuous_batcher", r.uid, reason
                )
            self._px_dirty = True

    def export_in_flight(self) -> tuple[list[tuple[Request, list, Any]],
                                        list[Request]]:
        """Non-destructive snapshot for prefix replay (serving-engine
        rebuild on a shrunk/regrown mesh): ``(active, queued)`` where
        ``active`` is ``[(request, tokens_generated_so_far, live_rng)]``
        per occupied slot in slot order and ``queued`` is the untouched
        admission queue. The live RNG rides along so a sampled request's
        continuation draws stay byte-identical after replay."""
        active = [
            (r, list(self.slot_out[i]), self.slot_rng[i])
            for i, r in enumerate(self.slot_req)
            if r is not None
        ]
        return active, list(self.queue)

    def step(self) -> None:
        """One ragged decode step for every slot + host scheduling."""
        self._admit()
        if self.idle:
            return
        self._chunk_pass()
        self._decode_round()

    def _chunk_pass(self) -> None:
        # chunked-prefill scheduling (ISSUE 18): each parked slot gets ONE
        # bounded ranged chunk per step, interleaved with the decode step
        # that follows — decode rows never mix across the batch dim, so
        # the chunk passes leave every neighbor's stream byte-identical
        for i in sorted(self._chunk):
            req = self.slot_req[i]
            if req is None:           # struck/poisoned mid-flight
                self._chunk.pop(i, None)
                continue
            lo = self._chunk[i]
            hi = min(lo + self.prefill_chunk_tokens, len(req.prompt))
            if hi < len(req.prompt):
                self._chunk[i] = hi
            else:
                del self._chunk[i]    # final chunk: _ranged_pass admits
            self._ranged_pass(i, req, lo, hi)

    def _publish_step(self, i: int, req: Request) -> None:
        # publish-on-completion: a prompt page enters the trie only
        # once its last position's KV is written (a reader admitted
        # earlier would attend to unwritten pages); generated
        # positions extend the slot's PRIVATE chain only, so pages
        # touching them are never published
        p, pg = int(self.pos[i]), self._px.page
        if p % pg == 0:
            g = p // pg - 1
            if (g == self._px.next_publish(i)
                    and (g + 1) * pg <= len(req.prompt)):
                if self._px.publish(
                    i, g, req.prompt[g * pg:(g + 1) * pg]
                ):
                    self._px_dirty = True

    def _decode_round(self) -> None:
        """The single-token decode half of :meth:`step` (the speculative
        serving batcher replaces this with a draft+verify round —
        serving/speculative.py — and falls back here when no slot is in
        a speculation-eligible state)."""
        self.rounds += 1
        with _span("tdt.batcher.decode_round", round=self.rounds) as sp:
            with _span("tdt.batcher.decode_round.upload"):
                if self._px is not None and self._px_dirty:
                    self._push_px_table()
                tok_d, pos_d, logits = self._round_inputs()
            with _span("tdt.batcher.decode_round.dispatch"):
                if logits is None:
                    logits, self.cache = self._step(
                        self.params, self.cache, tok_d, pos_d,
                    )
            # per-request poison detection (ISSUE 8): one [b]-bool
            # transfer when config.integrity arms the output checks — a
            # non-finite logit row quarantines exactly that slot's request
            # below
            from triton_dist_tpu.resilience import integrity as _integrity

            with _span("tdt.batcher.decode_round.pull"):
                row_ok = (
                    np.asarray(jnp.all(jnp.isfinite(logits), axis=-1))
                    if _integrity.output_checks_enabled() else None
                )
                # greedy slots need only the [b]-int argmax; the full
                # [b, vocab] row transfer (~vocab x 4 bytes/slot over a
                # possibly-remote link) is paid only when some active
                # request actually samples
                nxt = self._pull_next(sp, logits, tok_d, pos_d)
            logits_h = None
            if any(
                r is not None and r.temperature > 0.0
                and self.slot_fed[i] >= len(r.prompt)  # past prompt feed
                for i, r in enumerate(self.slot_req)
            ):
                with _span("tdt.batcher.decode_round.sample"):
                    logits_h = np.asarray(logits, np.float32)
            self._take_round(sp, nxt, logits_h, row_ok)

    def _set_counters(self, sp, values) -> None:
        for name, value in zip(self._counters, values):
            sp.set(name, int(value))

    def _set_prefill_blocks(self, sp, length: int, bucket: int) -> None:
        """``prefill_blocks_live`` / ``prefill_blocks_square`` of an
        admission whose family attends through the tiled prefill kernel
        (``cfg.prefill_blocks``: the key blocks the prompt's band holds
        against the causal square of its bucket; nothing for a bucket
        whose scores are materialized). Counted only while something
        records the span."""
        count = getattr(self.cfg, "prefill_blocks", None)
        if sp is NULL_SPAN or count is None:
            return
        blocks = count(length, bucket)
        if blocks is not None:
            sp.set("prefill_blocks_live", blocks[0])
            sp.set("prefill_blocks_square", blocks[1])

    def _set_page_counts(self, sp) -> None:
        """``kv_pages_live`` / ``kv_pages_table`` of the step this round
        took: the pages its lengths expose against what its tables hold,
        from the positions the step was given (a vacant slot stands at
        0; a parked slot sits at ``s_max``). Counted only while
        something records the span."""
        if sp is not NULL_SPAN and isinstance(
                self.spec, PagedKVCacheSpec):
            live, table = self.spec.pages_walked(
                self.cfg, np.clip(self.pos + 1, 0, self.spec.s_max))
            sp.set("kv_pages_live", live)
            sp.set("kv_pages_table", table)

    def _take_round(self, sp, nxt, logits_h, row_ok) -> None:
        """The host half of a decode round: every live slot takes its
        token (or feeds its next prompt token), finished requests leave
        their slots, and the round's span gets its counts."""
        self._set_page_counts(sp)
        live = feeding = tokens = finished = 0
        for i, req in enumerate(self.slot_req):
            if req is None:
                continue  # idle slot decoded a dummy token; ignore
            live += 1
            if i in self._chunk:
                # parked mid-chunk: the slot's decode row was a dummy
                # (pos = s_max — no PE owns it, nothing was written) and
                # its garbage logits carry no health signal; its position
                # advances via the ranged chunks, not here
                feeding += 1
                continue
            if row_ok is not None and not row_ok[i]:
                # poison quarantine: THIS request is evicted and typed-
                # rejected; its neighbors' rows are untouched (see
                # _poison_slot) and keep streaming byte-identically
                self._poison_slot(i, "non-finite logits")
                continue
            if self.slot_fed[i] < len(req.prompt):
                # still feeding the prompt: the model's prediction is
                # ignored, the next input is the given token
                self.tok[i] = req.prompt[self.slot_fed[i]]
                self.slot_fed[i] += 1
                feeding += 1
            else:
                t = (
                    int(nxt[i]) if req.temperature <= 0.0
                    else req.sample(logits_h[i], self.slot_rng[i])
                )
                self.slot_out[i].append(t)
                tokens += 1
                self.tok[i] = t
                done = len(self.slot_out[i]) >= req.max_new_tokens or (
                    req.eos_id is not None and t == req.eos_id
                )
                if done:
                    finished += 1
                    self.finished.append((req.uid, self.slot_out[i]))
                    self._vacate(i)
                    if self._px is not None:
                        self._px.release(i)
                        self._px_dirty = True
                    continue
            self.pos[i] += 1
            if self._px is not None:
                self._publish_step(i, req)
        sp.set("live", live)
        sp.set("feeding", feeding)
        sp.set("tokens", tokens)
        sp.set("finished", finished)

    def run(self, max_steps: int = 100000) -> list[tuple[Any, list]]:
        """Drive until every queued request finishes; returns
        ``[(uid, generated_tokens), ...]`` in completion order. Raises
        :class:`StepsExhaustedError` if `max_steps` elapse with work still
        in flight — a partial return would be indistinguishable from
        completion, but the finished generations stay drainable
        (``drain_finished``) and the error carries both uid rosters."""
        for _ in range(max_steps):
            if self.idle:
                break
            self.step()
        if not self.idle:
            pending = [r.uid for r in self.slot_req if r is not None] + [
                r.uid for r in self.queue
            ]
            raise StepsExhaustedError(
                max_steps, pending, [uid for uid, _ in self.finished]
            )
        return self.drain_finished()


@functools.lru_cache(maxsize=None)
def _advance_on(mesh: Mesh):
    """A round's inputs from the round before, on the device: a live slot
    takes its best token and the next position, an idle one stays.
    Shardings pinned (replicated over ``mesh``): the program's own outputs
    and a fresh upload are one signature, so it compiles once."""
    rep = NamedSharding(mesh, P(None))

    def advance(packed, tok, pos, live):
        return (jnp.where(live, packed[: tok.shape[0]], tok),
                pos + live.astype(pos.dtype))

    return jax.jit(advance, in_shardings=(rep,) * 4, out_shardings=(rep, rep))


@jax.jit
def _argmax_with(logits, stats):
    """``[argmax of each row | stats]`` as one int32 vector."""
    return jnp.concatenate(
        [jnp.argmax(logits, axis=-1).astype(jnp.int32), stats])


def _prompt_shard(prompt, b, length, cfg):
    """This PE's contiguous slice of the b-major flattened prompt — the
    model's token sharding (shared by generate's prefill and the
    batcher's admission program). Hierarchical deployments shard over
    BOTH axes outer-major: outer group ``o``'s PEs cover exactly
    sequences ``[o*b_att, (o+1)*b_att)`` — the group's own slots."""
    n = _axis_size(cfg.axis)
    me = jax.lax.axis_index(cfg.axis)
    n_o, my_o = _outer_dims(cfg)
    m_loc = b * length // (n * n_o)
    r = my_o * n + me
    return jax.lax.dynamic_slice_in_dim(
        prompt.reshape(-1), r * m_loc, m_loc, 0
    )


def _member_walk(cfg, spec, s_max: int, bucket: int):
    """The dense family's bucket prefill on ONE device (call inside
    shard_map), one program whatever the group: ``fn(params, cache, prompt
    [batch, bucket], slots [batch], pick [batch], m)`` is a ``fori_loop``
    over the pass's ``m`` members, a trip count that is DATA. Trip ``j``
    runs member ``j``'s ``[1, bucket]`` rows (``prompt[j]``: the members
    are packed first) through :func:`prefill_cache`, which writes cache
    slot ``slots[j]`` and no other, and puts its picked logit row into
    ``last[slots[j]]`` (``[batch, vocab]`` float32; rows of no member stay
    zero). The cache is the loop's carry, updated in place. Named ``fn``
    like the masked program: a trace finds both as ``jit_fn``."""
    pcfg = dataclasses.replace(cfg, seq=bucket, batch=1)

    def fn(params, cache, prompt, slots, pick, m):
        def one(j, carry):
            cache, last = carry
            cache, row = prefill_cache(
                pcfg, params, cache, prompt[j], spec, s_max,
                pick=pick[j][None], slot=slots[j],
            )
            return cache, jax.lax.dynamic_update_slice(
                last, row.astype(last.dtype), (slots[j], 0))

        last = jnp.zeros((cfg.batch, cfg.vocab), jnp.float32)
        return jax.lax.fori_loop(0, m, one, (cache, last))

    return fn


def prefill_cache(
    cfg, params, cache, prompt_loc, spec, s_max, slot_mask=None, pick=None,
    slot=None,
):
    """Bulk prefill (call inside shard_map): run the full TP transformer
    forward over the flattened prompt shard and write every position's
    post-RoPE k/v into the decode cache in ONE pass — prompt processing at
    MXU rates instead of token-by-token (the serving-side gap between a
    decode kernel and a serving system; the reference stops at the
    kernel). The per-layer head→sequence reshard lands either directly
    in the contiguous sequence-sharded layout, or — for a
    ``PagedKVCacheSpec(static_table=True)`` — as a batch page-range
    scatter into the pool (slot-masked admission gates the scatter
    indices, the paged discipline).

    prompt_loc: ``[b*L/world]`` int32 flattened prompt shard (b-major;
    ``world`` = all PEs — outer-major over a hierarchical mesh). On a
    hierarchical deployment ``cfg.batch`` is the outer GROUP's batch
    slice and ``slot_mask``/``pick`` arrive global (sliced here); the
    returned ``last`` is always the global ``[b_global, vocab]``.
    ``slot_mask [b] bool`` restricts the cache write to chosen sequences
    (continuous-batching admission: one slot prefills while its
    neighbors' cache rows must stay untouched); padded prompt positions
    beyond a slot's true length are harmless — causal attention keeps
    them out of earlier positions and the decode-side ``kv_lens`` mask
    never reads them. ``slot`` (int32 scalar) says the same by INDEX for a
    pass of ONE sequence (``cfg.batch == 1``, the batcher's member walk on
    one device): its ``[1, L]`` rows run alone and land in slot ``slot`` of
    a cache that holds any number of slots, none of the others read or
    written. Returns ``(cache, last_logits [b, vocab])`` — the
    cache holds positions ``[0, L)`` and `last_logits` are per-sequence
    position ``pick``'s (default ``L-1`` — ragged admission passes each
    slot's true ``len-1``; the row is selected BEFORE the vocab-shard
    gather, so only ``[b, V]`` ever materializes).
    """
    from triton_dist_tpu.models.tp_transformer import (
        EPMoETransformer, TPMoETransformer, TPTransformer,
    )

    if cfg.own_passes:
        # a layer-plan family: (cache, last, its pass_counters), the head
        # on the picked rows only
        return cfg.prefill_cache(
            params, cache, prompt_loc, spec, s_max,
            slot_mask=slot_mask, pick=pick, interpret=cfg.interpret)
    paged = isinstance(spec, PagedKVCacheSpec)
    if paged and not spec.static_table:
        raise ValueError(
            "paged prefill needs static_table=True (pre-assigned page "
            "ranges): the bump allocator hands out pages one step at a "
            "time and cannot batch-claim a whole prompt's worth"
        )
    c = cfg
    n = _axis_size(c.axis)
    me = jax.lax.axis_index(c.axis)
    b, L = c.batch, c.seq
    s_shard = _shard_of(s_max, n)
    # hierarchical deployment: `c.batch` is already the outer group's
    # batch slice (the caller's pcfg); slot_mask/pick arrive GLOBAL and
    # slice down to this group's slots here
    n_o, my_o = _outer_dims(c)
    if slot is not None and (b != 1 or n_o > 1 or slot_mask is not None):
        raise ValueError(
            "slot names the cache slot of a ONE-sequence pass (cfg.batch == "
            "1, flat mesh, no slot_mask): a pass of several runs masked")
    if n_o > 1:
        if slot_mask is not None:
            slot_mask = jax.lax.dynamic_slice_in_dim(slot_mask, my_o * b, b, 0)
        if pick is not None:
            pick = jax.lax.dynamic_slice_in_dim(pick, my_o * b, b, 0)

    if isinstance(c, EPMoETransformerConfig):
        model_cls = EPMoETransformer  # expert-parallel FFN in the forward
    elif isinstance(c, MoETransformerConfig):
        model_cls = TPMoETransformer
    else:
        model_cls = TPTransformer
    model = model_cls(c)
    model.kv_sink = []
    logits_loc = model(prompt_loc, params)            # [b*L, V/n]
    # every layer's post-RoPE k/v into the decode cache
    with _scope("attn"), _scope("attn/kv_write"):
        for li, (k_loc, v_loc) in enumerate(model.kv_sink):
            # heads are sharded contiguously, so a tiled gather on the head
            # dim restores global head order: [b, L, h_kv, d]
            k_full = jax.lax.all_gather(k_loc, c.axis, axis=2, tiled=True)
            v_full = jax.lax.all_gather(v_loc, c.axis, axis=2, tiled=True)
            k_full = jnp.swapaxes(k_full, 1, 2)           # [b, h_kv, L, d]
            v_full = jnp.swapaxes(v_full, 1, 2)
            kd = cache["k"].dtype
            # this PE's window [me*s_shard, me*s_shard + s_shard) of the
            # prompt: pad by ONE shard (not to s_max — a long-context cache
            # would otherwise allocate n x the PE's shard per layer as a
            # temp) and slice; a window past L is all-zero either way, so
            # clamping the start into the padded region stays correct
            zpad = jnp.zeros((b, c.n_kv_heads, s_shard, c.head_dim), kd)
            k_buf = jnp.concatenate([k_full.astype(kd), zpad], axis=2)
            v_buf = jnp.concatenate([v_full.astype(kd), zpad], axis=2)
            start = jnp.minimum(me * s_shard, L)
            k_new = jax.lax.dynamic_slice_in_dim(k_buf, start, s_shard, 2)
            v_new = jax.lax.dynamic_slice_in_dim(v_buf, start, s_shard, 2)
            if paged:
                # page pool write: this PE's window splits into its slot's
                # STATIC page range; slot_mask gates the scatter INDICES (the
                # paged discipline — out-of-range ids drop), not the values
                ps = spec.page_size
                pps = s_shard // ps
                kp = k_new.reshape(b, c.n_kv_heads, pps, ps, c.head_dim)
                vp = v_new.reshape(b, c.n_kv_heads, pps, ps, c.head_dim)
                kp = jnp.swapaxes(kp, 1, 2).reshape(
                    b * pps, c.n_kv_heads, ps, c.head_dim)
                vp = jnp.swapaxes(vp, 1, 2).reshape(
                    b * pps, c.n_kv_heads, ps, c.head_dim)
                ids = cache["block_table"][0]            # [b, pps] static
                n_pool = cache["k"].shape[1]
                if slot is not None:
                    ids = ids[slot][None]                # its pages alone
                if slot_mask is not None:
                    ids = jnp.where(slot_mask[:, None], ids, n_pool)  # drop
                cache = dict(
                    cache,
                    k=cache["k"].at[li, ids.reshape(-1)].set(
                        kp.astype(kd), mode="drop"
                    ),
                    v=cache["v"].at[li, ids.reshape(-1)].set(
                        vp.astype(kd), mode="drop"
                    ),
                )
                continue
            if slot is not None:
                # the slot's own [h_kv, s_shard, d] of the layer, in place
                at = (li, slot, 0, 0, 0)
                cache = dict(
                    cache,
                    k=jax.lax.dynamic_update_slice(cache["k"], k_new[None], at),
                    v=jax.lax.dynamic_update_slice(cache["v"], v_new[None], at),
                )
                continue
            if slot_mask is not None:
                sel = slot_mask.reshape(b, 1, 1, 1)
                k_new = jnp.where(sel, k_new, cache["k"][li])
                v_new = jnp.where(sel, v_new, cache["v"][li])
            cache = dict(
                cache,
                k=cache["k"].at[li].set(k_new),
                v=cache["v"].at[li].set(v_new),
            )
    if pick is None:
        pick = jnp.full((b,), L - 1, jnp.int32)
    rows = jnp.arange(b, dtype=jnp.int32) * L + jnp.clip(pick, 0, L - 1)
    with _scope("head"):
        sel = logits_loc[rows]                        # [b, V/n]
        last = jax.lax.all_gather(sel, c.axis, axis=1, tiled=True)  # [b, V]
        if n_o > 1:
            # restore the global batch layout the host loop schedules
            # against
            last = jax.lax.all_gather(
                last, _outer_of(c), axis=0, tiled=True)
    return cache, last


def prefill_cache_ranged(
    cfg: TransformerConfig,
    params: dict,
    cache: dict,
    tokens: jax.Array,   # [b, S] int32 — range inputs per sequence
    pos0: jax.Array,     # [] or [b] int32 — first range position
    *,
    spec: KVCacheSpec | PagedKVCacheSpec,
    fd_config: FlashDecodeConfig | None = None,
    interpret: Any = None,
) -> tuple[jax.Array, dict]:
    """Suffix-only RANGED prefill (call inside ``jax.shard_map``): run the
    transformer forward over a prompt RANGE ``[pos0, pos0+S)`` per
    sequence, attending to ALREADY-LANDED KV below the range — exact
    causal masking across the range boundary rides the per-row prefix
    lengths of the ranged flash entries
    (``ops.flash_decode.flash_ranged_prefill_distributed`` and its paged
    twin, via ``spec.update_multi_and_attend``). Returns ``(logits
    [b, S, vocab], new_cache)`` — row i's logits are the next-token
    distribution after inputs ``..., tokens[:, i]``, exactly what S
    successive ``decode_step`` calls would produce (bit-identical: pinned
    in tests/test_ranged_prefill.py), at ONE cache/weight pass.

    This is the primitive ROADMAP #2 queued three subsystems behind: a
    prefix-cache trie hit feeds only the divergent suffix (the shared
    pages' KV is the "already landed" prior), chunked-prefill scheduling
    feeds bounded consecutive ranges interleaved with decode steps, and
    the speculative verify step (``models.speculative.verify_step``,
    which delegates here) is the S-draft-token instance. Composing
    consecutive ranges equals one whole-range pass bit for bit — every
    row's causal mask names the same global prefix either way.

    Cache layouts dispatch through ``spec.update_multi_and_attend``
    (contiguous, or paged with a static table — the paged spec raises on
    the runtime bump allocator, which cannot batch-claim a range).
    Hierarchical deployments (``cfg.ep_outer``) run DP attention per
    outer group exactly as in ``decode_step``; the logits re-gather to
    the global layout."""
    n_o, my_o = _outer_dims(cfg)
    if cfg.batch % n_o:
        raise ValueError(
            f"batch={cfg.batch} must divide over the {n_o} outer groups"
        )
    b_att = cfg.batch // n_o
    c = dataclasses.replace(cfg, batch=b_att) if n_o > 1 else cfg
    n = _axis_size(c.axis)
    me = jax.lax.axis_index(c.axis)
    g = c.n_q_heads // c.n_kv_heads
    d = c.head_dim
    assert c.n_kv_heads % n == 0, (c.n_kv_heads, n)
    S = tokens.shape[1]
    pos0_g = jnp.broadcast_to(jnp.asarray(pos0, jnp.int32), (cfg.batch,))
    if n_o > 1:
        tokens = jax.lax.dynamic_slice_in_dim(tokens, my_o * b_att, b_att, 0)
        pos0_b = jax.lax.dynamic_slice_in_dim(pos0_g, my_o * b_att, b_att, 0)
    else:
        pos0_b = pos0_g
    b = b_att
    m = b * S
    pos_flat = (pos0_b[:, None] + jnp.arange(S, dtype=jnp.int32)).reshape(-1)

    with _scope("head"):
        x = params["embed"][tokens.reshape(-1)]            # [m, H] b-major
    for li, p in enumerate(params["layers"]):
        with _scope("attn"):
            h = rmsnorm(x, p["attn_norm"], c.norm_eps)
            with _scope("attn/qkv"):
                qkv_loc = h @ p["wqkv"]                    # [m, qkv/n]
                qkv = jax.lax.all_gather(qkv_loc, c.axis, axis=1, tiled=True)
            qkv = qkv.reshape(m, c.n_kv_heads, g + 2, d)
            q = qkv[:, :, :g, :].reshape(m, 1, c.n_q_heads, d)
            k_new = qkv[:, :, g, :].reshape(m, 1, c.n_kv_heads, d)
            v_new = qkv[:, :, g + 1, :]                    # [m, h_kv, d]
            rope_b = jax.vmap(lambda xi, pi: rope(xi, pi, c.rope_theta))
            q = rope_b(q, pos_flat[:, None])[:, 0]         # [m, hq, d]
            k_new = rope_b(k_new, pos_flat[:, None])[:, 0]  # [m, h_kv, d]

            attn, cache = spec.update_multi_and_attend(
                c, cache, li,
                k_new.reshape(b, S, c.n_kv_heads, d),
                v_new.reshape(b, S, c.n_kv_heads, d),
                q.reshape(b, S, c.n_q_heads, d),
                pos0_b, me, n, fd_config, interpret,
            )                                              # [b, S, hq, d]
            with _scope("attn/out"):
                attn_loc = jax.lax.dynamic_slice_in_dim(
                    attn.reshape(m, c.n_q_heads, d),
                    me * (c.n_q_heads // n), c.n_q_heads // n, axis=1,
                ).reshape(m, -1).astype(x.dtype)
                x = x + jax.lax.psum(attn_loc @ p["wo"], c.axis)
        x = _decode_mlp(c, x, p, me, n, n_o, interpret)

    with _scope("head"):
        x = rmsnorm(x, params["final_norm"], c.norm_eps)
        logits_loc = x @ params["lm_head"]                 # [m, V/n]
        logits = jax.lax.all_gather(logits_loc, c.axis, axis=1, tiled=True)
        logits = logits.reshape(b, S, c.vocab)
        if n_o > 1:
            logits = jax.lax.all_gather(
                logits, _outer_of(cfg), axis=0, tiled=True
            )
    return logits, cache
