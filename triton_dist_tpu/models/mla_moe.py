"""Latent-attention (MLA) decoder with gated sparse experts: the
DeepSeek-V3 family's block, served through the same batcher, block table
and spans as the dense decoder.

A model here is a LAYER PLAN (:func:`layer_kinds`): the kind of each layer,
in what varies between layers, two things a layer: the ATTENTION kind
(``full`` | ``window``, ``cfg.layer_types``; a config without the key
attends in full everywhere, as DeepSeek-V3 and JoyAI do) and the MLP kind:
``dense`` (SwiGLU) in the first ``first_k_dense`` layers, ``moe`` (router
+ routed experts + shared expert) after; the gated-expert MLP itself is
``models/gated_experts.py``, shared by both plan families. Every layer
attends through a LATENT (``cache_kind = "latent"``), but the two kinds
may do so with GEOMETRIES of their own (:class:`Geometry`: heads, ranks,
head widths, rope base; the ``swa_*`` fields are the window layers'), and
they are data of the config, read where a layer is built or run, not
branches of the passes. Parameters, their specs, prefill and the decode
step all walk the plan, so a layer is no longer "the" layer. The config answers for
its family (``own_passes``: ``param_specs`` / ``decode_step`` /
``prefill_cache`` / ``pass_counters`` / ``param_bytes``), so the shared
serving code (``tp_transformer.specs_for``, ``models/decode.py``) asks the
config and never imports this module.

Equations (``x [T, H]``; RMSNorm everywhere; softmax and router in f32):

- attention: ``c_q = norm(x W_qa)``; ``q = c_q W_qb`` -> heads x (nope |
  rope). ``[c_kv | k_r] = x W_kva``; ``c_kv = norm(c_kv)``; ``k_r`` and
  ``q_r`` rotated on adjacent pairs ``(2i, 2i+1)``, ``k_r`` shared by all
  heads. ``[k_n | v] = c_kv W_kvb``. ``s = (q_n.k_n + q_r.k_r) /
  sqrt(nope + rope)``, causal softmax, ``o = s v``, ``y = o W_o``.
  PREFILL runs this expanded form and writes ``[c_kv | k_r | 0]`` rows into
  the latent pool; DECODE runs the absorbed form against the pool
  (``ops/mla_decode.py``): ``q_lat[h] = q_n[h] W_kvb,k[h]^T``,
  ``o[h] = (softmax(q_lat.c_kv + q_r.k_r) c_kv) W_kvb,v[h]``.
- experts: ``s = sigmoid(x W_r)``; chosen = top-k of ``s + b``;
  ``w = s[chosen] / sum(s[chosen]) * routed_scaling``;
  ``y = sum_k w_k E_k(x) + E_shared(x)``, each ``E`` a SwiGLU. No token is
  dropped, there is no capacity. The routed part runs as two grouped GEMMs
  (``ops/group_gemm.py``) over the assignments sorted by expert
  (``ops/moe_utils.moe_align_block_size``), at decode and at prefill
  alike: only experts that were hit are read.
- ``experts_held = (first, count)`` is the chip's share of the bank: the
  router still scores every expert, the layer computes the part of the
  result its own experts give, and nothing stands in for the others.
  ``vocab_held = (first, count)``: ``count == vocab`` rows of a larger
  vocabulary live here (``window_moe.slice_vocab`` cuts a whole tree).

What a config of the dots3 kind adds (each a field, off by default):

- ``layer_types`` with ``window``: a WINDOW layer lets position ``t`` see
  ``(t - window, t]`` (the token and the ``window - 1`` before it), its
  latent rows in a ring of pages; its geometry is the ``swa_*`` fields.
- ``index_topk``: a FULL layer attends only the ``index_topk`` positions
  ``j <= t`` of largest INDEX score (every ``j <= t`` while ``t <
  index_topk``; ``ops/sparse_index.py``): ``qI_g = c_q W_iq`` (``G =
  index_n_heads`` heads of ``index_head_dim``), ``kI = LayerNorm(h
  W_ik)`` one a token, the first ``qk_rope_head_dim`` values of both
  rotated HALF-SPLIT (``i`` with ``i + rope / 2``), ``w = h W_w``;
  ``I(t, j) = sum_g w_g(t) relu(qI_g(t) . kI(j)) / sqrt(G * d_I)``. The
  index keys live in a pool of their own beside the latent rows.
- ``attn_gate``: ``g = sigmoid(h W_g)``, one scalar a head, on the
  attention's output before ``W_o``.
- ``lora_rescale``: ``c_q * sqrt(hidden / q_rank)`` and ``c_kv *
  sqrt(hidden / kv_rank)`` after their norms (the latent the cache holds
  is the rescaled one; the indexer reads the rescaled ``c_q``).

PREFILL attends a bucket past ``MATERIALIZED_UP_TO`` rows through the tiled
kernel in the EXPANDED form (``ops/flash_prefill.py``: q/k of ``nope +
rope``, values of ``v``; ``window=`` on a window layer, ``keep=`` the
indexer's selection on a full layer that has one), a smaller bucket
through materialized scores (:func:`prefill_attention` is the one place
that chooses, from the bucket alone). DECODE runs the absorbed form through
the cache kind (``LatentPagedCacheSpec.write_and_attend``): a ring's live
pages on a window layer, the selected rows on an indexed full layer.

PREFILL (an admission) computes THE ADMITTED SLOT'S ROWS ONLY, ``[1,
bucket]``, the slot found from ``slot_mask`` inside the pass
(``gated_experts.admitted_rows``), and writes that slot's pages and no
other's: the other slots are mid-sequence, and a whole batch of buckets is
``slots`` times the work. Without a mask (``generate``) every slot's rows
run.

Serving runs this family on a ONE-device shard: the expert exchange across
chips is not built, and the entry points refuse a wider axis by name.
"""

from __future__ import annotations

import dataclasses
import math
from typing import ClassVar

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.models.gated_experts import (  # noqa: F401  (the
    # family's names for what both plan families share)
    DECODE_BLOCK_M, MOE_STATS, PREFILL_BLOCK_M, add_stats, admitted_rows,
    dense_mlp, expert_bytes, last_rows, moe_mlp, no_stats, require_one_shard,
    route, routing_stats,
)
from triton_dist_tpu.models.tp_transformer import TransformerConfig, rmsnorm
from triton_dist_tpu.obs.scopes import scope
from triton_dist_tpu.ops.flash_prefill import blocks_walked, flash_prefill
from triton_dist_tpu.ops.mla_decode import latent_row
from triton_dist_tpu.ops.sparse_index import selection_mask

ATTENTION_KINDS = ("full", "window")
# The largest bucket whose scores prefill still MATERIALIZES; above it the
# tiled kernel (``window_moe.MATERIALIZED_UP_TO`` says what was measured:
# five more kernels traced cost a small bucket's admission and a run's
# set-up more than they save; at 8192 only the kernel can run: one full
# layer's materialized scores of 128 heads are 34 GB).
MATERIALIZED_UP_TO = 2048
# the counters a plan with window layers or an indexer adds to the routing
# counters: the chosen experts that live on other chips, the index keys a
# pass scored, the latent rows its full layers attended, the rows its
# window layers read from their rings
SPARSE_STATS = ("assignments_elsewhere", "index_rows", "selected_rows",
                "window_rows")


@dataclasses.dataclass(frozen=True)
class Geometry:
    """Latent attention of one attention kind: what a layer of the kind is
    built and run from."""

    kind: str
    n_heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    theta: float
    window: int | None      # positions a row sees, itself among them
    indexed: bool           # a learned selection of the keys

    @property
    def head_dim(self) -> int:
        return self.nope + self.rope

    @property
    def row(self) -> int:
        return latent_row(self.kv_rank, self.rope)


@dataclasses.dataclass(frozen=True)
class MLAMoEConfig(TransformerConfig):
    """``head_dim`` is the q/k width of the expanded form (nope + rope);
    ``ffn`` the leading dense layers' width; ``n_kv_heads`` = ``n_q_heads``
    (every head has its own up-projected key, but ONE cached row). The
    plain fields are the FULL layers' geometry; ``swa_*`` the window
    layers' (module docstring)."""

    q_lora_rank: int = 32
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 8
    qk_rope_head_dim: int = 8
    v_head_dim: int = 8
    n_experts: int = 8
    topk: int = 2
    expert_ffn: int = 32
    n_shared_experts: int = 1
    first_k_dense: int = 1
    routed_scaling: float = 2.5
    # (first expert, count) held here; None = the whole bank. The share
    # that holds the bank's first expert adds the shared expert (one share
    # of a layer does)
    experts_held: tuple[int, int] | None = None
    # (first row, count) of a larger vocabulary held here; count == vocab
    vocab_held: tuple[int, int] | None = None
    # "full" | "window", one a layer; () = every layer full
    layer_types: tuple[str, ...] = ()
    window: int = 0
    swa_n_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 0.0
    # the full layers' learned indexer; index_topk 0 = none
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    attn_gate: bool = False
    lora_rescale: bool = False

    own_passes: ClassVar[bool] = True
    cache_kind: ClassVar[str] = "latent"
    # the family's published router and gate (gated_experts reads them)
    scoring: ClassVar[str] = "sigmoid"
    gate_act: ClassVar[str] = "silu"

    def __post_init__(self):
        if self.head_dim != self.qk_nope_head_dim + self.qk_rope_head_dim:
            raise ValueError(
                f"head_dim={self.head_dim} must be qk_nope_head_dim + "
                f"qk_rope_head_dim = {self.qk_nope_head_dim} + "
                f"{self.qk_rope_head_dim}")
        if self.n_kv_heads != self.n_q_heads:
            raise ValueError("latent attention has n_kv_heads == n_q_heads")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even (rotated pairs)")
        first, count = self.held
        if not (0 <= first and first + count <= self.n_experts and count > 0):
            raise ValueError(f"experts_held={self.experts_held} outside the "
                             f"bank of {self.n_experts}")
        if self.vocab_held is not None and self.vocab_held[1] != self.vocab:
            raise ValueError(
                f"vocab_held={self.vocab_held} holds {self.vocab_held[1]} "
                f"rows but vocab={self.vocab}: the slice IS the vocabulary")
        kinds = self.layer_types
        if kinds and (len(kinds) != self.n_layers or any(
                k not in ATTENTION_KINDS for k in kinds)):
            raise ValueError(
                f"layer_types={kinds} must name one of {ATTENTION_KINDS} "
                f"for each of the {self.n_layers} layers")
        if "window" in kinds and (
                self.window < 1 or not self.swa_n_heads
                or self.swa_qk_rope_head_dim % 2):
            raise ValueError(
                "window layers need window >= 1 and the swa_* geometry")
        if self.index_topk and not (self.index_n_heads and self.index_head_dim
                                    >= self.qk_rope_head_dim):
            raise ValueError("index_topk needs index_n_heads and an "
                             "index_head_dim that holds the rotated part")

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def attention_kinds(self) -> tuple[str, ...]:
        return self.layer_types or ("full",) * self.n_layers

    @property
    def sparse(self) -> bool:
        """A plan with window layers or an indexer: what brings
        ``SPARSE_STATS`` beside the routing counters."""
        return bool(self.layer_types or self.index_topk)

    @property
    def pass_counters(self) -> tuple[str, ...]:
        return MOE_STATS + SPARSE_STATS if self.sparse else MOE_STATS

    def geometry(self, kind: str) -> Geometry:
        if kind == "window":
            return Geometry(
                kind, self.swa_n_heads, self.swa_q_lora_rank,
                self.swa_kv_lora_rank, self.swa_qk_nope_head_dim,
                self.swa_qk_rope_head_dim, self.swa_v_head_dim,
                self.swa_rope_theta, self.window, False)
        return Geometry(
            kind, self.n_q_heads, self.q_lora_rank, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
            self.rope_theta, None, bool(self.index_topk))

    @property
    def latent_row(self) -> int:
        return self.geometry("full").row

    @property
    def window_latent_row(self) -> int:
        return self.geometry("window").row

    @property
    def index_scale(self) -> float:
        return 1.0 / math.sqrt(self.index_n_heads * self.index_head_dim)

    # the family's answers to the shared serving code (own_passes)
    def param_specs(self) -> dict:
        return mla_moe_param_specs(self)

    def param_bytes(self, params: dict) -> dict:
        return dict(expert_bytes=expert_bytes(params))

    def decode_step(self, params, cache, tokens, pos, *, spec, interpret=None):
        return decode_step(self, params, cache, tokens, pos, spec=spec,
                           interpret=interpret)

    def prefill_cache(self, params, cache, prompt, spec, s_max, **kw):
        return prefill_cache(self, params, cache, prompt, spec, s_max, **kw)

    def prefill_blocks(self, length: int, bucket: int):
        """``(live, square)`` key blocks of one admission through the
        tiled kernel (host arithmetic, ``ops/flash_prefill.blocks_walked``
        over the plan), every layer and head; None where the bucket's
        scores are materialized (:func:`prefill_attention`)."""
        if bucket <= MATERIALIZED_UP_TO:
            return None
        live = square = 0
        for kind in self.attention_kinds:
            geo = self.geometry(kind)
            a, b = blocks_walked([length], bucket, 1,
                                 _clipping_window(geo, bucket))
            live, square = live + a * geo.n_heads, square + b * geo.n_heads
        return live, square


def layer_plan(cfg: MLAMoEConfig) -> tuple[str, ...]:
    """Each layer's MLP kind: ``"dense"`` | ``"moe"`` (the half of the
    plan a caller that builds weights by MLP kind reads: perfbench's
    accepted adapter does)."""
    return tuple("dense" if li < cfg.first_k_dense else "moe"
                 for li in range(cfg.n_layers))


def layer_kinds(cfg: MLAMoEConfig) -> tuple[tuple[str, str], ...]:
    """THE PLAN: each layer's ``(attention kind, MLP kind)``: ``"full"`` |
    ``"window"``, ``"dense"`` | ``"moe"``."""
    return tuple(zip(cfg.attention_kinds, layer_plan(cfg)))


def _numbered(cfg) -> list[tuple[str, int, str]]:
    """The plan with each layer's number AMONG THE LAYERS OF ITS ATTENTION
    KIND (its place in that kind's pools): ``(kind, ki, mlp)``."""
    seen = dict.fromkeys(ATTENTION_KINDS, 0)
    out = []
    for kind, mlp in layer_kinds(cfg):
        out.append((kind, seen[kind], mlp))
        seen[kind] += 1
    return out


# -- parameters --------------------------------------------------------------

def _layer_shapes(c: MLAMoEConfig, attn: str, mlp: str) -> dict:
    """``name -> (shape, init fan-in, None for a norm or "bias")`` of one
    layer, by its attention kind (whose geometry sizes the projections)
    and its MLP kind. Everything is replicated over ``cfg.axis`` (a
    one-device shard); expert banks lead with the expert dimension, the
    one expert parallelism shards."""
    h, g = c.hidden, c.geometry(attn)
    nh = g.n_heads
    fe, (_, held) = c.expert_ffn, c.held
    out = dict(
        attn_norm=((h,), None),
        wq_a=((h, g.q_rank), h),
        q_norm=((g.q_rank,), None),
        wq_b=((g.q_rank, nh * g.head_dim), g.q_rank),
        wkv_a=((h, g.kv_rank + g.rope), h),
        kv_norm=((g.kv_rank,), None),
        # W_kvb split per use: keys (absorbed into q at decode) and values
        wkv_b_k=((g.kv_rank, nh, g.nope), g.kv_rank),
        wkv_b_v=((g.kv_rank, nh, g.v), g.kv_rank),
        wo=((nh * g.v, h), nh * g.v),
        mlp_norm=((h,), None),
    )
    if c.attn_gate:
        out.update(w_attn_gate=((h, nh), h))
    if g.indexed:
        di = c.index_head_dim
        out.update(
            wi_q=((g.q_rank, c.index_n_heads * di), g.q_rank),
            wi_k=((h, di), h),
            wi_k_norm=((di,), None), wi_k_bias=((di,), "bias"),
            wi_w=((h, c.index_n_heads), h))
    if mlp == "dense":
        out.update(w_gate_up=((h, 2 * c.ffn), h), w_down=((c.ffn, h), c.ffn))
    else:
        fs = fe * c.n_shared_experts
        out.update(
            router=((h, c.n_experts), h),
            router_bias=((c.n_experts,), "bias"),
            # gate | up as contiguous halves: banks are never column-sharded
            we_gate_up=((held, h, 2 * fe), h),
            we_down=((held, fe, h), fe),
            ws_gate_up=((h, 2 * fs), h),
            ws_down=((fs, h), fs),
        )
    return out


def mla_moe_param_specs(cfg: MLAMoEConfig) -> dict:
    layers = [
        {k: P(*([None] * len(shape)))
         for k, (shape, _) in _layer_shapes(cfg, *kinds).items()}
        for kinds in layer_kinds(cfg)
    ]
    return dict(embed=P(None, None), layers=layers, final_norm=P(None),
                lm_head=P(None, None))


def init_mla_moe_params(key: jax.Array, cfg: MLAMoEConfig) -> dict:
    """Seeded parameters in the program's layout (tests, toy configs)."""
    def leaf(k, shape, fan_in, dtype=cfg.dtype):
        if fan_in is None:
            return jnp.ones(shape, dtype)
        if fan_in == "bias":
            return jax.random.normal(k, shape, jnp.float32) * 0.01
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    layers = []
    for li, kinds in enumerate(layer_kinds(cfg)):
        shapes = _layer_shapes(cfg, *kinds)
        keys = jax.random.split(jax.random.fold_in(key, li + 1), len(shapes))
        layers.append({name: leaf(k, shape, fan)
                       for k, (name, (shape, fan)) in zip(keys, shapes.items())})
    k_e, k_h = jax.random.split(jax.random.fold_in(key, 0))
    return dict(
        embed=(jax.random.normal(k_e, (cfg.vocab, cfg.hidden)) * 0.02
               ).astype(cfg.dtype),
        layers=layers,
        final_norm=jnp.ones((cfg.hidden,), cfg.dtype),
        lm_head=leaf(k_h, (cfg.hidden, cfg.vocab), cfg.hidden),
    )


# -- the block's pieces --------------------------------------------------------

def rope_pairs(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding on adjacent pairs ``(2i, 2i+1)`` of the last axis;
    ``positions`` has x's leading shape up to (not including) any head
    axis: x ``[..., d]`` with positions ``[...]``, or x ``[..., h, d]``
    with positions ``[...]``."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None] * freqs      # [..., d/2]
    if x.ndim == ang.ndim + 1:                                  # head axis
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xp = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    even, odd = xp[..., 0], xp[..., 1]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape).astype(x.dtype)


def rope_halves(x: jax.Array, positions: jax.Array, theta: float):
    """Rotary embedding HALF-SPLIT (element ``i`` with ``i + d / 2``) on
    the last axis; ``positions`` as for :func:`rope_pairs`."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None] * freqs      # [..., d/2]
    if x.ndim == ang.ndim + 1:                                  # head axis
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def _mla_project(c: MLAMoEConfig, g: Geometry, h, p, positions):
    """``h [m, H]``, ``positions [m]`` -> ``q_n [m, nh, nope]``, rotated
    ``q_r [m, nh, rope]``, normed ``c_kv [m, latent]``, rotated shared
    ``k_r [m, rope]``, and the normed ``c_q [m, q_rank]`` (the indexer
    reads it)."""
    m = h.shape[0]
    c_q = rmsnorm(h @ p["wq_a"], p["q_norm"], c.norm_eps)
    if c.lora_rescale:
        c_q = c_q * math.sqrt(c.hidden / g.q_rank)
    q = (c_q @ p["wq_b"]).reshape(m, g.n_heads, g.head_dim)
    q_n, q_r = q[..., : g.nope], q[..., g.nope:]
    kva = h @ p["wkv_a"]
    c_kv = rmsnorm(kva[:, : g.kv_rank], p["kv_norm"], c.norm_eps)
    if c.lora_rescale:
        c_kv = c_kv * math.sqrt(c.hidden / g.kv_rank)
    k_r = rope_pairs(kva[:, g.kv_rank:], positions, g.theta)
    q_r = rope_pairs(q_r, positions, g.theta)
    return q_n, q_r, c_kv, k_r, c_q


def _index_project(c: MLAMoEConfig, h, c_q, p, positions):
    """The indexer's operands of ``m`` rows: index key ``[m, d_i]``
    (LayerNorm with weight and bias), index queries ``[m, G, d_i]``, the
    first ``qk_rope_head_dim`` values of both rotated half-split; the
    heads' weights ``[m, G]`` float32."""
    m, r = h.shape[0], c.qk_rope_head_dim
    f32 = jnp.float32
    q = (c_q @ p["wi_q"]).reshape(m, c.index_n_heads, c.index_head_dim)
    k = (h @ p["wi_k"]).astype(f32)
    k = k - jnp.mean(k, -1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(k * k, -1, keepdims=True) + c.norm_eps)
    k = (k * p["wi_k_norm"].astype(f32) + p["wi_k_bias"].astype(f32)
         ).astype(h.dtype)
    turn = lambda x: jnp.concatenate(
        [rope_halves(x[..., :r], positions, c.rope_theta), x[..., r:]], -1)
    return turn(k), turn(q), (h @ p["wi_w"]).astype(f32)


def _latent_rows(g: Geometry, latent, rope):
    """``[latent | rope | 0]`` along the last axis, ``g.row`` wide: the
    row the pool stores (``c_kv | k_r``), and the query that meets it in
    one dot (``q_lat | q_r``)."""
    pad = g.row - g.kv_rank - g.rope
    parts = [latent, rope]
    if pad:
        parts.append(jnp.zeros((*latent.shape[:-1], pad), latent.dtype))
    return jnp.concatenate(parts, axis=-1)


def mla_attend_expanded(g: Geometry, q_n, q_r, c_kv, k_r, p, b, s,
                        keep=None):
    """Causal attention in the expanded form over ``b`` sequences of ``s``
    positions (rows b-major), scores materialized: ``[b*s, nh*v]``. A
    window geometry masks to its band; ``keep [b, s, s]`` to a selection."""
    nh = g.n_heads
    f32 = jnp.float32
    k_n = jnp.einsum("tc,chd->thd", c_kv, p["wkv_b_k"])
    v = jnp.einsum("tc,chd->thd", c_kv, p["wkv_b_v"])
    shape = lambda x: x.reshape(b, s, *x.shape[1:])
    scores = (
        jnp.einsum("bshd,bthd->bhst", shape(q_n).astype(f32),
                   shape(k_n).astype(f32))
        + jnp.einsum("bshd,btd->bhst", shape(q_r).astype(f32),
                     shape(k_r).astype(f32))
    ) / math.sqrt(g.head_dim)
    mask = jnp.tril(jnp.ones((s, s), bool))
    if g.window is not None and g.window < s:
        mask = mask & ~jnp.tril(jnp.ones((s, s), bool), -g.window)
    mask = mask[None, None]
    if keep is not None:
        mask = mask & (keep != 0)[:, None]
    scores = jnp.where(mask, scores, -jnp.inf)
    prob = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", prob, shape(v).astype(f32))
    return out.reshape(b * s, nh * g.v).astype(q_n.dtype)


def _clipping_window(g: Geometry, L: int) -> int | None:
    """The window of a geometry over ``L`` positions; None where it
    attends in full or its window is too wide to clip a row."""
    return g.window if g.window is not None and g.window < L else None


def prefill_attention(c: MLAMoEConfig, g: Geometry, q_n, q_r, c_kv, k_r, p,
                      b: int, s: int, lens, index=None, interpret=None):
    """An admission's attention of one layer over ``b`` sequences of ``s``
    rows (b-major): ``[b*s, nh*v]``. The ONE place that chooses the form,
    from the bucket ``s`` alone: the tiled kernel over the expanded q, k
    and v past ``MATERIALIZED_UP_TO``, materialized scores under it.
    ``index = (key, queries, weights)`` of an indexed layer: a row keeps
    its ``index_topk`` best keys; while ``s <= index_topk`` that is every
    key it can see, and nothing is scored. ``lens [b]`` (true lengths)
    bounds the kernel's walk."""
    keep = None
    if index is not None and s > c.index_topk:
        with scope("attn/index"):
            key, q_idx, w_idx = (x.reshape(b, s, *x.shape[1:]) for x in index)
            # a sequence at a time (an admission has one)
            keep = jnp.stack([selection_mask(
                q_idx[i].reshape(s, -1), w_idx[i], key[i], c.index_topk,
                g=c.index_n_heads, scale=c.index_scale, interpret=interpret)
                for i in range(b)])
    if s <= MATERIALIZED_UP_TO:
        return mla_attend_expanded(g, q_n, q_r, c_kv, k_r, p, b, s, keep)
    with scope("attn/prefill"):
        nh = g.n_heads
        k_n = jnp.einsum("tc,chd->thd", c_kv, p["wkv_b_k"])
        v = jnp.einsum("tc,chd->thd", c_kv, p["wkv_b_v"])
        k = jnp.concatenate([k_n, jnp.broadcast_to(
            k_r[:, None, :], (b * s, nh, g.rope))], -1)
        q = jnp.concatenate([q_n, q_r], -1)
        shape = lambda x: x.reshape(b, s, *x.shape[1:])
        return flash_prefill(
            shape(q), shape(k), shape(v), lens,
            window=_clipping_window(g, s), keep=keep,
            name="mla_flash_prefill", interpret=interpret,
        ).reshape(b * s, nh * g.v)


def _gate(c: MLAMoEConfig, g: Geometry, attn, h, p):
    """The headwise sigmoid gate on ``attn [m, nh * v]`` from the layer's
    normed input ``h``; the attention itself where the model has none."""
    if not c.attn_gate:
        return attn
    with scope("attn/gate"):
        gate = jax.nn.sigmoid((h @ p["w_attn_gate"]).astype(jnp.float32))
        out = attn.reshape(-1, g.n_heads, g.v).astype(jnp.float32) \
            * gate[..., None]
        return out.reshape(attn.shape).astype(attn.dtype)


def _mlp(c, kind: str, x, p, block_m, interpret, stats):
    with scope("ffn"):
        h = rmsnorm(x, p["mlp_norm"], c.norm_eps)
        if kind == "dense":
            return x + dense_mlp(c, h, p), stats
        y, st = moe_mlp(c, h, p, block_m, interpret)
        return x + y, add_stats(stats, st)


def _counters(c, stats, rows: int, lens, step: bool):
    """The pass's ``pass_counters``: the routing counters alone for a
    plain plan; for a sparse one also the chosen experts that live
    elsewhere (every one of ``rows`` rows chooses ``topk`` in each expert
    layer) and, from the sequences' lengths ``lens``, the index keys
    scored, the latent rows the full layers attended and the rows the
    window layers read: a STEP's one query a sequence at its last
    position, an admission's every position ``t < len``."""
    if not c.sparse:
        return stats
    kinds = c.attention_kinds
    n_moe = layer_plan(c).count("moe")
    n_full, n_win = kinds.count("full"), kinds.count("window")
    lens = lens.astype(jnp.int32)

    def seen(cap):
        """Keys the pass's queries see under a cap a query."""
        if step:
            return jnp.sum(jnp.minimum(lens, cap))
        m = jnp.minimum(lens, cap)          # sum_{t < len} min(t + 1, cap)
        return jnp.sum(m * (m + 1) // 2 + (lens - m) * cap)

    every = 2 ** 30                     # no cap: every key a query can see
    if not c.index_topk:
        scored = 0
    elif step:
        scored = seen(every)
    else:       # an admission scores only where a row can see past topk
        scored = jnp.sum(jnp.where(lens > c.index_topk,
                                   lens * (lens + 1) // 2, 0))
    extra = jnp.stack([
        jnp.asarray(rows * c.topk * n_moe, jnp.int32) - stats[1],
        jnp.asarray(n_full * scored, jnp.int32),
        (n_full * seen(c.index_topk or every)).astype(jnp.int32),
        (n_win * seen(max(c.window, 1))).astype(jnp.int32)])
    return jnp.concatenate([stats, extra.astype(jnp.int32)])


# -- the passes ------------------------------------------------------------------

def forward_hidden(cfg: MLAMoEConfig, params, tokens, b: int, s: int,
                   interpret=None, sink=None, lens=None):
    """Expanded-form forward over ``tokens [b*s]`` (b-major): the final
    residual ``[b*s, H]`` (before the last norm) and the pass's routing
    counters. ``sink`` (a list) collects each layer's ``(latent rows
    [b*s, row], index keys [b*s, d_i] or None)``. ``lens [b]`` are the
    sequences' true lengths (default ``s``): the tiled attention walks no
    key block past them."""
    c = cfg
    positions = jnp.tile(jnp.arange(s, dtype=jnp.int32), b)
    if lens is None:
        lens = jnp.full((b,), s, jnp.int32)
    with scope("head"):
        x = params["embed"][tokens]
    stats = no_stats()
    for (kind, mlp), p in zip(layer_kinds(c), params["layers"]):
        g = c.geometry(kind)
        with scope("attn"):
            h = rmsnorm(x, p["attn_norm"], c.norm_eps)
            with scope("attn/qkv"):
                q_n, q_r, c_kv, k_r, c_q = _mla_project(c, g, h, p, positions)
            index = None
            if g.indexed:
                with scope("attn/index"):
                    index = _index_project(c, h, c_q, p, positions)
            if sink is not None:
                sink.append((_latent_rows(g, c_kv, k_r),
                             None if index is None else index[0]))
            attn = prefill_attention(c, g, q_n, q_r, c_kv, k_r, p, b, s,
                                     lens, index, interpret)
            attn = _gate(c, g, attn, h, p)
            with scope("attn/out"):
                x = x + attn @ p["wo"]
        x, stats = _mlp(c, mlp, x, p, PREFILL_BLOCK_M, interpret, stats)
    return x, stats


def forward_logits(cfg: MLAMoEConfig, params, tokens, interpret=None):
    """Whole-sequence logits ``[b, s, V]`` of ``tokens [b, s]`` (tests)."""
    b, s = tokens.shape
    x, _ = forward_hidden(cfg, params, tokens.reshape(-1), b, s, interpret)
    with scope("head"):
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        return (x @ params["lm_head"]).reshape(b, s, -1)


def prefill_cache(cfg: MLAMoEConfig, params, cache, prompt, spec, s_max,
                  slot_mask=None, pick=None, interpret=None):
    """Bulk prefill (inside shard_map, one-device shard) of ``prompt
    [b*L]``: the expanded forward, every position's latent row (a full
    layer's index key beside it) written to the pools through its slot's
    static page range, a window layer's last ring of rows (counted from
    the slot's true length, ``pick + 1``) to their ring addresses, and the
    head applied to the picked row of each slot only. With ``slot_mask``
    (an admission) ONLY THE MASKED SLOT'S ROWS run, and only its pages are
    written; without, every slot's. Returns ``(cache, last [b, V],
    counters)``; ``last`` holds the rows of the slots that ran, zeros
    elsewhere."""
    require_one_shard(cfg, "latent-attention / gated-expert")
    c = cfg
    b, L = c.batch, c.seq
    slots, tokens, pick = admitted_rows(prompt, slot_mask, pick, b, L)
    n = len(slots)
    sink: list = []
    x, stats = forward_hidden(
        c, params, tokens.reshape(-1), n, L, interpret, sink, pick + 1)
    with scope("attn"), scope("attn/kv_write"):
        for (kind, ki, _), (rows, keys) in zip(_numbered(c), sink):
            cache = spec.write_prompt(
                cache, kind, ki, rows.reshape(n, L, -1), pick + 1, slots,
                None if keys is None else keys.reshape(n, L, -1))
    with scope("head"):
        rows = jnp.arange(n, dtype=jnp.int32) * L + pick
        xs = rmsnorm(x[rows], params["final_norm"], c.norm_eps)
        last = last_rows(xs @ params["lm_head"], slots, b)
    return cache, last, _counters(c, stats, n * L, pick + 1, step=False)


def decode_step(cfg: MLAMoEConfig, params, cache, tokens, pos, *, spec,
                interpret=None):
    """One ragged decode step (inside shard_map, one-device shard):
    ``(logits [b, V], cache, counters)``. Each slot's new latent row lands
    in its page first; the absorbed attention then reads the kind's pool
    (``spec.write_and_attend``)."""
    require_one_shard(cfg, "latent-attention / gated-expert")
    c = cfg
    b = c.batch
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    with scope("head"):
        x = params["embed"][tokens]
    stats = no_stats()
    for (kind, ki, mlp), p in zip(_numbered(c), params["layers"]):
        g = c.geometry(kind)
        with scope("attn"):
            h = rmsnorm(x, p["attn_norm"], c.norm_eps)
            with scope("attn/qkv"):
                q_n, q_r, c_kv, k_r, c_q = _mla_project(c, g, h, p, pos_b)
            index = None
            if g.indexed:
                with scope("attn/index"):
                    index = _index_project(c, h, c_q, p, pos_b)
            with scope("attn/decode"):
                q_lat = jnp.einsum(
                    "bhd,chd->bhc", q_n, p["wkv_b_k"]).astype(q_n.dtype)
            o_lat, cache = spec.write_and_attend(
                c, cache, kind, ki, _latent_rows(g, c_kv, k_r),
                _latent_rows(g, q_lat, q_r), pos_b, d_v=g.kv_rank,
                scale=1.0 / math.sqrt(g.head_dim), index=index,
                interpret=interpret)                    # [b, nh, latent] f32
            with scope("attn/decode"):
                attn = jnp.einsum("bhc,chd->bhd", o_lat.astype(q_n.dtype),
                                  p["wkv_b_v"]).reshape(b, g.n_heads * g.v)
            attn = _gate(c, g, attn, h, p)
            with scope("attn/out"):
                x = x + attn.astype(x.dtype) @ p["wo"]
        x, stats = _mlp(c, mlp, x, p, DECODE_BLOCK_M, interpret, stats)
    with scope("head"):
        x = rmsnorm(x, params["final_norm"], c.norm_eps)
        logits = x @ params["lm_head"]
    lens = jnp.clip(pos_b + 1, 0, spec.s_max)
    return logits, cache, _counters(c, stats, b, lens, step=True)
