"""Latent-attention (MLA) decoder with gated sparse experts: the
DeepSeek-V3 family's block, served through the same batcher, block table
and spans as the dense decoder.

A model here is a LAYER PLAN (:func:`layer_plan`): the kind of each layer,
in what varies between layers. A plan can name two things a layer, the
ATTENTION kind and the MLP kind (``models/window_moe.py`` names both:
window or full attention, dense or expert MLP). THIS family attends the
same way (latent attention) over the same cache kind (``cache_kind =
"latent"``) in every layer, so its plan names the one thing that varies
here, the MLP: ``dense`` (SwiGLU) in the first ``first_k_dense`` layers,
``moe`` (router + routed experts + shared expert) after; the gated-expert
MLP itself is ``models/gated_experts.py``, shared by both plan families.
Parameters, their specs, prefill and the decode step all
walk the plan, so a layer is no longer "the" layer. The config answers for
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
    dense_mlp, expert_bytes, last_rows, moe_mlp, require_one_shard, route,
    routing_stats,
)
from triton_dist_tpu.models.tp_transformer import TransformerConfig, rmsnorm
from triton_dist_tpu.obs.scopes import scope
from triton_dist_tpu.ops.mla_decode import latent_row, mla_paged_decode

@dataclasses.dataclass(frozen=True)
class MLAMoEConfig(TransformerConfig):
    """``head_dim`` is the q/k width of the expanded form (nope + rope);
    ``ffn`` the leading dense layers' width; ``n_kv_heads`` = ``n_q_heads``
    (every head has its own up-projected key, but ONE cached row)."""

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

    own_passes: ClassVar[bool] = True
    cache_kind: ClassVar[str] = "latent"
    pass_counters: ClassVar[tuple[str, ...]] = MOE_STATS
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

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def latent_row(self) -> int:
        return latent_row(self.kv_lora_rank, self.qk_rope_head_dim)

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


def layer_plan(cfg: MLAMoEConfig) -> tuple[str, ...]:
    """Each layer's MLP kind: ``"dense"`` | ``"moe"``."""
    return tuple("dense" if li < cfg.first_k_dense else "moe"
                 for li in range(cfg.n_layers))


# -- parameters --------------------------------------------------------------

def _layer_shapes(c: MLAMoEConfig, kind: str) -> dict:
    """``name -> (shape, init scale or None for a norm, spec)`` of one
    layer, by its kind. Everything is replicated over ``cfg.axis`` (a
    one-device shard); expert banks lead with the expert dimension, the
    one expert parallelism shards."""
    h, nh = c.hidden, c.n_q_heads
    fe, (_, held) = c.expert_ffn, c.held
    out = dict(
        attn_norm=((h,), None),
        wq_a=((h, c.q_lora_rank), h),
        q_norm=((c.q_lora_rank,), None),
        wq_b=((c.q_lora_rank, nh * c.head_dim), c.q_lora_rank),
        wkv_a=((h, c.kv_lora_rank + c.qk_rope_head_dim), h),
        kv_norm=((c.kv_lora_rank,), None),
        # W_kvb split per use: keys (absorbed into q at decode) and values
        wkv_b_k=((c.kv_lora_rank, nh, c.qk_nope_head_dim), c.kv_lora_rank),
        wkv_b_v=((c.kv_lora_rank, nh, c.v_head_dim), c.kv_lora_rank),
        wo=((nh * c.v_head_dim, h), nh * c.v_head_dim),
        mlp_norm=((h,), None),
    )
    if kind == "dense":
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
         for k, (shape, _) in _layer_shapes(cfg, kind).items()}
        for kind in layer_plan(cfg)
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
    for li, kind in enumerate(layer_plan(cfg)):
        shapes = _layer_shapes(cfg, kind)
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


def _mla_project(c: MLAMoEConfig, h, p, positions):
    """``h [m, H]``, ``positions [m]`` -> ``q_n [m, nh, nope]``, rotated
    ``q_r [m, nh, rope]``, normed ``c_kv [m, latent]``, rotated shared
    ``k_r [m, rope]``."""
    m = h.shape[0]
    c_q = rmsnorm(h @ p["wq_a"], p["q_norm"], c.norm_eps)
    q = (c_q @ p["wq_b"]).reshape(m, c.n_q_heads, c.head_dim)
    q_n, q_r = q[..., : c.qk_nope_head_dim], q[..., c.qk_nope_head_dim:]
    kva = h @ p["wkv_a"]
    c_kv = rmsnorm(kva[:, : c.kv_lora_rank], p["kv_norm"], c.norm_eps)
    k_r = rope_pairs(kva[:, c.kv_lora_rank:], positions, c.rope_theta)
    q_r = rope_pairs(q_r, positions, c.rope_theta)
    return q_n, q_r, c_kv, k_r


def _latent_rows(c: MLAMoEConfig, latent, rope):
    """``[latent | rope | 0]`` along the last axis, ``c.latent_row`` wide:
    the row the pool stores (``c_kv | k_r``), and the query that meets it
    in one dot (``q_lat | q_r``)."""
    pad = c.latent_row - c.kv_lora_rank - c.qk_rope_head_dim
    parts = [latent, rope]
    if pad:
        parts.append(jnp.zeros((*latent.shape[:-1], pad), latent.dtype))
    return jnp.concatenate(parts, axis=-1)


def mla_attend_expanded(c: MLAMoEConfig, q_n, q_r, c_kv, k_r, p, b, s):
    """Causal attention in the expanded form over ``b`` sequences of ``s``
    positions (rows b-major): returns ``[b*s, nh*v]``."""
    nh = c.n_q_heads
    f32 = jnp.float32
    k_n = jnp.einsum("tc,chd->thd", c_kv, p["wkv_b_k"])
    v = jnp.einsum("tc,chd->thd", c_kv, p["wkv_b_v"])
    shape = lambda x: x.reshape(b, s, *x.shape[1:])
    scores = (
        jnp.einsum("bshd,bthd->bhst", shape(q_n).astype(f32),
                   shape(k_n).astype(f32))
        + jnp.einsum("bshd,btd->bhst", shape(q_r).astype(f32),
                     shape(k_r).astype(f32))
    ) / math.sqrt(c.head_dim)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    prob = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", prob, shape(v).astype(f32))
    return out.reshape(b * s, nh * c.v_head_dim).astype(q_n.dtype)


def mla_attend_absorbed(
    c: MLAMoEConfig, q_n, q_r, p, pool, li, kv_lens, block_table, interpret,
):
    """Decode attention against the latent pool: ``[b, nh*v]``."""
    b = q_n.shape[0]
    q_lat = jnp.einsum("bhd,chd->bhc", q_n, p["wkv_b_k"]).astype(q_n.dtype)
    o_lat = mla_paged_decode(
        _latent_rows(c, q_lat, q_r), pool, li, kv_lens, block_table,
        d_v=c.kv_lora_rank, scale=1.0 / math.sqrt(c.head_dim),
        interpret=interpret,
    )                                                   # [b, nh, latent] f32
    o = jnp.einsum("bhc,chd->bhd", o_lat.astype(q_n.dtype), p["wkv_b_v"])
    return o.reshape(b, c.n_q_heads * c.v_head_dim)


def _mlp(c, kind: str, x, p, block_m, interpret, stats):
    with scope("ffn"):
        h = rmsnorm(x, p["mlp_norm"], c.norm_eps)
        if kind == "dense":
            return x + dense_mlp(c, h, p), stats
        y, st = moe_mlp(c, h, p, block_m, interpret)
        return x + y, add_stats(stats, st)


# -- the passes ------------------------------------------------------------------

def forward_hidden(cfg: MLAMoEConfig, params, tokens, b: int, s: int,
                   interpret=None, sink=None):
    """Expanded-form forward over ``tokens [b*s]`` (b-major): the final
    residual ``[b*s, H]`` (before the last norm) and the pass's routing
    counters. ``sink`` (a list) collects each layer's latent rows
    ``[b*s, row]``."""
    c = cfg
    positions = jnp.tile(jnp.arange(s, dtype=jnp.int32), b)
    with scope("head"):
        x = params["embed"][tokens]
    stats = jnp.zeros((3,), jnp.int32)
    for kind, p in zip(layer_plan(c), params["layers"]):
        with scope("attn"):
            h = rmsnorm(x, p["attn_norm"], c.norm_eps)
            with scope("attn/qkv"):
                q_n, q_r, c_kv, k_r = _mla_project(c, h, p, positions)
            if sink is not None:
                sink.append(_latent_rows(c, c_kv, k_r))
            attn = mla_attend_expanded(c, q_n, q_r, c_kv, k_r, p, b, s)
            with scope("attn/out"):
                x = x + attn @ p["wo"]
        x, stats = _mlp(c, kind, x, p, PREFILL_BLOCK_M, interpret, stats)
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
    [b*L]``: the expanded forward, every position's latent row written to
    the pool through its slot's static page range, and the head applied to
    the picked row of each slot only. With ``slot_mask`` (an admission)
    ONLY THE MASKED SLOT'S ROWS run, and only its pages are written;
    without, every slot's. Returns ``(cache, last [b, V], stats
    int32[3])``; ``last`` holds the rows of the slots that ran, zeros
    elsewhere."""
    require_one_shard(cfg, "latent-attention / gated-expert")
    c = cfg
    b, L = c.batch, c.seq
    ps = spec.page_size
    slots, tokens, pick = admitted_rows(prompt, slot_mask, pick, b, L)
    n = len(slots)
    sink: list = []
    x, stats = forward_hidden(
        c, params, tokens.reshape(-1), n, L, interpret, sink)
    n_pages = -(-L // ps)
    with scope("attn"), scope("attn/kv_write"):
        ids = cache["block_table"][0][slots, :n_pages].reshape(-1)
        lat = cache["lat"]
        for li, rows in enumerate(sink):
            rows = rows.reshape(n, L, -1)
            if n_pages * ps != L:
                rows = jnp.pad(rows, ((0, 0), (0, n_pages * ps - L), (0, 0)))
            lat = lat.at[li, ids].set(
                rows.reshape(n * n_pages, ps, -1).astype(lat.dtype))
    cache = dict(cache, lat=lat)
    with scope("head"):
        rows = jnp.arange(n, dtype=jnp.int32) * L + pick
        xs = rmsnorm(x[rows], params["final_norm"], c.norm_eps)
        last = last_rows(xs @ params["lm_head"], slots, b)
    return cache, last, stats


def decode_step(cfg: MLAMoEConfig, params, cache, tokens, pos, *, spec,
                interpret=None):
    """One ragged decode step (inside shard_map, one-device shard):
    ``(logits [b, V], cache, stats int32[3])``. Each slot's new latent row
    lands in its page first; the absorbed attention then reads the pool."""
    require_one_shard(cfg, "latent-attention / gated-expert")
    c = cfg
    b = c.batch
    ps, s_max = spec.page_size, spec.s_max
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    with scope("head"):
        x = params["embed"][tokens]
    with scope("attn"):
        bt = cache["block_table"][0]
        n_pool = cache["lat"].shape[1]
        # a parked slot (pos = s_max) is owned by no page: its write drops
        own = pos_b < s_max
        page_ids = bt[
            jnp.arange(b), jnp.minimum(pos_b // ps, bt.shape[1] - 1)]
        safe_ids = jnp.where(own, page_ids, n_pool)
        kv_lens = jnp.clip(pos_b + 1, 0, s_max)
    lat = cache["lat"]
    stats = jnp.zeros((3,), jnp.int32)
    for li, (kind, p) in enumerate(zip(layer_plan(c), params["layers"])):
        with scope("attn"):
            h = rmsnorm(x, p["attn_norm"], c.norm_eps)
            with scope("attn/qkv"):
                q_n, q_r, c_kv, k_r = _mla_project(c, h, p, pos_b)
            with scope("attn/kv_write"):
                lat = lat.at[li, safe_ids, pos_b % ps].set(
                    _latent_rows(c, c_kv, k_r).astype(lat.dtype),
                    mode="drop")
            with scope("attn/decode"):
                attn = mla_attend_absorbed(
                    c, q_n, q_r, p, lat, li, kv_lens, bt, interpret)
            with scope("attn/out"):
                x = x + attn.astype(x.dtype) @ p["wo"]
        x, stats = _mlp(c, kind, x, p, DECODE_BLOCK_M, interpret, stats)
    with scope("head"):
        x = rmsnorm(x, params["final_norm"], c.norm_eps)
        logits = x @ params["lm_head"]
    return logits, dict(cache, lat=lat), stats
