"""GQA decoder whose layers attend through a WINDOW or in FULL, with gated
sparse experts: the EXAONE-MoE block (K-EXAONE-236B-A23B) and the
SmallThinker block (SmallThinker-21BA3B-Instruct), served through the same
batcher, block tables and spans as the other families.

The LAYER PLAN (:func:`layer_plan`) names two things a layer: the
attention kind (``window`` | ``full``, ``cfg.layer_types``) and the MLP
kind (``dense`` in the first ``first_k_dense`` layers, ``moe`` after).
Parameters, their specs, prefill and the decode step walk it. The two
attention kinds keep their keys and values in pools of two page lifetimes
(``cache_kind = "kv_window"``, ``models/decode.py``
``WindowPagedKVCacheSpec``): a full layer's pages cover the sequence, a
window layer's are a ring of ``ceil(window / page) + 1`` pages a slot.
The gated-expert MLP is ``models/gated_experts.py``, the one
``models/mla_moe.py`` runs.

Equations (``x [T, H]``; RMSNorm everywhere; softmax, norms and router in
f32):

- attention: ``[q | k | v] = h W_qkv`` (no bias; stored kv-group-major as
  the dense family's ``wqkv``); with ``qk_norm``, ``q`` and ``k`` normed
  over the head width; on a WINDOW layer both are then rotated (the dense
  family's half-split convention), a FULL layer is not rotated. Scores
  ``q.k / sqrt(d)``; a window layer lets position ``p`` see ``max(0, p -
  window + 1) .. p``, a full layer ``0 .. p``. ``y = softmax(s) v W_o``.
- MLP: a dense gated MLP, or router + routed experts + shared experts
  (``gated_experts.moe_mlp``), ``experts_held`` the chip's share.

What differs between the family's two published blocks is read from the
model's config, each a field named for what it is (K-EXAONE's value is the
default; nobody tunes these):

- ``norm_placement``: ``"output"`` (K-EXAONE): each sub-layer's norm on
  its OUTPUT, ``x = x + norm(attn(x))``, ``x = x + norm(mlp(x))``, no
  input norm. ``"input"`` (SmallThinker): pre-norm, ``u = x + attn(norm_in
  (x))``, ``x' = u + mlp(norm_post(u))``. The leaves are ``attn_norm`` and
  ``mlp_norm`` in both.
- ``qk_norm``: the q/k norms, present (K-EXAONE) or not (no leaf).
- ``router_rows``: ``"mlp_input"`` (K-EXAONE: the router reads the rows
  the experts multiply) or ``"layer_input"`` (SmallThinker: the router
  reads the layer's UN-NORMED input ``x_l``, and the routing of layer
  ``l`` is issued BEFORE its attention, as the model is written: what an
  expert exchange would hide under attention, ROADMAP B1).
- ``scoring``: ``"sigmoid"`` with a choice bias and ``routed_scaling``
  (K-EXAONE), or ``"softmax"`` over the chosen logits, no bias leaf
  (SmallThinker: ``moe_primary_router_apply_softmax`` with
  ``norm_topk_prob``).
- ``gate_act``: ``"silu"`` (K-EXAONE) or ``"relu"`` (SmallThinker).
- ``first_k_dense`` 0 and ``n_shared_experts`` 0 allocate no such leaf.

SmallThinker's layer, whole (``x_l [T, H]``, eps 1e-6): ``r = x_l W_r``,
the ``topk`` largest logits chosen, weights softmax over them; ``h =
norm_in(x_l)``, ``q, k, v = h W_q, h W_k, h W_v`` (no bias, no q/k norm),
rotated where ``rope_layout[l] == 1`` (= ``sliding_window_layout[l]``: the
window layers), ``u = x_l + softmax(q.k / sqrt(d)) v W_o``; ``m =
norm_post(u)``, ``x_{l+1} = u + sum_e w_e (relu(m W_gate,e) * m W_up,e)
W_down,e``.
- ``vocab`` rows of embedding and head: where ``vocab_held = (first,
  count)`` is set, ``count == vocab`` rows of a larger vocabulary live
  here and token ids count from ``first`` (:func:`slice_vocab`): logits,
  argmax and the traffic are over the slice.

PREFILL attends a bucket past ``MATERIALIZED_UP_TO`` rows through
``ops/flash_prefill.flash_prefill`` (``window=`` on a window layer):
tiled, online softmax, the key blocks outside the band or past the
prompt's true length neither fetched nor multiplied, no ``[L, L]`` and no
``[L, 2 x window]`` array. A smaller bucket keeps the materialized forms
(:func:`prefill_attention` is the one place that chooses, from the bucket
and the window): a window layer whose window can clip over a BAND (blocks
of ``window`` queries against their own and the previous block of keys),
any other layer as the dense family does. DECODE reads the pools through
``ops/flash_decode.paged_flash_decode`` (``window=`` on a window layer:
``ceil(window / page) + 1`` pages a row at most, whatever the context). An
admission computes THE ADMITTED SLOT'S ROWS ONLY, ``[1, bucket]``, the slot
found from ``slot_mask`` inside the pass (``gated_experts.admitted_rows``),
and writes that slot's pages and rings and no other's: the other slots are
mid-sequence, and a whole batch of buckets is ``slots`` times the work.
Without a mask (``generate``) every slot's rows run.

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

from triton_dist_tpu.models.gated_experts import (
    DECODE_BLOCK_M, GATE_ACTS, MOE_STATS, PREFILL_BLOCK_M, SCORINGS,
    add_stats, admitted_rows, dense_mlp, expert_bytes, last_rows, moe_mlp,
    no_stats, require_one_shard, route_rows,
)
from triton_dist_tpu.models.tp_transformer import (
    TransformerConfig, _causal_gqa_attention, rmsnorm, rope,
)
from triton_dist_tpu.obs.scopes import scope
from triton_dist_tpu.ops.flash_prefill import blocks_walked, flash_prefill

ATTENTION_KINDS = ("window", "full")
NORM_PLACEMENTS = ("output", "input")
ROUTER_ROWS = ("mlp_input", "layer_input")
# The largest bucket whose scores prefill still MATERIALIZES (the band for
# a window that clips, the causal square otherwise); above it the tiled
# kernel. Measured on the chip at both ends (PERF.md section 6, PR 39): at
# bucket 256 / window 128 an admission through the kernel takes 12.76 ms
# against 12.10 (five more kernel launches for ~0.1 ms of work each) and a
# run's set-up 2-3.6 s more (five more kernels traced and lowered: past
# the 10% bound on `setup_s`); at bucket 8192 only the kernel can run (one
# layer's materialized scores are 7.5 GB). Every bucket measured through
# the materialized path so far was 2048 rows or fewer.
MATERIALIZED_UP_TO = 2048
FAMILY = "window-attention / gated-expert"


@dataclasses.dataclass(frozen=True)
class WindowMoEConfig(TransformerConfig):
    """``ffn`` is the leading dense layers' width; ``n_experts`` the
    router's width, whatever share of the bank ``experts_held`` names."""

    layer_types: tuple[str, ...] = ()   # "window" | "full", one a layer
    window: int = 8
    n_experts: int = 8
    topk: int = 2
    expert_ffn: int = 32
    n_shared_experts: int = 1
    first_k_dense: int = 1
    routed_scaling: float = 2.5
    # (first expert, count) held here; None = the whole bank
    experts_held: tuple[int, int] | None = None
    # (first row, count) of a larger vocabulary held here; count == vocab
    vocab_held: tuple[int, int] | None = None
    # the published block (module docstring); K-EXAONE's are the defaults
    norm_placement: str = "output"
    qk_norm: bool = True
    router_rows: str = "mlp_input"
    scoring: str = "sigmoid"
    gate_act: str = "silu"

    own_passes: ClassVar[bool] = True
    cache_kind: ClassVar[str] = "kv_window"
    # the routing counters over the HELD experts, the chosen experts that
    # live on other chips, and the key rows a step's window and full
    # layers read (from the slots' lengths; 0 on an admission's pass)
    pass_counters: ClassVar[tuple[str, ...]] = MOE_STATS + (
        "assignments_elsewhere", "window_rows", "full_rows")

    def __post_init__(self):
        if len(self.layer_types) != self.n_layers or any(
                k not in ATTENTION_KINDS for k in self.layer_types):
            raise ValueError(
                f"layer_types={self.layer_types} must name one of "
                f"{ATTENTION_KINDS} for each of the {self.n_layers} layers")
        if self.window < 1:
            raise ValueError(f"window={self.window} must be >= 1")
        for name, known in (("norm_placement", NORM_PLACEMENTS),
                            ("router_rows", ROUTER_ROWS),
                            ("scoring", SCORINGS), ("gate_act", GATE_ACTS)):
            if getattr(self, name) not in known:
                raise ValueError(
                    f"{name}={getattr(self, name)!r} is none of {tuple(known)}")
        first, count = self.held
        if not (0 <= first and first + count <= self.n_experts and count > 0):
            raise ValueError(f"experts_held={self.experts_held} outside the "
                             f"bank of {self.n_experts}")
        if self.vocab_held is not None and self.vocab_held[1] != self.vocab:
            raise ValueError(
                f"vocab_held={self.vocab_held} holds {self.vocab_held[1]} "
                f"rows but vocab={self.vocab}: the slice IS the vocabulary")

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    # the family's answers to the shared serving code (own_passes)
    def param_specs(self) -> dict:
        return window_moe_param_specs(self)

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
        over the plan): what it walks for a prompt of ``length`` in
        ``bucket``, every layer and kv head, and what the causal square of
        the bucket holds at the same block sizes. None where the bucket's
        scores are materialized (:func:`prefill_attention`)."""
        if bucket <= MATERIALIZED_UP_TO:
            return None
        g = self.n_q_heads // self.n_kv_heads
        live = square = 0
        for kind in self.layer_types:
            a, b = blocks_walked(
                [length], bucket, g, _clipping_window(self, kind, bucket))
            live, square = live + a, square + b
        return live * self.n_kv_heads, square * self.n_kv_heads


def layer_plan(cfg: WindowMoEConfig) -> tuple[tuple[str, str], ...]:
    """Each layer's ``(attention kind, MLP kind)``: ``"window"`` |
    ``"full"``, ``"dense"`` | ``"moe"``."""
    return tuple(
        (kind, "dense" if li < cfg.first_k_dense else "moe")
        for li, kind in enumerate(cfg.layer_types))


def _numbered(cfg) -> list[tuple[str, int, str]]:
    """The plan with each layer's number AMONG THE LAYERS OF ITS ATTENTION
    KIND (its place in that kind's pool): ``(kind, ki, mlp)``."""
    seen = dict.fromkeys(ATTENTION_KINDS, 0)
    out = []
    for kind, mlp in layer_plan(cfg):
        out.append((kind, seen[kind], mlp))
        seen[kind] += 1
    return out


# -- parameters --------------------------------------------------------------

def _layer_shapes(c: WindowMoEConfig, mlp: str) -> dict:
    """``name -> (shape, init fan-in or None for a norm)`` of one layer,
    by its MLP kind (the attention kinds hold the same tensors).
    Everything is replicated over ``cfg.axis`` (a one-device shard) and
    stored in the layout its GEMM reads."""
    h, d = c.hidden, c.head_dim
    fe, (_, held) = c.expert_ffn, c.held
    out = dict(
        wqkv=((h, c.qkv_dim), h),       # kv-group-major: g q heads | k | v
        wo=((c.q_dim, h), c.q_dim),
        # on the sub-layer's OUTPUT or INPUT (cfg.norm_placement)
        attn_norm=((h,), None),
        mlp_norm=((h,), None),
    )
    if c.qk_norm:
        out.update(q_norm=((d,), None), k_norm=((d,), None))
    if mlp == "dense":
        out.update(w_gate_up=((h, 2 * c.ffn), h), w_down=((c.ffn, h), c.ffn))
        return out
    out.update(
        router=((h, c.n_experts), h),
        # gate | up as contiguous halves: banks are never column-sharded
        we_gate_up=((held, h, 2 * fe), h),
        we_down=((held, fe, h), fe),
    )
    if c.scoring == "sigmoid":
        out.update(router_bias=((c.n_experts,), "bias"))
    if c.n_shared_experts:
        fs = fe * c.n_shared_experts
        out.update(ws_gate_up=((h, 2 * fs), h), ws_down=((fs, h), fs))
    return out


def window_moe_param_specs(cfg: WindowMoEConfig) -> dict:
    layers = [
        {k: P(*([None] * len(shape)))
         for k, (shape, _) in _layer_shapes(cfg, mlp).items()}
        for _, mlp in layer_plan(cfg)
    ]
    return dict(embed=P(None, None), layers=layers, final_norm=P(None),
                lm_head=P(None, None))


def init_window_moe_params(key: jax.Array, cfg: WindowMoEConfig) -> dict:
    """Seeded parameters in the program's layout (tests, toy configs)."""
    def leaf(k, shape, fan_in, dtype=cfg.dtype):
        if fan_in is None:
            return jnp.ones(shape, dtype)
        if fan_in == "bias":
            return jax.random.normal(k, shape, jnp.float32) * 0.01
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    layers = []
    for li, (_, mlp) in enumerate(layer_plan(cfg)):
        shapes = _layer_shapes(cfg, mlp)
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


def slice_vocab(params: dict, first: int, count: int) -> dict:
    """The share of a whole-vocabulary tree that holds rows ``first ..
    first + count - 1`` of embedding and head (token ids then count from
    ``first``)."""
    return dict(params, embed=params["embed"][first:first + count],
                lm_head=params["lm_head"][:, first:first + count])


def pack_qkv(wq, wk, wv, cfg) -> jax.Array:
    """Plain ``wq [H, hq*d]``, ``wk``, ``wv [H, h_kv*d]`` -> the stored
    ``wqkv [H, n_kv*(g+2)*d]`` (each kv group's q heads, then its k, its
    v), the dense family's layout."""
    h, d, n_kv = cfg.hidden, cfg.head_dim, cfg.n_kv_heads
    return jnp.concatenate([
        wq.reshape(h, n_kv, -1), wk.reshape(h, n_kv, d),
        wv.reshape(h, n_kv, d)], axis=-1).reshape(h, -1)


# -- the block's pieces --------------------------------------------------------

def _project(c: WindowMoEConfig, x, p, lead: tuple):
    """``x [m, H]`` -> ``q [*lead, hq, d]``, ``k`` and ``v [*lead, h_kv,
    d]`` (``lead`` multiplies to ``m``), q and k normed where the model
    has the norms; not yet rotated."""
    g, d = c.n_q_heads // c.n_kv_heads, c.head_dim
    qkv = (x @ p["wqkv"]).reshape(*lead, c.n_kv_heads, g + 2, d)
    q = qkv[..., :g, :].reshape(*lead, c.n_q_heads, d)
    k, v = qkv[..., g, :], qkv[..., g + 1, :]
    if c.qk_norm:
        q = rmsnorm(q, p["q_norm"], c.norm_eps)
        k = rmsnorm(k, p["k_norm"], c.norm_eps)
    return q, k, v


def banded_attention(q, k, v, window: int) -> jax.Array:
    """Causal attention in which position ``p`` sees ``p - window + 1 ..
    p``: ``q [b, s, hq, d]``, ``k, v [b, s, h_kv, d]`` -> ``[b, s, hq*d]``.
    Queries go in blocks of ``window`` against their own and the previous
    block of keys, which hold every position a block can see: scores are
    ``[.., s, 2*window]``, never ``[.., s, s]``."""
    b, s, hq, d = q.shape
    h_kv = k.shape[2]
    g, w = hq // h_kv, window
    nb = -(-s // w)
    pad = ((0, 0), (0, nb * w - s), (0, 0), (0, 0))
    f32 = jnp.float32
    qb = jnp.pad(q, pad).reshape(b, nb, w, h_kv, g, d)

    def keys(x):
        """``[b, nb, 2w, h_kv, d]``: block ``i`` = blocks ``i-1 | i``."""
        xb = jnp.pad(x, pad).reshape(b, nb, w, h_kv, d)
        prev = jnp.pad(xb, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
        return jnp.concatenate([prev, xb], axis=2)

    scores = jnp.einsum("bnqhgd,bnkhd->bnhgqk", qb.astype(f32),
                        keys(k).astype(f32)) / math.sqrt(d)
    # within a block pair: query r sits at w + r, key j at j; block 0's
    # "previous" block is padding (true position < 0)
    qp = w + jnp.arange(w)[:, None]
    kp = jnp.arange(2 * w)[None, :]
    band = (kp <= qp) & (kp > qp - w)                          # [w, 2w]
    first = jnp.arange(nb)[:, None, None] > 0                  # [nb, 1, 1]
    mask = band[None] & (first | (kp >= w)[None])              # [nb, w, 2w]
    scores = jnp.where(mask[None, :, None, None], scores, -jnp.inf)
    prob = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bnhgqk,bnkhd->bnqhgd", prob, keys(v).astype(f32))
    return out.reshape(b, nb * w, hq * d)[:, :s].astype(q.dtype)


def _clipping_window(c, kind: str, L: int) -> int | None:
    """The window of a layer of ``kind`` over ``L`` positions; None where
    it attends in full or its window is too wide to clip a row."""
    return c.window if kind == "window" and c.window < L else None


def prefill_attention(c: WindowMoEConfig, kind: str, q, k, v, lens,
                      interpret=None) -> jax.Array:
    """An admission's attention of one layer: ``q [n, L, hq, d]``, ``k, v
    [n, L, h_kv, d]`` -> ``[n, L, hq*d]``. The ONE place that chooses the
    form, from the bucket ``L`` and the window alone (``MATERIALIZED_UP_TO``
    says why): the tiled kernel past it; under it the band where a window
    can clip, the causal square where none can. ``lens [n]`` (true
    lengths) bounds the kernel's walk; the materialized forms compute
    every row of the bucket."""
    L = q.shape[1]
    window = _clipping_window(c, kind, L)
    if L > MATERIALIZED_UP_TO:
        with scope("attn/prefill"):
            return flash_prefill(q, k, v, lens, window=window,
                                 interpret=interpret)
    if window is None:
        return _causal_gqa_attention(q, k, v, c)
    return banded_attention(q, k, v, window)


def _sub_in(c, x, p, norm: str):
    """A sub-layer's input: normed where the block norms its inputs."""
    return (rmsnorm(x, p[norm], c.norm_eps)
            if c.norm_placement == "input" else x)


def _sub_out(c, x, y, p, norm: str):
    """The residual add: ``y`` normed where the block norms its outputs."""
    return x + (rmsnorm(y, p[norm], c.norm_eps)
                if c.norm_placement == "output" else y)


def _route_ahead(c, mlp: str, x, p, block_m: int):
    """The routing of an expert layer whose router reads the LAYER'S
    INPUT ``x`` (un-normed), issued before the layer's attention; None
    where the router reads the MLP's own input."""
    if mlp != "moe" or c.router_rows != "layer_input":
        return None
    with scope("ffn"):
        return route_rows(c, x, p, block_m)


def _mlp(c, mlp: str, x, p, block_m, interpret, stats, routing=None):
    """The MLP sub-layer with its residual, and the pass's routing
    counters."""
    with scope("ffn"):
        h = _sub_in(c, x, p, "mlp_norm")
        if mlp == "dense":
            y = dense_mlp(c, h, p)
        else:
            y, st = moe_mlp(c, h, p, block_m, interpret, routing)
            stats = add_stats(stats, st)
        return _sub_out(c, x, y, p, "mlp_norm"), stats


def _counters(c, stats, rows: int, window_rows, full_rows):
    """The pass's ``pass_counters``: the routing counters, the chosen
    experts that live elsewhere (every one of ``rows`` rows chooses
    ``topk`` in each expert layer), and the attention's key rows."""
    n_moe = sum(mlp == "moe" for _, mlp in layer_plan(c))
    elsewhere = rows * c.topk * n_moe - stats[1]
    return jnp.concatenate([stats, jnp.stack([
        elsewhere, jnp.asarray(window_rows, jnp.int32),
        jnp.asarray(full_rows, jnp.int32)]).astype(jnp.int32)])


# -- the passes ------------------------------------------------------------------

def forward_hidden(cfg: WindowMoEConfig, params, tokens, b: int, s: int,
                   interpret=None, sink=None, lens=None):
    """Forward over ``tokens [b*s]`` (b-major): the final residual
    ``[b*s, H]`` (before the last norm) and the pass's routing counters
    ``int32[3]``. ``sink`` (a list) collects each layer's ``(k, v)``
    ``[b, s, h_kv, d]`` as the pools store them (k normed where the model
    norms it and, on a window layer, rotated). ``lens [b]`` are the
    sequences' true lengths (default ``s``): attention walks no key block
    past them."""
    c = cfg
    positions = jnp.arange(s, dtype=jnp.int32)
    if lens is None:
        lens = jnp.full((b,), s, jnp.int32)
    with scope("head"):
        x = params["embed"][tokens]
    stats = no_stats()
    for (kind, mlp), p in zip(layer_plan(c), params["layers"]):
        routing = _route_ahead(c, mlp, x, p, PREFILL_BLOCK_M)
        with scope("attn"):
            with scope("attn/qkv"):
                q, k, v = _project(
                    c, _sub_in(c, x, p, "attn_norm"), p, (b, s))
            if kind == "window":
                q = rope(q, positions, c.rope_theta)
                k = rope(k, positions, c.rope_theta)
            attn = prefill_attention(c, kind, q, k, v, lens, interpret)
            if sink is not None:
                sink.append((k, v))
            with scope("attn/out"):
                y = attn.reshape(b * s, -1) @ p["wo"]
                x = _sub_out(c, x, y, p, "attn_norm")
        x, stats = _mlp(c, mlp, x, p, PREFILL_BLOCK_M, interpret, stats,
                        routing)
    return x, stats


def forward_logits(cfg: WindowMoEConfig, params, tokens, interpret=None):
    """Whole-sequence logits ``[b, s, V]`` of ``tokens [b, s]`` (tests)."""
    b, s = tokens.shape
    x, _ = forward_hidden(cfg, params, tokens.reshape(-1), b, s, interpret)
    with scope("head"):
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        return (x @ params["lm_head"]).reshape(b, s, -1)


def prefill_cache(cfg: WindowMoEConfig, params, cache, prompt, spec, s_max,
                  slot_mask=None, pick=None, interpret=None):
    """Bulk prefill (inside shard_map, one-device shard) of ``prompt
    [b*L]``. With ``slot_mask`` (an admission) ONLY THE MASKED SLOT'S ROWS
    run, and only its pages and rings are written; without, every slot's.
    A full layer's rows go to the slot's page range, a window layer's last
    ring of rows (counted from the slot's true length, ``pick + 1``) to
    their ring addresses; the head on the picked row of each slot only.
    Returns ``(cache, last [b, V], counters)``; ``last`` holds the rows of
    the slots that ran, zeros elsewhere."""
    require_one_shard(cfg, FAMILY)
    c = cfg
    b, L = c.batch, c.seq
    slots, tokens, pick = admitted_rows(prompt, slot_mask, pick, b, L)
    n = len(slots)
    sink: list = []
    x, stats = forward_hidden(
        c, params, tokens.reshape(-1), n, L, interpret, sink, pick + 1)
    with scope("attn"), scope("attn/kv_write"):
        for (kind, ki, _), (k, v) in zip(_numbered(c), sink):
            cache = spec.write_prompt(
                c, cache, kind, ki, k, v, pick + 1, slots)
    with scope("head"):
        rows = jnp.arange(n, dtype=jnp.int32) * L + pick
        xs = rmsnorm(x[rows], params["final_norm"], c.norm_eps)
        last = last_rows(xs @ params["lm_head"], slots, b)
    return cache, last, _counters(c, stats, n * L, 0, 0)


def decode_step(cfg: WindowMoEConfig, params, cache, tokens, pos, *, spec,
                interpret=None):
    """One ragged decode step (inside shard_map, one-device shard):
    ``(logits [b, V], cache, counters)``. Each slot's new k/v row lands in
    its page first; the kernel then reads the kind's pool."""
    require_one_shard(cfg, FAMILY)
    c = cfg
    b = c.batch
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    # per-sequence rotary position (ragged decode): vmap over the batch
    rope_b = jax.vmap(lambda xi, pi: rope(xi, pi, c.rope_theta))
    with scope("head"):
        x = params["embed"][tokens]
    stats = no_stats()
    for (kind, ki, mlp), p in zip(_numbered(c), params["layers"]):
        routing = _route_ahead(c, mlp, x, p, DECODE_BLOCK_M)
        with scope("attn"):
            with scope("attn/qkv"):
                q, k_new, v_new = _project(
                    c, _sub_in(c, x, p, "attn_norm"), p, (b,))
            if kind == "window":
                q = rope_b(q[:, None], pos_b[:, None])[:, 0]
                k_new = rope_b(k_new[:, None], pos_b[:, None])[:, 0]
            attn, cache = spec.write_and_attend(
                c, cache, kind, ki, k_new, v_new, q, pos_b, interpret)
            with scope("attn/out"):
                y = attn.reshape(b, -1).astype(x.dtype) @ p["wo"]
                x = _sub_out(c, x, y, p, "attn_norm")
        x, stats = _mlp(c, mlp, x, p, DECODE_BLOCK_M, interpret, stats,
                        routing)
    with scope("head"):
        x = rmsnorm(x, params["final_norm"], c.norm_eps)
    lens = jnp.clip(pos_b + 1, 0, spec.s_max)
    kinds = c.layer_types
    with scope("head"):
        logits = x @ params["lm_head"]
    return logits, cache, _counters(
        c, stats, b,
        kinds.count("window") * jnp.sum(jnp.minimum(lens, c.window)),
        kinds.count("full") * jnp.sum(lens))
