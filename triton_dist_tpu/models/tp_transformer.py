"""Megatron-style TP transformer on the fused distributed kernels.

Parallel layout (the classic column→row scheme the reference's AG-GEMM /
GEMM-RS kernels exist to serve — its perf suite literally sweeps LLaMA/Qwen
projection shapes, test_ag_gemm.py:149-156):

- The residual stream is TOKEN-SHARDED over the ``tp`` axis
  (sequence-parallel Megatron): each PE holds ``[m_loc, H]`` where
  ``m_loc = B*S / tp``.
- Column-parallel projections (QKV, gate/up, LM head) are fused AG-GEMMs:
  the all-gather of the token shard overlaps the MXU ride through
  ``ag_gemm_grad`` (differentiable, backward = fused GEMM-RS).
- Row-parallel projections (attention out, MLP down) are fused GEMM-RS:
  partial products reduce-scatter back to the token shard.
- Attention runs on LOCAL heads over the full (gathered) sequence —
  GQA + RoPE, causal. Long-context prefill can swap in
  ``ops.ring_attention``; decode serves from ``ops.flash_decode``.
- The loss is vocab-parallel cross-entropy: logits stay ``[m, V/tp]``
  sharded, the log-sum-exp and target-logit reductions ride ``psum``/
  ``pmax`` — no PE ever materializes the full logit matrix.

Everything here is called INSIDE ``jax.shard_map`` (see
:func:`train_step` / ``__graft_entry__.dryrun_multichip`` for the jit
plumbing); data parallelism is an outer mesh axis that only the gradient
``pmean`` sees.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, ClassVar

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from triton_dist_tpu.obs.scopes import scope as _scope
from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig
from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig
from triton_dist_tpu.ops.grads import ag_gemm_grad, gemm_rs_grad
from jax.sharding import PartitionSpec as P
from triton_dist_tpu.utils import axis_size as _axis_size


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """LLaMA-class decoder config (≙ the reference's model-shape tables)."""

    vocab: int = 256
    hidden: int = 128
    ffn: int = 256
    n_layers: int = 2
    n_q_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 32
    batch: int = 2
    seq: int = 32
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    axis: str = "tp"
    dtype: Any = jnp.float32
    ag_config: AGGemmConfig | None = None
    rs_config: GemmRSConfig | None = None
    interpret: Any = None

    # What a family answers for itself. These are the answers of the
    # one-kind families this module and models/decode.py serve; a family
    # that walks a layer plan of its own (models/mla_moe.py) sets
    # ``own_passes`` and brings ``param_specs()``, ``decode_step(...)`` and
    # ``prefill_cache(...)`` as methods, which the shared code calls.
    own_passes: ClassVar[bool] = False
    # which kind of paged cache its passes read and write
    # (models/decode.py ``PAGED_CACHE_KINDS``)
    cache_kind: ClassVar[str] = "kv"
    # names of the int32 counters a pass returns after its usual outputs
    pass_counters: ClassVar[tuple[str, ...]] = ()

    def param_bytes(self, params: dict) -> dict:
        """Byte counts of ``params`` worth a counter on
        ``tdt.batcher.take_params``, by name."""
        return {}

    @property
    def q_dim(self) -> int:
        return self.n_q_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def qkv_dim(self) -> int:
        return self.q_dim + 2 * self.kv_dim


def _init_tree(key: jax.Array, cfg: TransformerConfig) -> dict:
    """The parameter tree in its STORED layout. The rule for every weight
    leaf: it is stored as the 2-D matrix its contraction reads, tiled as
    the chip tiles a 2-D array. A reshape of a stored weight inside a step
    is a copy of that weight, made again at every use (``w_gate_up`` as
    ``[H, F, 2]``: a 75.2 ms decode step, 27.3 without, ledger PR 27;
    ``wqkv`` as ``[H, n_kv, (g+2)*d]``: 13.57 ms, 11.82 without, PR 31)."""
    n_mats = cfg.n_layers * 4 + 2
    keys = iter(jax.random.split(key, n_mats))

    def w(shape, scale):
        return (jax.random.normal(next(keys), shape) * scale).astype(cfg.dtype)

    def ones(shape):
        return jnp.ones(shape, cfg.dtype)

    h, f = cfg.hidden, cfg.ffn
    g = cfg.n_q_heads // cfg.n_kv_heads
    layers = []
    for _ in range(cfg.n_layers):
        layers.append(
            dict(
                attn_norm=ones((h,)),
                # QKV as ONE plain matrix [H, n_kv_heads*(g+2)*d], columns
                # KV-GROUP-MAJOR: each group's g query heads, its K head,
                # its V head, contiguous, so a column shard is whole
                # attention groups (Megatron's interleaved QKV; a flat
                # q|k|v concat would hand one PE only K columns). 2-D
                # because its GEMM reads the leaf as stored.
                wqkv=w((h, cfg.n_kv_heads * (g + 2) * cfg.head_dim), h**-0.5),
                # wo rows in the same group-major q-head order
                wo=w((cfg.q_dim, h), cfg.q_dim**-0.5),
                mlp_norm=ones((h,)),
                # gate|up as ONE plain matrix, paired per column block
                # (pack_gate_up): its GEMM reads the leaf as stored
                w_gate_up=w((h, 2 * f), h**-0.5),
                w_down=w((f, h), f**-0.5),
            )
        )
    return dict(
        embed=w((cfg.vocab, h), 0.02),
        layers=layers,
        final_norm=ones((h,)),
        lm_head=w((h, cfg.vocab), h**-0.5),
    )


@functools.lru_cache(maxsize=None)
def _init_program(cfg: TransformerConfig, mesh: Mesh | None):
    """The whole-tree initializer as ONE jitted program per (shapes, mesh):
    with a mesh its outputs carry the :func:`param_specs` shardings, so
    every leaf is born in its final layout."""
    shardings = None if mesh is None else jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), param_specs(cfg),
        is_leaf=lambda x: isinstance(x, P),
    )
    return jax.jit(
        functools.partial(_init_tree, cfg=cfg), out_shardings=shardings
    )


def init_params(
    key: jax.Array, cfg: TransformerConfig, mesh: Mesh | None = None
) -> dict:
    """Parameter pytree, built by one jitted program. With ``mesh`` every
    leaf comes out in its :func:`param_specs` sharding (serving-sized
    models: nothing larger than a shard ever sits on one device — an 8B
    tree built eagerly would sit whole, in f32, on the default device
    before the first ``device_put``). Without it the tree is unsharded on
    the default device — pair with :func:`param_specs` + ``device_put``
    (tests, tiny configs). Same values for the same key either way."""
    # the program depends on shapes, dtype and axis only: drop the kernel
    # configs so the cache key is hashable and shared across them
    shapes = dataclasses.replace(
        cfg, ag_config=None, rs_config=None, interpret=None
    )
    return _init_program(shapes, mesh)(key)


def param_specs(cfg: TransformerConfig) -> dict:
    """PartitionSpecs matching :func:`init_params`: column-parallel weights
    shard dim 1, row-parallel weights shard dim 0, norms/embed replicate.
    Every weight is the plain matrix its GEMM contracts (see
    :func:`_init_tree`), so a column shard is a contiguous run of columns:
    whole kv groups of ``wqkv``, matched gate/up blocks of ``w_gate_up``."""
    t = cfg.axis
    layer = dict(
        attn_norm=P(None),
        wqkv=P(None, t),             # whole kv groups a shard
        wo=P(t, None),               # row-parallel
        mlp_norm=P(None),
        w_gate_up=P(None, t),        # ffn units sharded, gate+up matched
        w_down=P(t, None),           # row-parallel
    )
    return dict(
        embed=P(None, None),
        layers=[dict(layer) for _ in range(cfg.n_layers)],
        final_norm=P(None),
        lm_head=P(None, t),    # vocab-parallel
    )


def _gate_up_block(cfg: TransformerConfig) -> int:
    """Width B of the column blocks gate and up alternate in: one lane
    tile (the activation's split then cuts whole tiles) where every TP
    degree up to 8 keeps whole tiles a PE, else 1 (toy widths; llama-7b's
    11008 and qwen2's 29568, which pay a lane-strided split instead)."""
    return 128 if cfg.ffn % (8 * 128) == 0 else 1


def pack_gate_up(
    w_gate: jax.Array, w_up: jax.Array, cfg: TransformerConfig
) -> jax.Array:
    """``[H, F]`` gate and up -> the stored ``w_gate_up [H, 2F]``: the
    order ``[H, F/B, 2, B]``, flattened. A column shard ``[H, 2F/n]``
    (``param_specs``) then holds matched gate and up units wherever
    ``(F/n) % B == 0``, whatever the TP degree, and packing a shard packs
    its slice of the whole."""
    blk = _gate_up_block(cfg)
    h = w_gate.shape[0]
    pair = [w.reshape(h, -1, blk) for w in (w_gate, w_up)]
    return jnp.concatenate(pair, axis=-1).reshape(h, -1)


def unpack_gate_up(
    x: jax.Array, cfg: TransformerConfig
) -> tuple[jax.Array, jax.Array]:
    """Inverse of :func:`pack_gate_up` over the last axis: ``(gate, up)``
    of a stored weight (or a column shard of it), and of the activation
    ``h @ w_gate_up`` — the only thing the model ever splits."""
    blk = _gate_up_block(cfg)
    if x.shape[-1] % (2 * blk):
        raise ValueError(
            f"{x.shape[-1]} gate/up columns are not whole pairs of "
            f"{blk}-column blocks: ffn={cfg.ffn} is sharded too finely"
        )
    lead = x.shape[:-1]
    pair = x.reshape(*lead, -1, 2 * blk)
    return (pair[..., :blk].reshape(*lead, -1),
            pair[..., blk:].reshape(*lead, -1))


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * r).astype(x.dtype) * scale


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding; x ``[..., s, n_heads, d]``, positions ``[s]``."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, jnp.float32) / d)
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [s, d/2]
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _causal_gqa_attention(q, k, v, cfg: TransformerConfig) -> jax.Array:
    """Local-head causal GQA over the full sequence; q ``[b, s, hq_loc, d]``,
    k/v ``[b, s, hkv_loc, d]``. Plain XLA — after the AG-GEMM gathered the
    sequence, attention is embarrassingly head-parallel and XLA fuses the
    softmax chain; swap in ops.ring_attention for seq-sharded long context."""
    b, s, hq_loc, d = q.shape
    hkv_loc = k.shape[2]
    g = hq_loc // hkv_loc
    qg = q.reshape(b, s, hkv_loc, g, d)
    scores = jnp.einsum(
        "bshgd,bthd->bhgst", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) / jnp.sqrt(jnp.float32(d))
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", p, v.astype(jnp.float32))
    return out.reshape(b, s, hq_loc * d).astype(q.dtype)


@dataclasses.dataclass
class TPTransformer:
    """Decoder-only forward; call INSIDE shard_map with the token stream
    sharded ``[m_loc]`` over ``cfg.axis`` (flattened ``B*S``)."""

    cfg: TransformerConfig

    def _col(self, x, w):
        """Fused column-parallel projection: [m_loc, H] -> [m_tot, N/n]."""
        c = self.cfg
        return ag_gemm_grad(x, w, c.axis, c.ag_config, c.rs_config, c.interpret)

    def _row(self, x, w):
        """Fused row-parallel projection: [m_tot, N/n] -> [m_loc, H]."""
        c = self.cfg
        return gemm_rs_grad(x, w, c.axis, c.rs_config, c.ag_config, c.interpret)

    def block(self, x: jax.Array, p: dict) -> jax.Array:
        c = self.cfg
        n = _axis_size(c.axis)
        b, s = c.batch, c.seq
        hq_loc = c.n_q_heads // n
        hkv_loc = c.n_kv_heads // n

        g = c.n_q_heads // c.n_kv_heads
        d = c.head_dim

        # --- attention ---
        with _scope("attn"):
            h = rmsnorm(x, p["attn_norm"], c.norm_eps)
            with _scope("attn/qkv"):
                qkv = self._col(h, p["wqkv"])
            qkv = qkv.reshape(b, s, hkv_loc, g + 2, d)  # local kv groups
            q = qkv[..., :g, :].reshape(b, s, hq_loc, d)
            k = qkv[..., g, :]
            v = qkv[..., g + 1, :]
            pos = jnp.arange(s, dtype=jnp.int32)
            q = rope(q, pos, c.rope_theta)
            k = rope(k, pos, c.rope_theta)
            if getattr(self, "kv_sink", None) is not None:
                # prefill capture (models/decode.prefill_cache): the
                # post-RoPE per-layer k/v in this PE's head shard,
                # [b, s, hkv_loc, d]
                self.kv_sink.append((k, v))
            attn = _causal_gqa_attention(q, k, v, c)   # [b, s, q_dim/n]
            with _scope("attn/out"):
                x = x + self._row(attn.reshape(b * s, hq_loc * d), p["wo"])

        with _scope("ffn"):
            return x + self._mlp(x, p)

    def _mlp(self, x: jax.Array, p: dict) -> jax.Array:
        """Dense SwiGLU MLP half of the block (overridden by the MoE model)."""
        c = self.cfg
        h = rmsnorm(x, p["mlp_norm"], c.norm_eps)
        with _scope("ffn/gate_up"):
            gu = self._col(h, p["w_gate_up"])              # [m, 2F/n]
        with _scope("ffn/act"):
            gate, up = unpack_gate_up(gu, c)               # [m, F/n]
            act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
        with _scope("ffn/down"):
            return self._row(act, p["w_down"])

    def __call__(self, tokens_loc: jax.Array, params: dict) -> jax.Array:
        """tokens_loc ``[m_loc]`` int32 → vocab-sharded logits
        ``[m_tot, V/n]``."""
        c = self.cfg
        with _scope("head"):
            x = params["embed"][tokens_loc]        # [m_loc, H]
        for p in params["layers"]:
            x = self.block(x, p)
        with _scope("head"):
            x = rmsnorm(x, params["final_norm"], c.norm_eps)
            return self._col(x, params["lm_head"])  # [m_tot, V/n]

    def loss(self, tokens_loc, targets, params) -> jax.Array:
        """Vocab-parallel cross-entropy (no PE sees the full logits):
        ``lse`` and the target logit are assembled with psum/pmax over the
        vocab shards. targets: ``[m_tot]`` int32 (full, replicated)."""
        c = self.cfg
        n = _axis_size(c.axis)
        me = jax.lax.axis_index(c.axis)
        v_loc = c.vocab // n
        logits = self(tokens_loc, params).astype(jnp.float32)  # [m, V/n]
        # the max is a numerical-stability shift whose gradient cancels in
        # the CE algebra; stop_gradient removes it from the backward pass
        # (and pmax has no differentiation rule anyway — ride all_gather)
        m_sh = jax.lax.stop_gradient(
            jnp.max(jax.lax.all_gather(jnp.max(logits, -1), c.axis), 0)  # [m]
        )
        se = jax.lax.psum(jnp.sum(jnp.exp(logits - m_sh[:, None]), -1), c.axis)
        lse = m_sh + jnp.log(se)
        local = targets - me * v_loc
        in_shard = (local >= 0) & (local < v_loc)
        tl = jnp.take_along_axis(
            logits, jnp.clip(local, 0, v_loc - 1)[:, None], axis=1
        )[:, 0]
        target_logit = jax.lax.psum(jnp.where(in_shard, tl, 0.0), c.axis)
        return jnp.mean(lse - target_logit)


@dataclasses.dataclass(frozen=True)
class MoETransformerConfig(TransformerConfig):
    """MoE decoder: dense attention + tensor-parallel expert MLPs
    (≙ the reference's MoE shapes — its AG-GroupGEMM / MoE-Reduce-RS tests
    compose exactly this block inline)."""

    n_experts: int = 8
    topk: int = 2
    gg_config: Any = None  # GroupGemmConfig


def init_moe_params(key: jax.Array, cfg: MoETransformerConfig) -> dict:
    """Like :func:`init_params` but each layer's MLP is a router + expert
    bank (single up-proj + gelu, matching layers.TPMoEMLP)."""
    params = init_params(key, cfg)
    h, f = cfg.hidden, cfg.ffn
    keys = iter(jax.random.split(jax.random.fold_in(key, 1), cfg.n_layers * 3))

    def w(shape, scale):
        return (jax.random.normal(next(keys), shape) * scale).astype(cfg.dtype)

    for p in params["layers"]:
        del p["w_gate_up"], p["w_down"]
        p["router"] = w((h, cfg.n_experts), h**-0.5)
        p["w_up"] = w((cfg.n_experts, h, f), h**-0.5)
        p["w_down"] = w((cfg.n_experts, f, h), f**-0.5)
    return params


def moe_param_specs(cfg: MoETransformerConfig) -> dict:
    specs = param_specs(cfg)
    t = cfg.axis
    for p in specs["layers"]:
        del p["w_gate_up"], p["w_down"]
        p["router"] = P(None, None)
        p["w_up"] = P(None, None, t)    # expert FFN columns sharded
        p["w_down"] = P(None, t, None)  # expert FFN rows sharded
    return specs


def quantize_moe_serving_params(params: dict, fmt: str = "int8") -> dict:
    """Quantize every layer's expert banks for SERVING (weight-only PTQ,
    per-(expert, out-column) scales): replaces ``w_up``/``w_down`` with
    quantized pools and adds ``w_up_scale``/``w_down_scale``.
    ``fmt="int8"`` (``ops.quantize_expert_weights``) halves the
    expert-weight HBM stream that decode-shaped MoE is bound by;
    ``fmt="fp8"`` (``ops.quantize_expert_weights_fp8``, ISSUE 19) quarters
    it on fp8-rate hardware via float8_e4m3 slabs. The model detects the
    quantized keys and dequantizes appropriately per path (post-matmul
    scale on the decode einsums; explicit dequant on the compute-bound
    prefill). Returns a NEW params tree; specs via
    :func:`moe_quantized_param_specs` (scale shapes match across formats)."""
    from triton_dist_tpu.ops.group_gemm import (
        quantize_expert_weights,
        quantize_expert_weights_fp8,
    )

    if fmt not in ("int8", "fp8"):
        raise ValueError(f"fmt must be 'int8' or 'fp8', got {fmt!r}")
    quantize = (
        quantize_expert_weights_fp8 if fmt == "fp8"
        else quantize_expert_weights
    )
    params = dict(params)
    params["layers"] = [dict(p) for p in params["layers"]]
    for p in params["layers"]:
        for name in ("w_up", "w_down"):
            w_q, scale = quantize(p[name])
            p[name] = w_q
            p[name + "_scale"] = scale
    return params


def moe_quantized_param_specs(cfg: MoETransformerConfig) -> dict:
    """Shardings for :func:`quantize_moe_serving_params` output: int8
    pools keep their bank's sharding; scales ``[E, 1, N]`` shard with the
    OUT dimension (w_up's F over the axis; w_down's H replicated)."""
    specs = moe_param_specs(cfg)
    t = cfg.axis
    for p in specs["layers"]:
        p["w_up_scale"] = P(None, None, t)
        p["w_down_scale"] = P(None, None, None)
    return specs


@dataclasses.dataclass
class TPMoETransformer(TPTransformer):
    """MoE decoder: the dense MLP half is replaced by router →
    fused AG-GroupGEMM up, MoE-Reduce-RS down — differentiable end-to-end
    via ``ops.grads.tp_moe_mlp_grad`` (the router trains through the
    routing-weight gradient), so :func:`train_step` works on this variant
    exactly as on the dense model."""

    def _mlp(self, x: jax.Array, p: dict) -> jax.Array:
        from triton_dist_tpu.ops.grads import tp_moe_mlp_grad
        from triton_dist_tpu.ops.moe_utils import select_experts

        c = self.cfg
        h = rmsnorm(x, p["mlp_norm"], c.norm_eps)
        logits = h.astype(jnp.float32) @ p["router"].astype(jnp.float32)
        tw, ids = select_experts(logits, c.topk)
        w_up, w_down = p["w_up"], p["w_down"]
        w_up_scale = w_down_scale = None
        if "w_up_scale" in p:
            if (getattr(c.gg_config, "w8", False)
                    or getattr(c.gg_config, "fp8", False)):
                # scaled-format single-pass serving (ISSUE 8 satellite,
                # fp8 rung ISSUE 19): feed the pre-quantized int8/fp8
                # pools + scales straight through the fused pipeline's
                # scale= operands, skipping BOTH the bf16 materialization
                # below AND resolve_w8's per-call quantize bank read+write
                w_up_scale = p["w_up_scale"]
                w_down_scale = p["w_down_scale"]
            else:
                # serving-quantized experts on the prefill/full-forward
                # path without w8 kernels: explicit dequant — this path is
                # MXU-compute-bound over the whole sequence, so the bf16
                # materialization amortizes (the decode einsums keep the
                # int8 stream; models/decode.py)
                w_up = (
                    w_up.astype(jnp.float32) * p["w_up_scale"]
                ).astype(x.dtype)
                w_down = (
                    w_down.astype(jnp.float32) * p["w_down_scale"]
                ).astype(x.dtype)
        return tp_moe_mlp_grad(
            h, w_up, w_down, ids, tw.astype(jnp.float32),
            c.axis, jax.nn.gelu, c.gg_config, c.interpret, True,
            w_up_scale, w_down_scale,
        ).astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class EPMoETransformerConfig(MoETransformerConfig):
    """Expert-parallel MoE decoder: attention stays TP over ``axis``; the
    FFN experts are WHOLE and spread over the EP world (DeepSeek-style),
    tokens traveling to them over the all-to-all. ``ep_outer=None`` → flat
    EP over ``axis``; set it (e.g. ``"dp"``) for the two-phase hierarchical
    dispatch over ``(ep_outer, axis)``."""

    ep_outer: str | None = None
    # Per-(src, dest) slab cap; None = worst case (never drops). An
    # undersized override silently drops assignments UNLESS
    # ``config.update(debug_ep_overflow=True)`` is set, which NaN-poisons
    # the layer output and reports the dropped count (see
    # ``layers.ep_moe_mlp`` — the flag applies to every EPMoEMLP call,
    # including this model's).
    ep_max_m: int | None = None
    # "int8"/"fp8": quantize the dispatch WIRE (per-row scales on the
    # metadata put — EPAll2AllLayer.quant). Inference only: it cuts the
    # router gradient, so leave None for training.
    ep_quant: str | None = None


def ep_moe_param_specs(cfg: EPMoETransformerConfig) -> dict:
    """Like :func:`moe_param_specs` but experts are sharded on the EXPERT
    dim (each PE holds whole experts) instead of the FFN dim."""
    specs = moe_param_specs(cfg)
    exp_axes = (
        (cfg.ep_outer, cfg.axis) if cfg.ep_outer is not None else cfg.axis
    )
    for p in specs["layers"]:
        p["w_up"] = P(exp_axes, None, None)
        p["w_down"] = P(exp_axes, None, None)
    return specs


def ep_moe_quantized_param_specs(cfg: EPMoETransformerConfig) -> dict:
    """Shardings for :func:`quantize_moe_serving_params` output on the EP
    layout: int8 pools keep the expert-dim sharding; the ``[E, 1, N]``
    scales shard with their experts (derived from the bank spec so the
    two can never diverge)."""
    specs = ep_moe_param_specs(cfg)
    for p in specs["layers"]:
        exp_axes = p["w_up"][0]  # the banks' expert-dim sharding
        p["w_up_scale"] = P(exp_axes, None, None)
        p["w_down_scale"] = P(exp_axes, None, None)
    return specs


@dataclasses.dataclass
class EPMoETransformer(TPMoETransformer):
    """MoE decoder with expert-parallel FFNs: router →
    ``layers.EPMoEMLP`` (EP dispatch a2a, local grouped expert GEMMs,
    push-based weighted combine). Params from :func:`init_moe_params` with
    :func:`ep_moe_param_specs` sharding — inside shard_map each PE sees
    ``[E/world, H, F]`` whole experts. Both layouts train end-to-end: the
    a2a and grouped-GEMM VJPs compose, and the hierarchical dispatch
    carries routing weights in the data slab (a differentiable channel),
    so the router gradient survives both hops."""

    def _mlp(self, x: jax.Array, p: dict) -> jax.Array:
        c = self.cfg
        h = rmsnorm(x, p["mlp_norm"], c.norm_eps)
        # worst-case slab bound: hierarchical phase 1 dedups to at most ONE
        # copy per (token, dest node), so m_loc suffices; flat dispatch can
        # send all topk assignments to one rank
        max_m = c.ep_max_m or (
            x.shape[0] if c.ep_outer is not None else x.shape[0] * c.topk
        )
        return ep_moe_apply(c, h, p, max_m)


def ep_moe_apply(
    cfg: EPMoETransformerConfig, h: jax.Array, p: dict, max_m: int,
    interpret: Any = None,
) -> jax.Array:
    """Router → EP dispatch → expert GEMMs → combine on a token shard —
    ONE implementation shared by the model forward and the serving decode
    (which differ only in how they shard the tokens and bound ``max_m``).
    Serving-quantized expert banks (scale entries present) thread their
    scales through automatically."""
    from triton_dist_tpu.layers.ep_moe_mlp import EPMoEMLP
    from triton_dist_tpu.ops.moe_utils import select_experts

    c = cfg
    logits = h.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    tw, ids = select_experts(logits, c.topk)
    moe = EPMoEMLP(
        n_experts=c.n_experts, topk=c.topk, max_m=max_m,
        axis=c.axis, outer=c.ep_outer,
        inner=c.axis if c.ep_outer is not None else None,
        quant=c.ep_quant, gg_config=c.gg_config,
        interpret=c.interpret if interpret is None else interpret,
    )
    scales = (
        dict(w_up_scale=p["w_up_scale"], w_down_scale=p["w_down_scale"])
        if "w_up_scale" in p  # quantize_moe_serving_params banks
        else {}
    )
    return moe(
        h, p["w_up"], p["w_down"], ids, tw.astype(jnp.float32), **scales
    )


def specs_for(cfg: TransformerConfig, params: dict | None = None) -> dict:
    """Partition specs matching the model variant's param tree. Pass the
    actual `params` when they might be serving-quantized
    (:func:`quantize_moe_serving_params` adds scale entries the spec tree
    must mirror)."""
    if cfg.own_passes:
        return cfg.param_specs()
    quantized = params is not None and params["layers"] and (
        "w_up_scale" in params["layers"][0]
    )
    if isinstance(cfg, EPMoETransformerConfig):
        return ep_moe_quantized_param_specs(cfg) if quantized else (
            ep_moe_param_specs(cfg)
        )
    if isinstance(cfg, MoETransformerConfig):
        return moe_quantized_param_specs(cfg) if quantized else (
            moe_param_specs(cfg)
        )
    return param_specs(cfg)


def opt_state_specs(opt, params, specs):
    """Partition specs for an optax optimizer state: subtrees that mirror
    the param tree (adam's mu/nu, momentum buffers, …) take the param
    specs; everything else (step counts, scalars) replicates. Use to
    device_put / shard_map the state alongside the params."""
    target = jax.tree.structure(params)

    def mirrors(x):
        # structure AND leaf shapes must match — structure alone would
        # mis-classify scalar state (adam's count) when params is itself
        # a single leaf
        if jax.tree.structure(x) != target:
            return False
        return all(
            getattr(xe, "shape", None) == getattr(pe, "shape", None)
            for xe, pe in zip(jax.tree.leaves(x), jax.tree.leaves(params))
        )

    def expand(x):
        if mirrors(x):
            return specs
        return jax.tree.map(lambda _: P(), x)

    state = jax.eval_shape(opt.init, params)
    return jax.tree.map(expand, state, is_leaf=mirrors)


def train_step(
    model: TPTransformer, params, tokens_loc, targets, lr=1e-2,
    dp_axis: str | None = "dp", opt=None, opt_state=None,
    skip_nonfinite: bool = False,
):
    """One optimizer step (call inside shard_map over a ``(dp, tp)`` mesh).
    Default is SGD at `lr`; pass ``opt=`` (any optax transform) and
    ``opt_state=`` for a stateful optimizer — `lr` is then UNUSED (the
    transform carries its own schedule) and the return becomes
    ``(params, opt_state, loss)``. Pass
    ``dp_axis=None`` on a pure-TP mesh, or the data axis's actual name).

    ``skip_nonfinite=True`` (ISSUE 8 containment): gate the update on a
    GLOBAL gradient finiteness check (``ops.grads.grads_all_finite`` over
    the tp and dp axes) — a poisoned step (NaN-storm activations, a
    corrupt collective that slipped past the kernel tiers, a
    NaN-poisoned timed-out op under ``raise_on_timeout=False``) is
    DROPPED whole: params come back bit-identical, optimizer state
    untouched, and one extra traced ``skipped`` int32 flag (1 = dropped)
    is appended to the return for the host loop to count
    (``resilience.integrity.record_skip_step``). A clean step under the
    flag applies exactly the same update as without it — ``jnp.where``
    on an all-true predicate is the identity, bit for bit.

    Gradient accounting (verified against the unsharded reference in
    tests/test_models.py): the per-PE loss is tp-replicated, so
    differentiating inside shard_map effectively differentiates the SUM of
    tp identical losses — every gradient comes back scaled by tp.
    Tensor-parallel params receive that scaled-but-complete gradient
    through the fused kernels' VJPs (each shard participates in every PE's
    loss via the collectives); REPLICATED params (embed, norms) accumulate
    only the paths through this PE's token shard and need a tp-psum.
    Hence: psum replicated grads, divide everything by tp, pmean over dp."""
    c = model.cfg
    if getattr(c, "ep_quant", None) is not None:
        # The quantized dispatch wire zeroes the router gradient (pinned by
        # test_quant_dispatch_grad_is_zero) — training with it set would
        # converge with a dead router, silently. Fail loudly instead.
        raise ValueError(
            "train_step with ep_quant="
            f"{c.ep_quant!r}: the quantized EP dispatch wire is "
            "inference-only (it cuts the router gradient). Train with "
            "ep_quant=None and quantize for serving."
        )
    tp = _axis_size(c.axis)
    loss, grads = jax.value_and_grad(
        lambda p: model.loss(tokens_loc, targets, p)
    )(params)
    if dp_axis is not None:
        loss = jax.lax.pmean(loss, dp_axis)
    specs = specs_for(c)

    def fix(g, spec):
        # flatten composite spec entries like ("dp", "tp") before asking
        # which axes this param is sharded over
        axes: set = set()
        for e in tuple(spec):
            axes.update(e if isinstance(e, (tuple, list)) else (e,))
        if c.axis not in axes:
            g = jax.lax.psum(g, c.axis)
        if dp_axis is not None:
            if dp_axis in axes:
                # dp-SHARDED param (EP expert banks over (dp, tp)): its
                # gradient already sums every dp group's contribution via
                # the a2a transports — a pmean would average in a DIFFERENT
                # expert's gradient from the peer dp rank. Just normalize.
                g = g / _axis_size(dp_axis)
            else:
                g = jax.lax.pmean(g, dp_axis)
        return g / tp

    grads = jax.tree.map(fix, grads, specs)
    ok = None
    if skip_nonfinite:
        from triton_dist_tpu.ops.grads import grads_all_finite

        # the loss rides the check too: a NaN loss with (somehow) finite
        # grads is still not a step anyone wants applied
        ok = grads_all_finite((grads, loss), c.axis, dp_axis)

    def gate(new, old):
        # ok=True is the bitwise identity on `new`; ok=False keeps `old`
        # (params AND optimizer state — a dropped step must be invisible)
        if ok is None:
            return new
        return jax.tree.map(
            lambda a, b: a if getattr(a, "dtype", None) is None
            else jnp.where(ok, a, b),
            new, old,
        )

    skipped = (
        None if ok is None
        else jnp.logical_not(ok).astype(jnp.int32)
    )
    if opt is not None:
        # any optax transform; state sharding via opt_state_specs. Returns
        # (params, opt_state, loss) in this mode (+ skipped when gated).
        import optax

        updates, new_opt_state = opt.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        params = gate(new_params, params)
        opt_state = gate(new_opt_state, opt_state)
        if skipped is None:
            return params, opt_state, loss
        return params, opt_state, loss, skipped
    new_params = jax.tree.map(
        lambda p, g: p - lr * g.astype(p.dtype), params, grads
    )
    params = gate(new_params, params)
    if skipped is None:
        return params, loss
    return params, loss, skipped
