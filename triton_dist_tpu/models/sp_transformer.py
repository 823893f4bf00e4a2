"""Context-parallel (SP) transformer: long-context TRAINING on the fused
ring-attention kernel.

The reference's sequence parallelism is decode-only (KV-sharded flash
decode, SURVEY.md §5: "prefill-side ring attention … not implemented");
this model goes past it: the residual stream stays SEQUENCE-SHARDED
end-to-end, attention is the fused ring kernel with its blockwise custom
VJP (ops/grads.ring_attention_grad), and weights are replicated — the
classic context-parallel recipe for sequences too long for one chip's
activation memory. Compose with the Megatron TP model over a 2-D mesh by
nesting shard_maps or choosing per-tensor specs; this module keeps the
pure-SP axis so the long-context math stays legible.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from triton_dist_tpu.models.tp_transformer import (
    TransformerConfig,
    rmsnorm,
    rope,
    unpack_gate_up,
)
from triton_dist_tpu.ops.grads import ring_attention_grad
from triton_dist_tpu.ops.ring_attention import (
    RingAttentionConfig,
    zigzag_positions,
)


@dataclasses.dataclass(frozen=True)
class SPTransformerConfig(TransformerConfig):
    """`axis` names the SEQUENCE axis here; weights replicate over it.

    ``zigzag=True`` uses the causal-load-balanced stripe-pair layout:
    feed tokens/targets PRE-PERMUTED with
    ``ring_attention.zigzag_permutation`` (logits come back in the same
    permuted order) — RoPE positions and the ring's causal mask follow
    automatically."""

    ring_config: RingAttentionConfig | None = None
    zigzag: bool = False


@dataclasses.dataclass
class SPTransformer:
    """Decoder forward on a sequence shard (call inside ``jax.shard_map``
    with tokens sharded ``[b, s_loc]`` over ``cfg.axis``)."""

    cfg: SPTransformerConfig

    def block(self, x: jax.Array, p: dict) -> jax.Array:
        c = self.cfg
        me = jax.lax.axis_index(c.axis)
        b, s_loc, _ = x.shape
        g = c.n_q_heads // c.n_kv_heads
        d = c.head_dim

        h = rmsnorm(x, p["attn_norm"], c.norm_eps)
        qkv = (h @ p["wqkv"]).reshape(
            b, s_loc, c.n_kv_heads, g + 2, d
        )
        # GLOBAL positions for this shard's rows
        if c.zigzag:
            n = int(jax.lax.axis_size(c.axis))
            pos = zigzag_positions(me, n, s_loc)
        else:
            pos = me * s_loc + jnp.arange(s_loc, dtype=jnp.int32)
        q = rope(qkv[..., :g, :].reshape(b, s_loc, c.n_q_heads, d), pos, c.rope_theta)
        k = rope(qkv[..., g, :], pos, c.rope_theta)
        v = qkv[..., g + 1, :]
        # ring attention wants [b, h, s_loc, d]; GQA via kv-head repeat
        q_t = q.transpose(0, 2, 1, 3)
        k_t = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1)
        v_t = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1)
        attn = ring_attention_grad(
            q_t, k_t, v_t, c.axis, True, c.ring_config, c.interpret,
            "zigzag" if c.zigzag else "contig",
        ).transpose(0, 2, 1, 3)                       # [b, s_loc, hq, d]
        x = x + attn.reshape(b, s_loc, c.q_dim) @ p["wo"]

        h = rmsnorm(x, p["mlp_norm"], c.norm_eps)
        gate, up = unpack_gate_up(h @ p["w_gate_up"], c)
        act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
        return x + act @ p["w_down"]

    def __call__(self, tokens_loc: jax.Array, params: dict) -> jax.Array:
        """tokens_loc ``[b, s_loc]`` → logits ``[b, s_loc, vocab]``
        (local rows; the sequence stays sharded end-to-end)."""
        c = self.cfg
        x = params["embed"][tokens_loc]               # [b, s_loc, H]
        for p in params["layers"]:
            x = self.block(x, p)
        x = rmsnorm(x, params["final_norm"], c.norm_eps)
        return x @ params["lm_head"]

    def loss(self, tokens_loc, targets_loc, params) -> jax.Array:
        """Mean CE over the LOCAL rows. The sequence shards PARTITION the
        batch, so the global objective is the sp-mean of these; grads of
        the replicated params assemble as ``psum(g)/n`` (each PE's local
        loss covers disjoint tokens — no double counting)."""
        logits = self(tokens_loc, params).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(logits, targets_loc[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - tl)


def sp_train_step(model: SPTransformer, params, tokens_loc, targets_loc, lr=1e-2):
    """One SGD step (inside shard_map over the sp axis): local-mean loss,
    ``psum/n`` gradient assembly for the replicated params."""
    c = model.cfg
    n = int(jax.lax.axis_size(c.axis))
    loss, grads = jax.value_and_grad(
        lambda p: model.loss(tokens_loc, targets_loc, p)
    )(params)
    loss = jax.lax.pmean(loss, c.axis)
    grads = jax.tree.map(lambda g: jax.lax.psum(g, c.axis) / n, grads)
    params = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype), params, grads)
    return params, loss
