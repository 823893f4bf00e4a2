"""Decoder whose layers mix tokens through POWER RETENTION, a linear
attention with a MATRIX state (Manifest AI's Brumby-14B-Base: the Qwen3-14B
block with its softmax attention replaced, arXiv 2507.04239), served
through the same batcher and spans as the other families.

Every layer is the same, so the LAYER PLAN (:func:`layer_plan`) names one
kind, ``retention``. What a layer carries between tokens is a STATE, not
rows: ``S [D, d]`` and ``Z [d, d]`` a kv head, float32, the same size
whatever the context and no page at all (``cache_kind = "state"``,
``models/decode.py`` ``RetentionStateCacheSpec``).

Equations (``x [T, H]``; RMSNorm everywhere; norms, gates, the retention's
sums and its state in f32; weights and activations ``cfg.dtype``; no bias on
q, k, v, o):

- ``u = norm_in(x)``; ``q = u W_q -> [T, h_q, d]``, ``k = u W_k``, ``v = u
  W_v -> [T, h_kv, d]`` as one kv-group-major ``wqkv`` (the dense family's
  layout); ``q``, ``k`` each RMS-normed over ``d`` with a learned weight
  (``q_norm``, ``k_norm``), then rotated (``rope_theta``, halves). Query
  head ``h`` reads kv head ``h // g``.
- gate: ``log g_t = logsigmoid(u_t W_g + b_g)  [T, h_kv]``, f32, one a kv
  head; ``G_t = sum_{l <= t} log g_l``.
- power retention of degree ``p = 2``, ``s = d ** -0.5``: for ``j <= i``,
  ``a_ij = (s q_i . k_j)^2 exp(G_i - G_j)``; ``y_i = sum_j a_ij v_j / (sum_j
  a_ij + eps)``. No softmax, no maximum: ``a_ij >= 0``.
- the same as a recurrence, which is what is served: ``phi(x) . phi(y) =
  (x . y)^2`` (the symmetric square in the tiled row order of
  ``ops/retention.py``: ``D = 8704`` rows at ``d = 128``); ``S_t = g_t
  S_{t-1} + phi(k_t) v_t^T``, ``Z_t = g_t Z_{t-1} + k_t k_t^T`` (the
  normaliser's ``z = sum phi(k)`` as the matrix it re-orders), ``S_{-1} =
  Z_{-1} = 0``; ``y_t = phi(s q_t)^T S_t / (s^2 q_t^T Z_t q_t + eps)``.
- ``x = x + concat_h(y) W_o``; then ``x = x + mlp(norm_ff(x))``, ``mlp(n) =
  (silu(n W_gate) * (n W_up)) W_down`` (``gated_experts.dense_mlp`` on the
  stored ``[H, 2F]`` leaf). After the last layer ``norm_f`` and an UNTIED
  head ``lm_head [H, V]``.

DECODE walks every slot one token on through ``RetentionStateCacheSpec.
state_step`` (the kernel ``retention_update``). PREFILL (an admission)
computes THE ADMITTED SLOT'S ROWS ONLY, ``[1, bucket]``, the slot found
from ``slot_mask`` inside the pass, through the chunked kernel
``retention_prefill`` from an empty state, and overwrites that slot's ``S``
and ``Z`` whole and no other's. The scan stops at the prompt's true length:
a padded row has ``log g = 0`` and ``k = 0``, which leaves the state as it
was. Without a mask (``generate``) every slot's rows run.

Serving runs this family on a ONE-device shard: a state sharded over kv
heads is not built, and the entry points refuse a wider axis by name.
"""

from __future__ import annotations

import dataclasses
from typing import ClassVar

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.models.gated_experts import (
    admitted_rows, dense_mlp, last_rows, require_one_shard,
)
from triton_dist_tpu.models.tp_transformer import (
    TransformerConfig, rmsnorm, rope,
)
from triton_dist_tpu.obs.scopes import scope
from triton_dist_tpu.ops.retention import (
    chunk_len, retention_prefill, state_rows,
)

FAMILY = "power-retention"
NOT_BUILT = "a slot's state sharded over kv heads"
# -log g a head at initialisation: memories of 100 to 10,000 tokens
FORGET_RANGE = (1e-4, 1e-2)
# rows of a prompt pass an MLP takes at a time: its ``[rows, 2F]`` gate|up
# is the pass's largest temporary (0.57 GB at a bucket of 8192 and the
# published widths), and 16 slots of state leave it no such room
MLP_ROWS = 2048


@dataclasses.dataclass(frozen=True)
class RetentionConfig(TransformerConfig):
    power: int = 2

    own_passes: ClassVar[bool] = True
    cache_kind: ClassVar[str] = "state"
    # slots whose state a pass advanced (a step advances EVERY slot of the
    # batch, idle ones too: it is not told which are live) and the chunks
    # an admission's scan walked (from its prompt's true length; 0 on a
    # step)
    pass_counters: ClassVar[tuple[str, ...]] = ("state_slots", "prompt_chunks")
    # the MLPs' gate, as gated_experts.dense_mlp reads it
    gate_act: ClassVar[str] = "silu"

    def __post_init__(self):
        if self.power != 2:
            raise NotImplementedError(
                f"power={self.power}: the state's rows are the symmetric "
                f"SQUARE of a key (ops/retention.py); another degree is "
                f"another row order and another kernel")
        state_rows(self.head_dim)       # whole blocks of 8, or refused

    @property
    def state_rows(self) -> int:
        """``D``: rows of a kv head's ``S``."""
        return state_rows(self.head_dim)

    def state_bytes(self) -> int:
        """Bytes of the state pools (``s`` and ``z``) of
        ``RetentionStateCacheSpec`` over ``batch`` slots, both parities."""
        d = self.head_dim
        per_slot = self.n_kv_heads * (self.state_rows + d) * d * 4
        return self.n_layers * 2 * self.batch * per_slot

    # the family's answers to the shared serving code (own_passes)
    def param_specs(self) -> dict:
        return retention_param_specs(self)

    def param_bytes(self, params: dict) -> dict:
        return dict(state_bytes=self.state_bytes())

    def decode_step(self, params, cache, tokens, pos, *, spec, interpret=None):
        return decode_step(self, params, cache, tokens, pos, spec=spec,
                           interpret=interpret)

    def prefill_cache(self, params, cache, prompt, spec, s_max, **kw):
        return prefill_cache(self, params, cache, prompt, spec, s_max, **kw)


def layer_plan(cfg: RetentionConfig) -> tuple[str, ...]:
    """Each layer's mixer kind: all ``"retention"``."""
    return ("retention",) * cfg.n_layers


# -- parameters --------------------------------------------------------------

def _layer_shapes(c: RetentionConfig) -> dict:
    """``name -> (shape, init)`` of one layer; ``init`` is a fan-in, or a
    name :func:`init_retention_params` knows. Everything is replicated over
    ``cfg.axis`` (a one-device shard) and stored in the layout its GEMM
    reads."""
    h, d = c.hidden, c.head_dim
    return dict(
        norm_in=((h,), "norm"), norm_ff=((h,), "norm"),
        wqkv=((h, c.qkv_dim), h),       # kv-group-major: q heads | k | v
        q_norm=((d,), "norm"), k_norm=((d,), "norm"),
        w_g=((h, c.n_kv_heads), "gate"), b_g=((c.n_kv_heads,), "gate_bias"),
        wo=((c.q_dim, h), c.q_dim),
        w_gate_up=((h, 2 * c.ffn), h), w_down=((c.ffn, h), c.ffn),
    )


def retention_param_specs(cfg: RetentionConfig) -> dict:
    layer = {k: P(*([None] * len(shape)))
             for k, (shape, _) in _layer_shapes(cfg).items()}
    return dict(embed=P(None, None), layers=[dict(layer)
                                             for _ in range(cfg.n_layers)],
                final_norm=P(None), lm_head=P(None, None))


def init_retention_params(key: jax.Array, cfg: RetentionConfig) -> dict:
    """Seeded parameters in the program's layout (tests, toy configs). The
    gate's initialisation is part of the model: ``b_g`` such that ``-log
    g`` is log-uniform in :data:`FORGET_RANGE` a head and ``W_g`` small, so
    that the state remembers."""
    def leaf(k, shape, init):
        if init == "norm":
            return jnp.ones(shape, cfg.dtype)
        if init == "gate":
            return (jax.random.normal(k, shape) * 0.1 * shape[0] ** -0.5
                    ).astype(cfg.dtype)
        if init == "gate_bias":
            lo, hi = (jnp.log(x) for x in FORGET_RANGE)
            forget = jnp.exp(jax.random.uniform(k, shape, minval=lo, maxval=hi))
            # logsigmoid(b) = -forget
            return -jnp.log(jnp.expm1(forget)).astype(cfg.dtype)
        return (jax.random.normal(k, shape, jnp.float32)
                * init ** -0.5).astype(cfg.dtype)

    shapes = _layer_shapes(cfg)
    layers = []
    for li in range(cfg.n_layers):
        keys = jax.random.split(jax.random.fold_in(key, li + 1), len(shapes))
        layers.append({name: leaf(k, shape, init)
                       for k, (name, (shape, init)) in zip(keys, shapes.items())})
    k_embed, k_head = jax.random.split(jax.random.fold_in(key, 0))
    return dict(
        embed=(jax.random.normal(k_embed, (cfg.vocab, cfg.hidden)) * 0.02
               ).astype(cfg.dtype),
        layers=layers,
        final_norm=jnp.ones((cfg.hidden,), cfg.dtype),
        lm_head=(jax.random.normal(k_head, (cfg.hidden, cfg.vocab))
                 * cfg.hidden ** -0.5).astype(cfg.dtype),
    )


# -- the block's pieces --------------------------------------------------------

def _project(c: RetentionConfig, u, p, positions):
    """``u [m, H]`` (normed) at ``positions [m]`` -> ``q [m, h_q, d]``,
    ``k`` and ``v [m, h_kv, d]``, ``q`` and ``k`` head-normed and rotated;
    nothing is scaled (the kernels hold ``s``)."""
    g, d = c.n_q_heads // c.n_kv_heads, c.head_dim
    with scope("retn/qkv"):
        qkv = (u @ p["wqkv"]).reshape(-1, c.n_kv_heads, g + 2, d)
        q = qkv[:, :, :g].reshape(-1, c.n_q_heads, d)
        k, v = qkv[:, :, g], qkv[:, :, g + 1]
        q = rope(rmsnorm(q, p["q_norm"], c.norm_eps), positions, c.rope_theta)
        k = rope(rmsnorm(k, p["k_norm"], c.norm_eps), positions, c.rope_theta)
    return q, k, v


def _log_gate(u, p):
    """``log g [m, h_kv]`` float32, ``<= 0``."""
    with scope("retn/gate"):
        return jax.nn.log_sigmoid(
            jnp.dot(u, p["w_g"], preferred_element_type=jnp.float32)
            + p["b_g"].astype(jnp.float32))


def _out(c, y, p):
    """``concat_h(y) W_o`` of ``y [m, h_q, d]`` float32."""
    with scope("retn/out"):
        return y.reshape(-1, c.q_dim).astype(p["wo"].dtype) @ p["wo"]


def _mlp(c, x, p):
    """``x + mlp(norm_ff(x))`` over ``x [m, H]``, :data:`MLP_ROWS` rows at
    a time where ``m`` is whole blocks of them."""
    with scope("ffn"):
        block = lambda rows: rows + dense_mlp(
            c, rmsnorm(rows, p["norm_ff"], c.norm_eps), p)
        m = x.shape[0]
        if m <= MLP_ROWS or m % MLP_ROWS:
            return block(x)
        return jax.lax.map(block, x.reshape(-1, MLP_ROWS, x.shape[1])
                           ).reshape(x.shape)


def _counters(state_slots, prompt_chunks):
    return jnp.stack([jnp.asarray(state_slots, jnp.int32),
                      jnp.asarray(prompt_chunks, jnp.int32)])


# -- the passes ------------------------------------------------------------------

def forward_hidden(cfg: RetentionConfig, params, tokens, lens=None,
                   interpret=None, sink=None):
    """Forward over ``tokens [n, L]``: the final residual ``[n, L, H]``
    (before the last norm). ``lens [n]`` are the true lengths (default
    ``L``). ``sink`` (a list) collects what each layer leaves in the cache:
    ``(S [n, h_kv, D, d], Z [n, h_kv, d, d])``, the state after each
    prompt's LAST TRUE token."""
    c = cfg
    n, L = tokens.shape
    if lens is None:
        lens = jnp.full((n,), L, jnp.int32)
    true = (jnp.arange(L) < lens[:, None]).reshape(n * L, 1)
    positions = jnp.tile(jnp.arange(L), n)
    with scope("head"):
        x = params["embed"][tokens.reshape(-1)]
    for p in params["layers"]:
        with scope("retn"):
            u = rmsnorm(x, p["norm_in"], c.norm_eps)
            q, k, v = _project(c, u, p, positions)
            # the scan stops at the prompt's end: k = 0 and log g = 0 leave
            # the state as it was
            k = jnp.where(true[..., None], k, 0)
            log_g = jnp.where(true, _log_gate(u, p), 0.0)
            rows = lambda a: a.reshape(n, L, *a.shape[1:])
            with scope("retn/prefill"):
                y, s, z = zip(*(
                    retention_prefill(*(rows(a)[i] for a in (q, k, v, log_g)),
                                      interpret=interpret) for i in range(n)))
            if sink is not None:
                sink.append((jnp.stack(s), jnp.stack(z)))
            x = x + _out(c, jnp.concatenate(y), p)
        x = _mlp(c, x, p)
    return x.reshape(n, L, -1)


def _head(cfg, params, x):
    """Logits of rows ``x [m, H]`` through the untied head."""
    with scope("head"):
        return rmsnorm(x, params["final_norm"], cfg.norm_eps) @ params["lm_head"]


def forward_logits(cfg: RetentionConfig, params, tokens, interpret=None):
    """Whole-sequence logits ``[n, L, V]`` of ``tokens [n, L]`` (tests)."""
    n, L = tokens.shape
    x = forward_hidden(cfg, params, tokens, interpret=interpret)
    return _head(cfg, params, x.reshape(n * L, -1)).reshape(n, L, -1)


def prefill_cache(cfg: RetentionConfig, params, cache, prompt, spec, s_max,
                  slot_mask=None, pick=None, interpret=None):
    """Bulk prefill (inside shard_map, one-device shard) of ``prompt
    [b*L]``. With ``slot_mask`` (an admission) ONLY THE MASKED SLOT'S ROWS
    run, and only its state is written; without, every slot's. Each
    prompt's true length is ``pick + 1``: the scan stops there and the head
    reads that row. Returns ``(cache, last [b, V], counters)``; ``last``
    holds the rows of the slots that ran, zeros elsewhere."""
    require_one_shard(cfg, FAMILY, NOT_BUILT)
    c = cfg
    b, L = c.batch, c.seq
    slots, tokens, pick = admitted_rows(prompt, slot_mask, pick, b, L)
    sink: list = []
    x = forward_hidden(c, params, tokens, pick + 1, interpret, sink)
    for li, (s, z) in enumerate(sink):
        with scope("retn"):
            cache = spec.write_state(cache, li, slots, pick + 1, s, z)
    rows = _head(c, params, x[jnp.arange(len(slots)), pick])
    chunks = -(-(pick + 1) // chunk_len(c.head_dim))
    return cache, last_rows(rows, slots, b), _counters(
        len(slots), c.n_layers * jnp.sum(chunks))


def decode_step(cfg: RetentionConfig, params, cache, tokens, pos, *, spec,
                interpret=None):
    """One ragged decode step (inside shard_map, one-device shard):
    ``(logits [b, V], cache, counters)``. A function of ``(tokens, pos)``
    and of the state after ``pos - 1``: run twice on the same inputs it
    leaves the same cache (``RetentionStateCacheSpec``)."""
    require_one_shard(cfg, FAMILY, NOT_BUILT)
    c = cfg
    b = c.batch
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    with scope("head"):
        x = params["embed"][tokens]
    for li, p in enumerate(params["layers"]):
        with scope("retn"):
            u = rmsnorm(x, p["norm_in"], c.norm_eps)
            q, k, v = _project(c, u, p, pos_b)
            y, cache = spec.state_step(cache, li, q, k, v, _log_gate(u, p),
                                       pos_b, interpret)
            x = x + _out(c, y, p)
        x = _mlp(c, x, p)
    return _head(c, params, x), cache, _counters(b, 0)
