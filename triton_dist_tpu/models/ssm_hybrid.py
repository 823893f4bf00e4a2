"""Decoder whose layers mix tokens through a selective STATE-SPACE
recurrence (Mamba-1) or, a few of them, through ATTENTION: the Jamba
family's block (AI21-Jamba2-3B: 26 state-space and 2 multi-query attention
layers of 28), served through the same batcher, block tables and spans as
the other families.

The LAYER PLAN (:func:`layer_plan`) names the MIXER kind of each layer:
``attention`` where ``i % attn_layer_period == attn_layer_offset``,
``mamba`` otherwise. Parameters, their specs, prefill and the decode step
walk it. The two kinds keep what they carry between tokens in one cache
(``cache_kind = "kv_state"``, ``models/decode.py``
``StatePagedKVCacheSpec``): an attention layer's keys and values in pages,
a state-space layer's recurrent state and convolution tail in a row of its
SLOT, float32, the same size whatever the context.

Equations (``x [T, H]``; RMSNorm everywhere; norms, softmax, softplus, the
recurrence and its state in f32):

- every layer: ``x = x + mixer(norm_in(x))``, then ``x = x +
  mlp(norm_ff(x))``, ``mlp(u) = (silu(u W_g) * (u W_u)) W_d``, no bias
  (``gated_experts.dense_mlp`` on the stored ``[H, 2F]`` leaf). After the
  last layer ``norm_f``, then logits ``= x E^T`` with ``E`` the embedding:
  the head is TIED, one leaf read by the lookup and by the head.
- MAMBA mixer (``d = d_inner``, ``N = d_state``, ``R = dt_rank``):
  ``[u | z] = x W_in`` (no bias); ``c_t = silu(b_conv + sum_{j<K} w_conv[j]
  * u_{t-K+1+j})`` (depthwise, causal, ``K = d_conv``, ``u`` = 0 before the
  start); ``[r | B | C] = c W_x`` (no bias); ``r, B, C`` each RMS-normed;
  ``dt = softplus(r W_dt + b_dt)``; ``A = -exp(A_log)``; ``h_t = exp(dt_t
  * A) * h_{t-1} + (dt_t * c_t) * B_t``, ``h_{-1} = 0``; ``y_t = h_t C_t +
  D * c_t``; ``out = (y * silu(z)) W_out`` (no bias). ``A_log`` and the
  state are stored ``[N, d]``, channels on the lanes
  (``ops/selective_scan.py``), the convolution's taps ``[K, d]``.
- ATTENTION mixer: ``q = x W_q``, ``k = x W_k``, ``v = x W_v`` as one
  kv-group-major ``wqkv`` (the dense family's layout), no bias, NO
  rotation and no positional term of any kind; scores ``q.k / sqrt(d)``,
  causal; ``softmax(s) v W_o``.

DECODE walks every slot one token on: a state-space layer through
``StatePagedKVCacheSpec.conv_step`` / ``state_step`` (the kernels
``conv_ring_step`` and ``selective_state_update``, each handed the
layer's per-channel vectors as they are stored), an attention layer
through ``paged_flash_decode``. PREFILL (an admission) computes THE ADMITTED
SLOT'S ROWS ONLY, ``[1, bucket]``, the slot found from ``slot_mask``
inside the pass, and writes that slot's state and pages and no other's:
the other slots are mid-sequence, and a whole batch of buckets is ``slots``
times the work. The scan (``selective_scan``) stops at the prompt's true
length: ``dt`` is 0 at padded positions, which leaves the state as it was.
Without a mask (``generate``) every slot's rows run.

Serving runs this family on a ONE-device shard: a state sharded over
channels is not built, and the entry points refuse a wider axis by name.
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
    TransformerConfig, _causal_gqa_attention, rmsnorm,
)
from triton_dist_tpu.obs.scopes import scope
from triton_dist_tpu.ops.selective_scan import selective_scan

MIXER_KINDS = ("mamba", "attention")
# the part of the layer a mixer of each kind is (obs/scopes.py)
MIXER_SCOPES = {"mamba": "ssm", "attention": "attn"}
FAMILY = "state-space / attention"
NOT_BUILT = "a slot's state sharded over channels"


@dataclasses.dataclass(frozen=True)
class SSMHybridConfig(TransformerConfig):
    """``rope_theta`` is inherited and unused: the family does not rotate."""

    attn_layer_period: int = 2
    attn_layer_offset: int = 1
    d_inner: int = 256      # mamba_expand * hidden
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 8

    own_passes: ClassVar[bool] = True
    cache_kind: ClassVar[str] = "kv_state"
    # slots whose state a pass advanced (a step advances EVERY slot of the
    # batch, idle ones too: it is not told which are live) and the key
    # rows its attention layers read (from the slots' lengths; 0 on an
    # admission's pass)
    pass_counters: ClassVar[tuple[str, ...]] = ("state_slots", "kv_rows")
    # the MLPs' gate, as gated_experts.dense_mlp reads it
    gate_act: ClassVar[str] = "silu"

    def __post_init__(self):
        if not 0 <= self.attn_layer_offset < self.attn_layer_period:
            raise ValueError(
                f"attn_layer_offset={self.attn_layer_offset} outside the "
                f"period of {self.attn_layer_period}")
        if self.d_conv < 2:
            raise ValueError(f"d_conv={self.d_conv} must be >= 2")

    @property
    def layer_kinds(self) -> tuple[str, ...]:
        return layer_plan(self)

    def state_bytes(self) -> int:
        """Bytes of the state pools (``ssm`` and ``conv``) of
        ``StatePagedKVCacheSpec`` over ``batch`` slots."""
        per_slot = self.d_inner * (2 * self.d_state + self.d_conv) * 4
        return self.layer_kinds.count("mamba") * self.batch * per_slot

    # the family's answers to the shared serving code (own_passes)
    def param_specs(self) -> dict:
        return ssm_hybrid_param_specs(self)

    def param_bytes(self, params: dict) -> dict:
        return dict(state_bytes=self.state_bytes())

    def decode_step(self, params, cache, tokens, pos, *, spec, interpret=None):
        return decode_step(self, params, cache, tokens, pos, spec=spec,
                           interpret=interpret)

    def prefill_cache(self, params, cache, prompt, spec, s_max, **kw):
        return prefill_cache(self, params, cache, prompt, spec, s_max, **kw)


def layer_plan(cfg: SSMHybridConfig) -> tuple[str, ...]:
    """Each layer's mixer kind: ``"mamba"`` | ``"attention"``."""
    return tuple(
        "attention" if li % cfg.attn_layer_period == cfg.attn_layer_offset
        else "mamba" for li in range(cfg.n_layers))


def _numbered(cfg) -> list[tuple[str, int]]:
    """The plan with each layer's number AMONG THE LAYERS OF ITS KIND (its
    place in that kind's pools): ``(kind, ki)``."""
    seen = dict.fromkeys(MIXER_KINDS, 0)
    out = []
    for kind in layer_plan(cfg):
        out.append((kind, seen[kind]))
        seen[kind] += 1
    return out


# -- parameters --------------------------------------------------------------

def _layer_shapes(c: SSMHybridConfig, kind: str) -> dict:
    """``name -> (shape, init)`` of one layer by its mixer kind; ``init``
    is a fan-in, or a name :func:`init_ssm_hybrid_params` knows. Everything
    is replicated over ``cfg.axis`` (a one-device shard) and stored in the
    layout its GEMM or kernel reads."""
    h, d, n, r = c.hidden, c.d_inner, c.d_state, c.dt_rank
    out = dict(norm_in=((h,), "norm"), norm_ff=((h,), "norm"),
               w_gate_up=((h, 2 * c.ffn), h), w_down=((c.ffn, h), c.ffn))
    if kind == "attention":
        out.update(wqkv=((h, c.qkv_dim), h),    # kv-group-major: q heads | k | v
                   wo=((c.q_dim, h), c.q_dim))
    else:
        out.update(
            w_in=((h, 2 * d), h),               # u | z
            conv_w=((c.d_conv, d), c.d_conv),   # tap-major; the last is newest
            conv_b=((d,), "bias"),
            w_x=((d, r + 2 * n), d),            # r | B | C
            dt_norm=((r,), "norm"), b_norm=((n,), "norm"),
            c_norm=((n,), "norm"),
            w_dt=((r, d), r), b_dt=((d,), "dt_bias"),
            a_log=((n, d), "a_log"), d_skip=((d,), "norm"),
            w_out=((d, h), d),
        )
    return out


def ssm_hybrid_param_specs(cfg: SSMHybridConfig) -> dict:
    layers = [
        {k: P(*([None] * len(shape)))
         for k, (shape, _) in _layer_shapes(cfg, kind).items()}
        for kind in layer_plan(cfg)
    ]
    return dict(embed=P(None, None), layers=layers, final_norm=P(None))


def init_ssm_hybrid_params(key: jax.Array, cfg: SSMHybridConfig) -> dict:
    """Seeded parameters in the program's layout (tests, toy configs):
    Mamba's published initialisation for ``A_log``, ``D`` and the step's
    bias (``dt`` log-uniform in ``[1e-3, 1e-1]``), so that the state
    remembers."""
    def leaf(k, shape, init):
        if init == "norm":
            return jnp.ones(shape, cfg.dtype)
        if init == "bias":
            return (jax.random.normal(k, shape) * 0.01).astype(cfg.dtype)
        if init == "a_log":
            n = jnp.arange(1, shape[0] + 1, dtype=jnp.float32)
            return jnp.broadcast_to(jnp.log(n)[:, None], shape).astype(cfg.dtype)
        if init == "dt_bias":
            dt0 = jnp.exp(jax.random.uniform(
                k, shape, minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
            return jnp.log(jnp.expm1(dt0)).astype(cfg.dtype)
        return (jax.random.normal(k, shape, jnp.float32)
                * init ** -0.5).astype(cfg.dtype)

    layers = []
    for li, kind in enumerate(layer_plan(cfg)):
        shapes = _layer_shapes(cfg, kind)
        keys = jax.random.split(jax.random.fold_in(key, li + 1), len(shapes))
        layers.append({name: leaf(k, shape, init)
                       for k, (name, (shape, init)) in zip(keys, shapes.items())})
    return dict(
        embed=(jax.random.normal(jax.random.fold_in(key, 0),
                                 (cfg.vocab, cfg.hidden)) * 0.02
               ).astype(cfg.dtype),
        layers=layers,
        final_norm=jnp.ones((cfg.hidden,), cfg.dtype),
    )


# -- the block's pieces --------------------------------------------------------

def _f32(x):
    return x.astype(jnp.float32)


def _project(c: SSMHybridConfig, x, p, lead: tuple):
    """``x [m, H]`` -> ``q [*lead, hq, d]``, ``k`` and ``v [*lead, h_kv,
    d]`` (``lead`` multiplies to ``m``); nothing is rotated."""
    g, d = c.n_q_heads // c.n_kv_heads, c.head_dim
    qkv = (x @ p["wqkv"]).reshape(*lead, c.n_kv_heads, g + 2, d)
    q = qkv[..., :g, :].reshape(*lead, c.n_q_heads, d)
    return q, qkv[..., g, :], qkv[..., g + 1, :]


def _split_in(c, x, p):
    """``x [m, H]`` -> the convolution's input ``u`` (f32) and the gate
    ``z``, ``[m, d]`` each."""
    with scope("ssm/proj"):
        uz = x @ p["w_in"]
    return _f32(uz[..., :c.d_inner]), uz[..., c.d_inner:]


def _scan_inputs(c, conv, p):
    """The convolution's output ``conv [m, d]`` (f32, bias added, not yet
    activated) -> what the recurrence reads, all f32: ``(c, dt_in [m, d],
    B, C [m, N], A [N, d])``; ``dt_in`` is the step BEFORE its bias and
    softplus (``dt = softplus(dt_in + b_dt)``: in a decode step the
    recurrence kernel's prologue, which takes ``b_dt`` and ``d_skip`` as
    stored)."""
    n, r, eps = c.d_state, c.dt_rank, c.norm_eps
    with scope("ssm/proj"):
        act = jax.nn.silu(conv)
        rbc = jnp.dot(act.astype(p["w_x"].dtype), p["w_x"],
                      preferred_element_type=jnp.float32)
        dt_n = rmsnorm(rbc[..., :r], _f32(p["dt_norm"]), eps)
        b_in = rmsnorm(rbc[..., r:r + n], _f32(p["b_norm"]), eps)
        c_out = rmsnorm(rbc[..., r + n:], _f32(p["c_norm"]), eps)
        dt_in = jnp.dot(dt_n.astype(p["w_dt"].dtype), p["w_dt"],
                        preferred_element_type=jnp.float32)
        return act, dt_in, b_in, c_out, -jnp.exp(_f32(p["a_log"]))


def _gate_out(y, z, p):
    """``(y * silu(z)) W_out``."""
    with scope("ssm/proj"):
        gated = y * jax.nn.silu(_f32(z))
        return gated.astype(p["w_out"].dtype) @ p["w_out"]


def _mamba_prompt(c: SSMHybridConfig, x, p, lens, interpret):
    """The state-space mixer over whole prompts ``x [n, L, H]`` of ``lens
    [n]`` true tokens: ``(out [n, L, H], u [n, L, d], h [n, N, d])``, ``h``
    the state after each prompt's LAST TRUE token."""
    n, L, _ = x.shape
    K = c.d_conv
    u, z = _split_in(c, x, p)
    with scope("ssm/conv"):
        w = _f32(p["conv_w"])
        padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
        conv = _f32(p["conv_b"]) + sum(
            w[j] * padded[:, j:j + L] for j in range(K))
    act, dt_in, b_in, c_out, a = _scan_inputs(c, conv, p)
    with scope("ssm/proj"):
        dt = jax.nn.softplus(dt_in + _f32(p["b_dt"]))
    # the scan stops at the prompt's end: dt = 0 leaves the state as it was
    dt = jnp.where((jnp.arange(L) < lens[:, None])[..., None], dt, 0.0)
    d_skip = _f32(p["d_skip"])
    h0 = jnp.zeros((c.d_state, c.d_inner), jnp.float32)
    with scope("ssm/scan"):
        y, h = zip(*(
            selective_scan(act[i], dt[i], b_in[i], c_out[i], a, d_skip, h0,
                           interpret=interpret) for i in range(n)))
    return _gate_out(jnp.stack(y), z, p), u, jnp.stack(h)


def _counters(state_slots, kv_rows):
    return jnp.stack([jnp.asarray(state_slots, jnp.int32),
                      jnp.asarray(kv_rows, jnp.int32)])


# -- the passes ------------------------------------------------------------------

def forward_hidden(cfg: SSMHybridConfig, params, tokens, lens=None,
                   interpret=None, sink=None):
    """Forward over ``tokens [n, L]``: the final residual ``[n, L, H]``
    (before the last norm). ``lens [n]`` are the true lengths (default
    ``L``). ``sink`` (a list) collects what each layer leaves in the cache:
    ``(k, v)`` ``[n, L, h_kv, d]`` of an attention layer, ``(u, h)`` of a
    state-space layer (:func:`_mamba_prompt`)."""
    c = cfg
    n, L = tokens.shape
    if lens is None:
        lens = jnp.full((n,), L, jnp.int32)
    with scope("head"):
        x = params["embed"][tokens]
    for kind, p in zip(layer_plan(c), params["layers"]):
        with scope(MIXER_SCOPES[kind]):
            h = rmsnorm(x, p["norm_in"], c.norm_eps)
            if kind == "mamba":
                y, *kept = _mamba_prompt(c, h, p, lens, interpret)
            else:
                with scope("attn/qkv"):
                    q, k, v = _project(c, h.reshape(n * L, -1), p, (n, L))
                kept = (k, v)
                attn = _causal_gqa_attention(q, k, v, c).reshape(n * L, -1)
                with scope("attn/out"):
                    y = (attn @ p["wo"]).reshape(n, L, -1)
            if sink is not None:
                sink.append(tuple(kept))
            x = x + y
        with scope("ffn"):
            h = rmsnorm(x, p["norm_ff"], c.norm_eps)
            x = x + dense_mlp(c, h.reshape(n * L, -1), p).reshape(n, L, -1)
    return x


def _head(cfg, params, x):
    """Logits of rows ``x [m, H]`` through the tied head."""
    with scope("head"):
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        return jnp.einsum("mh,vh->mv", x, params["embed"])


def prefill_cache(cfg: SSMHybridConfig, params, cache, prompt, spec, s_max,
                  slot_mask=None, pick=None, interpret=None):
    """Bulk prefill (inside shard_map, one-device shard) of ``prompt
    [b*L]``. With ``slot_mask`` (an admission) ONLY THE MASKED SLOT'S ROWS
    run, and only its state and pages are written; without, every slot's.
    Each prompt's true length is ``pick + 1``: the scan stops there, the
    convolution's tail is its last true inputs, the head reads that row.
    Returns ``(cache, last [b, V], counters)``; ``last`` holds the rows of
    the slots that ran, zeros elsewhere."""
    require_one_shard(cfg, FAMILY, NOT_BUILT)
    c = cfg
    b, L = c.batch, c.seq
    slots, tokens, pick = admitted_rows(prompt, slot_mask, pick, b, L)
    sink: list = []
    x = forward_hidden(c, params, tokens, pick + 1, interpret, sink)
    for (kind, ki), kept in zip(_numbered(c), sink):
        if kind == "mamba":
            with scope("ssm"):
                cache = spec.write_state(cache, ki, slots, pick + 1, *kept)
        else:
            with scope("attn"), scope("attn/kv_write"):
                cache = spec.write_prompt(cache, ki, *kept, slots)
    rows = _head(c, params, x[jnp.arange(len(slots)), pick])
    return cache, last_rows(rows, slots, b), _counters(len(slots), 0)


def decode_step(cfg: SSMHybridConfig, params, cache, tokens, pos, *, spec,
                interpret=None):
    """One ragged decode step (inside shard_map, one-device shard):
    ``(logits [b, V], cache, counters)``. A function of ``(tokens, pos)``
    and of what the cache holds for positions BEFORE ``pos``: run twice on
    the same inputs it leaves the same cache (``StatePagedKVCacheSpec``)."""
    require_one_shard(cfg, FAMILY, NOT_BUILT)
    c = cfg
    b = c.batch
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    with scope("head"):
        x = params["embed"][tokens]
    for (kind, ki), p in zip(_numbered(c), params["layers"]):
        with scope(MIXER_SCOPES[kind]):
            h = rmsnorm(x, p["norm_in"], c.norm_eps)
            if kind == "mamba":
                u, z = _split_in(c, h, p)
                # the per-channel leaves go to their kernels as stored
                conv, cache = spec.conv_step(
                    cache, ki, u, pos_b, p["conv_w"], p["conv_b"], interpret)
                act, dt_in, b_in, c_out, a = _scan_inputs(c, conv, p)
                y, cache = spec.state_step(
                    cache, ki, act, dt_in, p["b_dt"], b_in, c_out, a,
                    p["d_skip"], pos_b, interpret)
                y = _gate_out(y, z, p)
            else:
                with scope("attn/qkv"):
                    q, k_new, v_new = _project(c, h, p, (b,))
                attn, cache = spec.write_and_attend(
                    c, cache, ki, k_new, v_new, q, pos_b, interpret)
                with scope("attn/out"):
                    y = attn.reshape(b, -1).astype(x.dtype) @ p["wo"]
            x = x + y
        with scope("ffn"):
            x = x + dense_mlp(c, rmsnorm(x, p["norm_ff"], c.norm_eps), p)
    lens = jnp.clip(pos_b + 1, 0, spec.s_max)
    return _head(c, params, x), cache, _counters(
        b, c.layer_kinds.count("attention") * jnp.sum(lens))
