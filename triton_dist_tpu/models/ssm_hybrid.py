"""Decoder whose layers mix tokens through a STATE-SPACE recurrence or, a
few of them, through ATTENTION, served through the same batcher, block
tables and spans as the other families. Two published blocks: the Jamba
family's (AI21-Jamba2-3B: 26 Mamba-1 and 2 multi-query attention layers of
28, a dense MLP in every layer) and the Granite-4.0-H family's
(granite-4.0-h-small: Mamba-2 and grouped-query attention layers 9:1, a
bank of softmax-routed experts with a shared expert in every layer).

The LAYER PLAN (:func:`layer_plan`) names the MIXER kind of each layer,
``mamba`` (Mamba-1: a state ``[N, d]`` with a decay a channel and state),
``mamba2`` (a state ``[N, d]`` with ONE decay a head of ``ssm_head_dim``
channels, ``B`` and ``C`` shared by the heads) or ``attention``: read from
``cfg.layer_types`` where the model publishes one, else ``attention`` where
``i % attn_layer_period == attn_layer_offset`` and ``mamba`` otherwise. A
model has ONE state-space kind. The MLP kind (:func:`mlp_kind`) is the
model's: ``dense``, or ``experts`` where ``n_experts`` is set
(``models/gated_experts.py``: the router over the whole bank,
``experts_held`` the chip's share, the shared expert). Parameters, their
specs, prefill and the decode step walk the plan. The mixer kinds keep what
they carry between tokens in one cache (``cache_kind = "kv_state"``,
``models/decode.py`` ``StatePagedKVCacheSpec``): an attention layer's keys
and values in pages, a state-space layer's recurrent state and
convolution tail in a row of its SLOT, float32, the same size whatever the
context.

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
- MAMBA2 mixer (``d = H P`` = ``ssm_heads x ssm_head_dim``, ``N =
  d_state``, one group): ``[z | xBC | dt] = x W_in`` (no bias; widths ``d |
  d + 2N | H``); ``xBC = silu(b_conv + causal depthwise conv_K(xBC))``: the
  convolution runs over ``d + 2N`` CHANNELS (``cfg.conv_channels``), wider
  than ``d_inner``; ``[x' | B | C] = xBC``; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``, ``D``: one a head; ``h_t = exp(dt_t A) h_{t-1} +
  dt_t x'_t (x) B_t`` a head, ``y_t = h_t C_t + D x'_t``; ``y = RMSNorm(y *
  silu(z)) * w`` over all of ``d``; ``out = y W_out``. The state is stored
  ``[N, d]`` as Mamba-1's (``ops/ssd.py``).
- ATTENTION mixer: ``q = x W_q``, ``k = x W_k``, ``v = x W_v`` as one
  kv-group-major ``wqkv`` (the dense family's layout), no bias, NO
  rotation and no positional term of any kind; scores ``q.k / sqrt(d)``
  (``q.k * attention_multiplier`` where the model publishes one: ``q`` is
  scaled in float32 by ``attention_multiplier * sqrt(d)`` and the kernels
  keep their ``1 / sqrt(d)``), causal; ``softmax(s) v W_o``.
- the Granite block's scalars, each 1 (off) by default: ``x0 =
  embedding_multiplier * E[ids]``; both sub-layers' outputs times
  ``residual_multiplier`` before the residual add; logits divided by
  ``logits_scaling``.

DECODE walks every slot one token on: a state-space layer through
``StatePagedKVCacheSpec.conv_step`` / ``state_step`` (the kernels
``conv_ring_step`` and ``selective_state_update``, each handed the
layer's per-channel vectors as they are stored; ``ssd_state_update`` for a
``mamba2`` layer), an attention layer through ``paged_flash_decode``.
PREFILL (an admission) computes THE ADMITTED SLOT'S ROWS ONLY, ``[1,
bucket]``, the slot found from ``slot_mask`` inside the pass, and writes
that slot's state and pages and no other's: the other slots are
mid-sequence, and a whole batch of buckets is ``slots`` times the work. The
scan (``selective_scan`` token by token; ``ssd_chunk_scan``, the chunked
dual form on the MXU, for ``mamba2``) stops at the prompt's true length:
``dt`` is 0 at padded positions, which leaves the state as it was. Without
a mask (``generate``) every slot's rows run. An admission's attention goes
through ``ops/flash_prefill.flash_prefill`` past ``MATERIALIZED_UP_TO``
rows of bucket (``models/window_moe.py`` says why there).

Serving runs this family on a ONE-device shard: a state sharded over
channels is not built, and the entry points refuse a wider axis by name.
"""

from __future__ import annotations

import dataclasses
from typing import ClassVar

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.models.decode import StatePagedKVCacheSpec
from triton_dist_tpu.models.gated_experts import (
    DECODE_BLOCK_M, MOE_STATS, PREFILL_BLOCK_M, add_stats, admitted_rows,
    dense_mlp, expert_bytes, last_rows, moe_mlp, no_stats, require_one_shard,
)
from triton_dist_tpu.models.tp_transformer import (
    TransformerConfig, _causal_gqa_attention, rmsnorm,
)
from triton_dist_tpu.obs.scopes import scope
from triton_dist_tpu.ops.flash_prefill import flash_prefill
from triton_dist_tpu.ops.selective_scan import selective_scan
from triton_dist_tpu.ops.ssd import ssd_chunk_scan

MIXER_KINDS = ("mamba", "mamba2", "attention")
STATE_KINDS = ("mamba", "mamba2")
# the part of the layer a mixer of each kind is (obs/scopes.py)
MIXER_SCOPES = {"mamba": "ssm", "mamba2": "ssm", "attention": "attn"}
# the largest bucket whose attention scores an admission still
# materializes; above it the tiled kernel (window_moe.MATERIALIZED_UP_TO
# has the measurements)
MATERIALIZED_UP_TO = 2048
FAMILY = "state-space / attention"
NOT_BUILT = "a slot's state sharded over channels"


@dataclasses.dataclass(frozen=True)
class SSMHybridConfig(TransformerConfig):
    """``rope_theta`` is inherited and unused: the family does not rotate.
    ``ffn`` is the dense MLP's width; with ``n_experts`` set every MLP is
    the expert bank instead (``n_experts`` the router's width, whatever
    share of the bank ``experts_held`` names)."""

    # the plan as published, one mixer kind a layer; empty = by period/offset
    layer_types: tuple[str, ...] = ()
    attn_layer_period: int = 2
    attn_layer_offset: int = 1
    d_inner: int = 256      # mamba_expand * hidden
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 8        # mamba only
    # mamba2 only: d_inner = ssm_heads * ssm_head_dim, and the rows a chunk
    # of the admission's dual form holds (the published mamba_chunk_size)
    ssm_heads: int = 0
    ssm_chunk: int = 256
    # the expert bank (0 = a dense MLP in every layer)
    n_experts: int = 0
    topk: int = 2
    expert_ffn: int = 32
    n_shared_experts: int = 0       # the shared expert's width / expert_ffn
    # (first expert, count) held here; None = the whole bank
    experts_held: tuple[int, int] | None = None
    # (first row, count) of a larger vocabulary held here; count == vocab
    vocab_held: tuple[int, int] | None = None
    # the Granite block's scalars (module docstring); 1 / None = off
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float | None = None
    logits_scaling: float = 1.0

    own_passes: ClassVar[bool] = True
    cache_kind: ClassVar[str] = "kv_state"
    # the MLPs' gate and the router, as models/gated_experts.py reads them
    gate_act: ClassVar[str] = "silu"
    scoring: ClassVar[str] = "softmax"
    routed_scaling: ClassVar[float] = 1.0

    def __post_init__(self):
        if not 0 <= self.attn_layer_offset < self.attn_layer_period:
            raise ValueError(
                f"attn_layer_offset={self.attn_layer_offset} outside the "
                f"period of {self.attn_layer_period}")
        if self.d_conv < 2:
            raise ValueError(f"d_conv={self.d_conv} must be >= 2")
        kinds = self.layer_types
        if kinds and (len(kinds) != self.n_layers
                      or any(k not in MIXER_KINDS for k in kinds)):
            raise ValueError(
                f"layer_types={kinds} must name one of {MIXER_KINDS} for "
                f"each of the {self.n_layers} layers")
        if len(set(kinds) & set(STATE_KINDS)) > 1:
            raise ValueError("one state-space kind a model: the slots' pools "
                             f"have one shape (layer_types={kinds})")
        if "mamba2" in kinds and (
                self.ssm_heads < 1 or self.d_inner % self.ssm_heads):
            raise ValueError(f"d_inner={self.d_inner} is not whole heads: "
                             f"ssm_heads={self.ssm_heads}")
        if self.n_experts:
            first, count = self.held
            if not (0 <= first and first + count <= self.n_experts
                    and count > 0):
                raise ValueError(f"experts_held={self.experts_held} outside "
                                 f"the bank of {self.n_experts}")
        if self.vocab_held is not None and self.vocab_held[1] != self.vocab:
            raise ValueError(
                f"vocab_held={self.vocab_held} holds {self.vocab_held[1]} "
                f"rows but vocab={self.vocab}: the slice IS the vocabulary")

    @property
    def layer_kinds(self) -> tuple[str, ...]:
        return layer_plan(self)

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held or (0, self.n_experts)

    @property
    def ssm_head_dim(self) -> int:
        return self.d_inner // self.ssm_heads

    @property
    def conv_channels(self) -> int:
        """Channels the causal convolution runs over (the width of
        ``StatePagedKVCacheSpec``'s ring): ``d_inner`` for Mamba-1, whose
        ``B`` and ``C`` come after it; ``x | B | C`` for Mamba-2."""
        two = "mamba2" in self.layer_kinds
        return self.d_inner + (2 * self.d_state if two else 0)

    @property
    def pass_counters(self) -> tuple[str, ...]:
        """Slots whose state a pass advanced (a step advances EVERY slot of
        the batch, idle ones too: it is not told which are live) and the
        key rows its attention layers read (from the slots' lengths; 0 on
        an admission's pass); with an expert bank also the chunks an
        admission's dual form walked (each state-space layer's, summed)
        and the routing counters over the held experts."""
        base = ("state_slots", "kv_rows")
        return base + ("prompt_chunks",) + MOE_STATS if self.n_experts else base

    def state_bytes(self) -> int:
        """Bytes of the state pools (``ssm`` and ``conv``) of
        ``StatePagedKVCacheSpec`` over ``batch`` slots."""
        per_slot = (2 * self.d_state * self.d_inner
                    + self.d_conv * self.conv_channels) * 4
        n_state = sum(k in STATE_KINDS for k in self.layer_kinds)
        return n_state * self.batch * per_slot

    # the family's answers to the shared serving code (own_passes)
    def param_specs(self) -> dict:
        return ssm_hybrid_param_specs(self)

    def param_bytes(self, params: dict) -> dict:
        out = dict(state_bytes=self.state_bytes())
        if self.n_experts:
            out.update(expert_bytes=expert_bytes(params))
        return out

    def decode_step(self, params, cache, tokens, pos, *, spec, interpret=None):
        return decode_step(self, params, cache, tokens, pos, spec=spec,
                           interpret=interpret)

    def prefill_cache(self, params, cache, prompt, spec, s_max, **kw):
        return prefill_cache(self, params, cache, prompt, spec, s_max, **kw)


def layer_plan(cfg: SSMHybridConfig) -> tuple[str, ...]:
    """Each layer's mixer kind: ``"mamba"`` | ``"mamba2"`` |
    ``"attention"``."""
    if cfg.layer_types:
        return tuple(cfg.layer_types)
    return tuple(
        "attention" if li % cfg.attn_layer_period == cfg.attn_layer_offset
        else "mamba" for li in range(cfg.n_layers))


def mlp_kind(cfg: SSMHybridConfig) -> str:
    """Every layer's MLP kind: ``"dense"`` | ``"experts"``."""
    return "experts" if cfg.n_experts else "dense"


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
    out = dict(norm_in=((h,), "norm"), norm_ff=((h,), "norm"))
    if c.n_experts:
        fe, (_, held) = c.expert_ffn, c.held
        out.update(
            router=((h, c.n_experts), h),
            # gate | up as contiguous halves: banks are never column-sharded
            we_gate_up=((held, h, 2 * fe), h), we_down=((held, fe, h), fe))
        if c.n_shared_experts:
            fs = fe * c.n_shared_experts
            out.update(ws_gate_up=((h, 2 * fs), h), ws_down=((fs, h), fs))
    else:
        out.update(w_gate_up=((h, 2 * c.ffn), h), w_down=((c.ffn, h), c.ffn))
    if kind == "attention":
        out.update(wqkv=((h, c.qkv_dim), h),    # kv-group-major: q heads | k | v
                   wo=((c.q_dim, h), c.q_dim))
    elif kind == "mamba2":
        heads = c.ssm_heads
        out.update(
            w_in=((h, d + c.conv_channels + heads), h),     # z | xBC | dt
            conv_w=((c.d_conv, c.conv_channels), c.d_conv),  # tap-major
            conv_b=((c.conv_channels,), "bias"),
            dt_bias=((heads,), "dt_bias"), a_log=((heads,), "a_log_head"),
            d_skip=((heads,), "norm"), y_norm=((d,), "norm"),
            w_out=((d, h), d),
        )
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
        if init == "a_log_head":        # Mamba-2's: A uniform in [1, 16] a head
            return jnp.log(jax.random.uniform(
                k, shape, minval=1.0, maxval=16.0)).astype(cfg.dtype)
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
    d]`` (``lead`` multiplies to ``m``); nothing is rotated. Where the
    model publishes its score scale, ``q`` carries ``attention_multiplier *
    sqrt(d)``, scaled in float32: the kernels' ``1 / sqrt(d)`` then leaves
    ``q.k * attention_multiplier``."""
    g, d = c.n_q_heads // c.n_kv_heads, c.head_dim
    qkv = (x @ p["wqkv"]).reshape(*lead, c.n_kv_heads, g + 2, d)
    q = qkv[..., :g, :].reshape(*lead, c.n_q_heads, d)
    if c.attention_multiplier is not None:
        q = (_f32(q) * (c.attention_multiplier * d ** 0.5)).astype(q.dtype)
    return q, qkv[..., g, :], qkv[..., g + 1, :]


def _embed(c, params, tokens):
    with scope("head"):
        x = params["embed"][tokens]
        if c.embedding_multiplier != 1.0:
            x = (_f32(x) * c.embedding_multiplier).astype(x.dtype)
    return x


def _add(c, x, y):
    """The residual add, the sub-layer's output times
    ``residual_multiplier`` where the block has one."""
    if c.residual_multiplier == 1.0:
        return x + y
    return (_f32(x) + c.residual_multiplier * _f32(y)).astype(x.dtype)


def _mlp(c, x, p, block_m: int, interpret, stats):
    """``x + mlp(norm_ff(x))`` over ``x [..., H]`` by the model's MLP kind
    (the MLP sees rows ``[m, H]``), and the pass's routing counters."""
    with scope("ffn"):
        if c.n_experts:
            # the bank's pass is over rows, and so are its norm and its
            # residual add: around it in the prompt's [n, L, H] an
            # admission of 8192 rows took 324 ms for 300 (chip, PR 48)
            rows = x.reshape(-1, x.shape[-1])
            y, st = moe_mlp(c, rmsnorm(rows, p["norm_ff"], c.norm_eps), p,
                            block_m, interpret)
            return _add(c, rows, y).reshape(x.shape), add_stats(stats, st)
        h = rmsnorm(x, p["norm_ff"], c.norm_eps)
        y = dense_mlp(c, h.reshape(-1, x.shape[-1]), p)
        return _add(c, x, y.reshape(x.shape)), stats


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


def _causal_conv(u, p, K: int):
    """``b_conv + causal depthwise conv_K(u)`` over ``u [n, L, channels]``
    (f32, zeros before the start), not yet activated."""
    with scope("ssm/conv"):
        L = u.shape[1]
        w = _f32(p["conv_w"])
        padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
        return _f32(p["conv_b"]) + sum(
            w[j] * padded[:, j:j + L] for j in range(K))


def _mamba_prompt(c: SSMHybridConfig, x, p, lens, interpret):
    """The state-space mixer over whole prompts ``x [n, L, H]`` of ``lens
    [n]`` true tokens: ``(out [n, L, H], u [n, L, d], h [n, N, d])``, ``h``
    the state after each prompt's LAST TRUE token."""
    n, L, _ = x.shape
    u, z = _split_in(c, x, p)
    conv = _causal_conv(u, p, c.d_conv)
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


# -- the Mamba-2 mixer's pieces ----------------------------------------------------

def _split_in2(c, x, p):
    """``x [m, H]`` -> the gate ``z [m, d]``, the convolution's input
    ``xBC [m, d + 2N]`` (f32) and the step before its bias and softplus
    ``dt_in [m, heads]`` (f32)."""
    d, cw = c.d_inner, c.conv_channels
    with scope("ssm/proj"):
        zxd = x @ p["w_in"]
    return zxd[..., :d], _f32(zxd[..., d:d + cw]), _f32(zxd[..., d + cw:])


def _split_conv2(c, conv):
    """The convolution's output (f32, not yet activated) -> ``(x' [m, d],
    B, C [m, N])``, activated."""
    d, n = c.d_inner, c.d_state
    with scope("ssm/conv"):
        act = jax.nn.silu(conv)
    return act[..., :d], act[..., d:d + n], act[..., d + n:]


def _gated_norm_out(c, y, z, p):
    """``RMSNorm(y * silu(z)) * w`` over all of ``d`` (one group), then
    ``W_out``."""
    with scope("ssm/norm"):
        gated = rmsnorm(_f32(y) * jax.nn.silu(_f32(z)), _f32(p["y_norm"]),
                        c.norm_eps)
    with scope("ssm/proj"):
        return gated.astype(p["w_out"].dtype) @ p["w_out"]


def _mamba2_prompt(c: SSMHybridConfig, x, p, lens, interpret):
    """The Mamba-2 mixer over whole prompts ``x [n, L, H]`` of ``lens [n]``
    true tokens, the recurrence in the chunked dual form
    (``ops/ssd.ssd_chunk_scan``): ``(out [n, L, H], tail, first, h [n, N,
    d])``: ``h`` the state after each prompt's LAST TRUE token, ``tail [n,
    K, d + 2N]`` the convolution's last ``K`` true inputs, from position
    ``first [n]`` on (``StatePagedKVCacheSpec.tail``: the whole ``[L, d +
    2N]`` float32 of every layer would be kept until the pass ends)."""
    n, L, _ = x.shape
    z, xbc, dt_in = _split_in2(c, x, p)
    xs, b_in, c_out = _split_conv2(c, _causal_conv(xbc, p, c.d_conv))
    with scope("ssm/proj"):
        dt = jax.nn.softplus(dt_in + _f32(p["dt_bias"]))
        # the scan stops at the prompt's end: dt = 0 leaves the state as it was
        dt = jnp.where((jnp.arange(L) < lens[:, None])[..., None], dt, 0.0)
        a = -jnp.exp(_f32(p["a_log"]))
        xs = xs.astype(x.dtype)
    with scope("ssm/scan"):
        y, h = zip(*(
            ssd_chunk_scan(xs[i], dt[i], a, b_in[i], c_out[i], p["d_skip"],
                           lens[i], chunk=c.ssm_chunk, interpret=interpret)
            for i in range(n)))
    tail, first = StatePagedKVCacheSpec.tail(xbc, lens, c.d_conv)
    return _gated_norm_out(c, jnp.stack(y), z, p), tail, first, jnp.stack(h)


def _mamba2_step(c: SSMHybridConfig, h, p, cache, spec, ki, pos_b, interpret):
    """One token of every slot through the Mamba-2 mixer: ``(out [b, H],
    cache)``."""
    z, xbc, dt_in = _split_in2(c, h, p)
    # the kernel gets the LAYER's ring, cut out and put back (4 MB each
    # way): handed the pool whole, which at 9 layers x 32 slots x 8448
    # channels (39 MB) fits the compiler's fast memory, every call had the
    # pool moved there and back around it, 1.37 ms a step (PERF.md
    # section 6, PR 48; Mamba-1's 136 MB pool never was)
    conv, ring = spec.conv_step(
        dict(conv=cache["conv"][ki][None]), 0, xbc, pos_b, p["conv_w"],
        p["conv_b"], interpret)
    cache = dict(cache, conv=cache["conv"].at[ki].set(ring["conv"][0]))
    xs, b_in, c_out = _split_conv2(c, conv)
    with scope("ssm/proj"):
        a = -jnp.exp(_f32(p["a_log"]))
    y, cache = spec.head_state_step(
        cache, ki, xs, dt_in, p["dt_bias"], a, b_in, c_out, p["d_skip"],
        pos_b, interpret)
    return _gated_norm_out(c, y, z, p), cache


def _prefill_attention(c, q, k, v, lens, interpret):
    """An admission's attention of one layer: ``q [n, L, hq, d]``, ``k, v
    [n, L, h_kv, d]`` -> ``[n * L, hq * d]``: the tiled kernel past
    ``MATERIALIZED_UP_TO`` rows of bucket (it walks no key block past
    ``lens``), the causal square under it."""
    n, L = q.shape[:2]
    if L > MATERIALIZED_UP_TO:
        with scope("attn/prefill"):
            attn = flash_prefill(q, k, v, lens, interpret=interpret)
    else:
        attn = _causal_gqa_attention(q, k, v, c)
    return attn.reshape(n * L, -1)


def _counters(c, state_slots, kv_rows, prompt_chunks=0, stats=None):
    """The pass's ``pass_counters``."""
    out = [jnp.asarray(v, jnp.int32) for v in (state_slots, kv_rows)]
    if not c.n_experts:
        return jnp.stack(out)
    return jnp.concatenate([
        jnp.stack(out + [jnp.asarray(prompt_chunks, jnp.int32)]), stats])


# -- the passes ------------------------------------------------------------------

def forward_hidden(cfg: SSMHybridConfig, params, tokens, lens=None,
                   interpret=None, sink=None, stats=None):
    """Forward over ``tokens [n, L]``: the final residual ``[n, L, H]``
    (before the last norm). ``lens [n]`` are the true lengths (default
    ``L``). ``sink`` (a list) collects what each layer leaves in the cache:
    ``(k, v)`` ``[n, L, h_kv, d]`` of an attention layer, ``(u, h)`` of a
    Mamba-1 layer (:func:`_mamba_prompt`), ``(tail, first, h)`` of a
    Mamba-2 layer (:func:`_mamba2_prompt`).
    ``stats`` (a list) receives the pass's routing counters where the
    model has an expert bank."""
    c = cfg
    n, L = tokens.shape
    if lens is None:
        lens = jnp.full((n,), L, jnp.int32)
    x = _embed(c, params, tokens)
    moe = no_stats()
    for kind, p in zip(layer_plan(c), params["layers"]):
        with scope(MIXER_SCOPES[kind]):
            h = rmsnorm(x, p["norm_in"], c.norm_eps)
            if kind == "mamba":
                y, *kept = _mamba_prompt(c, h, p, lens, interpret)
            elif kind == "mamba2":
                y, *kept = _mamba2_prompt(c, h, p, lens, interpret)
            else:
                with scope("attn/qkv"):
                    q, k, v = _project(c, h.reshape(n * L, -1), p, (n, L))
                kept = (k, v)
                attn = _prefill_attention(c, q, k, v, lens, interpret)
                with scope("attn/out"):
                    y = (attn @ p["wo"]).reshape(n, L, -1)
            if sink is not None:
                sink.append(tuple(kept))
            x = _add(c, x, y)
        x, moe = _mlp(c, x, p, PREFILL_BLOCK_M, interpret, moe)
    if stats is not None:
        stats.append(moe)
    return x


def _head(cfg, params, x):
    """Logits of rows ``x [m, H]`` through the tied head."""
    with scope("head"):
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("mh,vh->mv", x, params["embed"])
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        return logits


def forward_logits(cfg: SSMHybridConfig, params, tokens, interpret=None):
    """Whole-sequence logits ``[n, L, V]`` of ``tokens [n, L]`` (tests)."""
    n, L = tokens.shape
    x = forward_hidden(cfg, params, tokens, interpret=interpret)
    return _head(cfg, params, x.reshape(n * L, -1)).reshape(n, L, -1)


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
    stats: list = []
    x = forward_hidden(c, params, tokens, pick + 1, interpret, sink, stats)
    for (kind, ki), kept in zip(_numbered(c), sink):
        if kind == "mamba":
            with scope("ssm"):
                cache = spec.write_state(cache, ki, slots, pick + 1, *kept)
        elif kind == "mamba2":
            tail, first, h = kept
            with scope("ssm"):
                cache = spec.write_state(cache, ki, slots, pick + 1, tail, h,
                                         first)
        else:
            with scope("attn"), scope("attn/kv_write"):
                cache = spec.write_prompt(cache, ki, *kept, slots)
    rows = _head(c, params, x[jnp.arange(len(slots)), pick])
    chunks = c.layer_kinds.count("mamba2") * jnp.sum(
        -(-(pick + 1) // c.ssm_chunk))
    return cache, last_rows(rows, slots, b), _counters(
        c, len(slots), 0, chunks, stats[0])


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
    x = _embed(c, params, tokens)
    stats = no_stats()
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
            elif kind == "mamba2":
                y, cache = _mamba2_step(c, h, p, cache, spec, ki, pos_b,
                                        interpret)
            else:
                with scope("attn/qkv"):
                    q, k_new, v_new = _project(c, h, p, (b,))
                attn, cache = spec.write_and_attend(
                    c, cache, ki, k_new, v_new, q, pos_b, interpret)
                with scope("attn/out"):
                    y = attn.reshape(b, -1).astype(x.dtype) @ p["wo"]
            x = _add(c, x, y)
        x, stats = _mlp(c, x, p, DECODE_BLOCK_M, interpret, stats)
    lens = jnp.clip(pos_b + 1, 0, spec.s_max)
    return _head(c, params, x), cache, _counters(
        c, b, c.layer_kinds.count("attention") * jnp.sum(lens), 0, stats)
