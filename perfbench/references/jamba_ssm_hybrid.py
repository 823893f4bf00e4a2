"""Plain reference for the Jamba family's decoder (AI21-Jamba2-3B): layers
that mix tokens through a selective state-space recurrence (Mamba-1) and,
where ``i % attn_layer_period == attn_layer_offset``, through multi-query
attention; a plain SwiGLU MLP in every layer (``num_experts`` 1); tied
head. Straight ``jax.numpy`` in float32 at ``precision=HIGHEST``: no
kernel, no cache, no batching, the recurrence a ``lax.scan`` over time ONE
TOKEN A STEP, and nothing imported from the program under test.

Equations (``x [T, H]``; every norm an RMSNorm, eps ``norm_eps``):

- every layer: ``x = x + mixer(norm_in(x))``; ``x = x + mlp(norm_ff(x))``,
  ``mlp(u) = down(silu(gate(u)) * up(u))``, no bias. After the last layer
  ``final_norm``, then logits ``= x E^T``, ``E`` the embedding.
- MAMBA mixer (``d = mamba_expand * H``, ``N = mamba_d_state``, ``K =
  mamba_d_conv``, ``R = mamba_dt_rank``): ``[u | z] = x W_in``; ``c_t =
  silu(b_conv + sum_{j<K} w_conv[:, j] * u_{t-K+1+j})`` (depthwise,
  causal, ``u`` = 0 before the start); ``[r | B | C] = c W_x``; ``r, B,
  C`` each RMS-normed (widths R, N, N); ``dt = softplus(r W_dt + b_dt)``;
  ``A = -exp(A_log)`` ``[d, N]``; ``h_t = exp(dt_t[:, None] * A) * h_{t-1}
  + (dt_t * c_t)[:, None] * B_t[None, :]``, ``h_{-1} = 0``; ``y_t = h_t
  C_t + D * c_t``; ``out = (y * silu(z)) W_out``. No projection has a
  bias; the convolution has one.
- ATTENTION mixer: ``q = x W_q`` -> heads x d, ``k, v = x W_k, x W_v`` ->
  kv heads x d, no bias, NO rotation and no positional term; scores ``q.k /
  sqrt(d)`` under an explicit causal ``[T, T]`` mask; ``softmax(s) v W_o``.

ASSUMED (not among the catalog row's keys; listed in the configuration's
file): the head width (hidden / heads), the three inner norms (the
family's published block), no positional term, the float32 state, the
order of the layer kinds, and THE INITIALISATION, which is part of the
model here: ``A_log = log(1..N)`` in every channel, ``D = 1``, ``b_dt =
softplus^-1(dt0)`` with ``dt0`` log-uniform in ``[1e-3, 1e-1]`` (Mamba's
published initialisation, which this family inherits), matrices normal /
sqrt(fan-in), embedding 0.02, norm weights ``1 + 0.1 x normal``. With
``dt`` of order 1 the state forgets within a few tokens, and neither a
stale state nor a step applied twice would move a logit for long. READ AT
TOY SIZE (four state-space layers alone, whose convolutions by themselves
reach 12 positions back; ``perfbench/tests/test_ssm_hybrid.py`` keeps the
reading): a token changed 24 / 40 positions back moves the last position's
logits by 12% / 8% of what a token changed 1 back moves them with this
initialisation; by 1.7% / 0.9% with ``b_dt`` plain normal; by 0.006% / 0
with ``b_dt`` = 1 (``dt`` = 1.3).

It OWNS the weights (bf16, from the seed, plain layout below, the
published one); the adapter packs them into the program's layout.

    norm_in, norm_ff [H]   w_gate, w_up [H, F]   w_down [F, H]   (every layer)
    w_in [H, 2d]  conv_w [d, K]  conv_b [d]  w_x [d, R+2N]       (mamba)
    dt_norm [R]  b_norm, c_norm [N]  w_dt [R, d]  b_dt [d]
    a_log [d, N]  d_skip [d]  w_out [d, H]
    wq [H, hq*dh]  wk, wv [H, hkv*dh]  wo [hq*dh, H]             (attention)
    embed [V, H]  final_norm [H]

``control=True`` is the lower-precision twin the comparison must reject:
every projection as W8A8 int8, through the same ``_mm``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
DT_RANGE = (1e-3, 1e-1)

# published key -> short name used below
_KEYS = dict(
    attn_layer_period="period", attn_layer_offset="offset",
    mamba_expand="expand", mamba_d_state="N", mamba_d_conv="K",
    mamba_dt_rank="R",
)
_MODEL: dict = {}


def configure(config: dict) -> None:
    """Take the model's own keys from the configuration file (published
    names)."""
    m = {short: int(config[key]) for key, short in _KEYS.items()}
    if config.get("num_experts", 1) != 1:
        raise ValueError("this reference has the plain MLP in every layer "
                         "(num_experts 1)")
    if not config.get("mamba_conv_bias", True) or config.get(
            "mamba_proj_bias", False):
        raise ValueError("this reference has a bias on the convolution and "
                         "none on the projections")
    if not config.get("tie_word_embeddings", True):
        raise ValueError("this reference's head is the embedding")
    _MODEL.clear()
    _MODEL.update(m)
    _programs.cache_clear()


def model() -> dict:
    if not _MODEL:
        raise RuntimeError(
            "jamba_ssm_hybrid: configure(config) first (the adapter's System "
            "does): the model's keys are not among the sizes")
    return _MODEL


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def is_attention(li: int) -> bool:
    m = model()
    return li % m["period"] == m["offset"]


def _dtype(sizes: dict):
    return jnp.dtype(sizes.get("dtype", "bfloat16"))


def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * fan_in ** -0.5).astype(dtype)


def _gain(key, shape, dtype):
    """A norm's weight: near 1, not 1, so that where a norm sits shows."""
    return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


# -- weights -------------------------------------------------------------------

def layer_weights(key, li, sizes: dict, attention: bool | None = None) -> dict:
    """Layer ``li`` in the plain layout. Traceable in ``li`` where the
    layer's kind is given (``attention``); else ``li`` is a Python int."""
    m = model()
    if attention is None:
        attention = is_attention(li)
    h, f, dt = sizes["hidden"], sizes["ffn"], _dtype(sizes)
    k = jax.random.split(jax.random.fold_in(key, li + 1), 20)
    w = dict(
        norm_in=_gain(k[0], (h,), dt), norm_ff=_gain(k[1], (h,), dt),
        w_gate=_normal(k[2], (h, f), h, dt),
        w_up=_normal(k[3], (h, f), h, dt),
        w_down=_normal(k[4], (f, h), f, dt),
    )
    if attention:
        d, hq, hkv = sizes["head_dim"], sizes["n_q_heads"], sizes["n_kv_heads"]
        w.update(
            wq=_normal(k[5], (h, hq * d), h, dt),
            wk=_normal(k[6], (h, hkv * d), h, dt),
            wv=_normal(k[7], (h, hkv * d), h, dt),
            wo=_normal(k[8], (hq * d, h), hq * d, dt),
        )
        return w
    d, n, kc, r = m["expand"] * h, m["N"], m["K"], m["R"]
    lo, hi = (np.log(x) for x in DT_RANGE)
    dt0 = jnp.exp(jax.random.uniform(k[13], (d,), minval=lo, maxval=hi))
    w.update(
        w_in=_normal(k[5], (h, 2 * d), h, dt),
        conv_w=_normal(k[6], (d, kc), kc, dt),
        conv_b=(jax.random.normal(k[7], (d,)) * 0.01).astype(dt),
        w_x=_normal(k[8], (d, r + 2 * n), d, dt),
        dt_norm=_gain(k[9], (r,), dt), b_norm=_gain(k[10], (n,), dt),
        c_norm=_gain(k[11], (n,), dt),
        w_dt=_normal(k[12], (r, d), r, dt),
        b_dt=jnp.log(jnp.expm1(dt0)).astype(dt),       # softplus^-1(dt0)
        a_log=jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), (d, n)).astype(dt),
        d_skip=jnp.ones((d,), dt),
        w_out=_normal(k[14], (d, h), d, dt),
    )
    return w


def outer_weights(key, sizes: dict) -> dict:
    """Embedding (the head too: tied) and final norm."""
    h, v = sizes["hidden"], sizes["vocab"]
    dt = _dtype(sizes)
    k = jax.random.split(jax.random.fold_in(key, 0), 2)
    return dict(
        embed=(jax.random.normal(k[0], (v, h), jnp.float32) * 0.02).astype(dt),
        final_norm=_gain(k[1], (h,), dt),
    )


def count_parameters(sizes: dict) -> int:
    """Parameters of the whole model, from the shapes :func:`layer_weights`
    and :func:`outer_weights` make (nothing is allocated)."""
    key = jax.random.PRNGKey(0)
    shapes = [jax.eval_shape(functools.partial(outer_weights, sizes=sizes), key)]
    shapes += [jax.eval_shape(functools.partial(
        layer_weights, li=li, sizes=sizes), key)
        for li in range(sizes["n_layers"])]
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))


# -- equations -----------------------------------------------------------------

def _q8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(x, w, control: bool):
    """``x [..., K] @ w [K, N]`` in float32; the control quantizes weights
    per output column and activations per row."""
    w = w.astype(jnp.float32)
    if control:
        x, w = _q8(x, -1), _q8(w, -2)
    return jnp.matmul(x, w, precision=HI)


def _f32(x):
    return x.astype(jnp.float32)


def _norm(x, w, eps):
    r = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * r * _f32(w)


def recurrence(c, dt, b_in, c_out, a, d_skip, h0=None):
    """``h_t = exp(dt_t A) h_{t-1} + dt_t c_t B_t``, ``y_t = h_t C_t + D
    c_t`` over one sequence, token by token: ``c, dt [T, d]``, ``b_in,
    c_out [T, N]``, ``a [d, N]`` -> ``(y [T, d], h_T [d, N])``."""
    if h0 is None:
        h0 = jnp.zeros(a.shape, jnp.float32)

    def step(h, xs):
        x, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * x)[:, None] * b_t[None, :]
        return h, jnp.sum(h * c_t[None, :], -1) + d_skip * x

    h, y = jax.lax.scan(step, h0, (c, dt, b_in, c_out))
    return y, h


def mamba_parts(x, w, sizes: dict, control: bool):
    """One sequence ``x [T, H]`` through the state-space mixer: ``(out [T,
    H], h_T [d, N], u [T, d])``, the state after the last token and the
    convolution's inputs beside the mixer's output."""
    m = model()
    n, kc, r, eps = m["N"], m["K"], m["R"], sizes["norm_eps"]
    t = x.shape[0]
    uz = _mm(x, w["w_in"], control)
    d = uz.shape[-1] // 2
    u, z = uz[:, :d], uz[:, d:]
    padded = jnp.pad(u, ((kc - 1, 0), (0, 0)))
    conv = _f32(w["conv_b"]) + sum(
        _f32(w["conv_w"])[:, j] * padded[j:j + t] for j in range(kc))
    c = jax.nn.silu(conv)
    rbc = _mm(c, w["w_x"], control)
    dt_in = _norm(rbc[:, :r], w["dt_norm"], eps)
    b_in = _norm(rbc[:, r:r + n], w["b_norm"], eps)
    c_out = _norm(rbc[:, r + n:], w["c_norm"], eps)
    dt = jax.nn.softplus(_mm(dt_in, w["w_dt"], control) + _f32(w["b_dt"]))
    y, h = recurrence(c, dt, b_in, c_out, -jnp.exp(_f32(w["a_log"])),
                      _f32(w["d_skip"]))
    return _mm(y * jax.nn.silu(z), w["w_out"], control), h, u


def mamba(x, w, sizes: dict, control: bool):
    return mamba_parts(x, w, sizes, control)[0]


def attention(x, w, sizes: dict, control: bool):
    """One sequence ``x [T, H]`` through causal attention; no rotation."""
    t, d = x.shape[0], sizes["head_dim"]
    hq, hkv = sizes["n_q_heads"], sizes["n_kv_heads"]
    q = _mm(x, w["wq"], control).reshape(t, hkv, hq // hkv, d)
    k = _mm(x, w["wk"], control).reshape(t, hkv, d)
    v = _mm(x, w["wv"], control).reshape(t, hkv, d)
    # query head h reads kv head h // (hq / hkv)
    s = jnp.einsum("shgd,thd->hgst", q, k, precision=HI) / np.sqrt(d)
    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(mask[None, None], s, -jnp.inf)
    o = jnp.einsum("hgst,thd->shgd", jax.nn.softmax(s, -1), v, precision=HI)
    return _mm(o.reshape(t, hq * d), w["wo"], control)


def layer(x, w, sizes: dict, li: int | None = None, control: bool = False):
    """A decoder layer whole over ``x [n, T, H]`` from its plain weights
    (the weights say which kind it is; ``li`` is not needed)."""
    eps = sizes["norm_eps"]
    mixer = attention if "wq" in w else mamba
    h = _norm(x, w["norm_in"], eps)
    x = x + jax.lax.map(lambda s: mixer(s, w, sizes, control), h)
    h = _norm(x, w["norm_ff"], eps)
    act = jax.nn.silu(_mm(h, w["w_gate"], control)) * _mm(h, w["w_up"], control)
    return x + _mm(act, w["w_down"], control)


def head(x, outer, first, n_new: int, sizes: dict, control: bool):
    """Logits ``[n, n_new, V]`` at the ``n_new`` positions from ``first``
    on: the positions that predict the served tokens. The head is the
    embedding, transposed."""
    idx = first[:, None] + jnp.arange(n_new, dtype=jnp.int32)[None, :]
    xs = jnp.take_along_axis(x, idx[:, :, None], axis=1)
    xs = _norm(xs, outer["final_norm"], sizes["norm_eps"])
    return _mm(xs, outer["embed"].T, control)


# -- the run, a layer at a time ------------------------------------------------

@functools.lru_cache(maxsize=None)
def _programs(sizes_items: tuple):
    sizes = dict(sizes_items)
    return dict(
        gen_layer=jax.jit(
            lambda key, li, attention: layer_weights(key, li, sizes, attention),
            static_argnames=("attention",)),
        run_layer=jax.jit(
            lambda x, w, control: layer(x, w, sizes, None, control),
            static_argnames=("control",), donate_argnums=(0,)),
        gen_outer=jax.jit(functools.partial(outer_weights, sizes=sizes)),
        run_head=jax.jit(functools.partial(head, sizes=sizes),
                         static_argnames=("n_new", "control")),
    )


def logits(sizes: dict, seed: int, tokens, first, n_new: int, *,
           control: bool = False, devices=None):
    """The reference's logits ``[n, n_new, V]`` (a device array) for
    ``tokens [n, T]`` at positions ``first[i] .. first[i] + n_new - 1``.
    Weights come from ``seed``, a layer at a time, dropped after use. One
    device: ``devices`` of more than one are refused (the configuration is
    a one-chip one)."""
    if devices is not None and len(devices) > 1:
        raise NotImplementedError("jamba_ssm_hybrid runs on one device")
    p = _programs(tuple(sorted(sizes.items(), key=lambda kv: kv[0])))
    key = seed_key(seed)
    outer = p["gen_outer"](key)
    x = outer["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    for li in range(sizes["n_layers"]):
        w = p["gen_layer"](key, jnp.int32(li), attention=is_attention(li))
        x = p["run_layer"](x, w, control=control)
    return p["run_head"](x, outer, jnp.asarray(first, jnp.int32), n_new=n_new,
                         control=control)


def gaps(ref_logits, judged):
    """How far each judged token ``[n, n_new]`` lies below the
    reference's best logit at its position, and whether it is that best."""
    judged = jnp.asarray(judged, jnp.int32)
    got = jnp.take_along_axis(ref_logits, judged[..., None], -1)[..., 0]
    best = ref_logits.max(-1)
    return np.asarray(best - got), np.asarray(ref_logits.argmax(-1) == judged)
