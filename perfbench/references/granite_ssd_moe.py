"""Plain reference for the Granite-4.0-H family's decoder
(granite-4.0-h-small): layers that mix tokens through a Mamba-2 recurrence
(a matrix state a head, ONE decay a head, ``B`` and ``C`` shared by the
heads) and, where ``layer_types`` says ``attention``, through grouped-query
attention with no positional term; in every layer a bank of softmax-routed
SwiGLU experts with a shared expert; tied head; four published scalars.
Straight ``jax.numpy`` in float32 at ``precision=HIGHEST``: no kernel, no
cache, no chunked form, no grouped GEMM, no batching, the recurrence a
``lax.scan`` over time ONE TOKEN A STEP, and nothing imported from the
program under test.

Equations (``x [T, H]``; every norm an RMSNorm, eps ``norm_eps``):

- ``x0 = embedding_multiplier * E[ids]``. Layer: ``x = x +
  residual_multiplier * mixer(norm_in(x))``; ``u = norm_ff(x)``; ``x = x +
  residual_multiplier * (moe(u) + shared(u))``. After the last layer
  ``logits = norm_f(x) E^T / logits_scaling`` (the head is the embedding).
- MAMBA-2 mixer (``d = heads x P``, ``N`` = ``mamba_d_state``, ``K`` =
  ``mamba_d_conv``, one group): ``[z | xBC | dt] = x W_in`` (no bias;
  widths ``d | d + 2N | heads``); ``xBC = silu(b_conv + causal depthwise
  conv_K(xBC))`` (zeros before the start); ``[x' | B | C] = xBC``; ``dt =
  softplus(dt + dt_bias)``; ``A = -exp(A_log)``, one a head; ``h_t =
  exp(dt_t A) h_{t-1} + dt_t x'_t (x) B_t`` a head (``h [P, N]``, ``h_{-1} =
  0``); ``y_t = h_t C_t + D x'_t``; ``y = RMSNorm(y * silu(z)) * w`` over
  all of ``d``; ``out = y W_out``.
- ATTENTION mixer: ``q = x W_q`` -> heads x dh, ``k, v = x W_k, x W_v`` ->
  kv heads x dh, no bias, NO rotation (``position_embedding_type``
  ``nope``); scores ``q.k * attention_multiplier`` under an explicit causal
  mask, queries :data:`QUERY_BLOCK` at a time; ``softmax(s) v W_o``.
- ``moe(u)``: ``l = u W_r``; chosen = top-k of ``l``; ``w =
  softmax(l[chosen])``; ``sum_k w_k E_k(u)``, ``E(u) = (silu(u W_g) * (u
  W_u)) W_d``. ``shared(u)`` the same at ``shared_intermediate_size``. No
  token is dropped.

ASSUMED (not among the catalog row's keys; listed in the configuration's
file): the head width of attention (hidden / heads), the expert width
(``intermediate_size``, the row's own note), the float32 state, and THE
RECURRENCE'S INITIALISATION, which is part of the model here (Mamba-2's
published one): ``A`` uniform in ``[1, 16]`` a head, ``D = 1``, ``dt_bias =
softplus^-1(dt0)`` with ``dt0`` log-uniform in ``[1e-3, 1e-1]``; matrices
normal / sqrt(fan-in), norm weights ``1 + 0.1 x normal``. With ``dt`` of
order 1 the state forgets within a few tokens, and neither a stale state
nor a step applied twice would move a logit for long. THE EMBEDDING is
normal x ``0.02 / embedding_multiplier``, so that the scaled lookup ``x0``
has the 0.02 of every other cell's. At 0.02 itself (read on the chip, PR
48's first run) the TIED head with ``x0 = 12 E[id]`` makes every position
predict ITS OWN TOKEN by 14 logits (``12 |E[id]|^2 / 16`` = 1.2 against
the 0.08 of everything the layers add): every served token is the
reference's best with gap 0 whatever the layers compute, and the
comparison sees nothing; a trained embedding has no such self-term.

THE SHARE. ``experts_held [first, count]`` is the chip's share of each
bank: the router scores all ``E`` experts and chooses among all of them,
and only the held experts' parts (and the shared expert) are added; that
partial result goes on to the next layer. ``sizes["vocab"]`` is the slice
of the vocabulary held: embedding, head and logits are over it.

It OWNS the weights (bf16, from the seed, plain layout below); the adapter
packs them into the program's layout. A layer's bank is made and used some
experts at a time (:func:`expert_weights`).

    norm_in, norm_ff [H]                                      (every layer)
    router [H, E]  we_gate, we_up [n, H, Fe]  we_down [n, Fe, H]
    ws_gate, ws_up [H, Fs]  ws_down [Fs, H]
    w_in [H, d + (d + 2N) + heads]  conv_w [d + 2N, K]  conv_b [d + 2N]
    dt_bias, a_log, d_skip [heads]  y_norm [d]  w_out [d, H]     (mamba)
    wq [H, hq*dh]  wk, wv [H, hkv*dh]  wo [hq*dh, H]         (attention)
    embed [V, H]  final_norm [H]

``control=True`` is the lower-precision twin the comparison must reject:
every projection (experts and router input included) as W8A8 int8, through
the same ``_mm``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
DT_RANGE = (1e-3, 1e-1)
A_RANGE = (1.0, 16.0)
EXPERT_CHUNK = 2
QUERY_BLOCK = 1024

# published key -> short name used below
_KEYS = dict(
    mamba_n_heads="heads", mamba_d_head="P", mamba_d_state="N",
    mamba_d_conv="K", num_experts_per_tok="topk", intermediate_size="fe",
    shared_intermediate_size="fs", embedding_multiplier="emb",
    residual_multiplier="res", attention_multiplier="attn",
    logits_scaling="logits",
)
_MODEL: dict = {}


def configure(config: dict) -> None:
    """Take the model's own keys from the configuration file (published
    names). ``num_local_experts`` in the file counts the experts HELD
    (``experts_held [first, count]``); the router's width is the published
    count (``published.num_local_experts``, or the file's own where nothing
    is cut)."""
    m = {short: config[key] for key, short in _KEYS.items()}
    if config.get("mamba_n_groups", 1) != 1:
        raise ValueError("this reference has one group: B and C are shared "
                         "by all heads")
    if not config.get("mamba_conv_bias", True) or config.get(
            "mamba_proj_bias", False) or config.get("attention_bias", False):
        raise ValueError("this reference has a bias on the convolution and "
                         "on no projection")
    if not config.get("tie_word_embeddings", True):
        raise ValueError("this reference's head is the embedding")
    if config.get("position_embedding_type", "nope") != "nope":
        raise ValueError("this reference's attention has no positional term")
    if m["heads"] * m["P"] != config["mamba_expand"] * config["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand x "
                         "hidden_size")
    m["E"] = (config.get("published") or config)["num_local_experts"]
    m["held"] = tuple(config.get("experts_held") or (0, m["E"]))
    if m["held"][1] != config["num_local_experts"]:
        raise ValueError("num_local_experts must count the experts held")
    m["kinds"] = tuple(config["layer_types"])
    if set(m["kinds"]) - {"mamba", "attention"}:
        raise ValueError(f"layer_types {set(m['kinds'])}: mamba | attention")
    _MODEL.clear()
    _MODEL.update(m)
    _programs.cache_clear()


def model() -> dict:
    if not _MODEL:
        raise RuntimeError(
            "granite_ssd_moe: configure(config) first (the adapter's System "
            "does): the model's keys are not among the sizes")
    return _MODEL


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def is_attention(li: int) -> bool:
    return model()["kinds"][li] == "attention"


def _dtype(sizes: dict):
    return jnp.dtype(sizes.get("dtype", "bfloat16"))


def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * fan_in ** -0.5).astype(dtype)


def _gain(key, shape, dtype):
    """A norm's weight: near 1, not 1, so that where a norm sits shows."""
    return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _log_uniform(key, shape, lo, hi):
    return jnp.exp(jax.random.uniform(
        key, shape, minval=np.log(lo), maxval=np.log(hi)))


# -- weights -------------------------------------------------------------------

def core_weights(key, li, sizes: dict, attention: bool) -> dict:
    """Everything of layer ``li`` but the routed expert bank (traceable in
    ``li``; the layer's mixer kind is static)."""
    m = model()
    h, dt = sizes["hidden"], _dtype(sizes)
    k = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, li + 1), 0), 20)
    fs = m["fs"]
    w = dict(
        norm_in=_gain(k[0], (h,), dt), norm_ff=_gain(k[1], (h,), dt),
        router=_normal(k[2], (h, m["E"]), h, dt),
        ws_gate=_normal(k[3], (h, fs), h, dt),
        ws_up=_normal(k[4], (h, fs), h, dt),
        ws_down=_normal(k[5], (fs, h), fs, dt),
    )
    if attention:
        d, hq, hkv = sizes["head_dim"], sizes["n_q_heads"], sizes["n_kv_heads"]
        w.update(
            wq=_normal(k[6], (h, hq * d), h, dt),
            wk=_normal(k[7], (h, hkv * d), h, dt),
            wv=_normal(k[8], (h, hkv * d), h, dt),
            wo=_normal(k[9], (hq * d, h), hq * d, dt),
        )
        return w
    heads, n, kc = m["heads"], m["N"], m["K"]
    d = heads * m["P"]
    cw = d + 2 * n
    w.update(
        w_in=_normal(k[6], (h, d + cw + heads), h, dt),
        conv_w=_normal(k[7], (cw, kc), kc, dt),
        conv_b=(jax.random.normal(k[8], (cw,)) * 0.01).astype(dt),
        # softplus^-1(dt0)
        dt_bias=jnp.log(jnp.expm1(_log_uniform(k[9], (heads,), *DT_RANGE))
                        ).astype(dt),
        a_log=jnp.log(jax.random.uniform(
            k[10], (heads,), minval=A_RANGE[0], maxval=A_RANGE[1])).astype(dt),
        d_skip=jnp.ones((heads,), dt),
        y_norm=_gain(k[11], (d,), dt),
        w_out=_normal(k[12], (d, h), d, dt),
    )
    return w


def expert_weights(key, li, e0, n: int, sizes: dict) -> dict:
    """Experts ``e0 .. e0+n-1`` of layer ``li``'s bank (``e0`` counts in
    the WHOLE bank; traceable in ``li`` and ``e0``): each expert's numbers
    depend on its own index only, so any chunking and any share give the
    same experts."""
    m = model()
    h, fe = sizes["hidden"], m["fe"]
    dt = _dtype(sizes)
    base = jax.random.fold_in(jax.random.fold_in(key, li + 1), 1)

    def one(e):
        k = jax.random.split(jax.random.fold_in(base, e), 3)
        return dict(
            we_gate=_normal(k[0], (h, fe), h, dt),
            we_up=_normal(k[1], (h, fe), h, dt),
            we_down=_normal(k[2], (fe, h), fe, dt),
        )

    return jax.vmap(one)(e0 + jnp.arange(n, dtype=jnp.int32))


def layer_weights(key, li: int, sizes: dict) -> dict:
    """Layer ``li`` whole, in the plain layout (``li`` a Python int: the
    layer's kind depends on it). The bank is the share held here."""
    w = core_weights(key, li, sizes, is_attention(li))
    first, count = model()["held"]
    w.update(expert_weights(key, li, first, count, sizes))
    return w


def outer_weights(key, sizes: dict) -> dict:
    """Embedding (the head too: tied) and final norm over ``sizes["vocab"]``
    rows: the slice held here IS the vocabulary."""
    h, v = sizes["hidden"], sizes["vocab"]
    dt = _dtype(sizes)
    k = jax.random.split(jax.random.fold_in(key, 0), 2)
    return dict(
        # (module docstring: the scaled lookup has the usual 0.02)
        embed=(jax.random.normal(k[0], (v, h), jnp.float32)
               * (0.02 / model()["emb"])).astype(dt),
        final_norm=_gain(k[1], (h,), dt),
    )


# -- equations -----------------------------------------------------------------

def _q8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(x, w, control: bool):
    """``x [..., K] @ w [K, N]`` (or a bank ``w [E, K, N]`` against
    ``x [E, T, K]``) in float32; the control quantizes weights per output
    column and activations per row."""
    w = w.astype(jnp.float32)
    if control:
        x, w = _q8(x, -1), _q8(w, -2)
    return jnp.matmul(x, w, precision=HI)


def _f32(x):
    return x.astype(jnp.float32)


def _norm(x, w, eps):
    r = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * r * _f32(w)


def recurrence(x, dt, a, b_in, c_out, d_skip, h0=None):
    """``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t C_t +
    D x_t`` over one sequence, token by token: ``x [T, heads, P]``, ``dt
    [T, heads]``, ``a, d_skip [heads]``, ``b_in, c_out [T, N]`` -> ``(y
    [T, heads, P], h_T [heads, P, N])``."""
    if h0 is None:
        h0 = jnp.zeros((*x.shape[1:], b_in.shape[1]), jnp.float32)

    def step(h, xs):
        x_t, dt_t, b_t, c_t = xs
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x_t)[..., None] * b_t)
        return h, jnp.sum(h * c_t, -1) + d_skip[:, None] * x_t

    h, y = jax.lax.scan(step, h0, (x, dt, b_in, c_out))
    return y, h


def mamba_parts(x, w, sizes: dict, control: bool):
    """One sequence ``x [T, H]`` (normed) through the Mamba-2 mixer: ``(out
    [T, H], h_T [heads, P, N], xBC [T, d + 2N])``, the state after the last
    token and the convolution's inputs beside the mixer's output."""
    m = model()
    heads, p, n, kc = m["heads"], m["P"], m["N"], m["K"]
    d, t = heads * p, x.shape[0]
    zxd = _mm(x, w["w_in"], control)
    z, xbc, dt = zxd[:, :d], zxd[:, d:2 * d + 2 * n], zxd[:, 2 * d + 2 * n:]
    padded = jnp.pad(xbc, ((kc - 1, 0), (0, 0)))
    conv = _f32(w["conv_b"]) + sum(
        _f32(w["conv_w"])[:, j] * padded[j:j + t] for j in range(kc))
    act = jax.nn.silu(conv)
    xs, b_in, c_out = act[:, :d], act[:, d:d + n], act[:, d + n:]
    dt = jax.nn.softplus(dt + _f32(w["dt_bias"]))
    y, h = recurrence(xs.reshape(t, heads, p), dt, -jnp.exp(_f32(w["a_log"])),
                      b_in, c_out, _f32(w["d_skip"]))
    y = _norm(y.reshape(t, d) * jax.nn.silu(z), w["y_norm"], sizes["norm_eps"])
    return _mm(y, w["w_out"], control), h, xbc


def mamba(x, w, sizes: dict, control: bool, block=None):
    return mamba_parts(x, w, sizes, control)[0]


def attention(x, w, sizes: dict, control: bool, block: int | None = None):
    """One sequence's normed rows ``x [T, H]`` through causal attention, no
    rotation, scores times ``attention_multiplier``; queries ``block`` at a
    time (default: all at once) against every key under an explicit
    mask."""
    t, d = x.shape[0], sizes["head_dim"]
    hq, hkv = sizes["n_q_heads"], sizes["n_kv_heads"]
    scale = model()["attn"]
    q = _mm(x, w["wq"], control).reshape(t, hq, d)
    k = _mm(x, w["wk"], control).reshape(t, hkv, d)
    v = _mm(x, w["wv"], control).reshape(t, hkv, d)
    block = min(block or t, t)
    if t % block:
        raise ValueError(f"{t} positions are not whole blocks of {block}")
    # query head i reads kv head i // (hq / hkv)
    q = q.reshape(t // block, block, hkv, hq // hkv, d)
    kp = jnp.arange(t)[None, :]

    def rows(args):
        qb, q0 = args
        s = jnp.einsum("shgd,thd->hgst", qb, k, precision=HI) * scale
        mask = kp <= q0 + jnp.arange(block)[:, None]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        return jnp.einsum("hgst,thd->shgd", jax.nn.softmax(s, -1), v,
                          precision=HI)

    o = jax.lax.map(rows, (q, jnp.arange(t // block) * block))
    return _mm(o.reshape(t, hq * d), w["wo"], control)


def mixer_block(x, w, sizes: dict, control: bool, block: int | None = None):
    """``x + residual_multiplier * mixer(norm_in(x))`` over ``x [n, T,
    H]``, one sequence at a time (the weights say which kind it is)."""
    mixer = attention if "wq" in w else mamba
    h = _norm(x, w["norm_in"], sizes["norm_eps"])
    return x + model()["res"] * jax.lax.map(
        lambda s: mixer(s, w, sizes, control, block), h)


def _swiglu(x, gate, up, down, control: bool):
    act = jax.nn.silu(_mm(x, gate, control)) * _mm(x, up, control)
    return _mm(act, down, control)


def combine_weights(u, w, control: bool):
    """``[T, E]``: each token's weight on every expert (0 if not chosen):
    top-k of the router's logits, softmax over the chosen."""
    logits = _mm(u, w["router"], control)
    top, ids = jax.lax.top_k(logits, model()["topk"])
    t = u.shape[0]
    return jnp.zeros((t, model()["E"]), jnp.float32).at[
        jnp.arange(t)[:, None], ids].add(jax.nn.softmax(top, -1))


def experts_part(u, comb, bank: dict, control: bool):
    """``sum_e comb[:, e] * E_e(u)`` over the experts of ``bank``
    (``comb [T, n]`` their columns): every expert on every token, the
    plainest form; a weight of 0 leaves an expert out."""
    n = bank["we_gate"].shape[0]
    ue = jnp.broadcast_to(u, (n, *u.shape))
    y = _swiglu(ue, bank["we_gate"], bank["we_up"], bank["we_down"], control)
    return jnp.einsum("te,eth->th", comb, y, precision=HI)


def shared_part(u, w, control: bool):
    return _swiglu(u, w["ws_gate"], w["ws_up"], w["ws_down"], control)


def moe_part(u, w, control: bool):
    """The expert MLP's output on rows ``u [T, H]`` from a layer's plain
    weights (the bank whole: small sizes): the held experts' parts and the
    shared expert."""
    first, count = model()["held"]
    comb = combine_weights(u, w, control)[:, first:first + count]
    return experts_part(u, comb, w, control) + shared_part(u, w, control)


def layer(x, w, sizes: dict, li: int | None = None, control: bool = False,
          block: int | None = None):
    """A decoder layer whole over ``x [n, T, H]`` from the plain weights of
    :func:`layer_weights` (the weights say which kind it is)."""
    x = mixer_block(x, w, sizes, control, block)
    n, t, hid = x.shape
    u = _norm(x, w["norm_ff"], sizes["norm_eps"]).reshape(n * t, hid)
    return x + model()["res"] * moe_part(u, w, control).reshape(n, t, hid)


def embed(outer, tokens):
    return model()["emb"] * outer["embed"][tokens].astype(jnp.float32)


def head(x, outer, first, n_new: int, sizes: dict, control: bool):
    """Logits ``[n, n_new, V]`` at the ``n_new`` positions from ``first``
    on: the positions that predict the served tokens. The head is the
    embedding, transposed; the logits are divided by ``logits_scaling``."""
    idx = first[:, None] + jnp.arange(n_new, dtype=jnp.int32)[None, :]
    xs = jnp.take_along_axis(x, idx[:, :, None], axis=1)
    xs = _norm(xs, outer["final_norm"], sizes["norm_eps"])
    return _mm(xs, outer["embed"].T, control) / model()["logits"]


# -- the run, in blocks --------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _programs(sizes_items: tuple):
    sizes = dict(sizes_items)
    eps = sizes["norm_eps"]

    def jit(*static, donate=()):
        return functools.partial(
            jax.jit, static_argnames=static, donate_argnums=donate)

    @jit("attention")
    def gen_core(key, li, attention):
        return core_weights(key, li, sizes, attention)

    @jit("n")
    def gen_experts(key, li, e0, n):
        return expert_weights(key, li, e0, n, sizes)

    @jit("control", "block", donate=(0,))
    def run_mixer(x, w, control, block):
        return mixer_block(x, w, sizes, control, block)

    @jit("control")
    def moe_open(x, w, control):
        n, t, hid = x.shape
        u = _norm(x, w["norm_ff"], eps).reshape(n * t, hid)
        return u, combine_weights(u, w, control), shared_part(u, w, control)

    @jit("control", donate=(0,))
    def moe_add(acc, u, comb, e0, bank, control):
        n = bank["we_gate"].shape[0]
        cols = jax.lax.dynamic_slice_in_dim(comb, e0, n, 1)
        return acc + experts_part(u, cols, bank, control)

    @jit(donate=(0,))
    def moe_close(x, acc):
        return x + model()["res"] * acc.reshape(x.shape)

    return dict(
        gen_core=gen_core, gen_experts=gen_experts, run_mixer=run_mixer,
        moe_open=moe_open, moe_add=moe_add, moe_close=moe_close,
        gen_outer=jax.jit(functools.partial(outer_weights, sizes=sizes)),
        embed=jax.jit(embed),
        run_head=jax.jit(functools.partial(head, sizes=sizes),
                         static_argnames=("n_new", "control")),
    )


def logits(sizes: dict, seed: int, tokens, first, n_new: int, *,
           control: bool = False, devices=None, query_block=QUERY_BLOCK,
           expert_chunk=EXPERT_CHUNK):
    """The reference's logits ``[n, n_new, V]`` (a device array) for
    ``tokens [n, T]`` at positions ``first[i] .. first[i] + n_new - 1``.
    Weights come from ``seed``: a layer's core at a time and its bank
    ``expert_chunk`` experts at a time, dropped after use; attention's
    queries ``query_block`` at a time. One device: ``devices`` of more
    than one are refused (the configuration is a one-chip one)."""
    if devices is not None and len(devices) > 1:
        raise NotImplementedError("granite_ssd_moe runs on one device")
    p = _programs(tuple(sorted(sizes.items(), key=lambda kv: kv[0])))
    first_e, count = model()["held"]
    key = seed_key(seed)
    outer = p["gen_outer"](key)
    tokens = jnp.asarray(tokens, jnp.int32)
    block = min(query_block, tokens.shape[1])
    x = p["embed"](outer, tokens)
    for li in range(sizes["n_layers"]):
        w = p["gen_core"](key, jnp.int32(li), attention=is_attention(li))
        x = p["run_mixer"](x, w, control=control, block=block)
        u, comb, acc = p["moe_open"](x, w, control=control)
        for e0 in range(first_e, first_e + count, expert_chunk):
            n = min(expert_chunk, first_e + count - e0)
            bank = p["gen_experts"](key, jnp.int32(li), jnp.int32(e0), n=n)
            acc = p["moe_add"](acc, u, comb, jnp.int32(e0), bank,
                               control=control)
        x = p["moe_close"](x, acc)
    return p["run_head"](x, outer, jnp.asarray(first, jnp.int32), n_new=n_new,
                         control=control)


def gaps(ref_logits, judged):
    """How far each judged token ``[n, n_new]`` lies below the
    reference's best logit at its position, and whether it is that best."""
    judged = jnp.asarray(judged, jnp.int32)
    got = jnp.take_along_axis(ref_logits, judged[..., None], -1)[..., 0]
    best = ref_logits.max(-1)
    return np.asarray(best - got), np.asarray(ref_logits.argmax(-1) == judged)
