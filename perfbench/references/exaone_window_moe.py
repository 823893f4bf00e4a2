"""Plain reference for the EXAONE-MoE family's decoder (K-EXAONE-236B-A23B):
grouped-query attention that is a sliding WINDOW on some layers and FULL
on the others, a leading dense SwiGLU layer, then layers of
sigmoid-routed SwiGLU experts with a shared expert, untied head. Straight
``jax.numpy`` in float32 at ``precision=HIGHEST``: no kernel, no cache, no
band, no ring, no grouped GEMM, no batching, and nothing imported from
the program under test.

Equations (``x [T, H]``; every norm an RMSNorm, eps ``norm_eps``):

- layer ``l``: attention kind from ``sliding_windows[l]`` (0 = full, else
  the window), MLP kind dense for ``l < first_k_dense_replace``.
- attention: ``q = x W_q`` -> heads x d, ``k, v = x W_k, x W_v`` -> kv
  heads x d (no bias); ``q`` and ``k`` normed over d; on a window layer
  both rotated (``rope_theta``, the whole head width, half-split: element
  ``i`` pairs with ``i + d/2``), a full layer is NOT rotated; scores
  ``q.k / sqrt(d)`` under an explicit ``[T, T]`` mask: causal, and on a
  window layer also ``t > s - window`` (``window`` keys, the query's own
  among them); ``y = softmax(s) v W_o``.
- the block: ``x = x + norm(attention(x))``; ``x = x + norm(mlp(x))``:
  each sub-layer's norm on its OUTPUT, no input norm.
- dense MLP: ``down(silu(gate(x)) * up(x))``.
- expert MLP: ``s = sigmoid(x W_r)``; chosen = top-k of ``s + b`` (``b``
  moves the choice, never the weight; ``n_group`` 1: no grouping);
  ``w = s[chosen] / sum(s[chosen]) * routed_scaling_factor``;
  ``y = sum_k w_k E_k(x) + E_shared(x)``. No token is dropped.

ASSUMED (not among the catalog row's keys; taken from the family's
published block, EXAONE 4.0, and listed in the configuration's file): the
q/k norms, rotation on the window layers only, the output norms with no
input norm. DEPARTURE: the multi-token-prediction module is not loaded;
it changes no logit of the main model.

THE SHARE. ``experts_held [first, count]`` is the chip's share of each
bank: the router scores all ``E`` experts and chooses among all of them,
and only the held experts' parts (and the shared expert) are added; a
token none of whose experts is held gets the shared expert alone, and
that partial result goes on to the next layer. ``sizes["vocab"]`` is the
slice of the vocabulary held: embedding, head and logits are over it.

It OWNS the weights (bf16, from the seed, plain layout below); the adapter
packs them into the program's layout. A layer's bank is made and used some
experts at a time (:func:`expert_weights`), so that float32 copies of 3.7 B
parameters never stand at once; :func:`layer_weights` is the same numbers
whole.

    wq [H, hq*d]  wk, wv [H, hkv*d]  wo [hq*d, H]  q_norm, k_norm [d]
    attn_norm, mlp_norm [H]            (on the sub-layer's OUTPUT)
    w_gate, w_up [H, F]  w_down [F, H]                  (dense layers)
    router [H, E]  router_bias [E] f32                  (expert layers)
    we_gate, we_up [n, H, Fe]  we_down [n, Fe, H]
    ws_gate, ws_up [H, Fs]  ws_down [Fs, H]
    embed [V, H]  lm_head [H, V]  final_norm [H]

``control=True`` is the lower-precision twin the comparison must reject:
every projection (experts and router input included) as W8A8 int8.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
EXPERT_CHUNK = 4
# The weight of the norm on attention's OUTPUT, against 1 on the MLP's.
# Attention's output over a diffuse random softmax is nearly the same
# vector for every token of a sequence. Normed to RMS 1 it was half the
# residual stream: the router hardly saw the token, each sequence kept
# hitting the same few experts for its whole answer (its 16 most used took
# 29-42% of its assignments; 12.5% if even), the held experts a step hit
# followed the seed (12.6 or 13.2 a layer) and the cell's round time with
# them by 3% (PERF.md section 6, PR 32). A trained router's balancing bias
# removes that; random weights need the token to lead the stream instead.
ATTN_OUT_GAIN = 0.25

# published key -> short name used below
_KEYS = dict(
    num_experts_per_tok="topk", moe_intermediate_size="fe",
    num_shared_experts="n_shared", first_k_dense_replace="k_dense",
    routed_scaling_factor="scaling",
)
_MODEL: dict = {}


def configure(config: dict) -> None:
    """Take the model's own keys from the configuration file (published
    names). ``num_experts`` in the file counts the experts HELD
    (``experts_held [first, count]``); the router's width is the
    published count (``published.num_experts``, or the file's own where
    nothing is cut)."""
    m = {short: config[key] for key, short in _KEYS.items()}
    if config.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("this reference scores experts by sigmoid")
    if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
        raise ValueError("grouped top-k (n_group > 1) is not written here")
    if not config.get("norm_topk_prob", True):
        raise ValueError("this reference normalizes the chosen weights")
    m["E"] = (config.get("published") or config)["num_experts"]
    m["held"] = tuple(config.get("experts_held") or (0, m["E"]))
    if m["held"][1] != config["num_experts"]:
        raise ValueError("num_experts must count the experts held")
    # 0 = full attention, else the window (one entry a published layer)
    m["windows"] = tuple(int(w) for w in config["sliding_windows"])
    kinds = config.get("layer_types")
    if kinds and any((k == "sliding_attention") != (w > 0)
                     for k, w in zip(kinds, m["windows"])):
        raise ValueError("layer_types and sliding_windows disagree")
    _MODEL.clear()
    _MODEL.update(m)
    _programs.cache_clear()


def model() -> dict:
    if not _MODEL:
        raise RuntimeError(
            "exaone_window_moe: configure(config) first (the adapter's "
            "System does): the model's keys are not among the sizes")
    return _MODEL


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def _dtype(sizes: dict):
    return jnp.dtype(sizes.get("dtype", "bfloat16"))


def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * fan_in ** -0.5).astype(dtype)


def _gain(key, shape, dtype, scale: float = 1.0):
    """A norm's weight: near ``scale``, not it, so that where a norm sits
    shows."""
    gain = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    return (scale * gain).astype(dtype)


def is_dense(li: int) -> bool:
    return li < model()["k_dense"]


def window_of(li: int) -> int:
    """Layer ``li``'s window; 0 = full attention."""
    return model()["windows"][li]


# -- weights -------------------------------------------------------------------

def core_weights(key, li, sizes: dict, dense: bool) -> dict:
    """Everything of layer ``li`` but the routed expert bank (traceable in
    ``li``; the layer's MLP kind is static, and a window and a full layer
    hold the same tensors)."""
    m = model()
    h, d = sizes["hidden"], sizes["head_dim"]
    hq, hkv = sizes["n_q_heads"], sizes["n_kv_heads"]
    dt = _dtype(sizes)
    k = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, li + 1), 0), 16)
    w = dict(
        wq=_normal(k[0], (h, hq * d), h, dt),
        wk=_normal(k[1], (h, hkv * d), h, dt),
        wv=_normal(k[2], (h, hkv * d), h, dt),
        wo=_normal(k[3], (hq * d, h), hq * d, dt),
        q_norm=_gain(k[4], (d,), dt),
        k_norm=_gain(k[5], (d,), dt),
        attn_norm=_gain(k[6], (h,), dt, ATTN_OUT_GAIN),
        mlp_norm=_gain(k[7], (h,), dt),
    )
    if dense:
        f = sizes["ffn"]
        w.update(
            w_gate=_normal(k[8], (h, f), h, dt),
            w_up=_normal(k[9], (h, f), h, dt),
            w_down=_normal(k[10], (f, h), f, dt),
        )
    else:
        fs = m["fe"] * m["n_shared"]
        w.update(
            router=_normal(k[8], (h, m["E"]), h, dt),
            router_bias=jax.random.normal(k[9], (m["E"],), jnp.float32) * 0.01,
            ws_gate=_normal(k[11], (h, fs), h, dt),
            ws_up=_normal(k[12], (h, fs), h, dt),
            ws_down=_normal(k[13], (fs, h), fs, dt),
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
    dense = is_dense(li)
    w = core_weights(key, li, sizes, dense)
    if not dense:
        first, count = model()["held"]
        w.update(expert_weights(key, li, first, count, sizes))
    return w


def outer_weights(key, sizes: dict) -> dict:
    """Embedding, final norm and head over ``sizes["vocab"]`` rows: the
    slice held here IS the vocabulary."""
    h, v = sizes["hidden"], sizes["vocab"]
    dt = _dtype(sizes)
    k = jax.random.split(jax.random.fold_in(key, 0), 3)
    return dict(
        embed=(jax.random.normal(k[0], (v, h), jnp.float32) * 0.02).astype(dt),
        final_norm=_gain(k[2], (h,), dt),
        lm_head=_normal(k[1], (h, v), h, dt),
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


def _norm(x, w, eps):
    r = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * r * w.astype(jnp.float32)


def _rope_halves(x, theta):
    """Rotate ``x [T, heads, d]`` by position (axis 0): element ``i`` of
    the first half pairs with element ``i`` of the second."""
    t, d = x.shape[0], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(x, w, sizes: dict, window: int, control: bool):
    """One sequence ``x [T, H]``; ``window`` 0 = full attention."""
    t, d = x.shape[0], sizes["head_dim"]
    hq, hkv = sizes["n_q_heads"], sizes["n_kv_heads"]
    eps = sizes["norm_eps"]
    q = _norm(_mm(x, w["wq"], control).reshape(t, hq, d), w["q_norm"], eps)
    k = _norm(_mm(x, w["wk"], control).reshape(t, hkv, d), w["k_norm"], eps)
    v = _mm(x, w["wv"], control).reshape(t, hkv, d)
    if window:
        q = _rope_halves(q, sizes["rope_theta"])
        k = _rope_halves(k, sizes["rope_theta"])
    # query head h reads kv head h // (hq / hkv)
    q = q.reshape(t, hkv, hq // hkv, d)
    s = jnp.einsum("shgd,thd->hgst", q, k, precision=HI) / np.sqrt(d)
    qp, kp = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = kp <= qp
    if window:
        mask = mask & (kp > qp - window)
    s = jnp.where(mask[None, None], s, -jnp.inf)
    o = jnp.einsum("hgst,thd->shgd", jax.nn.softmax(s, -1), v, precision=HI)
    return _mm(o.reshape(t, hq * d), w["wo"], control)


def _swiglu(x, gate, up, down, control: bool):
    act = jax.nn.silu(_mm(x, gate, control)) * _mm(x, up, control)
    return _mm(act, down, control)


def route(x, w, control: bool):
    """``(weights [T, topk], ids [T, topk])`` over the whole bank."""
    m = model()
    s = jax.nn.sigmoid(_mm(x, w["router"], control))
    _, ids = jax.lax.top_k(s + w["router_bias"], m["topk"])
    chosen = jnp.take_along_axis(s, ids, -1)
    return chosen / chosen.sum(-1, keepdims=True) * m["scaling"], ids


def combine_weights(x, w, control: bool):
    """``[T, E]``: each token's weight on every expert (0 if not chosen)."""
    wts, ids = route(x, w, control)
    t = x.shape[0]
    return jnp.zeros((t, model()["E"]), jnp.float32).at[
        jnp.arange(t)[:, None], ids].add(wts)


def experts_part(x, comb, bank: dict, control: bool):
    """``sum_e comb[:, e] * E_e(x)`` over the experts of ``bank``
    (``comb [T, n]`` their columns): every expert on every token, the
    plainest form; a weight of 0 leaves an expert out."""
    n = bank["we_gate"].shape[0]
    xe = jnp.broadcast_to(x, (n, *x.shape))
    y = _swiglu(xe, bank["we_gate"], bank["we_up"], bank["we_down"], control)
    return jnp.einsum("te,eth->th", comb, y, precision=HI)


def shared_part(x, w, control: bool):
    return _swiglu(x, w["ws_gate"], w["ws_up"], w["ws_down"], control)


def attn_block(x, w, sizes: dict, window: int, control: bool):
    """``x [n, T, H] + norm(attention(x))``, one sequence at a time."""
    y = jax.lax.map(lambda s: attention(s, w, sizes, window, control), x)
    return x + _norm(y, w["attn_norm"], sizes["norm_eps"])


def dense_block(x, w, sizes: dict, control: bool):
    y = _swiglu(x, w["w_gate"], w["w_up"], w["w_down"], control)
    return x + _norm(y, w["mlp_norm"], sizes["norm_eps"])


def moe_part(h, w, control: bool):
    """The expert MLP's output on rows ``h [T, H]`` from a layer's plain
    weights (the bank whole: small sizes): the held experts' parts and
    the shared expert."""
    first, count = model()["held"]
    comb = combine_weights(h, w, control)[:, first:first + count]
    return experts_part(h, comb, w, control) + shared_part(h, w, control)


def layer(x, w, sizes: dict, li: int, control: bool = False):
    """Decoder layer ``li`` whole over ``x [n, T, H]`` from the plain
    weights of :func:`layer_weights`."""
    x = attn_block(x, w, sizes, window_of(li), control)
    if "w_gate" in w:
        return dense_block(x, w, sizes, control)
    n, t, hid = x.shape
    y = moe_part(x.reshape(n * t, hid), w, control)
    return x + _norm(y.reshape(n, t, hid), w["mlp_norm"], sizes["norm_eps"])


def head(x, outer, first, n_new: int, sizes: dict, control: bool):
    """Logits ``[n, n_new, V]`` at the ``n_new`` positions from ``first``
    on: the positions that predict the served tokens."""
    idx = first[:, None] + jnp.arange(n_new, dtype=jnp.int32)[None, :]
    xs = jnp.take_along_axis(x, idx[:, :, None], axis=1)
    xs = _norm(xs, outer["final_norm"], sizes["norm_eps"])
    return _mm(xs, outer["lm_head"], control)


# -- the run, in blocks --------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _programs(sizes_items: tuple):
    sizes = dict(sizes_items)

    def jit(*static, donate=()):
        return functools.partial(
            jax.jit, static_argnames=static, donate_argnums=donate)

    @jit("dense")
    def gen_core(key, li, dense):
        return core_weights(key, li, sizes, dense)

    @jit("n")
    def gen_experts(key, li, e0, n):
        return expert_weights(key, li, e0, n, sizes)

    @jit("window", "control", donate=(0,))
    def run_attn(x, w, window, control):
        return attn_block(x, w, sizes, window, control)

    @jit("control", donate=(0,))
    def run_dense(x, w, control):
        return dense_block(x, w, sizes, control)

    @jit("control")
    def moe_open(x, w, control):
        n, t, hid = x.shape
        h = x.reshape(n * t, hid)
        return h, combine_weights(h, w, control), shared_part(h, w, control)

    @jit("control", donate=(0,))
    def moe_add(acc, h, comb, e0, bank, control):
        n = bank["we_gate"].shape[0]
        cols = jax.lax.dynamic_slice_in_dim(comb, e0, n, 1)
        return acc + experts_part(h, cols, bank, control)

    @jit(donate=(0,))
    def moe_close(x, acc, w):
        return x + _norm(acc.reshape(x.shape), w["mlp_norm"],
                         sizes["norm_eps"])

    return dict(
        gen_core=gen_core, gen_experts=gen_experts, run_attn=run_attn,
        run_dense=run_dense, moe_open=moe_open, moe_add=moe_add,
        moe_close=moe_close,
        gen_outer=jax.jit(functools.partial(outer_weights, sizes=sizes)),
        run_head=jax.jit(functools.partial(head, sizes=sizes),
                         static_argnames=("n_new", "control")),
    )


def logits(sizes: dict, seed: int, tokens, first, n_new: int, *,
           control: bool = False, devices=None):
    """The reference's logits ``[n, n_new, V]`` (a device array) for
    ``tokens [n, T]`` at positions ``first[i] .. first[i] + n_new - 1``.
    Weights come from ``seed``: a layer's core at a time and its bank
    ``EXPERT_CHUNK`` experts at a time, dropped after use. One device:
    ``devices`` of more than one are refused (the configuration is a
    one-chip one)."""
    if devices is not None and len(devices) > 1:
        raise NotImplementedError("exaone_window_moe runs on one device")
    p = _programs(tuple(sorted(sizes.items())))
    first_e, count = model()["held"]
    key = seed_key(seed)
    outer = p["gen_outer"](key)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = outer["embed"][tokens].astype(jnp.float32)
    for li in range(sizes["n_layers"]):
        dense = is_dense(li)
        w = p["gen_core"](key, jnp.int32(li), dense=dense)
        x = p["run_attn"](x, w, window=window_of(li), control=control)
        if dense:
            x = p["run_dense"](x, w, control=control)
            continue
        h, comb, acc = p["moe_open"](x, w, control=control)
        for e0 in range(first_e, first_e + count, EXPERT_CHUNK):
            n = min(EXPERT_CHUNK, first_e + count - e0)
            bank = p["gen_experts"](key, jnp.int32(li), jnp.int32(e0), n=n)
            acc = p["moe_add"](acc, h, comb, jnp.int32(e0), bank,
                               control=control)
        x = p["moe_close"](x, acc, w)
    return p["run_head"](x, outer, jnp.asarray(first, jnp.int32), n_new=n_new,
                         control=control)


def gaps(ref_logits, judged):
    """How far each judged token ``[n, n_new]`` lies below the
    reference's best logit at its position, and whether it is that best."""
    judged = jnp.asarray(judged, jnp.int32)
    got = jnp.take_along_axis(ref_logits, judged[..., None], -1)[..., 0]
    best = ref_logits.max(-1)
    return np.asarray(best - got), np.asarray(ref_logits.argmax(-1) == judged)
