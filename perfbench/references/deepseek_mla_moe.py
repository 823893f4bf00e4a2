"""Plain reference for the DeepSeek-V3 family's decoder (JoyAI-LLM-Flash):
latent attention (MLA) in its EXPANDED form, a leading dense SwiGLU layer,
then layers of sigmoid-routed SwiGLU experts with a shared expert, untied
head. Straight ``jax.numpy`` in float32 at ``precision=HIGHEST``: no
kernel, no cache, no absorbed attention, no grouped GEMM, and nothing
imported from the program under test.

Equations (``x [T, H]``; every norm an RMSNorm):

- attention: ``c_q = norm(x W_qa)``; ``q = c_q W_qb`` -> heads x (nope |
  rope). ``[c_kv | k_r] = x W_kva``; ``c_kv = norm(c_kv)``; ``k_r`` and
  ``q_r`` rotated on adjacent pairs ``(2i, 2i+1)`` (``rope_interleave``),
  ``k_r`` shared by all heads. ``[k_n | v] = c_kv W_kvb`` per head.
  ``s = (q_n.k_n + q_r.k_r) / sqrt(nope + rope)``, causal softmax,
  ``o = s v``, ``y = o W_o``.
- layer < ``first_k_dense_replace``: ``down(silu(gate(x)) * up(x))``.
- other layers: ``s = sigmoid(x W_r)``; chosen = top-k of ``s + b``
  (``b`` = ``e_score_correction_bias``: it moves the choice, never the
  weight); ``w = s[chosen] / sum(s[chosen]) * routed_scaling_factor``;
  ``y = sum_k w_k E_k(x) + E_shared(x)``, each ``E`` a SwiGLU of the
  expert width. No token is dropped.

The model's own keys (ranks, head widths, expert count and width...) are
not among the harness's ``sizes``: the adapter hands the whole
configuration to :func:`configure` first, and nothing runs before that.

It OWNS the weights (bf16, from the seed, plain layout below); the
adapter packs them into the program's layout. An expert layer is 2.5 GB,
so the bank is made and used a chunk of experts at a time
(:func:`expert_weights`); :func:`layer_weights` is the same numbers whole.

    wq_a [H, rq]  wq_b [rq, nh*(nope+rope)]  wkv_a [H, rkv+rope]
    wkv_b [rkv, nh*(nope+v)] (per head: k_nope | v)  wo [nh*v, H]
    w_gate, w_up [H, F]  w_down [F, H]                  (dense layers)
    router [H, E]  router_bias [E] f32                  (expert layers)
    we_gate, we_up [E, H, Fe]  we_down [E, Fe, H]
    ws_gate, ws_up [H, Fs]  ws_down [Fs, H]
    embed [V, H]  lm_head [H, V]  norms [.]

``control=True`` is the lower-precision twin the comparison must reject:
every projection (experts and router input included) as W8A8 int8.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
EXPERT_CHUNK = 16

# published key -> short name used below
_KEYS = dict(
    q_lora_rank="rq", kv_lora_rank="rkv", qk_nope_head_dim="nope",
    qk_rope_head_dim="rope", v_head_dim="dv", n_routed_experts="E",
    num_experts_per_tok="topk", moe_intermediate_size="fe",
    n_shared_experts="n_shared", first_k_dense_replace="k_dense",
    routed_scaling_factor="scaling",
)
_MODEL: dict = {}


def configure(config: dict) -> None:
    """Take the model's own keys from the configuration file (published
    names). ``experts_held`` ``[first, count]``, if present, is the chip's
    share of each bank: the reference is given the same share."""
    m = {short: config[key] for key, short in _KEYS.items()}
    if config.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("this reference scores experts by sigmoid")
    if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
        raise ValueError("grouped top-k (n_group > 1) is not written here")
    m["held"] = tuple(config.get("experts_held") or (0, m["E"]))
    _MODEL.clear()
    _MODEL.update(m)
    _programs.cache_clear()


def model() -> dict:
    if not _MODEL:
        raise RuntimeError(
            "deepseek_mla_moe: configure(config) first (the adapter's "
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


def is_dense(li: int) -> bool:
    return li < model()["k_dense"]


# -- weights -------------------------------------------------------------------

def core_weights(key, li, sizes: dict, dense: bool) -> dict:
    """Everything of layer ``li`` but the routed expert bank (traceable in
    ``li``; the layer's kind is static)."""
    m = model()
    h, nh = sizes["hidden"], sizes["n_q_heads"]
    dt = _dtype(sizes)
    k = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, li + 1), 0), 12)
    w = dict(
        attn_norm=jnp.ones((h,), dt),
        wq_a=_normal(k[0], (h, m["rq"]), h, dt),
        q_norm=jnp.ones((m["rq"],), dt),
        wq_b=_normal(k[1], (m["rq"], nh * (m["nope"] + m["rope"])), m["rq"], dt),
        wkv_a=_normal(k[2], (h, m["rkv"] + m["rope"]), h, dt),
        kv_norm=jnp.ones((m["rkv"],), dt),
        wkv_b=_normal(k[3], (m["rkv"], nh * (m["nope"] + m["dv"])), m["rkv"], dt),
        wo=_normal(k[4], (nh * m["dv"], h), nh * m["dv"], dt),
        mlp_norm=jnp.ones((h,), dt),
    )
    if dense:
        f = sizes["ffn"]
        w.update(
            w_gate=_normal(k[5], (h, f), h, dt),
            w_up=_normal(k[6], (h, f), h, dt),
            w_down=_normal(k[7], (f, h), f, dt),
        )
    else:
        fs = m["fe"] * m["n_shared"]
        w.update(
            router=_normal(k[5], (h, m["E"]), h, dt),
            router_bias=jax.random.normal(k[6], (m["E"],), jnp.float32) * 0.01,
            ws_gate=_normal(k[8], (h, fs), h, dt),
            ws_up=_normal(k[9], (h, fs), h, dt),
            ws_down=_normal(k[10], (fs, h), fs, dt),
        )
    return w


def expert_weights(key, li, e0, n: int, sizes: dict) -> dict:
    """Experts ``e0 .. e0+n-1`` of layer ``li``'s bank (``e0`` counts in
    the WHOLE bank; traceable in ``li`` and ``e0``): each expert's numbers
    depend on its own index only, so any chunking gives the same bank."""
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
    h, v = sizes["hidden"], sizes["vocab"]
    dt = _dtype(sizes)
    k = jax.random.split(jax.random.fold_in(key, 0), 2)
    return dict(
        embed=(jax.random.normal(k[0], (v, h), jnp.float32) * 0.02).astype(dt),
        final_norm=jnp.ones((h,), dt),
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


def _rope_pairs(x, theta):
    """Rotate adjacent pairs ``(2i, 2i+1)`` of the last axis by position
    (axis 0): x ``[T, ..., d]``."""
    t, d = x.shape[0], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    ang = ang.reshape(t, *([1] * (x.ndim - 2)), d // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape)


def attention(x, w, sizes: dict, control: bool):
    """Expanded MLA over one sequence ``x [T, H]`` (already normed)."""
    m = model()
    t, nh = x.shape[0], sizes["n_q_heads"]
    nope, rope, dv, rkv = m["nope"], m["rope"], m["dv"], m["rkv"]
    eps, theta = sizes["norm_eps"], sizes["rope_theta"]
    c_q = _norm(_mm(x, w["wq_a"], control), w["q_norm"], eps)
    q = _mm(c_q, w["wq_b"], control).reshape(t, nh, nope + rope)
    q_n, q_r = q[..., :nope], _rope_pairs(q[..., nope:], theta)
    kva = _mm(x, w["wkv_a"], control)
    c_kv = _norm(kva[:, :rkv], w["kv_norm"], eps)
    k_r = _rope_pairs(kva[:, rkv:], theta)                   # [T, rope]
    kv = _mm(c_kv, w["wkv_b"], control).reshape(t, nh, nope + dv)
    k_n, v = kv[..., :nope], kv[..., nope:]
    s = (jnp.einsum("shd,thd->hst", q_n, k_n, precision=HI)
         + jnp.einsum("shd,td->hst", q_r, k_r, precision=HI))
    s = s / np.sqrt(nope + rope)
    pos = jnp.arange(t)
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    o = jnp.einsum("hst,thd->shd", jax.nn.softmax(s, -1), v, precision=HI)
    return _mm(o.reshape(t, nh * dv), w["wo"], control)


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


def attn_block(x, w, sizes: dict, control: bool):
    """``x [n, T, H] + attention``, one sequence at a time."""
    h = _norm(x, w["attn_norm"], sizes["norm_eps"])
    return x + jax.lax.map(lambda s: attention(s, w, sizes, control), h)


def dense_block(x, w, sizes: dict, control: bool):
    h = _norm(x, w["mlp_norm"], sizes["norm_eps"])
    return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], control)


def layer(x, w, sizes: dict, control: bool = False):
    """One whole decoder layer over ``x [n, T, H]`` from the plain
    weights of :func:`layer_weights` (the bank whole: small sizes)."""
    x = attn_block(x, w, sizes, control)
    if "w_gate" in w:
        return dense_block(x, w, sizes, control)
    n, t, hid = x.shape
    first, count = model()["held"]
    h = _norm(x, w["mlp_norm"], sizes["norm_eps"]).reshape(n * t, hid)
    comb = combine_weights(h, w, control)[:, first:first + count]
    y = experts_part(h, comb, w, control) + shared_part(h, w, control)
    return x + y.reshape(n, t, hid)


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

    @jit("control", donate=(0,))
    def run_attn(x, w, control):
        return attn_block(x, w, sizes, control)

    @jit("control", donate=(0,))
    def run_dense(x, w, control):
        return dense_block(x, w, sizes, control)

    @jit("control")
    def moe_open(x, w, control):
        n, t, hid = x.shape
        h = _norm(x, w["mlp_norm"], sizes["norm_eps"]).reshape(n * t, hid)
        return h, combine_weights(h, w, control), shared_part(h, w, control)

    @jit("control", donate=(0,))
    def moe_add(acc, h, comb, e0, bank, control):
        n = bank["we_gate"].shape[0]
        cols = jax.lax.dynamic_slice_in_dim(comb, e0, n, 1)
        return acc + experts_part(h, cols, bank, control)

    @jit(donate=(0,))
    def moe_close(x, acc):
        return x + acc.reshape(x.shape)

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
    ``EXPERT_CHUNK`` experts at a time, dropped after use, so one 2.5 GB
    layer never sits whole beside the logits. One device: ``devices`` of
    more than one are refused (the configuration is a one-chip one)."""
    if devices is not None and len(devices) > 1:
        raise NotImplementedError("deepseek_mla_moe runs on one device")
    p = _programs(tuple(sorted(sizes.items())))
    first_e, count = model()["held"]
    key = seed_key(seed)
    outer = p["gen_outer"](key)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = outer["embed"][tokens].astype(jnp.float32)
    for li in range(sizes["n_layers"]):
        dense = is_dense(li)
        w = p["gen_core"](key, jnp.int32(li), dense=dense)
        x = p["run_attn"](x, w, control=control)
        if dense:
            x = p["run_dense"](x, w, control=control)
            continue
        h, comb, acc = p["moe_open"](x, w, control=control)
        for e0 in range(first_e, first_e + count, EXPERT_CHUNK):
            n = min(EXPERT_CHUNK, first_e + count - e0)
            bank = p["gen_experts"](key, jnp.int32(li), jnp.int32(e0), n=n)
            acc = p["moe_add"](acc, h, comb, jnp.int32(e0), bank,
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
