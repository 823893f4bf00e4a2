"""Plain reference for ``dots3-note-prev`` (``model_type`` ``dots3_note``):
latent attention (MLA) in its EXPANDED form in every layer, of two
geometries by ``layer_types``: FULL layers behind a learned sparse-attention
indexer (DeepSeek-V3.2's, whose key names ``index_*`` these are), WINDOW
layers with a low-rank of their own (the ``swa_*`` keys) over the last
``sliding_window_size`` positions; a headwise sigmoid gate on every
attention output; a leading dense SwiGLU layer, then layers of
sigmoid-routed SwiGLU experts with a shared expert; untied head. Straight
``jax.numpy`` in float32 at ``precision=HIGHEST``: no kernel, no cache, no
absorbed attention, no grouped GEMM, and nothing imported from the program
under test. Attention runs in blocks of query rows, so that 9k rows of 128
heads fit: one block's scores ``[heads, rows, T]`` exist at a time.

Equations (pre-norm block ``x + attn(norm(x))``, ``x + mlp(norm(x))``;
every norm an RMSNorm with ``rms_norm_eps`` but the index key's LayerNorm;
``h`` = the layer's normed input, one row a token, position ``t``):

- FULL layer (``H`` heads ``num_attention_heads``, ``d_n``
  ``qk_nope_head_dim``, ``d_r`` ``qk_rope_head_dim``, ``d_v``
  ``v_head_dim``, ranks ``q_lora_rank`` / ``kv_lora_rank``, base
  ``rope_theta``): ``c_q = norm(h W_dq) * a_q``; ``[q_n | q_r]_a = c_q
  W_uq``; ``[c_kv | k_r] = h W_dkv``, ``c_kv = norm(c_kv) * a_kv``;
  ``q_r``, ``k_r`` rotated on adjacent pairs ``(2i, 2i+1)``, ``k_r`` shared
  by the heads; ``[k_n | v]_a = c_kv W_ukv``; ``s_a(t, j) = (q_n.k_n +
  q_r.k_r) / sqrt(d_n + d_r)``.
  Indexer: ``qI_g(t) = c_q(t) W_iq`` for ``g < index_n_heads``,
  ``index_head_dim`` wide; ``kI(j) = LayerNorm(h(j) W_ik)`` (mean removed,
  weight and bias), one a token; the first ``d_r`` values of both rotated
  HALF-SPLIT (``i`` with ``i + d_r / 2``) with the full layers' base;
  ``w(t) = h(t) W_w``; ``I(t, j) = sum_g w_g(t) relu(qI_g(t) . kI(j)) /
  sqrt(index_n_heads * index_head_dim)`` for ``j <= t``. ``S(t)`` = the
  ``index_topk`` positions ``j <= t`` of largest ``I(t, j)``
  (``jax.lax.top_k``: a tie to the lower ``j``), every ``j <= t`` while
  ``t < index_topk``. ``o_a(t) = sum_{j in S(t)} softmax_{j in
  S(t)}(s_a(t, j)) v_a(j)``.
- WINDOW layer: the same with the ``swa_*`` keys, no indexer, ``j`` in
  ``(t - sliding_window_size, t]``.
- gate: ``g(t) = sigmoid(h(t) W_g)``, one scalar a head; the layer's
  output is ``concat_a(g_a o_a) W_o``.
- ``a_q = sqrt(hidden / q_rank)``, ``a_kv = sqrt(hidden / kv_rank)`` where
  ``apply_mla_qkv_lora_rescale`` (the kind's own ranks), else 1.
- MLP: layer ``< first_k_dense_replace``: ``down(silu(gate(h)) * up(h))``;
  other layers: ``s = sigmoid(h W_r)``; chosen = top-k of ``s + b`` (the
  correction bias moves the choice, never the weight); ``w = s[chosen] /
  sum(s[chosen]) * routed_scaling_factor``; ``y = sum_k w_k E_k(h) +
  E_shared(h)``. No token is dropped.

ASSUMED (the catalog row gives the keys, not the modeling code; the
configuration's file says the same under ``assumed``): (1) the rescale and
its place (LongCat-Flash's ``mla_scale_q_lora`` / ``mla_scale_kv_lora``);
the indexer reads the rescaled ``c_q`` (a positive factor changes no order
of ``I(t, .)``). (2) the gate's place and input (after the softmax-weighted
sum, before ``W_o``, from the layer's normed input). (3) the window counts
the token itself. (4) the rope pairings above, the indexer's rotated part
first, LayerNorm's eps = ``rms_norm_eps``, and the pre-norm block.

DEPARTURES: V3.2 rotates ``qI`` and ``kI`` by a Hadamard matrix before its
FP8 cast: orthogonal, it changes no score, and is left out. The vision and
audio towers and the multi-token-prediction module are not loaded (the
catalog's ``config`` has no key of theirs; none changes a logit of the
language model on text).

THE SHARE. ``experts_held = [first, count]`` is one chip's share of each
bank: the router scores all ``published.n_routed_experts``, the layer adds
the part its own experts give and the shared expert, and what the absent
experts would add is left out, here as in the program. ``sizes["vocab"]``
is the slice of the vocabulary held: embedding, head and logits are over
it.

It OWNS the weights (bf16, from the seed, plain layout below); the adapter
packs them into the program's layout. Random normal scaled ``1 /
sqrt(fan-in)``, but what reads a RESCALED latent (``wq_b``, ``wkv_b``,
``wi_q``: their input has RMS ``a``, not 1) ``1 / (a sqrt(fan-in)) = 1 /
sqrt(hidden)``, so that q, k and v come out with unit RMS and the scores
with unit spread, as JoyAI's do; left at ``1 / sqrt(fan-in)`` the rescale
makes the scores' spread 5.9 and a softmax over 2048 keys so sharp that
ONE selected row that differs decides a head (PERF.md section 6, PR 41).
A WINDOW layer's scores and ``wo`` are scaled besides
(:data:`WINDOW_SPREAD`, :data:`WINDOW_GAIN`). A softmax of unit-spread
scores over ``n`` keys averages about ``n / e`` value vectors: at ``1 /
sqrt(fan-in)`` a window block would add 0.04 to a stream to which every MLP
adds 0.6, the window layers (the LAST three of the five) would not bear on
the logits, and a whole ring of another request's rows would read as a
sound run does (0.053 against 0.038: PERF.md section 4, PR 41). A gain on
``wo`` alone does not cure that: an average over hundreds of rows is the
same vector at every position, so what the gain lifts is that common
vector, and from the second such layer on the stream IS it (84% of the
variance after layer 1, 91% after layer 2 at a gain of 27-55: every
position then routes to the same experts and predicts the same token).
So the window layers' queries are scaled for a score spread of 2 (about
``513 / e^4`` = 9 keys carry a row's softmax, so its output is its own and
not the window's mean) and ``wo`` by 3: a window block then adds about
what an MLP adds, position by position. The full layers, which lead the
plan and which the comparison sees as they are, keep unit spread.
Norm weights are ``1 + 0.1 x normal`` and the LayerNorm's bias ``0.1 x
normal``, so that where a norm sits shows in the comparison.

    wq_a [H, rq]  wq_b [rq, nh*(nope+rope)]  wkv_a [H, rkv+rope]
    wkv_b [rkv, nh*(nope+v)] (per head: k_nope | v)  wo [nh*v, H]
    w_attn_gate [H, nh]
    wi_q [rq, G*di]  wi_k [H, di]  wi_k_norm, wi_k_bias [di]  wi_w [H, G]
    w_gate, w_up [H, F]  w_down [F, H]                  (dense layers)
    router [H, E]  router_bias [E] f32                  (expert layers)
    we_gate, we_up [E, H, Fe]  we_down [E, Fe, H]
    ws_gate, ws_up [H, Fs]  ws_down [Fs, H]
    embed [V, H]  lm_head [H, V]  norms [.]

``control=True`` is the lower-precision twin the comparison must reject:
every projection (indexer, gate, experts and router input included) as
W8A8 int8.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
EXPERT_CHUNK = 16
ROW_BLOCK = 128

_MODEL: dict = {}


def configure(config: dict) -> None:
    """Take the model's own keys from the configuration file (published
    names). ``n_routed_experts`` counts the experts HELD where the file
    cuts the bank; the router's width is the published count."""
    if config.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("this reference scores experts by sigmoid")
    gates = {config.get("attention_gate_type"),
             config.get("swa_attention_gate_type")}
    if gates != {"headwise"}:
        raise ValueError(f"this reference gates headwise, not {gates}")
    n = config["sizes"]["n_layers"] if "sizes" in config else config["n_layers"]
    kinds = tuple({"full_attention": "full", "sliding_attention": "window"}[k]
                  for k in config["layer_types"][:n])
    full = dict(
        kind="full", nh=config["num_attention_heads"],
        rq=config["q_lora_rank"], rkv=config["kv_lora_rank"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        dv=config["v_head_dim"], theta=float(config["rope_theta"]),
        window=None, topk=config["index_topk"])
    window = dict(
        kind="window", nh=config["swa_num_attention_heads"],
        rq=config["swa_q_lora_rank"], rkv=config["swa_kv_lora_rank"],
        nope=config["swa_qk_nope_head_dim"],
        rope=config["swa_qk_rope_head_dim"], dv=config["swa_v_head_dim"],
        theta=float(config["swa_rope_theta"]),
        window=config["sliding_window_size"], topk=0)
    width = (config.get("published") or config)["n_routed_experts"]
    m = dict(
        kinds=kinds, full=full, window=window,
        G=config["index_n_heads"], di=config["index_head_dim"],
        rescale=bool(config["apply_mla_qkv_lora_rescale"]),
        E=width, topk=config["num_experts_per_tok"],
        fe=config["moe_intermediate_size"],
        n_shared=config["n_shared_experts"],
        k_dense=config["first_k_dense_replace"],
        scaling=config["routed_scaling_factor"],
        held=tuple(config.get("experts_held") or (0, width)),
    )
    _MODEL.clear()
    _MODEL.update(m)
    _programs.cache_clear()


def model() -> dict:
    if not _MODEL:
        raise RuntimeError(
            "dots_sparse_mla_moe: configure(config) first (the adapter's "
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


def _near(key, shape, centre, dtype):
    return (centre + 0.1 * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


# a window layer's score spread and the factor on its ``wo`` (the module's
# docstring says why, PERF.md section 4 what the chip read with them)
WINDOW_SPREAD, WINDOW_GAIN = 2.0, 3.0


def is_dense(li: int) -> bool:
    return li < model()["k_dense"]


def geometry(li: int) -> dict:
    """The attention geometry of layer ``li`` (a Python int)."""
    m = model()
    return m[m["kinds"][li]]


# -- weights -------------------------------------------------------------------

def core_weights(key, li, sizes: dict, kind: str, dense: bool) -> dict:
    """Everything of layer ``li`` but the routed expert bank (traceable in
    ``li``; the layer's two kinds are static)."""
    m = model()
    g = m[kind]
    h, nh = sizes["hidden"], g["nh"]
    dt = _dtype(sizes)
    # what reads a RESCALED latent (RMS a, not 1) is scaled for it, so
    # that q, k, v and the index queries have unit RMS as every other
    # projection's output has: fan-in x a^2 = hidden
    up_q = h if m["rescale"] else g["rq"]
    up_kv = h if m["rescale"] else g["rkv"]
    spread, gain = (WINDOW_SPREAD, WINDOW_GAIN) if g["window"] else (1.0, 1.0)
    k = jax.random.split(
        jax.random.fold_in(jax.random.fold_in(key, li + 1), 0), 24)
    w = dict(
        attn_norm=_near(k[11], (h,), 1.0, dt),
        wq_a=_normal(k[0], (h, g["rq"]), h, dt),
        q_norm=_near(k[12], (g["rq"],), 1.0, dt),
        wq_b=_normal(k[1], (g["rq"], nh * (g["nope"] + g["rope"])),
                     up_q / spread ** 2, dt),
        wkv_a=_normal(k[2], (h, g["rkv"] + g["rope"]), h, dt),
        kv_norm=_near(k[13], (g["rkv"],), 1.0, dt),
        wkv_b=_normal(k[3], (g["rkv"], nh * (g["nope"] + g["dv"])), up_kv, dt),
        wo=_normal(k[4], (nh * g["dv"], h), nh * g["dv"] / gain ** 2, dt),
        w_attn_gate=_normal(k[15], (h, nh), h, dt),
        mlp_norm=_near(k[14], (h,), 1.0, dt),
    )
    if g["topk"]:
        G, di = m["G"], m["di"]
        w.update(
            wi_q=_normal(k[16], (g["rq"], G * di), up_q, dt),
            wi_k=_normal(k[17], (h, di), h, dt),
            wi_k_norm=_near(k[18], (di,), 1.0, dt),
            wi_k_bias=_near(k[19], (di,), 0.0, dt),
            wi_w=_normal(k[20], (h, G), h, dt),
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
    layer's kinds depend on it). The bank is the share held here."""
    dense = is_dense(li)
    w = core_weights(key, li, sizes, model()["kinds"][li], dense)
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
        final_norm=_near(k[2], (h,), 1.0, dt),
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


def _layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    r = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * r * w.astype(jnp.float32) + b.astype(jnp.float32)


def _angles(t: int, d: int, theta: float, ndim: int):
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    ang = ang.reshape(t, *([1] * (ndim - 2)), d // 2)
    return jnp.cos(ang), jnp.sin(ang)


def _rope_pairs(x, theta):
    """Rotate adjacent pairs ``(2i, 2i+1)`` of the last axis by position
    (axis 0): x ``[T, ..., d]``."""
    cos, sin = _angles(x.shape[0], x.shape[-1], theta, x.ndim)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape)


def _rope_halves(x, theta):
    """Rotate element ``i`` with ``i + d / 2`` of the last axis by
    position (axis 0): x ``[T, ..., d]``."""
    d = x.shape[-1]
    cos, sin = _angles(x.shape[0], d, theta, x.ndim)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def index_scores(x, c_q, w, control: bool, sizes: dict):
    """The indexer's operands of one sequence: ``(qI [T, G, di], kI [T,
    di], weights [T, G])``, rotated."""
    m = model()
    t, G, di, r = x.shape[0], m["G"], m["di"], m["full"]["rope"]
    theta = m["full"]["theta"]
    turn = lambda a: jnp.concatenate(
        [_rope_halves(a[..., :r], theta), a[..., r:]], -1)
    q_i = turn(_mm(c_q, w["wi_q"], control).reshape(t, G, di))
    k_i = turn(_layer_norm(_mm(x, w["wi_k"], control), w["wi_k_norm"],
                           w["wi_k_bias"], sizes["norm_eps"]))
    return q_i, k_i, _mm(x, w["wi_w"], control)


def selection(q_i, k_i, w_i, pos, topk: int):
    """``[R, T]`` bool: ``S(t)`` of the rows at positions ``pos [R]``;
    ``q_i [R, G, di]``, ``w_i [R, G]`` those rows', ``k_i [T, di]``."""
    m = model()
    t = k_i.shape[0]
    s = jnp.einsum("sgd,td->sgt", q_i, k_i, precision=HI)
    s = jnp.einsum("sgt,sg->st", jnp.maximum(s, 0.0), w_i, precision=HI)
    s = s / np.sqrt(m["G"] * m["di"])
    causal = jnp.arange(t)[None, :] <= pos[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    _, idx = jax.lax.top_k(s, min(topk, t))
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], idx].set(True)
    return chosen & causal


def attention(x, w, g: dict, sizes: dict, control: bool, taps=None):
    """Expanded MLA over one sequence ``x [T, H]`` (already normed) in the
    geometry ``g``, the gate applied, ``W_o`` applied. ``taps`` (a dict)
    collects ``selected [T, T]`` of an indexed layer (tests)."""
    m = model()
    t, nh = x.shape[0], g["nh"]
    nope, rope, dv, rkv = g["nope"], g["rope"], g["dv"], g["rkv"]
    eps, hid = sizes["norm_eps"], sizes["hidden"]
    a_q = np.sqrt(hid / g["rq"]) if m["rescale"] else 1.0
    a_kv = np.sqrt(hid / rkv) if m["rescale"] else 1.0
    c_q = _norm(_mm(x, w["wq_a"], control), w["q_norm"], eps) * a_q
    q = _mm(c_q, w["wq_b"], control).reshape(t, nh, nope + rope)
    q_n, q_r = q[..., :nope], _rope_pairs(q[..., nope:], g["theta"])
    kva = _mm(x, w["wkv_a"], control)
    c_kv = _norm(kva[:, :rkv], w["kv_norm"], eps) * a_kv
    k_r = _rope_pairs(kva[:, rkv:], g["theta"])              # [T, rope]
    kv = _mm(c_kv, w["wkv_b"], control).reshape(t, nh, nope + dv)
    k_n, v = kv[..., :nope], kv[..., nope:]
    index = index_scores(x, c_q, w, control, sizes) if g["topk"] else None
    rows = ROW_BLOCK if t % ROW_BLOCK == 0 else t
    keys = jnp.arange(t)

    def block(r0):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, r0, rows)
        pos = r0 + jnp.arange(rows)
        s = (jnp.einsum("shd,thd->hst", cut(q_n), k_n, precision=HI)
             + jnp.einsum("shd,td->hst", cut(q_r), k_r, precision=HI))
        s = s / np.sqrt(nope + rope)
        ok = keys[None, :] <= pos[:, None]
        if g["window"] is not None:
            ok = ok & (keys[None, :] > pos[:, None] - g["window"])
        if index is not None:
            ok = ok & selection(cut(index[0]), index[1], cut(index[2]), pos,
                                g["topk"])
        s = jnp.where(ok[None], s, -jnp.inf)
        o = jnp.einsum("hst,thd->shd", jax.nn.softmax(s, -1), v, precision=HI)
        return o, ok

    o, seen = jax.lax.map(block, jnp.arange(t // rows, dtype=jnp.int32) * rows)
    if taps is not None and index is not None:
        taps["selected"] = seen.reshape(t, t)
    o = o.reshape(t, nh, dv)
    gate = jax.nn.sigmoid(_mm(x, w["w_attn_gate"], control))     # [T, nh]
    return _mm((o * gate[..., None]).reshape(t, nh * dv), w["wo"], control)


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


def attn_block(x, w, g: dict, sizes: dict, control: bool):
    """``x [n, T, H] + attention``, one sequence at a time."""
    h = _norm(x, w["attn_norm"], sizes["norm_eps"])
    return x + jax.lax.map(lambda s: attention(s, w, g, sizes, control), h)


def dense_block(x, w, sizes: dict, control: bool):
    h = _norm(x, w["mlp_norm"], sizes["norm_eps"])
    return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], control)


def layer(x, w, li: int, sizes: dict, control: bool = False):
    """One whole decoder layer ``li`` over ``x [n, T, H]`` from the plain
    weights of :func:`layer_weights` (the bank whole: small sizes)."""
    x = attn_block(x, w, geometry(li), sizes, control)
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
    m = model()

    def jit(*static, donate=()):
        return functools.partial(
            jax.jit, static_argnames=static, donate_argnums=donate)

    @jit("kind", "dense")
    def gen_core(key, li, kind, dense):
        return core_weights(key, li, sizes, kind, dense)

    @jit("n")
    def gen_experts(key, li, e0, n):
        return expert_weights(key, li, e0, n, sizes)

    @jit("kind", "control", donate=(0,))
    def run_attn(x, w, kind, control):
        return attn_block(x, w, m[kind], sizes, control)

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
    ``EXPERT_CHUNK`` experts at a time, dropped after use. One device:
    ``devices`` of more than one are refused (the configuration is a
    one-chip one)."""
    if devices is not None and len(devices) > 1:
        raise NotImplementedError("dots_sparse_mla_moe runs on one device")
    p = _programs(tuple(sorted(sizes.items())))
    m = model()
    first_e, count = m["held"]
    key = seed_key(seed)
    outer = p["gen_outer"](key)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = outer["embed"][tokens].astype(jnp.float32)
    for li in range(sizes["n_layers"]):
        dense, kind = is_dense(li), m["kinds"][li]
        w = p["gen_core"](key, jnp.int32(li), kind=kind, dense=dense)
        x = p["run_attn"](x, w, kind=kind, control=control)
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
