"""Plain reference for the power-retention decoder (Brumby-14B-Base: the
Qwen3-14B block with its softmax attention replaced by Manifest AI's power
retention, arXiv 2507.04239). Straight ``jax.numpy`` in float32 at
``precision=HIGHEST``, IN THE ATTENTION FORM: the weights ``a_ij`` below,
computed in row blocks; never ``phi``, never a state, no kernel, no cache,
no batching, and nothing imported from the program under test.

Equations (``x [T, H]``; every norm an RMSNorm, eps ``norm_eps``; no bias on
q, k, v, o):

- every layer: ``u = norm_in(x)``; ``q = u W_q -> [T, h_q, d]``, ``k = u
  W_k``, ``v = u W_v -> [T, h_kv, d]``; ``q``, ``k`` each RMS-normed over
  ``d`` with a learned weight (``q_norm``, ``k_norm``), then rotated
  (``rope_theta``, halves). Query head ``h`` reads kv head ``h // (h_q /
  h_kv)``.
- gate: ``log g_t = logsigmoid(u_t W_g + b_g)  [T, h_kv]``; ``G_t = sum_{l
  <= t} log g_l``.
- power retention, degree ``power`` = 2, ``s = d ** -0.5``: for ``j <=
  i``, ``a_ij = (s q_i . k_j)^2 exp(G_i - G_j)``; ``y_i = sum_j a_ij v_j /
  (sum_j a_ij + eps)``, ``eps`` = 1e-6. No softmax, no maximum.
- ``x = x + concat_h(y) W_o``; then ``x = x + mlp(norm_ff(x))``, ``mlp(n) =
  down(silu(gate(n)) * up(n))``. After the last layer ``final_norm`` and an
  untied head ``lm_head [H, V]``.

ASSUMED (not among the catalog row's keys; listed in the configuration's
file): the degree, the gate's shape, the normaliser and its ``eps``, the
head norms and the rotation (kept from the Qwen3 block), and THE
INITIALISATION, which is part of the model here: ``b_g`` such that ``-log
g`` is log-uniform in ``[1e-4, 1e-2]`` a head (memories of 100 to 10,000
tokens), ``W_g`` normal x 0.1 / sqrt(fan-in); matrices normal /
sqrt(fan-in), embedding 0.02, norm weights ``1 + 0.1 x normal``. With a
plain normal gate ``g ~ 0.5``: the sum forgets within a few tokens and
neither a stale state nor a doubled step would move a logit.

It OWNS the weights (bf16, from the seed, plain layout below); the adapter
packs them into the program's layout.

    norm_in, norm_ff [H]   wq [H, hq*d]   wk, wv [H, hkv*d]   wo [hq*d, H]
    q_norm, k_norm [d]   w_g [H, hkv]   b_g [hkv]
    w_gate, w_up [H, F]   w_down [F, H]
    embed [V, H]   final_norm [H]   lm_head [H, V]

``control=True`` is the lower-precision twin the comparison must reject:
every projection as W8A8 int8, through the same ``_mm``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness import cells

# the siblings' helpers, imported as they stand (no program's code)
_gen = cells.load_module("references", "jamba_ssm_hybrid")
HI = _gen.HI
seed_key, gaps = _gen.seed_key, _gen.gaps
_normal, _gain, _dtype, _mm, _f32, _norm = (
    _gen._normal, _gen._gain, _gen._dtype, _gen._mm, _gen._f32, _gen._norm)
_rope = cells.load_module("references", "llama_dense")._rope

EPS = 1e-6
FORGET_RANGE = (1e-4, 1e-2)
# query rows a block of the weights a_ij holds: [block, T] a head
ROW_BLOCK = 512
HEAD_SLICES = 8


def configure(config: dict) -> None:
    """Check the model's own keys in the configuration file."""
    power = int(config.get("power", 2))
    if power != 2:
        raise ValueError(f"this reference squares the scores: power={power}")
    if config.get("attention_bias", False) or config.get(
            "tie_word_embeddings", False):
        raise ValueError("this reference has no bias on q, k, v, o and an "
                         "untied head")


# -- weights -------------------------------------------------------------------

def layer_weights(key, li, sizes: dict) -> dict:
    """Layer ``li`` in the plain layout (traceable: ``li`` may be data)."""
    h, f, d = sizes["hidden"], sizes["ffn"], sizes["head_dim"]
    hq, hkv = sizes["n_q_heads"], sizes["n_kv_heads"]
    dt = _dtype(sizes)
    k = jax.random.split(jax.random.fold_in(key, li + 1), 13)
    lo, hi = (np.log(x) for x in FORGET_RANGE)
    forget = jnp.exp(jax.random.uniform(k[9], (hkv,), minval=lo, maxval=hi))
    return dict(
        norm_in=_gain(k[0], (h,), dt), norm_ff=_gain(k[1], (h,), dt),
        wq=_normal(k[2], (h, hq * d), h, dt),
        wk=_normal(k[3], (h, hkv * d), h, dt),
        wv=_normal(k[4], (h, hkv * d), h, dt),
        wo=_normal(k[5], (hq * d, h), hq * d, dt),
        q_norm=_gain(k[6], (d,), dt), k_norm=_gain(k[7], (d,), dt),
        w_g=_normal(k[8], (h, hkv), 100 * h, dt),      # 0.1 / sqrt(fan-in)
        # logsigmoid(b_g) = -forget
        b_g=(-jnp.log(jnp.expm1(forget))).astype(dt),
        w_gate=_normal(k[10], (h, f), h, dt),
        w_up=_normal(k[11], (h, f), h, dt),
        w_down=_normal(k[12], (f, h), f, dt),
    )


def outer_weights(key, sizes: dict) -> dict:
    h, v = sizes["hidden"], sizes["vocab"]
    dt = _dtype(sizes)
    k = jax.random.split(jax.random.fold_in(key, 0), 3)
    return dict(
        embed=(jax.random.normal(k[0], (v, h), jnp.float32) * 0.02).astype(dt),
        final_norm=_gain(k[1], (h,), dt),
        lm_head=_normal(k[2], (h, v), h, dt),
    )


def count_parameters(sizes: dict) -> int:
    """Parameters of the whole model, from the shapes :func:`layer_weights`
    and :func:`outer_weights` make (nothing is allocated)."""
    key = jax.random.PRNGKey(0)
    shapes = [jax.eval_shape(functools.partial(outer_weights, sizes=sizes), key),
              jax.eval_shape(functools.partial(layer_weights, li=0, sizes=sizes),
                             key)]
    outer, layer = (sum(int(np.prod(x.shape)) for x in jax.tree.leaves(s))
                    for s in shapes)
    return outer + sizes["n_layers"] * layer


# -- equations -----------------------------------------------------------------

def retention(q, k, v, log_g, block: int | None = None):
    """The attention form over one sequence: ``q [T, h_q, d]``, ``k, v [T,
    h_kv, d]`` (normed, rotated), ``log_g [T, h_kv]`` -> ``y [T, h_q, d]``.
    ``block`` query rows at a time (``T`` must be whole blocks; default all
    at once): ``[h_kv, g, block, T]`` weights, not ``[.., T, T]``."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    block = block or t
    G = jnp.cumsum(log_g, axis=0)                       # [T, h_kv]
    qg = q.reshape(t // block, block, hkv, hq // hkv, d) * d ** -0.5
    rows = jnp.arange(t).reshape(t // block, block)

    def rows_of(xs):
        qb, i, Gi = xs                                  # [block, h, g, d], [block]
        s = jnp.einsum("ihgd,jhd->hgij", qb, k, precision=HI)
        live = (jnp.arange(t)[None, :] <= i[:, None])[None]     # [1, i, j]
        decay = jnp.exp(jnp.where(live, Gi.T[:, :, None] - G.T[:, None, :],
                                  -jnp.inf))            # [h, i, j]
        a = s * s * decay[:, None]
        num = jnp.einsum("hgij,jhd->ihgd", a, v, precision=HI)
        den = a.sum(-1).transpose(2, 0, 1)              # [i, h, g]
        return num / (den[..., None] + EPS)

    y = jax.lax.map(rows_of, (qg, rows, G.reshape(t // block, block, hkv)))
    return y.reshape(t, hq, d)


def mixer(x, w, sizes: dict, control: bool, block: int | None = None):
    """One sequence ``x [T, H]`` (normed) through the retention layer's
    mixer, before the out-projection's residual."""
    t, d = x.shape[0], sizes["head_dim"]
    hq, hkv, eps = sizes["n_q_heads"], sizes["n_kv_heads"], sizes["norm_eps"]
    theta = sizes["rope_theta"]
    q = _mm(x, w["wq"], control).reshape(t, hq, d)
    k = _mm(x, w["wk"], control).reshape(t, hkv, d)
    v = _mm(x, w["wv"], control).reshape(t, hkv, d)
    q = _rope(_norm(q, w["q_norm"], eps), theta)
    k = _rope(_norm(k, w["k_norm"], eps), theta)
    log_g = jax.nn.log_sigmoid(_mm(x, w["w_g"], control) + _f32(w["b_g"]))
    y = retention(q, k, v, log_g, block)
    return _mm(y.reshape(t, hq * d), w["wo"], control)


def layer(x, w, sizes: dict, control: bool = False, block: int | None = None):
    """A decoder layer whole over ``x [n, T, H]`` (float32)."""
    eps = sizes["norm_eps"]
    h = _norm(x, w["norm_in"], eps)
    # one sequence at a time: the weights of all at once need not fit
    x = x + jax.lax.map(lambda s: mixer(s, w, sizes, control, block), h)
    h = _norm(x, w["norm_ff"], eps)
    act = jax.nn.silu(_mm(h, w["w_gate"], control)) * _mm(h, w["w_up"], control)
    return x + _mm(act, w["w_down"], control)


def head(x, outer, first, n_new: int, sizes: dict, control: bool):
    """Logits ``[n, n_new, V]`` at the ``n_new`` positions from ``first``
    on: the positions that predict the served tokens."""
    idx = first[:, None] + jnp.arange(n_new, dtype=jnp.int32)[None, :]
    xs = jnp.take_along_axis(x, idx[:, :, None], axis=1)
    xs = _norm(xs, outer["final_norm"], sizes["norm_eps"])
    # a slice of the vocabulary at a time: the float32 copy of a 1.6 GB
    # head need not stand whole beside its logits
    w = outer["lm_head"]
    cuts = np.linspace(0, w.shape[1], HEAD_SLICES + 1).astype(int)
    return jnp.concatenate(
        [_mm(xs, w[:, a:b], control) for a, b in zip(cuts[:-1], cuts[1:])], -1)


# -- the run, a layer at a time ------------------------------------------------

@functools.lru_cache(maxsize=None)
def _programs(sizes_items: tuple):
    sizes = dict(sizes_items)
    return dict(
        gen_layer=jax.jit(functools.partial(layer_weights, sizes=sizes)),
        run_layer=jax.jit(
            lambda x, w, control, block: layer(x, w, sizes, control, block),
            static_argnames=("control", "block"), donate_argnums=(0,)),
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
        raise NotImplementedError("brumby_retention runs on one device")
    p = _programs(tuple(sorted(sizes.items(), key=lambda kv: kv[0])))
    key = seed_key(seed)
    outer = p["gen_outer"](key)
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[1]
    block = ROW_BLOCK if t % ROW_BLOCK == 0 else None
    x = outer["embed"][tokens].astype(jnp.float32)
    for li in range(sizes["n_layers"]):
        x = p["run_layer"](x, p["gen_layer"](key, jnp.int32(li)),
                           control=control, block=block)
    return p["run_head"](x, outer, jnp.asarray(first, jnp.int32), n_new=n_new,
                         control=control)


def gaps(ref_logits, judged):
    """How far each judged token ``[n, n_new]`` lies below the
    reference's best logit at its position, and whether it is that best."""
    judged = jnp.asarray(judged, jnp.int32)
    got = jnp.take_along_axis(ref_logits, judged[..., None], -1)[..., 0]
    best = ref_logits.max(-1)
    return np.asarray(best - got), np.asarray(ref_logits.argmax(-1) == judged)
