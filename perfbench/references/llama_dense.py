"""Plain reference for a LLaMA-class dense decoder (Mistral-7B,
Mistral-Large): RMSNorm, grouped-query attention with rotary positions
(half-split rotation, as in the published Hugging Face modelling code),
SwiGLU MLP, untied head. Straight ``jax.numpy``: no kernel, no cache, no
batching, and nothing imported from the program under test.

It also OWNS the weights: they are made here from the seed, one layer per
call, in the plain layout below and in the type they are served in
(bf16). The program's adapter packs them into the program's own layout;
the reference makes them again, layer by layer, when it runs after the
window. So the comparison also covers the packing.

    wq [H, n_q*d]  wk, wv [H, n_kv*d]  wo [n_q*d, H]
    w_gate, w_up [H, F]  w_down [F, H]  norms [H]
    embed [V, H]  lm_head [H, V]

The reference computes in float32 at ``precision=HIGHEST`` from those
stored weights. ``control=True`` is the lower-precision twin that the
comparison must reject: every projection as W8A8 int8 (weights per output
column, activations per row, symmetric), the step below bf16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HI = jax.lax.Precision.HIGHEST
AXIS = "ref"

LAYER_SPECS = dict(
    attn_norm=P(None), wq=P(None, AXIS), wk=P(None, AXIS), wv=P(None, AXIS),
    wo=P(AXIS, None), mlp_norm=P(None), w_gate=P(None, AXIS),
    w_up=P(None, AXIS), w_down=P(AXIS, None),
)
OUTER_SPECS = dict(embed=P(None, None), final_norm=P(None), lm_head=P(None, AXIS))


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def _dtype(sizes: dict):
    return jnp.dtype(sizes.get("dtype", "bfloat16"))


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def layer_weights(key, li, sizes: dict) -> dict:
    """Layer ``li``'s weights (traceable: ``li`` may be data)."""
    h, f, d = sizes["hidden"], sizes["ffn"], sizes["head_dim"]
    q, kv = sizes["n_q_heads"] * d, sizes["n_kv_heads"] * d
    dt = _dtype(sizes)
    k = jax.random.split(jax.random.fold_in(key, li + 1), 7)
    return dict(
        attn_norm=jnp.ones((h,), dt),
        wq=_normal(k[0], (h, q), h ** -0.5, dt),
        wk=_normal(k[1], (h, kv), h ** -0.5, dt),
        wv=_normal(k[2], (h, kv), h ** -0.5, dt),
        wo=_normal(k[3], (q, h), q ** -0.5, dt),
        mlp_norm=jnp.ones((h,), dt),
        w_gate=_normal(k[4], (h, f), h ** -0.5, dt),
        w_up=_normal(k[5], (h, f), h ** -0.5, dt),
        w_down=_normal(k[6], (f, h), f ** -0.5, dt),
    )


def outer_weights(key, sizes: dict) -> dict:
    h, v = sizes["hidden"], sizes["vocab"]
    dt = _dtype(sizes)
    k = jax.random.split(jax.random.fold_in(key, 0), 2)
    return dict(
        embed=_normal(k[0], (v, h), 0.02, dt),
        final_norm=jnp.ones((h,), dt),
        lm_head=_normal(k[1], (h, v), h ** -0.5, dt),
    )


def _q8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(x, w, control: bool):
    w = w.astype(jnp.float32)
    if control:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.dot(x, w, precision=HI)


def _norm(x, w, eps):
    r = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * r * w.astype(jnp.float32)


def _rope(x, theta):          # x [T, heads, d]
    t, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend(q, k, v, sizes):   # one sequence: q [T, n_q, d], k, v [T, n_kv, d]
    t, n_q, d = q.shape
    n_kv = k.shape[1]
    q, k = _rope(q, sizes["rope_theta"]), _rope(k, sizes["rope_theta"])
    qg = q.reshape(t, n_kv, n_q // n_kv, d)
    s = jnp.einsum("shgd,thd->hgst", qg, k, precision=HI) / np.sqrt(d)
    pos = jnp.arange(t)
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    a = jnp.einsum("hgst,thd->shgd", jax.nn.softmax(s, -1), v, precision=HI)
    return a.reshape(t, n_q * d)


def layer(x, w, sizes: dict, control: bool):
    """One decoder layer over ``x [n, T, H]`` (float32)."""
    n, t, _ = x.shape
    d = sizes["head_dim"]
    h = _norm(x, w["attn_norm"], sizes["norm_eps"])
    q = _mm(h, w["wq"], control).reshape(n, t, sizes["n_q_heads"], d)
    k = _mm(h, w["wk"], control).reshape(n, t, sizes["n_kv_heads"], d)
    v = _mm(h, w["wv"], control).reshape(n, t, sizes["n_kv_heads"], d)
    # one sequence at a time: the [heads, T, T] scores of all at once
    # need not fit beside the weights
    a = jax.lax.map(lambda qkv: _attend(*qkv, sizes), (q, k, v))
    x = x + _mm(a, w["wo"], control)
    h = _norm(x, w["mlp_norm"], sizes["norm_eps"])
    act = jax.nn.silu(_mm(h, w["w_gate"], control)) * _mm(h, w["w_up"], control)
    return x + _mm(act, w["w_down"], control)


def head(x, outer, first, n_new: int, sizes: dict, control: bool):
    """Logits ``[n, n_new, V]`` at the ``n_new`` positions from ``first``
    on: the positions that predict the served tokens."""
    idx = first[:, None] + jnp.arange(n_new, dtype=jnp.int32)[None, :]
    xs = jnp.take_along_axis(x, idx[:, :, None], axis=1)
    xs = _norm(xs, outer["final_norm"], sizes["norm_eps"])
    return _mm(xs, outer["lm_head"], control)


def _shardings(devices, specs):
    if devices is None or len(devices) == 1:
        return None, None
    mesh = Mesh(np.array(devices), (AXIS,))
    return mesh, jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P),
    )


@functools.lru_cache(maxsize=None)
def _programs(sizes_items: tuple, devices: tuple | None):
    sizes = dict(sizes_items)
    _, lay = _shardings(devices, LAYER_SPECS)
    _, out = _shardings(devices, OUTER_SPECS)
    gen_layer = jax.jit(
        functools.partial(layer_weights, sizes=sizes), out_shardings=lay)
    gen_outer = jax.jit(
        functools.partial(outer_weights, sizes=sizes), out_shardings=out)
    run_layer = jax.jit(
        functools.partial(layer, sizes=sizes), static_argnames=("control",),
        donate_argnums=(0,))
    run_head = jax.jit(
        functools.partial(head, sizes=sizes),
        static_argnames=("n_new", "control"))
    return gen_layer, gen_outer, run_layer, run_head


def logits(sizes: dict, seed: int, tokens, first, n_new: int, *,
           control: bool = False, devices=None):
    """The reference's logits ``[n, n_new, V]`` (a device array) for
    ``tokens [n, T]`` at positions ``first[i] .. first[i] + n_new - 1``.
    Weights come from ``seed``, a layer at a time, and are dropped after
    use; with ``devices`` they are spread over those devices and XLA
    partitions the plain program."""
    gen_layer, gen_outer, run_layer, run_head = _programs(
        tuple(sorted(sizes.items())), None if devices is None else tuple(devices))
    key = seed_key(seed)
    outer = gen_outer(key)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = outer["embed"][tokens].astype(jnp.float32)
    for li in range(sizes["n_layers"]):
        x = run_layer(x, gen_layer(key, jnp.int32(li)), control=control)
    return run_head(x, outer, jnp.asarray(first, jnp.int32), n_new=n_new,
                    control=control)


def gaps(ref_logits, judged):
    """How far each judged token ``[n, n_new]`` lies below the
    reference's best logit at its position, and whether it is that best."""
    judged = jnp.asarray(judged, jnp.int32)
    got = jnp.take_along_axis(ref_logits, judged[..., None], -1)[..., 0]
    best = ref_logits.max(-1)
    return np.asarray(best - got), np.asarray(ref_logits.argmax(-1) == judged)
