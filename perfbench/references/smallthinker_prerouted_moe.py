"""Plain reference for the SmallThinker decoder
(SmallThinker-21BA3B-Instruct): grouped-query attention that is a sliding
WINDOW with rotary positions on three layers of four and FULL with no
positional term on the fourth, a pre-norm block, and in every layer a
bank of ReLU-gated experts whose ROUTER READS THE LAYER'S INPUT, before
attention. Straight ``jax.numpy`` in float32 at ``precision=HIGHEST``
(what ``jax.default_matmul_precision("highest")`` sets, stated on every
product): no kernel, no cache, no ring, no grouped GEMM, no batching, and
nothing imported from the program under test.

Equations (``x_l [T, H]``, layer ``l``'s input; every norm an RMSNorm,
eps ``norm_eps``):

- router, BEFORE attention and on the UN-normed input: ``r = x_l W_r``
  (``W_r [H, E]``); the ``topk`` largest logits are chosen; their weights
  are the softmax over those (= softmax over all ``E``, top-k,
  renormalized: ``moe_primary_router_apply_softmax`` with
  ``norm_topk_prob``). No bias, no scale.
- attention: ``h = norm_in(x_l)``; ``q, k, v = h W_q, h W_k, h W_v`` (no
  bias, no q/k norm). Where ``sliding_window_layout[l] == 1`` (and
  ``rope_layout[l] == 1``: the layouts must agree) q and k are rotated
  (``rope_theta``, the whole head width, half-split: element ``i`` pairs
  with ``i + d/2``) and position ``p`` sees ``max(0, p - window + 1) ..
  p``; where 0, no rotation and ``p`` sees ``0 .. p``. Scores ``q.k /
  sqrt(d)``; ``u = x_l + softmax(s) v W_o``.
- experts: ``m = norm_post(u)``; ``y = sum over the chosen e of w_e *
  (relu(m W_gate,e) * (m W_up,e)) W_down,e``; ``x_{l+1} = u + y``. No
  shared expert, no dense layer, no token dropped.
- then the final norm and the untied head.

ASSUMED (not among the catalog row's keys; listed in the configuration's
file): the pre-norm block, no bias and no q/k norm, the half-split rotary
pairs, ReLU as the gate (``described_as``: "sparse ReGLU"), the router on
the un-normed layer input (``described_as``: "router placed before
attention"). DEPARTURES: none but the depth.

It OWNS the weights (bf16, from the seed, plain layout below; the seed's
key, the normal draws and the outer weights are the sibling reference's
generators, ``exaone_window_moe.py``, imported as they stand); the adapter
packs them into the program's layout. It runs a layer at a time, attention
a sequence at a time with its QUERIES IN BLOCKS of ``QUERY_BLOCK`` (the
scores of one block are ``[heads, QUERY_BLOCK, T]``, never ``[T, T]`` per
head at once) and the bank ``EXPERT_CHUNK`` experts at a time, every
expert on every token with weight 0 where not chosen, so that three
sequences of 9216 positions fit one chip; :func:`layer_weights` and
:func:`layer` are the same numbers whole.

    wq [H, hq*d]  wk, wv [H, hkv*d]  wo [hq*d, H]
    attn_norm, mlp_norm [H]            (on the sub-layer's INPUT)
    router [H, E]
    we_gate, we_up [n, H, Fe]  we_down [n, Fe, H]
    embed [V, H]  lm_head [H, V]  final_norm [H]

``control=True`` is the lower-precision twin the comparison must reject:
every projection (experts and the router's input included) as W8A8 int8.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness import cells

_gen = cells.load_module("references", "exaone_window_moe")
HI = _gen.HI
seed_key, outer_weights, gaps = _gen.seed_key, _gen.outer_weights, _gen.gaps
_normal, _gain, _dtype, _mm, _norm = (
    _gen._normal, _gen._gain, _gen._dtype, _gen._mm, _gen._norm)
_rope_halves = _gen._rope_halves

EXPERT_CHUNK = 4
QUERY_BLOCK = 1024
_MODEL: dict = {}


def configure(config: dict) -> None:
    """Take the model's own keys from the configuration file (published
    names)."""
    if not config.get("moe_primary_router_apply_softmax", False):
        raise ValueError("this reference's router applies a softmax")
    if not config.get("norm_topk_prob", False):
        raise ValueError("this reference normalizes the chosen weights")
    windows, ropes = (tuple(int(x) for x in config[k]) for k in (
        "sliding_window_layout", "rope_layout"))
    if windows != ropes:
        raise ValueError("rope_layout and sliding_window_layout differ: a "
                         "layer is rotated where it attends through the "
                         "window, and nowhere else")
    _MODEL.clear()
    _MODEL.update(
        E=config["moe_num_primary_experts"],
        topk=config["moe_num_active_primary_experts"],
        fe=config["moe_ffn_hidden_size"],
        window=config["sliding_window_size"],
        # 1 = window and rotated, 0 = full and not (one a published layer)
        layout=windows,
    )
    _programs.cache_clear()


def model() -> dict:
    if not _MODEL:
        raise RuntimeError(
            "smallthinker_prerouted_moe: configure(config) first (the "
            "adapter's System does): the model's keys are not among the "
            "sizes")
    return _MODEL


def window_of(li: int) -> int:
    """Layer ``li``'s window (it is rotated then); 0 = full attention,
    not rotated."""
    m = model()
    return m["window"] if m["layout"][li] else 0


# -- weights -------------------------------------------------------------------

def core_weights(key, li, sizes: dict) -> dict:
    """Everything of layer ``li`` but the expert bank (traceable in
    ``li``: every layer holds the same tensors)."""
    h, d = sizes["hidden"], sizes["head_dim"]
    hq, hkv = sizes["n_q_heads"], sizes["n_kv_heads"]
    dt = _dtype(sizes)
    k = jax.random.split(
        jax.random.fold_in(jax.random.fold_in(key, li + 1), 0), 8)
    return dict(
        wq=_normal(k[0], (h, hq * d), h, dt),
        wk=_normal(k[1], (h, hkv * d), h, dt),
        wv=_normal(k[2], (h, hkv * d), h, dt),
        wo=_normal(k[3], (hq * d, h), hq * d, dt),
        attn_norm=_gain(k[4], (h,), dt),
        mlp_norm=_gain(k[5], (h,), dt),
        router=_normal(k[6], (h, model()["E"]), h, dt),
    )


def expert_weights(key, li, e0, n: int, sizes: dict) -> dict:
    """Experts ``e0 .. e0+n-1`` of layer ``li``'s bank (traceable in
    ``li`` and ``e0``): each expert's numbers depend on its own index
    only, so any chunking gives the same experts."""
    h, fe = sizes["hidden"], model()["fe"]
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


def layer_weights(key, li, sizes: dict) -> dict:
    """Layer ``li`` whole, in the plain layout."""
    w = core_weights(key, li, sizes)
    w.update(expert_weights(key, li, 0, model()["E"], sizes))
    return w


# -- equations -----------------------------------------------------------------

def combine_weights(x, w, control: bool):
    """``[T, E]`` from the layer's UN-normed input ``x [T, H]``: each
    token's weight on every expert, 0 where not chosen; softmax over the
    chosen logits."""
    m = model()
    top, ids = jax.lax.top_k(_mm(x, w["router"], control), m["topk"])
    t = x.shape[0]
    return jnp.zeros((t, m["E"]), jnp.float32).at[
        jnp.arange(t)[:, None], ids].add(jax.nn.softmax(top, -1))


def attention(h, w, sizes: dict, window: int, control: bool,
              block: int | None = None):
    """One sequence's normed rows ``h [T, H]`` -> ``[T, H]`` (after
    ``W_o``); ``window`` 0 = full attention and no rotation. Queries go
    ``block`` at a time (default: all at once) against every key under an
    explicit mask."""
    t, d = h.shape[0], sizes["head_dim"]
    hq, hkv = sizes["n_q_heads"], sizes["n_kv_heads"]
    q = _mm(h, w["wq"], control).reshape(t, hq, d)
    k = _mm(h, w["wk"], control).reshape(t, hkv, d)
    v = _mm(h, w["wv"], control).reshape(t, hkv, d)
    if window:
        q = _rope_halves(q, sizes["rope_theta"])
        k = _rope_halves(k, sizes["rope_theta"])
    block = min(block or t, t)
    if t % block:
        raise ValueError(f"{t} positions are not whole blocks of {block}")
    # query head i reads kv head i // (hq / hkv)
    q = q.reshape(t // block, block, hkv, hq // hkv, d)
    kp = jnp.arange(t)[None, :]

    def rows(args):
        qb, q0 = args
        s = jnp.einsum("shgd,thd->hgst", qb, k, precision=HI) / np.sqrt(d)
        qp = q0 + jnp.arange(block)[:, None]
        mask = kp <= qp
        if window:
            mask = mask & (kp > qp - window)
        s = jnp.where(mask[None, None], s, -jnp.inf)
        return jnp.einsum("hgst,thd->shgd", jax.nn.softmax(s, -1), v,
                          precision=HI)

    o = jax.lax.map(rows, (q, jnp.arange(t // block) * block))
    return _mm(o.reshape(t, hq * d), w["wo"], control)


def attn_block(x, w, sizes: dict, window: int, control: bool,
               block: int | None = None):
    """``u = x + attention(norm_in(x))`` over ``x [n, T, H]``, one
    sequence at a time."""
    eps = sizes["norm_eps"]
    return x + jax.lax.map(
        lambda s: attention(_norm(s, w["attn_norm"], eps), w, sizes, window,
                            control, block), x)


def experts_part(m, comb, bank: dict, control: bool):
    """``sum_e comb[:, e] * E_e(m)`` over the experts of ``bank``
    (``comb [T, n]`` their columns): every expert on every token, the
    plainest form; a weight of 0 leaves an expert out."""
    n = bank["we_gate"].shape[0]
    me = jnp.broadcast_to(m, (n, *m.shape))
    act = jax.nn.relu(_mm(me, bank["we_gate"], control)) * _mm(
        me, bank["we_up"], control)
    return jnp.einsum("te,eth->th", comb, _mm(act, bank["we_down"], control),
                      precision=HI)


def layer(x, w, sizes: dict, li: int, control: bool = False,
          block: int | None = None):
    """Decoder layer ``li`` whole over ``x [n, T, H]`` from the plain
    weights of :func:`layer_weights`."""
    n, t, hid = x.shape
    comb = combine_weights(x.reshape(n * t, hid), w, control)
    u = attn_block(x, w, sizes, window_of(li), control, block)
    m = _norm(u, w["mlp_norm"], sizes["norm_eps"]).reshape(n * t, hid)
    return u + experts_part(m, comb, w, control).reshape(n, t, hid)


head = _gen.head


# -- the run, in blocks --------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _programs(sizes_items: tuple):
    sizes = dict(sizes_items)

    def jit(*static, donate=()):
        return functools.partial(
            jax.jit, static_argnames=static, donate_argnums=donate)

    @jit()
    def gen_core(key, li):
        return core_weights(key, li, sizes)

    @jit("n")
    def gen_experts(key, li, e0, n):
        return expert_weights(key, li, e0, n, sizes)

    @jit("control")
    def route(x, w, control):
        n, t, hid = x.shape
        return combine_weights(x.reshape(n * t, hid), w, control)

    @jit("window", "control", "block", donate=(0,))
    def run_attn(x, w, window, control, block):
        return attn_block(x, w, sizes, window, control, block)

    @jit()
    def moe_open(u, w):
        n, t, hid = u.shape
        m = _norm(u, w["mlp_norm"], sizes["norm_eps"]).reshape(n * t, hid)
        return m, jnp.zeros_like(m)

    @jit("control", donate=(0,))
    def moe_add(acc, m, comb, e0, bank, control):
        n = bank["we_gate"].shape[0]
        cols = jax.lax.dynamic_slice_in_dim(comb, e0, n, 1)
        return acc + experts_part(m, cols, bank, control)

    @jit(donate=(0,))
    def moe_close(u, acc):
        return u + acc.reshape(u.shape)

    return dict(
        gen_core=gen_core, gen_experts=gen_experts, route=route,
        run_attn=run_attn, moe_open=moe_open, moe_add=moe_add,
        moe_close=moe_close,
        gen_outer=jax.jit(functools.partial(outer_weights, sizes=sizes)),
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
        raise NotImplementedError(
            "smallthinker_prerouted_moe runs on one device")
    p = _programs(tuple(sorted(sizes.items())))
    n_experts = model()["E"]
    key = seed_key(seed)
    outer = p["gen_outer"](key)
    tokens = jnp.asarray(tokens, jnp.int32)
    block = min(query_block, tokens.shape[1])
    x = outer["embed"][tokens].astype(jnp.float32)
    for li in range(sizes["n_layers"]):
        w = p["gen_core"](key, jnp.int32(li))
        comb = p["route"](x, w, control=control)      # from x_l, un-normed
        x = p["run_attn"](x, w, window=window_of(li), control=control,
                          block=block)
        m, acc = p["moe_open"](x, w)
        for e0 in range(0, n_experts, expert_chunk):
            n = min(expert_chunk, n_experts - e0)
            bank = p["gen_experts"](key, jnp.int32(li), jnp.int32(e0), n=n)
            acc = p["moe_add"](acc, m, comb, jnp.int32(e0), bank,
                               control=control)
        x = p["moe_close"](x, acc)
    return p["run_head"](x, outer, jnp.asarray(first, jnp.int32), n_new=n_new,
                         control=control)
