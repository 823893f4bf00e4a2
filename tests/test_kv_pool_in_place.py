"""The k/v page pool stays where it lies: a step writes its rows with ONE
index-gated scatter a tensor into the stacked pool ``[n_layers, n_pool,
h_kv, page, d]`` and the attention kernel reads its pages out of the whole
local pool (the block table shifted to the layer's run of pages). No layer
of the pool is sliced out, and none is put back."""

import dataclasses
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_dist_tpu import resilience
from triton_dist_tpu.models import TransformerConfig, init_params, param_specs
from triton_dist_tpu.models.decode import (
    PagedKVCacheSpec,
    _local_lens,
    decode_step,
    prefill_cache_ranged,
)
from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig
from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig

from tests.test_gate_up_layout import _CALLS, _mesh

# `triton_dist_tpu.ops.flash_decode` names a function; this is the module
fd = importlib.import_module("triton_dist_tpu.ops.flash_decode")

B, S_MAX, PAGE, CHUNK = 3, 32, 4, 3
SPEC = PagedKVCacheSpec(S_MAX, PAGE, static_table=True, extra_pages=1)


def _cfg():
    return TransformerConfig(
        vocab=32, hidden=32, ffn=64, n_layers=2, n_q_heads=4, n_kv_heads=2,
        head_dim=8, batch=B, seq=8,
        ag_config=AGGemmConfig(8, 16, 16), rs_config=GemmRSConfig(8, 16, 16),
    )


def _mapped(cfg, mesh, spec, multi: bool):
    """``decode_step`` (``update_and_attend``) or ``prefill_cache_ranged``
    (``update_multi_and_attend``) over the mesh: (params, cache, tokens,
    pos) -> (logits, cache)."""
    step = prefill_cache_ranged if multi else decode_step

    def fn(params, cache, tok, pos):
        return step(cfg, params, cache, tok, pos, spec=spec)

    tok_spec = P(None, None) if multi else P(None)
    out = P(None, None, None) if multi else P(None, None)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(param_specs(cfg), spec.specs(cfg), tok_spec, P(None)),
        out_specs=(out, spec.specs(cfg)), check_vma=False)


def _tokens(multi: bool):
    shape = (B, CHUNK) if multi else (B,)
    return jax.random.randint(jax.random.PRNGKey(5), shape, 0, 32, jnp.int32)


# -- structure: what touches the pool ----------------------------------------

_LAYER_MOVERS = ("dynamic_update_slice", "slice", "dynamic_slice")


def _is_var(v) -> bool:
    return not hasattr(v, "val")        # a Literal carries its value


def _body(eqn):
    """The jaxpr a call-like equation (shard_map, pjit) runs, else None."""
    if eqn.primitive.name == "pallas_call":
        return None
    inner = next((eqn.params[k] for k in _CALLS if k in eqn.params), None)
    return getattr(inner, "jaxpr", inner)


def _walk(jaxpr, pool_vars, seen):
    """Follow the pool through `jaxpr`: a scatter's result is the pool
    again, a reshape of it is a view of it. Records every equation that
    touches it in `seen` and returns the positions of `jaxpr.outvars` that
    are the pool or a view."""
    pool = {v: kind for v, kind in pool_vars}
    for eqn in jaxpr.eqns:
        at = [i for i, v in enumerate(eqn.invars)
              if _is_var(v) and v in pool]
        if not at:
            continue
        inner = _body(eqn)
        if inner is not None:
            skip = len(inner.invars) - len(eqn.invars)
            outs = _walk(
                inner,
                [(inner.invars[i + skip], pool[eqn.invars[i]]) for i in at],
                seen)
            for o, kind in outs.items():
                pool[eqn.outvars[o]] = kind
            continue
        name = eqn.primitive.name
        seen.append((name, eqn, at, [pool[eqn.invars[i]] for i in at]))
        if name == "scatter" and at == [0]:
            pool[eqn.outvars[0]] = "pool"
        elif name == "reshape" and pool[eqn.invars[0]] == "pool":
            pool[eqn.outvars[0]] = "view"
    return {i: pool[v] for i, v in enumerate(jaxpr.outvars)
            if _is_var(v) and v in pool}


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        inner = _body(eqn)
        if inner is not None:
            yield from _all_eqns(inner)


@pytest.mark.parametrize("n", [1, 2], ids=["one_device", "sharded"])
@pytest.mark.parametrize(
    "multi", [False, True], ids=["update_and_attend", "update_multi_and_attend"])
def test_pool_meets_only_row_scatters_and_the_kernel(multi, n):
    cfg, mesh = _cfg(), _mesh(n)
    params = init_params(jax.random.PRNGKey(0), cfg)
    cache = SPEC.init(cfg, n, 1)
    pos = jnp.array([5, 2, 17], jnp.int32)
    closed = jax.make_jaxpr(_mapped(cfg, mesh, SPEC, multi))(
        params, cache, _tokens(multi), pos)

    leaves, _ = jax.tree.flatten_with_path((params, cache))
    at = [i for i, (path, _) in enumerate(leaves)
          if getattr(path[-1], "key", None) in ("k", "v") and len(path) == 2
          and path[0].idx == 1]
    assert len(at) == 2
    seen = []
    outs = _walk(
        closed.jaxpr, [(closed.jaxpr.invars[i], "pool") for i in at], seen)
    # the step hands back the pool it was given, written in place
    assert sorted(outs.values()) == ["pool", "pool"]

    local = cache["k"].shape
    local = (local[0], local[1] // n) + local[2:]
    rows = (B, CHUNK) if multi else (B,)
    by_name = {}
    for name, eqn, where, kinds in seen:
        by_name.setdefault(name, []).append((eqn, where, kinds))
    assert set(by_name) == {"scatter", "reshape", "pallas_call"}, set(by_name)
    # one scatter a layer and tensor, of the step's rows
    assert len(by_name["scatter"]) == 2 * cfg.n_layers
    for eqn, where, _ in by_name["scatter"]:
        assert where == [0] and eqn.invars[0].aval.shape == local
        assert eqn.invars[2].aval.shape == rows + (cfg.n_kv_heads, cfg.head_dim)
    # the view merges the two leading dimensions and nothing else
    for eqn, _, kinds in by_name["reshape"]:
        assert kinds == ["pool"]
        assert eqn.outvars[0].aval.shape == (local[0] * local[1],) + local[2:]
    # the kernel reads views of the WHOLE local pool, k and v
    assert len(by_name["pallas_call"]) == cfg.n_layers
    for eqn, where, kinds in by_name["pallas_call"]:
        assert set(kinds) == {"view"} and len(where) >= 2
        for i in where:
            assert eqn.invars[i].aval.size == math.prod(local)
    # and nothing anywhere in the step moves a layer of the pool
    layer_shapes = (local, local[1:], (1,) + local[1:])
    for eqn in _all_eqns(closed.jaxpr):
        if eqn.primitive.name not in _LAYER_MOVERS:
            continue
        for v in (*eqn.invars, *eqn.outvars):
            assert v.aval.shape not in layer_shapes, eqn


# -- equivalence: the plain form, layer by layer ------------------------------

@dataclasses.dataclass(frozen=True)
class _SlicingSpec(PagedKVCacheSpec):
    """The plain reference: take the layer out of the stack, write the
    step's rows into the copy, put the layer back, attend over the copy.
    ``kernel=False`` attends through ``_xla_paged_decode`` /
    ``_xla_paged_verify``; ``kernel=True`` hands the copy to the same
    Pallas kernels the system runs."""

    kernel: bool = False

    def _write(self, cache, li, k_new, v_new, pos, me, n):
        s_shard = self.s_max // n
        off = pos % s_shard
        own = me == pos // s_shard
        bt = cache["block_table"][0]
        ids = jnp.take_along_axis(
            bt, (off // self.page_size).reshape(bt.shape[0], -1), axis=1
        ).reshape(pos.shape)
        slot = off % self.page_size
        k_l, v_l = cache["k"][li], cache["v"][li]
        for i in np.ndindex(*pos.shape):        # row by row, owners only
            at = (ids[i], slice(None), slot[i])
            k_l = k_l.at[at].set(jnp.where(own[i], k_new[i], k_l[at]))
            v_l = v_l.at[at].set(jnp.where(own[i], v_new[i], v_l[at]))
        cache = dict(
            cache, k=cache["k"].at[li].set(k_l), v=cache["v"].at[li].set(v_l))
        return k_l, v_l, bt, cache

    def update_and_attend(
        self, cfg, cache, li, k_new, v_new, q, pos_b, me, n, fd_config,
        interpret,
    ):
        k_l, v_l, bt, cache = self._write(cache, li, k_new, v_new, pos_b, me, n)
        lens = _local_lens(pos_b, me, self.s_max // n)
        attend = fd.paged_flash_decode if self.kernel else fd._xla_paged_decode
        out, lse = attend(q, k_l, v_l, lens, bt, return_lse=True)
        return fd._sp_allgather_combine(
            out, lse, cfg.axis, "full_mesh_push", interpret), cache

    def update_multi_and_attend(
        self, cfg, cache, li, k_new, v_new, q, pos0, me, n, fd_config,
        interpret,
    ):
        S = q.shape[1]
        pos = pos0[:, None] + jnp.arange(S, dtype=jnp.int32)
        k_l, v_l, bt, cache = self._write(cache, li, k_new, v_new, pos, me, n)
        lens = fd._ranged_local_lens(pos0, S, cfg.axis, self.s_max // n)
        attend = fd.paged_flash_verify if self.kernel else fd._xla_paged_verify
        out, lse = attend(q, k_l, v_l, lens, bt, return_lse=True)
        b, _, hq, d = out.shape
        merged = fd._sp_allgather_combine(
            out.reshape(b * S, hq, d), lse.reshape(b * S, hq), cfg.axis,
            "full_mesh_push", interpret)
        return merged.reshape(b, S, hq, d), cache


def _random_cache(cfg, n):
    """A pool of noise and a block table that is a random permutation of
    each PE's pages; slot 1 is idle: its rows are parked on the PE's
    scratch page (the one `extra_pages` adds, outside every range)."""
    cache = SPEC.init(cfg, n, 1)
    kk, kv = jax.random.split(jax.random.PRNGKey(7))
    pps = S_MAX // n // PAGE
    rng = np.random.default_rng(3)
    bt = np.stack([
        rng.permutation(B * pps).astype(np.int32).reshape(B, pps)
        for _ in range(n)])
    bt[:, 1, :] = B * pps                       # the scratch page
    return dict(
        cache,
        k=jax.random.normal(kk, cache["k"].shape, cache["k"].dtype),
        v=jax.random.normal(kv, cache["v"].shape, cache["v"].dtype),
        block_table=jnp.asarray(bt))


@pytest.mark.parametrize("attend", ["xla_golden", "kernel"])
@pytest.mark.parametrize(
    "multi", [False, True], ids=["step", "verify_chunk"])
def test_one_pass_equals_the_slicing_reference_bit_for_bit(multi, attend):
    """Two PEs, so every row has a non-owner; ragged positions; an idle
    slot on the scratch page; for the chunk, rows that straddle a page
    (slot 0: positions 2-4) and the PEs' boundary (slot 2: 15-17)."""
    n = 2
    cfg, mesh = _cfg(), _mesh(n)
    params = init_params(jax.random.PRNGKey(0), cfg)
    cache = _random_cache(cfg, n)
    pos = jnp.array([2, 0, 15] if multi else [5, 0, 22], jnp.int32)
    tok = _tokens(multi)
    plain = _SlicingSpec(
        S_MAX, PAGE, static_table=True, extra_pages=1,
        kernel=attend == "kernel")

    def run(spec):
        return jax.jit(_mapped(cfg, mesh, spec, multi))(params, cache, tok, pos)

    want_logits, want_cache = run(plain)
    if attend == "kernel":
        got_logits, got_cache = run(SPEC)
    else:
        # the system's own step with every kernel served by its XLA twin:
        # the same arithmetic as the reference's, over the whole pool
        with resilience.golden_path():
            got_logits, got_cache = run(SPEC)
    np.testing.assert_array_equal(np.asarray(got_logits), np.asarray(want_logits))
    for name in ("k", "v", "block_table", "n_alloc"):
        np.testing.assert_array_equal(
            np.asarray(got_cache[name]), np.asarray(want_cache[name]), name)
    # the step did write: one row a slot and position (the idle slot's on
    # the scratch pages), and nothing else moved
    changed = np.asarray(got_cache["k"] != cache["k"]).any(axis=(2, 4))
    assert changed.sum() == cfg.n_layers * B * (CHUNK if multi else 1)
    scratch = [pe * (cache["k"].shape[1] // n) + B * (S_MAX // n // PAGE)
               for pe in range(n)]
    assert changed[:, scratch].sum() == cfg.n_layers * (CHUNK if multi else 1)


# -- what a step walks against what its tables hold ---------------------------

@pytest.mark.parametrize(
    "kind", ["kv", "kv_window", "kv_state", "latent", "latent_sparse"])
def test_pages_walked_follow_the_lengths(kind):
    """``pages_walked`` (the round span's ``kv_pages_live`` /
    ``kv_pages_table``): the pages the lengths expose, a window layer's
    the pages of ``[len - window, len)``, times the attention layers of
    each kind; the table's side is capacity and does not move. The latent
    kind's kernel still walks the table row under a plain plan, and says
    so; under a sparse plan (an indexer, window layers) its full layers
    walk the live pages of their index keys and its window layers their
    rings."""
    import types

    from triton_dist_tpu.models.decode import PAGED_CACHE_KINDS

    spec = PAGED_CACHE_KINDS[kind.split("_sparse")[0]](
        S_MAX, PAGE, static_table=True)
    types5 = ("window",) * 3 + ("full",) * 2
    sparse = kind == "latent_sparse"
    cfg = types.SimpleNamespace(
        n_layers=5, window=6, layer_types=types5,
        layer_kinds=("mamba",) * 4 + ("attention",),
        attention_kinds=types5 if sparse else ("full",) * 5,
        index_topk=8 if sparse else 0)
    lens = np.array([0, 1, PAGE, PAGE + 1, S_MAX], np.int32)
    full = 0 + 1 + 1 + 2 + S_MAX // PAGE
    # [len - 6, len) over pages of 4: none, 1, 1, 2 and, ending on a page's
    # last position, 2; a ring of ceil(6 / 4) + 1 = 3 pages a slot
    win, ring = 0 + 1 + 1 + 2 + 2, 3
    want = {
        "kv": (full * 5, len(lens) * (S_MAX // PAGE) * 5),
        "kv_window": (full * 2 + win * 3,
                      len(lens) * (S_MAX // PAGE * 2 + ring * 3)),
        "kv_state": (full * 1, len(lens) * (S_MAX // PAGE) * 1),
        "latent": (len(lens) * (S_MAX // PAGE) * 5,) * 2,
        "latent_sparse": (full * 2 + win * 3,
                          len(lens) * (S_MAX // PAGE * 2 + ring * 3)),
    }[kind]
    assert spec.pages_walked(cfg, lens) == want
    # a window that ends one position into a page sees three pages of it
    if kind == "kv_window":
        live, _ = spec.pages_walked(cfg, np.array([2 * PAGE + 1], np.int32))
        assert live == 3 * 2 + 3 * 3
