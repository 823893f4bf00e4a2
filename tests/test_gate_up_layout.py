"""A weight is stored as the plain matrix its GEMM contracts (``w_gate_up``
``[H, 2F]``, ``pack_gate_up``; ``wqkv`` ``[H, n_kv*(g+2)*d]``): no user
reshapes, transposes or copies it, and the batcher re-lays the old public
3-D layouts (``[H, F, 2]``, ``[H, n_kv, (g+2)*d]``) once, at the door
(``ContinuousBatcher.params``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from triton_dist_tpu import config as tdt_config
from triton_dist_tpu import obs
from triton_dist_tpu.models import (
    SPTransformer,
    SPTransformerConfig,
    TPTransformer,
    TransformerConfig,
    decode_step,
    init_params,
    pack_gate_up,
    param_specs,
    unpack_gate_up,
)
from triton_dist_tpu.models.decode import (
    ContinuousBatcher,
    KVCacheSpec,
    Request,
    prefill_cache_ranged,
)
from triton_dist_tpu.obs import ObsConfig
from triton_dist_tpu.ops.allgather_gemm import AGGemmConfig
from triton_dist_tpu.ops.gemm_reduce_scatter import GemmRSConfig

# ffn -> the block B gate and up alternate in (128 needs ffn % 1024 == 0)
WIDTHS = {64: 1, 1024: 128}


def _cfg(ffn: int, cls=TransformerConfig, **kw):
    kw = dict(dict(
        vocab=32, hidden=32, ffn=ffn, n_layers=2, n_q_heads=4, n_kv_heads=2,
        head_dim=8, batch=2, seq=8,
        # few interpreted grid steps: the wide case has 2048 gate/up columns
        ag_config=AGGemmConfig(8, max(16, ffn // 4), 16),
        rs_config=GemmRSConfig(8, 16, max(16, ffn // 4)),
    ), **kw)
    return cls(**kw)


def _mesh(n: int) -> Mesh:
    return Mesh(np.array(jax.devices()[:n]), ("tp",))


# -- structure: the weight goes straight into its contraction ----------------

_CALLS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def _leaf_consumers(jaxpr, var) -> list:
    """Primitive names of the equations that read `var`, followed through
    every call-like equation (shard_map, pjit, custom_vjp) into its body."""
    found = []
    for eqn in jaxpr.eqns:
        for pos, v in enumerate(eqn.invars):
            if v is not var:
                continue
            inner = next(
                (eqn.params[k] for k in _CALLS if k in eqn.params), None)
            if inner is None or eqn.primitive.name == "pallas_call":
                found.append(eqn.primitive.name)
                continue
            inner = getattr(inner, "jaxpr", inner)
            # a call's operands are its body's inputs, in order (constants
            # of a closed body come first and are not operands)
            skip = len(inner.invars) - len(eqn.invars)
            found += _leaf_consumers(inner, inner.invars[pos + skip])
    return found


def _decode_case(n, step=decode_step, tok_shape=(2,)):
    cfg = _cfg(1024)
    spec = KVCacheSpec(16)
    cache = spec.init(cfg, n, 1)

    def fn(params, cache, tok):
        return step(cfg, params, cache, tok, jnp.int32(3), spec=spec)

    in_specs = (param_specs(cfg), spec.specs(cfg), P(*[None] * len(tok_shape)))
    out_specs = (P(None, None), spec.specs(cfg))
    return cfg, fn, in_specs, out_specs, (cache, jnp.zeros(tok_shape, jnp.int32))


def _ranged_case(n):
    return _decode_case(n, prefill_cache_ranged, (2, 4))


def _tp_case(n):
    cfg = _cfg(1024)
    x = jnp.zeros((cfg.batch * cfg.seq, cfg.hidden), cfg.dtype)

    def fn(params, x):
        return TPTransformer(cfg).block(x, params["layers"][0])

    return cfg, fn, (param_specs(cfg), P("tp", None)), P("tp", None), (x,)


def _sp_case(n):
    cfg = _cfg(1024, SPTransformerConfig, n_q_heads=4, n_kv_heads=4)
    x = jnp.zeros((cfg.batch, cfg.seq, cfg.hidden), cfg.dtype)
    rep = jax.tree.map(
        lambda s: P(), param_specs(cfg), is_leaf=lambda s: isinstance(s, P))

    def fn(params, x):
        return SPTransformer(cfg).block(x, params["layers"][0])

    return cfg, fn, (rep, P(None, "tp", None)), P(None, "tp", None), (x,)


_CASES = {"decode_step": _decode_case, "tp_block": _tp_case,
          "sp_block": _sp_case, "prefill_cache_ranged": _ranged_case}


@pytest.mark.parametrize("case,leaf", [
    *[(c, "w_gate_up") for c in ("decode_step", "tp_block", "sp_block")],
    *[(c, "wqkv") for c in _CASES],
])
def test_weight_is_read_by_its_contraction_alone(case, leaf):
    mesh = _mesh(2)
    cfg, fn, in_specs, out_specs, rest = _CASES[case](mesh.size)
    params = init_params(jax.random.PRNGKey(0), cfg)
    mapped = jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)
    closed = jax.make_jaxpr(mapped)(params, *rest)
    leaves, _ = jax.tree.flatten_with_path((params, *rest))
    at = [i for i, (path, _) in enumerate(leaves)
          if any(getattr(k, "key", None) == leaf for k in path)]
    assert at
    used = [_leaf_consumers(closed.jaxpr, closed.jaxpr.invars[i]) for i in at]
    used = [u for u in used if u]       # a block reads one layer's leaf
    assert used, f"no layer's {leaf} reached an equation"
    for u in used:
        assert len(u) == 1 and u[0] in ("dot_general", "pallas_call"), u


# -- the packer and its inverse ----------------------------------------------

@pytest.mark.parametrize("ffn", WIDTHS)
def test_pack_roundtrip_and_shard_locality(ffn):
    cfg = _cfg(ffn)
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    g = jax.random.normal(k1, (cfg.hidden, ffn))
    u = jax.random.normal(k2, (cfg.hidden, ffn))
    w = pack_gate_up(g, u, cfg)
    assert w.shape == (cfg.hidden, 2 * ffn)
    g2, u2 = unpack_gate_up(w, cfg)
    np.testing.assert_array_equal(g2, g)
    np.testing.assert_array_equal(u2, u)
    # a column shard holds matched units: packing a shard = a shard of the pack
    half = ffn // 2
    np.testing.assert_array_equal(
        pack_gate_up(g[:, half:], u[:, half:], cfg), w[:, ffn:])
    blk = WIDTHS[ffn]
    np.testing.assert_array_equal(w[:, :blk], g[:, :blk])
    np.testing.assert_array_equal(w[:, blk:2 * blk], u[:, :blk])


def test_shards_that_split_a_block_raise():
    cfg = _cfg(1024)    # 128-column blocks: whole on 1, 2, 4 and 8 PEs
    w = pack_gate_up(*jnp.ones((2, cfg.hidden, 1024)), cfg)
    unpack_gate_up(w[:, :2048 // 8], cfg)
    with pytest.raises(ValueError, match="128-column"):
        unpack_gate_up(w[:, :2048 // 16], cfg)    # a sixteenth: half a pair


# -- the door ------------------------------------------------------------------

# leaf -> (the public layout before it was stored as its GEMM reads it
# (PR 27, PR 31), its two counters on ``tdt.batcher.take_params``)
_OLD = {
    "w_gate_up": (lambda w, cfg: jnp.stack(unpack_gate_up(w, cfg), -1),
                  ("relaid", "bytes")),
    "wqkv": (lambda w, cfg: w.reshape(cfg.hidden, cfg.n_kv_heads, -1),
             ("relaid_wqkv", "bytes_wqkv")),
}


def _old_layout(params: dict, cfg, leaf: str) -> dict:
    layers = [dict(p, **{leaf: _OLD[leaf][0](p[leaf], cfg)})
              for p in params["layers"]]
    return dict(params, layers=layers)


def _serve(batcher) -> dict:
    """Two prompts of one prefill bucket, answers that cross one decode
    round: what shows whose weights a batcher serves with."""
    rng = np.random.default_rng(3)
    for i, (n_prompt, n_new) in enumerate([(3, 2), (4, 2)]):
        batcher.submit(Request(
            [int(t) for t in rng.integers(0, 32, n_prompt)], n_new, uid=i))
    return dict(batcher.run(max_steps=100))


@pytest.fixture
def ring():
    before = tdt_config.get_config().obs
    tdt_config.update(obs=ObsConfig(spans=True))
    obs.reset()
    yield
    tdt_config.update(obs=before)
    obs.reset()


def _intakes(leaf: str) -> list:
    return [tuple(s.attrs[k] for k in _OLD[leaf][1]) for s in obs.spans()
            if s.name == "tdt.batcher.take_params"]


def _buffers(x) -> list:
    return [s.data.unsafe_buffer_pointer() for s in x.addressable_shards]


@pytest.mark.parametrize("n", [1, 2])
@pytest.mark.parametrize(
    "leaf,ffn", [*[("w_gate_up", f) for f in WIDTHS], ("wqkv", 64)])
def test_door_relays_the_old_layout_once(ring, leaf, ffn, n):
    cfg = _cfg(ffn, n_layers=1)     # a leaf is re-laid a layer: one shows it
    mesh = _mesh(n)
    born = init_params(jax.random.PRNGKey(0), cfg, mesh)
    old = _old_layout(born, cfg, leaf)
    stored = born["layers"][0][leaf]
    assert stored.ndim == 2 and old["layers"][0][leaf].ndim == 3
    leaf_bytes = stored.nbytes

    def batcher(tree):
        return ContinuousBatcher(cfg, tree, mesh, s_max=16, prefill=True)

    from_old, from_born = batcher(old), batcher(born)
    # the reseed path: other weights first, the real ones assigned after
    assigned = batcher(init_params(jax.random.PRNGKey(9), cfg))
    assigned.params = old
    assert _intakes(leaf) == [
        (cfg.n_layers, cfg.n_layers * leaf_bytes), (0, 0), (0, 0),
        (cfg.n_layers, cfg.n_layers * leaf_bytes),
    ]
    other, = set(_OLD) - {leaf}     # the leaf that arrived as stored
    assert _intakes(other) == [(0, 0)] * 4
    # a tree born in the stored layout passes untouched: the same buffers
    assert _buffers(from_born.params["layers"][0][leaf]) == _buffers(stored)
    for b in (from_old, assigned):
        for p, q in zip(b.params["layers"], born["layers"]):
            assert p[leaf].sharding.is_equivalent_to(q[leaf].sharding, 2)
            np.testing.assert_array_equal(p[leaf], q[leaf])
    want = _serve(from_born)
    assert len(want) == 2
    assert _serve(from_old) == want
    assert _serve(assigned) == want
