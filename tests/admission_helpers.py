"""What the layer-plan families' admission tests share (test_mla_moe /
test_window_moe): an admission is ``prefill_cache`` with a one-hot
``slot_mask``, it runs the rows it admits and writes that slot's cache and
no other's; without a mask every slot's rows run (``generate``'s form).
A file brings its family's toy ``cfg`` / ``params`` / cache spec and says
which table names the pages of which pool (``pools``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def _program(cfg, spec, s_max, bucket, masked: bool):
    """The family's prefill at ``bucket`` as the batcher's program calls
    it, jitted over a one-device mesh."""
    pcfg = dataclasses.replace(cfg, seq=bucket)
    mesh = Mesh(np.array(jax.devices()[:1]), (cfg.axis,))
    return jax.jit(jax.shard_map(
        lambda p, c, t, m, k: pcfg.prefill_cache(
            p, c, t.reshape(-1), spec, s_max, slot_mask=m, pick=k,
            interpret=True),
        mesh=mesh, in_specs=(cfg.param_specs(), spec.specs(cfg), P(),
                             P() if masked else None, P()),
        out_specs=(spec.specs(cfg), P(), P()), check_vma=False))


def random_cache(cfg, spec, rng):
    """The spec's cache with every pool filled: what other slots hold."""
    return jax.tree.map(
        lambda x: x if x.dtype == jnp.int32
        else jnp.asarray(rng.standard_normal(x.shape), x.dtype),
        spec.init(cfg, 1))


def slot_rows(cache, pools: dict, slot: int) -> dict:
    """``pool name -> the pages of ``slot`` in it`` (every layer)."""
    return {name: np.asarray(cache[name])[:, np.asarray(cache[table][0][slot])]
            for name, table in pools.items()}


def check_admission(cfg, params, spec, s_max, pools, slot, length, bucket,
                    n_moe: int, tol: dict, seed: int = 0):
    """An admission of a ``length``-token prompt into ``slot`` against the
    unmasked pass over every slot's prompt from the same cache: the other
    slots' pages bit-identical to what they held, the admitted slot's
    pages and logit row the whole-batch pass's (to ``tol``), ``last`` zero
    elsewhere, and the pass's counters one slot's. Returns the admission's
    counters."""
    b = cfg.batch
    rng = np.random.default_rng(seed)
    tokens = np.zeros((b, bucket), np.int32)
    tokens[:, :length] = rng.integers(0, cfg.vocab, (b, length))
    pick = np.full(b, length - 1, np.int32)
    before = random_cache(cfg, spec, rng)
    whole, whole_last, whole_n = _program(cfg, spec, s_max, bucket, False)(
        params, before, jnp.asarray(tokens), None, jnp.asarray(pick))
    # the batcher's form: one live row, zeros elsewhere
    one = np.zeros_like(tokens)
    one[slot] = tokens[slot]
    one_pick = np.zeros(b, np.int32)
    one_pick[slot] = length - 1
    after, last, counters = _program(cfg, spec, s_max, bucket, True)(
        params, before, jnp.asarray(one), jnp.arange(b) == slot,
        jnp.asarray(one_pick))
    want = slot_rows(whole, pools, slot)
    for other in range(b):
        held, now = slot_rows(before, pools, other), slot_rows(after, pools, other)
        for name in pools:
            if other != slot:
                np.testing.assert_array_equal(now[name], held[name])
            else:
                assert not np.array_equal(now[name], held[name])
                np.testing.assert_allclose(now[name], want[name], **tol)
    last = np.asarray(last)
    np.testing.assert_allclose(last[slot], np.asarray(whole_last)[slot], **tol)
    assert not np.delete(last, slot, axis=0).any()
    # every row chooses topk experts in each expert layer, held here or not
    for n_slots, values in ((1, counters), (b, whole_n)):
        named = dict(zip(cfg.pass_counters, (int(v) for v in values)))
        assert (named["assignments"] + named.get("assignments_elsewhere", 0)
                == n_slots * bucket * cfg.topk * n_moe)
    return counters


def _pallas_operands(jaxpr) -> list:
    """Operand shapes of every ``pallas_call`` of a jaxpr, sub-jaxprs
    walked in order."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append([tuple(v.aval.shape) for v in eqn.invars])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_pallas_operands(sub))
    return out


def admission_kernel_operands(cfg, params, spec, s_max, bucket, batch: int):
    """Shapes of what the admission's Pallas kernels (the grouped GEMMs)
    read, with ``cfg.batch = batch``: traced, nothing runs."""
    cfg = dataclasses.replace(cfg, batch=batch)
    shapes = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    jaxpr = jax.make_jaxpr(_program(cfg, spec, s_max, bucket, True))(
        shapes(params), jax.eval_shape(lambda: spec.init(cfg, 1)),
        i32(batch, bucket), jax.ShapeDtypeStruct((batch,), jnp.bool_),
        i32(batch))
    return _pallas_operands(jaxpr.jaxpr)
