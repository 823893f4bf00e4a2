"""Ranged prefill, model tier: ``verify_step`` range composition reproduces
``prefill_cache``'s cache AND last logits, and equals the token-by-token
``decode_step`` chain. Written once here and run once a CELL (cache kind x
attention program): test_ranged_contiguous.py, test_ranged_kernel.py and
test_ranged_paged.py each name their cell and import the tests, so that a
cell's programs and reference passes are one xdist worker's job and no
file is a long pole (tests/conftest.py, runtime budget). Two layers: a
one-layer cache holds no bit that attention produced."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.models.decode import (
    KVCacheSpec,
    PagedKVCacheSpec,
    _prompt_shard,
    decode_step,
    prefill_cache,
    specs_for,
)
from triton_dist_tpu.models.speculative import verify_step
from triton_dist_tpu.ops.common import jit_shard_map
from triton_dist_tpu.ops.flash_decode import FlashDecodeConfig

from ranged_helpers import B, L, S_MAX, _put

# name -> (cache spec, flash-decode config): what a file's `cell` fixture picks
CELLS = {
    "contiguous/xla": (lambda: KVCacheSpec(S_MAX), None),
    "contiguous/kernel": (
        lambda: KVCacheSpec(S_MAX), FlashDecodeConfig(block_s=4)),
    "paged/static": (
        lambda: PagedKVCacheSpec(S_MAX, 4, static_table=True), None),
}


def _run_prefill(mesh, cfg, params_d, pspecs, spec, prompt):
    cache = _put(mesh, spec.init(cfg, 4, 1), spec.specs(cfg))

    def fn(params, cache, prompt):
        pcfg = dataclasses.replace(cfg, seq=L, batch=B)
        return prefill_cache(
            pcfg, params, cache, _prompt_shard(prompt, B, L, cfg), spec, S_MAX
        )

    prog = jit_shard_map(
        fn, mesh, (pspecs, spec.specs(cfg), P(None, None)),
        (spec.specs(cfg), P(None, None)), key=("rp_prefill", spec),
    )
    return prog(params_d, cache, prompt)


def _run_ranged(mesh, cfg, params_d, pspecs, spec, prompt, splits, fd):
    cache = _put(mesh, spec.init(cfg, 4, 1), spec.specs(cfg))

    def fn(params, cache, tokens, pos0):
        return verify_step(
            dataclasses.replace(cfg, seq=tokens.shape[1]), params, cache,
            tokens, pos0, spec=spec, fd_config=fd,
        )

    last = None
    lo = 0
    for hi in splits:
        prog = jit_shard_map(
            fn, mesh,
            (pspecs, spec.specs(cfg), P(None, None), P(None)),
            (P(None, None, None), spec.specs(cfg)),
            key=("rp_ranged", spec, hi - lo, fd),
        )
        logits, cache = prog(
            params_d, cache, prompt[:, lo:hi],
            jnp.full((B,), lo, jnp.int32),
        )
        last = logits[:, -1]
        lo = hi
    return cache, last


@pytest.fixture(scope="module")
def placed(mesh4, model):
    """The model's parameters on the mesh, with their specs."""
    cfg, params = model
    pspecs = specs_for(cfg, params)
    return pspecs, _put(mesh4, params, pspecs)


@pytest.fixture(scope="module")
def references(mesh4, model, prompt, placed, cell):
    """(whole-range ranged pass, bulk prefill) of the file's cell: what its
    tests compare against, run once (each is an interpreted two-layer pass
    on four devices)."""
    cfg, _ = model
    mkspec, fd = cell
    pspecs, params_d = placed
    return (
        _run_ranged(mesh4, cfg, params_d, pspecs, mkspec(), prompt, [L], fd),
        _run_prefill(mesh4, cfg, params_d, pspecs, mkspec(), prompt),
    )


def _cache_bits(spec, cache):
    """The comparable KV bits: landed positions < L (contiguous), or the
    pool pages the block table names for positions < L (paged)."""
    k, v = np.asarray(cache["k"]), np.asarray(cache["v"])
    if "block_table" in cache:
        bt = np.asarray(cache["block_table"][0])
        pages = bt[:, : L // 4].reshape(-1)
        return k[:, pages], v[:, pages]
    return k[:, :, :, :L], v[:, :, :, :L]


@pytest.mark.parametrize("splits", [[3, L], [2, 5, L]], ids=str)
def test_ranged_composition_matches_prefill(
        mesh4, model, prompt, placed, references, cell, splits):
    """Composing consecutive ranged passes over [0, L) is BIT-IDENTICAL
    to one whole-range pass — cache AND final logits, on the contiguous
    XLA, contiguous kernel, and paged static cells (the forward is
    row-independent, so the split point cannot change any landed bit) —
    and reproduces the bulk masked prefill's cache numerically (the bulk
    pass is a different attention program — dense padded rectangle vs
    the verify family — so cross-PROGRAM agreement is allclose; token
    byte-identity across programs is pinned at the batcher tier, where
    the sampler consumes the logits)."""
    cfg, _ = model
    mkspec, fd = cell
    spec = mkspec()
    pspecs, params_d = placed
    (cache_w, last_w), (cache_p, _) = references
    cache_r, last_r = _run_ranged(
        mesh4, cfg, params_d, pspecs, spec, prompt, splits, fd
    )
    np.testing.assert_array_equal(
        np.asarray(cache_r["k"]), np.asarray(cache_w["k"])
    )
    np.testing.assert_array_equal(
        np.asarray(cache_r["v"]), np.asarray(cache_w["v"])
    )
    np.testing.assert_array_equal(np.asarray(last_r), np.asarray(last_w))
    kp, vp = _cache_bits(spec, cache_p)
    kr, vr = _cache_bits(spec, cache_r)
    np.testing.assert_allclose(kr, kp, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(vr, vp, rtol=2e-4, atol=2e-4)


def test_ranged_matches_decode_chain(
        mesh4, model, prompt, placed, references, cell):
    """One whole-prompt ranged pass equals the token-by-token decode_step
    chain bit-for-bit (cache and final logits) — the ranged forward IS
    the decode forward, batched over positions."""
    cfg, _ = model
    mkspec, fd = cell
    spec = mkspec()
    pspecs, params_d = placed
    cache0 = _put(mesh4, spec.init(cfg, 4, 1), spec.specs(cfg))

    def chain(params, cache, prompt):
        def body(cache, i):
            logits, cache = decode_step(
                cfg, params, cache, prompt[:, i], i, spec=spec, fd_config=fd
            )
            return cache, logits

        cache2, logits = jax.lax.scan(body, cache, jnp.arange(L))
        return logits[-1], cache2

    prog = jit_shard_map(
        chain, mesh4, (pspecs, spec.specs(cfg), P(None, None)),
        (P(None, None), spec.specs(cfg)), key=("rp_chain", spec, fd),
    )
    last_a, cache_a = prog(params_d, cache0, prompt)
    (cache_b, last_b), _ = references
    np.testing.assert_array_equal(
        np.asarray(cache_a["k"]), np.asarray(cache_b["k"])
    )
    np.testing.assert_array_equal(
        np.asarray(cache_a["v"]), np.asarray(cache_b["v"])
    )
    np.testing.assert_array_equal(np.asarray(last_a), np.asarray(last_b))


def test_ranged_softcap_self_composition(
        mesh4, model, prompt, placed, references):
    """soft_cap lives in FlashDecodeConfig (the bulk prefill has no cap
    knob), so the cap≠0 composition pin is SELF-referential: [L] vs
    [3, L] under a capped kernel config must be bit-identical. (The
    kernel cell's test: its whole pass is the same kernel, uncapped.)"""
    cfg, _ = model
    spec = KVCacheSpec(S_MAX)
    fd = FlashDecodeConfig(block_s=4, soft_cap=15.0)
    pspecs, params_d = placed
    cache_a, last_a = _run_ranged(
        mesh4, cfg, params_d, pspecs, spec, prompt, [L], fd
    )
    cache_b, last_b = _run_ranged(
        mesh4, cfg, params_d, pspecs, spec, prompt, [3, L], fd
    )
    np.testing.assert_array_equal(
        np.asarray(cache_a["k"]), np.asarray(cache_b["k"])
    )
    np.testing.assert_array_equal(np.asarray(last_a), np.asarray(last_b))
    # and the cap actually bites: uncapped last logits differ
    (_, last_u), _ = references
    assert not np.array_equal(np.asarray(last_a), np.asarray(last_u))
