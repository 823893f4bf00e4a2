"""Suffix-only ranged prefill (ISSUE 18): one kernel — the verify-family
forward over a query RANGE against already-landed KV — proved bit-exact,
then driven through its three doors.

- **ops tier**: ``flash_ranged_prefill_distributed`` (and the paged twin)
  composed over consecutive ranges is bit-identical to one whole-range
  pass, at d=96 and soft_cap≠0, against the capped per-row golden.
- **model tier**: ``verify_step`` range composition reproduces
  ``prefill_cache``'s cache AND last logits bit-for-bit (contiguous XLA,
  contiguous kernel, paged static cells), and equals the token-by-token
  ``decode_step`` chain; bulk prefill is bucket-invariant.
- **batcher tier**: prefix-cache admission under ``prefill=True`` and
  chunked-prefill scheduling (``prefill_chunk_tokens``) are byte-identical
  to token-fed admission, greedy AND seeded-sampled; armed-but-untriggered
  arms are byte-identical to disarmed ones; the swept-work counter prices
  chunked admission below the bulk bucket rectangle.
- **serving tier**: engine-tier byte-identity of the px+prefill and
  chunked arms vs the cold engine; the long-prompt traffic stream keeps
  historical fingerprints; pipelined disagg admission gates on the FIRST
  page landing with the transfer-span decomposition still exact.
- **chaos tier** (``pytest.mark.chaos``, rides ``chaos_matrix.sh``):
  corrupt streamed chunks mid-pipelined-handoff walk the guard ladder and
  the campaign replays bit-identically.
"""

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
from triton_dist_tpu.ops.flash_decode import (
    FlashDecodeConfig,
    flash_ranged_prefill_distributed,
    paged_flash_ranged_prefill_distributed,
)
from ranged_helpers import B, L, S_MAX, _put, mesh1, model, prompt


# ---------------------------------------------------------------------------
# Ops tier: ranged entries, composition × d=96 × soft_cap, vs golden
# ---------------------------------------------------------------------------

def _ref_capped_row(q, k, v, kv_lens, soft_cap=0.0):
    """Capped masked-attention golden for one query row per sequence."""
    b, hq, d = q.shape
    _, h_kv, s, _ = k.shape
    g = hq // h_kv
    q4 = q.reshape(b, h_kv, g, d).astype(jnp.float32)
    scores = jnp.einsum("bhgd,bhsd->bhgs", q4, k.astype(jnp.float32))
    scores /= jnp.sqrt(jnp.float32(d))
    if soft_cap:
        scores = soft_cap * jnp.tanh(scores / soft_cap)
    mask = jnp.arange(s)[None, :] < kv_lens[:, None]
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(b, hq, d)


def test_ranged_ops_composition_softcap_d96(mesh4):
    """Contiguous ranged prefill at d=96 with soft_cap: composing the
    range [0, 4) + [4, 8) is bit-identical to one [0, 8) pass, and both
    match the capped per-row golden."""
    b, h_kv, g, s, d = 2, 2, 2, 64, 96
    hq = h_kv * g
    S = 8
    key = jax.random.PRNGKey(51)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, S, hq, d), jnp.float32)
    k = jax.random.normal(kk, (b, h_kv, s, d), jnp.float32)
    v = jax.random.normal(kv_, (b, h_kv, s, d), jnp.float32)
    cap = 15.0

    def run(q_part, lo):
        def fn(q, k, v, pos0):
            return flash_ranged_prefill_distributed(
                q, k, v, pos0,
                config=FlashDecodeConfig(block_s=16, soft_cap=cap),
            )

        prog = jit_shard_map(
            fn, mesh4,
            (
                P(None, None, None, None), P(None, None, "tp", None),
                P(None, None, "tp", None), P(None),
            ),
            P(None, None, None, None),
            key=("rp_ops_d96", q_part.shape[1], cap),
        )
        return prog(q_part, k, v, jnp.full((b,), lo, jnp.int32))

    whole = run(q, 0)
    split = jnp.concatenate([run(q[:, :4], 0), run(q[:, 4:], 4)], axis=1)
    np.testing.assert_array_equal(np.asarray(split), np.asarray(whole))
    for i in range(S):
        want = _ref_capped_row(
            q[:, i], k, v, jnp.full((b,), i + 1, jnp.int32), soft_cap=cap
        )
        np.testing.assert_allclose(
            np.asarray(whole[:, i]), np.asarray(want), rtol=2e-4, atol=2e-4
        )


def test_paged_ranged_ops_composition_softcap_d96(mesh1):
    """The paged twin (block-table indirection, soft_cap as kwarg) at
    d=96: range composition bit-identical, per-row capped golden."""
    b, h_kv, g, s, d, page = 2, 2, 2, 64, 96, 16
    hq = h_kv * g
    S = 8
    key = jax.random.PRNGKey(61)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, S, hq, d), jnp.float32)
    k = jax.random.normal(kk, (b, h_kv, s, d), jnp.float32)
    v = jax.random.normal(kv_, (b, h_kv, s, d), jnp.float32)
    ppseq = s // page
    bt = jnp.arange(b * ppseq, dtype=jnp.int32).reshape(b, ppseq)
    kp = k.reshape(b, h_kv, ppseq, page, d).swapaxes(1, 2).reshape(
        b * ppseq, h_kv, page, d
    )
    vp = v.reshape(b, h_kv, ppseq, page, d).swapaxes(1, 2).reshape(
        b * ppseq, h_kv, page, d
    )
    cap = 25.0

    def run(q_part, lo):
        def fn(q, kp, vp, pos0, bt):
            return paged_flash_ranged_prefill_distributed(
                q, kp, vp, pos0, bt, soft_cap=cap
            )

        prog = jit_shard_map(
            fn, mesh1,
            (
                P(None, None, None, None), P(None, None, None, None),
                P(None, None, None, None), P(None), P(None, None),
            ),
            P(None, None, None, None),
            key=("rp_ops_paged_d96", q_part.shape[1], cap),
        )
        return prog(q_part, kp, vp, jnp.full((b,), lo, jnp.int32), bt)

    whole = run(q, 0)
    split = jnp.concatenate(
        [run(q[:, :3], 0), run(q[:, 3:5], 3), run(q[:, 5:], 5)], axis=1
    )
    np.testing.assert_array_equal(np.asarray(split), np.asarray(whole))
    for i in range(S):
        want = _ref_capped_row(
            q[:, i], k, v, jnp.full((b,), i + 1, jnp.int32), soft_cap=cap
        )
        np.testing.assert_allclose(
            np.asarray(whole[:, i]), np.asarray(want), rtol=2e-4, atol=2e-4
        )


# ---------------------------------------------------------------------------
# Model tier: ranged composition ≡ whole-prompt prefill ≡ decode chain
# ---------------------------------------------------------------------------

CELLS = [
    ("contiguous/xla", lambda: KVCacheSpec(S_MAX), None),
    (
        "contiguous/kernel",
        lambda: KVCacheSpec(S_MAX),
        FlashDecodeConfig(block_s=4),
    ),
    (
        "paged/static",
        lambda: PagedKVCacheSpec(S_MAX, 4, static_table=True),
        None,
    ),
]


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


def _cache_bits(spec, cache):
    """The comparable KV bits: landed positions < L (contiguous), or the
    pool pages the block table names for positions < L (paged)."""
    k, v = np.asarray(cache["k"]), np.asarray(cache["v"])
    if "block_table" in cache:
        bt = np.asarray(cache["block_table"][0])
        pages = bt[:, : L // 4].reshape(-1)
        return k[:, pages], v[:, pages]
    return k[:, :, :, :L], v[:, :, :, :L]


@pytest.mark.parametrize(
    "cell", CELLS, ids=[c[0].replace("/", "-") for c in CELLS]
)
@pytest.mark.parametrize("splits", [[3, L], [2, 5, L]], ids=str)
def test_ranged_composition_matches_prefill(mesh4, model, prompt, cell, splits):
    """Composing consecutive ranged passes over [0, L) is BIT-IDENTICAL
    to one whole-range pass — cache AND final logits, on the contiguous
    XLA, contiguous kernel, and paged static cells (the forward is
    row-independent, so the split point cannot change any landed bit) —
    and reproduces the bulk masked prefill's cache numerically (the bulk
    pass is a different attention program — dense padded rectangle vs
    the verify family — so cross-PROGRAM agreement is allclose; token
    byte-identity across programs is pinned at the batcher tier, where
    the sampler consumes the logits)."""
    cfg, params = model
    name, mkspec, fd = cell
    spec = mkspec()
    pspecs = specs_for(cfg, params)
    params_d = _put(mesh4, params, pspecs)
    cache_w, last_w = _run_ranged(
        mesh4, cfg, params_d, pspecs, spec, prompt, [L], fd
    )
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
    cache_p, _ = _run_prefill(mesh4, cfg, params_d, pspecs, spec, prompt)
    kp, vp = _cache_bits(spec, cache_p)
    kr, vr = _cache_bits(spec, cache_r)
    np.testing.assert_allclose(kr, kp, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(vr, vp, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize(
    "cell", CELLS, ids=[c[0].replace("/", "-") for c in CELLS]
)
def test_ranged_matches_decode_chain(mesh4, model, prompt, cell):
    """One whole-prompt ranged pass equals the token-by-token decode_step
    chain bit-for-bit (cache and final logits) — the ranged forward IS
    the decode forward, batched over positions."""
    cfg, params = model
    name, mkspec, fd = cell
    spec = mkspec()
    pspecs = specs_for(cfg, params)
    params_d = _put(mesh4, params, pspecs)

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
    cache_b, last_b = _run_ranged(
        mesh4, cfg, params_d, pspecs, spec, prompt, [L], fd
    )
    np.testing.assert_array_equal(
        np.asarray(cache_a["k"]), np.asarray(cache_b["k"])
    )
    np.testing.assert_array_equal(
        np.asarray(cache_a["v"]), np.asarray(cache_b["v"])
    )
    np.testing.assert_array_equal(np.asarray(last_a), np.asarray(last_b))


def test_ranged_softcap_self_composition(mesh4, model, prompt):
    """soft_cap lives in FlashDecodeConfig (the bulk prefill has no cap
    knob), so the cap≠0 composition pin is SELF-referential: [L] vs
    [3, L] under a capped kernel config must be bit-identical."""
    cfg, params = model
    spec = KVCacheSpec(S_MAX)
    fd = FlashDecodeConfig(block_s=4, soft_cap=15.0)
    pspecs = specs_for(cfg, params)
    params_d = _put(mesh4, params, pspecs)
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
    _, last_u = _run_ranged(
        mesh4, cfg, params_d, pspecs, spec, prompt, [L],
        FlashDecodeConfig(block_s=4),
    )
    assert not np.array_equal(np.asarray(last_a), np.asarray(last_u))


def test_prefill_bucket_invariance(mesh4, model, prompt):
    """Bulk prefill of an 8-token prompt at bucket 8 vs bucket 16 is
    bit-identical on the landed positions — the padded rectangle's pad
    rows never leak into landed KV or the picked logits (the fact that
    lets chunked and bulk admission share one byte-identity class)."""
    cfg, params = model
    spec = KVCacheSpec(S_MAX)
    pspecs = specs_for(cfg, params)
    params_d = _put(mesh4, params, pspecs)

    def run(bucket):
        cache = _put(mesh4, spec.init(cfg, 4, 1), spec.specs(cfg))
        pr = np.zeros((B, bucket), np.int32)
        pr[:, :L] = np.asarray(prompt)
        pick = np.full((B,), L - 1, np.int32)

        def fn(params, cache, prompt, mask, pick):
            pcfg = dataclasses.replace(cfg, seq=bucket, batch=B)
            return prefill_cache(
                pcfg, params, cache, _prompt_shard(prompt, B, bucket, cfg),
                spec, S_MAX, slot_mask=mask, pick=pick,
            )

        prog = jit_shard_map(
            fn, mesh4,
            (pspecs, spec.specs(cfg), P(None, None), P(None), P(None)),
            (spec.specs(cfg), P(None, None)), key=("rp_bucket", bucket),
        )
        return prog(
            params_d, cache, jnp.asarray(pr), jnp.ones((B,), bool),
            jnp.asarray(pick),
        )

    c8, l8 = run(8)
    c16, l16 = run(16)
    np.testing.assert_array_equal(
        np.asarray(c8["k"])[:, :, :, :L], np.asarray(c16["k"])[:, :, :, :L]
    )
    np.testing.assert_array_equal(np.asarray(l8), np.asarray(l16))


