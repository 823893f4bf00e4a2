"""Suffix-only ranged prefill (ISSUE 18): one kernel — the verify-family
forward over a query RANGE against already-landed KV — proved bit-exact,
then driven through its three doors.

- **ops tier**: ``flash_ranged_prefill_distributed`` (and the paged twin)
  composed over consecutive ranges is bit-identical to one whole-range
  pass, at d=96 and soft_cap≠0, against the capped per-row golden.
- **model tier**: ``verify_step`` range composition reproduces
  ``prefill_cache``'s cache AND last logits bit-for-bit (contiguous XLA,
  contiguous kernel, paged static cells), and equals the token-by-token
  ``decode_step`` chain (ranged_model_tier.py, one test file a cell:
  test_ranged_contiguous / _kernel / _paged); bulk prefill is
  bucket-invariant (here).
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
from jax.sharding import PartitionSpec as P


from triton_dist_tpu.models.decode import (
    KVCacheSpec,
    _prompt_shard,
    prefill_cache,
    specs_for,
)


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
# Model tier: bulk prefill is bucket-invariant (the rest of the tier is
# ranged_model_tier.py, one file a cell)
# ---------------------------------------------------------------------------

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
