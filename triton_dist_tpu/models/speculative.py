"""Speculative decoding — draft-model speculation with multi-position
verification (beyond the reference, whose serving surface stops at the
single-token decode kernel; this is the standard big-model serving
accelerant built ON TOP of that kernel family).

Why it is TPU-shaped: single-token decode is HBM-bound — every step
streams the whole KV cache and every weight matrix for ONE token's worth
of MXU work per sequence. The verify step scores S = k+1 positions in
one pass: the cache and the weights stream ONCE for S tokens
(``ops.flash_decode.flash_verify`` — per-row prefix masks inside the
same online-softmax kernel), and every matmul feeds the MXU S× the rows.
Accepted-draft tokens therefore cost ~1/S of a decode step each.

Greedy-exact: the emitted stream equals the target model's own greedy
decode (tested token-for-token against ``decode.generate``). Accepted
tokens are verified (target argmax == draft token); the bonus token is
the target's argmax at the first divergence. Rollback is free by the
cache design: positions past the accepted prefix hold stale k/v that
``kv_lens = pos+1`` masks until they are overwritten.

Batch acceptance is LOCKSTEP (the round accepts ``min`` over sequences,
capped at k-1): every slot advances the same number of positions per
round, which keeps positions scalar and — with the k-1 cap — keeps the
draft's cache rows equal to the accepted inputs without a catch-up step.
Every serving deployment composes — flat 1-axis (dense / TP-MoE / flat
EP) and the hierarchical EP mesh (DP attention per outer group + the
two-phase dispatch, mirrored from decode_step), including a flat/dense
draft speculating for a hierarchical target on the same 2-axis mesh —
on EITHER cache layout (contiguous, or paged pools with static block
tables via ``page_size=``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_dist_tpu.models.decode import (
    KVCacheSpec,
    PagedKVCacheSpec,
    _mesh_outer,
    _prompt_shard,
    decode_step,
    prefill_cache,
    prefill_cache_ranged,
    specs_for,
)
from triton_dist_tpu.models.tp_transformer import TransformerConfig
from triton_dist_tpu.ops.flash_decode import FlashDecodeConfig


def accept_lengths(drafts, preds, k: int, xp=np):
    """PER-SLOT accepted-draft counts — the speculative acceptance core,
    shared by the lockstep loop below (which takes the batch ``min``) and
    the per-slot serving batcher (serving/speculative.py, which does
    not). ``drafts [b, k]`` are the draft's proposals, ``preds [b, >=k]``
    the verify pass's greedy predictions (row j = the target's choice
    after inputs ``tok, d_1..d_j``). Slot i accepts its longest prefix of
    drafts matching the target's own chain, capped at ``k-1`` — the cap
    keeps the draft cache rows equal to the accepted inputs without a
    catch-up forward (module docstring). Returns ``[b]`` counts in
    ``[0, k-1]``.

    ``xp`` selects the array namespace: ``np`` (host, the serving
    batcher) or ``jnp`` (inside the lockstep device loop) — one formula,
    both worlds, so the per-slot/lockstep equivalence is structural
    (pinned in tests/test_speculative.py)."""
    match = (preds[:, :k] == drafts).astype(xp.int32)
    return xp.minimum(xp.cumprod(match, axis=1).sum(axis=1), k - 1)


def verify_step(
    cfg: TransformerConfig,
    params: dict,
    cache: dict,
    tokens: jax.Array,   # [b, S] int32 — chunk inputs per sequence
    pos0: jax.Array,     # [] or [b] int32 — first chunk position
    *,
    spec: KVCacheSpec | PagedKVCacheSpec,
    fd_config: FlashDecodeConfig | None = None,
    interpret: Any = None,
) -> tuple[jax.Array, dict]:
    """Score S consecutive input tokens per sequence in ONE forward (call
    inside ``jax.shard_map``): returns ``(logits [b, S, vocab],
    new_cache)`` — row i's logits are the model's next-token distribution
    after inputs ``tokens[:, :i+1]``, exactly what S successive
    decode_steps would produce, at one cache/weight pass. The chunk's k/v
    are appended (owner-gated per position) before attention; causality
    within the chunk rides the per-row prefix lengths.

    The forward itself lives in ``decode.prefill_cache_ranged`` (ISSUE
    18): verification is the S-draft-token instance of the suffix-only
    ranged prefill — same append, same per-row causal mask against the
    landed prior. This entry is the stable speculative-decoding name."""
    return prefill_cache_ranged(
        cfg, params, cache, tokens, pos0,
        spec=spec, fd_config=fd_config, interpret=interpret,
    )


def speculative_generate(
    cfg: TransformerConfig,
    params: dict,
    draft_cfg: TransformerConfig,
    draft_params: dict,
    prompt: jax.Array,   # [b, prompt_len] int32
    n_steps: int,
    mesh: Mesh,
    *,
    s_max: int,
    draft_k: int = 4,
    page_size: int | None = None,
    fd_config: FlashDecodeConfig | None = None,
    draft_fd_config: FlashDecodeConfig | None = None,
    prefill: bool = False,
    interpret: Any = None,
) -> jax.Array:
    """Greedy speculative generation: the draft model proposes ``draft_k``
    tokens per round, one verify forward on the target accepts the
    longest matching prefix plus the target's own bonus token. Returns
    ``[b, n_steps]`` — TOKEN-IDENTICAL to ``decode.generate(cfg, params,
    ...)`` (greedy equivalence), in ~``n_steps / (accepted+1)`` target
    forwards instead of ``n_steps``.

    `draft_cfg`/`draft_params` are a (smaller) model over the SAME vocab
    and serving axis; both caches live on `mesh` (contiguous by default,
    page pools + static tables with ``page_size=``). ``prefill=True``
    warms BOTH caches through one full-forward prompt pass each
    (MXU-rate admission, as in ``generate``) instead of token-by-token."""
    from triton_dist_tpu.ops.common import jit_shard_map

    b, prompt_len = prompt.shape
    if cfg.vocab != draft_cfg.vocab or cfg.batch != draft_cfg.batch:
        raise ValueError("target and draft must share vocab and batch")
    # +k+1: each round may write up to draft_k chunk positions beyond the
    # accepted prefix before the position pointer rolls back
    if prompt_len + n_steps + draft_k + 1 > s_max:
        raise ValueError(
            f"speculative rounds write up to draft_k={draft_k} positions "
            f"past the accepted prefix: need prompt+steps+k+1 <= "
            f"s_max={s_max}"
        )
    if draft_k < 2:
        raise ValueError("draft_k must be >= 2 (k-1 accepted tokens max)")
    if page_size:
        # the serving cache layout: page pools + STATIC tables (the
        # chunk append batch-writes page ranges, like prefill) for both
        # models; both verify and single-token decode ride the tables
        if fd_config is not None or draft_fd_config is not None:
            raise ValueError(
                "fd_config tiles the contiguous kernel; with page_size "
                "the page is the block — pass one or the other"
            )
        spec_t = PagedKVCacheSpec(s_max, page_size, static_table=True)
        spec_d = PagedKVCacheSpec(s_max, page_size, static_table=True)
    else:
        spec_t, spec_d = KVCacheSpec(s_max), KVCacheSpec(s_max)
    n = mesh.shape[cfg.axis]
    # hierarchical targets serve on the 2-axis mesh (DP attention per
    # outer group — verify_step mirrors decode_step); a flat/dense DRAFT
    # on the same mesh simply replicates over the outer axis
    n_o_t = _mesh_outer(cfg, mesh)
    n_o_d = _mesh_outer(draft_cfg, mesh)

    def put_tree(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs
        )

    cache_t = put_tree(spec_t.init(cfg, n, n_o_t), spec_t.specs(cfg))
    cache_d = put_tree(spec_d.init(draft_cfg, n, n_o_d), spec_d.specs(draft_cfg))
    params_t = put_tree(params, specs_for(cfg, params))
    params_d = put_tree(draft_params, specs_for(draft_cfg, draft_params))
    step_t = functools.partial(
        decode_step, cfg, spec=spec_t, fd_config=fd_config,
        interpret=interpret,
    )
    step_d = functools.partial(
        decode_step, draft_cfg, spec=spec_d, fd_config=draft_fd_config,
        interpret=interpret,
    )

    def warm_prefill(pt, pd, ct, cd, prompt):
        # one full transformer forward per model writes the whole
        # prompt's KV (decode.prefill_cache — the chunked-prefill path
        # generate's prefill=True rides); the target's last-position
        # logits yield the first emitted token
        pcfg_t = dataclasses.replace(
            cfg, seq=prompt_len, batch=b // n_o_t
        )
        ct, last = prefill_cache(
            pcfg_t, pt, ct, _prompt_shard(prompt, b, prompt_len, cfg),
            spec_t, s_max,
        )
        pcfg_d = dataclasses.replace(
            draft_cfg, seq=prompt_len, batch=b // n_o_d
        )
        cd, _ = prefill_cache(
            pcfg_d, pd, cd, _prompt_shard(prompt, b, prompt_len, draft_cfg),
            spec_d, s_max,
        )
        return ct, cd, jnp.argmax(last, axis=-1).astype(jnp.int32)

    def warm(pt, pd, ct, cd, prompt):
        # feed the prompt into BOTH caches; only the LAST position's
        # argmax is needed (carried, not stacked — a stacked
        # [prompt_len, b, vocab] would dwarf the model at serving shapes)
        def body(carry, i):
            ct, cd, _ = carry
            lt, ct = step_t(pt, ct, prompt[:, i], i)
            _, cd = step_d(pd, cd, prompt[:, i], i)
            return (ct, cd, jnp.argmax(lt, axis=-1).astype(jnp.int32)), None

        b = prompt.shape[0]
        (ct, cd, t1), _ = jax.lax.scan(
            body, (ct, cd, jnp.zeros((b,), jnp.int32)),
            jnp.arange(prompt_len),
        )
        return ct, cd, t1

    def draft_roll(pd, cd, tok, pos0):
        def body(carry, j):
            cd, tok = carry
            lg, cd = step_d(pd, cd, tok, pos0 + j)
            nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            return (cd, nxt), nxt

        (cd, _), ds = jax.lax.scan(body, (cd, tok), jnp.arange(draft_k))
        return cd, ds.T                                    # [b, draft_k]

    def verify(pt, ct, chunk, pos0):
        logits, ct = verify_step(
            cfg, pt, ct, chunk, pos0, spec=spec_t, fd_config=fd_config,
            interpret=interpret,
        )
        return ct, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    k = draft_k

    def spec_run(pt, pd, ct, cd, prompt):
        # ONE device program: warm-up, then a lax.while_loop of
        # draft→verify→accept rounds with the accept decision ON DEVICE.
        # The first cut of this loop lived on the host (round-trip per
        # round for the accept argmaxes): each round paid ~2
        # dispatch+readback round trips while plain `generate` is a
        # single dispatch. Device-side accept makes this one dispatch too.
        ct, cd, tok0 = (warm_prefill if prefill else warm)(
            pt, pd, ct, cd, prompt
        )
        # write-ahead token buffer: each round writes its full k-column
        # candidate block at `cnt` (accepted drafts, then the bonus at
        # column a, then filler); only `cnt += a+1` commits — the next
        # round overwrites the uncommitted tail, and columns past
        # n_steps are sliced off at the end
        out0 = jnp.zeros((cfg.batch, n_steps + k), jnp.int32)
        out0 = jax.lax.dynamic_update_index_in_dim(out0, tok0, 0, axis=1)

        def cond(st):
            return st[5] < n_steps

        def body(st):
            ct, cd, tok, pos, out, cnt = st
            cd, drafts = draft_roll(pd, cd, tok, pos)
            chunk = jnp.concatenate([tok[:, None], drafts], axis=1)
            ct, preds = verify(pt, ct, chunk, pos)
            # longest verified prefix: the shared per-slot acceptance
            # core (accept_lengths), then lockstep over the batch — the
            # round advances by the MINIMUM slot's acceptance (min and
            # the k-1 cap commute, so per-slot-then-min equals the
            # historical min-then-cap formula bit for bit)
            a = jnp.min(accept_lengths(drafts, preds, k, xp=jnp)).astype(
                jnp.int32
            )
            bonus = jax.lax.dynamic_index_in_dim(
                preds, a, axis=1, keepdims=False
            )
            vals = jnp.where(
                jnp.arange(k, dtype=jnp.int32)[None, :] < a, drafts,
                bonus[:, None],
            )
            out = jax.lax.dynamic_update_slice(out, vals, (0, cnt))
            return ct, cd, bonus, pos + a + 1, out, cnt + a + 1

        st = (
            ct, cd, tok0, jnp.int32(prompt_len), out0, jnp.int32(1),
        )
        _, _, _, _, out, _ = jax.lax.while_loop(cond, body, st)
        return out[:, :n_steps]

    cs_t, cs_d = spec_t.specs(cfg), spec_d.specs(draft_cfg)
    ps_t, ps_d = specs_for(cfg, params), specs_for(draft_cfg, draft_params)
    key = (cfg, draft_cfg, s_max, draft_k, page_size, fd_config,
           draft_fd_config, str(interpret))
    if prefill:
        for nm, n_o_x in (("target", n_o_t), ("draft", n_o_d)):
            if (b * prompt_len) % (n * n_o_x):
                raise ValueError(
                    f"prefill warm-up shards b*prompt_len="
                    f"{b * prompt_len} over the {nm}'s {n * n_o_x} PEs — "
                    f"must divide evenly"
                )
    run_p = jit_shard_map(
        spec_run, mesh,
        (ps_t, ps_d, cs_t, cs_d, P(None, None)),
        P(None, None),
        key=("spec_run", prefill, prompt_len, n_steps, *key),
    )
    out = run_p(params_t, params_d, cache_t, cache_d, prompt)
    return np.asarray(out)                                 # [b, n_steps]
