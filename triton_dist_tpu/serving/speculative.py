"""Speculative decoding as a production serving mode (ISSUE 20
tentpole): per-slot acceptance in the continuous batcher.

Why this is the biggest untouched tokens/s lever: decode on TPU is
HBM-bound — every single-token step streams the whole KV cache and every
weight matrix for ONE token's worth of MXU work per sequence. The verify
pass scores k+1 positions at one cache/weight sweep
(``models.speculative.verify_step`` → the suffix-only ranged prefill),
so accepted draft tokens cost ~1/(k+1) of a decode step each (Leviathan
et al. 2023; Chen et al. 2023). The standalone lockstep loop
(``models.speculative.speculative_generate``) already proves the kernel
substrate; this module promotes it into the :class:`~.engine.
ServingEngine`'s continuous batcher, where slots are RAGGED:

- **Per-slot acceptance** — each speculating slot accepts its own
  longest verified draft prefix (the shared
  ``models.speculative.accept_lengths`` core, capped at ``k-1``) plus
  the target's bonus token; one slot rejecting everything never stalls a
  neighbor accepting ``k`` (the lockstep loop's ``min`` would). The
  rejected suffix needs NO undo work: the slot's position simply does
  not advance over it, and stale KV past the accepted prefix is masked
  by ``kv_lens = pos+1`` until the next round overwrites it — rollback
  is free by cache design.
- **One batched verify pass per round** — every occupied slot rides ONE
  ``k+1``-column ranged-prefill program (the batcher's existing
  ``_ranged_prog``): speculating slots carry ``[tok, d_1..d_k]``,
  prompt-feeding and non-eligible slots carry their plain decode input
  in column 0 (bit-identical to ``decode_step`` — the ranged-prefill
  pin) with filler columns whose junk KV the dirty-cache discipline
  overwrites before ``kv_lens`` exposes it, and idle slots park at
  ``pos0 = s_max`` exactly like the chunked-prefill scheduler.
- **The draft rides everything the target does** — its own cache
  (mirrored page-pool geometry when the target is paged), its own
  mirror of the prefix-cache trie over its own pool, per-slot ragged
  catch-up through its own ranged-prefill programs. The ``k-1``
  acceptance cap keeps the draft cache rows equal to the accepted
  inputs after every round without a catch-up forward; a fresh slot
  (admission, engine rebuild replay) ingests its history in one ranged
  pass.
- **Determinism** — greedy mode emits token-for-token what plain
  ``decode_step`` serving emits (every accepted draft equals the
  target's own argmax; the bonus IS the target's argmax). Sampled mode
  is seeded rejection sampling on the slot's own RNG stream
  (draft proposal draws, acceptance uniforms, residual/bonus draws, in
  a fixed per-slot order): replays are bit-identical, and the emitted
  distribution is the target's own (the Leviathan/Chen correctness
  argument) though the STREAM differs from non-speculative serving —
  the draws are spent differently (docs/serving.md).
- **Adaptive k** — a rolling acceptance-rate window backs ``k_live``
  off toward ``k_min`` when α drops (a cold draft burns draft+verify
  cost for nothing) and regrows it on recovery; transitions surface as
  informational health events via the engine callback.

Arming discipline: ``ServingConfig(speculative=None)`` is the pre-spec
engine byte for byte; ``SpecDecodeConfig(k=0)`` is dormant — the batcher
delegates every round to the plain ``_decode_round`` and charges plain
cost, pinned ≡ disarmed in tests/test_spec_serving.py. Step-cost
accounting: each round reports ``last_step_units`` (1.0 plain;
``1 + verify_cost_factor·k + draft_cost_factor·k`` speculative, plus the
draft catch-up sweep) and the engine scales ``virtual_step_s`` by it, so
FakeClock A/Bs measure the real step-count win
(``perf_model.estimate_spec_decode_gain`` is the closed-form surface).

Chaos seam: ``corrupt_draft_next`` (set by resilience/soak.py's
speculative campaign) flips one draft token before the next verify —
the acceptance rule provably rejects any corrupt draft that disagrees
with the target's own chain, so the stream stays byte-identical to
non-speculative serving whatever the draft proposes.
"""

from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from triton_dist_tpu.models.decode import (
    ContinuousBatcher,
    KVCacheSpec,
    PagedKVCacheSpec,
    _mesh_outer,
    decode_step,
    prefill_cache_ranged,
    refuse_ring,
    refuse_state,
    STATE_CACHE_KINDS,
    specs_for,
)
from triton_dist_tpu.models.speculative import accept_lengths
from triton_dist_tpu.obs.tracer import span as _span


@dataclasses.dataclass(frozen=True)
class SpecDecodeConfig:
    """Speculative serving knobs (arm via ``ServingConfig(speculative=
    SpecDecodeConfig(draft_cfg, draft_params, ...))``).

    draft_cfg / draft_params: the (smaller) draft model — SAME vocab and
                    batch as the target, flat serving axis on the same
                    mesh. Host param tree; each engine build device_puts
                    its own copy.
    k:              draft tokens proposed per round. ``0`` = dormant
                    (every round is the plain decode round, pinned
                    byte-identical to a disarmed engine); ``1`` is
                    rejected — the k-1 acceptance cap makes it pure
                    overhead.
    verify_cost_factor: step-time cost of ONE extra verify column as a
                    fraction of a plain decode step (the HBM-bound
                    argument says ~1/arithmetic-intensity gain; sweep it
                    in benches). Feeds the ``virtual_step_s`` charge and
                    nothing numerical.
    draft_cost_factor: cost of one draft decode step, same unit.
    adaptive:       arm the rolling-α k backoff.
    alpha_window:   rounds per acceptance-rate window (also the dwell
                    after an adjustment — the window refills before the
                    next move).
    alpha_low / alpha_high: back ``k_live`` off one step when the window
                    α falls below ``alpha_low``; regrow one step toward
                    ``k`` above ``alpha_high`` (the hysteresis band).
    k_min:          adaptive floor (>= 2: the acceptance cap needs k-1
                    >= 1 to ever accept a draft).
    """

    draft_cfg: Any = None
    draft_params: Any = None
    k: int = 4
    verify_cost_factor: float = 0.0625
    draft_cost_factor: float = 0.125
    adaptive: bool = False
    alpha_window: int = 32
    alpha_low: float = 0.35
    alpha_high: float = 0.7
    k_min: int = 2

    def validate(self) -> "SpecDecodeConfig":
        if self.k < 0:
            raise ValueError(f"k must be >= 0, got {self.k}")
        if self.k == 1:
            raise ValueError(
                "k=1 cannot accept a draft under the k-1 cap (pure "
                "verify overhead) — use k=0 (dormant) or k >= 2"
            )
        if self.k >= 2 and (self.draft_cfg is None
                            or self.draft_params is None):
            raise ValueError("k >= 2 needs draft_cfg and draft_params")
        for name in ("verify_cost_factor", "draft_cost_factor"):
            if getattr(self, name) < 0.0:
                raise ValueError(f"{name} must be >= 0")
        if self.alpha_window < 1:
            raise ValueError("alpha_window must be >= 1")
        if not 0.0 <= self.alpha_low < self.alpha_high <= 1.0:
            raise ValueError(
                f"need 0 <= alpha_low < alpha_high <= 1 (the hysteresis "
                f"band), got {self.alpha_low} / {self.alpha_high}"
            )
        if self.k_min < 2:
            raise ValueError("k_min must be >= 2 (the k-1 cap floor)")
        if self.k >= 2 and self.k_min > self.k:
            raise ValueError(f"k_min={self.k_min} must be <= k={self.k}")
        return self


class SpeculativeBatcher(ContinuousBatcher):
    """:class:`~triton_dist_tpu.models.decode.ContinuousBatcher` whose
    decode round is a draft→verify→per-slot-accept round. Admission,
    chunked prefill, the prefix cache, poison quarantine, struck-page
    fan-out and replay export are all inherited unchanged — only
    ``step``'s decode half is replaced, and only when some slot is in a
    speculation-eligible state (otherwise the inherited plain round runs
    at plain cost)."""

    def __init__(self, cfg, params, mesh, *, s_max, spec_decode, **kw):
        px_cfg = kw.get("prefix_cache")
        if cfg.cache_kind == "latent":
            raise NotImplementedError(
                "speculative decoding is not built for the latent cache "
                "kind (LatentPagedCacheSpec): its verify step reads k/v pools")
        if cfg.cache_kind == "kv_window":
            refuse_ring("speculative decoding (its verify step)")
        if cfg.cache_kind in STATE_CACHE_KINDS:
            refuse_state("speculative decoding (its verify step and the "
                         "roll-back of rejected drafts)", cfg.cache_kind)
        if kw.pop("lookahead", False):
            raise NotImplementedError(
                "lookahead sends the plain step ahead of its round; a "
                "draft+verify round decides its inputs from the tokens it "
                "pulls: pass one or the other")
        super().__init__(cfg, params, mesh, s_max=s_max, lookahead=False,
                         **kw)
        sd = spec_decode.validate()
        self.spec_decode = sd
        self.k_live = sd.k
        # the engine multiplies virtual_step_s by this after each step():
        # 1.0 for a plain round, the speculative cost model otherwise
        self.last_step_units = 1.0
        # per-round per-slot acceptance readout (tests / divergence
        # audits): {slot: accepted_count} for the LAST speculative round
        self.last_accepts: dict[int, int] = {}
        self.spec_rounds = 0
        self.spec_tokens_offered = 0     # (k_live - 1) per speculating slot
        self.spec_tokens_accepted = 0    # accepted drafts
        self.spec_rollback_total = 0     # offered - accepted
        self.spec_bonus_total = 0        # bonus/residual tokens emitted
        self.spec_k_transitions: list[tuple[int, int, float]] = []
        self.spec_draft_faults_injected = 0
        # chaos seam (resilience/soak.py speculative campaign): sticky
        # until a speculative round actually consumes it, so a fault
        # scheduled on a round with no eligible slot still fires
        self.corrupt_draft_next = False
        self.on_k_change: Callable | None = None
        self._alpha_win: deque = deque(maxlen=sd.alpha_window)
        self._spec_armed = sd.k >= 2
        b = cfg.batch
        # positions [0, _draft_pos[i]) hold valid draft KV for slot i's
        # CURRENT request (identity-tracked via _draft_owner)
        self._draft_pos = np.zeros(b, np.int32)
        self._draft_owner: list[Any] = [None] * b
        self._draft_px = None
        self._draft_px_dirty = False
        if not self._spec_armed:
            return                      # dormant: no draft machinery
        dcfg = sd.draft_cfg
        if dcfg.vocab != cfg.vocab or dcfg.batch != cfg.batch:
            raise ValueError(
                f"draft must share vocab and batch with the target, got "
                f"vocab {dcfg.vocab}/{cfg.vocab} batch "
                f"{dcfg.batch}/{cfg.batch}"
            )
        if self._n_o > 1 or _mesh_outer(dcfg, mesh) > 1:
            raise ValueError(
                "speculative serving supports flat (1-axis) meshes: a "
                "hierarchical deployment shards its batch per outer "
                "group and the per-slot ragged draft roll has no "
                "per-group owner there"
            )
        n = mesh.shape[dcfg.axis]
        if isinstance(self.spec, PagedKVCacheSpec):
            dspec = PagedKVCacheSpec(
                s_max, self.spec.page_size, static_table=True,
                extra_pages=self.spec.extra_pages,
            )
        else:
            dspec = KVCacheSpec(s_max)
        self._draft_spec = dspec
        self._draft_cache = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            dspec.init(dcfg, n, 1), dspec.specs(dcfg),
        )
        self._draft_params = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            sd.draft_params, specs_for(dcfg, sd.draft_params),
        )
        from triton_dist_tpu.ops.common import jit_shard_map

        dstep = functools.partial(
            decode_step, dcfg, spec=dspec, fd_config=None,
            interpret=self._interpret,
        )
        self._draft_step = jit_shard_map(
            dstep, mesh,
            (
                specs_for(dcfg, sd.draft_params), dspec.specs(dcfg),
                P(None), P(None),
            ),
            (P(None, None), dspec.specs(dcfg)),
            key=("spec_draft_step", dcfg, dspec, str(self._interpret)),
            donate_argnums=(1,),
        )
        self._draft_ranged_progs: dict[int, Any] = {}
        if self._px is not None:
            # the draft's own MIRROR of the prefix trie (ISSUE 20): page
            # chains name DRAFT pool pages, so the trie structure is
            # shared-by-construction (same config, same geometry) while
            # the physical pages stay per-model. Divergent hit depths
            # between the two tries are harmless — each cache is
            # self-consistent.
            from triton_dist_tpu.models.prefix_cache import PagePrefixCache

            self._draft_px = PagePrefixCache(
                px_cfg, n_slots=b, page=self.spec.page_size,
                pps_local=(s_max // n) // self.spec.page_size, n_pes=n,
            )
            self._draft_px_dirty = True

    # -- draft-side plumbing --------------------------------------------

    def _draft_ranged_prog(self, bucket: int):
        """Jitted draft-side twin of ``_ranged_prog`` (per-slot catch-up
        ingestion): same parked-row discipline, draft cfg/spec/params."""
        if bucket in self._draft_ranged_progs:
            return self._draft_ranged_progs[bucket]
        dcfg, dspec = self.spec_decode.draft_cfg, self._draft_spec

        def fn(params, cache, tokens, pos0):
            return prefill_cache_ranged(
                dcfg, params, cache, tokens, pos0, spec=dspec,
                fd_config=None, interpret=self._interpret,
            )

        from triton_dist_tpu.ops.common import jit_shard_map

        prog = jit_shard_map(
            fn, self.mesh,
            (
                specs_for(dcfg, self.spec_decode.draft_params),
                dspec.specs(dcfg), P(None, None), P(None),
            ),
            (P(None, None, None), dspec.specs(dcfg)),
            key=(
                "spec_draft_ranged", dcfg, dspec, bucket,
                str(self._interpret),
            ),
            donate_argnums=(1,),
        )
        self._draft_ranged_progs[bucket] = prog
        return prog

    def _push_draft_px_table(self) -> None:
        self._draft_cache = dict(
            self._draft_cache,
            block_table=jax.device_put(
                jnp.asarray(self._draft_px.table),
                NamedSharding(
                    self.mesh,
                    self._draft_spec.specs(
                        self.spec_decode.draft_cfg
                    )["block_table"],
                ),
            ),
        )
        self._draft_px_dirty = False

    def _input_at(self, i: int, j: int) -> int:
        """The token fed at position ``j`` of slot ``i``'s stream —
        prompt token or generated token (the draft catch-up's history;
        how the TARGET admitted the slot — token feed, bucket prefill,
        trie hit — is irrelevant, the inputs are the inputs)."""
        req = self.slot_req[i]
        L = len(req.prompt)
        return int(req.prompt[j]) if j < L else int(self.slot_out[i][j - L])

    def _reconcile_draft_slots(self) -> None:
        """Release draft-side state of slots whose request finished, was
        evicted (poison/strike — draft pages are released WITHOUT a
        strike: the poison was the TARGET's logits, the draft trie holds
        no corrupt data), or was replaced by a new admission."""
        for i in range(self.cfg.batch):
            if (self._draft_owner[i] is not None
                    and self._draft_owner[i] is not self.slot_req[i]):
                self._draft_owner[i] = None
                self._draft_pos[i] = 0
                if self._draft_px is not None:
                    self._draft_px.release(i)
                    self._draft_px_dirty = True

    def _draft_catchup(self, i: int, lo: int, hi: int) -> int:
        """Ingest slot ``i``'s input history over positions ``[lo, hi)``
        into the draft cache in one ranged pass (neighbor rows parked at
        ``pos0 = s_max``). Returns the padded column count (the cost
        model charges it at draft rate)."""
        req = self.slot_req[i]
        S = hi - lo
        bucket = 1
        while bucket < S:
            bucket *= 2
        tokens = np.zeros((self.cfg.batch, bucket), np.int32)
        tokens[i, :S] = [self._input_at(i, j) for j in range(lo, hi)]
        pos0 = np.full(self.cfg.batch, self.s_max, np.int32)
        pos0[i] = lo
        if self._draft_px is not None and self._draft_px_dirty:
            self._push_draft_px_table()
        _, self._draft_cache = self._draft_ranged_prog(bucket)(
            self._draft_params, self._draft_cache,
            jnp.asarray(tokens), jnp.asarray(pos0),
        )
        if self._draft_px is not None:
            # publish-on-completion, batch form (mirrors _ranged_pass):
            # prompt pages fully covered by [0, hi) enter the draft trie
            pg = self._draft_px.page
            while True:
                g = self._draft_px.next_publish(i)
                if (g + 1) * pg > hi or (g + 1) * pg > len(req.prompt):
                    break
                if self._draft_px.publish(
                    i, g, req.prompt[g * pg:(g + 1) * pg]
                ):
                    self._draft_px_dirty = True
        return bucket

    # -- the speculative round ------------------------------------------

    def step(self) -> None:
        """One serving round: admission + chunked prefill (inherited),
        then EITHER the plain decode round (no eligible slot, or
        dormant) or one draft-roll → batched-verify → per-slot-accept
        round."""
        self._admit()
        if self.idle:
            self.last_step_units = 1.0
            return
        self._chunk_pass()
        if self._spec_armed:
            self._reconcile_draft_slots()
        k = self.k_live
        spec: list[int] = []
        if self._spec_armed:
            for i, req in enumerate(self.slot_req):
                if req is None or i in self._chunk:
                    continue
                # eligible = generating (prompt fully fed) with room for
                # the k-column draft roll below s_max (the ragged draft
                # positions must stay real — junk draft logits would
                # poison sampled proposals)
                if (self.slot_fed[i] >= len(req.prompt)
                        and int(self.pos[i]) + k + 1 <= self.s_max):
                    spec.append(i)
        if not spec:
            self._decode_round()
            self.last_accepts = {}
            self.last_step_units = 1.0
            return
        self.rounds += 1
        with _span("tdt.batcher.decode_round", round=self.rounds,
                   kind="spec") as sp:
            self._spec_round(spec, k, sp)

    def _spec_round(self, spec: list[int], k: int, sp) -> None:
        sd = self.spec_decode
        b = self.cfg.batch
        catchup_cols = 0
        for i in spec:
            req = self.slot_req[i]
            if self._draft_owner[i] is not req:
                lo = 0
                if self._draft_px is not None:
                    lo = self._draft_px.acquire(
                        i, req.prompt, req.max_new_tokens
                    )
                    self._draft_px_dirty = True
                self._draft_owner[i] = req
                self._draft_pos[i] = lo
            if self._draft_pos[i] < self.pos[i]:
                catchup_cols += self._draft_catchup(
                    i, int(self._draft_pos[i]), int(self.pos[i])
                )
                self._draft_pos[i] = self.pos[i]

        # -- draft roll: k ragged draft decode steps ---------------------
        spec_set = set(spec)
        sampled = {
            i for i in spec if self.slot_req[i].temperature > 0.0
        }
        tok_d = np.zeros(b, np.int32)
        pos_d = np.full(b, self.s_max, np.int32)   # parked: writes drop
        for i in spec:
            tok_d[i] = self.tok[i]
            pos_d[i] = self.pos[i]
        drafts = np.zeros((b, k), np.int32)
        # per sampled slot, the draft's proposal distributions q_1..q_k
        # (rejection sampling needs the full vector for the residual)
        q_dists: dict[int, list] = {i: [] for i in sampled}
        if self._draft_px is not None and self._draft_px_dirty:
            self._push_draft_px_table()
        cur = tok_d
        for j in range(k):
            lg, self._draft_cache = self._draft_step(
                self._draft_params, self._draft_cache,
                jnp.asarray(cur), jnp.asarray(pos_d + j),
            )
            nxt = np.asarray(jnp.argmax(lg, axis=-1), np.int32)
            lg_h = np.asarray(lg, np.float32) if sampled else None
            cur = np.zeros(b, np.int32)
            for i in spec:
                req = self.slot_req[i]
                if i in sampled:
                    # the draft PROPOSES by sampling its own dist on the
                    # slot's RNG (draw 1..k of the round's fixed order)
                    q = req.dist(lg_h[i])
                    q_dists[i].append(q)
                    d = int(self.slot_rng[i].choice(len(q), p=q))
                else:
                    d = int(nxt[i])
                drafts[i, j] = d
                cur[i] = d

        if self.corrupt_draft_next:
            # chaos seam: flip the first speculating slot's first draft
            # token. The acceptance rule must reject it (unless the
            # corruption lands on the target's own choice — equally
            # correct), keeping the stream byte-identical either way.
            i = spec[0]
            drafts[i, 0] = (int(drafts[i, 0]) + 1) % self.cfg.vocab
            self.corrupt_draft_next = False
            self.spec_draft_faults_injected += 1

        # -- ONE batched verify pass over every occupied slot ------------
        S = k + 1
        bucket = 1
        while bucket < S:
            bucket *= 2
        tokens = np.zeros((b, bucket), np.int32)
        pos0 = np.full(b, self.s_max, np.int32)
        for i, req in enumerate(self.slot_req):
            if req is None or i in self._chunk:
                continue           # idle / chunk-parked: row stays parked
            tokens[i, 0] = self.tok[i]
            pos0[i] = self.pos[i]
            if i in spec_set:
                tokens[i, 1:S] = drafts[i]
        if self._px is not None and self._px_dirty:
            self._push_px_table()
        logits, self.cache = self._ranged_prog(bucket)(
            self.params, self.cache, jnp.asarray(tokens), jnp.asarray(pos0)
        )
        from triton_dist_tpu.resilience import integrity as _integrity

        fin = (
            np.asarray(jnp.all(jnp.isfinite(logits), axis=-1))
            if _integrity.output_checks_enabled() else None
        )
        preds = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        # full logits transfer only when some consuming slot samples
        # (mirrors the base decode round's lazy [b, vocab] transfer)
        need_h = any(
            r is not None and r.temperature > 0.0
            and i not in self._chunk
            and self.slot_fed[i] >= len(r.prompt)
            for i, r in enumerate(self.slot_req)
        )
        logits_h = np.asarray(logits, np.float32) if need_h else None

        # -- per-slot consume --------------------------------------------
        self.last_accepts = {}
        acc_round = off_round = 0
        live = tokens = finished = 0   # for the round's span
        for i, req in enumerate(self.slot_req):
            if req is None or i in self._chunk:
                continue
            live += 1
            n_cols = S if i in spec_set else 1
            if fin is not None and not fin[i, :n_cols].all():
                self._poison_slot(i, "non-finite logits")
                continue
            if self.slot_fed[i] < len(req.prompt):
                # prompt feed rides verify column 0 (≡ decode_step)
                self.tok[i] = req.prompt[self.slot_fed[i]]
                self.slot_fed[i] += 1
                self.pos[i] += 1
                if self._px is not None:
                    self._publish_step(i, req)
                continue
            if i not in spec_set:
                # plain decode via column 0 — bit-identical to the
                # inherited round (the ranged-prefill pin)
                t = (
                    int(preds[i, 0]) if req.temperature <= 0.0
                    else req.sample(logits_h[i, 0], self.slot_rng[i])
                )
                emitted, a = [t], None
            else:
                emitted, a = self._accept(
                    i, req, drafts[i], preds[i], logits_h,
                    q_dists.get(i), k,
                )
            n_before = len(self.slot_out[i])
            for t in emitted:
                self.slot_out[i].append(t)
                self.tok[i] = t
                if len(self.slot_out[i]) >= req.max_new_tokens or (
                    req.eos_id is not None and t == req.eos_id
                ):
                    finished += 1
                    self.finished.append((req.uid, self.slot_out[i]))
                    self._vacate(i)
                    if self._px is not None:
                        self._px.release(i)
                        self._px_dirty = True
                    break
                self.pos[i] += 1
                if self._px is not None:
                    self._publish_step(i, req)
            n_done = len(self.slot_out[i]) - n_before
            tokens += n_done
            if i in spec_set:
                # accounting is over COMMITTED tokens: EOS/max_new can
                # cut the emitted run short, and counting uncommitted
                # accepts would overstate α into the adaptive loop
                a_done = min(a, n_done)
                self.last_accepts[i] = a_done
                acc_round += a_done
                off_round += k - 1
                self.spec_tokens_accepted += a_done
                self.spec_tokens_offered += k - 1
                self.spec_rollback_total += (k - 1) - a_done
                self.spec_bonus_total += n_done - a_done
            if self.slot_req[i] is req:
                # committed frontier: the draft's rows now equal the
                # accepted inputs (the k-1 cap — no catch-up forward)
                self._draft_pos[i] = self.pos[i]

        self.spec_rounds += 1
        self.last_step_units = (
            1.0 + sd.verify_cost_factor * k + sd.draft_cost_factor * k
            + sd.draft_cost_factor * catchup_cols
        )
        self._note_round(acc_round, off_round)
        sp.set("live", live)
        sp.set("offered", off_round)
        sp.set("accepted", acc_round)
        sp.set("tokens", tokens)
        sp.set("finished", finished)

    def _accept(self, i, req, drafts_i, preds_i, logits_h, q_list, k):
        """Per-slot acceptance: returns ``(emitted_tokens,
        accepted_count)``. Greedy is exact-prefix match against the
        target's argmax chain (the shared ``accept_lengths`` core);
        sampled is seeded rejection sampling — accept ``d_j`` with
        probability ``min(1, p_j(d)/q_j(d))``, emit a residual
        ``max(p-q, 0)`` draw at the first rejection, a bonus ``p`` draw
        when all ``k-1`` acceptable drafts pass."""
        if req.temperature <= 0.0:
            a = int(accept_lengths(
                drafts_i[None, :k], preds_i[None, :], k
            )[0])
            return [int(d) for d in drafts_i[:a]] + [int(preds_i[a])], a
        rng = self.slot_rng[i]
        emitted: list[int] = []
        a = 0
        for j in range(k - 1):
            q = q_list[j]
            p_dist = req.dist(logits_h[i, j])
            d = int(drafts_i[j])
            qd = float(q[d])
            ratio = 1.0 if qd <= 0.0 else min(1.0, float(p_dist[d]) / qd)
            if float(rng.random()) < ratio:
                emitted.append(d)
                a += 1
                continue
            resid = np.maximum(p_dist - q, 0.0)
            s = resid.sum()
            if s > 0.0:
                t = int(rng.choice(len(resid), p=resid / s))
            else:
                # p == q everywhere yet d rejected (measure-zero edge):
                # fall back to the target dist — still target-marginal
                t = int(rng.choice(len(p_dist), p=p_dist))
            emitted.append(t)
            return emitted, a
        p_dist = req.dist(logits_h[i, k - 1])
        emitted.append(int(rng.choice(len(p_dist), p=p_dist)))
        return emitted, a

    def _note_round(self, accepted: int, offered: int) -> None:
        """Fold one round into the rolling-α window and move ``k_live``
        at most one step (adaptive arming only). Public-ish for the
        backoff unit test."""
        sd = self.spec_decode
        self._alpha_win.append((accepted, offered))
        if not sd.adaptive or len(self._alpha_win) < sd.alpha_window:
            return
        off = sum(o for _, o in self._alpha_win)
        alpha = (sum(a for a, _ in self._alpha_win) / off) if off else 1.0
        new_k = self.k_live
        if alpha < sd.alpha_low and self.k_live > sd.k_min:
            new_k = self.k_live - 1
        elif alpha > sd.alpha_high and self.k_live < sd.k:
            new_k = self.k_live + 1
        if new_k == self.k_live:
            return
        old, self.k_live = self.k_live, new_k
        # the cleared window is the dwell: alpha_window fresh rounds at
        # the new k before the next move — no flapping on one bad round
        self._alpha_win.clear()
        self.spec_k_transitions.append((old, new_k, round(alpha, 6)))
        if self.on_k_change is not None:
            self.on_k_change(old, new_k, alpha)

    # -- readout ---------------------------------------------------------

    @property
    def spec_accept_rate(self) -> float | None:
        """Cumulative acceptance rate α (accepted / offered under the
        k-1 cap), or None before the first speculative round."""
        if not self.spec_tokens_offered:
            return None
        return self.spec_tokens_accepted / self.spec_tokens_offered

    def spec_snapshot(self) -> dict:
        rate = self.spec_accept_rate
        return {
            "k": self.spec_decode.k,
            "k_live": self.k_live,
            "rounds": self.spec_rounds,
            "tokens_offered": self.spec_tokens_offered,
            "tokens_accepted": self.spec_tokens_accepted,
            "rollback_total": self.spec_rollback_total,
            "bonus_total": self.spec_bonus_total,
            "accept_rate": None if rate is None else round(rate, 6),
            "k_transitions": len(self.spec_k_transitions),
            "draft_faults_injected": self.spec_draft_faults_injected,
        }
