#!/usr/bin/env python3
"""The quickest proof that the serving path still starts on the chip.

    python chip_smoke.py               # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4     # TP=4 over one four-chip host

One chip: Llama-3.1-8B at its published widths (hidden 4096, ffn 14336,
32 q / 8 kv heads, head_dim 128, vocab 128256, bf16), depth cut to 16 of
32 layers (32 layers are 16.06 GB of weights and do not fit 16 GB of
HBM), random weights from ``--seed``, served through
``serving.ServingEngine`` over the paged continuous batcher with MXU
prefill, on the real clock: 8 requests, prompts of 128-512 tokens, 32
greedy tokens each. The engine's tokens are then checked against a plain
``jax.numpy`` reference (no Pallas, nothing from ``ops/``) that is
teacher-forced on the served sequence: every served token must lie within
``LOGIT_TOL`` of the reference's best logit at its position, and at least
``MIN_EXACT`` of them must be the reference argmax outright (bf16 near-ties
make token-for-token equality over 32 steps a coin the test must not
flip).

Four chips (``--chips 4``) runs ONLY the tensor-parallel phase: the full
32-layer model, TP=4, through the fused ``ag_gemm`` / ``gemm_rs`` /
distributed flash-decode kernels, then the same traffic through the
XLA-collective goldens (``resilience.golden_path()``) TEACHER-FORCED on
the fused engine's tokens: every fused token must lie within ``LOGIT_TOL``
of the golden engine's own best logit at its position (free-running
streams part at the first near-tie and are unrelated afterwards, so their
common prefix says little). Weights are shown spread over the four devices.

Every phase failure is an exception: there is no fallback to the CPU, to
the Pallas interpreter or to an XLA golden in place of a fused kernel
(``fallback_to_xla=False``). The last line of stdout is the contract line
``{"ok": true, "device": {...}}`` and is printed only when all checks
passed. ``--rehearse`` swaps in a toy-sized model so the same code can be
walked on a CPU by the tests (which stub the device check); on a chip it
weakens nothing.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

# a served token may trail the reference's best logit by at most this much
# (logits of the random-weight model are ~N(0, 1); a wrong position, mask
# or page lands ~4 away, bf16 reordering noise ~0.05)
LOGIT_TOL = 0.25
# ... and this share of served tokens must be the reference argmax itself
MIN_EXACT = 0.70
# the golden engine, teacher-forced on the fused engine's tokens, holds
# them to the same two limits (its logits in place of the reference's)

FULL = dict(
    s_max=2048, page=128, slots=8, n_req=8, prompt_lo=128, prompt_hi=512,
    new_tokens=32, layers={1: 16, 4: 32},
)
TOY = dict(   # the least that walks every phase: the answers cross one round
    s_max=32, page=8, slots=2, n_req=2, prompt_lo=6, prompt_hi=8,
    new_tokens=2, layers={1: 1, 4: 1},
)


def require_chip(n_chips: int) -> dict:
    """(a) of the contract: a TPU, enough of them, kernels compiled. Any
    backend error rises as it is."""
    import jax

    from triton_dist_tpu import config as tdt_config

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU (jax.devices()[0].platform="
            f"{devs[0].platform!r}); this smoke runs on the chip only"
        )
    if len(devs) < n_chips:
        raise SystemExit(
            f"chip_smoke: --chips {n_chips} needs {n_chips} TPU devices, "
            f"found {len(devs)}"
        )
    if tdt_config.interpreting():
        raise SystemExit("chip_smoke: kernels resolve to interpret mode")
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def assert_kernels_lowered(text: str, what: str) -> None:
    """(c): the Pallas kernels are in the program, not an XLA stand-in."""
    if "tpu_custom_call" not in text:
        raise AssertionError(f"{what}: no tpu_custom_call in the lowered program")


def model_config(size: dict, n_chips: int, rehearse: bool):
    import jax.numpy as jnp

    from triton_dist_tpu.models import presets
    from triton_dist_tpu.models.tp_transformer import TransformerConfig

    n_layers = size["layers"][n_chips]
    if rehearse:
        return TransformerConfig(
            vocab=256, hidden=64, ffn=128, n_layers=n_layers, n_q_heads=8,
            n_kv_heads=4, head_dim=16, batch=size["slots"], seq=8,
            dtype=jnp.float32,
        )
    cfg = presets.preset(
        "llama-3.1-8b", batch=size["slots"], seq=8, n_layers=n_layers,
        tp_check=n_chips,
    )
    if n_layers != 32:
        print(
            f"[smoke] depth cut: {n_layers} of 32 layers (32 layers of "
            f"bf16 weights are 16.06 GB; one v5e chip has 16 GB)", flush=True,
        )
    return cfg


def make_requests(size: dict, vocab: int, seed: int):
    import numpy as np

    from triton_dist_tpu.models.decode import Request

    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(size["n_req"]):
        n = int(rng.integers(size["prompt_lo"], size["prompt_hi"] + 1))
        reqs.append(Request(
            [int(t) for t in rng.integers(0, vocab, n)],
            max_new_tokens=size["new_tokens"], uid=f"req{i}",
        ))
    return reqs


def forced_requests(reqs, tokens: dict) -> tuple[list, dict]:
    """The same requests with the token choice taken out of the engine's
    hands — teacher forcing through the engine's own sampling seam. A
    request that samples (``temperature > 0``) is handed its slot's f32
    logit row at every position (``Request.sample``); these record how far
    the given token trails the row's best logit and what the row's argmax
    is, then answer with the given token. The engine and its programs are
    exactly those of the free-running serve. Returns the requests and the
    ``(gap, argmax)`` records by uid."""
    from triton_dist_tpu.models.decode import Request

    scores = {r.uid: [] for r in reqs}

    class Forced(Request):
        def sample(self, logits, rng) -> int:
            mine = scores[self.uid]
            t = tokens[self.uid][len(mine)]
            mine.append((float(logits.max() - logits[t]), int(logits.argmax())))
            return t

    forced = [
        Forced(r.prompt, r.max_new_tokens, temperature=1.0, uid=r.uid)
        for r in reqs
    ]
    return forced, scores


_cache_events: dict = {}


def compile_cache_counts() -> dict:
    """Persistent-compile-cache lookups and hits of this process so far
    (JAX's own monitoring events): says whether a "compile included" time
    was a compile or a read."""
    if not _cache_events:
        from jax import monitoring

        _cache_events.update(requests=0, hits=0)
        names = {
            "/jax/compilation_cache/compile_requests_use_cache": "requests",
            "/jax/compilation_cache/cache_hits": "hits",
        }

        def count(event, **_):
            if event in names:
                _cache_events[names[event]] += 1

        monitoring.register_event_listener(count)
    return dict(_cache_events)


def _abstract(tree):
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        tree,
    )


def lowered_programs(batcher) -> dict:
    """StableHLO text of the batcher's decode step and of every prefill
    bucket it compiled — what (c) and the four-chip collective census read."""
    import jax
    import jax.numpy as jnp

    b = batcher.cfg.batch
    params, cache = _abstract(batcher.params), _abstract(batcher.cache)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    out = {
        "step": batcher._step.jitted.lower(
            params, cache, i32(b), i32(b)
        ).as_text()
    }
    # the group as the program takes it: slots, pick and the members'
    # count where it walks them (one chip), a mask and pick where it runs
    # the whole batch masked
    group = ((i32(b), i32(b), i32()) if batcher._walks_members
             else (jax.ShapeDtypeStruct((b,), jnp.bool_), i32(b)))
    for bucket, prog in sorted(batcher._prefill_progs.items()):
        out[f"prefill{bucket}"] = prog.jitted.lower(
            params, cache, i32(b, bucket), *group).as_text()
    return out


def serve(cfg, params, mesh, size: dict, reqs) -> tuple[dict, dict, dict]:
    """Phase: the main path. Build the engine, serve `reqs`, return
    ``(tokens by uid, stats, lowered programs)``."""
    import jax

    from triton_dist_tpu.serving import ServingConfig, ServingEngine

    cache0 = compile_cache_counts()
    t0 = time.perf_counter()
    eng = ServingEngine(
        cfg, params, mesh, s_max=size["s_max"], page_size=size["page"],
        prefill=True, serving=ServingConfig(max_queue=len(reqs)),
    )
    for r in reqs:
        uid = eng.submit(r)
        if uid != r.uid:
            raise AssertionError(f"request {r.uid} not enqueued: {uid!r}")
    t1 = time.perf_counter()
    results = eng.run_until_idle()
    jax.block_until_ready(eng._batcher.cache)
    t2 = time.perf_counter()
    missing = [r.uid for r in reqs if r.uid not in results]
    if missing:
        raise AssertionError(f"requests without a Finished result: {missing}")
    tokens = {r.uid: list(results[r.uid].tokens) for r in reqs}
    for r in reqs:
        if len(tokens[r.uid]) != r.max_new_tokens:
            raise AssertionError(
                f"{r.uid}: {len(tokens[r.uid])} tokens, "
                f"wanted {r.max_new_tokens}"
            )
    n_tok = sum(len(t) for t in tokens.values())
    stats = dict(
        build_s=t1 - t0, serve_s=t2 - t1, requests=len(reqs), tokens=n_tok,
        buckets=sorted(eng._batcher._prefill_progs),
        compile_cache={
            k: n - cache0[k] for k, n in compile_cache_counts().items()
        },
    )
    programs = lowered_programs(eng._batcher)
    return tokens, stats, programs


def reference_logits(cfg, params, tokens, prompt_lens, n_new: int):
    """Plain ``jax.numpy`` decoder forward (no Pallas, nothing from
    ``ops/``): weights as stored, f32 accumulation and residual. ``tokens``
    is ``[b, T]`` (prompt + served tokens, zero-padded); returns the f32
    logits at the ``n_new`` positions per row that predict the served
    tokens, ``[b, n_new, V]``."""
    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.models.tp_transformer import unpack_gate_up

    c = cfg
    g, d = c.n_q_heads // c.n_kv_heads, c.head_dim

    def norm(x, w):
        r = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + c.norm_eps)
        return (x * r).astype(c.dtype) * w

    def mm(x, w):
        return jnp.dot(x.astype(c.dtype), w, preferred_element_type=jnp.float32)

    def rope(x, pos):  # x [b, T, h, d]
        freqs = c.rope_theta ** (-jnp.arange(0, d, 2, jnp.float32) / d)
        ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
        cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1
        ).astype(c.dtype)

    def forward(params, tokens, last):
        b, t = tokens.shape
        pos = jnp.arange(t, dtype=jnp.int32)
        causal = pos[None, :] <= pos[:, None]
        x = params["embed"][tokens].astype(jnp.float32)          # [b, T, H]
        for p in params["layers"]:
            h = norm(x, p["attn_norm"])
            qkv = mm(h, p["wqkv"]).astype(c.dtype)   # kv-group-major columns
            qkv = qkv.reshape(b, t, c.n_kv_heads, g + 2, d)
            q = rope(qkv[..., :g, :].reshape(b, t, c.n_q_heads, d), pos)
            k = rope(qkv[..., g, :], pos)
            v = qkv[..., g + 1, :]
            qg = q.reshape(b, t, c.n_kv_heads, g, d)
            s = jnp.einsum(
                "bshgd,bthd->bhgst", qg, k,
                preferred_element_type=jnp.float32,
            ) / jnp.sqrt(jnp.float32(d))
            s = jnp.where(causal[None, None, None], s, -jnp.inf)
            a = jnp.einsum(
                "bhgst,bthd->bshgd", jax.nn.softmax(s, -1).astype(c.dtype), v,
                preferred_element_type=jnp.float32,
            ).reshape(b, t, c.q_dim)
            x = x + mm(a, p["wo"])
            h = norm(x, p["mlp_norm"])
            w_gate, w_up = unpack_gate_up(p["w_gate_up"], c)
            x = x + mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), p["w_down"])
        # only the positions that predict a served token reach the vocab
        idx = last[:, None] + jnp.arange(n_new, dtype=jnp.int32)[None, :]
        xs = jnp.take_along_axis(x, idx[:, :, None], axis=1)
        return mm(norm(xs, params["final_norm"]), params["lm_head"])

    last = jnp.asarray([n - 1 for n in prompt_lens], jnp.int32)
    return jax.jit(forward)(params, tokens, last)


def reference_scores(cfg, params, reqs, tokens: dict):
    """The plain reference's logits ``[b, n_new, V]`` at the positions
    that predict each request's served tokens, teacher-forced on them."""
    import numpy as np

    n_new = reqs[0].max_new_tokens
    t_pad = max(len(r.prompt) for r in reqs) + n_new
    t_pad = -(-t_pad // 64) * 64
    toks = np.zeros((len(reqs), t_pad), np.int32)
    for i, r in enumerate(reqs):
        seq = list(r.prompt) + list(tokens[r.uid])
        toks[i, : len(seq)] = seq
    logits = np.asarray(reference_logits(
        cfg, params, toks, [len(r.prompt) for r in reqs], n_new,
    ))                                                     # [b, n_new, V]
    if not np.isfinite(logits).all():
        raise AssertionError("reference logits are not finite")
    return logits


def hold_to_limits(gap, exact, reqs, label: str, judge: str) -> None:
    """``gap [b, n_new]``: how far each judged token trails the judge's
    best logit; ``exact [b, n_new]``: whether it is the judge's argmax."""
    import numpy as np

    out = dict(
        max_gap=float(gap.max()), exact=float(exact.mean()), tol=LOGIT_TOL,
    )
    print(f"[smoke] {label} vs {judge}: {json.dumps(out)}", flush=True)
    if gap.max() > LOGIT_TOL:
        i, j = np.unravel_index(gap.argmax(), gap.shape)
        raise AssertionError(
            f"{label}: {reqs[i].uid} token {j} trails the best logit of "
            f"{judge} by {gap[i, j]:.3f} > {LOGIT_TOL}"
        )
    if exact.mean() < MIN_EXACT:
        raise AssertionError(
            f"{label}: only {exact.mean():.2%} of the tokens are the argmax "
            f"of {judge} (< {MIN_EXACT:.0%})"
        )


def check_against_reference(logits, reqs, served: dict, label: str) -> None:
    """(b): ``served`` tokens against the plain reference's ``logits``
    (:func:`reference_scores` of the sequence they were served on)."""
    import numpy as np

    toks = np.array([served[r.uid] for r in reqs])          # [b, n_new]
    got = np.take_along_axis(logits, toks[..., None], -1)[..., 0]
    hold_to_limits(
        logits.max(-1) - got, logits.argmax(-1) == toks, reqs, label,
        "the plain reference",
    )


def check_health(label: str) -> None:
    """(d): nothing was downgraded, pinned to a golden, or timed out."""
    from triton_dist_tpu.resilience import health

    snap = health.snapshot()
    bad = {
        k: n for k, n in snap["counters"].items()
        if n and k.rsplit(":", 1)[-1] in health.FLIP_KINDS
    }
    if bad or snap["short_circuited"] or not snap["healthy"]:
        raise AssertionError(f"{label}: health is not clean: {json.dumps(snap)}")
    print(f"[smoke] {label} health clean: {json.dumps(snap['counters'])}",
          flush=True)


def memory_report(devices, label: str) -> list[dict]:
    out = []
    for dev in devices:
        ms = dev.memory_stats() or {}
        out.append(dict(
            id=dev.id, bytes_in_use=ms.get("bytes_in_use"),
            peak_bytes_in_use=ms.get("peak_bytes_in_use"),
        ))
    print(f"[smoke] {label} memory: {json.dumps(out)}", flush=True)
    return out


def _census(text: str) -> dict:
    return dict(
        custom_calls=text.count("tpu_custom_call"),
        all_gather=text.count("stablehlo.all_gather"),
        reduce_scatter=text.count("stablehlo.reduce_scatter"),
    )


def build_model(args, size: dict, n_chips: int):
    """Config, a 1-D mesh over the first ``n_chips`` devices, and the
    parameters born sharded on it (``init_params(..., mesh)``)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from triton_dist_tpu.models import init_params

    cfg = model_config(size, n_chips, args.rehearse)
    mesh = Mesh(np.array(jax.devices()[:n_chips]), (cfg.axis,))
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        init_params(jax.random.PRNGKey(args.seed), cfg, mesh)
    )
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"[smoke] params: {n_bytes / 1e9:.2f} GB (global) in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    return cfg, mesh, params, n_bytes


def run_one_chip(args, size) -> None:
    cfg, mesh, params, _ = build_model(args, size, 1)
    reqs = make_requests(size, cfg.vocab, args.seed)
    tokens, stats, programs = serve(cfg, params, mesh, size, reqs)
    print(f"[smoke] served (compile included): {json.dumps(stats)}", flush=True)
    for name, text in programs.items():
        assert_kernels_lowered(text, name)
        print(f"[smoke] program {name}: {json.dumps(_census(text))}", flush=True)
    # the same traffic again: every program is compiled now, so this is
    # the serving time without compilation (still one cold engine build)
    tokens2, stats2, _ = serve(cfg, params, mesh, size, reqs)
    print(f"[smoke] served again (warm): {json.dumps(stats2)}", flush=True)
    if tokens2 != tokens:
        raise AssertionError("the same traffic served twice gave other tokens")
    check_against_reference(
        reference_scores(cfg, params, reqs, tokens), reqs, tokens, "engine"
    )
    check_health("one chip")
    memory_report(list(mesh.devices.flat), "one chip")


def run_four_chips(args, size) -> None:
    import numpy as np

    from triton_dist_tpu import resilience

    cfg, mesh, params, n_bytes = build_model(args, size, 4)
    devices = list(mesh.devices.flat)
    mem = memory_report(devices, "after parameter placement")
    if all(m["bytes_in_use"] is not None for m in mem):
        used = [m["bytes_in_use"] for m in mem]
        # a quarter of the sharded weights each, plus the replicated
        # embedding and norms: well under half the tree, and even
        if max(used) > 0.40 * n_bytes or max(used) > 1.05 * min(used):
            raise AssertionError(
                f"weights are not spread over the four devices: {used} of "
                f"{n_bytes} bytes"
            )
    elif not args.rehearse:
        raise AssertionError("memory_stats() reports no bytes_in_use")
    reqs = make_requests(size, cfg.vocab, args.seed)

    tokens, stats, programs = serve(cfg, params, mesh, size, reqs)
    print(f"[smoke] fused TP=4 served (compile included): {json.dumps(stats)}",
          flush=True)
    check_health("fused TP=4")
    tokens2, stats2, _ = serve(cfg, params, mesh, size, reqs)
    print(f"[smoke] fused TP=4 served again (warm): {json.dumps(stats2)}",
          flush=True)
    if tokens2 != tokens:
        raise AssertionError("the same traffic served twice gave other tokens")
    # the same engine over the XLA-collective goldens, teacher-forced on
    # the fused tokens
    forced, scores = forced_requests(reqs, tokens)
    with resilience.golden_path():
        g_tokens, g_stats, g_programs = serve(cfg, params, mesh, size, forced)
    print(f"[smoke] golden TP=4 served (compile included, teacher-forced): "
          f"{json.dumps(g_stats)}", flush=True)
    if g_tokens != tokens:
        raise AssertionError("the golden engine was not fed the fused tokens")

    # the fused programs hold the kernels, and no XLA collective stands
    # where a fused kernel should be: against the golden twin of the same
    # program, prefill loses one all-gather per column-parallel projection
    # (qkv and gate/up per layer, the LM head) and every reduce-scatter
    # (wo and w_down per layer); the decode step loses the flash-decode
    # combine's all-gather per layer
    n_l = cfg.n_layers
    for name, text in programs.items():
        assert_kernels_lowered(text, f"fused {name}")
        fused, gold = _census(text), _census(g_programs[name])
        print(f"[smoke] program {name}: fused {json.dumps(fused)} "
              f"golden {json.dumps(gold)}", flush=True)
        if name == "step":
            want = dict(all_gather=gold["all_gather"] - n_l, reduce_scatter=0)
        else:
            want = dict(
                all_gather=gold["all_gather"] - (2 * n_l + 1), reduce_scatter=0,
            )
            if gold["reduce_scatter"] != 2 * n_l:
                raise AssertionError(
                    f"golden {name}: {gold['reduce_scatter']} reduce-scatters, "
                    f"expected {2 * n_l}"
                )
        for k, v in want.items():
            if fused[k] != v:
                raise AssertionError(
                    f"fused {name}: {fused[k]} XLA {k} ops, expected {v} "
                    f"(golden has {gold[k]})"
                )

    # fused against golden, position by position on the same sequence: the
    # golden engine's own logits judge the fused tokens ...
    scores = np.array([scores[r.uid] for r in reqs])        # [b, n_new, 2]
    if scores.shape != (len(reqs), reqs[0].max_new_tokens, 2):
        raise AssertionError(f"golden engine scored {scores.shape} positions")
    g_argmax = scores[..., 1].astype(np.int64)
    fused = np.array([tokens[r.uid] for r in reqs])
    hold_to_limits(
        scores[..., 0], g_argmax == fused, reqs, "fused TP=4 tokens",
        "the golden TP=4 engine (teacher-forced)",
    )
    # ... and the plain reference judges both: the fused tokens, and what
    # the golden engine would have picked at each of the same positions
    logits = reference_scores(cfg, params, reqs, tokens)
    check_against_reference(logits, reqs, tokens, "fused TP=4")
    check_against_reference(
        logits, reqs, {r.uid: g_argmax[i] for i, r in enumerate(reqs)},
        "golden TP=4 (argmax on the fused sequence)",
    )
    memory_report(devices, "four chips")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--rehearse", action="store_true",
        help="toy-sized model through the same code (for the CPU tests)",
    )
    args = ap.parse_args(argv)

    device = require_chip(args.chips)
    from triton_dist_tpu import config as tdt_config

    # loud: a fused kernel that cannot build ends the run
    tdt_config.update(fallback_to_xla=False)
    print(f"[smoke] device: {json.dumps(device)}; compile cache: "
          f"{tdt_config.compile_cache_dir()}", flush=True)
    size = TOY if args.rehearse else FULL
    t0 = time.perf_counter()
    (run_four_chips if args.chips == 4 else run_one_chip)(args, size)
    print(f"[smoke] wall: {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
