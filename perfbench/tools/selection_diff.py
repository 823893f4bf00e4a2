#!/usr/bin/env python3
"""How far the program's SELECTION lies from the reference's, counted row
by row at the cell's own size: for every position ``t`` of a few served
requests, the keys of ``S(t)`` the program attended that
``reference.selection`` does not hold (as many of the reference's are then
missing, both sets being ``index_topk`` wide), in each indexed layer, at
the ADMISSION (``ops.sparse_index.selection_mask``) and at every STEP
(``topk_mask`` of the paged index scores).

The program's sets are tapped where it makes them (a host callback on the
traced mask, put on from outside: the program has no such hook), while it
serves through its engine as in a run, one request at a time; the
reference's come from its own plain attention (``taps``), layer by layer
up to the last indexed layer, on the prompt and the served tokens. The
two differ for two reasons the count cannot tell apart: the program
scores index keys in bf16 (the reference in float32), and from the second
indexed layer on its inputs already differ by the layers before. A
selected row that differs moves a head's output by about 1 / index_topk
of a value vector.

    python3 perfbench/tools/selection_diff.py --workload <cell> --seed 7 \\
        --requests 2 --tokens 48
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(HERE), HERE]

from harness import cells, traffic  # noqa: E402


class Taps:
    """The program's selections as they are made: ``admissions`` a list
    of ``(layer, bits [L, L / 8])``, ``steps`` of ``(layer, counts [b],
    bits [b, s / 8])`` (``counts`` = each slot's live rows)."""

    def __init__(self, n_indexed: int):
        self.n, self.calls = n_indexed, 0
        self.admissions, self.steps = [], []

    def install(self):
        import jax
        import jax.numpy as jnp
        from triton_dist_tpu.models import mla_moe
        from triton_dist_tpu.models.decode import LatentPagedCacheSpec
        from triton_dist_tpu.ops import sparse_index

        sound_mask = mla_moe.selection_mask
        sound_step = LatentPagedCacheSpec.write_and_attend

        def admission_mask(*args, **kw):
            # an admission's trace calls this once an indexed layer, in order
            layer, self.calls = self.calls % self.n, self.calls + 1
            keep = sound_mask(*args, **kw)
            jax.debug.callback(
                lambda bits: self.admissions.append((layer, np.asarray(bits))),
                jnp.packbits(keep != 0, axis=-1))
            return keep

        def step(spec, cfg, cache, kind, ki, *args, **kw):
            def tapped(scores, topk):
                keep = sound_topk(scores, topk)
                jax.debug.callback(
                    lambda n, bits: self.steps.append(
                        (ki, np.asarray(n), np.asarray(bits))),
                    jnp.sum(jnp.isfinite(scores), -1, dtype=jnp.int32),
                    jnp.packbits(keep, axis=-1))
                return keep

            # the step looks the selection up when it is traced
            sound_topk, sparse_index.topk_mask = sparse_index.topk_mask, tapped
            try:
                return sound_step(spec, cfg, cache, kind, ki, *args, **kw)
            finally:
                sparse_index.topk_mask = sound_topk

        mla_moe.selection_mask = admission_mask
        LatentPagedCacheSpec.write_and_attend = step
        self._sound = (mla_moe, sound_mask, LatentPagedCacheSpec, sound_step)

    def remove(self):
        """The program as it was (a program traced with the taps on keeps
        them: the caller drops its compiled programs)."""
        mla_moe, sound_mask, spec, sound_step = self._sound
        mla_moe.selection_mask = sound_mask
        spec.write_and_attend = sound_step

    def drain(self):
        import jax

        jax.effects_barrier()
        out = self.admissions, self.steps
        self.admissions, self.steps = [], []
        return out


def reference_selections(reference, sizes: dict, seed: int, tokens) -> list:
    """``[T, T]`` bool an indexed layer, in order: the reference's ``S(t)``
    of one sequence (padded to whole row blocks), the layers run plainly
    up to the last indexed one."""
    import jax
    import jax.numpy as jnp

    m = reference.model()
    last = max(i for i, k in enumerate(m["kinds"]) if m[k]["topk"])
    pad = -len(tokens) % reference.ROW_BLOCK
    key = reference.seed_key(seed)
    outer = reference.outer_weights(key, sizes)
    x = outer["embed"][jnp.asarray(list(tokens) + [0] * pad, jnp.int32)]
    x = x.astype(jnp.float32)

    @functools.partial(jax.jit, static_argnames=("kind",))
    def attend(x, w, kind):
        taps = {}
        h = reference._norm(x, w["attn_norm"], sizes["norm_eps"])
        o = reference.attention(h, w, m[kind], sizes, False, taps)
        return x + o, taps.get("selected")

    out = []
    for li in range(last + 1):
        w = reference.core_weights(key, li, sizes, m["kinds"][li],
                                   reference.is_dense(li))
        x, selected = attend(x, w, kind=m["kinds"][li])
        if selected is not None:
            out.append(np.asarray(selected))
        if li < last:
            x = _mlp_half(reference, x, w, li, key, sizes)
    return out


def _mlp_half(reference, x, w, li: int, key, sizes: dict):
    """The MLP half of layer ``li`` on ``x [T, H]`` (its attention is
    added already), the bank a chunk of experts at a time as
    ``reference.logits`` runs it."""
    import jax.numpy as jnp

    p = reference._programs(tuple(sorted(sizes.items())))
    if reference.is_dense(li):
        return p["run_dense"](x[None], w, control=False)[0]
    first, count = reference.model()["held"]
    h, comb, acc = p["moe_open"](x[None], w, control=False)
    for e0 in range(first, first + count, reference.EXPERT_CHUNK):
        n = min(reference.EXPERT_CHUNK, first + count - e0)
        bank = p["gen_experts"](key, jnp.int32(li), jnp.int32(e0), n=n)
        acc = p["moe_add"](acc, h, comb, jnp.int32(e0), bank, control=False)
    return p["moe_close"](x[None], acc)[0]


def count(prog_bits: np.ndarray, ref_rows: np.ndarray) -> np.ndarray:
    """Keys a row of the program's holds that the reference's does not."""
    width = ref_rows.shape[-1]
    prog = np.unpackbits(prog_bits, axis=-1)[..., :width].astype(bool)
    return (prog & ~ref_rows).sum(-1)


def summary(diffs: list, topk: int) -> dict:
    d = np.concatenate(diffs) if diffs else np.zeros(0, int)
    if not d.size:
        return {"rows": 0}
    return {"rows": int(d.size), "mean": float(d.mean()),
            "p99": float(np.percentile(d, 99)), "max": int(d.max()),
            "rows_that_differ_share": float((d > 0).mean()),
            "mean_share_of_topk": float(d.mean() / topk)}


def main(argv=None, devices=None, bench=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=2)
    ap.add_argument("--tokens", type=int, default=48)
    args = ap.parse_args(argv)
    cell = cells.Cell(bench or cells.benchmark(), args.workload)
    config = cell.config
    import run as bench_run

    used = (devices or bench_run.require_chips)(cell)
    reference, adapter, spec = bench_run.open_cell(cell)
    topk = config["index_topk"]
    n_indexed = sum(k == "full_attention" for k in
                    config["layer_types"][: config["sizes"]["n_layers"]])
    taps = Taps(n_indexed)
    taps.install()
    try:
        reqs = traffic.generate(spec, config["sizes"]["vocab"], args.seed, 1.0)
        reqs = [traffic.Req(r.uid, 0.0, r.prompt, min(args.tokens, r.n_out))
                for r in reqs[: args.requests]]
        system = adapter.System(config, reference, used, args.seed)
        system.warm(reqs)
        taps.drain()
        served = []
        for r in reqs:
            (rec,), _ = system._serve([r], None)
            served.append((r, rec, *taps.drain()))
        system._drop_weights()
    finally:
        taps.remove()
    admit = [[] for _ in range(n_indexed)]
    steps = [[] for _ in range(n_indexed)]
    for r, rec, admissions, step_taps in served:
        n_p = len(r.prompt)
        want = reference_selections(
            reference, config["sizes"], args.seed, list(r.prompt) + list(rec.tokens))
        for layer, bits in admissions:
            rows = count(bits[:n_p], want[layer][:n_p, : bits.shape[1] * 8])
            admit[layer].append(rows[topk:])
        # one request at a time: its slot is the one whose length moves
        # (a slot another request left keeps its length); a step at
        # position t attends [0, t], t + 1 live rows
        if not step_taps:
            continue
        lens = np.stack([counts for _, counts, _ in step_taps])
        slot = int(np.argmax([len(set(col)) for col in lens.T]))
        for layer, counts, bits in step_taps:
            t = int(counts[slot]) - 1
            if n_p <= t < n_p + len(rec.tokens) - 1:
                steps[layer].append(count(
                    bits[slot], want[layer][t, : bits.shape[1] * 8])[None])
    out = {
        "workload": cell.name, "seed": args.seed, "index_topk": topk,
        "requests": [{"prompt": len(r.prompt), "tokens": len(rec.tokens)}
                     for r, rec, _, _ in served],
        "admission_rows_past_topk": [summary(a, topk) for a in admit],
        "step_rows": [summary(s, topk) for s in steps],
    }
    print("SELECTION-DIFF " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
