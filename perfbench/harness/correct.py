"""The comparison that decides ``correct``: what the timed window served,
held against the plain reference.

Once the window has closed (and the program's state is freed), a sample
of the requests it finished is drawn from the seed, the longest among
them. The reference runs once over each sampled prompt with its served
tokens, and every served token's logit is compared with the reference's
best at its position. Compared numbers, each with a limit of its own:

``max_gap``       the widest such gap (limit: the configuration's file)
``mean_gap``      the mean gap (same source), steadier than the widest
``failed``        requests of the window that did not finish whole (0)
``health_flips``  kernels the program served by a fallback (0)
"""

from __future__ import annotations

import math

import numpy as np

from harness import traffic


def sample(records: list, seed: int, n: int) -> list:
    """``n`` finished requests: the longest, and the rest by the seed."""
    done = [r for r in records if r.ok]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.n_prompt + len(r.tokens), r.uid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    picks = [rest[i] for i in rng.permutation(len(rest))[: n - 1]]
    return [longest] + picks


def shape(spec: dict, n: int) -> tuple[int, int, int]:
    """``(n, T, n_new)``, the same for every seed of a cell, so that the
    reference's programs are compiled once per checkout."""
    n_new = traffic.max_len(spec["output_len"])
    longest = traffic.max_len(spec["prompt_len"]) + n_new
    return n, -(-longest // 128) * 128, n_new


def judge(reference, sizes: dict, seed: int, picked: list, prompts: dict,
          dims: tuple, devices, control: bool = False) -> dict:
    """Gaps of the served tokens under the reference; with ``control``
    also the gaps of the tokens that the lower-precision twin puts first
    at the same positions."""
    n, t_pad, n_new = dims
    tokens = np.zeros((n, t_pad), np.int32)
    first = np.zeros(n, np.int32)
    served = np.zeros((n, n_new), np.int32)
    valid = np.zeros((n, n_new), bool)
    for i, r in enumerate(picked):
        seq = list(prompts[r.uid]) + list(r.tokens)
        tokens[i, : len(seq)] = seq
        first[i] = r.n_prompt - 1
        served[i, : len(r.tokens)] = r.tokens
        valid[i, : len(r.tokens)] = True
    ref = reference.logits(sizes, seed, tokens, first, n_new, devices=devices)
    gap, exact = reference.gaps(ref, served)
    if not np.isfinite(gap[valid]).all():
        raise RuntimeError("the reference's logits are not finite")
    out = {
        "max_gap": float(gap[valid].max()),
        "mean_gap": float(gap[valid].mean()),
        "exact_share": float(exact[valid].mean()),
        "tokens_compared": int(valid.sum()),
        "requests_compared": len(picked),
    }
    if control:
        low = reference.logits(
            sizes, seed, tokens, first, n_new, control=True, devices=devices)
        cgap, cexact = reference.gaps(ref, np.asarray(low.argmax(-1)))
        out.update(
            control_max_gap=float(cgap[valid].max()),
            control_mean_gap=float(cgap[valid].mean()),
            control_exact_share=float(cexact[valid].mean()),
        )
    return out


def verdict(readings: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, numbers)``; ``numbers`` maps each compared name to
    ``[value, limit]``. A number that could not be read fails."""
    numbers = {}
    for name in ("max_gap", "mean_gap", "failed", "health_flips"):
        limit = limits.get(name, 0)
        value = readings.get(name)
        numbers[name] = [value, limit]
    ok = all(
        v is not None and math.isfinite(v) and v <= lim
        for v, lim in numbers.values()
    ) and readings.get("tokens_compared", 0) > 0
    return ok, numbers
