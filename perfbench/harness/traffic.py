"""One general, seeded traffic generator. A traffic mix is a JSON file of
parameters under ``perfbench/traffic/``; nothing here knows a cell by name.

Every seed offers the SAME work at the SAME times: the ``n`` requests of
a run take their lengths from the distribution's quantiles at
``(i + 1/2) / n`` (stratified), and which request gets which length and
when it is due is the mix's own schedule, a fixed trace drawn once from
``SCHEDULE_SEED`` and replayed in every run. ``--seed`` draws the token
ids (and, elsewhere, the weights). A tail over some tens of requests
swings with which long prompt lands in which clump of arrivals, far more
than a system's change moves it; so run-to-run spread is the system's,
not the draw's.

Parameters (all lengths in tokens; a key that starts with ``_`` is a
comment; any other key is refused, so a mix that needs code this
generator does not have fails loudly):

``process``      ``"poisson"`` (open loop: ``round(rate_rps * seconds)``
                 arrivals, a Poisson process conditioned on that count, so
                 the times are sorted uniforms over the window) or
                 ``"backlog"`` (everything due at t=0; the count is
                 ``backlog_tokens_per_s * seconds / mean output``).
``prompt_len`` / ``output_len``
                 ``{"uniform": [lo, hi]}`` or ``{"quantiles": [[q, v],
                 ...]}`` (log-linear between the knots, q from 0 to 1).
``temperature``  0 = greedy (the only kind the comparison can judge).
``check_requests``  how many finished requests the reference judges.
"""

from __future__ import annotations

import dataclasses
import json
import math

import numpy as np

PROCESSES = ("poisson", "backlog")
KEYS = {"process", "rate_rps", "backlog_tokens_per_s", "prompt_len",
        "output_len", "temperature", "check_requests"}
SCHEDULE_SEED = 25


@dataclasses.dataclass(frozen=True)
class Req:
    uid: str
    t_s: float              # due time, seconds after the window opens
    prompt: tuple           # token ids
    n_out: int
    temperature: float = 0.0


def load(path: str) -> dict:
    with open(path) as f:
        spec = json.load(f)
    if spec.get("process") not in PROCESSES:
        raise ValueError(f"{path}: process must be one of {PROCESSES}")
    unknown = {k for k in spec if not k.startswith("_")} - KEYS
    if unknown:
        raise ValueError(f"{path}: unknown parameters {sorted(unknown)}")
    for key in ("prompt_len", "output_len"):
        ppf(spec[key], 0.5)
    return spec


def ppf(dist: dict, q: float) -> int:
    """The length at quantile ``q`` of a length distribution."""
    if "uniform" in dist:
        lo, hi = dist["uniform"]
        return int(min(hi, lo + math.floor(q * (hi - lo + 1))))
    if "quantiles" in dist:
        knots = dist["quantiles"]
        if knots[0][0] != 0 or knots[-1][0] != 1:
            raise ValueError("quantiles must run from q=0 to q=1")
        for (q0, v0), (q1, v1) in zip(knots, knots[1:]):
            if q <= q1:
                w = (q - q0) / (q1 - q0)
                return int(round(math.exp(
                    (1 - w) * math.log(v0) + w * math.log(v1)
                )))
        return int(knots[-1][1])
    raise ValueError(f"unknown length distribution {dist!r}")


def stratified(dist: dict, n: int) -> list[int]:
    return [ppf(dist, (i + 0.5) / n) for i in range(n)]


def max_len(dist: dict) -> int:
    return ppf(dist, 1.0)


def n_requests(spec: dict, seconds: float) -> int:
    if spec["process"] == "backlog":
        probe = stratified(spec["output_len"], 512)
        mean_out = sum(probe) / len(probe)
        n = spec["backlog_tokens_per_s"] * seconds / mean_out
    else:
        n = spec["rate_rps"] * seconds
    return max(1, int(round(n)))


def generate(spec: dict, vocab: int, seed: int, seconds: float) -> list[Req]:
    """The run's requests, sorted by due time. ``seed`` may be any whole
    number; the same seed gives the same requests."""
    tok = np.random.default_rng([int(seed), 0x7AFF1C])
    rng = np.random.default_rng([SCHEDULE_SEED, 0x5C4ED])
    n = n_requests(spec, seconds)
    prompts = stratified(spec["prompt_len"], n)
    outputs = stratified(spec["output_len"], n)
    prompts = [prompts[i] for i in rng.permutation(n)]
    outputs = [outputs[i] for i in rng.permutation(n)]
    if spec["process"] == "backlog":
        due = [0.0] * n
    else:
        due = sorted(float(t) for t in rng.uniform(0.0, seconds, n))
    temperature = float(spec.get("temperature", 0.0))
    out = [
        Req(uid=f"w{i}", t_s=due[i],
            prompt=tuple(int(x) for x in tok.integers(0, vocab, prompts[i])),
            n_out=outputs[i], temperature=temperature)
        for i in range(n)
    ]
    out.sort(key=lambda r: (r.t_s, r.uid))
    return out


def work(reqs: list[Req]) -> dict:
    """What a run offers, as counts: equal across seeds by construction."""
    return {
        "requests": len(reqs),
        "prompt_tokens": sum(len(r.prompt) for r in reqs),
        "output_tokens": sum(r.n_out for r in reqs),
        "prompt_lens": sorted(len(r.prompt) for r in reqs),
        "output_lens": sorted(r.n_out for r in reqs),
    }
