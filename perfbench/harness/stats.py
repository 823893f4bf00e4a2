"""End-to-end arithmetic over the lifecycle records of a window's requests.
A record is what the program's ``Finished`` carries, on the program's
clock, with the time the request was DUE in place of any enqueue time the
program chose."""

from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class Record:
    uid: str
    n_prompt: int
    n_wanted: int
    tokens: tuple            # () for a request that failed or was refused
    t_due: float
    t_admitted: float | None
    t_first: float | None
    t_finished: float | None

    @property
    def ok(self) -> bool:
        return (
            self.t_first is not None and self.t_finished is not None
            and len(self.tokens) == self.n_wanted
        )


def percentile(values: list[float], p: float) -> float:
    """Nearest-rank percentile over ALL values; a missing value is passed
    in as ``math.inf`` so that a failed request lies beyond any limit."""
    if not values:
        raise ValueError("percentile of nothing")
    ordered = sorted(values)
    rank = max(1, math.ceil(p / 100.0 * len(ordered)))
    return ordered[rank - 1]


def ttft_ms(records: list[Record]) -> list[float]:
    return [
        (r.t_first - r.t_due) * 1e3 if r.ok else math.inf for r in records
    ]


def tpot_mean_ms(records: list[Record]) -> float | None:
    """Whole decode time over all decode tokens, pooled over the window:
    a stall inside any request shows."""
    t = sum(r.t_finished - r.t_first for r in records if r.ok)
    n = sum(len(r.tokens) - 1 for r in records if r.ok)
    return t / n * 1e3 if n else None


def tokens_per_s(records: list[Record], t_open: float) -> float | None:
    """All output tokens of the window's requests over the wall time from
    the window's opening to the last counted token."""
    done = [r for r in records if r.ok]
    if not done:
        return None
    wall = max(r.t_finished for r in done) - t_open
    return sum(len(r.tokens) for r in done) / wall if wall > 0 else None


def mean(xs: list[float]) -> float | None:
    return sum(xs) / len(xs) if xs else None


def kv_token_reads(records: list[Record]) -> int:
    """Cached tokens that decoding must read over the window: the step
    that yields output token j+1 of a request attends to its prompt and
    its j earlier outputs."""
    total = 0
    for r in records:
        if r.ok:
            n = len(r.tokens) - 1
            total += n * r.n_prompt + n * (n + 1) // 2
    return total
