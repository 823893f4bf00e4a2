"""The paged flash-decode kernel under a model whose layers attend through
a window or in full (``ops/flash_decode.py``: ``paged_flash_decode_w128*``
on the window layers, ``paged_flash_decode*`` on the full ones; one
pattern matches both). Per step it must read the key and value rows that
the slots' lengths make visible: ``min(length, window)`` a slot in each
window layer, ``length`` in each full layer. The program counts both
itself (``window_rows``, ``full_rows`` on ``tdt.batcher.decode_round``);
each query head multiplies with every such row twice (scores, values)."""
from harness import spans as sp

PATTERN = r"^paged_flash_decode"


def row_bytes(run) -> float:
    """One position's key and value, every kv head."""
    s = run.sizes
    width = 2 if s["dtype"] in ("bfloat16", "float16") else 4
    return 2.0 * s["n_kv_heads"] * s["head_dim"] * width


def rounds(run) -> list:
    """The decode rounds that carry the row counters; none where the run
    has no trace or the program writes no such counter."""
    spans = sp.of(run)
    return [s for s in (spans.named(sp.ROUND) if spans else [])
            if "window_rows" in s.stats and "full_rows" in s.stats]


def rows(run) -> tuple[int, int]:
    """``(window rows, full rows)`` summed over the window's rounds."""
    got = rounds(run)
    return (sum(int(s.stats["window_rows"]) for s in got),
            sum(int(s.stats["full_rows"]) for s in got))


def bytes_per_step(run, steps: int) -> float:
    return sum(rows(run)) * row_bytes(run) / steps


def flops_per_step(run, steps: int) -> float:
    s = run.sizes
    return sum(rows(run)) * 4.0 * s["n_q_heads"] * s["head_dim"] / steps
