"""The tiled prefill attention (``ops/flash_prefill.py``: named
``flash_prefill_w<window>`` on the window layers and ``flash_prefill`` on
the full ones in the trace; one pattern matches both), one call a layer
and admission. What it MUST multiply is set by the prompt's TRUE length,
not by its bucket: each true query ``p`` with the keys its layer lets it
see, ``min(p + 1, window)`` on a window layer and ``p + 1`` on a full
one, twice over the head width (scores, values), for every query head.
The blocks the kernel really walks hold more pairs than that (edge blocks
are multiplied whole), so this count is a lower bound of the work and the
share cannot pass 100%. The lengths are the program's own
(``prompt_len`` on ``tdt.batcher.admit_prefill``)."""
from harness import spans as sp

PATTERN = r"^flash_prefill"


def admissions(run) -> list:
    """The window's admissions that went through the tiled kernel; none
    where the run has no trace or the program counts no blocks."""
    spans = sp.of(run)
    return [s for s in (spans.named(sp.PREFILL) if spans else [])
            if "prefill_blocks_live" in s.stats and "prompt_len" in s.stats]


def pairs(length: int, window: int) -> int:
    """(query, key) pairs of one sequence of ``length`` true positions
    under a causal mask and a window (0 = none)."""
    if not window or length <= window:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def flops(run) -> float:
    """Over the window's admissions, every layer."""
    c, s = run.config, run.sizes
    layout = c["sliding_window_layout"][: s["n_layers"]]
    n_window = sum(1 for x in layout if x)
    per_pair = 4.0 * s["n_q_heads"] * s["head_dim"]
    total = 0
    for a in admissions(run):
        n = int(a.stats["prompt_len"])
        total += (n_window * pairs(n, c["sliding_window_size"])
                  + (len(layout) - n_window) * pairs(n, 0))
    return total * per_pair
