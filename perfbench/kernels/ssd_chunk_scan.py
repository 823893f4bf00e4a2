"""The chunked pass over a prompt (``ops/ssd.py``, named ``ssd_chunk_scan``
in the trace), one call a Mamba-2 layer and admission. What it MUST
multiply is set by the prompt's TRUE length ``n``, not by its bucket, in
chunks of ``mamba_chunk_size`` rows:

- inside a chunk ``C B^T`` once for all heads (``2 N`` a pair of rows ``r
  <= t``) and each head's masked product with ``dt x`` (``2 P`` a pair and
  head: ``2 d`` a pair);
- across chunks every true row PAST THE FIRST CHUNK reads the carried state
  (``C_t . H``: ``2 N d``; the first chunk's state is empty), and every
  true row is folded into it (``dt x (x) B``: ``2 N d``).

The kernel multiplies whole chunks (the last live one's padded rows too),
feeds the float32 state to a bf16 MXU as its two halves, multiplies a
128-lane tile once a head in it with the other heads' lanes zeroed, and
builds each head's ``[Q, Q]`` decay on the vector unit first, so this count
is a lower bound of its work and the share cannot pass 100%. Bytes: ``x``
read and ``y`` written over the prompt's live chunks in the model's dtype,
``B``, ``C`` and the step read, the state written once. The lengths are the
program's own (``prompt_len`` on ``tdt.batcher.admit_prefill``)."""
from harness import spans as sp

PATTERN = r"^ssd_chunk_scan"


def admissions(run) -> list:
    """The window's admissions that carry the plan's counters; none where
    the run has no trace or the program counts no chunks."""
    spans = sp.of(run)
    return [s for s in (spans.named(sp.PREFILL) if spans else [])
            if "prompt_chunks" in s.stats and "prompt_len" in s.stats]


def _dims(run) -> tuple:
    c = run.config
    return (c["mamba_n_heads"] * c["mamba_d_head"], c["mamba_d_state"],
            c["mamba_n_heads"], c["mamba_chunk_size"])


def flops_per_layer(run, n: int) -> float:
    d, N, _, Q = _dims(run)
    whole, rest = divmod(n, Q)
    pairs = whole * Q * (Q + 1) // 2 + rest * (rest + 1) // 2
    return 2.0 * (N + d) * pairs + 2.0 * N * d * (max(n - Q, 0) + n)


def bytes_per_layer(run, n: int) -> float:
    d, N, heads, Q = _dims(run)
    width = 2 if run.sizes["dtype"] in ("bfloat16", "float16") else 4
    rows = -(-n // Q) * Q
    return rows * (2.0 * width * d + 4.0 * (2 * N + heads)) + 4.0 * N * d


def _lengths(run) -> list:
    return [int(a.stats["prompt_len"]) for a in admissions(run)]


def flops(run) -> float:
    """Over the window's admissions, every Mamba-2 layer."""
    layers = run.kernel("ssd_moe_decode_step").mamba_layers(run)
    return layers * sum(flops_per_layer(run, n) for n in _lengths(run))


def nbytes(run) -> float:
    layers = run.kernel("ssd_moe_decode_step").mamba_layers(run)
    return layers * sum(bytes_per_layer(run, n) for n in _lengths(run))
