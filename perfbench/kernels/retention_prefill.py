"""The chunked pass over a prompt (``ops/retention.py``, named
``retention_prefill`` in the trace), one call a layer and admission. What
it MUST multiply is set by the prompt's TRUE length ``n``, not by its
bucket, in chunks of ``CHUNK`` rows:

- inside a chunk the masked ``(Q K^T)^2`` product and its ``A V``: each
  true row with the rows of its chunk up to itself, twice over the head
  width, for every query head;
- across chunks every true row PAST THE FIRST CHUNK queries the carried
  state (``phi(q) . S``: ``2 D d`` a query head; the first chunk's state
  is empty), and every true row is folded into it (``phi(k) v^T``: ``2 D
  d`` a kv head), ``D = 8704`` rows at ``d = 128``.

The kernel multiplies whole chunks (the bucket's padded rows too), feeds
the float32 state to a bf16 MXU as its two halves, and builds ``phi`` on
the vector unit first, so this count is a lower bound of its work and the
share cannot pass 100%. Bytes: ``q``, ``k``, ``v`` read and ``y`` written
over the bucket, the state written once. The lengths are the program's own
(``prompt_len`` on ``tdt.batcher.admit_prefill``)."""
from harness import spans as sp

PATTERN = r"^retention_prefill"
CHUNK = 512


def admissions(run) -> list:
    """The window's admissions that carry the family's counters; none
    where the run has no trace or the program counts no chunks."""
    spans = sp.of(run)
    return [s for s in (spans.named(sp.PREFILL) if spans else [])
            if "prompt_chunks" in s.stats and "prompt_len" in s.stats]


def flops_per_layer(run, n: int) -> float:
    s = run.sizes
    d, hq, hkv = s["head_dim"], s["n_q_heads"], s["n_kv_heads"]
    rows = run.kernel("retention_decode_step").state_rows(d)
    whole, rest = divmod(n, CHUNK)
    pairs = whole * CHUNK * (CHUNK + 1) // 2 + rest * (rest + 1) // 2
    return (4.0 * hq * d * pairs + 2.0 * rows * d * (
        hq * max(n - CHUNK, 0) + hkv * n))


def flops(run) -> float:
    """Over the window's admissions, every layer."""
    return run.sizes["n_layers"] * sum(
        flops_per_layer(run, int(a.stats["prompt_len"]))
        for a in admissions(run))


def nbytes(run) -> float:
    s = run.sizes
    d, hq, hkv = s["head_dim"], s["n_q_heads"], s["n_kv_heads"]
    step = run.kernel("retention_decode_step")
    width = 2 if s["dtype"] in ("bfloat16", "float16") else 4
    per_row = d * (width * (hq + 2 * hkv) + 4.0 * hq)
    buckets = run.kernel("selective_scan").buckets_of(run, admissions(run))
    return s["n_layers"] * (per_row * sum(buckets)
                            + len(buckets) * 4.0 * step.state_elements(run))
