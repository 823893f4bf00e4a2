"""The scan over a prompt (``ops/selective_scan.py``, named
``selective_scan`` in the trace), one call a state-space layer and
admission, over the admitted prompt's BUCKET: it must read ``c`` and
``dt`` and write ``y`` (``[bucket, d]`` float32 each), read ``B`` and
``C`` (``[bucket, N]``), ``A``, ``D`` and the state it starts from and
write the state it ends in (``[N, d]``). ``peaks.json`` has no peak of the
vector unit, and none is guessed: the share is of the HBM rate, and a low
one says the kernel is bound by the vector unit or by the chain of steps,
not by bytes."""

PATTERN = r"^selective_scan"


def bytes_per_call(run, bucket: int) -> float:
    c = run.config
    d, n = c["mamba_expand"] * c["hidden"], c["mamba_d_state"]
    return 4.0 * (3 * bucket * d + 2 * bucket * n + 3 * n * d + d)


def buckets_of(run, progs) -> list:
    """The bucket of each of the given prefill executions. Which request
    an execution admitted is not in the trace; admissions are in order of
    first token, and so are the executions (``run.prefill_rows``: the
    rows an admission runs = its own bucket, one slot's rows)."""
    admitted = sorted((r for r in run.records if r.ok), key=lambda r: r.t_first)
    return [run.prefill_rows[r.uid] for r in admitted[: len(progs)]]
