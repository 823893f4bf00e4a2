"""The fused ring kernels of tensor-parallel prefill, by their names in
the trace: ``ag_gemm`` (qkv, gate/up and the vocabulary head: the
all-gather of the row shards fused into a column-parallel GEMM) and
``gemm_rs*`` (wo, down: a row-parallel GEMM fused into a
reduce-scatter). Per device and per row of
one prefill program, which runs ``slots x bucket`` rows: the whole batch
through the bucket, whatever the one admitted prompt's length."""

PATTERN = r"^(ag_gemm|gemm_rs)"


def _widths(run) -> tuple[int, int, int]:
    s = run.sizes
    q = s["n_q_heads"] * s["head_dim"]
    kv = s["n_kv_heads"] * s["head_dim"]
    return q, kv, s["ffn"]


def flops_per_row(run) -> float:
    q, kv, f = _widths(run)
    h = run.sizes["hidden"]
    per_layer = 2 * h * (q + 2 * kv + 2 * f) + 2 * (q + f) * h
    head = 2 * h * run.sizes["vocab"]      # every row goes through the head
    return (run.sizes["n_layers"] * per_layer + head) / run.chips


def weight_bytes(run) -> float:
    """The four projections' weights a device holds, read once a program."""
    return flops_per_row(run)          # 2 bytes a bf16 weight, 2 FLOPs a weight


def bytes_per_row(run) -> float:
    """Activations in and out of the four kernels, bf16, a device's share."""
    q, kv, f = _widths(run)
    h = run.sizes["hidden"]
    layer = 2 * h + (q + 2 * kv) + 2 * f + q + f + 2 * h
    return 2.0 * (run.sizes["n_layers"] * layer + h + run.sizes["vocab"]) / run.chips


def rows_in_prefills(run, progs) -> int:
    """Rows over the given prefill executions. Which request an execution
    admitted is not in the trace; admissions are in order of first token,
    and so are the executions."""
    admitted = sorted((r for r in run.records if r.ok), key=lambda r: r.t_first)
    return sum(run.prefill_rows[r.uid] for r in admitted[: len(progs)])
