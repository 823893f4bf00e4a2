"""The paged flash-decode kernel (``ops/flash_decode.py``, named
``paged_flash_decode`` in the trace): per step and device it must read
the keys and values of the live tokens, and multiply each query head
with them twice (scores, values)."""
from harness import stats

PATTERN = r"^paged_flash_decode"


def bytes_per_step(run, steps: int) -> float:
    per_token = run.kernel("decode_step").kv_bytes_per_token(run)
    return stats.kv_token_reads(run.records) * per_token / steps


def flops_per_step(run, steps: int) -> float:
    s = run.sizes
    per_token = 4.0 * s["n_layers"] * s["n_q_heads"] * s["head_dim"] / run.chips
    return stats.kv_token_reads(run.records) * per_token / steps
