"""What one decode step needs on the fullest device. Bytes: the layer and
head weights it holds (read once a step) and the keys and values of the
live tokens (mean over the window's steps). Operations: two per weight
and slot."""
from harness import stats


def kv_bytes_per_token(run) -> float:
    s = run.sizes
    width = 2 if s["dtype"] in ("bfloat16", "float16") else 4
    return 2 * s["n_layers"] * s["n_kv_heads"] * s["head_dim"] * width / run.chips


def bytes_per_step(run, steps: int) -> float:
    kv = stats.kv_token_reads(run.records) * kv_bytes_per_token(run) / steps
    return run.weight_bytes + kv


def flops_per_step(run) -> float:
    width = 2 if run.sizes["dtype"] in ("bfloat16", "float16") else 4
    return 2.0 * (run.weight_bytes / width) * run.config["engine"]["slots"]
