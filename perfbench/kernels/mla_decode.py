"""The latent decode attention (``ops/mla_decode.py``, named
``mla_paged_decode`` in the trace): per step and layer it must read one
latent row (normed kv latent + rotated shared key, as published: 576
values) for every live token, and multiply every head's query with it
twice (scores over the whole row, values over the latent)."""
from harness import stats

PATTERN = r"^mla_paged_decode"


def row_bytes(run) -> float:
    c = run.config
    width = 2 if c["dtype"] in ("bfloat16", "float16") else 4
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * width


def bytes_per_step(run, steps: int) -> float:
    return (stats.kv_token_reads(run.records) * run.sizes["n_layers"]
            * row_bytes(run) / steps)


def flops_per_step(run, steps: int) -> float:
    c = run.config
    per_token = 2.0 * run.sizes["n_layers"] * c["num_attention_heads"] * (
        2 * c["kv_lora_rank"] + c["qk_rope_head_dim"])
    return stats.kv_token_reads(run.records) * per_token / steps
