"""What the sparse latent plan (``dots3-note-prev-ep8``: full layers behind
a learned indexer, window layers on latent rings, a share of each bank)
needs, a decode step and an admission, from the program's own counters on
its spans (``index_rows``, ``selected_rows``, ``window_rows``,
``experts_hit``, ``assignments`` on ``tdt.batcher.decode_round`` and
``tdt.batcher.admit_prefill``; docs/observability.md). A program without
those counters (a parent commit) gives no rounds, and every reader built
on this returns None.

A STEP's bytes: every weight outside the routed banks (attention,
indexer, gates, the dense layer, routers, shared experts, the held slice
of the head: read once a step), the held experts that were hit and no
others, one index key for every row scored (``index_rows`` x 128 values),
one latent row for every row a full layer attended (``selected_rows`` x
(512 + 64) values as published; the unselected rows the masked walk reads
beside them are not counted as needed) and for every row a window layer read from its ring
(``window_rows`` x (1024 + 64)). Operations: two per weight outside the
banks and slot, the routed experts' per assignment, the index scores'
(64 heads x 128, a row), the absorbed attention's (each head's query with
the row twice: scores over latent + rotary, values over the latent). Each
count is what the step cannot do without, so no share passes 100%.

An ADMISSION's tiled attention (``mla_flash_prefill*``): each TRUE query
with the keys it attends, ``min(t + 1, 513)`` on a window layer and the
SELECTED ``min(t + 1, 2048)`` on a full one (the masked form multiplies
every causal block: it reads lower, never over 100%), twice over the
expanded widths (q/k 192 or 256 for the scores, 128 for the values), every
head."""
from harness import spans as sp

INDEX_PATTERN = r"^index_score"
SPARSE_PATTERN = r"^sparse_mla_decode"
RING_PATTERN = r"^ring_mla_decode"
PREFILL_PATTERN = r"^mla_flash_prefill"
COUNTERS = ("index_rows", "selected_rows", "window_rows")


def _width(run) -> int:
    return 2 if run.sizes["dtype"] in ("bfloat16", "float16") else 4


def _with_counters(run, name: str) -> list:
    spans = sp.of(run)
    return [s for s in (spans.named(name) if spans else [])
            if all(k in s.stats for k in COUNTERS)]


def rounds(run) -> list:
    """The decode rounds that carry the sparse plan's counters."""
    return _with_counters(run, sp.ROUND)


def admissions(run) -> list:
    """The admissions that carry them."""
    return _with_counters(run, sp.PREFILL)


def total(spans: list, key: str) -> int:
    return sum(int(s.stats[key]) for s in spans)


def kinds(run) -> tuple[int, int]:
    """``(full layers, window layers)`` of the depth that runs."""
    types = run.config["layer_types"][: run.sizes["n_layers"]]
    n_full = sum(1 for t in types if t == "full_attention")
    return n_full, len(types) - n_full


def index_key_bytes(run) -> float:
    return run.config["index_head_dim"] * _width(run)


def full_row_bytes(run) -> float:
    c = run.config
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * _width(run)


def window_row_bytes(run) -> float:
    c = run.config
    return (c["swa_kv_lora_rank"] + c["swa_qk_rope_head_dim"]) * _width(run)


def index_flops(run, rows: float) -> float:
    c = run.config
    return rows * 2.0 * c["index_n_heads"] * c["index_head_dim"]


def full_decode_flops(run, rows: float) -> float:
    c = run.config
    return rows * 2.0 * c["num_attention_heads"] * (
        2 * c["kv_lora_rank"] + c["qk_rope_head_dim"])


def window_decode_flops(run, rows: float) -> float:
    c = run.config
    return rows * 2.0 * c["swa_num_attention_heads"] * (
        2 * c["swa_kv_lora_rank"] + c["swa_qk_rope_head_dim"])


def bank_bytes(run) -> float:
    """The routed banks held: what a step does NOT have to read whole."""
    kern = run.kernel("expert_gemm")
    held = run.config["experts_held"][1]
    return kern.expert_layers(run) * held * kern.expert_bytes(run)


def cache_bytes_per_step(run, steps: int) -> float:
    got = rounds(run)
    return (total(got, "index_rows") * index_key_bytes(run)
            + total(got, "selected_rows") * full_row_bytes(run)
            + total(got, "window_rows") * window_row_bytes(run)) / steps


def bytes_per_step(run, steps: int) -> float:
    return (run.weight_bytes - bank_bytes(run)
            + run.kernel("expert_gemm").bytes_per_step(run, steps)
            + cache_bytes_per_step(run, steps))


def flops_per_step(run, steps: int) -> float:
    got = rounds(run)
    dense = 2.0 * (run.weight_bytes - bank_bytes(run)) / _width(run)
    return (dense * run.config["engine"]["slots"]
            + run.kernel("expert_gemm").flops_per_step(run, steps)
            + (index_flops(run, total(got, "index_rows"))
               + full_decode_flops(run, total(got, "selected_rows"))
               + window_decode_flops(run, total(got, "window_rows"))) / steps)


def prefill_flops(run) -> float:
    """The tiled attention's products over the window's admissions: the
    counters are sums over the layers of a kind already."""
    c = run.config
    got = admissions(run)
    full = 2.0 * c["num_attention_heads"] * (
        c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])
    win = 2.0 * c["swa_num_attention_heads"] * (
        c["swa_qk_nope_head_dim"] + c["swa_qk_rope_head_dim"]
        + c["swa_v_head_dim"])
    return (total(got, "selected_rows") * full
            + total(got, "window_rows") * win)
