"""What one decode step of the window-attention / gated-expert model's
second block needs (the whole bank and the whole vocabulary on the chip,
no shared expert, no dense layer). Bytes: every weight outside the banks
(attention, routers, norms, the head: read once a step), the experts the
step's tokens were routed to and no others (``experts_hit``, the
program's count), and the key and value rows the window layers (at most
``window`` a slot, through the ring) and the full layers (all that is
live) can see (``window_rows`` + ``full_rows``, the program's counts;
``kernels/window_decode.py`` reads them). Operations: two per weight
outside the banks and slot, the routed experts' per assignment,
attention's per visible row. Each count is what the step cannot do
without, so the share cannot pass 100%."""
from harness import spans as sp


def expert_bytes(run) -> float:
    """One expert's three matrices."""
    c = run.config
    width = 2 if c["dtype"] in ("bfloat16", "float16") else 4
    return 3.0 * c["hidden"] * c["moe_ffn_hidden_size"] * width


def rounds(run) -> list:
    """The decode rounds that carry the routing counters; none where the
    run has no trace or the program writes no such counter."""
    spans = sp.of(run)
    return [s for s in (spans.named(sp.ROUND) if spans else [])
            if "experts_hit" in s.stats]


def experts_hit_per_layer(run) -> float:
    got = rounds(run)
    return (sum(int(s.stats["experts_hit"]) for s in got) / len(got)
            / run.sizes["n_layers"])


def bank_bytes(run) -> float:
    """The banks held whole: what a step does NOT have to read whole."""
    return (run.sizes["n_layers"] * run.config["moe_num_primary_experts"]
            * expert_bytes(run))


def bytes_per_step(run, steps: int) -> float:
    hit = sum(int(s.stats["experts_hit"]) for s in rounds(run))
    return (run.weight_bytes - bank_bytes(run)
            + hit * expert_bytes(run) / steps
            + run.kernel("window_decode").bytes_per_step(run, steps))


def flops_per_step(run, steps: int) -> float:
    c = run.config
    width = 2 if run.sizes["dtype"] in ("bfloat16", "float16") else 4
    dense = 2.0 * (run.weight_bytes - bank_bytes(run)) / width
    rows = sum(int(s.stats["assignments"]) for s in rounds(run))
    return (dense * c["engine"]["slots"]
            + rows * 6.0 * c["hidden"] * c["moe_ffn_hidden_size"] / steps
            + run.kernel("window_decode").flops_per_step(run, steps))
