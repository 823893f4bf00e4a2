"""The grouped expert GEMM (``ops/group_gemm.py``, named ``group_gemm`` in
the trace) at decode under the Mamba-2 / expert plan, where the chip holds
a SHARE of each bank and every layer has one: per step it must read the
gate, up and down matrices (``intermediate_size`` wide) of the HELD experts
its tokens were routed to, and no other expert; it multiplies each
assignment that landed here with them. What was hit is the program's own
count (``experts_hit`` / ``assignments`` on ``tdt.batcher.decode_round``,
summed over the expert layers, the held experts only)."""
from harness import spans as sp

PATTERN = r"^group_gemm"


def expert_bytes(run) -> float:
    """One expert's three matrices."""
    c = run.config
    width = 2 if c["dtype"] in ("bfloat16", "float16") else 4
    return 3.0 * c["hidden"] * c["intermediate_size"] * width


def expert_layers(run) -> int:
    return run.sizes["n_layers"]


def bank_bytes(run) -> float:
    """The routed banks held: what a step does NOT have to read whole."""
    c = run.config
    held = (c.get("experts_held") or [0, c["num_local_experts"]])[1]
    return expert_layers(run) * held * expert_bytes(run)


def rounds(run) -> list:
    """The decode rounds that carry the routing counters; none where the
    run has no trace or the program writes no such counter."""
    spans = sp.of(run)
    return [s for s in (spans.named(sp.ROUND) if spans else [])
            if "experts_hit" in s.stats and "assignments" in s.stats]


def bytes_per_step(run) -> float:
    got = rounds(run)
    return (sum(int(s.stats["experts_hit"]) for s in got) / len(got)
            * expert_bytes(run))


def flops_per_step(run) -> float:
    c, got = run.config, rounds(run)
    rows = sum(int(s.stats["assignments"]) for s in got) / len(got)
    return rows * 6.0 * c["hidden"] * c["intermediate_size"]
