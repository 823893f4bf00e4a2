"""The grouped expert GEMM (``ops/group_gemm.py``, named ``group_gemm`` in
the trace) at decode: per step it must read the gate, up and down
matrices of the experts its tokens were routed to, and no other expert;
it multiplies each assignment's row with them. What was hit is the
program's own count (``experts_hit`` on ``tdt.batcher.decode_round``,
summed over the expert layers)."""
from harness import spans as sp

PATTERN = r"^group_gemm"


def expert_bytes(run) -> float:
    """One expert's three matrices."""
    c = run.config
    width = 2 if c["dtype"] in ("bfloat16", "float16") else 4
    return 3.0 * c["hidden"] * c["moe_intermediate_size"] * width


def expert_layers(run) -> int:
    return run.sizes["n_layers"] - run.config["first_k_dense_replace"]


def rounds(run) -> list:
    """The decode rounds that carry the routing counters; none where the
    run has no trace or the program writes no such counter."""
    spans = sp.of(run)
    return [s for s in (spans.named(sp.ROUND) if spans else [])
            if "experts_hit" in s.stats]


def bytes_per_step(run, steps: int) -> float:
    hit = sum(int(s.stats["experts_hit"]) for s in rounds(run))
    return hit * expert_bytes(run) / steps


def flops_per_step(run, steps: int) -> float:
    c = run.config
    rows = sum(int(s.stats["assignments"]) for s in rounds(run))
    return rows * 6.0 * c["hidden"] * c["moe_intermediate_size"] / steps
