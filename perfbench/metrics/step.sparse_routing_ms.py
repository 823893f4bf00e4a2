"""Device time under ``ffn/route`` per execution of the decode step under
the sparse latent plan: the router over all 256 experts, the alignment of
the held share's assignments, the gather, the combine and the counters. The
arithmetic is ``step.moe_routing_ms``'s."""
from harness import cells

UNIT = "ms"


def read(run):
    return cells.load_module("metrics", "step.moe_routing_ms").read(run)
