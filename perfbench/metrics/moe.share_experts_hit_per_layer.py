"""Distinct HELD experts (32 of 256 a layer here) a decode step's tokens
were routed to, per expert layer: the step reads that many experts'
weights. The arithmetic is ``moe.experts_hit_per_layer``'s."""
from harness import cells

UNIT = "experts"


def read(run):
    return cells.load_module("metrics", "moe.experts_hit_per_layer").read(run)
