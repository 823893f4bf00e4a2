"""Device time under ``tdt.attn`` per execution of the Mamba-2 / expert
model's decode step: its attention layers whole (norm, projections, the
page write, the paged decode kernel, the out-projection), fullest device.
The arithmetic is ``step.attn_ms``'s."""
from harness import cells

UNIT = "ms"


def read(run):
    return cells.load_module("metrics", "step.attn_ms").read(run)
