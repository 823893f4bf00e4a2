"""Device time under ``tdt.ffn`` per execution of the Mamba-2 / expert
model's decode step: the expert banks whole (norm, router, alignment,
grouped GEMMs, shared expert, combine), fullest device. The arithmetic is
``step.ffn_ms``'s."""
from harness import cells

UNIT = "ms"


def read(run):
    return cells.load_module("metrics", "step.ffn_ms").read(run)
