"""Device time under ``tdt.head`` per execution of the Mamba-2 / expert
model's decode step: the embedding lookup with its multiplier; the final
norm, the tied head's GEMV over the held slice and the logits' scaling,
fullest device. The arithmetic is ``step.head_ms``'s."""
from harness import cells

UNIT = "ms"


def read(run):
    return cells.load_module("metrics", "step.head_ms").read(run)
