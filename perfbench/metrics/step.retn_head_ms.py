"""Device time under ``tdt.head`` per execution of the power-retention
model's decode step: the embedding lookup, the final norm and the whole
vocabulary's head, fullest device. The arithmetic is ``step.head_ms``'s."""
from harness import cells

UNIT = "ms"


def read(run):
    return cells.load_module("metrics", "step.head_ms").read(run)
