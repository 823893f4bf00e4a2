"""Device time under ``tdt.ffn`` per execution of the power-retention
model's decode step: the dense MLPs, fullest device. The arithmetic is
``step.ffn_ms``'s."""
from harness import cells

UNIT = "ms"


def read(run):
    return cells.load_module("metrics", "step.ffn_ms").read(run)
