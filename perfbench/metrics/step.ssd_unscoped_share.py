"""Share of the decode step's summed op time under NO part of the program
(the instrument's health, and what the compiler adds under no name), in
the Mamba-2 / expert model's step. The arithmetic is
``step.unscoped_share``'s."""
from harness import cells

UNIT = "%"


def read(run):
    return cells.load_module("metrics", "step.unscoped_share").read(run)
