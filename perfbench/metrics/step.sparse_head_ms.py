"""Device time under ``tdt.head`` per execution of the decode step under
the sparse latent plan: the final norm and the held slice of the
vocabulary, fullest device. The
arithmetic is ``step.head_ms``'s."""
from harness import cells

UNIT = "ms"


def read(run):
    return cells.load_module("metrics", "step.head_ms").read(run)
