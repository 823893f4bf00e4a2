"""Device time under ``tdt.ffn`` per execution of the decode step under
the sparse latent plan: the dense layer, the routing, the held experts'
grouped GEMMs and the shared experts, fullest device. The
arithmetic is ``step.ffn_ms``'s."""
from harness import cells

UNIT = "ms"


def read(run):
    return cells.load_module("metrics", "step.ffn_ms").read(run)
