"""Device time under ``tdt.attn`` per execution of the decode step under
the sparse latent plan: both attention kinds whole (projections, the
indexer and its selection, the cache's writes, the masked and the ring
decode, the gate, the out-projection), fullest device. The
arithmetic is ``step.attn_ms``'s."""
from harness import cells

UNIT = "ms"


def read(run):
    return cells.load_module("metrics", "step.attn_ms").read(run)
