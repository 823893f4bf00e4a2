"""Mean over decode rounds of ``tdt.batcher.decode_round`` less its
``.pull`` in the sparse latent plan's cell: the host's serial share of a
round of 32 slots under lookahead. The
arithmetic is ``batcher.round_host_ms``'s."""
from harness import cells

UNIT = "ms"


def read(run):
    return cells.load_module("metrics", "batcher.round_host_ms").read(run)
