"""Device time of one admission's program under the sparse latent plan
(one slot's bucket of rows through every layer: the indexer's scores and
selection, both tiled attentions, the grouped GEMMs), fullest device. The
arithmetic is ``step.prefill_device_ms``'s."""
from harness import cells

UNIT = "ms"


def read(run):
    return cells.load_module("metrics", "step.prefill_device_ms").read(run)
