"""Device time under ``attn/index`` per execution of the decode step: the
indexer's projections, the index key's write, the paged score kernel and
the selection (``topk_mask``: the bisected threshold), fullest device."""
from harness import scopes as sc

UNIT = "ms"


def read(run):
    return sc.part_ms(run, "decode_step", "attn", "index")
