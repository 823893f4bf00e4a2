"""Device time under ``tdt.attn`` per execution of the decode step: the
attention layers whole (norm, projections, rope, the cache's write, the
decode kernel, the out-projection), fullest device."""
from harness import scopes as sc

UNIT = "ms"


def read(run):
    return sc.part_ms(run, "decode_step", "attn")
