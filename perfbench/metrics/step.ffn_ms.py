"""Device time under ``tdt.ffn`` per execution of the decode step: the
feed-forwards whole, dense or routed (norm, gate/up, activation, down;
router, alignment, grouped GEMMs, shared expert, combine), fullest
device."""
from harness import scopes as sc

UNIT = "ms"


def read(run):
    return sc.part_ms(run, "decode_step", "ffn")
