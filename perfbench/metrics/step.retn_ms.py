"""Device time under ``tdt.retn`` per execution of the decode step: the
retention mixers whole (norm, the q/k/v projection with head norms and
rotation, the gate, the in-place state kernel, the out-projection),
fullest device."""
from harness import scopes as sc

UNIT = "ms"


def read(run):
    return sc.part_ms(run, "decode_step", "retn")
