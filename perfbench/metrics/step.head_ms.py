"""Device time under ``tdt.head`` per execution of the decode step: the
vocabulary's two ends (the embedding lookup; the final norm, the head's
GEMV and the logits' gather), fullest device."""
from harness import scopes as sc

UNIT = "ms"


def read(run):
    return sc.part_ms(run, "decode_step", "head")
