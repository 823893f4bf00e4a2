"""Device time under ``tdt.ssm`` per execution of the decode step: the
state-space mixers whole (norm, in-projection, convolution and its ring,
the small projections, the recurrence kernel, gate and out-projection),
fullest device."""
from harness import scopes as sc

UNIT = "ms"


def read(run):
    return sc.part_ms(run, "decode_step", "ssm")
