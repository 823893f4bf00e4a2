"""Device time under ``tdt.ssm`` per execution of the Mamba-2 / expert
model's decode step: the Mamba-2 mixers whole (norm, in-projection,
convolution and its ring, the in-place state kernel, the gated norm, the
out-projection), fullest device."""
from harness import scopes as sc

UNIT = "ms"


def read(run):
    return sc.part_ms(run, "decode_step", "ssm")
