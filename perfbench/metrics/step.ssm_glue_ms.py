"""Device time under ``tdt.ssm`` LESS its ``ssm/scan`` (the recurrence
kernel's call) per execution of the decode step: the projections, the
convolution's ring, the norms and the gate around the kernel, fullest
device."""
from harness import scopes as sc

UNIT = "ms"


def read(run):
    whole = sc.part_ms(run, "decode_step", "ssm")
    if whole is None:
        return None
    return whole - (sc.part_ms(run, "decode_step", "ssm", "scan") or 0.0)
