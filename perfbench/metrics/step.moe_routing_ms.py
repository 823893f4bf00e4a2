"""Device time under ``ffn/route`` per execution of the decode step: what
a routed layer runs AROUND its two grouped GEMMs (router scores and top-k,
the alignment, the gather of sorted rows, the weighted combine, the
routing counters), fullest device."""
from harness import scopes as sc

UNIT = "ms"


def read(run):
    return sc.part_ms(run, "decode_step", "ffn", "route")
