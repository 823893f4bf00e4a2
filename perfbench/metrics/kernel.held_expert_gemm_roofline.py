"""The grouped expert GEMM's share of its roofline at decode where the
chip holds a SHARE of each bank: the least time for the weights of the
held experts that were hit (``experts_hit`` counts those only), over the
summed device time of its custom calls per step. The arithmetic is
``kernel.expert_gemm_roofline``'s, through ``kernels/expert_gemm.py`` as
it stands."""
from harness import cells

UNIT = "%"


def read(run):
    return cells.load_module("metrics", "kernel.expert_gemm_roofline").read(run)
