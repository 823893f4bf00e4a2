"""The grouped expert GEMM's share of its roofline at decode under the
Mamba-2 / expert plan (18 held experts of 768 a layer): the least time for
the weights of the held experts that were hit (HBM bounds it), over the
summed device time of its custom calls per step
(kernels/ssd_expert_gemm.py)."""
from harness import roofline

UNIT = "%"


def read(run):
    steps = run.modules("decode_step")
    kern = run.kernel("ssd_expert_gemm")
    calls = run.ops().matching(kern.PATTERN).inside(steps)
    if not len(steps) or not len(calls) or not kern.rounds(run):
        return None
    floor, _ = roofline.floor_s(
        kern.flops_per_step(run), kern.bytes_per_step(run), run.peaks)
    return 100.0 * floor / (calls.total_s() / len(steps))
