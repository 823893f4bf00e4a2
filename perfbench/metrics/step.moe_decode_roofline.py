"""The gated-expert decode step's share of its roofline: the least time
the chip could take for the bytes and operations the step needs
(kernels/moe_decode_step.py: the weights outside the banks, the experts
actually hit, the live latent rows; HBM bounds it at 16 slots), over the
device time a step takes."""
from harness import roofline

UNIT = "%"


def read(run):
    ev = run.modules("decode_step")
    kern = run.kernel("moe_decode_step")
    if not len(ev) or not run.kernel("expert_gemm").rounds(run):
        return None
    floor, _ = roofline.floor_s(
        kern.flops_per_step(run, len(ev)), kern.bytes_per_step(run, len(ev)),
        run.peaks)
    return 100.0 * floor / (ev.total_s() / len(ev))
