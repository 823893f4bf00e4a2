"""The grouped expert GEMM's share of its roofline at decode: the least
time for the weights of the experts hit (HBM bounds it), over the summed
device time of its custom calls per step."""
from harness import roofline

UNIT = "%"


def read(run):
    steps = run.modules("decode_step")
    kern = run.kernel("expert_gemm")
    calls = run.ops().matching(kern.PATTERN).inside(steps)
    if not len(steps) or not len(calls) or not kern.rounds(run):
        return None
    floor, _ = roofline.floor_s(
        kern.flops_per_step(run, len(steps)),
        kern.bytes_per_step(run, len(steps)), run.peaks)
    return 100.0 * floor / (calls.total_s() / len(steps))
