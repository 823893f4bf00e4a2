"""The Mamba-2 / attention / expert decode step's share of its roofline:
the least time the chip could take for the bytes and operations the step
needs (kernels/ssd_moe_decode_step.py: the weights outside the banks, the
held experts hit, the advanced slots' state read AND written, the rows the
attention layer can see; HBM bounds it at 32 slots), over the device time
a step takes."""
from harness import roofline

UNIT = "%"


def read(run):
    ev = run.modules("decode_step")
    kern = run.kernel("ssd_moe_decode_step")
    if not len(ev) or not kern.rounds(run):
        return None
    floor, _ = roofline.floor_s(
        kern.flops_per_step(run), kern.bytes_per_step(run), run.peaks)
    return 100.0 * floor / (ev.total_s() / len(ev))
