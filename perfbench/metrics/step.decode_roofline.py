"""The decode step's share of its roofline: the least time the chip
could take for the bytes and operations a step needs
(kernels/decode_step.py; HBM bounds it at 8 slots), over the device time
a step takes."""
from harness import roofline

UNIT = "%"


def read(run):
    ev = run.modules("decode_step")
    if not len(ev):
        return None
    kern = run.kernel("decode_step")
    floor, _ = roofline.floor_s(
        kern.flops_per_step(run), kern.bytes_per_step(run, len(ev)), run.peaks)
    return 100.0 * floor / (ev.total_s() / len(ev))
