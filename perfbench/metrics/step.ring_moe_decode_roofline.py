"""The decode step's share of its roofline where window layers read
through rings and the whole bank is on the chip: the least time the chip
could take for the bytes and operations the step needs
(kernels/ring_moe_decode_step.py: the weights outside the banks, the
experts actually hit, the rows the window and the full layers can see;
HBM bounds it at 32 slots), over the device time a step takes."""
from harness import roofline

UNIT = "%"


def read(run):
    ev = run.modules("decode_step")
    kern = run.kernel("ring_moe_decode_step")
    if (not len(ev) or not kern.rounds(run)
            or not run.kernel("window_decode").rounds(run)):
        return None
    floor, _ = roofline.floor_s(
        kern.flops_per_step(run, len(ev)), kern.bytes_per_step(run, len(ev)),
        run.peaks)
    return 100.0 * floor / (ev.total_s() / len(ev))
