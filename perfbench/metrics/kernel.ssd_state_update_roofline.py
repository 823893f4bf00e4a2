"""The Mamba-2 state update's share of its roofline: the least time for
the advanced slots' matrix state read once and written once in every
Mamba-2 layer (HBM bounds it), over the summed device time of the
``ssd_state_update`` calls per step."""
from harness import roofline

UNIT = "%"


def read(run):
    steps = run.modules("decode_step")
    kern = run.kernel("ssd_state_update")
    calls = run.ops().matching(kern.PATTERN).inside(steps)
    if (not len(steps) or not len(calls)
            or not run.kernel("ssd_moe_decode_step").rounds(run)):
        return None
    floor, _ = roofline.floor_s(
        kern.flops_per_step(run), kern.bytes_per_step(run), run.peaks)
    return 100.0 * floor / (calls.total_s() / len(steps))
