"""The paged flash-decode calls' share of their roofline under window and
full layers: the least time for the key and value rows the slots' lengths
make visible (``window_rows`` + ``full_rows``; HBM bounds it), over the
summed device time of the window AND the full calls per step."""
from harness import roofline

UNIT = "%"


def read(run):
    steps = run.modules("decode_step")
    kern = run.kernel("window_decode")
    calls = run.ops().matching(kern.PATTERN).inside(steps)
    if not len(steps) or not len(calls) or not kern.rounds(run):
        return None
    floor, _ = roofline.floor_s(
        kern.flops_per_step(run, len(steps)),
        kern.bytes_per_step(run, len(steps)), run.peaks)
    return 100.0 * floor / (calls.total_s() / len(steps))
