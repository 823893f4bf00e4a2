"""The latent decode attention kernel's share of its roofline: the least
time for the latent rows of the live tokens (HBM bounds it), over the
summed device time of its custom calls per step."""
from harness import roofline

UNIT = "%"


def read(run):
    steps = run.modules("decode_step")
    kern = run.kernel("mla_decode")
    calls = run.ops().matching(kern.PATTERN).inside(steps)
    if not len(steps) or not len(calls):
        return None
    floor, _ = roofline.floor_s(
        kern.flops_per_step(run, len(steps)),
        kern.bytes_per_step(run, len(steps)), run.peaks)
    return 100.0 * floor / (calls.total_s() / len(steps))
