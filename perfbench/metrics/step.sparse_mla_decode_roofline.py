"""The decode step's share of its roofline under the sparse latent plan:
the least time the chip could take for the bytes and operations the step
needs (kernels/sparse_mla_decode_step.py: the weights outside the banks,
the held experts actually hit, the index keys scored, the latent rows
selected, the ring rows; HBM bounds it at 32 slots), over the device time
a step takes."""
from harness import roofline

UNIT = "%"


def read(run):
    ev = run.modules("decode_step")
    kern = run.kernel("sparse_mla_decode_step")
    if not len(ev) or not kern.rounds(run):
        return None
    floor, _ = roofline.floor_s(
        kern.flops_per_step(run, len(ev)), kern.bytes_per_step(run, len(ev)),
        run.peaks)
    return 100.0 * floor / (ev.total_s() / len(ev))
