"""The fused ring kernels' (all-gather GEMM, GEMM reduce-scatter) share
of their roofline in prefill: the least time for their operations and
bytes from the call shapes (the MXU bounds them at these row counts),
over the summed device time of their custom calls on the fullest device."""
from harness import roofline

UNIT = "%"


def read(run):
    progs = run.modules("prefill")
    kern = run.kernel("ag_gemm")
    calls = run.ops().matching(kern.PATTERN).inside(progs)
    if not len(progs) or not len(calls):
        return None
    rows = kern.rows_in_prefills(run, progs)
    floor, _ = roofline.floor_s(
        rows * kern.flops_per_row(run),
        len(progs) * kern.weight_bytes(run) + rows * kern.bytes_per_row(run),
        run.peaks)
    return 100.0 * floor / calls.total_s()
