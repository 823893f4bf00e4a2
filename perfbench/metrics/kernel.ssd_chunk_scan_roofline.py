"""The chunked SSD pass's share of its roofline: the least time for the
products the admitted prompts' TRUE lengths need in the dual form at the
MXU's peak, or for its bytes if that is longer
(kernels/ssd_chunk_scan.py), over the summed device time of the
``ssd_chunk_scan`` calls inside the window's admissions. The kernel
multiplies whole chunks and whole lane tiles, reads the float32 state as
two bf16 halves and builds each head's decay on the vector unit first, so
it reads under a form that did none of that would, never over 100%."""
from harness import roofline

UNIT = "%"


def read(run):
    progs = run.modules("prefill")
    kern = run.kernel("ssd_chunk_scan")
    calls = run.ops().matching(kern.PATTERN).inside(progs)
    if not len(progs) or not len(calls) or not kern.admissions(run):
        return None
    floor, _ = roofline.floor_s(kern.flops(run), kern.nbytes(run), run.peaks)
    return 100.0 * floor / calls.total_s()
