"""The prompt scan's share of the HBM rate: the least time for the inputs
and outputs of the ``selective_scan`` calls inside the window's prefill
programs (each over its admission's bucket), over their summed device
time. No vector-unit peak is published for the chip, so the share is of
bytes alone (kernels/selective_scan.py says what a low one means)."""
UNIT = "%"


def read(run):
    progs = run.modules("prefill")
    kern = run.kernel("selective_scan")
    calls = run.ops().matching(kern.PATTERN).inside(progs)
    if not len(progs) or not len(calls):
        return None
    per_prog = len(calls) / len(progs)         # the state-space layers
    buckets = kern.buckets_of(run, progs)
    nbytes = per_prog * sum(kern.bytes_per_call(run, b) for b in buckets)
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / calls.total_s()
