"""Share of a decode step's needed bytes that is recurrent STATE under the
Mamba-2 / expert plan: the advanced slots' matrix state read and written
(with the convolution's ring) over that + the weights outside the banks +
the held experts hit + the key and value rows the attention layer reads,
mean over the window's decode rounds (kernels/ssd_moe_decode_step.py). It
does not grow with the context."""
UNIT = "%"


def read(run):
    kern = run.kernel("ssd_moe_decode_step")
    if not kern.rounds(run):
        return None
    return 100.0 * kern.state_bytes_per_step(run) / kern.bytes_per_step(run)
