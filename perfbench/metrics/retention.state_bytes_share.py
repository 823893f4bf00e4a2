"""Share of a decode step's bytes that is retention STATE: the advanced
slots' state read and written over the weights held + that state, mean
over the window's decode rounds (``state_slots``). It does not grow with
the context: a slot's state is the same size at every length, which is
what the family is served for."""
UNIT = "%"


def read(run):
    kern = run.kernel("retention_decode_step")
    if not kern.rounds(run):
        return None
    return 100.0 * kern.state_bytes_per_step(run) / kern.bytes_per_step(run)
