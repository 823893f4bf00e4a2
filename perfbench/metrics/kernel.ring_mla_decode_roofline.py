"""The window layers' absorbed decode through their rings: its share of
its roofline, the least time for one latent row (1024 + 64 values as
published) a row inside a slot's window (``window_rows``: at most 513 a
slot and layer) and every head's two products with it, over the summed
device time of the ``ring_mla_decode`` calls inside the window's decode
steps."""
from harness import roofline

UNIT = "%"


def read(run):
    steps = run.modules("decode_step")
    kern = run.kernel("sparse_mla_decode_step")
    calls = run.ops().matching(kern.RING_PATTERN).inside(steps)
    got = kern.rounds(run)
    if not len(steps) or not len(calls) or not got:
        return None
    rows = kern.total(got, "window_rows")
    floor, _ = roofline.floor_s(
        kern.window_decode_flops(run, rows),
        rows * kern.window_row_bytes(run), run.peaks)
    return 100.0 * floor / calls.total_s()
