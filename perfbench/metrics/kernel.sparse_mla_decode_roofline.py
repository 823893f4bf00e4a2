"""The full layers' absorbed decode over the SELECTED rows: its share of
its roofline, the least time for one latent row (512 + 64 values as
published) a row attended (``selected_rows``) and every head's two
products with it, over the summed device time of the
``sparse_mla_decode`` calls inside the window's decode steps. The kernel
walks a slot's LIVE pages and masks the unselected rows, so it reads
``live / selected`` times the rows counted here as needed, and the share
says so: about ``attn.selected_rows_share`` of what the walk itself
reaches of the HBM rate."""
from harness import roofline

UNIT = "%"


def read(run):
    steps = run.modules("decode_step")
    kern = run.kernel("sparse_mla_decode_step")
    calls = run.ops().matching(kern.SPARSE_PATTERN).inside(steps)
    got = kern.rounds(run)
    if not len(steps) or not len(calls) or not got:
        return None
    rows = kern.total(got, "selected_rows")
    floor, _ = roofline.floor_s(
        kern.full_decode_flops(run, rows), rows * kern.full_row_bytes(run),
        run.peaks)
    return 100.0 * floor / calls.total_s()
