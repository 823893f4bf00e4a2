"""The window layers' paged flash-decode calls' share of the HBM rate:
the least time for the key and value rows inside each slot's window
(``window_rows``: at most ``window`` a slot and layer, read through a
ring of pages), over the summed device time of the
``paged_flash_decode_w<window>*`` calls per step."""
UNIT = "%"
PATTERN = r"^paged_flash_decode_w\d"


def read(run):
    steps = run.modules("decode_step")
    kern = run.kernel("window_decode")
    calls = run.ops().matching(PATTERN).inside(steps)
    if not len(steps) or not len(calls) or not kern.rounds(run):
        return None
    nbytes = kern.rows(run)[0] * kern.row_bytes(run)
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / calls.total_s()
