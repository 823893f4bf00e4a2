"""The indexer's paged score kernel's share of its roofline: the least
time for one index key (128 values) a row scored (``index_rows``; HBM
bounds it: 256 B against 16 kFLOP a row), over the summed device time of
the ``index_score`` calls inside the window's decode steps."""
from harness import roofline

UNIT = "%"


def read(run):
    steps = run.modules("decode_step")
    kern = run.kernel("sparse_mla_decode_step")
    calls = run.ops().matching(kern.INDEX_PATTERN).inside(steps)
    got = kern.rounds(run)
    if not len(steps) or not len(calls) or not got:
        return None
    rows = kern.total(got, "index_rows")
    floor, _ = roofline.floor_s(
        kern.index_flops(run, rows), rows * kern.index_key_bytes(run),
        run.peaks)
    return 100.0 * floor / calls.total_s()
